"""The plain reference: Mistral/Llama-style decoder in float32 `jax.numpy`.

No kernels, no cache, no batching tricks, `default_matmul_precision("highest")`.
It follows the published architecture (pre-norm RMSNorm, rotary embeddings on
half-split head dimensions as HF's `rotate_half`, grouped-query attention,
SwiGLU, untied output head); Mistral-7B-v0.3 has no sliding window.

It takes its inputs from the SEED and nothing the program has made: each
layer's weights are regenerated from the seed inside the layer loop
(`weights.make_layer`, the benchmark's own generator, in the served type
bfloat16) and cast to float32 there, one layer at a time, so the reference
fits beside the system's own weights at the cell's own size.

Two comparisons decide `correct`:

serving   `logit_gaps`: over prompt + the tokens the system emitted, for each
          emitted token the reference's largest logit minus its logit for that
          token. Under greedy decoding the gap is 0 but for near-ties.
training  `grad_check`: the relative L2 distance of the program's gradient
          from the reference's, at the seed's initial weights on the run's
          first batch (with the reference's loss and gradient norm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, H, D]; rotate_half convention, positions 0..T-1."""
    T, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(x, w, cfg):
    """One decoder layer on one sequence x [T, d], all in float32."""
    T, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    w = jax.tree.map(lambda a: a.astype(F32), w)
    a = _rms_norm(x, w["attn_norm"], cfg.rms_eps)
    q = _rope((a @ w["wq"]).reshape(T, h, hd), cfg.rope_theta)
    k = _rope((a @ w["wk"]).reshape(T, kvh, hd), cfg.rope_theta)
    v = (a @ w["wv"]).reshape(T, kvh, hd)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def group(qkv):  # one KV head and the query heads that share it
        qg, kg, vg = qkv  # [g, T, hd], [T, hd], [T, hd]
        s = jnp.einsum("gtd,sd->gts", qg, kg) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->gtd", p, vg)

    qg = q.reshape(T, kvh, h // kvh, hd).transpose(1, 2, 0, 3)
    # one group at a time, recomputed in the backward pass: the full score
    # matrix of all heads would not fit beside the system at 4k
    o = jax.lax.map(jax.checkpoint(group), (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(T, h * hd)
    x = x + o @ w["wo"]
    m = _rms_norm(x, w["mlp_norm"], cfg.rms_eps)
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


def _hidden(key, tokens, cfg):
    """Final-norm hidden states [N, T, d] of N sequences. The layer loop is
    outermost, so each layer's weights are made from `key` once for all of
    them; the sequences go through a layer one at a time."""
    k_embed, layer_keys, _ = weights.part_keys(key, cfg)
    x = weights.make_embed(k_embed, cfg)[tokens].astype(F32)

    def body(x, k):
        w = weights.make_layer(k, cfg)
        return jax.lax.map(lambda xb: _layer(xb, w, cfg), x), None

    x, _ = jax.lax.scan(body, x, layer_keys)
    return _rms_norm(x, jnp.ones((cfg.d_model,), F32), cfg.rms_eps)


# ------------------------------------------------------------------- serving
@functools.lru_cache(maxsize=8)
def _jitted_gaps(cfg, n_out):
    def fn(key, tokens, first, count):
        with jax.default_matmul_precision("highest"):
            T = tokens.shape[1]
            x = _hidden(key, tokens, cfg)
            head = weights.make_lm_head(weights.part_keys(key, cfg)[2], cfg).astype(F32)
            # the emitted tokens are tokens[first : first + count], each
            # predicted from the position before it
            idx = first[:, None] - 1 + jnp.arange(n_out)[None, :]
            at = jnp.clip(idx, 0, T - 1)
            logits = jnp.take_along_axis(x, at[:, :, None], axis=1) @ head  # [N, n_out, V]
            emitted = jnp.take_along_axis(tokens, jnp.clip(idx + 1, 0, T - 1), axis=1)
            gap = logits.max(-1) - jnp.take_along_axis(logits, emitted[..., None], -1)[..., 0]
            valid = jnp.arange(n_out)[None, :] < count[:, None]
            return jnp.where(valid, gap, -1.0), logits.std(-1)
    return jax.jit(fn)


def logit_gaps(key, tokens, first, count, cfg, n_out: int):
    """tokens [N, T] int32 (prompt + emitted, right-padded with 0), first [N]
    the prompt lengths, count [N] the emitted tokens (0 for a padding row).
    Returns (gaps [N, n_out], -1 where nothing was emitted; the spread of the
    reference's logits there)."""
    return _jitted_gaps(cfg, n_out)(key, tokens, first, count)


def summarize_gaps(gaps) -> dict:
    """The numbers compared: the mean gap over every emitted token checked
    (it grows with the SQUARE of the logit error, so a lower precision
    separates far better than a flip count) and the largest single gap."""
    import numpy as np

    g = np.asarray(gaps, np.float64)
    g = g[g >= 0]
    return {"tokens_checked": int(g.size), "gap_mean": float(g.mean()) if g.size else None,
            "gap_max": float(g.max()) if g.size else None,
            "flipped": int((g > 0).sum())}


# ------------------------------------------------------------------ training
@functools.lru_cache(maxsize=4)
def _jitted_grad_check(cfg):
    """Mean loss of a batch, the global L2 norm of its gradient, and how far
    the SYSTEM's gradient is from it, layer by layer: the forward pass keeps
    each layer's input; the backward pass regenerates one layer's weights,
    takes each sequence's vjp in float32, sums them, and keeps only that
    layer's squared norm and its squared distance from the system's gradient.
    No float32 copy of the model, or of its gradient, is ever alive, so it
    fits at the cell's own depth."""

    def sq(tree):
        return sum(jnp.sum(jnp.square(g.astype(F32))) for g in jax.tree.leaves(tree))

    def sq_diff(a, b):
        return sum(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32)))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    def fn(key, batch, sys_grads):
        with jax.default_matmul_precision("highest"):
            k_embed, layer_keys, k_out = weights.part_keys(key, cfg)
            inputs, targets = batch[:, :-1], batch[:, 1:]
            n = inputs.size
            embed = weights.make_embed(k_embed, cfg).astype(F32)
            x0 = embed[inputs]  # [B, T, d]

            def fwd(x, k):
                w = weights.make_layer(k, cfg)
                return jax.lax.map(lambda xb: _layer(xb, w, cfg), x), x

            xL, xs = jax.lax.scan(fwd, x0, layer_keys)

            def tail(x, norm_w, head):
                def one(xt):
                    xb, tb = xt
                    logp = jax.nn.log_softmax(_rms_norm(xb, norm_w, cfg.rms_eps) @ head, axis=-1)
                    return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0].sum()
                return jax.lax.map(one, (x, targets)).sum() / n

            head = weights.make_lm_head(k_out, cfg).astype(F32)
            loss, (gx, g_norm_w, g_head) = jax.value_and_grad(tail, argnums=(0, 1, 2))(
                xL, jnp.ones((cfg.d_model,), F32), head)
            ref_sq = sq((g_norm_w, g_head))
            err_sq = sq_diff((g_norm_w, g_head), (sys_grads["final_norm"], sys_grads["lm_head"]))

            def bwd(carry, k_x_g):
                gx, ref_sq, err_sq = carry
                k, x, g_sys = k_x_g
                w32 = jax.tree.map(lambda a: a.astype(F32), weights.make_layer(k, cfg))

                def one(gw_sum, xg):
                    xb, gb = xg
                    _, vjp = jax.vjp(lambda x_, w_: _layer(x_, w_, cfg), xb, w32)
                    gxb, gw = vjp(gb)
                    return jax.tree.map(jnp.add, gw_sum, gw), gxb

                gw, gx = jax.lax.scan(one, jax.tree.map(jnp.zeros_like, w32), (x, gx))
                return (gx, ref_sq + sq(gw), err_sq + sq_diff(gw, g_sys)), None

            (gx0, ref_sq, err_sq), _ = jax.lax.scan(
                bwd, (gx, ref_sq, err_sq), (layer_keys, xs, sys_grads["layers"]), reverse=True)
            g_embed = jnp.zeros_like(embed).at[inputs].add(gx0)
            ref_sq = ref_sq + sq(g_embed)
            err_sq = err_sq + sq_diff(g_embed, sys_grads["embed"])
            return loss, jnp.sqrt(ref_sq), jnp.sqrt(err_sq / ref_sq)
    return jax.jit(fn)


def grad_check(key, batch_tokens, cfg, sys_grads):
    """(reference loss, reference gradient norm, ||g_system - g_reference|| /
    ||g_reference||) for the batch [B, T+1] at the seed's initial weights."""
    loss, gnorm, rel = _jitted_grad_check(cfg)(
        key, jnp.asarray(batch_tokens, jnp.int32), sys_grads)
    return float(loss), float(gnorm), float(rel)
