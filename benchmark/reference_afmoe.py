"""The plain reference of the AFMoE configuration (HF `model_type` `afmoe`,
Arcee's Trinity family) in float32 `jax.numpy` under
`default_matmul_precision("highest")`.

No kernels, no cache, no batching tricks, and no algorithm of the program's:
attention is the whole score matrix of one sequence under its mask; the expert
layer is the definition itself, every expert applied to every row and weighted
by that row's routing weight for it, which is zero for the experts the row did
not choose (nothing is sorted, grouped or gathered). For one sequence of T
rows, x the residual stream:

  layer      h = x + N2(Attn(N1(x)));  y = h + N4(FFN(N3(h)));  four RMSNorms
  Attn(u)    q = Wq u, k = Wk u, v = Wv u, g = Wg u; q and k RMS-normed over
             the head size; a `sliding_attention` layer: RoPE (theta 10000)
             on q and k, position i attends j with 0 <= i - j < window; a
             `full_attention` layer: no position term, causal; scores times
             head_dim^-0.5; out = Wo (heads * sigmoid(g))
  FFN        the first `num_dense_layers`: SwiGLU of width intermediate_size;
             the others: s = sigmoid(Wr u); the top_k largest of s + b chosen;
             w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale;
             out = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)
  ends       x_0 = E[token] * sqrt(hidden_size) (mup); final RMSNorm; untied head

It takes its inputs from the SEED and nothing the program has made: each
layer's weights, and inside an expert layer each expert's, are regenerated
where they are used (`weights_afmoe.make_*`, in the served type bfloat16) and
cast to float32 there, so the reference fits beside the system; the head is
applied a slice of the vocabulary at a time.

`logit_gaps` and `summarize_gaps` keep `reference.py`'s contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights_afmoe as W
from benchmark.reference import _rms_norm, summarize_gaps  # noqa: F401

F32 = jnp.float32
SLIDING = "sliding_attention"
HEAD_SLICES = 8  # of the vocabulary, one at a time


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1: the two halves of a head's
    vector rotated against each other (HF's rotate_half)."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(u, w, cfg, kind):
    """u [T, d] -> [T, d], one sequence; one KV head's group of query heads
    at a time, each against its whole [T, T] score matrix."""
    T = u.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _rms_norm((u @ w["wq"]).reshape(T, h, hd), w["q_norm"], cfg.rms_eps)
    k = _rms_norm((u @ w["wk"]).reshape(T, kvh, hd), w["k_norm"], cfg.rms_eps)
    v = (u @ w["wv"]).reshape(T, kvh, hd)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if kind == SLIDING:
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        seen = seen & (i - j < cfg.sliding_window)

    def group(qkv):
        qg, kg, vg = qkv                                    # [T, g, hd] [T, hd] [T, hd]
        s = jnp.einsum("tgd,sd->gts", qg, kg) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vg)

    o = jax.lax.map(group, (jnp.moveaxis(q.reshape(T, kvh, h // kvh, hd), 1, 0),
                            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, h * hd)
    return (o * jax.nn.sigmoid(u @ w["wg"])) @ w["wo"]


def swiglu(u, w):
    return (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


def routing_weights(u, router, bias, cfg):
    """[N, E]: each row's weight for every expert, zero for those it did not
    choose. The bias enters the choice only."""
    scores = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(scores + bias, cfg.top_k)
    picked = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], chosen].set(1.0)
    w = scores * picked
    if cfg.route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.route_scale


def expert_layer(u, k_moe, cfg):
    """u [N, d] -> [N, d]; the experts one after another, each made from its
    key, applied to every row and weighted."""
    k_r, k_b, k_e, k_s = W.moe_keys(k_moe, cfg)
    router, bias = W.make_router(k_r, k_b, cfg)
    w = routing_weights(u, router.astype(F32), bias, cfg)

    def one(acc, inp):
        k_expert, w_e = inp
        return acc + w_e[:, None] * swiglu(u, _f32(W.make_expert(k_expert, cfg))), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (k_e, w.T))
    return routed + swiglu(u, _f32(W.make_shared(k_s, cfg)))


def hidden(key, tokens, cfg):
    """Final-norm hidden states [S, T, d]: S sequences, each on its own
    through the attention; the layer loop is outermost, so each layer's
    weights are made from `key` once."""
    k_embed, _, k_l, k_d, k_m = W.part_keys(key, cfg)
    S, T = tokens.shape
    x = W.make_embed(k_embed, cfg)[tokens].astype(F32)
    if cfg.mup_enabled:
        x = x * cfg.d_model ** 0.5
    for g, kind in enumerate(cfg.layer_types):
        w = _f32(W.make_layer(k_l[g], cfg))
        a = _rms_norm(x, w["attn_norm"], cfg.rms_eps)
        o = jax.lax.map(lambda row: attention(row, w, cfg, kind), a)  # noqa: B023
        x = x + _rms_norm(o, w["attn_post_norm"], cfg.rms_eps)
        m = _rms_norm(x, w["ffn_norm"], cfg.rms_eps)
        if g < cfg.n_dense_layers:
            y = swiglu(m, _f32(W.make_dense_ffn(k_d[g], cfg)))
        else:
            y = expert_layer(m.reshape(S * T, -1), k_m[g - cfg.n_dense_layers], cfg).reshape(m.shape)
        x = x + _rms_norm(y, w["ffn_post_norm"], cfg.rms_eps)
    return _rms_norm(x, jnp.ones((cfg.d_model,), F32), cfg.rms_eps)


def logits(key, tokens, cfg):
    """Logits [S, T, V] float32 of token rows [S, T] (tests and small sizes:
    at the cell's size `logit_gaps` never holds all positions' logits)."""
    with jax.default_matmul_precision("highest"):
        head = W.make_lm_head(W.part_keys(key, cfg)[1], cfg).astype(F32)
        return hidden(key, tokens, cfg) @ head


@functools.lru_cache(maxsize=8)
def _jitted_gaps(cfg, n_out):
    def fn(key, tokens, first, count):
        with jax.default_matmul_precision("highest"):
            S, T = tokens.shape
            x = hidden(key, tokens, cfg)
            # the emitted tokens are tokens[first : first + count], each
            # predicted from the position before it
            idx = first[:, None] - 1 + jnp.arange(n_out)[None, :]
            at = jnp.clip(idx, 0, T - 1)
            emitted = jnp.take_along_axis(tokens, jnp.clip(idx + 1, 0, T - 1), axis=1)
            xs = jnp.take_along_axis(x, at[:, :, None], axis=1)          # [S, n_out, d]
            V = cfg.vocab_size
            n = HEAD_SLICES if V % HEAD_SLICES == 0 else 1
            head = W.make_lm_head(W.part_keys(key, cfg)[1], cfg)         # served type
            slices = jnp.moveaxis(head.reshape(cfg.d_model, n, V // n), 1, 0)

            def one(carry, inp):  # a slice of the vocabulary at a time
                top, own, total, squares = carry
                head_slice, v0 = inp
                lg = xs @ head_slice.astype(F32)                          # [S, n_out, V / n]
                inside = (emitted >= v0) & (emitted < v0 + V // n)
                picked = jnp.take_along_axis(
                    lg, jnp.clip(emitted - v0, 0, V // n - 1)[..., None], -1)[..., 0]
                return (jnp.maximum(top, lg.max(-1)), jnp.where(inside, picked, own),
                        total + lg.sum(-1), squares + (lg * lg).sum(-1)), None

            zero = jnp.zeros((S, n_out), F32)
            (top, own, total, squares), _ = jax.lax.scan(
                one, (jnp.full((S, n_out), -jnp.inf, F32), zero, zero, zero),
                (slices, jnp.arange(n) * (V // n)))
            spread = jnp.sqrt(jnp.maximum(squares / V - (total / V) ** 2, 0.0))
            valid = jnp.arange(n_out)[None, :] < count[:, None]
            return jnp.where(valid, top - own, -1.0), spread
    return jax.jit(fn)


def logit_gaps(key, tokens, first, count, cfg, n_out: int):
    """tokens [S, T] int32 (prompt + emitted, right-padded with 0), first [S]
    the prompt lengths, count [S] the emitted tokens (0 for a padding row).
    Returns (gaps [S, n_out], -1 where nothing was emitted; the spread of the
    reference's logits there)."""
    return _jitted_gaps(cfg, n_out)(key, tokens, first, count)
