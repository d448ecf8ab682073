"""The plain reference of the hybrid configuration: Granite-4.0-H's decoder
(HF `GraniteMoeHybrid*` with no routed experts; its Mamba layer is Bamba's) in
float32 `jax.numpy` under `default_matmul_precision("highest")`.

No kernels, no cache, no batching tricks, and no algorithm of the program's:
the state-space layer is the recurrence itself, one `lax.scan` step a position
(`h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`, `y_t = h_t C_t + D x_t`), not
the chunked form; the depthwise conv is four explicit taps. For one sequence
of T rows:

  block      x = x + r * mixer(rmsnorm(x)); x = x + r * mlp(rmsnorm(x))
             mlp(a) = (silu(g) * v) @ W_out, [g, v] = a @ W_in
  mamba      [z | xBC | dt] = a @ W_in; xBC_t = silu(b + sum_j w[j] xBC_{t-3+j})
             with zeros before the start; x, B, C = split(xBC) (one group);
             dt = softplus(dt + dt_bias), A = -exp(A_log), no clamp on dt;
             out = (rmsnorm(y * silu(z)) * w) @ W_out
  attention  q, k, v, o without bias, grouped-query, NO position term,
             softmax(attention_multiplier * q k^T) causal
  ends       x_0 = embedding_multiplier * E[token]; final rmsnorm;
             logits = (x @ E^T) / logits_scaling, E the one tied matrix

It takes its inputs from the SEED and nothing the program has made: each
layer's weights are regenerated inside the layer loop
(`weights_granite_hybrid.make_*`, in the served type bfloat16) and cast to
float32 there, one layer at a time, so the reference fits beside the system.

`logit_gaps` and `summarize_gaps` keep `reference.py`'s contract.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp

from benchmark import weights_granite_hybrid as W
from benchmark.reference import _rms_norm, summarize_gaps  # noqa: F401

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def mamba_mixer(a, w, cfg):
    """a [S, T, d] float32 -> [S, T, d]: S sequences side by side, the
    recurrence one position at a time."""
    S, T, _ = a.shape
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    di = H * P
    conv_dim = di + 2 * N
    zxbcdt = a @ jnp.concatenate([w["in_proj"], w["dt_proj"]], axis=1)  # the source's W_in
    z, xBC, dt = zxbcdt[..., :di], zxbcdt[..., di:di + conv_dim], zxbcdt[..., di + conv_dim:]
    K = w["conv_w"].shape[0]
    xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))  # zeros before the start
    conv = w["conv_b"] + sum(w["conv_w"][j] * xp[:, j:j + T] for j in range(K))
    xBC = jax.nn.silu(conv)
    x = xBC[..., :di].reshape(S, T, H, P)
    B, C = xBC[..., di:di + N], xBC[..., di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])        # [S, T, H]
    A = -jnp.exp(w["A_log"])                        # [H]

    def position(h, inp):
        x_t, B_t, C_t, dt_t = inp                   # [S,H,P] [S,N] [S,N] [S,H]
        h = (jnp.exp(dt_t * A)[:, :, None, None] * h
             + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :])
        y_t = jnp.sum(h * C_t[:, None, None, :], axis=-1) + w["D"][None, :, None] * x_t
        return h, y_t

    time_major = lambda t: jnp.moveaxis(t, 1, 0)    # noqa: E731
    _, y = jax.lax.scan(position, jnp.zeros((S, H, P, N), F32),
                        (time_major(x), time_major(B), time_major(C), time_major(dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(S, T, di)
    return _rms_norm(y * jax.nn.silu(z), w["gate_norm"], cfg.rms_eps) @ w["out_proj"]


def attention_mixer(a, w, cfg):
    """a [S, T, d] -> [S, T, d]; one sequence at a time."""
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def one(a):
        T = a.shape[0]
        q = (a @ w["wq"]).reshape(T, kvh, h // kvh, hd)
        k = (a @ w["wk"]).reshape(T, kvh, hd)
        v = (a @ w["wv"]).reshape(T, kvh, hd)
        s = jnp.einsum("tkgd,skd->kgts", q, k) * cfg.attention_multiplier
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, h * hd) @ w["wo"]

    return jax.lax.map(one, a)


def block(x, mixer, w, ff, cfg):
    r = cfg.residual_multiplier
    x = x + r * mixer(_rms_norm(x, w["norm"], cfg.rms_eps), w, cfg)
    m = _rms_norm(x, ff["norm"], cfg.rms_eps)
    g, v = jnp.split(m @ ff["w_in"], 2, axis=-1)
    return x + r * ((jax.nn.silu(g) * v) @ ff["w_out"])


def hidden(key, tokens, cfg):
    """Final-norm hidden states [S, T, d] and the tied matrix in float32.
    The layer loop is outermost: each layer's weights are made from `key`
    once, a run of layers of one kind is one `lax.scan` over its keys."""
    k_embed, k_m, k_a, k_f = W.part_keys(key, cfg)
    embed = W.make_embed(k_embed, cfg).astype(F32)
    x = cfg.embedding_multiplier * embed[tokens]
    make = {W.MAMBA: (W.make_mamba_layer, mamba_mixer, k_m),
            W.ATTENTION: (W.make_attn_layer, attention_mixer, k_a)}
    seen = {W.MAMBA: 0, W.ATTENTION: 0}
    g = 0
    for kind, run in itertools.groupby(cfg.layer_types):
        n = len(list(run))
        make_layer, mixer, keys = make[kind]

        def body(x, kk, make_layer=make_layer, mixer=mixer):
            k_layer, k_ff = kk
            return block(x, mixer, _f32(make_layer(k_layer, cfg)), _f32(W.make_mlp(k_ff, cfg)),
                         cfg), None

        x, _ = jax.lax.scan(body, x, (keys[seen[kind]:seen[kind] + n], k_f[g:g + n]))
        seen[kind] += n
        g += n
    return _rms_norm(x, jnp.ones((cfg.d_model,), F32), cfg.rms_eps), embed


def logits(key, tokens, cfg):
    """Logits [S, T, V] float32 of token rows [S, T] (tests and small sizes:
    at the cell's size `logit_gaps` never holds all positions' logits)."""
    with jax.default_matmul_precision("highest"):
        x, embed = hidden(key, tokens, cfg)
        return (x @ embed.T) / cfg.logits_scaling


@functools.lru_cache(maxsize=8)
def _jitted_gaps(cfg, n_out):
    def fn(key, tokens, first, count):
        with jax.default_matmul_precision("highest"):
            T = tokens.shape[1]
            x, embed = hidden(key, tokens, cfg)
            # the emitted tokens are tokens[first : first + count], each
            # predicted from the position before it
            idx = first[:, None] - 1 + jnp.arange(n_out)[None, :]
            at = jnp.clip(idx, 0, T - 1)
            emitted = jnp.take_along_axis(tokens, jnp.clip(idx + 1, 0, T - 1), axis=1)

            def one(row):  # a row's [n_out, V] logits at a time
                x_row, at_row, emitted_row = row
                lg = (x_row[at_row] @ embed.T) / cfg.logits_scaling
                gap = lg.max(-1) - jnp.take_along_axis(lg, emitted_row[:, None], -1)[:, 0]
                return gap, lg.std(-1)

            gap, spread = jax.lax.map(one, (x, at, emitted))
            valid = jnp.arange(n_out)[None, :] < count[:, None]
            return jnp.where(valid, gap, -1.0), spread
    return jax.jit(fn)


def logit_gaps(key, tokens, first, count, cfg, n_out: int):
    """tokens [S, T] int32 (prompt + emitted, right-padded with 0), first [S]
    the prompt lengths, count [S] the emitted tokens (0 for a padding row).
    Returns (gaps [S, n_out], -1 where nothing was emitted; the spread of the
    reference's logits there)."""
    return _jitted_gaps(cfg, n_out)(key, tokens, first, count)
