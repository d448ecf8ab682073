"""The plain reference of the Phi-4-mini-flash configuration: the SambaY
decoder-hybrid-decoder (HF `phi4flash`, arXiv:2507.06607) with Differential
Attention (arXiv:2410.05258), in float32 `jax.numpy` under
`default_matmul_precision("highest")`.

No kernels, no cache, no batching tricks, and no algorithm of the program's:
ALL layers run at EVERY position (the program's prefill skips the cross-decoder
but for a row's last position); the state-space layer is the recurrence
itself, one `lax.scan` step a position, not a chunked form; the depthwise conv
is four explicit taps; every attention is whole score matrices; the head
pairs are taken by the source's own reshape `(heads / 2, 2, head_dim)` and its
four attentions a layer (`attn11`, `attn12`, `attn21`, `attn22`), not by the
program's laid-out queries. For one sequence of T rows, layer `l` of `n`:

  block      x = x + mixer(LN(x)); x = x + fc2(u * silu(g)), [u | g] = fc1(LN(x))
             LN = LayerNorm with weight and bias, eps 1e-5; no position term
  mamba      even l <= n/2: [x | z] = a @ W_in; x_t = silu(b + sum_j w[j]
             x_{t-3+j}) with zeros before the start; [dt_r | B | C] = x @ W_x;
             dt = softplus(dt_r @ W_dt + dt_bias); A = -exp(A_log) [d_inner, N];
             h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t^T; y_t = h_t C_t + D
             x_t; out = (y * silu(z)) @ W_out. Layer n/2's y is the memory m
  attention  odd l: q [T, h/2, 2, hd]; k, v [T, kvh/2, 2, hd] (for l <= n/2 + 1
             from Wqkv(a) with bias; for l > n/2 + 1 q = Wq(a) and k, v are
             layer n/2 + 1's); attn_i = softmax(q_i k_i^T / sqrt(hd) + mask)
             [v_1 | v_2], grouped-query over the pairs; lambda = exp(lq1 . lk1)
             - exp(lq2 . lk2) + lambda_init(l), lambda_init(l) = 0.8 - 0.6
             exp(-0.3 l); out = (RMSNorm_2hd(attn_1 - lambda attn_2) (1 -
             lambda_init)) @ W_o + b_o. Mask: causal, and 0 <= i - j <
             sliding_window for l < n/2
  memory     even l > n/2: out = (silu(a @ W_in) * m) @ W_out
  ends       x_0 = E[token]; final LayerNorm; logits = x @ E^T, E the one tied
             matrix

It takes its inputs from the SEED and nothing the program has made: each
layer's weights are regenerated inside the layer loop
(`weights_phi4flash.make_*`, in the served type bfloat16) and cast to float32
there, one layer at a time, and inside a layer the sequences are walked `ROWS`
at a time and the head in pieces of positions, so the reference fits beside
the system.

`logit_gaps` and `summarize_gaps` keep `reference.py`'s contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights_phi4flash as W
from benchmark.reference import summarize_gaps  # noqa: F401

F32 = jnp.float32
ROWS = 2      # sequences the layers walk at a time
PIECE = 256   # positions whose logits are held at a time


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _layer_norm(x, w, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w["norm_w"] + w["norm_b"]


def lambda_init(l):
    return 0.8 - 0.6 * jnp.exp(-0.3 * l)


def by_rows(f, *xs):
    """`f` over the sequences [S, ...] of `xs`, ROWS of them at a time."""
    S = xs[0].shape[0]
    rows = ROWS if S % ROWS == 0 else 1
    out = jax.lax.map(lambda r: f(*r), tuple(a.reshape(S // rows, rows, *a.shape[1:]) for a in xs))
    return jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), out)


def mamba_mixer(a, w, cfg):
    """a [S, T, d] float32 -> (out [S, T, d], y [S, T, d_inner] before the
    gate): S sequences side by side, the recurrence one position at a time."""
    S, T, _ = a.shape
    N, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    x, z = jnp.split(a @ w["in_proj"], 2, axis=-1)
    K = w["conv_w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))  # zeros before the start
    x = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][j] * xp[:, j:j + T] for j in range(K)))
    dbc = x @ w["x_proj"].T                          # the source's x_proj: d_inner -> r + 2 N
    dt = jax.nn.softplus(dbc[..., :r] @ w["dt_proj"] + w["dt_bias"])   # [S, T, d_inner]
    B, C = dbc[..., r:r + N], dbc[..., r + N:]
    A = -jnp.exp(w["A_log"]).T                       # [d_inner, N], as the source keeps it

    def position(h, inp):
        x_t, dt_t, B_t, C_t = inp                    # [S,c] [S,c] [S,N] [S,N]
        h = jnp.exp(dt_t[:, :, None] * A) * h + (dt_t * x_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], axis=-1) + w["D"] * x_t

    time_major = lambda t: jnp.moveaxis(t, 1, 0)     # noqa: E731
    _, y = jax.lax.scan(position, jnp.zeros((S, x.shape[-1], N), F32),
                        (time_major(x), time_major(dt), time_major(B), time_major(C)))
    y = jnp.moveaxis(y, 0, 1)
    return (y * jax.nn.silu(z)) @ w["out_proj"], y


def _softmax_attention(q, k, v, mask):
    """q [T, Hq, hd], k [S, Hk, hd], v [S, Hk, hv], Hq a multiple of Hk
    (query head j reads key head j // (Hq / Hk)) -> [T, Hq, hv]."""
    T, Hq, hd = q.shape
    Hk = k.shape[1]
    s = jnp.einsum("tkrd,skd->krts", q.reshape(T, Hk, Hq // Hk, hd), k) / jnp.sqrt(1.0 * hd)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("krts,skd->tkrd", p, v).reshape(T, Hq, v.shape[-1])


def diff_attention(q, k, v, w, l, mask, cfg):
    """One sequence: q [T, h * hd], k and v [T, kvh * hd] as projected -> [T,
    h * hd], by the source's reshape and its four attentions."""
    T, hd = q.shape[0], cfg.head_dim
    q = q.reshape(T, cfg.n_heads // 2, 2, hd)
    k = k.reshape(T, cfg.n_kv_heads // 2, 2, hd)
    v = v.reshape(T, cfg.n_kv_heads // 2, 2, hd)
    q1, q2, k1, k2, v1, v2 = q[:, :, 0], q[:, :, 1], k[:, :, 0], k[:, :, 1], v[:, :, 0], v[:, :, 1]
    attn1 = jnp.concatenate([_softmax_attention(q1, k1, v1, mask),
                             _softmax_attention(q1, k1, v2, mask)], axis=-1)
    attn2 = jnp.concatenate([_softmax_attention(q2, k2, v1, mask),
                             _softmax_attention(q2, k2, v2, mask)], axis=-1)
    init = lambda_init(l)
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + init)
    attn = attn1 - lam * attn2                                           # [T, h / 2, 2 hd]
    attn = attn * jax.lax.rsqrt(jnp.mean(attn * attn, -1, keepdims=True) + cfg.layer_norm_eps)
    return (attn * w["subln"] * (1.0 - init)).reshape(T, cfg.n_heads * hd)


def _mask(T: int, window):
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    return (j <= i) if window is None else (j <= i) & (i - j < window)


def attention_mixer(a, w, l, window, cfg):
    """A window or the full layer, a [S, T, d] -> (out, (k, v) [S, T, kvh * hd]);
    one sequence at a time."""
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    qkv = a @ w["wqkv"] + w["bqkv"]
    q, k, v = qkv[..., :hq], qkv[..., hq:hq + hkv], qkv[..., hq + hkv:]
    mask = _mask(a.shape[1], window)
    o = jax.lax.map(lambda r: diff_attention(*r, w, l, mask, cfg), (q, k, v))
    return o @ w["wo"] + w["bo"], (k, v)


def cross_mixer(a, kv, w, l, cfg):
    q = a @ w["wq"] + w["bq"]
    mask = _mask(a.shape[1], None)
    o = jax.lax.map(lambda r: diff_attention(*r, w, l, mask, cfg), (q, *kv))
    return o @ w["wo"] + w["bo"]


def _mlp(x, ff, cfg):
    u, g = jnp.split(_layer_norm(x, ff, cfg.layer_norm_eps) @ ff["fc1"], 2, axis=-1)
    return x + (u * jax.nn.silu(g)) @ ff["fc2"]


def mamba_layer(x, keys, cfg):
    """x [S, T, d] through a Mamba layer and its MLP -> (x, y)."""
    k, k_ff = keys
    w, ff = _f32(W.make_mamba_layer(k, cfg)), _f32(W.make_mlp(k_ff, cfg))

    def rows(x):
        o, y = mamba_mixer(_layer_norm(x, w, cfg.layer_norm_eps), w, cfg)
        return _mlp(x + o, ff, cfg), y

    return by_rows(rows, x)


def attention_layer(x, keys, l, window, cfg):
    """x through a window or the full layer and its MLP -> (x, (k, v))."""
    k, k_ff = keys
    w, ff = _f32(W.make_attn_layer(k, cfg)), _f32(W.make_mlp(k_ff, cfg))

    def rows(x):
        o, kv = attention_mixer(_layer_norm(x, w, cfg.layer_norm_eps), w, l, window, cfg)
        return _mlp(x + o, ff, cfg), kv

    return by_rows(rows, x)


def hidden(key, tokens, cfg):
    """Final-norm hidden states [S, T, d] in float32. The layer loop is
    outermost: each layer's weights are made from `key` once, and inside a
    layer the sequences are walked ROWS at a time; the pairs (Mamba, window)
    and (memory unit, cross) are each one `lax.scan` over their keys."""
    k_embed, k_m, k_a, k_g, k_c, k_f = W.part_keys(key, cfg)
    half, eps = cfg.n_layers // 2, cfg.layer_norm_eps
    n_w, n_c = half // 2, (cfg.n_layers - half - 2) // 2
    x = W.make_embed(k_embed, cfg).astype(F32)[tokens]

    def self_pair(x, ks):
        km, ka, kf, l = ks
        x, _ = mamba_layer(x, (km, kf[0]), cfg)
        x, _ = attention_layer(x, (ka, kf[1]), l + 1.0, cfg.sliding_window, cfg)
        return x, None

    x, _ = jax.lax.scan(self_pair, x, (k_m[:n_w], k_a[:n_w], k_f[:half].reshape(n_w, 2, -1),
                                       2.0 * jnp.arange(n_w)))
    x, m = mamba_layer(x, (k_m[n_w], k_f[half]), cfg)
    x, kv = attention_layer(x, (k_a[n_w], k_f[half + 1]), half + 1.0, None, cfg)

    def cross_pair(x, ks):
        kg, kc, kf, l = ks
        wg, wc = _f32(W.make_gmu_layer(kg, cfg)), _f32(W.make_cross_layer(kc, cfg))
        ffg, ffc = _f32(W.make_mlp(kf[0], cfg)), _f32(W.make_mlp(kf[1], cfg))

        def rows(x, m, k, v):
            o = (jax.nn.silu(_layer_norm(x, wg, eps) @ wg["in_proj"]) * m) @ wg["out_proj"]
            x = _mlp(x + o, ffg, cfg)
            return _mlp(x + cross_mixer(_layer_norm(x, wc, eps), (k, v), wc, l + 1.0, cfg), ffc, cfg)

        return by_rows(rows, x, m, *kv), None

    x, _ = jax.lax.scan(cross_pair, x, (k_g, k_c, k_f[half + 2:].reshape(n_c, 2, -1),
                                        half + 2.0 + 2.0 * jnp.arange(n_c)))
    final = {"norm_w": jnp.ones((cfg.d_model,), F32), "norm_b": jnp.zeros((cfg.d_model,), F32)}
    return _layer_norm(x, final, eps)


def _embed(key, cfg):
    return W.make_embed(W.part_keys(key, cfg)[0], cfg).astype(F32)


def logits(key, tokens, cfg):
    """Logits [S, T, V] float32 of token rows [S, T] (tests and small sizes:
    at the cell's size `logit_gaps` never holds all positions' logits)."""
    with jax.default_matmul_precision("highest"):
        return hidden(key, tokens, cfg) @ _embed(key, cfg).T


@functools.lru_cache(maxsize=8)
def _jitted_gaps(cfg, n_out):
    def fn(key, tokens, first, count):
        with jax.default_matmul_precision("highest"):
            S, T = tokens.shape
            x = hidden(key, tokens, cfg)
            embed = _embed(key, cfg)
            # the emitted tokens are tokens[first : first + count], each
            # predicted from the position before it
            idx = first[:, None] - 1 + jnp.arange(n_out)[None, :]
            at = jnp.clip(idx, 0, T - 1)
            emitted = jnp.take_along_axis(tokens, jnp.clip(idx + 1, 0, T - 1), axis=1)
            piece = PIECE if n_out % PIECE == 0 else n_out
            x_at = jnp.take_along_axis(x, at[:, :, None], axis=1)

            def one(rows_):  # [piece, V] logits at a time
                x_p, emitted_p = rows_
                lg = x_p @ embed.T
                gap = lg.max(-1) - jnp.take_along_axis(lg, emitted_p[:, None], -1)[:, 0]
                return gap, lg.std(-1)

            gap, spread = jax.lax.map(one, (x_at.reshape(-1, piece, x.shape[-1]),
                                            emitted.reshape(-1, piece)))
            valid = jnp.arange(n_out)[None, :] < count[:, None]
            return jnp.where(valid, gap.reshape(S, n_out), -1.0), spread.reshape(S, n_out)
    return jax.jit(fn)


def logit_gaps(key, tokens, first, count, cfg, n_out: int):
    """tokens [S, T] int32 (prompt + emitted, right-padded with 0), first [S]
    the prompt lengths, count [S] the emitted tokens (0 for a padding row).
    Returns (gaps [S, n_out], -1 where nothing was emitted; the spread of the
    reference's logits there)."""
    return _jitted_gaps(cfg, n_out)(key, tokens, first, count)
