"""The plain reference of the latent-attention configuration (HF `model_type`
`sarvam_mla`) in float32 `jax.numpy` under `default_matmul_precision("highest")`.

No kernels, no cache, no batching tricks, and no algorithm of the program's:
every position's keys and values of every head are EXPANDED from its latent
(nothing is absorbed into a query, nothing is cached), attention is the whole
score matrix of one sequence under the causal mask, and the expert layer is
the definition itself: every HELD expert applied to every row and weighted by
that row's routing weight for it, zero where the row did not choose it. For
one sequence of T rows, x the residual stream:

  layer      h = x + Attn(N1(x));  y = h + FFN(N2(h));  RMSNorm, learned scale
  Attn(u)    q = Wq u -> heads of [q_nope | q_rope]; [c | k_r] = W_kv_a u;
             c = N_kv(c); each query head RMS-normed over all its entries and
             k_r over its own (`use_qk_norm`); RoPE on q_rope and on k_r (one
             vector for all heads); k_nope_h = W_uk,h c, v_h = W_uv,h c;
             s_h = scale (q_nope_h . k_nope_h + q_rope_h . k_r), causal softmax,
             out = Wo concat_h(sum_j p_h v_h)
  scale      (nope + rope)^-0.5 x (0.1 mscale_all_dim ln(factor) + 1)^2
  RoPE       rotate_half pairs; frequencies YaRN's blend of theta^(-2i/d) and
             the same over `factor`, ramped between the pair that turns
             beta_fast times over the original span and the one that turns
             beta_slow times; cos and sin times mscale / mscale_all_dim's terms
  FFN        the first `n_dense_layers`: SwiGLU of width intermediate_size;
             the others: s = sigmoid(Wr u); the top_k largest of s + b chosen;
             w = route_scale s[chosen] / sum s[chosen]; out = sum over the
             HELD experts e of w_e SwiGLU_e(u) + SwiGLU_shared(u). An expert
             the row chose and this share does not hold adds nothing.
  ends       x_0 = E[token]; final RMSNorm; untied head

It takes its inputs from the SEED and nothing the program has made: each
layer's weights, and inside an expert layer each held expert's, are
regenerated where they are used (`weights_sarvam_mla.make_*`, in the served
type) and cast to float32 there; the head is applied a slice of the
vocabulary at a time.

`logit_gaps` and `summarize_gaps` keep `reference.py`'s contract.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights_sarvam_mla as W
from benchmark.reference import _rms_norm, summarize_gaps  # noqa: F401
from benchmark.reference_afmoe import _f32, routing_weights, swiglu

F32 = jnp.float32
HEAD_SLICES = 8  # of the vocabulary, one at a time


def yarn_cos_sin(cfg, T):
    """cos and sin [T, rope / 2] of positions 0..T-1 under `deepseek_yarn`."""
    d, half = cfg.qk_rope_head_dim, cfg.qk_rope_head_dim // 2
    base = cfg.rope_theta ** (jnp.arange(half, dtype=F32) / half)

    def pair_of(turns):  # the pair that makes `turns` turns over the original span
        return d * math.log(cfg.rope_original_max / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(pair_of(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(pair_of(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / (high - low), 0.0, 1.0)
    freqs = (1.0 / (cfg.rope_factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    m = mscale(cfg.rope_factor, cfg.rope_mscale) / mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def mscale(factor, m):
    return 1.0 if factor <= 1 or not m else 0.1 * m * math.log(factor) + 1.0


def rotate(x, cos, sin):
    """x [T, ..., rope]: the two halves rotated against each other."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(u, w, cfg):
    """u [T, d] -> [T, d], one sequence; one head at a time, each against its
    whole [T, T] score matrix, its keys and values expanded from the latent."""
    T = u.shape[0]
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    cos, sin = yarn_cos_sin(cfg, T)
    q = _rms_norm((u @ w["wq"]).reshape(T, h, -1), w["q_norm"], cfg.rms_eps)
    ckr = u @ w["w_kv_a"]
    c = _rms_norm(ckr[:, :r], w["kv_norm"], cfg.rms_eps)
    k_r = rotate(_rms_norm(ckr[:, r:], w["k_rope_norm"], cfg.rms_eps), cos, sin)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cos, sin)
    scale = (nope + cfg.qk_rope_head_dim) ** -0.5 * mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head(inp):
        qn, qr, w_uk, w_uv = inp                         # [T, nope] [T, rope] [nope, r] [r, v]
        k_nope, v = c @ w_uk.T, c @ w_uv                 # expanded: [T, nope], [T, v]
        s = (qn @ k_nope.T + qr @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    o = jax.lax.map(head, (jnp.moveaxis(q_nope, 1, 0), jnp.moveaxis(q_rope, 1, 0),
                           w["w_uk"], w["w_uv"]))
    return jnp.moveaxis(o, 0, 1).reshape(T, -1) @ w["wo"]


def expert_layer(u, k_moe, cfg):
    """u [N, d] -> [N, d]; the held experts one after another, each made from
    its key, applied to every row and weighted."""
    k_r, k_b, k_e, k_s = W.moe_keys(k_moe, cfg)
    router, bias = W.make_router(k_r, k_b, cfg)
    w = routing_weights(u, router.astype(F32), bias, cfg)          # [N, router's experts]
    w_held = w[:, cfg.held_first:cfg.held_first + cfg.held_count]

    def one(acc, inp):
        k_expert, w_e = inp
        return acc + w_e[:, None] * swiglu(u, _f32(W.make_expert(k_expert, cfg))), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (W.held_keys(k_e, cfg), w_held.T))
    return routed + swiglu(u, _f32(W.make_shared(k_s, cfg)))


def hidden(key, tokens, cfg):
    """Final-norm hidden states [S, T, d]: S sequences, each on its own
    through the attention; the layer loop is outermost, so each layer's
    weights are made from `key` once."""
    k_embed, _, k_l, k_d, k_m = W.part_keys(key, cfg)
    S, T = tokens.shape
    x = W.make_embed(k_embed, cfg)[tokens].astype(F32)
    for g in range(cfg.n_layers):
        w = _f32(W.make_layer(k_l[g], cfg))
        a = _rms_norm(x, w["attn_norm"], cfg.rms_eps)
        x = x + jax.lax.map(lambda row: attention(row, w, cfg), a)  # noqa: B023
        m = _rms_norm(x, w["ffn_norm"], cfg.rms_eps)
        if g < cfg.n_dense_layers:
            y = swiglu(m, _f32(W.make_dense_ffn(k_d[g], cfg)))
        else:
            y = expert_layer(m.reshape(S * T, -1), k_m[g - cfg.n_dense_layers], cfg).reshape(m.shape)
        x = x + y
    return _rms_norm(x, jnp.ones((cfg.d_model,), F32), cfg.rms_eps)


def logits(key, tokens, cfg):
    """Logits [S, T, V] float32 of token rows [S, T] (tests and small sizes:
    at the cell's size `logit_gaps` never holds all positions' logits)."""
    with jax.default_matmul_precision("highest"):
        head = W.make_lm_head(W.part_keys(key, cfg)[1], cfg).astype(F32)
        return hidden(key, tokens, cfg) @ head


@functools.lru_cache(maxsize=8)
def _jitted_gaps(cfg, n_out):
    # `reference_afmoe._jitted_gaps` with this file's `hidden` (that file may
    # not be edited to share it: a benchmark file an earlier PR wrote)
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
