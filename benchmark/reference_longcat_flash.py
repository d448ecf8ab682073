"""The plain reference of the LongCat-Flash configuration (`LongCat-Flash-Chat`)
in float32 `jax.numpy` under `default_matmul_precision("highest")`.

No kernels, no cache, no batching of experts, and no algorithm of the
program's: every position's keys and values of every head are EXPANDED from
its latent (nothing is absorbed into a query, nothing is cached), attention is
the whole score matrix of one sequence under the causal mask, both attentions
and both dense FFNs of a layer are written out, and the expert layer is the
definition itself: every HELD expert applied to every row and weighted by that
row's routing weight for it (zero where the row did not choose it), the
identity experts' term as (sum of the row's weights for them) x m. For one
sequence of T rows, x the residual stream, every N an RMSNorm (eps 1e-5) with
its own scale:

  layer      h1 = x + Attn_0(N_a0 x);  m = N_f0 h1;  e = MoE(m)
             h2 = h1 + FFN_0(m);  h3 = h2 + Attn_1(N_a1 h2)
             y = h3 + FFN_1(N_f1 h3) + e       (e crosses the second half)
  Attn(u)    q = W_qb N_q(W_qa u) -> heads of [q_nope | q_rope], times
             (d / q_lora_rank)^0.5; [c | k_r] = W_kva u; c = N_kv(c) times
             (d / kv_lora_rank)^0.5; RoPE on q_rope and on k_r (one vector for
             all heads; k_r is neither normed nor scaled); k_nope_h = W_uk,h c,
             v_h = W_uv,h c; s_h = (nope + rope)^-0.5 (q_nope_h . k_nope_h +
             q_rope_h . k_r), causal softmax, out = Wo concat_h(sum_j p_h v_h)
  RoPE       rotate_half pairs, frequencies theta^(-2i/rope), no scaling
  FFN        SwiGLU of width ffn_hidden_size
  MoE(m)     p = softmax(W_r m) over real + identity outputs; the top_k largest
             of p + b chosen; w = route_scale p at the chosen, not normalised;
             sum over the HELD real experts j of w_j SwiGLU_j(m), plus
             (sum of w over the chosen identity experts) m. A real expert the
             row chose and this share does not hold adds nothing.
  ends       x_0 = E[token]; final RMSNorm; untied head

Departures from the published description: the rotary pairs are halves where
the source interleaves them (a permutation of seed-made weights); W_kv_b lies
as its two halves; what the real experts on the other chips would add is left
out, here and in the program alike (model-configs guide, section 4), and that
partial result goes on to the next layer; otherwise none.

It takes its inputs from the SEED and nothing the program has made: each
sublayer's weights, and inside an expert layer each held expert's, are
regenerated where they are used (`weights_longcat_flash.make_*`, in the served
type) and cast to float32 there; the head is applied a slice of the
vocabulary at a time. At the cell's size the sequences go through in blocks
of rows (the driver's ROWS_AT_A_TIME), so that it fits beside the weights.

`logit_gaps` and `summarize_gaps` keep `reference.py`'s contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights_longcat_flash as W
from benchmark.reference import _rms_norm, summarize_gaps  # noqa: F401
from benchmark.reference_afmoe import _f32, swiglu
from benchmark.reference_sarvam_mla import HEAD_SLICES, rotate

F32 = jnp.float32


def cos_sin(cfg, T):
    """cos and sin [T, rope / 2] of positions 0..T-1, plain frequencies."""
    half = cfg.qk_rope_head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def attention(u, w, cfg):
    """u [T, d] -> [T, d], one sequence; one head at a time, each against its
    whole [T, T] score matrix, its keys and values expanded from the latent."""
    T = u.shape[0]
    h, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    cos, sin = cos_sin(cfg, T)
    q = _rms_norm(u @ w["w_qa"], w["q_a_norm"], cfg.rms_eps) @ w["w_qb"]
    q = q.reshape(T, h, -1)
    if cfg.mla_scale_q_lora:
        q = q * (cfg.d_model / cfg.q_lora_rank) ** 0.5
    ckr = u @ w["w_kv_a"]
    c = _rms_norm(ckr[:, :r], w["kv_norm"], cfg.rms_eps)
    if cfg.mla_scale_kv_lora:
        c = c * (cfg.d_model / r) ** 0.5
    k_r = rotate(ckr[:, r:], cos, sin)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cos, sin)
    scale = (nope + cfg.qk_rope_head_dim) ** -0.5
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head(inp):
        qn, qr, w_uk, w_uv = inp                         # [T, nope] [T, rope] [nope, r] [r, v]
        k_nope, v = c @ w_uk.T, c @ w_uv                 # expanded: [T, nope], [T, v]
        s = (qn @ k_nope.T + qr @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

    o = jax.lax.map(head, (jnp.moveaxis(q_nope, 1, 0), jnp.moveaxis(q_rope, 1, 0),
                           w["w_uk"], w["w_uv"]))
    return jnp.moveaxis(o, 0, 1).reshape(T, -1) @ w["wo"]


def routing_weights(u, router, bias, cfg):
    """[N, real + identity]: each row's weight for every output of the router,
    zero for those it did not choose. The bias enters the choice only; the
    chosen probabilities are NOT normalised."""
    p = jax.nn.softmax(u @ router, axis=-1)
    _, chosen = jax.lax.top_k(p + bias, cfg.top_k)
    picked = jnp.zeros_like(p).at[jnp.arange(u.shape[0])[:, None], chosen].set(1.0)
    return p * picked * cfg.route_scale


def expert_layer(u, k_moe, cfg):
    """The shortcut branch for u [N, d]: the held real experts one after
    another, each made from its key, applied to every row and weighted; then
    the identity experts' term."""
    k_r, k_b, k_e = W.moe_keys(k_moe, cfg)
    router, bias = W.make_router(k_r, k_b, cfg)
    w = routing_weights(u, router.astype(F32), bias, cfg)
    w_held = w[:, cfg.held_first:cfg.held_first + cfg.held_count]

    def one(acc, inp):
        k_expert, w_e = inp
        return acc + w_e[:, None] * swiglu(u, _f32(W.make_expert(k_expert, cfg))), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (W.held_keys(k_e, cfg), w_held.T))
    sum_w_identity = w[:, cfg.n_routed_experts:].sum(axis=-1)
    return routed + sum_w_identity[:, None] * u


def hidden(key, tokens, cfg):
    """Final-norm hidden states [S, T, d]: S sequences, each on its own
    through the attentions; the layer loop is outermost, so each sublayer's
    weights are made from `key` once."""
    k_embed, _, k_l, k_d, k_m = W.part_keys(key, cfg)
    S, T = tokens.shape
    x = W.make_embed(k_embed, cfg)[tokens].astype(F32)

    def half(x, s):
        """h = x + Attn_s(N_a x); (h, N_f h)."""
        w = _f32(W.make_sublayer(k_l[s], cfg))
        a = _rms_norm(x, w["attn_norm"], cfg.rms_eps)
        h = x + jax.lax.map(lambda row: attention(row, w, cfg), a)
        return h, _rms_norm(h, w["ffn_norm"], cfg.rms_eps)

    for i in range(cfg.n_layers):
        h1, m = half(x, 2 * i)
        e = expert_layer(m.reshape(S * T, -1), k_m[i], cfg).reshape(m.shape)
        h2 = h1 + swiglu(m, _f32(W.make_dense_ffn(k_d[2 * i], cfg)))
        h3, m = half(h2, 2 * i + 1)
        x = h3 + swiglu(m, _f32(W.make_dense_ffn(k_d[2 * i + 1], cfg))) + e
    return _rms_norm(x, jnp.ones((cfg.d_model,), F32), cfg.rms_eps)


def logits(key, tokens, cfg):
    """Logits [S, T, V] float32 of token rows [S, T] (tests and small sizes:
    at the cell's size `logit_gaps` never holds all positions' logits)."""
    with jax.default_matmul_precision("highest"):
        head = W.make_lm_head(W.part_keys(key, cfg)[1], cfg).astype(F32)
        return hidden(key, tokens, cfg) @ head


@functools.lru_cache(maxsize=8)
def _jitted_gaps(cfg, n_out):
    # `reference_sarvam_mla._jitted_gaps` with this file's `hidden` (that file
    # may not be edited to share it: a benchmark file an earlier PR wrote)
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
