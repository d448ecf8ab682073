"""The plain reference of the `qwen3_next` configuration (gated-delta-rule
linear attention, a gated full-attention layer every fourth, softmax-routed
experts beside a gated shared one) in float32 `jax.numpy` under
`default_matmul_precision("highest")`.

No kernels, no cache, no chunks, and no algorithm of the program's: the
linear-attention layer is the recurrence itself, one `lax.scan` step a
position, the depthwise conv is four explicit taps, attention is the whole
score matrix of one sequence and head under the causal mask, and the expert
layer is the definition itself: every HELD expert applied to every row and
weighted by that row's routing weight for it, zero where the row did not
choose it. For one sequence of T rows, x the residual stream:

  layer      h = x + Mixer(N1(x));  y = h + MoE(N2(h))
  N(x)       x / sqrt(mean(x^2) + eps) * (1 + w)            (zero-centred)
  linear     layers with (i + 1) % 4 != 0. [q | k | v | z] = W_qkvz u,
             [b | a] = W_ba u; qkv_t = silu(sum_j w[j] qkv_{t-3+j}), zeros
             before the start, no bias; beta = sigmoid(b); g = -exp(A_log)
             softplus(a + dt_bias); q and k over their L2 norm a head (eps
             1e-6), q times K^-0.5; a q / k head serves two consecutive value
             heads; a value head's state S [K, V] from zero:
               S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t (x) d
               o_t = S^T q_t
             out = W_o concat_h(o_h / sqrt(mean(o_h^2) + eps) w_n silu(z_h))
  attention  layers with (i + 1) % 4 == 0. q, gate = W_q u (heads of hd each),
             k, v = KV heads of hd; q and k through N over a head; rotate_half
             RoPE on the FIRST `rotary_dim` entries of each head, the others
             untouched; causal softmax at hd^-0.5, grouped-query;
             out = W_o concat_h(o_h * sigmoid(gate_h))
  MoE        p = softmax(W_r u) over all the router's experts; the top_k
             largest chosen; w = p[chosen] / sum p[chosen]; out = sum over the
             HELD experts e of w_e SwiGLU_e(u) + sigmoid(w_g . u) SwiGLU_shared(u).
             An expert the row chose and this share does not hold adds nothing.
  ends       x_0 = E[token]; final N; untied head

It takes its inputs from the SEED and nothing the program has made: each
layer's weights, and inside an expert layer each held expert's, are
regenerated where they are used (`weights_qwen3_next.make_*`, in the served
type) and cast to float32 there. The mixers go one sequence at a time and the
head a slice of the vocabulary at a time, so that a 4k-token sample fits
beside the system.

`logit_gaps` and `summarize_gaps` keep `reference.py`'s contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights_qwen3_next as W
from benchmark.reference import summarize_gaps  # noqa: F401
from benchmark.reference_afmoe import _f32, swiglu

F32 = jnp.float32
HEAD_SLICES = 8  # of the vocabulary, one at a time


def norm(x, w, eps):
    """The zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def linear_mixer(u, w, cfg):
    """u [T, d] -> [T, d], one sequence; the recurrence one position at a time."""
    T = u.shape[0]
    Hk, K, H, V = cfg.lin_k_heads, cfg.lin_k_dim, cfg.lin_v_heads, cfg.lin_v_dim
    qkvz, ba = u @ w["in_proj"], u @ w["ba_proj"]
    c = 2 * Hk * K + H * V
    qkv, z = qkvz[:, :c], qkvz[:, c:]
    taps = w["conv_w"].shape[0]
    xp = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))  # zeros before the start
    qkv = jax.nn.silu(sum(w["conv_w"][j] * xp[j:j + T] for j in range(taps)))
    q = l2norm(qkv[:, :Hk * K].reshape(T, Hk, K)) * K ** -0.5
    k = l2norm(qkv[:, Hk * K:2 * Hk * K].reshape(T, Hk, K))
    v = qkv[:, 2 * Hk * K:].reshape(T, H, V)
    # a q / k head serves H / Hk consecutive value heads
    q, k = jnp.repeat(q, H // Hk, axis=1), jnp.repeat(k, H // Hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :H])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, H:] + w["dt_bias"])

    def position(S, inp):
        q_t, k_t, v_t, g_t, beta_t = inp            # [H,K] [H,K] [H,V] [H] [H]
        S = jnp.exp(g_t)[:, None, None] * S
        d = beta_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(position, jnp.zeros((H, K, V), F32), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps) * w["head_norm"]
    return (o.reshape(T, H * V) * jax.nn.silu(z)) @ w["out_proj"]


def rotate_first(x, rot, theta):
    """x [T, heads, hd]: rotate_half RoPE at positions 0..T-1 on x[..., :rot]."""
    T, half = x.shape[0], rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]], axis=-1)


def attention_mixer(u, w, cfg):
    """u [T, d] -> [T, d], one sequence; one head at a time, each against its
    whole [T, T] score matrix."""
    T = u.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rot = int(hd * cfg.partial_rotary_factor)
    q = norm((u @ w["wq"]).reshape(T, h, hd), w["q_norm"], cfg.rms_eps)
    k = norm((u @ w["wk"]).reshape(T, kvh, hd), w["k_norm"], cfg.rms_eps)
    v = (u @ w["wv"]).reshape(T, kvh, hd)
    q, k = rotate_first(q, rot, cfg.rope_theta), rotate_first(k, rot, cfg.rope_theta)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head(inp):
        q_h, i = inp                                   # [T, hd], the head's index
        k_h, v_h = k[:, i // (h // kvh)], v[:, i // (h // kvh)]
        s = (q_h @ k_h.T) * hd ** -0.5
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v_h

    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(h)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, h * hd)
    return (o * jax.nn.sigmoid(u @ w["wg"])) @ w["wo"]


def routing_weights(u, router, cfg):
    """[N, router's experts]: each row's weight for every expert, zero for
    those it did not choose: softmax over all, the top_k, renormalised."""
    p = jax.nn.softmax(u @ router, axis=-1)
    _, chosen = jax.lax.top_k(p, cfg.top_k)
    picked = jnp.zeros_like(p).at[jnp.arange(u.shape[0])[:, None], chosen].set(1.0)
    w = p * picked
    return w / w.sum(-1, keepdims=True) if cfg.route_norm else w


def expert_layer(u, k_moe, cfg):
    """u [N, d] -> [N, d]; the held experts one after another, each made from
    its key, applied to every row and weighted."""
    k_r, k_e, k_s, k_g = W.moe_keys(k_moe, cfg)
    w = routing_weights(u, W.make_router(k_r, cfg).astype(F32), cfg)
    w_held = w[:, cfg.held_first:cfg.held_first + cfg.held_count]

    def one(acc, inp):
        k_expert, w_e = inp
        return acc + w_e[:, None] * swiglu(u, _f32(W.make_expert(k_expert, cfg))), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (W.held_keys(k_e, cfg), w_held.T))
    gate = jax.nn.sigmoid(u @ W.make_shared_gate(k_g, cfg).astype(F32))
    return routed + gate[:, None] * swiglu(u, _f32(W.make_shared(k_s, cfg)))


def hidden(key, tokens, cfg):
    """Final-norm hidden states [S, T, d]: S sequences, each on its own
    through the mixers; the layer loop is outermost, so each layer's weights
    are made from `key` once."""
    k_embed, _, k_l, k_f, k_m = W.part_keys(key, cfg)
    S, T = tokens.shape
    x = W.make_embed(k_embed, cfg)[tokens].astype(F32)
    seen = {W.LINEAR: 0, W.FULL: 0}
    for g, kind in enumerate(W.layer_types(cfg)):
        if kind == W.LINEAR:
            w, mixer = _f32(W.make_linear_layer(k_l[seen[kind]], cfg)), linear_mixer
        else:
            w, mixer = _f32(W.make_full_layer(k_f[seen[kind]], cfg)), attention_mixer
        seen[kind] += 1
        a = norm(x, w["norm"], cfg.rms_eps)
        x = x + jax.lax.map(lambda row: mixer(row, w, cfg), a)  # noqa: B023
        # N2's offset is the expert layer's own leaf in the tree: zero, as every norm's
        m = norm(x, jnp.zeros((cfg.d_model,), F32), cfg.rms_eps)
        x = x + expert_layer(m.reshape(S * T, -1), k_m[g], cfg).reshape(m.shape)
    return norm(x, jnp.zeros((cfg.d_model,), F32), cfg.rms_eps)


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
