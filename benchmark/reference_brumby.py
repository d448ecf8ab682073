"""The plain reference of the `brumby` configuration (a dense GQA decoder's
block with power retention of degree 2 in every layer) in float32 `jax.numpy`
under `default_matmul_precision("highest")`.

No kernels, no cache, no chunks, NO STATE and no `phi`: the mixer is the
ATTENTION form, written from the equations and not by calling the program.
For one sequence of T rows and one query head, the whole [T, T] matrix of
scores is squared, weighted by the decays (one cumulative sum of log g down
the sequence), masked to the past and present, and the output is its product
with the values over its row sums: the program keeps a symmetric-square
state and never forms a score against a position of an earlier chunk; the
reference forms every score and never expands anything. That the two agree
is the point. For one sequence, x the residual stream:

  layer      h = x + Mixer(N1(x));  y = h + W_down(silu(W_gate N2 h) * (W_up N2 h))
  N(x)       x / sqrt(mean(x^2) + eps) * w
  mixer      q = rope(N_q(W_q a)) [T, h, d], k = rope(N_k(W_k a)) [T, kvh, d],
             v = W_v a [T, kvh, d]; rotate_half RoPE over the whole head;
             log g = log_sigmoid(W_g a + b) [T, kvh]; c = cumsum(log g) down T;
             query head i reads KV head i // (h / kvh):
               w[t, j] = (q_t . k_j / sqrt d)^2 exp(c_t - c_j)   for j <= t, else 0
               o_t = sum_j w[t, j] v_j / (sum_j w[t, j] + eps)
             out = W_o concat_i(o_i)
  ends       x_0 = E[token]; final N; untied head

It takes its inputs from the SEED and nothing the program has made: each
layer's matrices are regenerated where they are used (`weights_brumby
.make_matrix`, in the served type) and cast to float32 there, ONE MATRIX AT A
TIME and the FFN's a slice of its columns at a time; the mixers and the FFN go
one sequence at a time and the head a slice of the vocabulary at a time: the
system's weights and lanes fill most of the chip, and the reference has to
fit beside them.

`logit_gaps` and `summarize_gaps` keep `reference.py`'s contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights_brumby as W
from benchmark.reference import summarize_gaps  # noqa: F401

F32 = jnp.float32
HEAD_SLICES = 8  # of the vocabulary, one at a time
FFN_SLICES = 4   # of the FFN's columns, one at a time
RET_EPS = 1e-6   # the normaliser's eps (the configuration's `assumed.normaliser`)


def norm(x, eps):
    """RMS norm with the weight 1 that every norm of these weights has."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rotate(x, theta):
    """x [T, heads, d]: rotate_half RoPE at positions 0..T-1 over the whole head."""
    T, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def retention(q, k, v, log_g, eps=RET_EPS):
    """The attention form for one sequence: q [T, h, d], k and v [T, kvh, d],
    log_g [T, kvh] -> [T, h, d]; one query head at a time, each against its
    whole [T, T] matrix of squared, decayed scores."""
    T, h, d = q.shape
    kvh = k.shape[1]
    c = jnp.cumsum(log_g, axis=0)                                  # [T, kvh]
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head(inp):
        q_i, i = inp                                               # [T, d], the head's index
        j = i // (h // kvh)
        k_j, v_j, c_j = k[:, j], v[:, j], c[:, j]
        s = (q_i @ k_j.T) * d ** -0.5
        w = jnp.where(seen, s * s * jnp.exp(jnp.where(seen, c_j[:, None] - c_j[None, :], 0.0)), 0.0)
        return (w @ v_j) / (w.sum(axis=-1, keepdims=True) + eps)

    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(h)))
    return jnp.moveaxis(o, 0, 1)


def mixer(a, k_layer, cfg):
    """a [T, d_model] -> [T, d_model], one sequence."""
    T = a.shape[0]
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    m = lambda name: W.make_matrix(k_layer, name, cfg).astype(F32)  # noqa: E731
    q = rotate(norm((a @ m("wq")).reshape(T, h, d), cfg.rms_eps), cfg.rope_theta)
    k = rotate(norm((a @ m("wk")).reshape(T, kvh, d), cfg.rms_eps), cfg.rope_theta)
    v = (a @ m("wv")).reshape(T, kvh, d)
    log_g = jax.nn.log_sigmoid(a @ m("wg") + W.make_gate_bias(k_layer, cfg))
    return retention(q, k, v, log_g).reshape(T, h * d) @ m("wo")


def ffn(x, k_layer, cfg):
    """x [S, T, d_model] -> SwiGLU(x), a slice of the FFN's columns and a
    sequence at a time."""
    f = cfg.d_ff
    n = FFN_SLICES if f % FFN_SLICES == 0 else 1
    gate, up, down = (W.make_matrix(k_layer, name, cfg) for name in ("w_gate", "w_up", "w_down"))
    out = jnp.zeros_like(x)
    for i in range(n):
        cols = slice(i * (f // n), (i + 1) * (f // n))
        g, u, dn = gate[:, cols].astype(F32), up[:, cols].astype(F32), down[cols].astype(F32)
        out = out + jax.lax.map(lambda row: (jax.nn.silu(row @ g) * (row @ u)) @ dn, x)  # noqa: B023
    return out


def hidden(key, tokens, cfg):
    """Final-norm hidden states [S, T, d]: S sequences, each on its own
    through the mixers; the layer loop is outermost, so each matrix is made
    from `key` once."""
    k_embed, _, k_l = W.part_keys(key, cfg)
    x = W.make_embed(k_embed, cfg)[tokens].astype(F32)
    for k_layer in k_l:
        a = norm(x, cfg.rms_eps)
        x = x + jax.lax.map(lambda row: mixer(row, k_layer, cfg), a)  # noqa: B023
        x = x + ffn(norm(x, cfg.rms_eps), k_layer, cfg)
    return norm(x, cfg.rms_eps)


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
