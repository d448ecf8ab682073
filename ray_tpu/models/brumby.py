"""Brumby style decoder (HF `model_type` `brumby`): a dense GQA decoder's
block in which EVERY mixer is power retention of degree 2 (the gated form of
the power attention of "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239). No layer attends: a sequence's whole context is a float32
state a KV head, the same bytes at position 2k and at position 2M.

One layer, x the residual stream, two norms a layer (pre-norm):

    h = x + Mixer(N1(x));   y = h + W_down(silu(W_gate N2(h)) * (W_up N2(h)))

N is an RMS norm with a plain weight, also before the untied head; the
embedding is unscaled. The mixer, a = N1(x) at position t, query head h of
`n_heads`, KV head c = h // (n_heads / n_kv_heads), d = `head_dim`:

- q_h = rope(N_q(W_q a)_h), k_c = rope(N_k(W_k a)_c), v_c = (W_v a)_c: N_q and
  N_k RMS norms over a head with their own weights, rotary over the whole
  head (pairs as halves). The gate is one number a KV head:
  log g_t = log_sigmoid((W_g a)_c + b_c), float32.
- as attention over the past: w_(t,j) = (q_t . k_j / sqrt d)^2 *
  exp(sum_(l=j+1..t) log g_l) for j <= t, o_t = sum_j w_(t,j) v_j /
  (sum_j w_(t,j) + eps); out = W_o concat_h(o_h). A weight is a square: no
  softmax and no maximum.
- as a recurrence. `phi` maps R^d onto its symmetric square, so that
  phi(x) . phi(y) = (x . y)^2; with S (phi's width x d) and z (phi's width)
  a KV head's state, zero at position 0,

      S_t = g_t S_(t-1) + phi(k_t) v_t^T,   z_t = g_t z_(t-1) + phi(k_t)
      o_t = phi(q_t / sqrt d)^T S_t / (phi(q_t / sqrt d)^T z_t + eps)

  for each of the KV head's n_heads / n_kv_heads query heads
  (`retention_step`; `retention_step_stacked` where the lanes' states lie in
  the cache's stack: on a TPU ops/retention_update.py, one pass over each
  live lane's state).
- in chunks of C positions (`retention_chunked`), b_i the running sum of
  log g inside a chunk, B its last: A_(ij) = (q_i . k_j)^2 exp(b_i - b_j) for
  j <= i inside the chunk, what the chunk enters with weighted by exp(b_i),
  and the state leaves as exp(B) S + sum_j exp(B - b_j) phi(k_j) v_j^T. Any C
  gives the same numbers in exact arithmetic.

HOW `phi` AND THE STATE LIE. `phi(x)` is d/2 + 1 rows of d:
row s holds c_s x_i x_((i + s) mod d), c_0 = c_(d/2) = 1 and sqrt 2 between.
Row 0 is the squares; a row 0 < s < d/2 holds every pair {i, j} with j - i = s
or d - s once; row d/2 holds the d/2 pairs at distance d/2 twice over, so the
rows together are the symmetric square (d (d + 1) / 2 = 8,256 distinct
products at d = 128) in (d/2 + 1) d = 8,320 columns: a rotation and a product
a row, no gather, every row a whole lane-row on a TPU. A KV head's state is
ONE matrix (d + `STATE_PAD`, phi's width), the TRANSPOSE of S with z as row d
(rows past it zero): with the value row extended by a one, [v | 1 | 0..], z's
recurrence IS S's, the normaliser is one more column of every product, and
the wide axis is the minor one. float32, whatever `cfg.dtype`.

Precision: weights and activations in `cfg.dtype`; matrix products take
`cfg.dtype` operands and accumulate in float32; the state, the gates, the
decays, `phi` and the division in float32. The chunked form rounds the
operands of its products (q, k, the squared decayed scores, phi(q), phi(k),
the decay-weighted value rows and the carried state AS AN OPERAND) to
`cfg.dtype`; the state is carried in float32 between chunks. The decode step
is float32 elementwise throughout.

Params are one pytree with every layer stacked on a leading axis
(`layers`); `run_layers` is one rolled `lax.scan`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.afmoe import _dense, _layer_at, logits_of, make_swiglu, swiglu  # noqa: F401
from ray_tpu.models.llama import _qkv
from ray_tpu.models.paged import rows_a_piece
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

F32 = jnp.float32
# scopes of a device trace (benchmark/brumby_spans.py reads them), inside the
# macro-step's admit_prefill / decode_chunk
SCOPE_PROJ, SCOPE_SCAN, SCOPE_UPDATE = "retention_proj", "retention_scan", "retention_update"
# rows of a KV head's state behind the d value rows: z, then zeros up to a
# whole group of eight sublanes
STATE_PAD = 8
# tokens one step of `retention_chunked`'s loop takes (rows a piece x chunk,
# one row at the least): a step's float32 squared scores are n_heads x chunk
# numbers a token (0.67 GB for ONE row at the published heads and a chunk of
# 2,048) and behind a sequence's first chunk phi(q) is n_heads x phi's width
# numbers a token in `cfg.dtype` (0.67 MB). A longer admission walks its rows
# in pieces (rows are independent sequences), the block's FFN with them.
SCAN_TOKENS = 1024


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """The source's fields under this repo's names; the defaults are
    Brumby-14B-Base's published values. Nothing is derived from another
    width."""
    vocab_size: int = 151936
    d_model: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 17408
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    ret_eps: float = 1e-6                 # the normaliser's eps
    ret_chunk: int = 2048                 # `retention_chunked`'s chunk (a system's choice)
    max_seq_len: int = 32768
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("a KV head serves a whole number of query heads")
        if self.head_dim % 2:
            raise ValueError("rotary pairs and phi's rows want an even head size")

    @property
    def phi_dim(self) -> int:
        """Columns of `phi`: (d/2 + 1) rows of d for d (d + 1) / 2 products."""
        return (self.head_dim // 2 + 1) * self.head_dim

    @property
    def state_rows(self) -> int:
        return self.head_dim + STATE_PAD

    @property
    def model_module(self):
        from ray_tpu.models import brumby

        return brumby

    @property
    def decode_module(self):
        from ray_tpu.models import brumby_decode

        return brumby_decode

    @staticmethod
    def tiny(**kw) -> "BrumbyConfig":
        """Test-sized, with the real shape of things: three layers, two query
        heads a KV head, 16-wide heads (phi: 136 products in 144 columns), a
        chunk shorter than a prompt."""
        return BrumbyConfig(**{**dict(
            vocab_size=512, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, ret_chunk=8, max_seq_len=256), **kw})


# ------------------------------------------------------------------- params
def make_layer(k, cfg: BrumbyConfig) -> Dict[str, Any]:
    """One layer: the mixer's four matrices, head norms and gate (`wg`
    (d, KV heads) and its bias stay out of the wide matrices: a minor axis of
    8), and the SwiGLU. The gate's bias is drawn so that a state's memory is
    some hundreds to thousands of positions: g = sigmoid(b), 1 / (1 - g)
    log-uniform in [64, 4096]."""
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 7)
    horizon = jnp.exp(jax.random.uniform(ks[5], (kvh,), F32, jnp.log(64.0), jnp.log(4096.0)))
    return {
        "attn_norm": jnp.ones((d,), cfg.dtype), "mlp_norm": jnp.ones((d,), cfg.dtype),
        "q_norm": jnp.ones((hd,), cfg.dtype), "k_norm": jnp.ones((hd,), cfg.dtype),
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wo": _dense(ks[3], (h * hd, d), h * hd, cfg.dtype),
        "wg": _dense(ks[4], (d, kvh), d, cfg.dtype),
        "bg": jnp.log(horizon - 1.0),
        **make_swiglu(ks[6], d, cfg.d_ff, cfg.dtype),
    }


def part_keys(key, cfg: BrumbyConfig):
    """(embedding key, head key, one key a layer)."""
    k_embed, k_head, k_l = jax.random.split(key, 3)
    return k_embed, k_head, jax.random.split(k_l, cfg.n_layers)


def init_params(key, cfg: BrumbyConfig) -> Dict[str, Any]:
    k_embed, k_head, k_l = part_keys(key, cfg)
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype),
        "layers": jax.vmap(functools.partial(make_layer, cfg=cfg))(k_l),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": _dense(k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, cfg.dtype),
    }


def num_params(cfg: BrumbyConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# --------------------------------------------------------- power retention
def phi(x):
    """The symmetric square of x (..., d), d even, as (d/2 + 1) d columns
    with phi(x) . phi(y) = (x . y)^2 (this module's text says which product
    lies where). In x's type; float32 everywhere but as a product's operand."""
    d = x.shape[-1]
    xx = jnp.concatenate([x, x], axis=-1)
    return jnp.concatenate(
        [(x if s in (0, d // 2) else x * 2.0 ** 0.5) * xx[..., s:s + d] for s in range(d // 2 + 1)],
        axis=-1)


def extended(v, real=None):
    """A value row behind its one: v (..., d) -> [v | 1 | 0..] (..., d +
    STATE_PAD), all zeros where `real` (v's leading shape) is false, so that a
    position that is padding adds nothing to S nor to z."""
    one = jnp.zeros(v.shape[:-1] + (STATE_PAD,), v.dtype).at[..., 0].set(1)
    vx = jnp.concatenate([v, one], axis=-1)
    return vx if real is None else jnp.where(real[..., None], vx, 0)


def normalised(ox, eps: float):
    """[numerator | denominator | ..] (..., d + STATE_PAD) float32 -> o (..., d)."""
    d = ox.shape[-1] - STATE_PAD
    return ox[..., :d] / (ox[..., d:d + 1] + eps)


def retention_chunked(q, k, v, log_g, lengths, chunk: int, eps: float):
    """Power retention over whole right-padded sequences in its chunked form,
    from a zero state. q (R, T, H, d) times d^-0.5 already, k and v (R, T, KV,
    d), H a multiple of KV; log_g (R, T, KV) float32 <= 0; lengths (R,): past
    a row's length log g = 0 and the position adds nothing, so the state
    stands. Products take operands of v's type and accumulate in float32;
    the state is carried in float32. Returns (o (R, T, H, d) in v's type,
    meaningless past a row's length; the final state (R, KV, d + STATE_PAD,
    phi's width) float32)."""
    R, T, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    C = min(chunk, T)
    nc = -(-T // C)
    mm = v.dtype
    real = jnp.arange(T)[None, :] < lengths[:, None]
    log_g = jnp.where(real[..., None], log_g, 0.0)
    vx = extended(v, jnp.broadcast_to(real[..., None], v.shape[:-1]))
    pad = nc * C - T
    if pad:
        q, k, vx, log_g = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                           for x in (q, k, vx, log_g))

    def chunks(x, heads: int = 1):
        """(R, T, `heads` axes of heads, ..) -> (nc, R, heads.., C, ..):
        chunk-major for the loop, head-major for the products."""
        x = x.reshape(R, nc, C, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 2 + heads), 1, 0)

    lower = jnp.tril(jnp.ones((C, C), bool))

    def product(spec, a, b):
        return jnp.einsum(spec, a.astype(mm), b.astype(mm), preferred_element_type=F32)

    def step(S, inp):
        """One chunk. S None: the sequence's first, which enters with nothing
        (no query of a state, so no phi(q) at all)."""
        qc, kc, vc, gc = inp  # (R,KV,G,C,d) (R,KV,C,d) (R,KV,C,d+pad) (R,KV,C)
        b = jnp.cumsum(gc, axis=-1)                                      # <= 0 and falling
        B = b[..., -1:]
        decay = jnp.exp(jnp.where(lower, b[..., :, None] - b[..., None, :], -jnp.inf))
        s = product("rkgid,rkjd->rkgij", qc, kc)
        ox = product("rkgij,rkje->rkgie", s * s * decay[:, :, None], vc)
        leaving = vc.astype(F32) * jnp.exp(B - b)[..., None]             # exp(B - b_j) [v | 1]
        new = product("rkje,rkjw->rkew", leaving, phi(kc.astype(F32)))
        if S is not None:
            before = product("rkgiw,rkew->rkgie", phi(qc.astype(F32)), S)
            ox = ox + jnp.exp(b)[:, :, None, :, None] * before
            new = jnp.exp(B)[..., None] * S + new
        return new, normalised(ox, eps).astype(mm)

    xs = (chunks(q.reshape(R, nc * C, KV, G, d), 2), chunks(k), chunks(vx), chunks(log_g))
    S, o = step(None, tuple(x[0] for x in xs))
    o = o[None]
    if nc > 1:
        S, rest = jax.lax.scan(step, S, tuple(x[1:] for x in xs))
        o = jnp.concatenate([o, rest], axis=0)
    # (nc,R,KV,G,C,d) -> (R,T,H,d)
    return jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(R, nc * C, H, d)[:, :T], S


def retention_step(S, q, k, v, g, eps: float):
    """Power retention for one position: the definition, the path off the TPU
    and the tests' oracle (`retention_step_stacked` is what a decode step
    calls). S (R, KV, d + STATE_PAD, phi's width) float32; q (R, H, d) times
    d^-0.5 already; k, v (R, KV, d); g (R, KV) float32, the decay. float32
    throughout (`highest`: a TPU's default float32 product rounds its
    operands to bfloat16). Returns (o (R, H, d) float32, new state)."""
    R, H, d = q.shape
    KV = k.shape[1]
    S = (g[..., None, None] * S
         + extended(v.astype(F32))[..., :, None] * phi(k.astype(F32))[..., None, :])
    ox = jnp.einsum("rkgw,rkew->rkge", phi(q.astype(F32)).reshape(R, KV, H // KV, -1), S,
                    precision=jax.lax.Precision.HIGHEST)
    return normalised(ox, eps).reshape(R, H, d), S


def retention_step_stacked(state, li, live, q, k, v, g, eps: float):
    """`retention_step` on layer `li` of the cache's stacked state (layers, R,
    KV, d + STATE_PAD, phi's width), for the rows that are live (`live` is
    their `granite_hybrid.live_rows`); a row that is not live and every other
    layer stay bit for bit. On a TPU, for shapes its tiles take,
    ops/retention_update.py: one pass over each live row; elsewhere
    `retention_step` on the layer, a select and the write. Returns (o (R, H,
    d) float32, meaningless on a row that is not live; the stack)."""
    from ray_tpu.ops import retention_update  # Pallas: imported where it is traced

    if retention_update.engages(*state.shape[3:], q.shape[1] // k.shape[1]):
        return retention_update.update_stacked_state(state, li, live, q, k, v, g, eps)
    S = jax.lax.dynamic_index_in_dim(state, li, 0, keepdims=False)
    o, new_S = retention_step(S, q, k, v, g, eps)
    new_S = jnp.where(live[0][:, None, None, None], new_S, S)
    return o, jax.lax.dynamic_update_index_in_dim(state, new_S, li, 0)


# ------------------------------------------------------------------ the mixer
def rope_tables(cfg: BrumbyConfig, span: int):
    return rope_frequencies(cfg.head_dim, span, cfg.rope_theta)


def project(layer, a, cos, sin, positions, cfg: BrumbyConfig):
    """a (R, T, d_model) at `positions` (R, T) or None (0..T-1) -> q (R, T, H,
    d) normed, rotated and times d^-0.5; k (R, T, KV, d) normed and rotated;
    v (R, T, KV, d); log g (R, T, KV) float32. The three products are
    llama._qkv's, for its reason."""
    q, k, v = _qkv(a, layer, cfg)
    # the scale rides the rotation's float32 tables: one rounding of q, not two
    scale = cfg.head_dim ** -0.5
    q = apply_rope(rms_norm(q, layer["q_norm"], cfg.rms_eps), cos * scale, sin * scale, positions)
    k = apply_rope(rms_norm(k, layer["k_norm"], cfg.rms_eps), cos, sin, positions)
    gate = jnp.einsum("...d,dk->...k", a, layer["wg"], preferred_element_type=F32)
    return q, k, v, jax.nn.log_sigmoid(gate + layer["bg"])


def retention_sequence(layer, a, lengths, cos, sin, cfg: BrumbyConfig):
    """The mixer over whole right-padded rows a (R, T, d_model) from a zero
    state. Returns (out (R, T, d_model), final state (R, KV, d + STATE_PAD,
    phi's width) float32)."""
    R, T, _ = a.shape
    with jax.named_scope(SCOPE_PROJ):
        q, k, v, log_g = project(layer, a, cos, sin, None, cfg)
    with jax.named_scope(SCOPE_SCAN):
        o, S = retention_chunked(q, k, v, log_g, lengths, cfg.ret_chunk, cfg.ret_eps)
    with jax.named_scope(SCOPE_PROJ):
        return o.reshape(R, T, -1) @ layer["wo"], S


def retention_token(layer, li, a, pos, state, live, cos, sin, cfg: BrumbyConfig):
    """The mixer for one position of each row: a (R, d_model) at positions
    `pos` (R,), the stacked state of all layers, of which this is layer `li`,
    and the rows' `live_rows`. Returns (out (R, d_model), the stack with the
    live rows' states stepped)."""
    R = a.shape[0]
    with jax.named_scope(SCOPE_PROJ):
        q, k, v, log_g = project(layer, a[:, None, :], cos, sin, pos[:, None], cfg)
    with jax.named_scope(SCOPE_UPDATE):
        o, state = retention_step_stacked(state, li, live, q[:, 0], k[:, 0], v[:, 0],
                                          jnp.exp(log_g[:, 0]), cfg.ret_eps)
    with jax.named_scope(SCOPE_PROJ):
        return o.astype(cfg.dtype).reshape(R, -1) @ layer["wo"], state


# ----------------------------------------------------------- the layer loop
def rows_of_a_step(R: int, T: int, cfg: BrumbyConfig) -> int:
    """Rows of R rows of T positions that one pass of a layer takes: what
    `SCAN_TOKENS` allows a step of the chunked form, a divisor of R."""
    return rows_a_piece(R, min(cfg.ret_chunk, T), SCAN_TOKENS)


def over_row_pieces(fn: Callable, carry, n: int, *rows):
    """`carry, out = fn(carry, *piece)` over pieces of n rows of `rows`
    (arrays with a leading R, n a divisor of it) in order; the outs side by
    side again."""
    R = rows[0].shape[0]
    if n == R:
        return fn(carry, *rows)
    carry, out = jax.lax.scan(lambda c, piece: fn(c, *piece), carry,
                              tuple(r.reshape(R // n, n, *r.shape[1:]) for r in rows))
    return carry, out.reshape(R, *out.shape[2:])


def run_layers(params, x, carry, cfg: BrumbyConfig, mixer: Callable):
    """x (..., d_model) through every layer in order, one rolled scan.
    `mixer(layer, index, x, ffn, carry) -> (y, carry)` is the whole block,
    handed `ffn(h) = h + SwiGLU(N2 h)`: the full forward and the admission
    walk their rows in pieces, mixer and FFN together, the decode step takes
    all lanes at once."""
    def body(c, i):
        x, carry = c
        layer = _layer_at(params["layers"], i)

        def ffn(h):
            return h + swiglu(rms_norm(h, layer["mlp_norm"], cfg.rms_eps), layer, cfg)

        return mixer(layer, i, x, ffn, carry), None

    (x, carry), _ = jax.lax.scan(body, (x, carry), jnp.arange(cfg.n_layers))
    return x, carry


def sequence_block(layer, x, lengths, cos, sin, ffn, cfg: BrumbyConfig):
    """One layer over whole rows x (R, T, d_model) -> (y, the rows' final
    states)."""
    o, S = retention_sequence(layer, rms_norm(x, layer["attn_norm"], cfg.rms_eps), lengths,
                              cos, sin, cfg)
    return ffn(x + o), S


def embed_tokens(params, tokens, cfg: BrumbyConfig):
    return params["embed"][tokens].astype(cfg.dtype)


# ------------------------------------------------------------ full forward
def forward(params, tokens, cfg: BrumbyConfig, lengths=None):
    """Logits (R, T, V) float32 of right-padded token rows (R, T): the
    whole-sequence pass, no cache. Positions past `lengths` (default: all
    real) hold nothing meaningful."""
    R, T = tokens.shape
    lengths = jnp.full((R,), T, jnp.int32) if lengths is None else lengths
    cos, sin = rope_tables(cfg, T)

    def mixer(layer, _, x, ffn, carry):
        def piece(c, x, lengths):
            return c, sequence_block(layer, x, lengths, cos, sin, ffn, cfg)[0]

        return over_row_pieces(piece, carry, rows_of_a_step(R, T, cfg), x, lengths)[::-1]

    x, _ = run_layers(params, embed_tokens(params, tokens, cfg), (), cfg, mixer)
    return logits_of(params, x, cfg)
