"""Phi-4-mini-flash-reasoning's decoder (HF `phi4flash`): SambaY, a
decoder-hybrid-decoder (arXiv:2507.06607), with Differential Attention
(arXiv:2410.05258) in every attention layer.

Every layer is `x += mixer(LN1(x)); x += MLP(LN2(x))` with `LayerNorm` (weight
and bias) and `MLP(a) = fc2(u * silu(g))`, `[u | g] = fc1(a)`, no bias. There
is no position term anywhere. The mixer by the layer's index `l` of
`n_layers` (`half = n_layers // 2`; the source's `mb_per_layer` is 2):

- **Mamba-1**, even `l <= half`: `[x | z] = in_proj(a)`; a causal depthwise
  conv of `mamba_d_conv` taps with bias, then SiLU, over `x`; `[dt_r | B | C] =
  x_proj(x)`; `dt = softplus(dt_proj(dt_r) + dt_bias)`; `A = -exp(A_log)`, ONE
  NUMBER A CHANNEL AND STATE COLUMN; `h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t)
  B_t^T`; `y_t = h_t C_t + D x_t`; out `out_proj(y * silu(z))`. `selective_scan`
  is the recurrence over whole rows, `s6_step` for one position. Layer `half`
  also hands on `m_t = y_t` (before the gate): the memory of the gated memory
  units.
- **Differential attention**, odd `l`: 2 x `n_heads / 2` query heads and 2 x
  `n_kv_heads / 2` key heads in PAIRS; query head (pair j, part i) scores key
  head (pair g = j // (pairs of queries a pair of keys), part i), and both
  parts weigh the SAME value, the pair's two value heads side by side (2 x
  head_dim wide): `a_i = softmax(q_ji k_gi^T / sqrt(head_dim)) [v_g0 | v_g1]`,
  `o_j = RMSNorm(a_0 - lambda a_1) (1 - lambda_init(l))`, `lambda = exp(lq1 .
  lk1) - exp(lq2 . lk2) + lambda_init(l)`, `lambda_init(l) = 0.8 - 0.6
  exp(-0.3 l)`. `diff_attention` owns that map, lambda, the norm and the
  scale; the scores and the product with the values are whoever's it is
  handed (`attend`: the flash forward, a ring, the block pool). For odd `l <
  half` the mask is causal with `0 <= i - j < sliding_window`; layer `half + 1`
  is causal and full and its K/V are THE cache: for odd `l > half + 1` the
  layer projects a query only and reads layer `half + 1`'s keys and values.
- **Gated memory unit**, even `l > half`: `out_proj(silu(in_proj(a)) * m)`.

Layers `0 .. half + 1` are the SELF-DECODER, the rest the CROSS-DECODER. What a
cross-decoder layer computes at a position is read by nothing but that
position's own logits, so a prefill runs it at each row's last position only
(models/phi4flash_decode.py); `forward` here runs everything everywhere.

How the pair map is computed: a query head's vector is laid into ITS HALF of
a row 2 x head_dim wide, zeros in the other (`pair_queries`), and keys and
values are read as `n_kv_heads / 2` heads of 2 x head_dim, `[k_g0 | k_g1]` and
`[v_g0 | v_g1]`: as they lie in `Wqkv`'s output, in the pool and in a ring.
Then plain grouped-query attention (query head q reads KV head q // 4) IS the
map, the zeros pick the key part, and no key or value is copied or permuted.
The score product is 2 x head_dim deep, which on a matrix unit 128 deep costs
what head_dim = 64 costs.

Precision: weights and activations in `cfg.dtype`; `dt`, the decays, the
recurrence and the SSM state in float32; matrix products take `cfg.dtype`
operands and accumulate in float32; norms, softmax, the difference of the two
attentions and logits in float32.

Params are one pytree with a stacked leading axis per KIND of layer (`mamba`,
`attn`: the window layers then the full one, `gmu`, `cross`) and one over all
layers for the MLPs; the layer walk is two rolled `lax.scan`s over PAIRS of
layers (Mamba + window, memory unit + cross) around the two layers between
them. TPU layouts, the same numbers: `x_proj` is kept transposed (dt_rank + 2
N, d_inner) and `A_log` (N, d_inner), the state (N, d_inner): a minor axis of
16 or 192 would be padded to 128 or 256.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.granite_hybrid import _dense, _layer_at, causal_conv, conv_step
from ray_tpu.ops.normalization import layer_norm, rms_norm

F32 = jnp.float32
MAMBA, ATTN, GMU, CROSS, MLP = "mamba", "attn", "gmu", "cross", "mlp"
# scopes of a device trace (benchmark/phi4flash_spans.py reads them); they lie
# inside the macro-step's admit_prefill / decode_chunk and name neither, and
# none is part of a scope another model uses
SCOPE_SCAN, SCOPE_UPDATE, SCOPE_PROJ, SCOPE_WINDOW, SCOPE_FULL, SCOPE_CROSS, SCOPE_GMU = (
    "s6_scan", "s6_update", "s6_proj", "diff_window", "diff_full", "cross_attn", "gmu")
# positions a step of `selective_scan`'s loop runs in straight-line code: what
# is live at once is a chunk's x, dt, B, C and y beside the carried state, and
# the loop's trip count is the admission's positions over this. The one value
# run on the chip (PR 49; `kernels.s6_scan_roofline_pct` reads 3.8 there): no
# sweep stands behind it
SCAN_CHUNK = 16


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The source's fields under this repo's names; the defaults are
    Phi-4-mini-flash-reasoning's published values (what its `config.json`
    does not say is the source's `Phi4FlashConfig` default or its modeling
    file's, listed under `assumed` in the benchmark's configuration file)."""
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    d_ff: int = 10240
    mb_per_layer: int = 2
    sliding_window: int = 512
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160          # ceil(d_model / 16)
    layer_norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError(f"n_layers must be a multiple of 4 and at least 8, got {self.n_layers}")
        if self.mb_per_layer != 2:
            raise ValueError("mb_per_layer must be 2: every second layer a Mamba or memory layer")
        if self.n_heads % 2 or self.n_kv_heads % 2 or (self.n_heads // 2) % (self.n_kv_heads // 2):
            raise ValueError("differential attention pairs its heads: n_heads and n_kv_heads are "
                             "even and the query pairs a multiple of the key pairs")

    @property
    def half(self) -> int:
        return self.n_layers // 2

    @property
    def n_window_layers(self) -> int:
        """The odd layers under `half`: as many as the rolled self-decoder pairs."""
        return self.half // 2

    @property
    def n_mamba_layers(self) -> int:
        return self.half // 2 + 1

    @property
    def n_cross_layers(self) -> int:
        """The odd layers past `half + 1`, each behind a gated memory unit."""
        return (self.n_layers - self.half - 2) // 2

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def kv_row(self) -> int:
        """Columns of a position's keys (or values) in a ring or the pool."""
        return self.n_kv_heads * self.head_dim

    @property
    def model_module(self):
        from ray_tpu.models import phi4flash

        return phi4flash

    @property
    def decode_module(self):
        from ray_tpu.models import phi4flash_decode

        return phi4flash_decode

    @staticmethod
    def tiny(**kw) -> "Phi4FlashConfig":
        """Test-sized, with every kind of layer and both rolled walks more
        than once: 12 layers = 3 x (Mamba, window), the memory's Mamba layer,
        the full layer, 2 x (memory unit, cross); two query pairs to a key
        pair as published; a window shorter than the tests' prompts."""
        return Phi4FlashConfig(**{**dict(
            vocab_size=512, d_model=64, n_layers=12, n_heads=8, n_kv_heads=4, head_dim=8,
            d_ff=96, sliding_window=8, mamba_d_state=4, mamba_dt_rank=4,
            max_seq_len=256), **kw})


def lambda_init(l):
    """0.8 - 0.6 exp(-0.3 l) for the layer's index `l` (a number or a traced one)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, F32))


# ------------------------------------------------------------------- params
def _norm(cfg):
    return {"norm_w": jnp.ones((cfg.d_model,), cfg.dtype), "norm_b": jnp.zeros((cfg.d_model,), cfg.dtype)}


def make_mamba_layer(k, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """One Mamba-1 layer, initialised as Mamba does: `dt_bias` the inverse
    softplus of a log-uniform step in [1e-3, 1e-1], `A_log = log(1 .. N)` in
    every channel, `D` 1, `dt_proj` uniform in +-dt_rank^-0.5, conv weights and
    bias uniform in +-(taps)^-0.5."""
    d, di, N, K, r = cfg.d_model, cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    ks = jax.random.split(k, 7)
    dt = jnp.exp(jax.random.uniform(ks[4], (di,), F32, math.log(1e-3), math.log(1e-1)))
    u = lambda key, shape, lim: jax.random.uniform(key, shape, F32, -lim, lim)  # noqa: E731
    return {
        **_norm(cfg),
        "in_proj": _dense(ks[0], (d, 2 * di), d, cfg.dtype),               # [x | z]
        "conv_w": u(ks[1], (K, di), K ** -0.5).astype(cfg.dtype),
        "conv_b": u(ks[2], (di,), K ** -0.5).astype(cfg.dtype),
        "x_proj": _dense(ks[3], (r + 2 * N, di), di, cfg.dtype),           # [dt_r | B | C], transposed
        "dt_proj": u(ks[5], (r, di), r ** -0.5).astype(cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=F32))[:, None], (N, di)),
        "D": jnp.ones((di,), F32),
        "out_proj": _dense(ks[6], (di, d), di, cfg.dtype),
    }


def _diff_params(ks, cfg):
    hd = cfg.head_dim
    return {name: 0.1 * jax.random.normal(k, (hd,), F32)
            for name, k in zip(("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"), ks)} | {
        "subln": jnp.ones((2 * hd,), cfg.dtype)}


def make_attn_layer(k, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """A window or the full layer: `Wqkv` (queries, then keys, then values,
    as the source splits it) and `out_proj`, both with bias."""
    d, hq, hkv = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.kv_row
    ks = jax.random.split(k, 8)
    return {
        **_norm(cfg),
        "wqkv": _dense(ks[0], (d, hq + 2 * hkv), d, cfg.dtype),
        "bqkv": (0.02 * jax.random.normal(ks[1], (hq + 2 * hkv,), F32)).astype(cfg.dtype),
        "wo": _dense(ks[2], (hq, d), hq, cfg.dtype),
        "bo": (0.02 * jax.random.normal(ks[3], (d,), F32)).astype(cfg.dtype),
        **_diff_params(ks[4:], cfg),
    }


def make_cross_layer(k, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """A cross-decoder attention layer: a query projection only."""
    d, hq = cfg.d_model, cfg.n_heads * cfg.head_dim
    ks = jax.random.split(k, 8)
    return {
        **_norm(cfg),
        "wq": _dense(ks[0], (d, hq), d, cfg.dtype),
        "bq": (0.02 * jax.random.normal(ks[1], (hq,), F32)).astype(cfg.dtype),
        "wo": _dense(ks[2], (hq, d), hq, cfg.dtype),
        "bo": (0.02 * jax.random.normal(ks[3], (d,), F32)).astype(cfg.dtype),
        **_diff_params(ks[4:], cfg),
    }


def make_gmu_layer(k, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    k_in, k_out = jax.random.split(k)
    return {
        **_norm(cfg),
        "in_proj": _dense(k_in, (cfg.d_model, cfg.d_inner), cfg.d_model, cfg.dtype),
        "out_proj": _dense(k_out, (cfg.d_inner, cfg.d_model), cfg.d_inner, cfg.dtype),
    }


def make_mlp(k, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    k_in, k_out = jax.random.split(k)
    return {
        **_norm(cfg),
        "fc1": _dense(k_in, (d, 2 * f), d, cfg.dtype),  # [u | g]
        "fc2": _dense(k_out, (f, d), f, cfg.dtype),
    }


def init_params(key, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """The params pytree: one stacked leading axis per kind of layer."""
    k_embed, k_m, k_a, k_g, k_c, k_f = jax.random.split(key, 6)
    stack = lambda make, k, n: jax.vmap(functools.partial(make, cfg=cfg))(jax.random.split(k, n))  # noqa: E731
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype),
        MAMBA: stack(make_mamba_layer, k_m, cfg.n_mamba_layers),
        ATTN: stack(make_attn_layer, k_a, cfg.n_window_layers + 1),
        GMU: stack(make_gmu_layer, k_g, cfg.n_cross_layers),
        CROSS: stack(make_cross_layer, k_c, cfg.n_cross_layers),
        MLP: stack(make_mlp, k_f, cfg.n_layers),
        "final_norm_w": jnp.ones((cfg.d_model,), cfg.dtype),
        "final_norm_b": jnp.zeros((cfg.d_model,), cfg.dtype),
    }


def num_params(cfg: Phi4FlashConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# ------------------------------------------------------------ layer pieces
def _ln(x, p, cfg: Phi4FlashConfig):
    return layer_norm(x, p["norm_w"], p["norm_b"], cfg.layer_norm_eps)


def mlp(a, p, cfg: Phi4FlashConfig):
    u, g = jnp.split(a @ p["fc1"], 2, axis=-1)
    return (u * jax.nn.silu(g.astype(F32)).astype(cfg.dtype)) @ p["fc2"]


def embed_tokens(params, tokens, cfg: Phi4FlashConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def logits_of(params, x, cfg: Phi4FlashConfig):
    """Final LayerNorm and the tied head, float32, for x (..., d)."""
    x = layer_norm(x, params["final_norm_w"], params["final_norm_b"], cfg.layer_norm_eps)
    # operands as they are stored, float32 accumulation: a float32 copy of
    # the matrix (2 GB at 200k x 2560) would be made in every decode step
    return jnp.einsum("...d,vd->...v", x, params["embed"], preferred_element_type=F32)


def _layer(params, kind, ki, li, x, carry, mix, cfg: Phi4FlashConfig):
    """x through layer `li`, the `ki`-th of its kind: `mix(layer, ki, li,
    normed x, carry) -> (mixer output, what else the mixer hands on, carry)`,
    then the MLP. The block around the mixer is the same for every caller."""
    p, ff = _layer_at(params[kind], ki), _layer_at(params[MLP], li)
    o, extra, carry = mix(p, ki, li, _ln(x, p, cfg), carry)
    x = x + o.astype(x.dtype)
    x = x + mlp(_ln(x, ff, cfg), ff, cfg).astype(x.dtype)
    return x, extra, carry


def self_decoder(params, x, carry, cfg: Phi4FlashConfig, mamba: Callable, window: Callable,
                 full: Callable):
    """Layers 0 .. half + 1: the (Mamba, window) pairs as one rolled scan,
    then the memory's Mamba layer and the full layer. `mamba(layer, mi, li, a,
    carry) -> (out, y, carry)`; `window` likewise with None for y, `full` with
    what it hands on to the cross-decoder (its keys and values, or None).
    Returns (x, m = layer `half`'s y, what `full` handed on, carry)."""
    def pair(c, i):
        x, carry = c
        x, _, carry = _layer(params, MAMBA, i, 2 * i, x, carry, mamba, cfg)
        x, _, carry = _layer(params, ATTN, i, 2 * i + 1, x, carry, window, cfg)
        return (x, carry), None

    n = cfg.n_window_layers
    (x, carry), _ = jax.lax.scan(pair, (x, carry), jnp.arange(n))
    x, m, carry = _layer(params, MAMBA, n, cfg.half, x, carry, mamba, cfg)
    x, kv, carry = _layer(params, ATTN, n, cfg.half + 1, x, carry, full, cfg)
    return x, m, kv, carry


def cross_decoder(params, x, m, cfg: Phi4FlashConfig, attend: Callable):
    """Layers half + 2 .. : (memory unit, cross attention) pairs as one
    rolled scan. `m` is the memory at x's positions; `attend(q (.., h, 2 hd))`
    the attention over layer `half + 1`'s keys and values, as
    `diff_attention` takes it."""
    def unit(layer, ki, li, a, carry):
        with jax.named_scope(SCOPE_GMU):
            return gmu(layer, a, m, cfg), None, carry

    def cross(layer, ki, li, a, carry):
        with jax.named_scope(SCOPE_CROSS):
            q = (a @ layer["wq"] + layer["bq"]).reshape(*a.shape[:-1], cfg.n_heads, cfg.head_dim)
            return diff_attention(layer, q, attend, li, cfg) @ layer["wo"] + layer["bo"], None, carry

    def pair(x, i):
        li = cfg.half + 2 + 2 * i
        x, _, _ = _layer(params, GMU, i, li, x, (), unit, cfg)
        x, _, _ = _layer(params, CROSS, i, li + 1, x, (), cross, cfg)
        return x, None

    x, _ = jax.lax.scan(pair, x, jnp.arange(cfg.n_cross_layers))
    return x


# -------------------------------------------------------------- Mamba mixer
def s6_inputs(x, layer, cfg: Phi4FlashConfig):
    """x (..., d_inner) after the conv -> (dt (..., d_inner) float32, B, C
    (..., N)): `x_proj`, then `softplus(dt_proj(dt_r) + dt_bias)`."""
    r, N = cfg.mamba_dt_rank, cfg.mamba_d_state
    dbc = jnp.einsum("...c,kc->...k", x, layer["x_proj"])
    dt = jax.nn.softplus((dbc[..., :r] @ layer["dt_proj"]).astype(F32) + layer["dt_bias"])
    return dt, dbc[..., r:r + N], dbc[..., r + N:]


def s6_step(h, x, dt, A, B, C, D):
    """The recurrence for one position: the definition, the path off the TPU
    and the tests' oracle. h (R, N, c) float32; x (R, c); dt (R, c) float32; A
    (N, c) negative float32; B, C (R, N); D (c,). Elementwise in float32
    throughout. Returns (y (R, c) float32, new state)."""
    xf = x.astype(F32)
    h = (jnp.exp(dt[:, None, :] * A) * h
         + (dt * xf)[:, None, :] * B.astype(F32)[:, :, None])
    return jnp.sum(h * C.astype(F32)[:, :, None], axis=1) + D * xf, h


def selective_scan(x, dt, A, B, C, D, chunk: int = SCAN_CHUNK):
    """The recurrence over whole rows from a zero state. x (R, T, c); dt (R,
    T, c) float32 step sizes, 0 where a position is padding (decay 1, input 0:
    the state stands still); A (N, c); B, C (R, T, N); D (c,). A loop over
    chunks of `chunk` positions that carries the state (R, N, c) float32 and
    writes a chunk's y; inside a chunk the positions run in straight-line
    code, each `s6_step`, so that nothing of (positions, N, c) is ever held.
    Returns (y (R, T, c) in x's type, final state (R, N, c) float32)."""
    R, T, c = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (x, dt, B, C))

    def step(i, carry):
        h, y = carry
        xc, dtc, Bc, Cc = (jax.lax.dynamic_slice_in_dim(a, i * Q, Q, axis=1) for a in (x, dt, B, C))
        ys = []
        for t in range(Q):
            y_t, h = s6_step(h, xc[:, t], dtc[:, t], A, Bc[:, t], Cc[:, t], D)
            ys.append(y_t.astype(x.dtype))
        return h, jax.lax.dynamic_update_slice_in_dim(y, jnp.stack(ys, axis=1), i * Q, axis=1)

    h, y = jax.lax.fori_loop(0, (T + pad) // Q, step,
                             (jnp.zeros((R, A.shape[0], c), F32), jnp.zeros_like(x)))
    return y[:, :T], h


def mamba_sequence(layer, a, lengths, cfg: Phi4FlashConfig):
    """The Mamba mixer over whole right-padded rows a (R, T, d) from a zero
    state. Past a row's length the step is frozen and nothing is taken into
    the conv tail. Returns (out (R, T, d), y (R, T, d_inner) before the gate,
    conv tail (R, K-1, d_inner), final state (R, N, d_inner) float32)."""
    T = a.shape[1]
    with jax.named_scope(SCOPE_PROJ):
        x, z = jnp.split(a @ layer["in_proj"], 2, axis=-1)
    with jax.named_scope(SCOPE_SCAN):
        x, tail = causal_conv(x, layer, lengths)
    with jax.named_scope(SCOPE_PROJ):
        dt, B, C = s6_inputs(x, layer, cfg)
    with jax.named_scope(SCOPE_SCAN):
        real = jnp.arange(T)[None, :] < lengths[:, None]
        y, h = selective_scan(x, jnp.where(real[:, :, None], dt, 0.0), -jnp.exp(layer["A_log"]),
                              B, C, layer["D"])
    with jax.named_scope(SCOPE_PROJ):
        out = (y * jax.nn.silu(z.astype(F32)).astype(cfg.dtype)) @ layer["out_proj"]
    return out, y, tail, h


def s6_step_stacked(ssm, mi, live, x, dt, A, B, C, D):
    """`s6_step` on layer `mi` of the cache's stacked state (layers, R, N, c),
    for the rows that are live (`live` is their `granite_hybrid.live_rows`); a
    row that is not live and every other layer stay bit for bit. On a TPU,
    for shapes its tiles take, the kernel of ops/s6_update.py reads each live
    row once and writes it back in place; elsewhere `s6_step` on the layer, a
    select and the write. Returns (y (R, c) float32, meaningless on a row that
    is not live; the stack)."""
    from ray_tpu.ops import s6_update  # Pallas: imported where it is traced

    if s6_update.engages(*ssm.shape[2:]):
        return s6_update.update_stacked_state(ssm, mi, live, x, dt, A, B, C, D)
    h = jax.lax.dynamic_index_in_dim(ssm, mi, 0, keepdims=False)
    y, new_h = s6_step(h, x, dt, A, B, C, D)
    new_h = jnp.where(live[0][:, None, None], new_h, h)
    return y, jax.lax.dynamic_update_index_in_dim(ssm, new_h, mi, 0)


def mamba_token(layer, mi, a, tail, ssm, live, cfg: Phi4FlashConfig):
    """The Mamba mixer for one position of each row: a (R, d), the rows' conv
    tails (K-1, R, d_inner), the stacked state of all Mamba layers, of which
    this is layer `mi`, and the rows' `live_rows`. Returns (out (R, d), y (R,
    d_inner) before the gate, new tails for every row, the stack with the live
    rows' states stepped)."""
    with jax.named_scope(SCOPE_PROJ):
        x, z = jnp.split(a @ layer["in_proj"], 2, axis=-1)
    with jax.named_scope(SCOPE_UPDATE):
        x, tail = conv_step(tail, x, layer)
    with jax.named_scope(SCOPE_PROJ):
        dt, B, C = s6_inputs(x, layer, cfg)
    with jax.named_scope(SCOPE_UPDATE):
        y, ssm = s6_step_stacked(ssm, mi, live, x, dt, -jnp.exp(layer["A_log"]), B, C, layer["D"])
        y = y.astype(cfg.dtype)
    with jax.named_scope(SCOPE_PROJ):
        out = (y * jax.nn.silu(z.astype(F32)).astype(cfg.dtype)) @ layer["out_proj"]
    return out, y, tail, ssm


def gmu(layer, a, m, cfg: Phi4FlashConfig):
    """The gated memory unit: `out_proj(silu(in_proj(a)) * m)`, m the
    memory (layer `half`'s y, before its gate) at a's positions."""
    g = jax.nn.silu((a @ layer["in_proj"]).astype(F32)).astype(cfg.dtype)
    return (g * m) @ layer["out_proj"]


# --------------------------------------------------- differential attention
def qkv(layer, a, cfg: Phi4FlashConfig):
    """a (..., d) -> q (..., h, hd), k and v (..., kv_row) as `Wqkv` lays
    them: n_kv_heads / 2 pairs of 2 x head_dim, the row of a ring or the pool."""
    hq = cfg.n_heads * cfg.head_dim
    y = a @ layer["wqkv"] + layer["bqkv"]
    return (y[..., :hq].reshape(*a.shape[:-1], cfg.n_heads, cfg.head_dim),
            y[..., hq:hq + cfg.kv_row], y[..., hq + cfg.kv_row:])


def pair_queries(q):
    """q (..., h, hd) -> (..., h, 2 hd): head 2 j + i's vector in half i of a
    row as wide as a PAIR of key heads, zeros in the other half, so that its
    product with `[k_g0 | k_g1]` is its score against `k_gi` alone."""
    *lead, h, hd = q.shape
    own = jnp.eye(2, dtype=q.dtype)[:, :, None]                       # (part, half, 1)
    return (q.reshape(*lead, h // 2, 2, 1, hd) * own).reshape(*lead, h, 2 * hd)


def diff_attention(layer, q, attend: Callable, li, cfg: Phi4FlashConfig):
    """Differential attention of layer `li` (its depth, for `lambda_init`):
    q (..., h, hd) as projected, `attend(q (..., h, 2 hd))` -> (..., h, 2 hd)
    the softmax attention of each laid-out query over keys and values read as
    n_kv_heads / 2 heads of 2 x head_dim, grouped-query, scores times
    head_dim^-0.5 (the caller's mask and cache). Returns (..., h * hd): the
    pairs' `RMSNorm(a_0 - lambda a_1) (1 - lambda_init)` side by side,
    `out_proj`'s input."""
    *lead, h, hd = q.shape
    a = attend(pair_queries(q)).astype(F32).reshape(*lead, h // 2, 2, 2 * hd)
    init = lambda_init(li)
    lam = (jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
           - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"])) + init)
    o = rms_norm(a[..., 0, :] - lam * a[..., 1, :], layer["subln"], cfg.layer_norm_eps)
    return (o * (1.0 - init)).reshape(*lead, h * hd).astype(cfg.dtype)


def sequence_attend(k, v, cfg: Phi4FlashConfig, window):
    """`attend` over whole rows: keys and values (R, T, kv_row) of the rows'
    own positions, causal, `window` positions back or all of them (None): the
    flash forward (Pallas on the chip, blockwise XLA elsewhere). A real query
    never sees a right-pad key behind it."""
    from ray_tpu.ops.flash_attention import flash_attention_fwd

    R, T, _ = k.shape
    pairs = (R, T, cfg.n_kv_heads // 2, 2 * cfg.head_dim)

    def attend(q):
        o, _ = flash_attention_fwd(q, k.reshape(pairs), v.reshape(pairs), causal=True,
                                   sm_scale=cfg.head_dim ** -0.5, window=window)
        return o

    return attend


# ------------------------------------------------------------ full forward
def forward(params, tokens, cfg: Phi4FlashConfig, lengths=None):
    """Logits (R, T, V) float32 of right-padded token rows (R, T): the
    whole-sequence pass, no cache, all layers at every position. Positions
    past `lengths` (default: all real) hold nothing meaningful."""
    R, T = tokens.shape
    lengths = jnp.full((R,), T, jnp.int32) if lengths is None else lengths

    def mamba(layer, mi, li, a, carry):
        out, y, _, _ = mamba_sequence(layer, a, lengths, cfg)
        return out, y, carry

    def attention(scope, window):
        def mix(layer, ai, li, a, carry):
            with jax.named_scope(scope):
                q, k, v = qkv(layer, a, cfg)
                o = diff_attention(layer, q, sequence_attend(k, v, cfg, window), li, cfg)
                return o @ layer["wo"] + layer["bo"], (k, v), carry
        return mix

    x, m, (k, v), _ = self_decoder(
        params, embed_tokens(params, tokens, cfg), (), cfg, mamba,
        attention(SCOPE_WINDOW, cfg.sliding_window), attention(SCOPE_FULL, None))
    x = cross_decoder(params, x, m, cfg, sequence_attend(k, v, cfg, None))
    return logits_of(params, x, cfg)
