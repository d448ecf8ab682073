"""Granite-4.0-H style hybrid decoder: Mamba-2 layers with a few attention
layers between them (HF `GraniteMoeHybrid*` with no routed experts, whose
Mamba layer is Bamba's).

Every layer is `x += r * mixer(norm(x)); x += r * mlp(norm(x))` with the
residual multiplier `r`; `layer_types` says which mixer a layer has:

- `mamba`: `[z | xBC | dt] = a @ in_proj`; a depthwise causal conv of
  `mamba_d_conv` taps and SiLU over `xBC`, split into `x` (heads x head
  size), `B` and `C` (one group, shared by all heads); the selective state
  space recurrence `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`,
  `y_t = h_t C_t + D x_t` with `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`
  a scalar a head; `rmsnorm(y * silu(z)) * w` over all features; `out_proj`.
  The recurrence has two forms here: `ssd_chunked` over a whole sequence
  (within a chunk of `mamba_chunk_size` the masked `C B^T` form on the matrix
  unit, between chunks the carried state) and `ssm_step` for one token
  (`ssm_step_stacked` where the rows live in the cache's stacked state: on a
  TPU the Pallas kernel of ops/ssm_update.py, one pass over each live row).
- `attention`: grouped-query attention with NO position term (`nope`),
  scores times `attention_multiplier`.

The ends: `x_0 = embedding_multiplier * E[token]`, a final RMSNorm,
`logits = (x @ E^T) / logits_scaling` with the one tied matrix `E`.

Precision: weights and activations in `cfg.dtype`; `dt`, the decays, the
recurrence's accumulations and the SSM state in float32; matrix products take
`cfg.dtype` operands and accumulate in float32 (so the chunked form rounds
its decay-weighted `C B^T` and the carried state to `cfg.dtype` before the
products with `x` and `C`, as mamba_ssm's kernels do); norms, softmax and
logits in float32 as models/llama.py has them.

Params are one pytree with a stacked leading axis per KIND of layer
(`mamba`, `attention`) and one over all layers for the MLPs; `run_layers`
walks `layer_types` as runs of one kind, each run one `lax.scan` that indexes
the stacks, so a program's size follows the number of runs, not of layers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.normalization import rms_norm

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"
# scopes of a device trace (benchmark/hybrid_spans.py reads them); they lie
# inside the macro-step's admit_prefill / decode_chunk and name neither
SCOPE_SCAN, SCOPE_UPDATE, SCOPE_PROJ, SCOPE_ATTN = (
    "ssm_scan", "ssm_update", "ssm_proj", "attn_mix")

_MICRO_LAYERS = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40))


def runs_of(layer_types) -> Tuple[Tuple[str, int, int, int], ...]:
    """Maximal runs of one kind of layer: (kind, index of the run's first
    layer among its kind, among all layers, layers in the run)."""
    out, seen = [], {}
    for g, kind in enumerate(layer_types):
        if out and out[-1][0] == kind:
            out[-1][3] += 1
        else:
            out.append([kind, seen.get(kind, 0), g, 1])
        seen[kind] = seen.get(kind, 0) + 1
    return tuple(tuple(r) for r in out)


def scan_runs(runs, c, body):
    """`c = body(c, kind, index among its kind, index among all layers)` for
    every layer in order, each of `runs` (`runs_of`) one rolled `lax.scan`:
    a program's size follows the number of runs, not of layers."""
    for kind, k0, g0, n in runs:
        def step(c, i, kind=kind, k0=k0, g0=g0):
            return body(c, kind, k0 + i, g0 + i), None

        c, _ = jax.lax.scan(step, c, jnp.arange(n))
    return c


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The source's fields under this repo's names; the defaults are
    granite-4.0-h-micro's published values. Nothing is derived from another
    width: the attention head size and the Mamba head size are fields of
    their own."""
    vocab_size: int = 100352
    d_model: int = 2048
    layer_types: Tuple[str, ...] = _MICRO_LAYERS
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 8192                      # shared_intermediate_size
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.mamba_n_groups != 1:
            raise ValueError(
                "mamba_n_groups must be 1: B, C and the gated norm are "
                "written for one group shared by all heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_attn_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def runs(self) -> Tuple[Tuple[str, int, int, int], ...]:
        return runs_of(self.layer_types)

    @property
    def model_module(self):
        from ray_tpu.models import granite_hybrid

        return granite_hybrid

    @property
    def decode_module(self):
        from ray_tpu.models import granite_hybrid_decode

        return granite_hybrid_decode

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """Test-sized, with the real shape of things: both kinds of layer,
        grouped-query attention, multipliers that are not 1."""
        return GraniteHybridConfig(**{**dict(
            vocab_size=512, d_model=64, layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA),
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, mamba_n_heads=4,
            mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8,
            embedding_multiplier=3.0, attention_multiplier=0.2,
            residual_multiplier=0.5, logits_scaling=2.0, max_seq_len=256), **kw})


# ------------------------------------------------------------------- params
def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) * (fan_in ** -0.5)).astype(dtype)


def make_mamba_layer(k, cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """One Mamba-2 layer, initialised as Mamba-2 does: `dt_bias` the inverse
    softplus of a log-uniform step in [1e-3, 1e-1], `A_log = log(U[1, 16])`,
    `D` 1, conv weights uniform in +-(taps)^-0.5 and conv bias 0. The conv
    weight is kept (taps, channels): channels on the minor axis. The
    source's one input projection `[z | xBC | dt]` is kept as two matrices,
    `in_proj` for `[z | xBC]` and `dt_proj` for the H columns of `dt`: a
    minor axis that is no multiple of 128 costs a relayout copy of the whole
    stack in every dispatch on a TPU."""
    d, di, H, K = cfg.d_model, cfg.d_inner, cfg.mamba_n_heads, cfg.mamba_d_conv
    ks = jax.random.split(k, 6)
    dt = jnp.exp(jax.random.uniform(ks[2], (H,), F32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "norm": jnp.ones((d,), cfg.dtype),
        "in_proj": _dense(ks[0], (d, di + cfg.conv_dim), d, cfg.dtype),  # [z | xBC]
        "dt_proj": _dense(ks[5], (d, H), d, cfg.dtype),
        "conv_w": jax.random.uniform(
            ks[1], (K, cfg.conv_dim), F32, -(K ** -0.5), K ** -0.5).astype(cfg.dtype),
        "conv_b": jnp.zeros((cfg.conv_dim,), cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), F32, 1.0, 16.0)),
        "D": jnp.ones((H,), F32),
        "gate_norm": jnp.ones((di,), cfg.dtype),
        "out_proj": _dense(ks[4], (di, d), di, cfg.dtype),
    }


def make_attn_layer(k, cfg: GraniteHybridConfig) -> Dict[str, Any]:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 4)
    return {
        "norm": jnp.ones((d,), cfg.dtype),
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wo": _dense(ks[3], (h * hd, d), h * hd, cfg.dtype),
    }


def make_mlp(k, cfg: GraniteHybridConfig) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    k_in, k_out = jax.random.split(k)
    return {
        "norm": jnp.ones((d,), cfg.dtype),
        "w_in": _dense(k_in, (d, 2 * f), d, cfg.dtype),  # [gate | value]
        "w_out": _dense(k_out, (f, d), f, cfg.dtype),
    }


def part_keys(key, cfg: GraniteHybridConfig):
    """(embedding key, one key a Mamba layer, an attention layer, an MLP)."""
    k_embed, k_m, k_a, k_f = jax.random.split(key, 4)
    return (k_embed, jax.random.split(k_m, cfg.n_mamba_layers),
            jax.random.split(k_a, cfg.n_attn_layers), jax.random.split(k_f, cfg.n_layers))


def make_embed(k, cfg: GraniteHybridConfig):
    return _dense(k, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype)


def init_params(key, cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """The params pytree: one stacked leading axis per kind of layer."""
    k_embed, k_m, k_a, k_f = part_keys(key, cfg)
    return {
        "embed": make_embed(k_embed, cfg),
        MAMBA: jax.vmap(functools.partial(make_mamba_layer, cfg=cfg))(k_m),
        ATTENTION: jax.vmap(functools.partial(make_attn_layer, cfg=cfg))(k_a),
        "mlp": jax.vmap(functools.partial(make_mlp, cfg=cfg))(k_f),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }


def num_params(cfg: GraniteHybridConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# ------------------------------------------------------------ layer pieces
def _layer_at(stacked, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stacked)


def mlp(m, p, cfg: GraniteHybridConfig):
    g, v = jnp.split(m @ p["w_in"], 2, axis=-1)
    return (jax.nn.silu(g.astype(F32)).astype(cfg.dtype) * v) @ p["w_out"]


def run_layers(params, x, carry, cfg: GraniteHybridConfig, mixers: Dict[str, Callable]):
    """x through every layer in order. `mixers[kind](layer, index among
    its kind, normed x, carry) -> (mixer output, carry)`; the block around
    the mixer (norms, residual multiplier, MLP) is the same for every
    caller: the full forward, admission and the decode step."""
    r = cfg.residual_multiplier

    def body(c, kind, ki, gi):
        x, carry = c
        layer = _layer_at(params[kind], ki)
        ff = _layer_at(params["mlp"], gi)
        o, carry = mixers[kind](layer, ki, rms_norm(x, layer["norm"], cfg.rms_eps), carry)
        x = x + (r * o).astype(x.dtype)
        x = x + (r * mlp(rms_norm(x, ff["norm"], cfg.rms_eps), ff, cfg)).astype(x.dtype)
        return x, carry

    return scan_runs(cfg.runs, (x, carry), body)


def embed_tokens(params, tokens, cfg: GraniteHybridConfig):
    return (params["embed"][tokens] * cfg.embedding_multiplier).astype(cfg.dtype)


def logits_of(params, x, cfg: GraniteHybridConfig):
    """Final norm and the tied head, float32, for x (..., d)."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    # operands as they are stored, float32 accumulation: a float32 copy of
    # the matrix (0.8 GB at 100k x 2048) would be made in every decode step
    return jnp.einsum("...d,vd->...v", x, params["embed"],
                      preferred_element_type=F32) / cfg.logits_scaling


# -------------------------------------------------------------- Mamba mixer
def in_proj(a, layer, cfg: GraniteHybridConfig):
    """a (..., d) -> z (..., d_inner), xBC (..., conv_dim), dt (..., H)."""
    zx = a @ layer["in_proj"]
    return zx[..., :cfg.d_inner], zx[..., cfg.d_inner:], a @ layer["dt_proj"]


def split_xbc(xBC, cfg: GraniteHybridConfig):
    """(..., conv_dim) -> x (..., H, P), B (..., N), C (..., N)."""
    di, N = cfg.d_inner, cfg.mamba_d_state
    x = xBC[..., :di].reshape(*xBC.shape[:-1], cfg.mamba_n_heads, cfg.mamba_d_head)
    return x, xBC[..., di:di + N], xBC[..., di + N:]


def step_sizes(dt, layer):
    """dt = softplus(dt + dt_bias), float32; no clamp (time_step_limit
    (0, inf))."""
    return jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])


def _conv_bias(layer):
    return layer["conv_b"].astype(F32) if "conv_b" in layer else 0.0


def causal_conv(xBC, layer, lengths):
    """Depthwise causal conv (`conv_w` (K, C), `conv_b` where the layer has
    one) + SiLU over xBC (B, T, C), zeros before the start, and each row's
    conv tail: its last K-1 REAL inputs, positions lengths-K+1 .. lengths-1
    (zeros where the row is shorter)."""
    K, T = layer["conv_w"].shape[0], xBC.shape[1]
    xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    w = layer["conv_w"].astype(F32)
    acc = _conv_bias(layer)
    for j in range(K):
        acc = acc + w[j] * xp[:, j:j + T].astype(F32)
    idx = lengths[:, None] + jnp.arange(K - 1)[None, :]  # into the padded rows
    tail = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return jax.nn.silu(acc).astype(xBC.dtype), tail


def conv_step(tail, xBC, layer):
    """One position: tail (K-1, R, C) the K-1 inputs before it (oldest
    first; rows on the second axis, as the cache keeps them), xBC (R, C)
    -> (conv + SiLU (R, C), the new tail)."""
    window = jnp.concatenate([tail, xBC[None]], axis=0)
    acc = _conv_bias(layer) + jnp.sum(
        layer["conv_w"].astype(F32)[:, None, :] * window.astype(F32), axis=0)
    return jax.nn.silu(acc).astype(xBC.dtype), window[1:]


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """The recurrence over whole sequences in its chunked ("state space
    dual") form. x (R, T, H, P); dt (R, T, H) float32 step sizes, 0 where a
    position is padding (decay 1, input 0: the state stands still); A (H,)
    negative float32; B, C (R, T, N); D (H,). From a zero state. Returns
    (y (R, T, H, P) in x's type, final state (R, H, P, N) float32).

    Within a chunk of Q positions, y_t = sum_{s<=t} (C_t . B_s) *
    exp(cum_t - cum_s) * dt_s * x_s is one masked (Q x Q) matrix a head
    times x; the state at the chunk's end is the decayed old state plus
    sum_s exp(cum_Q - cum_s) dt_s x_s (x) B_s; the old state adds
    exp(cum_t) C_t . h to y_t. cum is the running sum of dt * A."""
    R, T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (T + pad) // Q
    chunks = lambda a: jnp.moveaxis(a.reshape(R, nc, Q, *a.shape[2:]), 1, 0)  # noqa: E731
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    mm = x.dtype

    def step(h, inp):
        xc, dtc, Bc, Cc = inp                       # (R,Q,H,P) (R,Q,H) (R,Q,N) (R,Q,N)
        cum = jnp.cumsum(dtc * A, axis=1)           # (R,Q,H), <= 0 and falling
        cum_h = jnp.moveaxis(cum, 1, 2)             # (R,H,Q)
        diff = cum_h[:, :, :, None] - cum_h[:, :, None, :]          # [t, s]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))          # (R,H,Q,Q)
        cb = jnp.einsum("rtn,rsn->rts", Cc, Bc, preferred_element_type=F32)
        m = cb[:, None] * decay * jnp.moveaxis(dtc, 1, 2)[:, :, None, :]
        y = jnp.einsum("rhts,rshp->rthp", m.astype(mm), xc, preferred_element_type=F32)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "rtn,rhpn->rthp", Cc, h.astype(mm), preferred_element_type=F32)
        to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc                # (R,Q,H)
        xw = (xc.astype(F32) * to_end[..., None]).astype(mm)
        h = jnp.exp(cum[:, -1])[:, :, None, None] * h + jnp.einsum(
            "rshp,rsn->rhpn", xw, Bc, preferred_element_type=F32)
        y = y + D[None, None, :, None] * xc.astype(F32)
        return h, y.astype(mm)

    h, ys = jax.lax.scan(step, jnp.zeros((R, H, P, N), F32),
                         (chunks(x), chunks(dt), chunks(B), chunks(C)))
    y = jnp.moveaxis(ys, 0, 1).reshape(R, nc * Q, H, P)
    return y[:, :T], h


def ssm_step(h, x, dt, A, B, C, D):
    """The recurrence for one position: the definition, the path off the
    TPU and the tests' oracle (`ssm_step_stacked` is what a decode step
    calls). h (R, H, P, N) float32; x (R, H, P); dt (R, H) float32; B, C
    (R, N). Elementwise in float32 throughout (no matrix unit: its float32
    products would round to bfloat16). Returns (y (R, H, P) float32, new
    state)."""
    xf, Bf, Cf = x.astype(F32), B.astype(F32), C.astype(F32)
    h = (jnp.exp(dt * A)[:, :, None, None] * h
         + (dt[:, :, None] * xf)[..., None] * Bf[:, None, None, :])
    y = jnp.sum(h * Cf[:, None, None, :], axis=-1) + D[None, :, None] * xf
    return y, h


def live_rows(active):
    """What `ssm_step_stacked` wants to know of the rows' flags (R,) bool,
    made once a step for all its layers: (the flags, the live rows' indices
    in rising order with the last of them repeated to the end (R,) int32
    (row 0 where none is live), their number (1,) int32)."""
    n_live = jnp.sum(active, dtype=jnp.int32)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n_live - 1, 0)]
    return active, jnp.where(jnp.arange(active.shape[0]) < n_live, order, last), n_live[None]


def ssm_step_stacked(ssm, mi, live, x, dt, A, B, C, D):
    """`ssm_step` on layer `mi` of the cache's stacked state (layers, R, H,
    P, N), for the rows that are live (`live` is their `live_rows`); a row
    that is not live and every other layer stay bit for bit. The path
    follows what can be seen here: on a TPU, for shapes its tiles take, the
    kernel of ops/ssm_update.py reads each live row once, writes it back in
    place and takes `h . C` in the same pass; elsewhere `ssm_step` on the
    layer, a select and the write. Returns (y (R, H, P) float32, meaningless
    on a row that is not live; the stack)."""
    from ray_tpu.ops import ssm_update  # Pallas: imported where it is traced

    if ssm_update.engages(*ssm.shape[2:]):
        return ssm_update.update_stacked_state(ssm, mi, live, x, dt, A, B, C, D)
    h = jax.lax.dynamic_index_in_dim(ssm, mi, 0, keepdims=False)
    y, new_h = ssm_step(h, x, dt, A, B, C, D)
    new_h = jnp.where(live[0][:, None, None, None], new_h, h)
    return y, jax.lax.dynamic_update_index_in_dim(ssm, new_h, mi, 0)


def gated_norm(y, z, layer, cfg: GraniteHybridConfig):
    """rmsnorm(y * silu(z)) * w over all d_inner features, float32 inside."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    return rms_norm(g, layer["gate_norm"], cfg.rms_eps).astype(cfg.dtype)


def mamba_sequence(layer, a, lengths, cfg: GraniteHybridConfig):
    """The Mamba mixer over whole right-padded rows a (R, T, d) from a zero
    state. Past a row's length the step is frozen and nothing is taken into
    the conv tail. Returns (out (R, T, d), conv tail (R, K-1, conv_dim),
    final state (R, H, P, N) float32)."""
    R, T, _ = a.shape
    with jax.named_scope(SCOPE_PROJ):
        z, xBC, dt = in_proj(a, layer, cfg)
    with jax.named_scope(SCOPE_SCAN):
        xBC, tail = causal_conv(xBC, layer, lengths)
        x, B, C = split_xbc(xBC, cfg)
        real = jnp.arange(T)[None, :] < lengths[:, None]
        dt = jnp.where(real[:, :, None], step_sizes(dt, layer), 0.0)
        y, h = ssd_chunked(x, dt, -jnp.exp(layer["A_log"]), B, C, layer["D"],
                           cfg.mamba_chunk_size)
    with jax.named_scope(SCOPE_PROJ):
        out = gated_norm(y.reshape(R, T, cfg.d_inner), z, layer, cfg) @ layer["out_proj"]
    return out, tail, h


def mamba_token(layer, mi, a, tail, ssm, live, cfg: GraniteHybridConfig):
    """The Mamba mixer for one position of each row: a (R, d), the rows'
    conv tails (K-1, R, conv_dim), the stacked state of all Mamba layers, of
    which this is layer `mi`, and the rows' `live_rows`. Returns (out
    (R, d), new tails for every row, the stack with the live rows' states
    stepped)."""
    R = a.shape[0]
    with jax.named_scope(SCOPE_PROJ):
        z, xBC, dt = in_proj(a, layer, cfg)
    with jax.named_scope(SCOPE_UPDATE):
        xBC, tail = conv_step(tail, xBC, layer)
        x, B, C = split_xbc(xBC, cfg)
        y, ssm = ssm_step_stacked(ssm, mi, live, x, step_sizes(dt, layer),
                                  -jnp.exp(layer["A_log"]), B, C, layer["D"])
    with jax.named_scope(SCOPE_PROJ):
        out = gated_norm(y.reshape(R, cfg.d_inner), z, layer, cfg) @ layer["out_proj"]
    return out, tail, ssm


# ---------------------------------------------------------- attention mixer
def qkv(layer, a, cfg: GraniteHybridConfig):
    """a (..., d) -> q (..., h, hd), k and v (..., kvh, hd); no position term."""
    lead = a.shape[:-1]
    return ((a @ layer["wq"]).reshape(*lead, cfg.n_heads, cfg.head_dim),
            (a @ layer["wk"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim),
            (a @ layer["wv"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim))


def causal_attention(q, k, v, cfg: GraniteHybridConfig):
    """Causal self-attention over whole rows (R, T, heads, hd), scores times
    attention_multiplier: the flash forward (Pallas on the chip, blockwise
    XLA elsewhere). A real query never sees a right-pad key behind it."""
    from ray_tpu.ops.flash_attention import flash_attention_fwd

    o, _ = flash_attention_fwd(q, k, v, causal=True, sm_scale=cfg.attention_multiplier)
    return o.reshape(*q.shape[:2], cfg.n_heads * cfg.head_dim).astype(cfg.dtype)


# ------------------------------------------------------------ full forward
def forward(params, tokens, cfg: GraniteHybridConfig, lengths=None):
    """Logits (R, T, V) float32 of right-padded token rows (R, T): the
    whole-sequence pass, no cache. Positions past `lengths` (default: all
    real) hold nothing meaningful."""
    R, T = tokens.shape
    lengths = jnp.full((R,), T, jnp.int32) if lengths is None else lengths

    def mamba_mixer(layer, _, a, carry):
        return mamba_sequence(layer, a, lengths, cfg)[0], carry

    def attn_mixer(layer, _, a, carry):
        with jax.named_scope(SCOPE_ATTN):
            return causal_attention(*qkv(layer, a, cfg), cfg) @ layer["wo"], carry

    x, _ = run_layers(params, embed_tokens(params, tokens, cfg), (), cfg,
                      {MAMBA: mamba_mixer, ATTENTION: attn_mixer})
    return logits_of(params, x, cfg)
