"""Qwen3-Next style decoder (HF `model_type` `qwen3_next`): gated-delta-rule
linear-attention layers with a gated full-attention layer every few, and in
every layer softmax-routed experts beside a gated shared one.

One layer, x the residual stream, two norms a layer (pre-norm):

    h = x + Mixer(N1(x));   y = h + MoE(N2(h))

N is the family's zero-centred RMSNorm, `x / sqrt(mean(x^2) + eps) * (1 + w)`
(`norm`), also the final norm; the embedding is unscaled, the head untied.
Layer i attends when `(i + 1) % full_attention_interval == 0`; every other
layer is linear attention (`layer_types`).

- LINEAR attention, u = N1(x) at position t. `[q | k | v | z] = W_qkvz u`: q
  and k `lin_k_heads` heads of `lin_k_dim`, v and z `lin_v_heads` heads of
  `lin_v_dim`; `[b | a] = W_ba u`, one of each a value head. A causal
  depthwise convolution of `lin_conv` taps, no bias, over the channels
  `[q | k | v]`, then SiLU (`granite_hybrid.causal_conv` / `conv_step`: a
  lane's conv tail is its last taps - 1 inputs). `beta = sigmoid(b)`,
  `g = -exp(A_log) softplus(a + dt_bias)`, `alpha = exp(g)`, float32. q and
  k are divided by their L2 norm over a head and q times `lin_k_dim`^-0.5;
  a q / k head serves `lin_v_heads / lin_k_heads` consecutive value heads.
  A value head's state S is (key, value), float32, zero at a sequence's
  start, and follows the gated delta rule:

      S_t = alpha_t S_(t-1) + k_t (beta_t (v_t - (alpha_t S_(t-1))^T k_t))^T
      o_t = S_t^T q_t

  out = W_o concat_h(o_h / sqrt(mean(o_h^2) + eps) * w_n * SiLU(z_h)).
- The rule has two forms. `gdn_step` is the line above for one position
  (`gdn_step_stacked` where the rows live in the cache's stacked state: on
  a TPU the second body of ops/ssm_update.py, one pass over each live row).
  `gdn_chunked` is the same recurrence over whole sequences in chunks of C
  positions, made of matrix products: with G_i the running sum of g inside
  a chunk, A = -strict_lower(beta_i (k_i . k_j) exp(G_i - G_j)),
  T = (I - A)^-1 = (I + A)(I + A^2)(I + A^4)... (A is strictly lower, so
  the product ends after log2 C factors and is exact), W = T (beta k exp G),
  U = T (beta v); a chunk entering with state S gives V' = U - W S,
  O = (q exp G) S + lower(q k^T exp(G_i - G_j)) V', and leaves
  S exp(G_C) + (k exp(G_C - G))^T V'. Past a row's length g = 0 and beta =
  0: the state stands. Of all this only V', O and the state's update read
  the state a chunk enters with, so `gdn_chunked` works in two stages, a
  group of `GROUP_TOKENS` tokens' chunks after another. First, for ALL
  chunks of the group at once, the chunk a batch axis of every operation: G,
  the decays exp(G_i - G_j), k k^T, q k^T, A, T, W, U and the three
  decay-weighted operands q exp G, lower(q k^T exp(G_i - G_j)) and
  k exp(G_C - G). Then a loop over the group's chunks in order, which
  carries S and makes those three and nothing else: four products a chunk.
  A group is as many chunks as keep the first stage's float32 C x C
  matrices in the chip's fast memory from one product to the next (all
  chunks of a pass at once go through main memory and are slower than one
  chunk at a time). In the chain of T each factor's T P and the next
  factor's P P are ONE product, [T; P] P: the same rows at the same
  precision, and the matrix unit streams 2 C rows past a right-hand side
  it would otherwise load twice.
- FULL attention (models/afmoe.py's `qkvg` / `gated_out`, at this model's
  sizes): q, k, v and an output gate as wide as q; q and k through the
  zero-centred norm over a head; RoPE on the FIRST `rotary_dim` entries of
  each head (`partial_rotary_factor`), the others untouched; causal
  softmax at head_dim^-0.5; out = W_o (concat(heads) * sigmoid(gate)).
- MoE: models/afmoe.py's `route` in its softmax case (p = softmax over all
  the router's experts, float32; the `top_k` largest; w = p_chosen /
  sum(p_chosen); no bias, no scale) and `moe_ffn` (ragged products over the
  chosen pairs; the shared expert's output times sigmoid(w_g . u)). This
  program may hold a PART of a layer's experts (`held_first`, `held_count`
  of the router's `n_experts`): what the others would add is left out
  (`afmoe.expert_ffn`).

Precision: weights and activations in `cfg.dtype`; matrix products take
`cfg.dtype` operands and accumulate in float32; the state, the decays, beta,
the L2 norms, the inverse T (float32 products at `highest`), softmax, norms,
the router's scores and the logits in float32. The chunked form rounds the
operands of its other products (q, k, the decay-weighted key and value rows,
T, the carried state) to `cfg.dtype`, as the family's kernels do.

Params are one pytree with a stacked leading axis per KIND of mixer
(`linear_attention`, `full_attention`) and one over all layers for the
expert layers (`moe`); `run_layers` walks `layer_types` as runs of one kind
(`granite_hybrid.scan_runs`), each run one rolled `lax.scan`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import afmoe
from ray_tpu.models.afmoe import MOE, _dense, _layer_at, make_swiglu, moe_ffn
from ray_tpu.models.granite_hybrid import causal_conv, conv_step, runs_of, scan_runs
from ray_tpu.models.paged import rows_a_piece
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_partial_rope, rope_frequencies

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
# scopes of a device trace (benchmark/qwen3_next_spans.py reads them), inside
# the macro-step's admit_prefill / decode_chunk; the expert layer's three
# (moe_route, moe_experts, moe_shared) come with afmoe.moe_ffn
SCOPE_PROJ, SCOPE_SCAN, SCOPE_UPDATE, SCOPE_ATTN = (
    "gdn_proj", "gdn_scan", "gdn_update", afmoe.SCOPE_FULL)
# tokens one pass of the linear mixer takes: a longer admission goes through
# in pieces of whole rows (rows are independent sequences), so that the
# projections' output (6 x d_model numbers a token) and the conv's float32
# sums stay under a GB
LIN_TOKENS = 8192
# tokens whose chunks `gdn_chunked` prepares at once: their float32 C x C
# matrices (a chunk's decays, A, the inverse's powers: a MB a row's chunk at
# the published sizes) then stay in a v5e's fast memory from product to
# product. On the chip, one pass of 8,192 tokens (my chip runs, PR 44): 5.1 ms
# at 512 and at 1,024 for two rows of 4,096 (6.7 and 5.1 for four of 2,048),
# 6.2 at 2,048, 10.8 with all 8,192 at once, 6.0 a chunk at a time (8.0 the
# parent, everything in one loop over chunks)
GROUP_TOKENS = 1024


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The source's fields under this repo's names; the defaults are
    Qwen3-Next-80B-A3B's published values, the held range all of the
    experts. Nothing is derived from another width."""
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    lin_k_heads: int = 16                 # linear_num_key_heads
    lin_v_heads: int = 32                 # linear_num_value_heads
    lin_k_dim: int = 128                  # linear_key_head_dim
    lin_v_dim: int = 128                  # linear_value_head_dim
    lin_conv: int = 4                     # linear_conv_kernel_dim
    lin_chunk: int = 64                   # `gdn_chunked`'s chunk (not the source's)
    moe_d_ff: int = 512                   # moe_intermediate_size
    shared_d_ff: int = 512                # shared_expert_intermediate_size
    n_experts: int = 512                  # the router's width
    held_first: int = 0                   # of the router's experts, the range
    held_count: Optional[int] = None      # whose weights are here (None: all)
    top_k: int = 10
    route_norm: bool = True               # norm_topk_prob
    rms_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    # constants of the family, no fields (afmoe.route): a softmax over all the
    # experts, and no scale on the chosen weights
    route_scoring = "softmax"
    route_scale = 1.0

    def __post_init__(self):
        if self.held_count is None:
            object.__setattr__(self, "held_count", self.n_experts - self.held_first)
        if not 0 <= self.held_first <= self.held_first + self.held_count <= self.n_experts:
            raise ValueError("the held experts are a range of the router's n_experts")
        if self.top_k > self.n_experts:
            raise ValueError("top_k experts a token of n_experts")
        if self.lin_v_heads % self.lin_k_heads:
            raise ValueError("a q / k head serves a whole number of value heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("the rotary part is an even number of a head's entries")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0 else LINEAR
                     for i in range(self.n_layers))

    @property
    def runs(self) -> Tuple[Tuple[str, int, int, int], ...]:
        return runs_of(self.layer_types)

    @property
    def n_linear_layers(self) -> int:
        return self.layer_types.count(LINEAR)

    @property
    def n_full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_dim(self) -> int:
        """The channels the conv runs over: [q | k | v]."""
        return 2 * self.lin_k_heads * self.lin_k_dim + self.lin_v_heads * self.lin_v_dim

    @property
    def lin_d_inner(self) -> int:
        return self.lin_v_heads * self.lin_v_dim

    @property
    def held_experts(self) -> Tuple[int, int]:
        return self.held_first, self.held_count

    @property
    def model_module(self):
        from ray_tpu.models import qwen3_next

        return qwen3_next

    @property
    def decode_module(self):
        from ray_tpu.models import qwen3_next_decode

        return qwen3_next_decode

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """Test-sized, with the real shape of things: two runs of linear
        layers around an attention layer, two value heads a key head, half a
        head rotary, a quarter of the experts held, a chunk shorter than a
        prompt."""
        return Qwen3NextConfig(**{**dict(
            vocab_size=512, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=16,
            partial_rotary_factor=0.5, lin_k_heads=2, lin_v_heads=4, lin_k_dim=8, lin_v_dim=8,
            lin_chunk=8, moe_d_ff=32, shared_d_ff=32, n_experts=16, held_first=4, held_count=4,
            top_k=4, max_seq_len=256), **kw})


# ------------------------------------------------------------------- params
def make_linear_layer(k, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """One linear-attention mixer. `A_log`, `dt_bias` and the conv as
    Mamba-2 draws them (`granite_hybrid.make_mamba_layer`). The source's
    interleaved `in_proj_qkvz` and `in_proj_ba` lie de-interleaved: `in_proj`
    [q | k | v | z] and `ba_proj` [b | a] (64 columns: a minor axis that is
    no multiple of 128 stays out of the wide matrix, as `dt_proj` does
    there). The conv weight is kept (taps, channels)."""
    d, H, K = cfg.d_model, cfg.lin_v_heads, cfg.lin_conv
    ks = jax.random.split(k, 6)
    dt = jnp.exp(jax.random.uniform(ks[2], (H,), F32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "norm": jnp.zeros((d,), cfg.dtype),
        "in_proj": _dense(ks[0], (d, cfg.conv_dim + cfg.lin_d_inner), d, cfg.dtype),
        "ba_proj": _dense(ks[5], (d, 2 * H), d, cfg.dtype),
        "conv_w": jax.random.uniform(
            ks[1], (K, cfg.conv_dim), F32, -(K ** -0.5), K ** -0.5).astype(cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), F32, 1.0, 16.0)),
        "head_norm": jnp.ones((cfg.lin_v_dim,), cfg.dtype),
        "out_proj": _dense(ks[4], (cfg.lin_d_inner, d), cfg.lin_d_inner, cfg.dtype),
    }


def make_full_layer(k, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """One gated attention mixer: the source's `q_proj` [q | gate] a head
    lies as `wq` and `wg`, the names `afmoe.qkvg` reads."""
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 5)
    return {
        "norm": jnp.zeros((d,), cfg.dtype),
        "q_norm": jnp.zeros((hd,), cfg.dtype), "k_norm": jnp.zeros((hd,), cfg.dtype),
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wg": _dense(ks[3], (d, h * hd), d, cfg.dtype),
        "wo": _dense(ks[4], (h * hd, d), h * hd, cfg.dtype),
    }


def make_moe(k, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """One expert layer with the norm before it: the router (no bias), the
    HELD experts stacked on a leading axis, the shared expert and its gate."""
    d = cfg.d_model
    k_r, k_e, k_s, k_g = jax.random.split(k, 4)
    return {"norm": jnp.zeros((d,), cfg.dtype),
            "router": _dense(k_r, (d, cfg.n_experts), d, cfg.dtype),
            "experts": make_swiglu(k_e, d, cfg.moe_d_ff, cfg.dtype, (cfg.held_count,)),
            "shared": make_swiglu(k_s, d, cfg.shared_d_ff, cfg.dtype),
            "shared_gate": _dense(k_g, (d,), d, cfg.dtype)}


def part_keys(key, cfg: Qwen3NextConfig):
    """(embedding key, head key, one key a linear mixer, an attention mixer,
    an expert layer)."""
    k_embed, k_head, k_l, k_f, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, cfg.n_linear_layers),
            jax.random.split(k_f, cfg.n_full_layers), jax.random.split(k_m, cfg.n_layers))


def init_params(key, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    k_embed, k_head, k_l, k_f, k_m = part_keys(key, cfg)
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype),
        LINEAR: jax.vmap(functools.partial(make_linear_layer, cfg=cfg))(k_l),
        FULL: jax.vmap(functools.partial(make_full_layer, cfg=cfg))(k_f),
        MOE: jax.vmap(functools.partial(make_moe, cfg=cfg))(k_m),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
        "lm_head": _dense(k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, cfg.dtype),
    }


def num_params(cfg: Qwen3NextConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# ------------------------------------------------------------------ the ends
def one_plus(w):
    """The scale of a zero-centred norm, for `rms_norm`."""
    return 1.0 + w.astype(F32)


def norm(x, w, cfg: Qwen3NextConfig):
    """The family's zero-centred RMSNorm: x / sqrt(mean(x^2) + eps) * (1 + w)."""
    return rms_norm(x, one_plus(w), cfg.rms_eps)


def embed_tokens(params, tokens, cfg: Qwen3NextConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def logits_of(params, x, cfg: Qwen3NextConfig):
    """Final norm (zero-centred) and the untied head, float32: afmoe's."""
    return afmoe.logits_of({**params, "final_norm": one_plus(params["final_norm"])}, x, cfg)


# ------------------------------------------------- the linear-attention mixer
def lin_project(a, layer, cfg: Qwen3NextConfig):
    """a (..., d) -> qkv (..., conv_dim) before the conv, z (..., d_inner),
    b and a (..., value heads)."""
    H = cfg.lin_v_heads
    qkvz, ba = a @ layer["in_proj"], a @ layer["ba_proj"]
    return qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:], ba[..., :H], ba[..., H:]


def gates(b, a, layer):
    """(beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias)), float32."""
    return (jax.nn.sigmoid(b.astype(F32)),
            -jnp.exp(layer["A_log"]) * jax.nn.softplus(a.astype(F32) + layer["dt_bias"]))


def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def split_qkv(qkv, cfg: Qwen3NextConfig):
    """The conv's output (..., conv_dim) -> q and k (..., key heads, K)
    float32, each over its L2 norm and q times K^-0.5; v (..., value heads,
    V) as it is."""
    Hk, K = cfg.lin_k_heads, cfg.lin_k_dim
    lead = qkv.shape[:-1]
    q = _l2norm(qkv[..., :Hk * K].astype(F32).reshape(*lead, Hk, K)) * K ** -0.5
    k = _l2norm(qkv[..., Hk * K:2 * Hk * K].astype(F32).reshape(*lead, Hk, K))
    return q, k, qkv[..., 2 * Hk * K:].reshape(*lead, cfg.lin_v_heads, cfg.lin_v_dim)


def gated_head_norm(o, z, layer, cfg: Qwen3NextConfig):
    """o_h / sqrt(mean(o_h^2) + eps) * w_n * SiLU(z_h), heads side by side:
    o (..., H, V), z (..., H * V) -> (..., H * V)."""
    n = rms_norm(o.astype(F32), layer["head_norm"], cfg.rms_eps)
    n = n.reshape(z.shape) * jax.nn.silu(z.astype(F32))
    return n.astype(cfg.dtype)


def gdn_step(S, q, k, v, a, b):
    """The gated delta rule for one position: the definition, the path off
    the TPU and the tests' oracle (`gdn_step_stacked` is what a decode step
    calls). S (R, H, K, V) float32; q, k (R, H, K) (a key head repeated for
    each of its value heads); v (R, H, V); a = exp(g) and b = beta (R, H)
    float32. Elementwise in float32 throughout (no matrix unit: its float32
    products would round to bfloat16). Returns (o (R, H, V) float32, new
    state)."""
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
    S = a[:, :, None, None] * S
    d = b[:, :, None] * (vf - jnp.sum(S * kf[..., None], axis=-2))
    S = S + kf[..., None] * d[:, :, None, :]
    return jnp.sum(S * qf[..., None], axis=-2), S


def gdn_step_stacked(state, li, live, q, k, v, a, b):
    """`gdn_step` on layer `li` of the cache's stacked state (layers, R, H,
    K, V), for the rows that are live (`live` is their
    `granite_hybrid.live_rows`); a row that is not live and every other
    layer stay bit for bit. On a TPU, for shapes its tiles take, the second
    body of ops/ssm_update.py: one pass over each live row; elsewhere
    `gdn_step` on the layer, a select and the write. Returns (o (R, H, V)
    float32, meaningless on a row that is not live; the stack)."""
    from ray_tpu.ops import ssm_update  # Pallas: imported where it is traced

    if ssm_update.engages(*state.shape[2:]):
        return ssm_update.delta_update_stacked_state(state, li, live, q, k, v, a, b)
    S = jax.lax.dynamic_index_in_dim(state, li, 0, keepdims=False)
    o, new_S = gdn_step(S, q, k, v, a, b)
    new_S = jnp.where(live[0][:, None, None, None], new_S, S)
    return o, jax.lax.dynamic_update_index_in_dim(state, new_S, li, 0)


def gdn_chunked(q, k, v, g, beta, chunk: int):
    """The gated delta rule over whole sequences in its chunked form (this
    module's text has the algebra, and what is made a group of chunks at a
    time and what in the loop over chunks). q, k (R, T, Hk, K) float32,
    normalised; v (R, T, H, V), H a multiple of Hk; g <= 0 and beta (R, T, H)
    float32, both 0 where a position is padding (decay 1, nothing written:
    the state stands still). From a zero state. Returns (o (R, T, H, V) in
    v's type, final state (R, H, K, V) float32)."""
    R, T, H, V = v.shape
    Hk, K = q.shape[2:]
    E = H // Hk  # value heads a key head
    C = min(chunk, T)
    nc = -(-T // C)
    ng = min(nc, -(-R * nc * C // GROUP_TOKENS))  # groups, of
    per = -(-nc // ng)                            # chunks each, the last filled with padding
    pad = ng * per * C - T
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    mm = v.dtype
    hi = jax.lax.Precision.HIGHEST
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    eye = jnp.eye(C, dtype=F32)
    doublings = max(C - 1, 1).bit_length() - 1  # factors after (I + A)

    def chunks(x, *heads):
        """(R, T, H or Hk, ..) -> (ng, per, R, *heads, C, ..): group- and
        chunk-major for the two loops, head-major for the products, and so
        to the end."""
        x = x.reshape(R, ng, per, C, *heads, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 3 + len(heads)), (1, 2), (0, 1))

    def product(a, b):
        return jnp.matmul(a, b, preferred_element_type=F32)

    def prepare(qc, kc, vc, gc, bc):
        """A group's chunks at once, (per, R, Hk, C, K) x 2 and (per, R, Hk,
        E, C[, V]) x 3: nothing here reads the state."""
        G = jnp.cumsum(gc, axis=-1)                                      # <= 0 and falling
        decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
        kb, qb = kc.astype(mm), qc.astype(mm)
        kk = product(kb, jnp.swapaxes(kb, -1, -2))                        # (per,R,Hk,C,C)
        qk = product(qb, jnp.swapaxes(kb, -1, -2))
        A = jnp.where(strict, -(bc[..., None] * kk[:, :, :, None] * decay), 0.0)
        # both operands of the chain's first product: left to itself the TPU
        # compiler builds A inside that product, once for each (on the chip
        # 108 us a group of 16 chunks where the bare product takes 43)
        A = jax.lax.optimization_barrier(A)
        # T = (I - A)^-1 = (I + A)(I + A^2)(I + A^4)..: float32 products. A
        # factor's T P and the next factor's P P have their right-hand side
        # in common and are one product, of X = [T; P]: X <- [T; 0] + X P
        Tm = eye + A
        if doublings:
            X = jnp.concatenate([Tm, jnp.matmul(A, A, precision=hi)], axis=-2)
            for _ in range(doublings - 1):
                X = (jnp.concatenate([X[..., :C, :], jnp.zeros_like(X[..., C:, :])], axis=-2)
                     + jnp.matmul(X, X[..., C:, :], precision=hi))
            Tm = X[..., :C, :] + jnp.matmul(X[..., :C, :], X[..., C:, :], precision=hi)
        Tb = Tm.astype(mm)
        eG = jnp.exp(G)
        k_in = (kc[:, :, :, None] * (bc * eG)[..., None]).astype(mm)      # beta k exp G
        v_in = (vc.astype(F32) * bc[..., None]).astype(mm)                # beta v
        W = product(Tb, k_in).astype(mm)                                  # (per,R,Hk,E,C,K)
        U = product(Tb, v_in)                                             # (per,R,Hk,E,C,V) float32
        q_in = (qc[:, :, :, None] * eG[..., None]).astype(mm)             # q exp G
        qk_in = (qk[:, :, :, None] * decay).astype(mm)                    # lower(q k^T decay)
        k_out = (kc[:, :, :, None] * jnp.exp(G[..., -1:] - G)[..., None]).astype(mm)
        return W, U, q_in, qk_in, k_out, eG[..., -1]

    def step(S, inp):
        """One chunk: what reads the state S it enters with."""
        W, U, q_in, qk_in, k_out, eG_end = inp
        Sb = S.astype(mm)
        Vb = (U - product(W, Sb)).astype(mm)
        o = product(q_in, Sb) + product(qk_in, Vb)
        S = eG_end[..., None, None] * S + product(jnp.swapaxes(k_out, -1, -2), Vb)
        return S, o.astype(mm)

    S, os_ = jax.lax.scan(lambda S, group: jax.lax.scan(step, S, prepare(*group)),
                          jnp.zeros((R, Hk, E, K, V), F32),
                          (chunks(q, Hk), chunks(k, Hk), chunks(v, Hk, E), chunks(g, Hk, E),
                           chunks(beta, Hk, E)))
    # (ng,per,R,Hk,E,C,V) -> (R,T,H,V)
    o = jnp.transpose(os_, (2, 0, 1, 5, 3, 4, 6)).reshape(R, ng * per * C, H, V)
    return o[:, :T], S.reshape(R, H, K, V)


def linear_sequence(layer, a, lengths, cfg: Qwen3NextConfig):
    """The linear-attention mixer over whole right-padded rows a (R, T, d)
    from a zero state, LIN_TOKENS tokens' rows at a time. Past a row's length
    the state stands and nothing is taken into the conv tail. Returns (out
    (R, T, d), conv tail (R, taps - 1, conv_dim), final state (R, H, K, V)
    float32)."""
    R, T, _ = a.shape
    n = rows_a_piece(R, T, LIN_TOKENS)

    def piece(inp):
        a, lengths = inp
        with jax.named_scope(SCOPE_PROJ):
            qkv, z, b, a_ = lin_project(a, layer, cfg)
            qkv, tail = causal_conv(qkv, layer, lengths)
            q, k, v = split_qkv(qkv, cfg)
            real = (jnp.arange(T)[None, :] < lengths[:, None])[:, :, None]
            beta, g = gates(b, a_, layer)
            beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
        with jax.named_scope(SCOPE_SCAN):
            o, S = gdn_chunked(q, k, v, g, beta, cfg.lin_chunk)
        with jax.named_scope(SCOPE_PROJ):
            return gated_head_norm(o, z, layer, cfg) @ layer["out_proj"], tail, S

    if n == R:
        return piece((a, lengths))
    out, tail, S = jax.lax.map(piece, (a.reshape(R // n, n, T, -1), lengths.reshape(R // n, n)))
    return out.reshape(a.shape), tail.reshape(R, *tail.shape[2:]), S.reshape(R, *S.shape[2:])


def linear_token(layer, li, a, tail, state, live, cfg: Qwen3NextConfig):
    """The linear-attention mixer for one position of each row: a (R, d),
    the rows' conv tails (taps - 1, R, conv_dim), the stacked state of all
    linear layers, of which this is layer `li`, and the rows' `live_rows`.
    Returns (out (R, d), new tails for every row, the stack with the live
    rows' states stepped)."""
    E = cfg.lin_v_heads // cfg.lin_k_heads
    with jax.named_scope(SCOPE_PROJ):
        qkv, z, b, a_ = lin_project(a, layer, cfg)
    with jax.named_scope(SCOPE_UPDATE):
        qkv, tail = conv_step(tail, qkv, layer)
        q, k, v = split_qkv(qkv, cfg)
        beta, g = gates(b, a_, layer)
        o, state = gdn_step_stacked(state, li, live, jnp.repeat(q, E, axis=1),
                                    jnp.repeat(k, E, axis=1), v, jnp.exp(g), beta)
    with jax.named_scope(SCOPE_PROJ):
        out = gated_head_norm(o, z, layer, cfg) @ layer["out_proj"]
    return out, tail, state


# ------------------------------------------------------- the attention mixer
def rope_tables(cfg: Qwen3NextConfig, span: int):
    return rope_frequencies(cfg.rotary_dim, span, cfg.rope_theta)


def qkvg(layer, a, cos, sin, positions, cfg: Qwen3NextConfig):
    """`afmoe.qkvg` with this family's zero-centred head norms, then RoPE on
    the rotary part of q and k: a (R, T, d) at `positions` (R, T) or None
    (0..T-1) -> q (R, T, h, hd), k and v (R, T, kvh, hd), gate (R, T, h * hd)."""
    q, k, v, gate = afmoe.qkvg(
        {**layer, "q_norm": one_plus(layer["q_norm"]), "k_norm": one_plus(layer["k_norm"])}, a, cfg)
    return (apply_partial_rope(q, cos, sin, positions),
            apply_partial_rope(k, cos, sin, positions), v, gate)


# ----------------------------------------------------------- the layer loop
def run_layers(params, x, carry, cfg: Qwen3NextConfig, mixers: Dict[str, Callable],
               experts: Optional[Callable] = None):
    """x (..., d) through every layer in order. `mixers[kind](layer, index
    among its kind, normed x, carry) -> (mixer output, carry)`;
    `experts(expert layer's params, normed rows (N, d), carry) -> (FFN
    output, carry)`, by default the expert layer over every row. The block
    around them is the same for the full forward, the admission and the
    decode step."""
    if experts is None:
        experts = lambda p, m, carry: (moe_ffn(m, p, cfg)[0], carry)  # noqa: E731
    # the experts stay stacked: afmoe.expert_ffn says why
    own = {k: v for k, v in params[MOE].items() if k != "experts"}

    def body(c, kind, ki, gi):
        x, carry = c
        layer = _layer_at(params[kind], ki)
        o, carry = mixers[kind](layer, ki, norm(x, layer["norm"], cfg), carry)
        x = x + o
        p = {**_layer_at(own, gi), "experts": params[MOE]["experts"], "at": gi}
        m = norm(x, p["norm"], cfg)
        y, carry = experts(p, m.reshape(-1, cfg.d_model), carry)
        return x + y.reshape(m.shape), carry

    return scan_runs(cfg.runs, (x, carry), body)


# ------------------------------------------------------------ full forward
def forward(params, tokens, cfg: Qwen3NextConfig, lengths=None):
    """Logits (R, T, V) float32 of right-padded token rows (R, T): the
    whole-sequence pass, no cache. Positions past `lengths` (default: all
    real) hold nothing meaningful."""
    R, T = tokens.shape
    lengths = jnp.full((R,), T, jnp.int32) if lengths is None else lengths
    cos, sin = rope_tables(cfg, T)

    def linear_mixer(layer, _, a, carry):
        return linear_sequence(layer, a, lengths, cfg)[0], carry

    def full_mixer(layer, _, a, carry):
        with jax.named_scope(SCOPE_ATTN):
            q, k, v, gate = qkvg(layer, a, cos, sin, None, cfg)
            o = afmoe.sequence_attention(q, k, v, cfg, None)
            return afmoe.gated_out(o, gate, layer, cfg), carry

    x, _ = run_layers(params, embed_tokens(params, tokens, cfg), (), cfg,
                      {LINEAR: linear_mixer, FULL: full_mixer})
    return logits_of(params, x, cfg)
