"""Latent-attention decoder with routed experts (HF `model_type` `sarvam_mla`:
multi-head latent attention as the DeepSeek family writes it, with no query
compression; one leading dense layer, then sigmoid-routed experts beside a
shared one).

One layer, x the residual stream, two RMSNorms a layer (pre-norm):

    h = x + Attn(N1(x));   y = h + FFN(N2(h))

- `Attn(u)`, latent attention. q = Wq u, `n_heads` heads of `qk_nope_head_dim
  + qk_rope_head_dim` = [q_nope | q_rope]. [c | k_r] = W_kv_a u, `kv_lora_rank`
  | `qk_rope_head_dim`; c = N_kv(c). Each query head (over all its entries)
  and k_r are RMS-normed with a learned scale; then RoPE on every head's
  q_rope and on k_r, which is ONE vector for all heads. What a position
  leaves behind is the row [c | RoPE(k_r)]: `kv_lora_rank + qk_rope_head_dim`
  wide whatever the number of heads. Keys and values are that row expanded:
  k_nope_h = W_uk,h c, v_h = W_uv,h c (the two halves of the source's
  W_kv_b). Scores s_h(t, j) = scale (q_nope_h(t) . k_nope_h(j) + q_rope_h(t) .
  k_r(j)), causal softmax, o_h = sum_j p_h(t, j) v_h(j), out = Wo concat(o_h).
  `scale` = (nope + rope)^-0.5 x yarn_mscale(factor, mscale_all_dim)^2 and the
  rotary frequencies are YaRN's blend (ops/rope.yarn_frequencies).
- The same attention two ways. EXPANDED (`expand_kv`, the whole-sequence
  forward and the admission): every head's keys and values from c, then an
  ordinary causal attention with two score products (ops/flash_attention's
  `q_shared` / `k_shared`: k_r is never copied a head). ABSORBED (`absorb_q`
  / `absorbed_out`, the decode step): q_lat_h = W_uk,h^T q_nope_h, scores
  [q_lat_h | q_rope_h] . row against the cached rows themselves, o_lat_h =
  sum_j p c(j), o_h = W_uv,h o_lat_h. Equal in exact arithmetic; the absorbed
  one reads `kv_lora_rank + qk_rope_head_dim` numbers a position where the
  expanded one would read heads x (nope + rope + v).
- `FFN` of the first `n_dense_layers` layers: SwiGLU of width `d_ff`. Of every
  other layer: models/afmoe.py's `route` (sigmoid scores, a bias that moves
  the choice only, chosen scores normalised and times `route_scale`) and
  `moe_ffn` (routed experts as ragged products, the shared expert added
  unweighted). This program may hold a PART of a layer's experts
  (`held_first`, `held_count` of the router's `n_experts`): one chip's share
  where the experts are divided over chips. What the others would add is left
  out (`afmoe.expert_ffn`).

The ends: x_0 = E[token], a final RMSNorm, an untied head.

Params are one pytree: `layers` stacked over all layers (norms and attention),
`dense` over the dense layers, `moe` over the expert layers; `run_layers`
walks them as two runs, each one rolled `lax.scan`. Precision as
models/llama.py has it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.afmoe import (  # the FFN half is that model's, called not copied
    DENSE, MOE, _dense, _layer_at, logits_of, make_moe, make_swiglu, moe_ffn, swiglu)
from ray_tpu.models.paged import rows_a_piece
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_rope, yarn_frequencies, yarn_mscale

# scopes of a device trace (benchmark/sarvam_mla_spans.py reads them), inside
# the macro-step's admit_prefill / decode_chunk; the expert layer's three
# (moe_route, moe_experts, moe_shared) come with afmoe.moe_ffn
# mla_absorb lies inside mla_proj: W_kv_b's halves in a decode step
SCOPE_PROJ, SCOPE_CTX, SCOPE_ABSORB = "mla_proj", "mla_ctx", "mla_absorb"
# tokens one pass of the expanded attention takes: a longer admission goes
# through in pieces of whole rows, so that q and the expanded keys and values
# (heads x (nope + rope + 2 v) numbers a token) stay under a GB or two
ATTN_TOKENS = 8192


@dataclasses.dataclass(frozen=True)
class SarvamMlaConfig:
    """The source's fields under this repo's names; the defaults are
    sarvam-105b's published values, the held range all of the experts."""
    vocab_size: int = 262144
    d_model: int = 4096
    n_layers: int = 32
    n_dense_layers: int = 1               # first_k_dense_replace
    n_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 16384                     # intermediate_size (dense layers)
    moe_d_ff: int = 2048                  # moe_intermediate_size
    n_experts: int = 128                  # the router's width
    held_first: int = 0                   # of the router's experts, the range
    held_count: Optional[int] = None      # whose weights are here (None: all)
    top_k: int = 8
    n_shared_experts: int = 1
    route_scale: float = 2.5              # routed_scaling_factor
    route_norm: bool = True
    rope_theta: float = 10000.0
    rope_factor: float = 40.0             # rope_scaling (deepseek_yarn)
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    route_scoring = "sigmoid"             # a constant of the family, no field: afmoe.route
    mla_q_scale = mla_kv_scale = 1.0      # no `mla_scale_*` in this family: `project`

    def __post_init__(self):
        if self.held_count is None:
            object.__setattr__(self, "held_count", self.n_experts - self.held_first)
        if not 0 <= self.held_first <= self.held_first + self.held_count <= self.n_experts:
            raise ValueError("the held experts are a range of the router's n_experts")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers counts leading layers")
        if self.top_k > self.n_experts:
            raise ValueError("top_k experts a token of n_experts")

    @property
    def held_experts(self) -> Tuple[int, int]:
        return self.held_first, self.held_count

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """What a position leaves in the cache: [c | RoPE(k_r)]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def sm_scale(self) -> float:
        return self.q_head_dim ** -0.5 * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

    @property
    def model_module(self):
        from ray_tpu.models import sarvam_mla

        return sarvam_mla

    @property
    def decode_module(self):
        from ray_tpu.models import sarvam_mla_decode

        return sarvam_mla_decode

    @staticmethod
    def tiny(**kw) -> "SarvamMlaConfig":
        """Test-sized, with the real shape of things: a leading dense layer,
        a latent narrower than the heads' keys together, a quarter of the
        experts held, YaRN over a short original span."""
        return SarvamMlaConfig(**{**dict(
            vocab_size=512, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            d_ff=128, moe_d_ff=32, n_experts=16, held_first=4, held_count=4, top_k=4,
            rope_original_max=32, rope_factor=8.0, max_seq_len=256), **kw})


# ------------------------------------------------------------------- params
def make_layer(k, cfg: SarvamMlaConfig) -> Dict[str, Any]:
    """What every layer has: its two norms and its attention. W_kv_b lies as
    its two halves, each laid out for the absorbed products: `w_uk` (heads,
    nope, latent), `w_uv` (heads, latent, v)."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(k, 5)
    one = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    return {
        "attn_norm": one(d), "ffn_norm": one(d), "kv_norm": one(r),
        "q_norm": one(cfg.q_head_dim), "k_rope_norm": one(cfg.qk_rope_head_dim),
        "wq": _dense(ks[0], (d, h * cfg.q_head_dim), d, cfg.dtype),
        "w_kv_a": _dense(ks[1], (d, cfg.latent_row), d, cfg.dtype),
        "w_uk": _dense(ks[2], (h, cfg.qk_nope_head_dim, r), r, cfg.dtype),
        "w_uv": _dense(ks[3], (h, r, cfg.v_head_dim), r, cfg.dtype),
        "wo": _dense(ks[4], (h * cfg.v_head_dim, d), h * cfg.v_head_dim, cfg.dtype),
    }


def part_keys(key, cfg: SarvamMlaConfig):
    """(embedding key, head key, one key a layer, a dense FFN, an expert layer)."""
    k_embed, k_head, k_l, k_d, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, cfg.n_layers),
            jax.random.split(k_d, cfg.n_dense_layers), jax.random.split(k_m, cfg.n_moe_layers))


def init_params(key, cfg: SarvamMlaConfig) -> Dict[str, Any]:
    k_embed, k_head, k_l, k_d, k_m = part_keys(key, cfg)
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype),
        "layers": jax.vmap(functools.partial(make_layer, cfg=cfg))(k_l),
        DENSE: jax.vmap(lambda k: make_swiglu(k, cfg.d_model, cfg.d_ff, cfg.dtype))(k_d),
        MOE: jax.vmap(functools.partial(make_moe, cfg=cfg))(k_m),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": _dense(k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, cfg.dtype),
    }


def num_params(cfg: SarvamMlaConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# ------------------------------------------------------- the attention half
def rope_tables(cfg: SarvamMlaConfig, span: int):
    return yarn_frequencies(cfg.qk_rope_head_dim, span, cfg.rope_theta, cfg.rope_factor,
                            cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow,
                            cfg.rope_mscale, cfg.rope_mscale_all_dim)


def project(layer, a, cos, sin, positions, cfg: SarvamMlaConfig):
    """a (R, T, d) at `positions` (R, T) or None (0..T-1) -> q_nope (R, T, h,
    nope), q_rope (R, T, h, rope) with its RoPE on, and the cache row (R, T,
    latent_row) = [N_kv(c) | RoPE(N(k_r))]. The head split stays out of the
    products (llama._qkv says why).

    What the layer holds says which of the family's forms it is: with `w_qa`
    the query is compressed, q = W_qb N_q(W_qa a) (`q_lora_rank`); without
    `q_norm` / `k_rope_norm` the query heads and k_r go un-normed. `cfg.
    mla_q_scale` / `mla_kv_scale`, where they are not 1, multiply q (every
    entry, after W_qb) and the normed latent c (never k_r)."""
    if "w_qa" in layer:
        q, ckr = jax.lax.optimization_barrier((a @ layer["w_qa"], a @ layer["w_kv_a"]))
        # the barrier keeps the head split out of this product too
        q = jax.lax.optimization_barrier(rms_norm(q, layer["q_a_norm"], cfg.rms_eps) @ layer["w_qb"])
    else:
        q, ckr = jax.lax.optimization_barrier((a @ layer["wq"], a @ layer["w_kv_a"]))
    q = q.reshape(*a.shape[:2], cfg.n_heads, cfg.q_head_dim)
    if "q_norm" in layer:
        q = rms_norm(q, layer["q_norm"], cfg.rms_eps)
    if cfg.mla_q_scale != 1.0:
        q = q * cfg.mla_q_scale
    q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    c = rms_norm(ckr[..., :cfg.kv_lora_rank], layer["kv_norm"], cfg.rms_eps)
    if cfg.mla_kv_scale != 1.0:
        c = c * cfg.mla_kv_scale
    k_r = ckr[..., cfg.kv_lora_rank:]
    if "k_rope_norm" in layer:
        k_r = rms_norm(k_r, layer["k_rope_norm"], cfg.rms_eps)
    k_r = apply_rope(k_r[:, :, None, :], cos, sin, positions)[:, :, 0, :]
    return q_nope, apply_rope(q_rope, cos, sin, positions), jnp.concatenate([c, k_r], axis=-1)


def expand_kv(layer, c, cfg: SarvamMlaConfig):
    """Every head's keys and values from the latent: c (R, T, latent) ->
    k_nope (R, T, h, nope), v (R, T, h, v)."""
    return (jnp.einsum("rtc,hnc->rthn", c, layer["w_uk"]),
            jnp.einsum("rtc,hcv->rthv", c, layer["w_uv"]))


def absorb_q(layer, q_nope):
    """q_lat_h = W_uk,h^T q_nope_h: (B, h, nope) -> (B, h, latent)."""
    with jax.named_scope(SCOPE_ABSORB):
        return jnp.einsum("bhn,hnc->bhc", q_nope, layer["w_uk"])


def absorbed_out(layer, o_lat, cfg: SarvamMlaConfig):
    """o_h = W_uv,h o_lat_h, heads side by side: (B, h, latent) -> (B, h * v)."""
    with jax.named_scope(SCOPE_ABSORB):
        return jnp.einsum("bhc,hcv->bhv", o_lat, layer["w_uv"]).reshape(o_lat.shape[0], -1)


def expanded_attention(q_nope, q_rope, row, layer, cfg: SarvamMlaConfig):
    """Causal self-attention of whole rows the expanded way: (R, T, h * v)."""
    from ray_tpu.ops.flash_attention import flash_attention_fwd

    k_nope, v = expand_kv(layer, row[..., :cfg.kv_lora_rank], cfg)
    o, _ = flash_attention_fwd(q_nope, k_nope, v, causal=True, sm_scale=cfg.sm_scale,
                               q_shared=q_rope, k_shared=row[..., cfg.kv_lora_rank:])
    return o.reshape(*o.shape[:2], -1).astype(cfg.dtype)


def sequence_mixer(layer, a, cos, sin, cfg: SarvamMlaConfig):
    """The attention half over whole rows a (R, T, d): projections, the
    expanded attention, Wo, ATTN_TOKENS tokens' rows at a time. Returns
    (output (R, T, d), the cache rows (R, T, latent_row): the admission
    writes them to the pool)."""
    R, T, _ = a.shape
    n = rows_a_piece(R, T, ATTN_TOKENS)

    def piece(a_piece):
        with jax.named_scope(SCOPE_PROJ):
            q_nope, q_rope, row = project(layer, a_piece, cos, sin, None, cfg)
        with jax.named_scope(SCOPE_CTX):
            o = expanded_attention(q_nope, q_rope, row, layer, cfg)
        with jax.named_scope(SCOPE_PROJ):
            return o @ layer["wo"], row

    if n == R:
        return piece(a)
    out, rows = jax.lax.map(piece, a.reshape(R // n, n, T, -1))
    return out.reshape(a.shape), rows.reshape(R, T, -1)


# ----------------------------------------------------------- the layer loop
def run_layers(params, x, carry, cfg: SarvamMlaConfig, mixer: Callable,
               experts: Optional[Callable] = None):
    """x (..., d) through every layer in order. `mixer(layer, index, normed
    x, carry) -> (attention output, carry)`; `experts(expert layer's params,
    normed rows (N, d), carry) -> (FFN output, carry)`, by default the expert
    layer over every row. The block around them is the same for the full
    forward, the admission and the decode step."""
    if experts is None:
        experts = lambda p, m, carry: (moe_ffn(m, p, cfg)[0], carry)  # noqa: E731

    def body(c, i, ffn, g0):
        x, carry = c
        layer = _layer_at(params["layers"], g0 + i)
        o, carry = mixer(layer, g0 + i, rms_norm(x, layer["attn_norm"], cfg.rms_eps), carry)
        x = x + o
        m = rms_norm(x, layer["ffn_norm"], cfg.rms_eps)
        if ffn == DENSE:
            y = swiglu(m, _layer_at(params[DENSE], i), cfg)
        else:  # the experts stay stacked: afmoe.expert_ffn says why
            own = {k: v for k, v in params[MOE].items() if k != "experts"}
            p = {**_layer_at(own, i), "experts": params[MOE]["experts"], "at": i}
            y, carry = experts(p, m.reshape(-1, cfg.d_model), carry)
            y = y.reshape(m.shape)
        return (x + y, carry), None

    for ffn, g0, n in ((DENSE, 0, cfg.n_dense_layers), (MOE, cfg.n_dense_layers, cfg.n_moe_layers)):
        if n:
            (x, carry), _ = jax.lax.scan(functools.partial(body, ffn=ffn, g0=g0), (x, carry),
                                         jnp.arange(n))
    return x, carry


def embed_tokens(params, tokens, cfg: SarvamMlaConfig):
    return params["embed"][tokens].astype(cfg.dtype)


# ------------------------------------------------------------ full forward
def forward(params, tokens, cfg: SarvamMlaConfig):
    """Logits (R, T, V) float32 of token rows (R, T): the whole-sequence
    pass, no cache, attention the expanded way."""
    cos, sin = rope_tables(cfg, tokens.shape[1])

    def mixer(layer, _, a, carry):
        return sequence_mixer(layer, a, cos, sin, cfg)[0], carry

    x, _ = run_layers(params, embed_tokens(params, tokens, cfg), (), cfg, mixer)
    return logits_of(params, x, cfg)
