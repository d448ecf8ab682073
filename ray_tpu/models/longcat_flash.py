"""LongCat-Flash decoder (Meituan's `LongCat-Flash-Chat`): a DOUBLE layer of
two latent attentions and two dense FFNs, with the expert layer on a shortcut
across the second attention, and identity ("zero-computation") experts among
those the router chooses from.

One layer, x the residual stream, every N an RMSNorm with its own scale:

    h1 = x  + Attn_0(N_a0 x)
    m  = N_f0 h1
    e  = MoE(m)                      # the shortcut branch: taken here, added at the layer's end
    h2 = h1 + FFN_0(m)
    h3 = h2 + Attn_1(N_a1 h2)
    y  = h3 + FFN_1(N_f1 h3) + e

- `Attn_i(u)`, latent attention with a compressed query: q = W_qb N_q(W_qa u)
  (`q_lora_rank`), `n_heads` heads of [q_nope | q_rope]; [c | k_r] = W_kva u,
  c = N_kv(c); q times (d_model / q_lora_rank)^0.5 and c times (d_model /
  kv_lora_rank)^0.5 (`mla_scale_q_lora`, `mla_scale_kv_lora`: q after W_qb, c
  after its norm, k_r never); RoPE (plain frequencies) on q_rope and on k_r,
  one k_r for all heads; k_nope_h = W_uk,h c, v_h = W_uv,h c; scores
  (nope + rope)^-0.5 (q_nope . k_nope + q_rope . k_r), causal softmax, out =
  W_o concat(o_h). No bias, no norm of a query head or of k_r. The row a
  position leaves behind is [c (scaled) | RoPE(k_r)], TWICE a layer. It is
  models/sarvam_mla.py's attention (`project` with the compressed query and
  the two scales, `expand_kv`, `absorb_q`, `absorbed_out`,
  `expanded_attention`), called and not copied.
- `FFN_i`: SwiGLU of width `d_ff`.
- `MoE(m)`: logits = W_r m over `n_routed_experts + n_zero_experts` outputs,
  float32; p = softmax over all of them; the `top_k` largest of p + b are
  chosen (b the choice bias, moving the choice and never the weight); w =
  `route_scale` p at the chosen, NOT normalised. A chosen index under
  `n_routed_experts` adds w x SwiGLU_j(m) of width `moe_d_ff`; one at or above
  it adds w x m (`zero_expert_type: identity`). No shared expert. The router
  and the expert products are models/afmoe.py's `route` and `expert_ffn`
  (`n_experts` here is the ROUTER's width, so an identity index is to them an
  expert that is not held: no product, nothing added); the identity term is
  one weighted sum of m, in float32 as the experts' sum. This program may hold
  a PART of the real experts (`held_first`, `held_count` of `n_routed_experts`),
  one chip's share; the identity term is whole on every chip. How many experts
  a row costs differs from row to row.

The ends: x_0 = E[token] unscaled, a final RMSNorm, an untied head.

Params are one pytree: `layers` stacked over the 2 x n_layers SUBLAYERS (the
two norms and the attention of sublayer s of layer i at 2 i + s), `dense` over
the same (the dense FFNs), `moe` over the layers (router, choice bias, held
experts); `run_layers` walks the layers in one rolled `lax.scan`. Precision
as models/llama.py has it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import sarvam_mla
from ray_tpu.models.afmoe import (  # the router, the expert products and the FFN are that model's
    DENSE, F32, MOE, SCOPE_EXPERTS, SCOPE_ROUTE, _dense, _layer_at, expert_ffn, logits_of,
    make_swiglu, route, swiglu)
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import rope_frequencies

# scopes of a device trace (benchmark/longcat_flash_spans.py reads them) beside
# sarvam_mla's mla_proj / mla_absorb / mla_ctx and afmoe's moe_route /
# moe_experts: the two dense FFNs, and the identity experts' term
SCOPE_DENSE, SCOPE_ZERO = "ffn_dense", "moe_zero"


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    """The source's fields under this repo's names; the defaults are
    LongCat-Flash-Chat's published values, the held range all of the real
    experts."""
    vocab_size: int = 131072
    d_model: int = 6144
    n_layers: int = 28                    # num_layers: DOUBLE layers
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 12288                     # ffn_hidden_size
    moe_d_ff: int = 2048                  # expert_ffn_hidden_size
    n_routed_experts: int = 512           # experts that have weights
    n_zero_experts: int = 256             # zero_expert_num, type identity
    held_first: int = 0                   # of the routed experts, the range
    held_count: Optional[int] = None      # whose weights are here (None: all)
    top_k: int = 12                       # moe_topk
    route_scale: float = 6.0              # routed_scaling_factor
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    rms_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    # constants of the family, no fields: afmoe.route
    route_scoring = "softmax"
    route_norm = False

    def __post_init__(self):
        if self.held_count is None:
            object.__setattr__(self, "held_count", self.n_routed_experts - self.held_first)
        if not 0 <= self.held_first <= self.held_first + self.held_count <= self.n_routed_experts:
            raise ValueError("the held experts are a range of n_routed_experts")
        if self.top_k > self.n_experts:
            raise ValueError("top_k experts a token of the router's outputs")

    @property
    def n_experts(self) -> int:
        """The ROUTER's width, which is what afmoe.route and expert_ffn call
        `n_experts`: the real experts, then the identity ones."""
        return self.n_routed_experts + self.n_zero_experts

    @property
    def held_experts(self) -> Tuple[int, int]:
        return self.held_first, self.held_count

    @property
    def n_sublayers(self) -> int:
        """Attentions, dense FFNs and cache planes: two a layer."""
        return 2 * self.n_layers

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """What a position leaves in the cache, a sublayer: [c | RoPE(k_r)]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def sm_scale(self) -> float:
        return self.q_head_dim ** -0.5

    @property
    def mla_q_scale(self) -> float:
        return (self.d_model / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def mla_kv_scale(self) -> float:
        return (self.d_model / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0

    @property
    def model_module(self):
        from ray_tpu.models import longcat_flash

        return longcat_flash

    @property
    def decode_module(self):
        from ray_tpu.models import longcat_flash_decode

        return longcat_flash_decode

    @staticmethod
    def tiny(**kw) -> "LongcatFlashConfig":
        """Test-sized, with the real shape of things: two double layers, a
        compressed query, a router of 8 real and 4 identity experts top-3,
        half of the real ones held."""
        return LongcatFlashConfig(**{**dict(
            vocab_size=512, d_model=64, n_layers=2, n_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            d_ff=128, moe_d_ff=32, n_routed_experts=8, n_zero_experts=4, held_first=4,
            held_count=4, top_k=3, max_seq_len=256), **kw})


# ------------------------------------------------------------------- params
def make_sublayer(k, cfg: LongcatFlashConfig) -> Dict[str, Any]:
    """One attention with the two norms of its half-layer. W_kv_b lies as its
    two halves, laid out for the absorbed products, as sarvam_mla has them.
    The matrices that lead out of the two compressed spaces (W_qb, W_uk, W_uv)
    are drawn at d_model^-0.5, the initialisation the two `mla_scale_*` are
    made for: scaled, q, k_nope and v then stand at unit variance beside
    k_r."""
    d, h, r, rq = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    ks = jax.random.split(k, 6)
    one = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    return {
        "attn_norm": one(d), "ffn_norm": one(d), "q_a_norm": one(rq), "kv_norm": one(r),
        "w_qa": _dense(ks[0], (d, rq), d, cfg.dtype),
        "w_qb": _dense(ks[1], (rq, h * cfg.q_head_dim), d, cfg.dtype),
        "w_kv_a": _dense(ks[2], (d, cfg.latent_row), d, cfg.dtype),
        "w_uk": _dense(ks[3], (h, cfg.qk_nope_head_dim, r), d, cfg.dtype),
        "w_uv": _dense(ks[4], (h, r, cfg.v_head_dim), d, cfg.dtype),
        "wo": _dense(ks[5], (h * cfg.v_head_dim, d), h * cfg.v_head_dim, cfg.dtype),
    }


def make_moe(k, cfg: LongcatFlashConfig) -> Dict[str, Any]:
    """One expert layer: the router over real and identity experts, its
    choice bias (a buffer of the source, zero in a fresh model), the held
    experts stacked on a leading axis. An identity expert has no weights."""
    k_r, k_e = jax.random.split(k)
    return {"router": _dense(k_r, (cfg.d_model, cfg.n_experts), cfg.d_model, cfg.dtype),
            "bias": jnp.zeros((cfg.n_experts,), F32),
            "experts": make_swiglu(k_e, cfg.d_model, cfg.moe_d_ff, cfg.dtype, (cfg.held_count,))}


def part_keys(key, cfg: LongcatFlashConfig):
    """(embedding key, head key, one key a sublayer, a dense FFN, an expert layer)."""
    k_embed, k_head, k_l, k_d, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, cfg.n_sublayers),
            jax.random.split(k_d, cfg.n_sublayers), jax.random.split(k_m, cfg.n_layers))


def init_params(key, cfg: LongcatFlashConfig) -> Dict[str, Any]:
    k_embed, k_head, k_l, k_d, k_m = part_keys(key, cfg)
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype),
        "layers": jax.vmap(functools.partial(make_sublayer, cfg=cfg))(k_l),
        DENSE: jax.vmap(lambda k: make_swiglu(k, cfg.d_model, cfg.d_ff, cfg.dtype))(k_d),
        MOE: jax.vmap(functools.partial(make_moe, cfg=cfg))(k_m),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": _dense(k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, cfg.dtype),
    }


def num_params(cfg: LongcatFlashConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# ----------------------------------------------------------- the expert layer
def moe_ffn(m, p, cfg: LongcatFlashConfig, live=None):
    """The shortcut branch for rows m (N, d): afmoe's router and expert
    products over the held real experts, and the identity experts' term, w x m
    summed over a row's chosen identity indices: one weighted sum of m, in
    float32 like the experts' sum, for EVERY row (it is whole on every chip,
    whatever share of the real experts is held). `p` as `run_layers` hands it
    on. Returns (out (N, d), rows a held expert (E,) int32, (real, identity)
    choices of the `live` rows (2,) int32)."""
    with jax.named_scope(SCOPE_ROUTE):
        chosen, w = route(m, p["router"], p["bias"], cfg)
    with jax.named_scope(SCOPE_EXPERTS):
        out, sizes = expert_ffn(m, chosen, w, p["experts"], p["at"], cfg, live)
    with jax.named_scope(SCOPE_ZERO):
        zero = chosen >= cfg.n_routed_experts
        w_zero = jnp.where(zero, w, 0.0).sum(axis=-1)
        out = (out.astype(F32) + w_zero[:, None] * m.astype(F32)).astype(cfg.dtype)
        counted = zero if live is None else zero & live[:, None]
        n_live = zero.shape[0] if live is None else live.sum()
        n_zero = counted.sum()
        choices = jnp.stack([n_live * cfg.top_k - n_zero, n_zero]).astype(jnp.int32)
    return out, sizes, choices


# ----------------------------------------------------------- the layer loop
def rope_tables(cfg: LongcatFlashConfig, span: int):
    return rope_frequencies(cfg.qk_rope_head_dim, span, cfg.rope_theta)


def run_layers(params, x, carry, cfg: LongcatFlashConfig, mixer: Callable,
               experts: Optional[Callable] = None):
    """x (..., d) through every double layer in order. `mixer(sublayer's
    params, its index among the 2 n_layers (its plane of the cache), normed x,
    carry) -> (attention output, carry)`; `experts(expert layer's params,
    normed rows (N, d), carry) -> (the shortcut branch's output, carry)`, by
    default the expert layer over every row. The block around them is the
    same for the full forward, the admission and the decode step; `e` is held
    across the second half of the layer in each."""
    if experts is None:
        experts = lambda p, m, carry: (moe_ffn(m, p, cfg)[0], carry)  # noqa: E731
    own = {k: v for k, v in params[MOE].items() if k != "experts"}

    def half(x, carry, s):
        """h = x + Attn_s(N_a x) for sublayer s: (h, N_f h, carry)."""
        layer = _layer_at(params["layers"], s)
        o, carry = mixer(layer, s, rms_norm(x, layer["attn_norm"], cfg.rms_eps), carry)
        h = x + o
        return h, rms_norm(h, layer["ffn_norm"], cfg.rms_eps), carry

    def dense(m, s):
        with jax.named_scope(SCOPE_DENSE):
            return swiglu(m, _layer_at(params[DENSE], s), cfg)

    def body(c, i):
        x, carry = c
        h1, m, carry = half(x, carry, 2 * i)
        # the experts stay stacked: afmoe.expert_ffn says why
        p = {**_layer_at(own, i), "experts": params[MOE]["experts"], "at": i}
        e, carry = experts(p, m.reshape(-1, cfg.d_model), carry)
        h2 = h1 + dense(m, 2 * i)
        h3, m, carry = half(h2, carry, 2 * i + 1)
        return (h3 + dense(m, 2 * i + 1) + e.reshape(m.shape), carry), None

    (x, carry), _ = jax.lax.scan(body, (x, carry), jnp.arange(cfg.n_layers))
    return x, carry


def embed_tokens(params, tokens, cfg: LongcatFlashConfig):
    return params["embed"][tokens].astype(cfg.dtype)


# ------------------------------------------------------------ full forward
def forward(params, tokens, cfg: LongcatFlashConfig):
    """Logits (R, T, V) float32 of token rows (R, T): the whole-sequence
    pass, no cache, attention the expanded way."""
    cos, sin = rope_tables(cfg, tokens.shape[1])

    def mixer(layer, _, a, carry):
        return sarvam_mla.sequence_mixer(layer, a, cos, sin, cfg)[0], carry

    x, _ = run_layers(params, embed_tokens(params, tokens, cfg), (), cfg, mixer)
    return logits_of(params, x, cfg)
