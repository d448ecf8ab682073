"""Seed-made weights of the `qwen3_next` configuration (gated-delta-rule
linear-attention layers with a gated full-attention layer every fourth, in
every layer softmax-routed experts beside a gated shared one), built on the
device in one jitted call, as `weights.py` does for the dense decoder.

The values are the benchmark's own: this file imports nothing of the program.
The serve replica is handed `init_params`' tree; the reference regenerates the
same layers, and inside an expert layer the same experts one at a time, from
the same seed. The tree's shape is the program's (`ray_tpu/models/
qwen3_next.py`): a stacked leading axis per kind of mixer (`linear_attention`,
`full_attention`) and one over all layers for the expert layers (`moe`: the
norm before it, the router, the HELD experts stacked on a second axis, the
shared expert and its gate).

An expert's weights come from the key of its index among the ROUTER's experts
(`moe_keys` draws one key for each of the 512): a program that holds experts
128-255 holds the same matrices as the 129th to 256th of a program that holds
them all, so the four shares of a layer add up to the layer.

Distribution: matrices normal x fan_in^-0.5 in the served type, as
`weights.py` has them, the embedding and the untied head among them (no
token's own logit stands out: the two matrices are independent); the
zero-centred norms' offsets 0 and the gated head norm's scale 1; the shared
expert's gate vector normal x fan_in^-0.5; and the linear mixer's small
parameters as Mamba-2 draws its own (`weights_granite_hybrid.py`), so that a
state neither dies nor stands still over the cell's 4,288 positions:
`A_log = log(uniform[1, 16])`, `dt_bias = softplus^-1(log-uniform[1e-3,
1e-1])`, conv weights uniform in +-(taps)^-0.5, no conv bias. `cfg` is any
object with the program config's field names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.weights import _dense, make_embed, make_lm_head, seed_key  # noqa: F401
from benchmark.weights_afmoe import make_expert, make_swiglu
from benchmark.weights_sarvam_mla import held_keys

F32 = jnp.float32
LINEAR, FULL, MOE = "linear_attention", "full_attention", "moe"
# the leaves the lower-precision control rounds: the matrices a token is
# multiplied with. Stacked vectors (norms, dt_bias, A_log, conv, the shared
# expert's gate) stay as they are
MATRICES = ("embed", "lm_head", "in_proj", "ba_proj", "out_proj", "wq", "wk", "wv", "wg", "wo",
            "router", "w_gate", "w_up", "w_down")


def layer_types(cfg):
    return tuple(FULL if (i + 1) % cfg.full_attention_interval == 0 else LINEAR
                 for i in range(cfg.n_layers))


def part_keys(key, cfg):
    """(embedding key, head key, one key a linear mixer, an attention mixer,
    an expert layer)."""
    n_f = layer_types(cfg).count(FULL)
    k_embed, k_head, k_l, k_f, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, cfg.n_layers - n_f),
            jax.random.split(k_f, n_f), jax.random.split(k_m, cfg.n_layers))


def make_linear_layer(k, cfg):
    d, H, K = cfg.d_model, cfg.lin_v_heads, cfg.lin_conv
    conv_dim = 2 * cfg.lin_k_heads * cfg.lin_k_dim + H * cfg.lin_v_dim
    di = H * cfg.lin_v_dim
    ks = jax.random.split(k, 6)
    dt = jnp.exp(jax.random.uniform(ks[2], (H,), F32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "norm": jnp.zeros((d,), cfg.dtype),
        # the source's interleaved in_proj_qkvz and in_proj_ba, de-interleaved
        # as the program keeps them: [q | k | v | z] and [b | a]
        "in_proj": _dense(ks[0], (d, conv_dim + di), d, cfg.dtype),
        "ba_proj": _dense(ks[5], (d, 2 * H), d, cfg.dtype),
        "conv_w": jax.random.uniform(
            ks[1], (K, conv_dim), F32, -(K ** -0.5), K ** -0.5).astype(cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), F32, 1.0, 16.0)),
        "head_norm": jnp.ones((cfg.lin_v_dim,), cfg.dtype),
        "out_proj": _dense(ks[4], (di, d), di, cfg.dtype),
    }


def make_full_layer(k, cfg):
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 5)
    return {
        "norm": jnp.zeros((d,), cfg.dtype),
        "q_norm": jnp.zeros((hd,), cfg.dtype), "k_norm": jnp.zeros((hd,), cfg.dtype),
        # the source's q_proj, [q | gate] a head, as the program keeps it
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wg": _dense(ks[3], (d, h * hd), d, cfg.dtype),
        "wo": _dense(ks[4], (h * hd, d), h * hd, cfg.dtype),
    }


def moe_keys(k, cfg):
    """(router key, one key a ROUTER's expert, shared expert's key, its gate's)."""
    k_r, k_e, k_s, k_g = jax.random.split(k, 4)
    return k_r, jax.random.split(k_e, cfg.n_experts), k_s, k_g


def make_router(k_r, cfg):
    return _dense(k_r, (cfg.d_model, cfg.n_experts), cfg.d_model, cfg.dtype)


def make_shared(k_s, cfg):
    return make_swiglu(k_s, cfg.d_model, cfg.shared_d_ff, cfg.dtype)


def make_shared_gate(k_g, cfg):
    return _dense(k_g, (cfg.d_model,), cfg.d_model, cfg.dtype)


def make_moe(k, cfg):
    k_r, k_e, k_s, k_g = moe_keys(k, cfg)
    # one expert at a time, so the generator's 32-bit scratch is one expert's
    return {"norm": jnp.zeros((cfg.d_model,), cfg.dtype),
            "router": make_router(k_r, cfg),
            "experts": jax.lax.map(functools.partial(make_expert, cfg=cfg), held_keys(k_e, cfg)),
            "shared": make_shared(k_s, cfg),
            "shared_gate": make_shared_gate(k_g, cfg)}


def _init(key, cfg):
    k_embed, k_head, k_l, k_f, k_m = part_keys(key, cfg)
    return {
        "embed": make_embed(k_embed, cfg),
        LINEAR: jax.lax.map(functools.partial(make_linear_layer, cfg=cfg), k_l),
        FULL: jax.lax.map(functools.partial(make_full_layer, cfg=cfg), k_f),
        MOE: jax.lax.map(functools.partial(make_moe, cfg=cfg), k_m),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
        "lm_head": make_lm_head(k_head, cfg),
    }


@functools.lru_cache(maxsize=8)
def _jitted_init(cfg):
    return jax.jit(functools.partial(_init, cfg=cfg))


def init_params(key, cfg):
    """Same signature as the program's init_params; one device program."""
    return _jitted_init(cfg)(key)


def round_to_fewer_bits(params, kind: str):
    """The control's weights: `weights.round_to_fewer_bits` over each matrix
    (MATRICES) in turn, one call a leaf so that no more than one leaf's
    float32 scratch is alive beside the weights."""

    def walk(tree):
        return {k: walk(v) if isinstance(v, dict)
                else weights.round_to_fewer_bits({k: v}, kind)[k] if k in MATRICES else v
                for k, v in tree.items()}

    return walk(params)
