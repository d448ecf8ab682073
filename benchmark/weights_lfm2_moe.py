"""Seed-made weights of the LFM2-MoE configuration (gated short-convolution
and grouped-query attention layers, a dense leading FFN, then sigmoid-routed
experts with a choice bias and no shared expert), built on the device in one
jitted call, as `weights.py` does for the dense decoder.

The values are the benchmark's own: this file imports nothing of the program,
the trainer is handed `init_params`' tree and the reference regenerates the
same layers one at a time from the same seed. The tree's shape is the
program's (`ray_tpu/models/lfm2_moe.py`): `embed`, `final_norm`, and one dict
a layer under `layers` (two norms, the mixer under `op`, the FFN under `ffn`;
an expert layer's `ffn` is the router, the choice bias and the HELD experts
stacked). The head is the embedding transposed: there is no head leaf.

Distribution: matrices normal x fan_in^-0.5 in bfloat16 as `weights.py` has
them (the convolution's taps: fan_in 3); norms 1; the router's choice bias
normal x BIAS_STD in float32. The source keeps that bias as a buffer that
training moves by a rule its config does not give: the seed draws it, and
nothing updates it. What such a bias stands for is a load in balance, and a
drawn one can only unbalance a seed-made router: at 0.005, a sixth of the gap
between neighbouring scores near the fourth of 32 (0.03), it is exercised (a
sixth of the rows' fourth choice moves) and this chip's quarter of the experts
draws within a few percent of its quarter of the pairs from seed to seed
(`weights_sarvam_mla`'s argument; at 0.02 the quarter would swing several times as far,
which a step's time follows). An
expert's matrices come from the key of its index among the router's experts,
so the four shares of a layer are pieces of one layer. `cfg` is any object
with the program config's field names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _dense, round_to_fewer_bits, seed_key  # noqa: F401  (for the driver)

F32 = jnp.float32
CONV, FULL = "conv", "full_attention"
DENSE, MOE = "dense", "moe"
BIAS_STD = 0.005


def kinds(cfg):
    """(mixer kind, FFN kind) of each layer, in order."""
    return tuple((op, DENSE if i < cfg.n_dense_layers else MOE)
                 for i, op in enumerate(cfg.layer_types))


def part_keys(key, cfg):
    """(embedding key, one key a layer)."""
    k_embed, k_layers = jax.random.split(key)
    return k_embed, jax.random.split(k_layers, len(cfg.layer_types))


def make_embed(k, cfg):
    return _dense(k, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype)


def make_op(k, kind, cfg):
    d, h, kvh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = d // h
    ks = jax.random.split(k, 4)
    if kind == CONV:
        return {"w_in": _dense(ks[0], (d, 3 * d), d, cfg.dtype),
                "conv": _dense(ks[1], (cfg.conv_taps, d), cfg.conv_taps, cfg.dtype),
                "w_out": _dense(ks[2], (d, d), d, cfg.dtype)}
    return {"wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
            "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
            "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
            "wo": _dense(ks[3], (h * hd, d), h * hd, cfg.dtype),
            "q_norm": jnp.ones((hd,), cfg.dtype), "k_norm": jnp.ones((hd,), cfg.dtype)}


def make_swiglu(k, d, f, dtype):
    ks = jax.random.split(k, 3)
    return {"w_gate": _dense(ks[0], (d, f), d, dtype), "w_up": _dense(ks[1], (d, f), d, dtype),
            "w_down": _dense(ks[2], (f, d), f, dtype)}


def make_expert(k_experts, e, cfg):
    """Expert `e` of the router's `n_experts`: one SwiGLU of width `moe_d_ff`."""
    return make_swiglu(jax.random.fold_in(k_experts, e), cfg.d_model, cfg.moe_d_ff, cfg.dtype)


def ffn_keys(k):
    """(router key, bias key, experts' key) of an expert layer's FFN key."""
    return jax.random.split(k, 3)


def make_ffn(k, kind, cfg):
    if kind == DENSE:
        return make_swiglu(k, cfg.d_model, cfg.d_ff, cfg.dtype)
    k_r, k_b, k_e = ffn_keys(k)
    first, count = cfg.held_experts
    # one expert at a time, so the generator's 32-bit scratch is one expert's
    return {"router": _dense(k_r, (cfg.d_model, cfg.n_experts), cfg.d_model, cfg.dtype),
            "bias": BIAS_STD * jax.random.normal(k_b, (cfg.n_experts,), F32),
            "experts": jax.lax.map(lambda e: make_expert(k_e, e, cfg), first + jnp.arange(count))}


def make_layer(k, kind, cfg):
    """Layer of `kind` = (mixer, FFN) from its key."""
    k_op, k_ffn = jax.random.split(k)
    return {"op_norm": jnp.ones((cfg.d_model,), cfg.dtype),
            "ffn_norm": jnp.ones((cfg.d_model,), cfg.dtype),
            "op": make_op(k_op, kind[0], cfg), "ffn": make_ffn(k_ffn, kind[1], cfg)}


def _init(key, cfg):
    k_embed, layer_keys = part_keys(key, cfg)
    return {"embed": make_embed(k_embed, cfg),
            "layers": [make_layer(k, kind, cfg) for k, kind in zip(layer_keys, kinds(cfg))],
            "final_norm": jnp.ones((cfg.d_model,), cfg.dtype)}


@functools.lru_cache(maxsize=8)
def _jitted_init(cfg):
    return jax.jit(functools.partial(_init, cfg=cfg))


def init_params(key, cfg):
    """Same signature as models.lfm2_moe.init_params; one device program."""
    return _jitted_init(cfg)(key)
