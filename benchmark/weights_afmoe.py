"""Seed-made weights of the AFMoE configuration (window and full attention
layers, a dense leading FFN, then sigmoid-routed experts beside a shared
one), built on the device in one jitted call, as `weights.py` does for the
dense decoder.

The values are the benchmark's own: this file imports nothing of the program,
the serve replica is handed `init_params`' tree and the reference regenerates
the same layers, and inside an expert layer the same experts one at a time,
from the same seed. The tree's shape is the program's
(`ray_tpu/models/afmoe.py`): `layers` stacked over all layers (four norms and
the attention), `dense` over the dense FFNs, `moe` over the expert layers
(router, choice bias, experts stacked on a second axis, shared expert).

Distribution: matrices normal x fan_in^-0.5 in bfloat16 as `weights.py` has
them; norms 1; the router's choice bias normal x BIAS_STD in float32. The
source keeps that bias as a buffer that training moves and a fresh model
holds at zero; at zero it would be exercised by nothing, so the seed draws it
at about five times the gap between neighbouring scores near the eighth of
128: most tokens' choices then differ from what the scores alone would give,
and a system that let the bias into the weights, or left it out of the
choice, reads wrong. `cfg` is any object with the program config's field
names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.weights import _dense, seed_key  # noqa: F401  (seed_key: for the drivers)

F32 = jnp.float32
DENSE, MOE = "dense", "moe"
BIAS_STD = 0.02
# the leaves the lower-precision control rounds: the matrices a token is
# multiplied with. Norms and the choice bias stay as they are
MATRICES = ("embed", "lm_head", "wq", "wk", "wv", "wg", "wo", "router",
            "w_gate", "w_up", "w_down")


def counts(cfg):
    """(layers, dense FFNs, expert layers)."""
    n = len(cfg.layer_types)
    return n, cfg.n_dense_layers, n - cfg.n_dense_layers


def part_keys(key, cfg):
    """(embedding key, head key, one key a layer, a dense FFN, an expert layer)."""
    n, n_d, n_m = counts(cfg)
    k_embed, k_head, k_l, k_d, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, n), jax.random.split(k_d, n_d),
            jax.random.split(k_m, n_m))


def make_layer(k, cfg):
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 5)
    one = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    return {
        "attn_norm": one(d), "attn_post_norm": one(d),
        "ffn_norm": one(d), "ffn_post_norm": one(d),
        "q_norm": one(hd), "k_norm": one(hd),
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wg": _dense(ks[3], (d, h * hd), d, cfg.dtype),
        "wo": _dense(ks[4], (h * hd, d), h * hd, cfg.dtype),
    }


def make_swiglu(k, d, f, dtype):
    ks = jax.random.split(k, 3)
    return {"w_gate": _dense(ks[0], (d, f), d, dtype),
            "w_up": _dense(ks[1], (d, f), d, dtype),
            "w_down": _dense(ks[2], (f, d), f, dtype)}


def make_dense_ffn(k, cfg):
    return make_swiglu(k, cfg.d_model, cfg.d_ff, cfg.dtype)


def make_expert(k, cfg):
    return make_swiglu(k, cfg.d_model, cfg.moe_d_ff, cfg.dtype)


def moe_keys(k, cfg):
    """(router key, bias key, one key an expert, shared expert's key)."""
    k_r, k_b, k_e, k_s = jax.random.split(k, 4)
    return k_r, k_b, jax.random.split(k_e, cfg.n_experts), k_s


def make_router(k_r, k_b, cfg):
    """(router (d, E) in the served type, choice bias (E,) float32)."""
    return (_dense(k_r, (cfg.d_model, cfg.n_experts), cfg.d_model, cfg.dtype),
            BIAS_STD * jax.random.normal(k_b, (cfg.n_experts,), F32))


def make_shared(k_s, cfg):
    return make_swiglu(k_s, cfg.d_model, cfg.moe_d_ff * cfg.n_shared_experts, cfg.dtype)


def make_moe(k, cfg):
    k_r, k_b, k_e, k_s = moe_keys(k, cfg)
    router, bias = make_router(k_r, k_b, cfg)
    # one expert at a time, so the generator's 32-bit scratch is one expert's
    return {"router": router, "bias": bias,
            "experts": jax.lax.map(functools.partial(make_expert, cfg=cfg), k_e),
            "shared": make_shared(k_s, cfg)}


def make_embed(k, cfg):
    return _dense(k, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype)


def make_lm_head(k, cfg):
    return _dense(k, (cfg.d_model, cfg.vocab_size), cfg.d_model, cfg.dtype)


def _init(key, cfg):
    k_embed, k_head, k_l, k_d, k_m = part_keys(key, cfg)
    return {
        "embed": make_embed(k_embed, cfg),
        "layers": jax.lax.map(functools.partial(make_layer, cfg=cfg), k_l),
        DENSE: jax.lax.map(functools.partial(make_dense_ffn, cfg=cfg), k_d),
        MOE: jax.lax.map(functools.partial(make_moe, cfg=cfg), k_m),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
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
