"""Seed-made weights of the latent-attention configuration (`sarvam_mla`: MLA
with no query compression, one leading dense layer, then sigmoid-routed
experts beside a shared one), built on the device in one jitted call, as
`weights.py` does for the dense decoder.

The values are the benchmark's own: this file imports nothing of the program.
The serve replica is handed `init_params`' tree; the reference regenerates the
same layers, and inside an expert layer the same experts one at a time, from
the same seed. The tree's shape is the program's (`ray_tpu/models/
sarvam_mla.py`): `layers` stacked over all layers (two norms, the three small
norms of the attention, Wq, W_kv_a, W_kv_b as its two halves `w_uk` (heads,
nope, latent) and `w_uv` (heads, latent, v), Wo), `dense` over the dense FFNs,
`moe` over the expert layers (router, choice bias, the HELD experts stacked on
a second axis, the shared expert).

An expert's weights come from the key of its index among the ROUTER's experts
(`weights_afmoe.moe_keys` draws one key for each of them): a program that
holds experts 32-63 of 128 holds the same matrices as the 33rd to 64th of a
program that holds them all, so the shares of a layer add up to the layer.

Distribution as `weights_afmoe.py` has it but for the size of the bias:
matrices normal x fan_in^-0.5 in the served type, norms 1, the router's choice
bias normal x BIAS_STD in float32 (at zero it would be exercised by nothing). `cfg` is any object with
the program config's field names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.weights import _dense, seed_key  # noqa: F401  (seed_key: for the drivers)
from benchmark.weights_afmoe import (  # noqa: F401  the FFN half's generators are that file's
    DENSE, MOE, make_dense_ffn, make_embed, make_expert, make_lm_head, make_shared, moe_keys)

# The router's choice bias: an ASSUMPTION of this configuration (the file's
# `assumed.routing`), not `weights_afmoe`'s 0.02. In the source it is the
# buffer that aux-loss-free training moves until every expert gets its share
# of the rows, so what it stands for is a BALANCED load: a chip that holds a
# quarter of the experts draws a quarter of the pairs. Seed-made router
# weights are balanced in expectation already; a drawn bias can only unbalance
# them, so its size is set against the spacing of the scores it perturbs.
# Neighbouring scores at the eighth of 128 lie 0.009 apart: at 0.005 the bias
# still decides the eighth choice for about half of the rows (it is exercised)
# and moves one expert's load by 7 % either way, this chip's quarter of the
# experts by 1.3 % from seed to seed (a count on the host over 16 seeds). At
# 0.02, where Trinity's program holds all 128 experts and their balance moves
# no figure, one expert's load moves by a quarter either way (the scores'
# density there is 0.85 a unit: 0.02 x 0.85 of a share of 8 / 128) and this
# chip's quarter by 3-4 % (my chip runs, PR 39). The limits of `correct` and
# every figure of PERF.md are read at 0.005. (Balancing the quarter's share
# exactly, columns at norm 1 and a bias that sums to zero over each share, was
# tried and moved no timing: PERF.md section 6.)
BIAS_STD = 0.005
# the leaves the lower-precision control rounds: the matrices a token is
# multiplied with. Norms and the choice bias stay as they are
MATRICES = ("embed", "lm_head", "wq", "w_kv_a", "w_uk", "w_uv", "wo", "router",
            "w_gate", "w_up", "w_down")


def part_keys(key, cfg):
    """(embedding key, head key, one key a layer, a dense FFN, an expert layer)."""
    n_m = cfg.n_layers - cfg.n_dense_layers
    k_embed, k_head, k_l, k_d, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, cfg.n_layers),
            jax.random.split(k_d, cfg.n_dense_layers), jax.random.split(k_m, n_m))


def make_layer(k, cfg):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(k, 5)
    one = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    return {
        "attn_norm": one(d), "ffn_norm": one(d), "kv_norm": one(r),
        "q_norm": one(nope + rope), "k_rope_norm": one(rope),
        "wq": _dense(ks[0], (d, h * (nope + rope)), d, cfg.dtype),
        "w_kv_a": _dense(ks[1], (d, r + rope), d, cfg.dtype),
        "w_uk": _dense(ks[2], (h, nope, r), r, cfg.dtype),
        "w_uv": _dense(ks[3], (h, r, v), r, cfg.dtype),
        "wo": _dense(ks[4], (h * v, d), h * v, cfg.dtype),
    }


def make_router(k_r, k_b, cfg):
    """(router (d, E) in the served type, choice bias (E,) float32)."""
    return (_dense(k_r, (cfg.d_model, cfg.n_experts), cfg.d_model, cfg.dtype),
            BIAS_STD * jax.random.normal(k_b, (cfg.n_experts,), jnp.float32))


def held_keys(k_e, cfg):
    """Of one key a router's expert, those of the experts this share holds."""
    return k_e[cfg.held_first:cfg.held_first + cfg.held_count]


def make_moe(k, cfg):
    k_r, k_b, k_e, k_s = moe_keys(k, cfg)
    router, bias = make_router(k_r, k_b, cfg)
    # one expert at a time, so the generator's 32-bit scratch is one expert's
    return {"router": router, "bias": bias,
            "experts": jax.lax.map(functools.partial(make_expert, cfg=cfg), held_keys(k_e, cfg)),
            "shared": make_shared(k_s, cfg)}


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
