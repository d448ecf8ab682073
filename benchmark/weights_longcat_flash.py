"""Seed-made weights of the LongCat-Flash configuration (a double layer of
two latent attentions with a compressed query and two dense FFNs, softmax-
routed experts of which some are identity experts without weights), built on
the device in one jitted call, as `weights.py` does for the dense decoder.

The values are the benchmark's own: this file imports nothing of the program.
The serve replica is handed `init_params`' tree; the reference regenerates the
same sublayers, and inside an expert layer the same experts one at a time,
from the same seed. The tree's shape is the program's (`ray_tpu/models/
longcat_flash.py`): `layers` stacked over the 2 x n_layers SUBLAYERS (two
norms, the compressed query's norm and the latent's, W_qa, W_qb, W_kv_a,
W_kv_b as its two halves `w_uk` (heads, nope, latent) and `w_uv` (heads,
latent, v), Wo), `dense` over the same (the dense FFNs), `moe` over the layers
(the router over real and identity experts, its choice bias, the HELD real
experts stacked on a second axis).

An expert's weights come from the key of its index among the REAL experts
(`n_routed_experts`): a program that holds experts 16-31 of 512 holds the same
matrices as the 17th to 32nd of a program that holds them all, so the shares
of a layer add up to the layer. An identity expert has no weights.

Distribution as `weights_sarvam_mla.py` has it but for the size of the bias
and for the three matrices that lead OUT of the two compressed spaces: matrices
normal x fan_in^-0.5 in the served type, norms 1, the router's choice bias
normal x BIAS_STD in float32; W_qb, W_uk and W_uv normal x d_model^-0.5
(`make_sublayer` says why). `cfg` is any object with the program config's
field names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.weights import _dense, seed_key  # noqa: F401  (seed_key: for the drivers)
from benchmark.weights_afmoe import (  # noqa: F401  the FFN half's generators are that file's
    DENSE, MOE, make_dense_ffn, make_embed, make_expert, make_lm_head)

# The router's choice bias: an ASSUMPTION of this configuration (the file's
# `assumed.routing`), drawn as `weights_sarvam_mla` draws it and for its
# reason: the source's buffer stands for a BALANCED load, a drawn one can only
# unbalance a seed-made router, so its size is set against the spacing of the
# scores it perturbs. The scores are a softmax over 768 logits of unit
# variance: the twelfth largest of 768 stands near 0.0068 and its neighbours
# 0.00022 from it (768 phi(2.15) = 30 scores a unit of logit there). At
# 1.2e-4, a little over half that spacing as sarvam's 0.005 is of 0.009, the
# bias decides the twelfth choice for about half of the rows and moves no
# expert's load by more than a few percent.
BIAS_STD = 1.2e-4
# the leaves the lower-precision control rounds: the matrices a token is
# multiplied with. Norms and the choice bias stay as they are
MATRICES = ("embed", "lm_head", "w_qa", "w_qb", "w_kv_a", "w_uk", "w_uv", "wo", "router",
            "w_gate", "w_up", "w_down")


def part_keys(key, cfg):
    """(embedding key, head key, one key a sublayer, a dense FFN, an expert layer)."""
    k_embed, k_head, k_l, k_d, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, 2 * cfg.n_layers),
            jax.random.split(k_d, 2 * cfg.n_layers), jax.random.split(k_m, cfg.n_layers))


def make_sublayer(k, cfg):
    """One attention with its half-layer's norms. W_qb, W_uk and W_uv, the
    matrices that lead out of the compressed query and the latent, are
    drawn as if their fan-in were the hidden size. That is the
    initialisation `mla_scale_q_lora` / `mla_scale_kv_lora` are made for: the
    family's report gives the two scales, (d_model / rank)^0.5, as the correction
    that aligns the variance of the low-rank paths (q, k_nope, v, proportional to
    the rank under one std for every matrix) with that of k_r, which reads the
    hidden stream (proportional to d_model). At d_model^-0.5 the scaled q, k_nope
    and v come out at unit variance beside k_r and the scores' spread is 1. At
    rank^-0.5 they would be 2 and 3.46 times that, the scores' spread 5.7 and
    every softmax all but one-hot, which no trained model's is: the first chip
    run read a sound logit gap of 0.39 with 70 % of the tokens flipped (my chip
    run, PR 45), a hard attention carrying bfloat16's rounding from choice to
    choice, and could have told a lower precision from nothing."""
    d, h, r, rq = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(k, 6)
    one = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    return {
        "attn_norm": one(d), "ffn_norm": one(d), "q_a_norm": one(rq), "kv_norm": one(r),
        "w_qa": _dense(ks[0], (d, rq), d, cfg.dtype),
        "w_qb": _dense(ks[1], (rq, h * (nope + rope)), d, cfg.dtype),  # not rq
        "w_kv_a": _dense(ks[2], (d, r + rope), d, cfg.dtype),
        "w_uk": _dense(ks[3], (h, nope, r), d, cfg.dtype),            # not r
        "w_uv": _dense(ks[4], (h, r, v), d, cfg.dtype),               # not r
        "wo": _dense(ks[5], (h * v, d), h * v, cfg.dtype),
    }


def moe_keys(k, cfg):
    """(router key, bias key, one key a REAL expert)."""
    k_r, k_b, k_e = jax.random.split(k, 3)
    return k_r, k_b, jax.random.split(k_e, cfg.n_routed_experts)


def make_router(k_r, k_b, cfg):
    """(router (d, real + identity) in the served type, choice bias float32)."""
    E = cfg.n_routed_experts + cfg.n_zero_experts
    return (_dense(k_r, (cfg.d_model, E), cfg.d_model, cfg.dtype),
            BIAS_STD * jax.random.normal(k_b, (E,), jnp.float32))


def held_keys(k_e, cfg):
    """Of one key a real expert, those of the experts this share holds."""
    return k_e[cfg.held_first:cfg.held_first + cfg.held_count]


def make_moe(k, cfg):
    k_r, k_b, k_e = moe_keys(k, cfg)
    router, bias = make_router(k_r, k_b, cfg)
    # one expert at a time, so the generator's 32-bit scratch is one expert's
    return {"router": router, "bias": bias,
            "experts": jax.lax.map(functools.partial(make_expert, cfg=cfg), held_keys(k_e, cfg))}


def _init(key, cfg):
    k_embed, k_head, k_l, k_d, k_m = part_keys(key, cfg)
    return {
        "embed": make_embed(k_embed, cfg),
        "layers": jax.lax.map(functools.partial(make_sublayer, cfg=cfg), k_l),
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
