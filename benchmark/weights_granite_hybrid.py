"""Seed-made weights of the hybrid (Mamba-2 + attention) configuration, built
on the device in one jitted call, as `weights.py` does for the dense decoder.

The values are the benchmark's own: this file imports nothing of the program,
the serve replica is handed `init_params`' tree and the reference regenerates
the same layers from the same seed, one at a time. The tree's shape is the
program's (`ray_tpu/models/granite_hybrid.py`): one stacked leading axis per
kind of layer (`mamba`, `attention`) and one over all layers for the MLPs.

Distribution: matrices normal x fan_in^-0.5 in bfloat16 as `weights.py` has
them (the tied embedding divided by `embedding_multiplier` besides, see
`make_embed`); norms 1; and the Mamba-2 layer as Mamba-2 itself initialises it, so that
states neither die nor blow up over the cell's 768 positions: `D` 1,
`A_log = log(uniform[1, 16])`, `dt_bias = softplus^-1(log-uniform[1e-3, 1e-1])`,
conv weights uniform in +-(taps)^-0.5, conv bias 0. `cfg` is any object with
the program config's field names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.weights import _dense, seed_key  # noqa: F401  (seed_key: for the drivers)

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"
# the leaves the lower-precision control rounds: the matrices a token is
# multiplied with. Stacked vectors (norms, dt_bias, A_log, D, conv) stay as they are
MATRICES = ("embed", "in_proj", "dt_proj", "out_proj", "wq", "wk", "wv", "wo", "w_in", "w_out")


def counts(cfg):
    n_m = sum(1 for t in cfg.layer_types if t == MAMBA)
    return n_m, len(cfg.layer_types) - n_m


def part_keys(key, cfg):
    """(embedding key, one key a Mamba layer, an attention layer, an MLP)."""
    n_m, n_a = counts(cfg)
    k_embed, k_m, k_a, k_f = jax.random.split(key, 4)
    return (k_embed, jax.random.split(k_m, n_m), jax.random.split(k_a, n_a),
            jax.random.split(k_f, n_m + n_a))


def make_mamba_layer(k, cfg):
    d, H, K, N = cfg.d_model, cfg.mamba_n_heads, cfg.mamba_d_conv, cfg.mamba_d_state
    di = H * cfg.mamba_d_head
    conv_dim = di + 2 * cfg.mamba_n_groups * N
    ks = jax.random.split(k, 6)
    dt = jnp.exp(jax.random.uniform(ks[2], (H,), F32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "norm": jnp.ones((d,), cfg.dtype),
        # the source's one input projection [z | xBC | dt], as the program
        # keeps it: [z | xBC] and the H columns of dt apart
        "in_proj": _dense(ks[0], (d, di + conv_dim), d, cfg.dtype),
        "dt_proj": _dense(ks[5], (d, H), d, cfg.dtype),
        "conv_w": jax.random.uniform(
            ks[1], (K, conv_dim), F32, -(K ** -0.5), K ** -0.5).astype(cfg.dtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), F32, 1.0, 16.0)),
        "D": jnp.ones((H,), F32),
        "gate_norm": jnp.ones((di,), cfg.dtype),
        "out_proj": _dense(ks[4], (di, d), di, cfg.dtype),
    }


def make_attn_layer(k, cfg):
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 4)
    return {
        "norm": jnp.ones((d,), cfg.dtype),
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wo": _dense(ks[3], (h * hd, d), h * hd, cfg.dtype),
    }


def make_mlp(k, cfg):
    d, f = cfg.d_model, cfg.d_ff
    k_in, k_out = jax.random.split(k)
    return {
        "norm": jnp.ones((d,), cfg.dtype),
        "w_in": _dense(k_in, (d, 2 * f), d, cfg.dtype),  # [gate | value]
        "w_out": _dense(k_out, (f, d), f, cfg.dtype),
    }


def make_embed(k, cfg):
    """The one tied matrix: the embedding and, transposed, the output head.
    Normal x fan_in^-0.5 / embedding_multiplier, so that x_0 = multiplier x
    E[token] has the norm a row of any other matrix has. At fan_in^-0.5 alone
    the tied head drowns everything else: the token's own logit (multiplier x
    |E[token]|^2) stood 11.6 standard deviations above the rest, the model
    repeated its last input token for ever, no emitted token was ever a near
    tie and the comparison with the reference read 0 in every run, whatever
    the precision (my chip run, PR 29)."""
    return _dense(k, (cfg.vocab_size, cfg.d_model), cfg.d_model * cfg.embedding_multiplier ** 2,
                  cfg.dtype)


def _init(key, cfg):
    k_embed, k_m, k_a, k_f = part_keys(key, cfg)
    # one layer at a time, so the generator's 32-bit scratch is one layer's
    return {
        "embed": make_embed(k_embed, cfg),
        MAMBA: jax.lax.map(functools.partial(make_mamba_layer, cfg=cfg), k_m),
        ATTENTION: jax.lax.map(functools.partial(make_attn_layer, cfg=cfg), k_a),
        "mlp": jax.lax.map(functools.partial(make_mlp, cfg=cfg), k_f),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
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
