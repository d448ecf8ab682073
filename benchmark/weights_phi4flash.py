"""Seed-made weights of the Phi-4-mini-flash configuration (Mamba-1, window
and full differential attention, gated memory units, cross attention), built
on the device in one jitted call, as `weights.py` does for the dense decoder.

The values are the benchmark's own: this file imports nothing of the program,
the serve replica is handed `init_params`' tree and the reference regenerates
the same layers from the same seed, one at a time. The tree's shape is the
program's (`ray_tpu/models/phi4flash.py`): one stacked leading axis per kind
of layer (`mamba`; `attn`, the window layers then the full one; `gmu`;
`cross`) and one over all layers for the MLPs. `x_proj` is kept transposed
(dt_rank + 2 N, d_inner) and `A_log` (N, d_inner): the program's TPU layouts,
the same numbers.

Distribution: matrices normal x fan_in^-0.5 in bfloat16 as `weights.py` has
them; LayerNorm weights 1 and biases 0; the attention's biases normal x 0.02
(a bias of 0 would hide a bias left out); the four `lambda` vectors of a
differential attention normal(0, 0.1) as the source draws them and its
sub-norm's weight 1; the Mamba-1 layer as Mamba itself initialises it, so
that states neither die nor blow up over the cell's 1,536 positions: `D` 1,
`A_log = log(1 .. N)` in every channel, `dt_bias = softplus^-1(log-uniform[1e-3,
1e-1])`, `dt_proj` uniform in +-dt_rank^-0.5, conv weights and bias uniform in
+-(taps)^-0.5. The tied matrix is drawn at fan_in^-0.5 like the others
(`make_embed` says why that is enough here). `cfg` is any object with the
program config's field names.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.weights import _dense, seed_key  # noqa: F401  (seed_key: for the drivers)

F32 = jnp.float32
MAMBA, ATTN, GMU, CROSS, MLP = "mamba", "attn", "gmu", "cross", "mlp"
# the leaves the lower-precision control rounds: the matrices a token is
# multiplied with. Stacked vectors (norms, biases, dt_bias, A_log, D, conv,
# lambdas) stay as they are
MATRICES = ("embed", "in_proj", "x_proj", "dt_proj", "out_proj", "wqkv", "wq", "wo", "fc1", "fc2")


def counts(cfg):
    """(Mamba layers, window + full attention layers, memory units = cross layers)."""
    half = cfg.n_layers // 2
    return half // 2 + 1, half // 2 + 1, (cfg.n_layers - half - 2) // 2


def part_keys(key, cfg):
    """(embedding key, one key a Mamba layer, an attention layer, a memory
    unit, a cross layer, an MLP)."""
    n_m, n_a, n_c = counts(cfg)
    k_embed, k_m, k_a, k_g, k_c, k_f = jax.random.split(key, 6)
    return (k_embed, jax.random.split(k_m, n_m), jax.random.split(k_a, n_a),
            jax.random.split(k_g, n_c), jax.random.split(k_c, n_c),
            jax.random.split(k_f, cfg.n_layers))


def _norm(cfg):
    return {"norm_w": jnp.ones((cfg.d_model,), cfg.dtype),
            "norm_b": jnp.zeros((cfg.d_model,), cfg.dtype)}


def _uniform(key, shape, limit, dtype):
    return jax.random.uniform(key, shape, F32, -limit, limit).astype(dtype)


def make_mamba_layer(k, cfg):
    d, N, K, r = cfg.d_model, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    di = cfg.mamba_expand * d
    ks = jax.random.split(k, 7)
    dt = jnp.exp(jax.random.uniform(ks[4], (di,), F32, math.log(1e-3), math.log(1e-1)))
    return {
        **_norm(cfg),
        "in_proj": _dense(ks[0], (d, 2 * di), d, cfg.dtype),                # [x | z]
        "conv_w": _uniform(ks[1], (K, di), K ** -0.5, cfg.dtype),
        "conv_b": _uniform(ks[2], (di,), K ** -0.5, cfg.dtype),
        "x_proj": _dense(ks[3], (r + 2 * N, di), di, cfg.dtype),            # [dt_r | B | C], transposed
        "dt_proj": _uniform(ks[5], (r, di), r ** -0.5, cfg.dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),                            # softplus^-1(dt)
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=F32))[:, None], (N, di)),
        "D": jnp.ones((di,), F32),
        "out_proj": _dense(ks[6], (di, d), di, cfg.dtype),
    }


def _diff_params(ks, cfg):
    out = {name: 0.1 * jax.random.normal(k, (cfg.head_dim,), F32)
           for name, k in zip(("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"), ks)}
    out["subln"] = jnp.ones((2 * cfg.head_dim,), cfg.dtype)
    return out


def _bias(key, n, cfg):
    return (0.02 * jax.random.normal(key, (n,), F32)).astype(cfg.dtype)


def make_attn_layer(k, cfg):
    d, hq, hkv = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(k, 8)
    return {
        **_norm(cfg),
        "wqkv": _dense(ks[0], (d, hq + 2 * hkv), d, cfg.dtype),             # [q | k | v]
        "bqkv": _bias(ks[1], hq + 2 * hkv, cfg),
        "wo": _dense(ks[2], (hq, d), hq, cfg.dtype),
        "bo": _bias(ks[3], d, cfg),
        **_diff_params(ks[4:], cfg),
    }


def make_cross_layer(k, cfg):
    d, hq = cfg.d_model, cfg.n_heads * cfg.head_dim
    ks = jax.random.split(k, 8)
    return {
        **_norm(cfg),
        "wq": _dense(ks[0], (d, hq), d, cfg.dtype),
        "bq": _bias(ks[1], hq, cfg),
        "wo": _dense(ks[2], (hq, d), hq, cfg.dtype),
        "bo": _bias(ks[3], d, cfg),
        **_diff_params(ks[4:], cfg),
    }


def make_gmu_layer(k, cfg):
    d = cfg.d_model
    di = cfg.mamba_expand * d
    k_in, k_out = jax.random.split(k)
    return {**_norm(cfg), "in_proj": _dense(k_in, (d, di), d, cfg.dtype),
            "out_proj": _dense(k_out, (di, d), di, cfg.dtype)}


def make_mlp(k, cfg):
    d, f = cfg.d_model, cfg.d_ff
    k_in, k_out = jax.random.split(k)
    return {**_norm(cfg), "fc1": _dense(k_in, (d, 2 * f), d, cfg.dtype),   # [u | g]
            "fc2": _dense(k_out, (f, d), f, cfg.dtype)}


def make_embed(k, cfg):
    """The one tied matrix: the embedding and, transposed, the output head,
    normal x fan_in^-0.5 as every other matrix. Every layer norms its input
    and the head reads a LayerNorm of the stream, so no token's own logit
    stands out (PR 29's hybrid needed its matrix divided by 12 for that: here a
    sound run on the chip does not read exactly 0, PERF.md section 2)."""
    return _dense(k, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype)


def _init(key, cfg):
    k_embed, k_m, k_a, k_g, k_c, k_f = part_keys(key, cfg)
    # one layer at a time, so the generator's 32-bit scratch is one layer's
    stack = lambda make, ks: jax.lax.map(functools.partial(make, cfg=cfg), ks)  # noqa: E731
    return {
        "embed": make_embed(k_embed, cfg),
        MAMBA: stack(make_mamba_layer, k_m),
        ATTN: stack(make_attn_layer, k_a),
        GMU: stack(make_gmu_layer, k_g),
        CROSS: stack(make_cross_layer, k_c),
        MLP: stack(make_mlp, k_f),
        "final_norm_w": jnp.ones((cfg.d_model,), cfg.dtype),
        "final_norm_b": jnp.zeros((cfg.d_model,), cfg.dtype),
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
