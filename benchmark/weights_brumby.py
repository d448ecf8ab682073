"""Seed-made weights of the Brumby configuration (a dense GQA decoder's block
with power retention of degree 2 in every layer), built on the device in one
jitted call, as `weights.py` does for the dense decoder.

The values are the benchmark's own: this file imports nothing of the program.
The serve replica is handed `init_params`' tree; the reference regenerates the
same matrices, one at a time, from the same seed. The tree's shape is the
program's (`ray_tpu/models/brumby.py`): every layer stacked on one leading
axis (`layers`), a layer its two norms, the head norms of q and k, `wq`, `wk`,
`wv`, `wo`, the gate's `wg` (d_model, KV heads) and bias `bg`, and the SwiGLU's
three matrices.

Distribution: matrices normal x fan_in^-0.5 in the served type, as
`weights.py` has them, the embedding and the untied head among them (the two
are independent, so no token's own logit stands out and a sound run's gaps
are not all exactly 0); every norm's weight 1; and the gate so that over the
cell's 2,560 positions a state neither dies nor stands still: `bg` =
logit(1 - 1 / h) with the horizon h log-uniform in [64, 4096] positions a KV
head, `wg` normal x fan_in^-0.5 like the others, so that a position's logit
moves about its head's bias by about one (at the published widths the median
decay a position reads 0.9981 and the smallest 0.876: my CPU reading, PR 52,
`gate_readings`). `cfg` is any object with the program config's field names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.weights import _dense, make_embed, make_lm_head, seed_key  # noqa: F401

F32 = jnp.float32
# the leaves the lower-precision control rounds: the matrices a token is
# multiplied with. Stacked vectors (norms, the gate's bias) stay as they are
MATRICES = ("embed", "lm_head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down")
HORIZON = (64.0, 4096.0)  # positions a KV head remembers, log-uniform


def part_keys(key, cfg):
    """(embedding key, head key, one key a layer)."""
    k_embed, k_head, k_l = jax.random.split(key, 3)
    return k_embed, k_head, jax.random.split(k_l, cfg.n_layers)


def matrix_shapes(cfg):
    """name -> (shape, fan_in) of a layer's matrices, in the order of their keys."""
    d, h, kvh, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    return {"wq": ((d, h * hd), d), "wk": ((d, kvh * hd), d), "wv": ((d, kvh * hd), d),
            "wo": ((h * hd, d), h * hd), "wg": ((d, kvh), d), "w_gate": ((d, f), d),
            "w_up": ((d, f), d), "w_down": ((f, d), f)}


def make_matrix(k, name: str, cfg):
    """One matrix of the layer whose key is `k`, alone (the reference makes
    them one at a time)."""
    shapes = matrix_shapes(cfg)
    shape, fan_in = shapes[name]
    return _dense(jax.random.split(k, len(shapes) + 1)[list(shapes).index(name)], shape, fan_in,
                  cfg.dtype)


def make_gate_bias(k, cfg):
    """`bg` (KV heads,) float32 of the layer whose key is `k`."""
    lo, hi = HORIZON
    horizon = jnp.exp(jax.random.uniform(jax.random.split(k, len(matrix_shapes(cfg)) + 1)[-1],
                                         (cfg.n_kv_heads,), F32, jnp.log(lo), jnp.log(hi)))
    return jnp.log(horizon - 1.0)


def make_layer(k, cfg):
    one = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    return {"attn_norm": one(cfg.d_model), "mlp_norm": one(cfg.d_model),
            "q_norm": one(cfg.head_dim), "k_norm": one(cfg.head_dim),
            **{name: make_matrix(k, name, cfg) for name in matrix_shapes(cfg)},
            "bg": make_gate_bias(k, cfg)}


def _init(key, cfg):
    k_embed, k_head, k_l = part_keys(key, cfg)
    return {
        "embed": make_embed(k_embed, cfg),
        # one layer at a time, so the generator's 32-bit scratch is one layer's
        "layers": jax.lax.map(functools.partial(make_layer, cfg=cfg), k_l),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": make_lm_head(k_head, cfg),
    }


@functools.lru_cache(maxsize=8)
def _jitted_init(cfg):
    return jax.jit(functools.partial(_init, cfg=cfg))


def init_params(key, cfg):
    """Same signature as the program's init_params; one device program."""
    return _jitted_init(cfg)(key)


def gate_readings(key, cfg, positions: int = 4096):
    """(median, smallest) decay a position over `positions` unit-RMS inputs of
    every layer's gate: what `assumed.small_parameters` quotes."""
    _, _, k_l = part_keys(key, cfg)
    a = jax.random.normal(jax.random.fold_in(key, 1), (positions, cfg.d_model), F32)
    g = jnp.stack([jax.nn.sigmoid(a @ make_matrix(k, "wg", cfg).astype(F32)
                                  + make_gate_bias(k, cfg)) for k in k_l])
    return float(jnp.median(g)), float(g.min())


def round_to_fewer_bits(params, kind: str):
    """The control's weights: `weights.round_to_fewer_bits` over each matrix
    (MATRICES) in turn, one call a leaf so that no more than one leaf's
    float32 scratch is alive beside the weights."""

    def walk(tree):
        return {k: walk(v) if isinstance(v, dict)
                else weights.round_to_fewer_bits({k: v}, kind)[k] if k in MATRICES else v
                for k, v in tree.items()}

    return walk(params)
