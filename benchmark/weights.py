"""Seed-made weights, built on the device in one jitted call.

The stock `models.llama.init_params` runs eagerly: dozens of small programs,
85 s cold and 20 s warm at these widths (PERF.md, PR 24). The benchmark's own
files may shorten set-up that serves no request, so the serve replica and the
trainer are both handed these. The distribution is the stock one (normal
scaled by fan_in^-0.5, norms 1, bf16) so the activations are the same size;
the values are the benchmark's own: the reference regenerates them from the
seed and takes nothing the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """--seed may pass 2**31: fold it to 32 unsigned bits for the key."""
    return jax.random.PRNGKey(jnp.uint32(int(seed) % (1 << 32)))


def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)


def part_keys(key, cfg):
    """(embedding key, one key a layer, output head key)."""
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    return k_embed, jax.random.split(k_layers, cfg.n_layers), k_out


def make_layer(k, cfg):
    d, h, kvh, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    ks = jax.random.split(k, 7)
    return {
        "attn_norm": jnp.ones((d,), cfg.dtype),
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wo": _dense(ks[3], (h * hd, d), h * hd, cfg.dtype),
        "mlp_norm": jnp.ones((d,), cfg.dtype),
        "w_gate": _dense(ks[4], (d, f), d, cfg.dtype),
        "w_up": _dense(ks[5], (d, f), d, cfg.dtype),
        "w_down": _dense(ks[6], (f, d), f, cfg.dtype),
    }


def make_embed(k, cfg):
    return _dense(k, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype)


def make_lm_head(k, cfg):
    return _dense(k, (cfg.d_model, cfg.vocab_size), cfg.d_model, cfg.dtype)


def _init(key, cfg):
    k_embed, layer_keys, k_out = part_keys(key, cfg)
    # one layer at a time, so the generator's 32-bit scratch is one layer's
    layers = jax.lax.map(functools.partial(make_layer, cfg=cfg), layer_keys)
    return {
        "embed": make_embed(k_embed, cfg),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": make_lm_head(k_out, cfg),
    }


@functools.lru_cache(maxsize=8)
def _jitted_init(cfg):
    return jax.jit(functools.partial(_init, cfg=cfg))


def init_params(key, cfg):
    """Same signature as models.llama.init_params; one device program."""
    return _jitted_init(cfg)(key)


# --- the lower-precision control (never used by a benchmark run) -------------
def round_to_fewer_bits(params, kind: str):
    """The weights the SYSTEM is given in the control: every matrix rounded
    to `kind` and cast back to its own type. The reference keeps the
    unrounded ones. `int8` is symmetric with one scale an output column, the
    gentler of the two and so the one the limits are set against. `fp8` keeps
    e4m3's 3 mantissa bits, rounded by arithmetic: a cast to float8_e4m3fn and
    back is folded away by the v5e's compiler, which has no such type (my chip
    run, PR 26: the cast-made control read like a sound run)."""

    def one(w):
        if w.ndim < 2:
            return w
        wf = w.astype(jnp.float32)
        if kind == "fp8":
            m, e = jnp.frexp(wf)  # wf = m * 2**e, 0.5 <= |m| < 1
            q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
        elif kind == "int8":
            scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 127.0
            q = jnp.round(wf / scale) * scale
        else:
            raise ValueError(f"unknown lower precision {kind!r}")
        return q.astype(w.dtype)

    # donated: at the serve depth a second copy of the weights does not fit
    return jax.jit(lambda p: jax.tree.map(one, p), donate_argnums=(0,))(params)
