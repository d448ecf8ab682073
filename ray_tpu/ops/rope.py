"""Rotary position embeddings (RoPE).

Pure-XLA: rope is bandwidth-trivial and fuses into the surrounding
matmuls; a pallas kernel would buy nothing here (guide: let XLA fuse what
it already fuses).
"""
from __future__ import annotations

import math

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0, dtype=jnp.float32):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    t = jnp.arange(max_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)  # [T, half]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def yarn_frequencies(rot_dim: int, max_len: int, theta: float, factor: float,
                     original_max_len: int, beta_fast: float = 32.0, beta_slow: float = 1.0,
                     mscale: float = 1.0, mscale_all_dim: float = 0.0, dtype=jnp.float32):
    """`rope_frequencies` under YaRN (the `deepseek_yarn` rule): each pair's
    frequency a blend of theta^(-2i/d) and the same over `factor`. A pair that
    turns more than `beta_fast` times over `original_max_len` positions keeps
    its frequency, one that turns fewer than `beta_slow` times takes the
    divided one, linear in the pair's index between the two corrections. cos
    and sin carry yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim); what the softmax scale carries is the caller's
    (`yarn_mscale(factor, mscale_all_dim) ** 2`)."""
    half = rot_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))

    def correction(turns: float) -> float:  # the pair index that makes `turns` turns
        return rot_dim * math.log(original_max_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), rot_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
    angles = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), freqs)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return (jnp.cos(angles) * m).astype(dtype), (jnp.sin(angles) * m).astype(dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """0.1 mscale ln(factor) + 1: YaRN's attention temperature."""
    return 1.0 if factor <= 1 or not mscale else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, cos, sin, positions=None):
    """x: [B, T, H, D]; cos/sin: [maxT, D/2]; positions: [B, T] or None."""
    B, T, H, D = x.shape
    if positions is None:
        c = cos[:T][None, :, None, :]
        s = sin[:T][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def apply_partial_rope(x, cos, sin, positions=None):
    """`apply_rope` on the FIRST entries of each head only: x [B, T, H, D];
    cos/sin [maxT, R/2] with R <= D the rotary part (`rope_frequencies(R,
    ...)`); x[..., R:] passes untouched."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin, positions)
    return jnp.concatenate([apply_rope(x[..., :rot], cos, sin, positions), x[..., rot:]], axis=-1)
