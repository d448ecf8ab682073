"""Tests for ops: blockwise/flash attention, normalization, rope.

Runs on the CPU backend (conftest pins jax to cpu with 8 virtual
devices); the pallas kernel is exercised in interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.blockwise_attention import blockwise_attention, reference_attention
from ray_tpu.ops.normalization import layer_norm, rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


@pytest.fixture(scope="module")
def qkv():
    B, T, H, D = 2, 128, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_reference(qkv, causal):
    q, k, v = qkv
    o1 = blockwise_attention(q, k, v, causal, 32)
    o2 = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.array(o1), np.array(o2), atol=2e-5)


def test_blockwise_grads_match_reference(qkv):
    q, k, v = qkv
    g1 = jax.grad(lambda *a: (blockwise_attention(*a, True, 32) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (reference_attention(*a, True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.array(a), np.array(b), atol=5e-4)


def test_blockwise_gqa(qkv):
    q, _, _ = qkv
    B, T, H, D = q.shape
    k = jax.random.normal(jax.random.PRNGKey(3), (B, T, 2, D))
    v = jax.random.normal(jax.random.PRNGKey(4), (B, T, 2, D))
    o1 = blockwise_attention(q, k, v, True, 32)
    o2 = reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.array(o1), np.array(o2), atol=2e-5)
    # gqa kv grads reduce over the query-head groups
    g1 = jax.grad(lambda k: (blockwise_attention(q, k, v, True, 32) ** 2).sum())(k)
    g2 = jax.grad(lambda k: (reference_attention(q, k, v, True) ** 2).sum())(k)
    np.testing.assert_allclose(np.array(g1), np.array(g2), atol=5e-4)


def test_blockwise_uneven_length(qkv):
    q, k, v = qkv
    q, k, v = q[:, :100], k[:, :100], v[:, :100]
    o1 = blockwise_attention(q, k, v, True, 32)
    o2 = reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.array(o1), np.array(o2), atol=2e-5)


def test_flash_pallas_interpret_matches(qkv):
    from ray_tpu.ops.flash_attention import _flash_fwd_pallas

    q, k, v = qkv
    B, T, H, D = q.shape
    o, lse = _flash_fwd_pallas(q, k, v, True, None, 64, 64, interpret=True)
    ref = reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.array(o), np.array(ref), atol=2e-5)
    # lse matches the blockwise implementation's
    from ray_tpu.ops.blockwise_attention import _fwd_impl

    _, lse2 = _fwd_impl(q, k, v, True, 64, None, 0, 0)
    np.testing.assert_allclose(np.array(lse), np.array(lse2), atol=1e-4)


def test_rms_norm_matches_numpy():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128,))
    xn, wn = np.array(x, np.float64), np.array(w, np.float64)
    ref = xn / np.sqrt((xn * xn).mean(-1, keepdims=True) + 1e-6) * wn
    np.testing.assert_allclose(np.array(rms_norm(x, w)), ref, atol=1e-5)
    # bf16 activations are normalised in f32 and come back as bf16
    assert rms_norm(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


def test_layer_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    w = jnp.ones((64,))
    b = jnp.zeros((64,))
    y = layer_norm(x, w, b)
    np.testing.assert_allclose(np.array(y.mean(-1)), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.array(y.std(-1)), 1.0, atol=1e-2)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(32, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 32))
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.array(x), axis=-1), np.linalg.norm(np.array(y), axis=-1), rtol=1e-5
    )
    # position 0 is identity
    np.testing.assert_allclose(np.array(y[:, 0]), np.array(x[:, 0]), atol=1e-6)


def test_rope_relative_property():
    # <rope(q,m), rope(k,n)> depends only on m-n
    cos, sin = rope_frequencies(16, 64)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))

    def dot_at(m, n):
        pm = jnp.array([[m]])
        pn = jnp.array([[n]])
        qr = apply_rope(q, cos, sin, pm)
        kr = apply_rope(k, cos, sin, pn)
        return float((qr * kr).sum())

    assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4
    assert abs(dot_at(5, 3) - dot_at(6, 3)) > 1e-6
