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


# --------------------------------------------------------------------------
# A rematted layer keeps the flash attention's output and row statistics
# (`llama.remat_layer`): the backward pass runs the attention forward once a
# layer, not twice. On the CPU `flash_attention`'s forward rule falls back to
# `_fwd_impl`, under the same two names.

REMAT_B, REMAT_T = 2, 32


def _remat_case(model, remat=True):
    """(cfg, params, loss(params), a rematted attention layer, its arguments)
    of a tiny dense Llama or LFM2, float32, through `flash_attention`."""
    import functools

    from ray_tpu.models import lfm2_moe, llama

    tokens = np.random.default_rng(0).integers(0, 256, (REMAT_B, REMAT_T + 1), dtype=np.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (REMAT_B, REMAT_T, 1), jnp.float32)
    if model == "llama":
        cfg = llama.LlamaConfig.tiny(attn_impl="flash", remat=remat, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        layer = jax.tree.map(lambda p: p[0], params["layers"])
        fn, loss = functools.partial(llama._layer_fn, cfg=cfg), llama.loss_fn
    else:
        cfg = lfm2_moe.Lfm2MoeConfig.tiny(attn_impl="flash", remat=remat, dtype=jnp.float32)
        params = lfm2_moe.init_params(jax.random.PRNGKey(0), cfg)
        at = cfg.layer_types.index(lfm2_moe.FULL)
        layer = params["layers"][at]
        fn, loss = functools.partial(lfm2_moe._layer, kinds=cfg.kinds[at], cfg=cfg), lfm2_moe.loss_fn
    args = (layer, jnp.broadcast_to(x, (REMAT_B, REMAT_T, cfg.d_model)),
            rope_frequencies(cfg.head_dim, REMAT_T, cfg.rope_theta))
    return cfg, params, lambda p: loss(p, {"tokens": tokens}, cfg), llama.remat_layer(fn), args


def _attention_forwards(jaxpr, cfg):
    """Row maxima of (B, T, heads) in `jaxpr` and every jaxpr under it: what
    the attention's forward takes (`_fwd_impl`'s running maximum, once in its
    loop's body) and its backward, which reads lse, does not."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "reduce_max" and eqn.outvars[0].aval.shape == (
                REMAT_B, REMAT_T, cfg.n_heads):
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _attention_forwards(sub, cfg)
    return n


@pytest.mark.parametrize("model", ["llama", "lfm2_moe"])
def test_a_rematted_layer_keeps_its_arguments_and_the_attentions_output_and_statistics(model):
    from jax._src.ad_checkpoint import saved_residuals

    cfg, _, _, layer_fn, args = _remat_case(model)
    kept = [aval.shape for aval, why in saved_residuals(layer_fn, *args)
            if not why.startswith("from the argument")]
    assert sorted(kept) == [(REMAT_B, REMAT_T, cfg.n_heads),
                            (REMAT_B, REMAT_T, cfg.n_heads, cfg.head_dim)]


@pytest.mark.parametrize("model", ["llama", "lfm2_moe"])
def test_the_gradient_runs_the_attention_forward_once_a_layer_where_a_plain_checkpoint_runs_it_twice(
        model, monkeypatch):
    from ray_tpu.models import lfm2_moe, llama

    cfg, params, loss, _, _ = _remat_case(model)
    # the scanned layers are one body, the walked layers have one attention layer
    bodies = 1 if model == "llama" else cfg.layer_types.count(lfm2_moe.FULL)
    assert _attention_forwards(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, cfg) == bodies
    for module in (llama, lfm2_moe):
        monkeypatch.setattr(module, "remat_layer", jax.checkpoint)
    assert _attention_forwards(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, cfg) == 2 * bodies


@pytest.mark.parametrize("model", ["llama", "lfm2_moe"])
def test_gradients_under_remat_equal_those_without(model):
    _, params, loss, _, _ = _remat_case(model)
    plain_loss = _remat_case(model, remat=False)[2]
    with jax.default_matmul_precision("highest"):
        (l1, g1), (l0, g0) = (jax.jit(jax.value_and_grad(f))(params) for f in (loss, plain_loss))
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g1), jax.tree.leaves(g0)):
        scale = float(jnp.abs(b).max()) + 1e-30
        np.testing.assert_allclose(np.array(a) / scale, np.array(b) / scale, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_forward_outside_the_vjp_names_nothing(qkv):
    """`flash_attention_fwd` is what every serve admission calls: its program
    is the one it was before the names."""
    from ray_tpu.ops.flash_attention import flash_attention, flash_attention_fwd

    assert "name[" not in str(jax.make_jaxpr(flash_attention_fwd)(*qkv))
    assert "name[" not in str(jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, True))(*qkv))
    grad = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(q, k, v, True).sum(), (0, 1, 2)))(*qkv)
    assert str(grad).count("name[") == 2
