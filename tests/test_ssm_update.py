"""The Pallas kernel of ops/ssm_update.py against `ssm_step` + select + write,
on the CPU in the TPU interpret mode (which fills what a kernel leaves
unwritten with NaN, so a row the kernel skips shows if anything reads it).

Shapes with the real shape of things at a tiny size: a stack of 3 layers, 4
lanes, 32 heads x 8, state 128 (the kernel's tiles want N a multiple of 128,
P of 8 and a block's heads x P of 128).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import granite_hybrid as G
from ray_tpu.ops import ssm_update as SU

M, L, H, P, N = 3, 4, 32, 8, 128
MASKS = {"all": [1, 1, 1, 1], "half": [0, 1, 0, 1], "one": [0, 0, 1, 0], "none": [0, 0, 0, 0]}


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    monkeypatch.setattr(SU, "_on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        yield


def _inputs(dtype=jnp.float32, seed=36):
    f = lambda k, *s: jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), k), s)  # noqa: E731
    return dict(ssm=f(0, M, L, H, P, N), x=f(1, L, H, P).astype(dtype),
                dt=jax.nn.softplus(f(2, L, H) - 2.0), A=-jnp.exp(f(3, H)),
                B=f(4, L, N).astype(dtype), C=f(5, L, N).astype(dtype), D=f(6, H))


def _plain(ssm, mi, active, x, dt, A, B, C, D):
    """What a decode step did before the kernel: `ssm_step` on the layer, a
    select over all lanes, the layer written back."""
    y, new = G.ssm_step(ssm[mi], x, dt, A, B, C, D)
    return y, ssm.at[mi].set(jnp.where(active[:, None, None, None], new, ssm[mi]))


def _both(mask, mi, dtype=jnp.float32):
    i = _inputs(dtype)
    active = jnp.asarray(MASKS[mask], bool)
    args = (i["x"], i["dt"], i["A"], i["B"], i["C"], i["D"])
    got = jax.jit(G.ssm_step_stacked)(i["ssm"], mi, G.live_rows(active), *args)
    want = _plain(i["ssm"], mi, active, *args)
    return np.asarray(active), i["ssm"], [np.asarray(a) for a in got], [np.asarray(a) for a in want]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("mi", [0, M - 1], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_kernel_is_ssm_step_on_the_live_lanes(kernel_on_cpu, mask, mi, dtype):
    """y and the new state of every live lane, to the order of the sum over
    N and a fused multiply-add; y is 0 where the kernel wrote nothing."""
    active, _, (y, ssm), (y_want, ssm_want) = _both(mask, mi, dtype)
    np.testing.assert_allclose(ssm[mi][active], ssm_want[mi][active], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[active], y_want[active], rtol=1e-5, atol=1e-4)
    assert not y[~active].any()


@pytest.mark.parametrize("mi", [0, M - 1], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_kernel_leaves_dead_lanes_and_other_layers_bit_for_bit(kernel_on_cpu, mask, mi):
    active, before, (_, ssm), _ = _both(mask, mi)
    before = np.asarray(before)
    np.testing.assert_array_equal(ssm[mi][~active], before[mi][~active])
    others = [m for m in range(M) if m != mi]
    np.testing.assert_array_equal(ssm[others], before[others])


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_kernel_in_blocks_of_heads(kernel_on_cpu, monkeypatch, mask):
    """A VMEM that holds half a lane's heads: two blocks a lane, and the
    steps past the live lanes repeat the last live block."""
    monkeypatch.setattr(SU, "_VMEM_FOR_BLOCKS", 4 * (H // 2) * P * N * 4)
    assert SU.heads_per_block(H, P, N) == H // 2
    active, before, (y, ssm), (y_want, ssm_want) = _both(mask, 1)
    np.testing.assert_allclose(ssm[1][active], ssm_want[1][active], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[active], y_want[active], rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(ssm[1][~active], np.asarray(before)[1][~active])
    np.testing.assert_array_equal(ssm[[0, 2]], np.asarray(before)[[0, 2]])


@pytest.mark.parametrize("mask,order,n_live", [
    ("all", [0, 1, 2, 3], 4), ("half", [1, 3, 3, 3], 2), ("one", [2, 2, 2, 2], 1),
    ("none", [0, 0, 0, 0], 0)])
def test_live_rows_are_compacted_and_the_last_repeated(mask, order, n_live):
    active, got_order, got_n = G.live_rows(jnp.asarray(MASKS[mask], bool))
    assert got_order.tolist() == order and got_n.tolist() == [n_live]
    assert active.tolist() == [bool(a) for a in MASKS[mask]]


@pytest.mark.parametrize("shape,heads", [
    ((64, 64, 128), 64),    # granite-4.0-h-micro: 2 MiB a block, the four in 8 MiB
    ((128, 64, 128), 64),   # twice the heads: two blocks a lane
    ((64, 64, 256), 32),
    ((32, 8, 128), 32),     # this file's
])
def test_heads_a_block_follow_the_shapes(shape, heads):
    assert SU.supported(*shape) and SU.heads_per_block(*shape) == heads


def test_a_tpu_traces_one_kernel_that_takes_the_stack_in_place(monkeypatch):
    monkeypatch.setattr(SU, "_on_tpu", lambda: True)
    i = _inputs()
    jaxpr = str(jax.make_jaxpr(G.ssm_step_stacked)(
        i["ssm"], 1, G.live_rows(jnp.ones((L,), bool)), i["x"], i["dt"], i["A"], i["B"],
        i["C"], i["D"]))
    assert jaxpr.count("pallas_call") == 1 and "name=ssm_update" in jaxpr
    assert "input_output_aliases=((7, 1),)" in jaxpr
    before_kernel = jaxpr[:jaxpr.index("pallas_call")].splitlines()
    for op in ("dynamic_slice", "dynamic_update_slice", "select_n[", "gather", "scatter"):
        assert not [ln for ln in before_kernel if op in ln and f"f32[{M},{L},{H},{P},{N}]" in ln], op


@pytest.mark.parametrize("shape", [(4, 8, 16), (64, 64, 64), (64, 60, 128), (4, 8, 128)],
                         ids=["tiny", "N64", "P60", "one-lane-row-short"])
def test_other_shapes_take_ssm_step(monkeypatch, shape):
    """Shapes the tiles do not take go through `ssm_step` on a TPU too: no
    kernel is traced."""
    monkeypatch.setattr(SU, "_on_tpu", lambda: True)
    assert not SU.supported(*shape)
    Hs, Ps, Ns = shape
    ssm = jnp.zeros((2, 3, Hs, Ps, Ns), jnp.float32)
    live = G.live_rows(jnp.asarray([True, False, True]))
    z = jnp.zeros
    jaxpr = jax.make_jaxpr(G.ssm_step_stacked)(
        ssm, 1, live, z((3, Hs, Ps)), z((3, Hs)), z((Hs,)), z((3, Ns)), z((3, Ns)), z((Hs,)))
    assert "pallas_call" not in str(jaxpr)
