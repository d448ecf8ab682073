"""ops/ring_write.py against its definition, `afmoe_decode.write_ring_token`'s
loop: the kernel in the TPU interpret mode (what a TPU runs, here on the
CPU), at the two cells' ring shapes and in float32. The write is a copy, so
every comparison is exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import afmoe_decode as D
from ray_tpu.ops import ring_write

CASES = {
    "reasoning-generate": (jnp.bfloat16, 1280, 512),   # phi-4-mini-flash's rings
    "mixed-context-generate": (jnp.bfloat16, 512, 64),  # trinity-mini's row, its window of 2,048 cut to 64
    "float32": (jnp.float32, 256, 32),
    "lanes-in-groups": (jnp.bfloat16, 40960, 16),  # 7 lanes' tiles pass the VMEM's share: a lane a grid step
}


def _bits(a):
    return np.asarray(a).view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _rings(dtype, row, window, W=3, seed=51):
    """Two ring stacks of W layers, rows for one step and the slots the
    satellite names: 0, T - 1, T, window - 1 and two lanes on one slot
    number, as positions anywhere past the window too."""
    rng = np.random.default_rng(seed)
    T = ring_write.slots_per_tile(dtype)
    pos = jnp.asarray([0, T - 1, T, window - 1, 3 * window + T, 5 * window + 3, T + 1], jnp.int32)
    L = pos.shape[0]
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape, np.float32), dtype)  # noqa: E731
    wk, wv = draw(W, L, window, row), draw(W, L, window, row)
    k, v = draw(L, row), draw(L, row)
    return wk, wv, k, v, pos


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_writes_what_the_loop_writes(case, monkeypatch):
    """The written rows are k and v exactly; every other slot, lane and LAYER
    of both stacks is the ring's bit for bit, which is the loop's result; and
    `write_ring_tokens` takes the kernel where it engages."""
    dtype, row, window = CASES[case]
    wk, wv, k, v, pos = _rings(dtype, row, window)
    assert ring_write.supported(window, row, dtype)
    assert ring_write.lanes_per_step(pos.shape[0], row, dtype) == (1 if case == "lanes-in-groups" else 7)
    monkeypatch.setattr(ring_write, "_on_tpu", lambda: True)
    assert ring_write.engages(window, row, dtype)
    calls = []
    monkeypatch.setattr(ring_write, "write_rows", lambda *a, _f=ring_write.write_rows: calls.append(1) or _f(*a))
    with pltpu.force_tpu_interpret_mode():
        got_k, got_v = jax.jit(D.write_ring_tokens)(wk, wv, jnp.asarray(1), k, v, pos)
    assert calls == [1]
    lanes, slots = np.arange(pos.shape[0]), np.asarray(pos) % window
    for got, ring, new in ((got_k, wk, k), (got_v, wv, v)):
        np.testing.assert_array_equal(_bits(got[1])[lanes, slots], _bits(new))
        keep = np.ones(ring.shape[1:3], bool)
        keep[lanes, slots] = False
        np.testing.assert_array_equal(_bits(got[1])[keep], _bits(ring[1])[keep])
        np.testing.assert_array_equal(_bits(got[::2]), _bits(ring[::2]))
        np.testing.assert_array_equal(_bits(got), _bits(D.write_ring_token(ring, 1, new, pos)))


@pytest.mark.parametrize("case", list(CASES)[:3])
def test_steps_of_rolled_layers_through_the_kernel_leave_the_loops_rings(case, monkeypatch):
    """Several decode steps of a rolled scan over the layers (the layer index
    traced, as the decode modules walk them), the positions passing the
    window's end: the stacks equal the loop's after every step."""
    dtype, row, window = CASES[case]
    wk, wv, k, v, pos = _rings(dtype, row, window, W=2)

    def steps(write):
        def layer(carry, wi):
            wk, wv, pos = carry
            wk, wv = write(wk, wv, wi, k + wi.astype(dtype), v - wi.astype(dtype), pos)
            return (wk, wv, pos), None

        def step(_, carry):
            (wk, wv, pos), _ = jax.lax.scan(layer, carry, jnp.arange(2))
            return wk, wv, pos + 1

        return jax.jit(lambda wk, wv, pos: jax.lax.fori_loop(0, 3, step, (wk, wv, pos)))(wk, wv, pos)

    want = steps(D.write_ring_tokens)  # off the TPU: the loop
    monkeypatch.setattr(ring_write, "_on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        got = steps(D.write_ring_tokens)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def test_the_kernel_engages_on_a_tpu_alone_and_for_shapes_its_tiles_take(monkeypatch):
    assert ring_write.slots_per_tile(jnp.bfloat16) == 16 and ring_write.slots_per_tile(jnp.float32) == 8
    for dtype, row, window in ((jnp.bfloat16, 1280, 512), (jnp.bfloat16, 512, 2048), (jnp.float32, 128, 8)):
        assert ring_write.supported(window, row, dtype)
        assert not ring_write.engages(window, row, dtype)  # no TPU here
    assert not ring_write.supported(512, 192, jnp.bfloat16)  # a row of 192 columns
    assert not ring_write.supported(24, 128, jnp.bfloat16)   # a window that is no whole tiles
    assert ring_write.supported(24, 128, jnp.float32)
    assert not ring_write.supported(512, 1280, jnp.int8)
    assert not ring_write.supported(512, 2**18, jnp.bfloat16)  # one lane's two tiles pass the VMEM's share
    assert ring_write.lanes_per_step(64, 1280, jnp.bfloat16) == 64 and ring_write.lanes_per_step(8, 512, jnp.bfloat16) == 8
    assert ring_write.lanes_per_step(256, 1280, jnp.bfloat16) == 64 and ring_write.lanes_per_step(6, 40960, jnp.bfloat16) == 3
    monkeypatch.setattr(ring_write, "_on_tpu", lambda: True)
    assert ring_write.engages(512, 1280, jnp.bfloat16) and not ring_write.engages(512, 192, jnp.bfloat16)


def test_an_odd_ring_is_written_by_the_loop_on_a_tpu_too(monkeypatch):
    """A row of 192 columns: `write_ring_tokens` never reaches the kernel."""
    wk, wv, k, v, pos = _rings(jnp.bfloat16, 192, 32)
    monkeypatch.setattr(ring_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(ring_write, "write_rows", lambda *a: pytest.fail("the kernel was called"))
    got_k, got_v = D.write_ring_tokens(wk, wv, 1, k, v, pos)
    np.testing.assert_array_equal(_bits(got_k), _bits(D.write_ring_token(wk, 1, k, pos)))
    np.testing.assert_array_equal(_bits(got_v), _bits(D.write_ring_token(wv, 1, v, pos)))
