"""ops/paged_decode_attention.py in the TPU interpret mode on the CPU, against
its definition, `paged.attend_decode_paged`, on the same pools: what a walk by
each lane's own blocks has to get right (the edges of a block, of a chunk and
of a group, a lane that is not live, the table's whole span, blocks out of
order, a layer index inside a stack), that it fetches no block past a lane's
own, and which shapes take it (`engages`), in both of its forms: a K and a V
pool of flat rows, and ONE pool whose rows hold their own values (a latent
cache: 64 heads as wide as a row, the value a row's first columns, the
queries a few lanes a grid step). Compiling it for the chip is
tests/test_tpu_compile.py's; its speed PERF.md's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import paged
from ray_tpu.models import phi4flash_decode
from ray_tpu.ops import paged_decode_attention as PDA

# five lanes on tables 20 blocks of 16 wide (a span of 320: chunks of 128,
# groups of 256, the last of each part padding). pos and active:
LANE_CASES = {
    # tests/test_phi4flash.py's three until PR 53: a context that ends on a
    # chunk's boundary, one a position past it, a short one, a lane at the
    # table's span, a lane that is not live
    "unequal-lanes": ([127, 128, 4, 319, 300], [True, True, True, True, False]),
    "two-chunks-of-three": ([127, 128, 4, 129, 319], [True, True, True, True, False]),
    "no-live-lane": ([127, 128, 4, 319, 300], [False] * 5),
    # what a walk by blocks adds
    "one-position-and-a-blocks-edges": ([0, 15, 16, 31, 32], [True] * 5),
    "a-groups-edges": ([255, 256, 257, 239, 240], [True] * 5),
    "not-live-and-would-be-the-longest": ([40, 3, 100, 17, 319], [True, True, True, True, False]),
    "every-lane-at-the-tables-span": ([319] * 5, [True] * 5),
    "live-lanes-between-lanes-that-are-not": ([90, 200, 7, 310, 60], [False, True, False, True, False]),
}
B, MB, BS, HD, H, ROW = 5, 20, 16, 32, 10, 160
# the three forms of a call: (heads, a head's width, the row, `v_cols`, whether
# the single pool is walked as this file's lane cases want it). The single
# pool's queries are one "KV head" as wide as a row, and its groups are FOUR
# chunks, so the tables' span of 320 is one group: 'one-pool' has a chunk's
# edges inside a group; 'one-pool-groups-of-two-chunks-a-lane-a-grid-step' cuts
# the groups to the flat form's 256 positions, which the lane cases cross, and
# the VMEM a call may take to ONE lane's queries and results beside the groups,
# so the walk crosses five grid steps (32 heads: shapes of its own, so a jit
# cache entry of its own: both are read when the call is traced)
FORMS = {
    "k-and-v": (H, HD, ROW, 0, False),
    "one-pool": (64, 256, 256, 128, False),
    "one-pool-groups-of-two-chunks-a-lane-a-grid-step": (32, 256, 256, 128, True),
}


def _pools(dtype, seed, layers=2, row=ROW, single=False, mb=MB):
    rng = np.random.default_rng([53, seed])
    shape = (layers, 1 + B * mb, BS, row)
    k_full, v_full = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(B * mb).reshape(B, mb), jnp.int32)  # out of order
    return rng, k_full, None if single else v_full, tables


def _poisoner(tables, pos, active):
    """NaN into every block past a lane's own, every block of a lane that is
    not live, and the whole first layer, of a pool (None stays None)."""
    own = np.where(np.asarray(active), np.asarray(pos) // BS + 1, 0)
    beyond = np.concatenate([np.asarray(tables)[b, own[b]:] for b in range(B)])
    return lambda pool: pool if pool is None else pool.at[:, beyond].set(jnp.nan).at[0].set(jnp.nan)


def _close(got, want, dtype):
    """The same chunks in the same order under the same online softmax: an
    ulp of the type, for the interpreter's products and exp."""
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("lanes", sorted(LANE_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_kernel_attends_each_lanes_own_blocks_as_the_loop_attends_the_pool(form, dtype, lanes, monkeypatch):
    """`attend` gives what `attend_decode_paged` gives, for two readers with
    queries of their own, on a pool whose tables are shuffled and whose layer
    (a latent pool's plane) is not the first. EVERY BLOCK PAST A LANE'S OWN,
    and every block of a lane that is not live, is NaN in the pools when the
    kernel runs: one fetched would show in the result (a probability of 0
    times NaN). A lane that is not live comes out zeros. The single pool's
    values are its rows' first `v_cols` columns, out of the keys' buffer."""
    h, hd, row, v_cols, cut = FORMS[form]
    if cut:
        monkeypatch.setattr(PDA, "LATENT_GROUP_CHUNKS", 2)
        monkeypatch.setattr(PDA, "_VMEM", PDA._vmem_bytes(1, h, hd, BS, row, dtype, v_cols))
        assert PDA.lanes_per_step(B, h, hd, BS, row, dtype, v_cols) == 1
    rng, k_full, v_full, tables = _pools(dtype, 0, row=row, single=v_cols > 0)
    pos, active = (jnp.asarray(a) for a in LANE_CASES[lanes])
    pos = pos.astype(jnp.int32)
    poison = _poisoner(tables, pos, active)
    on = np.asarray(active)
    for reader in range(2):
        q = jnp.asarray(rng.normal(size=(B, h, hd)), dtype)
        want = paged.attend_decode_paged(q, k_full, v_full, 1, tables, pos, active, hd ** -0.5, v_cols=v_cols)
        with pltpu.force_tpu_interpret_mode():
            got = PDA.attend(q, poison(k_full), poison(v_full), 1, tables, pos, active, hd ** -0.5, v_cols)
        assert got.dtype == want.dtype == dtype and got.shape == want.shape == (B, h * (v_cols or hd))
        _close(np.asarray(got, np.float32)[on], np.asarray(want, np.float32)[on], dtype)
        np.testing.assert_array_equal(np.asarray(got, np.float32)[~on], 0.0)
        assert (np.abs(np.asarray(got, np.float32)).max() > 0) == bool(on.any())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_latent_lane_walks_into_its_second_group_of_four_chunks(dtype):
    """The single pool's groups as they are, 512 positions, on tables 40 blocks
    wide: contexts that end on the first group's last position, open the
    second, fill a chunk of it, reach the tables' span, and a lane that is not
    live; every block past a lane's own NaN."""
    h, row, v_cols = 16, 256, 128
    assert PDA.group_blocks(BS, v_cols) * BS == 512
    rng, pool, _, tables = _pools(dtype, 3, row=row, single=True, mb=40)
    pos, active = jnp.asarray([511, 512, 639, 630, 200], jnp.int32), jnp.asarray([True] * 4 + [False])
    q = jnp.asarray(rng.normal(size=(B, h, row)), dtype)
    want = paged.attend_decode_paged(q, pool, None, 1, tables, pos, active, 0.06, v_cols=v_cols)
    with pltpu.force_tpu_interpret_mode():
        got = PDA.attend(q, _poisoner(tables, pos, active)(pool), None, 1, tables, pos, active, 0.06, v_cols)
    _close(got[:4], want[:4], dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[4], 0.0)


@pytest.mark.parametrize("form", ["k-and-v", "one-pool"])
def test_a_traced_layer_index_reads_its_own_layer_of_a_stack_of_several(form):
    """Three layers (a latent pool's planes) read in a rolled scan, out of
    order: the index is data in the program (the cross-decoder's scan, a
    latent model's layer scan, stays rolled) and the pools are never sliced
    by it."""
    h, hd, row, v_cols, _ = FORMS[form]
    rng, k_full, v_full, tables = _pools(jnp.float32, 1, layers=3, row=row, single=v_cols > 0)
    pos, active = jnp.asarray([127, 128, 4, 319, 300], jnp.int32), jnp.asarray([True] * 4 + [False])
    qs = jnp.asarray(rng.normal(size=(3, B, h, hd)), jnp.float32)
    order = jnp.asarray([2, 0, 1], jnp.int32)

    def readers(attend):
        return jax.lax.scan(
            lambda _, x: (None, attend(x[0], k_full, v_full, x[1], tables, pos, active, 0.2, v_cols)),
            None, (qs, order))[1]

    want = readers(paged.attend_decode_paged)
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(lambda: readers(PDA.attend))()
    _close(got[:, :4], want[:, :4], jnp.float32)
    assert np.abs(np.asarray(want[0] - want[1])).max() > 0.1  # the layers differ


Q = (64, 40, 128)
ENGAGES = {
    # (q's shape, the pools' shape, the pools' type, q's type, V given, `v_cols`) -> on a TPU
    "the-cells-pool": (Q, (1, 8193, 16, 1280), jnp.bfloat16, jnp.bfloat16, True, 0, True),
    "float32-blocks-of-its-tile": (Q, (1, 8193, 8, 1280), jnp.float32, jnp.float32, True, 0, True),
    "one-kv-head": ((8, 16, 128), (4, 513, 16, 128), jnp.bfloat16, jnp.bfloat16, True, 0, True),
    "a-row-of-heads": (Q, (1, 8193, 16, 10, 128), jnp.bfloat16, jnp.bfloat16, True, 0, False),
    "an-odd-row": ((5, 10, 32), (2, 101, 16, 160), jnp.bfloat16, jnp.bfloat16, True, 0, False),
    "heads-of-half-a-lane-row": ((64, 40, 64), (1, 8193, 16, 1280), jnp.bfloat16, jnp.bfloat16, True, 0, False),
    "an-odd-block-size": (Q, (1, 8193, 32, 1280), jnp.bfloat16, jnp.bfloat16, True, 0, False),
    "float32-in-bfloat16s-blocks": (Q, (1, 8193, 16, 1280), jnp.float32, jnp.float32, True, 0, False),
    "a-type-the-tiles-do-not-take": (Q, (1, 8193, 16, 1280), jnp.int8, jnp.int8, True, 0, False),
    "queries-of-another-type": (Q, (1, 8193, 16, 1280), jnp.bfloat16, jnp.float32, True, 0, False),
    "one-pool-of-latent-rows": ((8, 64, 640), (1, 513, 16, 640), jnp.bfloat16, jnp.bfloat16, False, 0, False),
    "more-lanes-than-the-vmem-holds": ((1024, 40, 128), (1, 8193, 16, 1280), jnp.bfloat16, jnp.bfloat16, True, 0, False),
    # the single-pool form: the two latent cells' shapes (32 lanes over 8 planes, 8 lanes over 5 of tables of 512)
    "agent-fanouts-latent-pool": ((32, 64, 640), (8, 2049, 16, 640), jnp.bfloat16, jnp.bfloat16, False, 512, True),
    "longdocs-latent-pool": ((8, 64, 640), (5, 4097, 16, 640), jnp.bfloat16, jnp.bfloat16, False, 512, True),
    "a-float32-latent-pool-in-blocks-of-its-tile": ((8, 64, 640), (5, 513, 8, 640), jnp.float32, jnp.float32, False, 512, True),
    "more-latent-lanes-than-a-grid-step-holds": ((64, 64, 640), (8, 2049, 16, 640), jnp.bfloat16, jnp.bfloat16, False, 512, True),
    "values-the-whole-row": ((8, 64, 640), (5, 513, 16, 640), jnp.bfloat16, jnp.bfloat16, False, 640, True),
    "a-576-column-row": ((8, 64, 576), (5, 513, 16, 576), jnp.bfloat16, jnp.bfloat16, False, 512, False),
    "values-of-half-a-lane-row": ((8, 64, 640), (5, 513, 16, 640), jnp.bfloat16, jnp.bfloat16, False, 576, False),
    "queries-narrower-than-the-row": ((8, 64, 128), (5, 513, 16, 640), jnp.bfloat16, jnp.bfloat16, False, 128, False),
    "a-latent-pool-in-blocks-of-4": ((8, 64, 640), (5, 513, 4, 640), jnp.bfloat16, jnp.bfloat16, False, 512, False),
    "v-cols-beside-a-v-pool": ((8, 16, 128), (4, 513, 16, 128), jnp.bfloat16, jnp.bfloat16, True, 128, False),
}


@pytest.mark.parametrize("case", sorted(ENGAGES))
def test_engages_on_a_tpu_alone_and_for_the_shapes_the_tiles_take(case, monkeypatch):
    q_shape, pool_shape, dtype, q_dtype, v_given, v_cols, on_a_tpu = ENGAGES[case]
    q = jax.ShapeDtypeStruct(q_shape, q_dtype)
    k_full = jax.ShapeDtypeStruct(pool_shape, dtype)
    v_full = k_full if v_given else None
    assert not PDA.engages(q, k_full, v_full, v_cols)  # no TPU here
    monkeypatch.setattr(PDA, "_on_tpu", lambda: True)
    assert PDA.engages(q, k_full, v_full, v_cols) == on_a_tpu


@pytest.mark.parametrize("row,bs", [((2, 32), 16), ((160,), 16), ((128,), 32)],
                         ids=["a-row-of-heads", "an-odd-row", "an-odd-block-size"])
def test_a_pool_the_tiles_do_not_take_goes_the_loops_way_on_a_tpu_too(row, bs, monkeypatch):
    """`phi4flash_decode.attend_pool` on a TPU (the backend test patched) with
    a pool of (kvh, hd) rows, of rows that are no whole lane-rows, or of
    blocks that are not the type's tile: the definition runs and the kernel is
    not called."""
    monkeypatch.setattr(PDA, "_on_tpu", lambda: True)
    monkeypatch.setattr(PDA, "attend", lambda *a, **k: pytest.fail("the kernel was called"))
    rng = np.random.default_rng(3)
    hd = row[-1] if len(row) == 2 else 32
    k_full, v_full = (jnp.asarray(rng.normal(size=(1, 9, bs) + row), jnp.bfloat16) for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(8).reshape(2, 4), jnp.int32)
    pos, active = jnp.asarray([3 * bs, 5], jnp.int32), jnp.asarray([True, True])
    q = jnp.asarray(rng.normal(size=(2, 20, hd)), jnp.bfloat16)
    got = phi4flash_decode.attend_pool(k_full, v_full, tables, pos, active, 0.2)(q)
    want = paged.attend_decode_paged(q, k_full, v_full, 0, tables, pos, active, 0.2).reshape(q.shape)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_attend_pool_takes_the_kernel_where_it_engages(monkeypatch):
    """The same call with `engages` true (the interpreter takes any shape):
    the kernel's result, the definition's numbers."""
    calls = []
    attend = PDA.attend
    monkeypatch.setattr(PDA, "engages", lambda q, k, v: True)
    monkeypatch.setattr(PDA, "attend", lambda *a: calls.append(1) or attend(*a))
    rng, k_full, v_full, tables = _pools(jnp.bfloat16, 2, layers=1)
    pos, active = jnp.asarray([127, 128, 4, 319, 300], jnp.int32), jnp.asarray([True] * 4 + [False])
    q = jnp.asarray(rng.normal(size=(B, H, HD)), jnp.bfloat16)
    with pltpu.force_tpu_interpret_mode():
        got = phi4flash_decode.attend_pool(k_full, v_full, tables, pos, active, 0.2)(q)
    assert calls == [1] and got.shape == q.shape
    want = paged.attend_decode_paged(q, k_full, v_full, 0, tables, pos, active, 0.2).reshape(q.shape)
    _close(got[:4], want[:4], jnp.bfloat16)
