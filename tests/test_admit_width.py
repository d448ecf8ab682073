"""An admitting phase runs at the width of its own admissions (ISSUE 42), as
the binary pieces of their count (ISSUE 46).

The paged macro-step's skeleton (`paged.admit_phase`) hands the model's
own admission the rows of a phase a piece at a time: `admit_pieces(n, A, P)`,
3 rows as 2 + 1 where a piece has tokens enough to be worth its pass over the
weights, one piece rounded up to a power of two where it has not (PR 42's), and
never all A. The four models' admissions are row-independent, so nothing a
real row leaves behind may differ: CPU, float32, each model's tiny config.

Tolerance: 1e-4 of the largest entry (a product over w rows and one over A
rows may sum in another order); integers are equal.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import afmoe, granite_hybrid, llama, longcat_flash, paged, sarvam_mla
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

RTOL = 1e-4
CHUNK = 4
MODELS = {
    "llama": (llama, lambda: llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                                     remat=False)),
    "granite_hybrid": (granite_hybrid, lambda: granite_hybrid.GraniteHybridConfig.tiny(
        dtype=jnp.float32)),
    "afmoe": (afmoe, lambda: afmoe.AfmoeConfig.tiny(dtype=jnp.float32)),
    "sarvam_mla": (sarvam_mla, lambda: sarvam_mla.SarvamMlaConfig.tiny(dtype=jnp.float32)),
}
# walked through the one-by-one comparison below and not through the widths
ONE_BY_ONE = {**MODELS, "longcat_flash": (
    longcat_flash, lambda: longcat_flash.LongcatFlashConfig.tiny(dtype=jnp.float32))}
# (A, P, block, blocks a lane): rows too short to be worth a second pass over
# the weights, PR 42's program, and the shortest of which one is worth a pass
LONG_P = paged.RIDGE_TOKENS
SHORT, LONG = (4, 16, 4, 8), (8, LONG_P, 16, LONG_P // 16 + 1)
# row i of the phase: its prompt's length in sixteenths of P and the lane it
# lands in (not its own index)
LENGTHS, LANES = (13, 5, 16, 9, 2, 11, 16, 7), (2, 0, 3, 1, 6, 4, 7, 5)


@functools.lru_cache(maxsize=8)
def _model(name):
    module, make = ONE_BY_ONE[name]
    cfg = make()
    return cfg, module.init_params(jax.random.PRNGKey(7), cfg), cfg.decode_module


@functools.lru_cache(maxsize=8)
def _halves(name):
    """The model's own admission and decode step, jitted once a model: every
    case of a shape shares their compiles."""
    cfg, _, D = _model(name)
    return (jax.jit(functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False)),
            jax.jit(functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False)))


def _phase(n, vocab, A, P):
    """The plan arrays of one phase whose first `n` rows are real, as
    `_dispatch_macro` lays them out: (prompts, lengths, starts, slots, rems, seeds)."""
    rng = np.random.default_rng(42)
    prompts = np.zeros((A, P), np.int32)
    lengths, slots, rems = (np.zeros(A, np.int32) for _ in range(3))
    for i in range(n):
        lengths[i], slots[i], rems[i] = LENGTHS[i] * P // 16, LANES[i], 5
        prompts[i, :lengths[i]] = rng.integers(0, vocab, lengths[i])
    return tuple(jnp.asarray(x) for x in (
        prompts, lengths, np.zeros(A, np.int32), slots, rems, np.zeros(A, np.uint32)))


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    if np.issubdtype(want.dtype, np.floating):
        assert np.abs(got - want).max() <= RTOL * max(np.abs(want).max(), 1e-30)
    else:
        assert np.array_equal(got, want)


@pytest.mark.parametrize("shape,n", [(SHORT, 1), (SHORT, 2), (SHORT, 3), (SHORT, 4),
                                     (LONG, 3), (LONG, 5), (LONG, 6), (LONG, 7)],
                         ids=lambda v: "long" if v == LONG else "short" if v == SHORT else str(v))
@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_phase_admitted_at_its_own_width_is_the_full_width_admission(name, shape, n):
    """n real rows of a phase A wide, through the macro-step and through the
    model's own admission at all A rows: the real rows' first tokens, EVERY
    leaf of the cache (the pool or latent pool, rings, recurrent state,
    positions, remaining: a padding row writes nothing in either, so no entry
    is left out) and the next decode step's logits at the admitted lanes are
    the same. Of A = 4 at P = 16 the macro-step runs ONE piece, of 1, 2, 4 and
    4 rows, as the parent of PR 46 did; of A = 8 at rows of the ridge's length it
    runs 3, 5, 6 and 7 rows as 2 + 1, 4 + 1, 4 + 2 and 4 + 2 + 1, each piece
    behind the one before it, and no row that holds no prompt."""
    A, P, block, MB = shape
    cfg, params, D = _model(name)
    tables = 1 + jnp.arange(A * MB, dtype=jnp.int32).reshape(A, MB)
    z = jnp.zeros((A,), jnp.int32)
    plan = (tables, jnp.zeros((A,), jnp.float32), z, jnp.ones((A,), jnp.float32),
            jnp.full((A, 1), -1, jnp.int32))
    rows = _phase(n, cfg.vocab_size, A, P)
    fresh = lambda: D.init_paged_cache(cfg, A, A * MB + 1, block)  # noqa: E731

    admit, step = _halves(name)
    first_full, cache_full, feed_full = admit(params, *rows, fresh(), z, *plan)

    # one phase that admits and decodes nothing, then one that does neither
    K = 2
    per_phase = lambda x: jnp.stack([x, jnp.zeros_like(x)])  # noqa: E731
    _, firsts, feed, cache, *_ = D.jitted_macro_step_slots_paged(cfg, CHUNK, sampled=False)(
        params, fresh(), z, jnp.zeros((K,), jnp.int32), jnp.asarray([True, False]),
        *(per_phase(r) for r in rows), *(jnp.stack([p, p]) for p in plan))

    pieces = paged.admit_pieces(n, A, P)
    if shape == SHORT:
        assert pieces == (1 << (n - 1).bit_length(),)
    else:
        assert sum(pieces) == n and len(pieces) == bin(n).count("1")
    # the rows that ran give what they give at full width (a padding row among
    # them its garbage, which no host reads); the rows that did not run give 0
    w = sum(pieces)
    assert np.array_equal(np.asarray(firsts[0, :w]), np.asarray(first_full[:w]))
    assert not np.asarray(firsts[0, w:]).any() and not np.asarray(firsts[1]).any()
    assert np.array_equal(np.asarray(feed), np.asarray(feed_full))
    assert jax.tree.structure(cache) == jax.tree.structure(cache_full)
    for got, want in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_full)):
        _close(got, want)

    logits, nxt, _ = step(params, cache, feed, *plan)
    logits_full, nxt_full, _ = step(params, cache_full, feed_full, *plan)
    lanes = list(LANES[:n])
    _close(np.asarray(logits)[lanes], np.asarray(logits_full)[lanes])
    assert np.array_equal(np.asarray(nxt)[lanes], np.asarray(nxt_full)[lanes])


@pytest.mark.parametrize("name", sorted(ONE_BY_ONE))
def test_rows_admitted_together_are_the_rows_admitted_one_by_one(name):
    """Three right-padded prompts of unequal length and a padding row in one
    (4, 16) admission, landing in lanes 2, 0 and 3, against the same three
    admitted one after another, each alone beside three padding rows: the
    first tokens, the feed, EVERY leaf of the cache and the next decode
    step's logits at the admitted lanes are the same. What
    `test_a_padded_admission_is_each_prompt_admitted_alone` held in three
    model files until PR 48, each for its own model's pool rows; here every
    leaf (a padding row writes nothing, a row nothing of another's) through
    the program the cases above compiled, so a case costs no compile of its own."""
    A, P, block, MB = SHORT
    cfg, params, D = _model(name)
    tables = 1 + jnp.arange(A * MB, dtype=jnp.int32).reshape(A, MB)
    z = jnp.zeros((A,), jnp.int32)
    plan = (tables, jnp.zeros((A,), jnp.float32), z, jnp.ones((A,), jnp.float32),
            jnp.full((A, 1), -1, jnp.int32))
    rows = _phase(3, cfg.vocab_size, A, P)
    admit, step = _halves(name)
    first_all, cache_all, feed_all = admit(
        params, *rows, D.init_paged_cache(cfg, A, A * MB + 1, block), z, *plan)

    cache, feed = D.init_paged_cache(cfg, A, A * MB + 1, block), z
    for i in range(3):
        alone = tuple(jnp.zeros_like(r).at[0].set(r[i]) for r in rows)
        first, cache, feed = admit(params, *alone, cache, feed, *plan)
        assert int(first[0]) == int(first_all[i])
    assert np.array_equal(np.asarray(feed), np.asarray(feed_all))
    for got, want in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_all)):
        _close(got, want)
    lanes = list(LANES[:3])
    logits, nxt, _ = step(params, cache, feed, *plan)
    logits_all, nxt_all, _ = step(params, cache_all, feed_all, *plan)
    _close(np.asarray(logits)[lanes], np.asarray(logits_all)[lanes])
    assert np.array_equal(np.asarray(nxt)[lanes], np.asarray(nxt_all)[lanes])


@pytest.mark.parametrize("lanes", [4, 6, 8, 11, 32])
def test_the_pieces_of_a_count(lanes):
    """`admit_pieces` alone, plain Python: distinct powers of two, widest
    first (`lanes` itself where it is none and one piece holds them all),
    that hold the n prompts in no more rows than the one piece PR 42 ran; one
    piece for a power of two, for a full program, for a phase that admits
    nobody and wherever that piece costs no more than two passes over the
    weights; with a longer row never more rows nor fewer pieces; from rows of
    the ridge's length up the count itself, bit by bit."""
    R = paged.RIDGE_TOKENS
    one = lambda n: min(1 << max(n - 1, 0).bit_length(), lanes)  # noqa: E731
    buckets = [16 << i for i in range(9)]
    assert buckets[0] < R < buckets[-1]
    for n in range(lanes + 1):
        before = None
        for P in buckets:
            pieces = paged.admit_pieces(n, lanes, P)
            assert all(w == lanes or w & (w - 1) == 0 for w in pieces)
            assert list(pieces) == sorted(set(pieces), reverse=True)
            assert max(n, 1) <= sum(pieces) <= one(n)
            if n in (0, lanes) or n & (n - 1) == 0 or one(n) * P <= 2 * R:
                assert pieces == (one(n),)
            elif P >= R:  # every piece is worth its pass (5 of 6 lanes as 4 + 1)
                assert sum(pieces) == n and len(pieces) == bin(n).count("1")
            if before:
                assert sum(pieces) <= sum(before) and len(pieces) >= len(before)
            before = pieces
    assert paged.admit_pieces(3, 8, R // 2) == (4,)  # a one-row piece costs a pass: no gain
    assert paged.admit_pieces(5, 8, R // 2) == (4, 1)  # three rows dropped for it: a gain
    assert paged.admit_pieces(7, 8, R // 2) == (8,)
    assert paged.admit_pieces(3, 8, R) == (2, 1)
    assert paged.admit_pieces(7, 8, R) == (4, 2, 1)
    assert paged.admit_pieces(11, 16, R) == (8, 2, 1)
    assert paged.admit_pieces(11, 16, R // 4) == (8, 4)  # the remainder rounded up
    assert paged.admit_pieces(23, 32, 16) == (32,)


def test_the_pieces_follow_the_last_real_row_not_the_count():
    """The device runs the pieces that reach the LAST non-empty row, each on
    the rows behind the piece before it, widest first, so a real row behind an
    empty one (no plan of the engine's makes one) is still admitted; below the
    ridge one piece, rounded up to a power of two, the program's A at the most."""
    seen = []

    def admit_rows(rows, carry):
        seen.append(rows[1].shape[0])
        assert rows[0].shape[0] == rows[1].shape[0]
        return rows[1] * 10, carry * 10 + rows[1].shape[0]  # the widths run, in their order

    def run(lengths, P, admits=True):
        lengths = jnp.asarray(lengths, jnp.int32)
        first, carry = jax.jit(lambda l: paged.admit_phase(
            admit_rows, jnp.asarray(admits), (jnp.zeros((l.shape[0], P), jnp.int32), l),
            jnp.asarray(0)))(lengths)
        return np.asarray(first).tolist(), int(carry)

    for lengths, want_carry in (
            ([7, 0, 0, 0, 0], 1),       # one row
            ([7, 3, 0, 0, 0], 2),       # two
            ([0, 0, 5, 0, 0], 4),       # a gap: four rows reach row 2
            ([1, 1, 1, 1, 1], 5),       # A itself, not a power of two
            ([0, 0, 0, 0, 0], 1)):      # flagged, nothing set: one row
        assert run(lengths, 16) == ([10 * n for n in lengths], want_carry)
    assert set(seen) == {1, 2, 4, 5}  # one body a width, each traced in every program
    del seen[:]
    for lengths, want_carry in (
            ([7, 3, 2, 0, 0, 0, 0, 0], 21),          # 3 = 2 + 1
            ([0, 0, 5, 0, 0, 0, 0, 0], 21),          # a gap: the second piece holds row 2
            ([1, 2, 3, 4, 5, 0, 0, 0], 41),
            ([1, 2, 3, 4, 5, 6, 0, 0], 42),
            ([1, 2, 3, 4, 5, 6, 7, 0], 421),
            ([1, 2, 3, 4, 5, 6, 7, 8], 8),
            ([0, 0, 0, 0, 0, 0, 0, 0], 1)):
        assert run(lengths, 1024) == ([10 * n for n in lengths], want_carry)
    assert seen == [8, 4, 2, 1] * 7  # log2(A) + 1 bodies a program, widest first
    assert run([1, 2, 3], 1024, admits=False) == ([0, 0, 0], 0)  # a phase that admits nothing


# what the parent commit's `_plan` made of the arrivals below (PR 41, f45e62d): per
# plan its phases' (steps, [(lane, prompt length)] admitted, [(lane, take)])
PARENT_PLANS = [
    [(4, [(0, 9), (1, 17), (2, 12)], [(0, 4), (1, 4), (2, 4)]),
     (1, [], [(0, 1), (1, 1), (2, 1)]),
     (1, [(0, 30)], [(0, 1), (1, 1), (2, 1)]),
     (2, [(0, 5)], [(0, 2), (1, 2)])],
    [(1, [(0, 21), (2, 8)], [(0, 1), (1, 1), (2, 1)]),
     (2, [(0, 14), (2, 6)], [(0, 2), (1, 2), (2, 2)]),
     (2, [], [(0, 2), (2, 2)]),
     (3, [], [(0, 3)])],
    [(4, [(0, 11)], [(0, 4)]), (4, [], [(0, 4)]), (4, [], [(0, 4)]), (4, [], [(0, 4)])],
    [(4, [], [(0, 4)]), (3, [], [(0, 3)])],
]
# the programs the parent named for them: (A, P) by each plan's widest phase
PARENT_VARIANTS = [(4, 32), (2, 32), (1, 16), (1, 16)]


def test_the_plan_is_the_parents_for_a_scripted_arrival_sequence():
    """Three lanes, chunk 4, four phases a plan, ten requests arriving in
    three batches, then plans with no arrival. While every lane is live or
    somebody waits the phases, their admissions and their takes are the
    parent's to the entry; since ISSUE 47 the first phase that opens with a
    lane vacant is the plan's last, so the second plan is the parent's first
    three phases and what the parent ran behind them goes out in the plans
    after it, a quantum (`chunk` here: no step has been timed) at a time.
    What ISSUE 42 changed is the program each plan names: A is the lanes'
    bucket, 4, whatever a phase admits, P alone follows the plan (its longest
    prompt's bucket), and a plan that admits nobody keeps the P of the
    dispatch before it, so no program is compiled for it."""
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, chunk=4, macro_phases=4, max_len=64,
                                   block_size=8, prefix_cache=False)
    eng.shutdown()  # planned on this thread; nothing is dispatched
    assert eng._variant([]) == (4, 16)  # before any dispatch: the smallest bucket
    rng = np.random.default_rng(5)
    batches = [[(9, 6), (17, 12), (12, 7), (30, 2), (5, 3)],
               [(21, 2), (8, 2), (14, 8), (6, 5)],
               [(11, 24)]] + [[]] * 5
    plans, variants, vacant = [], [], []
    for batch in batches:
        for n, new in batch:
            eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(), new)
        eng._drain_queue()
        phases = eng._plan()
        variants.append(eng._variant(phases))
        eng._last_P = variants[-1][1]  # as `_dispatch_macro` leaves it
        plans.append([(ph["steps"], [(s, len(r.prompt)) for s, r in ph["admissions"]],
                       [(s, t) for s, _, t in ph["takes"]]) for ph in phases])
        vacant.append([ph["vacant"] for ph in phases])
    assert eng._plan() is None
    # the first plan's lanes are full or waited for until its fourth phase,
    # the second's until its third: the parent's, phase for phase, to there
    assert plans[0] == PARENT_PLANS[0] and vacant[0] == [0, 0, 0, 1]
    assert plans[1] == PARENT_PLANS[1][:3] and vacant[1] == [0, 0, 1]
    # the third arrival finds two lanes free and a resident with 3 steps owed
    # (the parent's fourth phase of its second plan, now run beside it), then
    # decodes alone, 4 steps a plan where the parent planned 16 and 7
    assert plans[2] == [(3, [(1, 11)], [(0, 3), (1, 3)])]
    assert plans[3:] == [[(4, [], [(1, 4)])]] * 5
    assert sum(n for plan in plans[2:] for n, _, _ in plan) == 23
    assert all(v[-1] > 0 and not any(v[:-1]) for v in vacant)
    assert variants == [(4, 32), (4, 32)] + [(4, 16)] * 6
    # one program a P bucket: the parent named three for its four plans
    assert len(set(variants)) == 2 < len(set(PARENT_VARIANTS))


def test_one_program_a_prompt_bucket():
    """A live engine of four lanes: bursts of 4, 1, 3 and 2 prompts in the
    bucket of 32, one of 2 in the bucket of 16, then a request alone that
    decodes through dispatches that admit nobody. The parent compiled a
    program for every (A, P) a plan named, five for these and a sixth,
    (1, 16), for the dispatch that admits nobody; now there is one a bucket."""
    cfg, params, _ = _model("llama")
    eng = ContinuousBatchingEngine(params, cfg, n_slots=4, chunk=2, macro_phases=2, max_len=64,
                                   block_size=8, prefix_cache=False)
    rng = np.random.default_rng(6)
    try:
        for burst, n, new in ((4, 30, 2), (1, 20, 2), (3, 25, 2), (2, 17, 2), (2, 9, 2), (1, 27, 12)):
            prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for _ in range(burst)]
            reqs = eng.call_on_loop(lambda: [eng.submit(p, new) for p in prompts], timeout=60.0)
            assert all(r.done.wait(120) and r.error is None for r in reqs)
        assert eng._macro_paged_fn._cache_size() == 2
        m = eng.metrics()
        # 13 admissions; the burst of three ran four rows (a row of 32 is not
        # worth a pass over the weights), every other its own count
        assert m["admit_rows"] == (4 + 1 + 4 + 2 + 1) * 32 + 2 * 16
        assert m["admit_pieces"] == m["admit_phases"] == 6
    finally:
        eng.shutdown()
