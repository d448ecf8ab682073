"""A plan ends where a lane is left vacant (ISSUE 47).

`ContinuousBatchingEngine._plan` runs on to `macro_phases` phases while every
lane is live or somebody waits; the first phase that opens with a lane free
and nobody waiting is its last and decodes at most the vacancy quantum, which
the engine derives from the time it measures for its own decode steps. Driven
synchronously, as `test_serve_llm.test_macro_dispatch_amortization_smoke`
drives it (no device timing), but in the order `_loop_macro` keeps: intake,
plan, dispatch, then resolve down to one dispatch in flight.
"""
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, llama_decode
from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import VACANT_PLAN_S, ContinuousBatchingEngine


@functools.lru_cache(maxsize=1)
def _cfg_params():
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _engine(q=None, stopped=True, **options):
    """A tiny paged engine of chunk 8, its loop stopped, whose quantum is held
    at `q` steps (for `q` None its own: `chunk`, until it has timed a step;
    the CPU's step times would make every quantum `chunk` again)."""
    cfg, params = _cfg_params()
    options = {"n_slots": 4, "chunk": 8, "macro_phases": 4, "max_len": 64, "block_size": 8,
               "prefix_cache": False, **options}
    eng = ContinuousBatchingEngine(params, cfg, **options)
    if stopped:
        eng.shutdown()
    if q is not None:
        eng._quantum = lambda: q
    return eng


def _prompts(seed, lengths):
    cfg, _ = _cfg_params()
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def _shape(phases):
    """(steps, admitted prompt lengths, [(lane, take)]) a phase."""
    return [(ph["steps"], [len(r.prompt) for _, r in ph["admissions"]],
             [(s, t) for s, _, t in ph["takes"]]) for ph in phases]


def _iteration(eng, seen):
    """One iteration of `_loop_macro` on this thread: intake, plan, dispatch,
    resolve one behind. -> the plan's phases (None: nothing to plan)."""
    eng._drain_queue()
    phases = eng._plan()
    if phases:
        A, P = eng._variant(phases)
        counts = llm_engine._dispatch_counts(phases, False, eng._ctx_chunk, variant=(A, P),
                                             n_slots=eng.n_slots)
        eng._dispatch_macro(phases, counts)
        seen.append((phases, counts))
    while len(eng._pending) > (1 if phases else 0):
        eng._resolve_next()
    return phases


def _expected(prompts_and_answers):
    cfg, params = _cfg_params()
    return [llama_decode.generate(params, jnp.asarray([p], jnp.int32), cfg,
                                  max_new_tokens=n)[0].tolist() for p, n in prompts_and_answers]


def test_the_quantum_follows_the_measured_step():
    """`chunk` until a step has been timed; then the steps that take about
    `VACANT_PLAN_S`, between 1 and `chunk`, by the MEDIAN of the last five
    readings: one interval that held a compile or a collection moves nothing."""
    eng = _engine()
    assert eng._quantum() == eng.chunk == 8
    eng._admit_s.extend([0.02] * 3)  # three short admissions timed: the gate is open
    for step_ms, want in ((10.27, 4), (18.2, 3), (3.78, 8), (6.8, 6), (45.0, 1), (0.2, 8)):
        eng._step_s.clear()
        eng._step_s.append(step_ms / 1e3)
        assert eng._quantum() == want, step_ms
    eng._step_s.extend([0.0102, 30.0, 0.0103, 1e-6])  # a compile, then a late host
    assert eng._quantum() == 4
    eng._step_s.extend([0.02] * 5)  # five readings back, the old ones are gone
    assert eng._quantum() == 2


def test_long_or_untimed_admissions_leave_the_plan_its_length():
    """Letting an arrival in stalls every resident for an admission's time. The
    engine times its admitting phases as it times its steps (what a dispatch
    that ran behind another took, less its decode steps at the measured pace,
    a phase); until it has timed three, and while their median is over
    `VACANT_PLAN_S`, the quantum is 0 and a vacant lane closes no plan: the
    plan is the parent's."""
    eng = _engine(n_slots=3)
    eng._time_dispatch({"steps": 4, "admit_phases": 1}, 0.5)   # no step timed yet: not read
    assert not eng._admit_s and not eng._step_s
    eng._time_dispatch({"steps": 4, "admit_phases": 0}, 0.040)
    assert list(eng._step_s) == [0.010] and eng._quantum() == 0  # no admission timed yet
    eng._time_dispatch({"steps": 0, "admit_phases": 0}, 0.3)   # an empty dispatch says nothing
    # 75 ms for 3 steps and a phase of 512 tokens; 190 ms for 5 steps and two phases
    eng._time_dispatch({"steps": 3, "admit_phases": 1}, 0.075)
    eng._time_dispatch({"steps": 5, "admit_phases": 2}, 0.190)
    assert [round(a, 6) for a in eng._admit_s] == [0.045, 0.070] and list(eng._step_s) == [0.010]
    assert eng._quantum() == 0
    load = list(zip(_prompts(7, (9, 12)), (30, 3)))
    for p, n in load:
        eng.submit(p, n)
    eng._drain_queue()
    phases = eng._plan()
    # lane 2 stands vacant all along and nothing closes the plan
    assert _shape(phases) == [(2, [9, 12], [(0, 2), (1, 2)]), (8, [], [(0, 8)]),
                              (8, [], [(0, 8)]), (8, [], [(0, 8)])]
    assert all(ph["vacant"] and "short" not in ph for ph in phases)
    counts = llm_engine._dispatch_counts(phases, n_slots=3)
    assert (counts["short"], counts["q"]) == (0, 0) and counts["vacant_lane_steps"] == 2 + 3 * 16
    # a third reading, short, is not yet the median; two more are
    eng._time_dispatch({"steps": 4, "admit_phases": 1}, 0.060)
    assert len(eng._admit_s) == 3 and eng._quantum() == 0
    for _ in range(2):
        eng._time_dispatch({"steps": 4, "admit_phases": 1}, 0.060)
    assert eng._quantum() == 4
    assert [len(ph) for ph in (eng._plan(),)] == [1]


class _StillRunning:
    """A dispatch's result that the host finds still in the making when it
    comes to fetch it, as it does on the chip: the host is on time."""

    def __init__(self, array):
        self._array = array

    def is_ready(self):
        return False

    def __array__(self, *args, **kwargs):
        return np.asarray(self._array)


def test_a_shut_gate_opens_again_on_lone_arrivals():
    """While long admissions hold the quantum at 0 a plan holds a lone
    request's whole answer, so no dispatch runs behind another. One shipped
    to an idle device is timed from its own dispatch: the next admissions are
    read all the same, and three short ones open the gate again."""
    eng = _engine()
    eng._step_s.append(1.0)        # a step so slow that what an admission leaves over is short
    eng._admit_s.extend([0.1] * 5)
    assert eng._quantum() == 0 and eng._t_started is None
    for p in _prompts(8, (9, 12, 17)):
        assert eng._quantum() == 0
        eng.submit(p, 11)
        phases = _iteration(eng, [])
        assert [ph["steps"] for ph in phases] == [8, 2] and "short" not in phases[-1]
        assert len(eng._pending) == 1 and eng._t_started is not None
        entry = eng._pending.popleft()
        eng._pending.append(entry[:2] + (_StillRunning(entry[2]),) + entry[3:])
        eng._resolve_next()
        assert len(eng._admit_s) == 5 and eng._admit_s[-1] < VACANT_PLAN_S
        assert eng._t_started is None  # nothing behind it: the device is idle again
    assert eng._quantum() == 1


@pytest.mark.parametrize("q", [None, 3, 1])
def test_a_vacant_lane_closes_the_plan(q):
    """(a) lanes vacant and nobody waiting: one phase of at most the quantum,
    and the next plan continues the residents; (e) every dispatch's lane
    account adds to n_slots x steps and `short_plans` counts these plans."""
    eng = _engine(q or 8)
    quantum = q or eng.chunk
    load = list(zip(_prompts(1, (9, 20)), (19, 7)))
    reqs = [eng.submit(p, n) for p, n in load]
    seen = []
    while _iteration(eng, seen):
        pass
    assert [r.tokens for r in reqs] == _expected(load)
    for phases, counts in seen:
        # two of four lanes stand empty throughout: every plan is one phase
        assert len(phases) == 1 and phases[0]["vacant"] >= 2 and phases[0]["short"] == quantum
        assert (counts["short"], counts["q"], counts["phases"]) == (1, quantum, 1)
        assert counts["steps"] <= quantum
        assert (counts["lane_steps"] + counts["vacant_lane_steps"] + counts["blocked_lane_steps"]
                + counts["spent_lane_steps"] == eng.n_slots * counts["steps"])
    # the first plan admits both; the residents then ride quantum after
    # quantum until the shorter has its 6 steps, the longer its 18
    assert _shape(seen[0][0]) == [(min(quantum, 6), [9, 20], [(0, min(quantum, 6)),
                                                             (1, min(quantum, 6))])]
    assert sum(c["steps"] for _, c in seen) == 18
    assert sum(t for ph, _ in seen for _, _, t in ph[0]["takes"]) == 18 + 6
    assert len(seen) >= -(-6 // quantum) + -(-12 // quantum)
    m = eng.metrics()
    assert m["short_plans"] == m["dispatches"] == len(seen)
    assert m["vacant_lane_steps"] == sum(c["vacant_lane_steps"] for _, c in seen) > 0


FULL_PLANS = {
    # (lanes, [(prompt length, answer)]) -> the parent's plan (PR 46, 2a4acdf),
    # phase for phase: (steps, admitted prompt lengths, [(lane, take)])
    # twice the lanes' requests: somebody waits until the last pair is in,
    # and the lanes are full until it ends
    "somebody_waits": (2, [(9, 6), (12, 12), (17, 4), (5, 9)],
                       [(5, [9, 12], [(0, 5), (1, 5)]),
                        (3, [17], [(0, 3), (1, 3)]),
                        (3, [5], [(0, 3), (1, 3)]),
                        (5, [], [(0, 5)])]),
    # as many requests as lanes, of equal length: never a lane free
    "lanes_full": (3, [(9, 20), (12, 20), (17, 20)],
                   [(8, [9, 12, 17], [(0, 8), (1, 8), (2, 8)]),
                    (8, [], [(0, 8), (1, 8), (2, 8)]),
                    (3, [], [(0, 3), (1, 3), (2, 3)])]),
}


@pytest.mark.parametrize("state", sorted(FULL_PLANS))
def test_full_lanes_or_a_queue_keep_the_parents_plan(state):
    """(b) while every lane is live or somebody waits the plan is the
    parent's, phase for phase, whatever the quantum: pinned on fixed states
    from the parent commit's `_plan`."""
    n_slots, load, parents = FULL_PLANS[state]
    eng = _engine(q=2, n_slots=n_slots)
    for p, (_, n) in zip(_prompts(2, [n for n, _ in load]), load):
        eng.submit(p, n)
    eng._drain_queue()
    phases = eng._plan()
    if state == "somebody_waits":
        # the parent's to the phase that opens with lane 1 free and nobody
        # waiting; that one is cut at the quantum and closes the plan
        assert _shape(phases) == parents[:3] + [(2, [], [(0, 2)])]
        assert [ph["vacant"] for ph in phases] == [0, 0, 0, 1]
        assert "short" in phases[-1] and not any("short" in ph for ph in phases[:-1])
    else:
        assert _shape(phases) == parents
        assert not any(ph["vacant"] or "short" in ph for ph in phases)
        counts = llm_engine._dispatch_counts(phases, n_slots=n_slots)
        assert (counts["short"], counts["q"], counts["vacant_lane_steps"]) == (0, 0, 0)


def test_the_pool_refusing_the_queues_head_is_not_a_vacancy():
    """Lanes empty because the pool is short are `blocked`, not `vacant`:
    nobody new could be let in sooner, so the plan keeps its length."""
    eng = _engine(q=2, n_slots=2, n_blocks=6)  # four blocks a request, five in the pool
    for p in _prompts(3, (20, 20)):
        eng.submit(p, 12)
    eng._drain_queue()
    phases = eng._plan()
    assert [(ph["vacant"], ph["blocked"]) for ph in phases][0] == (0, 1)
    assert phases[0]["steps"] == 8 > eng._quantum() and "short" not in phases[0]
    assert len(phases) > 1


def test_an_arrival_between_two_short_dispatches_rides_the_plan_after_the_next():
    """(c) the pipeline stays two deep: a request that arrives while dispatch
    N runs and N+1 is queued behind it is admitted by plan N+2, whose phase
    still ends at the quantum; it waits no whole answer of a resident's."""
    eng = _engine(q=2)
    (resident, late) = _prompts(4, (9, 12))
    first = eng.submit(resident, 30)
    seen = []
    _iteration(eng, seen)                # N: admits the resident; nothing to resolve yet
    _iteration(eng, seen)                # N+1 queued behind N; N resolved
    assert len(eng._pending) == 1 and [c["admissions"] for _, c in seen] == [1, 0]
    second = eng.submit(late, 5)         # arrives while the host would block in a resolve
    phases = _iteration(eng, seen)       # N+2: the first plan to see it
    assert _shape(phases) == [(2, [12], [(0, 2), (1, 2)])] and phases[0]["short"] == 2
    assert second._t_admit == second._t_seen == eng._t_plan
    # it ran behind 2 x 2 decode steps of the resident's 29, not behind them all
    assert sum(c["steps"] for _, c in seen[:2]) == 4
    while _iteration(eng, seen):
        pass
    assert [first.tokens, second.tokens] == _expected([(resident, 30), (late, 5)])
    m = eng.metrics()
    assert m["short_plans"] == m["dispatches"] == len(seen) >= 15
    assert m["slot_steps"] == eng.n_slots * sum(c["steps"] for _, c in seen)
    assert (m["useful_slot_steps"] + m["vacant_lane_steps"] + m["blocked_lane_steps"]
            + m["spent_lane_steps"] == m["slot_steps"])


@pytest.mark.parametrize("q", [1, 3])
def test_tokens_are_generates_under_arrivals_mid_flight(q):
    """(d) a live engine whose quantum is one or three steps, requests
    arriving while others decode, more of them than lanes for a while: every
    answer is `llama_decode.generate`'s, and both kinds of plan were made."""
    eng = _engine(q, stopped=False, n_slots=3, macro_phases=3)
    try:
        lengths, answers = (9, 17, 12, 30, 5, 21, 8, 14), (14, 3, 9, 1, 20, 6, 11, 4)
        load = list(zip(_prompts(5, lengths), answers))
        reqs, lock = [None] * len(load), threading.Lock()

        def client(i, delay):
            time.sleep(delay)
            r = eng.submit(*load[i])
            with lock:
                reqs[i] = r

        # a burst of five on three lanes, then three stragglers
        threads = [threading.Thread(target=client, args=(i, 0.0 if i < 5 else 0.03 * (i - 4)))
                   for i in range(len(load))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r.done.wait(180) and r.error is None for r in reqs)
        assert [r.tokens for r in reqs] == _expected(load)
        m = eng.metrics()
        assert 0 < m["short_plans"] <= m["dispatches"]
        assert (m["useful_slot_steps"] + m["vacant_lane_steps"] + m["blocked_lane_steps"]
                + m["spent_lane_steps"] == m["slot_steps"])
        assert m["useful_slot_steps"] == sum(n - 1 for n in answers)
    finally:
        eng.shutdown()


def test_the_loop_times_its_own_dispatches_and_nothing_else_sets_the_quantum():
    """A live engine reads a step's time off decode-only dispatches that ran
    behind another while the host was on time for both ends (on this CPU it
    may never be: then there is no reading, and the quantum is `chunk`), and
    nothing decides the quantum but those readings: no constructor argument,
    no environment variable, no field of the model's config."""
    import inspect

    # (a chunk of 3: `test_admit_width` counts the programs of chunk 2's jit, which a worker's tests share)
    eng = _engine(stopped=False, n_slots=2, chunk=3, macro_phases=2)
    try:
        assert not eng._step_s and eng._quantum() == 3
        (p,) = _prompts(6, (9,))
        assert len(eng.generate(p, 25)) == 25  # eight decode-only dispatches, one behind another
        assert all(0 < s < 5.0 for s in eng._step_s) and all(a < 5.0 for a in eng._admit_s)
        assert 0 <= eng._quantum() <= 3
    finally:
        eng.shutdown()
    arguments = set(inspect.signature(ContinuousBatchingEngine.__init__).parameters)
    assert not {a for a in arguments if "quant" in a or "vacan" in a or "short" in a}
    source = "".join(inspect.getsource(getattr(llm_engine.ContinuousBatchingEngine, name))
                     for name in ("_quantum", "_time_dispatch", "_plan", "_resolve_next"))
    assert "environ" not in source and "cfg" not in source
