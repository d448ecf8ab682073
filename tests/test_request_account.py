"""A request's own account across every dispatch it rides (ISSUE 54).

- the five host stations of every finished request tile submit to finish, to
  the microsecond, and its first two are the wait account's own;
- the requests' counts and the dispatches' are two cuts of one plan: their
  sums meet `engine.metrics()`;
- under the vacancy rule an answer that rides k dispatches says so, and sits
  through an admission that a LATER dispatch makes;
- one `engine.request` event a finished rid in a `jax.profiler` trace, inside
  an `engine.resolve`, naming dispatches the trace holds;
- a cancelled and a shed request write theirs; a speculative plan says that
  its counts are estimates.

CPU, the tiny engine of `tests/test_engine_spans.py`. Where the loop is
stopped the test keeps `_loop_macro`'s order (intake, plan, dispatch, resolve
down to one dispatch in flight) and the spans are caught in place of
`jax.profiler.TraceAnnotation`.
"""
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama_decode
from ray_tpu.observability import ENGINE_SPANS, REQUEST_SPAN, lifeline
from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.test_engine_spans import _cfg_params, _engine_events

STATIONS = ("unseen_us", "lane_wait_us", "plan_us", "flight_us", "deliver_us")
COUNTS = ("dispatches", "lead_steps", "lead_phases", "lead_rows", "own_rows", "decode_steps",
          "stall_phases", "stall_rows", "tail_steps", "tail_phases", "tail_rows")
STATS = {"rid", "reason", "tokens", "submit_us", "done_us", "seq_first", "seq_last", *STATIONS,
         *COUNTS, "ahead_us", "late", "spec"}


class _Caught:
    """Stands where the engine makes a `TraceAnnotation`: keeps (name, stats)."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **stats):
        self.spans.append((name, stats))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass

    def requests(self):
        return [st for name, st in self.spans if name == REQUEST_SPAN]


def _engine(stopped=True, q=None, **options):
    cfg, params = _cfg_params()
    options = {"n_slots": 2, "chunk": 4, "macro_phases": 4, "max_len": 64, "block_size": 8,
               "prefix_cache": False, **options}
    eng = ContinuousBatchingEngine(params, cfg, **options)
    if stopped:
        eng.shutdown()  # the plans are made on the test's thread
    if q is not None:
        eng._quantum = lambda: q  # held: what this CPU's timings would make of it is not the test's
    eng._span = caught = _Caught()
    return eng, caught


def _prompts(seed, lengths):
    cfg, _ = _cfg_params()
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def _iteration(eng, seen):
    """One iteration of `_loop_macro` on this thread."""
    eng._drain_queue()
    eng._repair()
    phases = eng._plan()
    if phases:
        A, P = eng._variant(phases)
        counts = llm_engine._dispatch_counts(phases, False, eng._ctx_chunk, variant=(A, P),
                                             n_slots=eng.n_slots)
        seen.append({"seq": eng._m["dispatches"], "P": P, **counts})
        eng._dispatch_macro(phases, counts)
    while len(eng._pending) > (1 if phases else 0):
        eng._resolve_next()
    return phases


def _run(eng, seen):
    while eng._waiting or any(r is not None for r in eng._slots) or not eng._queue.empty():
        _iteration(eng, seen)
    while eng._pending:
        eng._resolve_next()


def _check_stations(req, st):
    """The five host stations tile [submit, done] to the microsecond."""
    whole = round((req._t_done - req._t_submit) * 1e6)
    assert sum(st[k] for k in STATIONS) == whole == st["done_us"] - st["submit_us"]
    assert all(st[k] >= 0 for k in STATIONS)
    assert st["submit_us"] == round(req._t_submit * 1e6)
    if req._t_admit is not None:  # the first two are the wait account's own
        assert (st["unseen_us"], st["lane_wait_us"]) == llm_engine._wait_us(req)


# (engine options, [(prompt length, answer)], index of a request given a stop token or None)
SCENARIOS = {
    # several admissions a plan, a one-token answer among them, the rest in later phases
    "several_admissions_a_plan": (dict(n_slots=2), [(9, 3), (17, 20), (12, 7), (9, 1), (30, 11), (14, 4)], None),
    # one lane: every request but the first is admitted in a later phase or plan, and waits for it
    "one_lane": (dict(n_slots=1), [(9, 6), (12, 1), (9, 9), (20, 2)], None),
    # a stop token ends the long answer ahead of its plan
    "a_stop_token": (dict(n_slots=2), [(9, 5), (17, 24), (12, 7), (10, 3)], 1),
    # four lanes and two requests: lanes vacant, nobody waits, nothing stalls
    "fewer_requests_than_lanes": (dict(n_slots=4), [(9, 12), (11, 1)], None),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_stations_tile_and_counts_are_conserved(scenario):
    cfg, params = _cfg_params()
    options, load, stopper = SCENARIOS[scenario]
    eng, caught = _engine(**options)
    prompts = _prompts(5, [n for n, _ in load])
    sampling = [None] * len(load)
    stop_at = None
    if stopper is not None:  # a token the greedy answer holds early on, and not before that place
        p, n = prompts[stopper], load[stopper][1]
        answer = llama_decode.generate(params, jnp.asarray([p], jnp.int32), cfg,
                                       max_new_tokens=n)[0].tolist()
        stop_at = max(i for i in range(8) if answer[i] not in answer[:i])
        sampling[stopper] = {"stop": [answer[stop_at]]}
    m0 = eng.metrics()
    reqs = [eng.submit(p, n, sampling=s, rid=f"{scenario}-{i}")
            for i, (p, (_, n), s) in enumerate(zip(prompts, load, sampling))]
    seen = []
    _run(eng, seen)
    assert all(r.done.is_set() and r.error is None for r in reqs)
    m = {k: v - m0[k] for k, v in eng.metrics().items() if isinstance(v, int) and k in m0}

    spans = {st["rid"]: st for st in caught.requests()}
    assert sorted(spans) == sorted(r.rid for r in reqs) and len(caught.requests()) == len(reqs)
    for r in reqs:
        st = spans[r.rid]
        assert set(st) == STATS
        _check_stations(r, st)
        assert st["reason"] == r.finish_reason and st["tokens"] == len(r.tokens)
        assert st["spec"] == 0 and st["ahead_us"] >= 0 and 0 <= st["late"] <= st["dispatches"]
        # its parents: the dispatch that admitted it and the one whose resolve finished it
        seqs = [d["seq"] for d in seen]
        assert st["seq_first"] in seqs and st["seq_last"] in seqs
        assert st["seq_first"] <= st["seq_last"]
        if r.finish_reason == "length":  # an exact plan: it rode every dispatch between the two
            assert st["dispatches"] == st["seq_last"] - st["seq_first"] + 1
            assert st["decode_steps"] == len(r.tokens) - 1
        # the same figures on the lifeline's `finish` event, where an operator reads them
        (finish,) = [e for e in eng.request_timeline(r.rid) if e["kind"] == "finish"]
        assert {k: finish[k] for k in STATS - {"rid"}} == {k: st[k] for k in STATS - {"rid"}}
    if stopper is not None:
        st = spans[reqs[stopper].rid]
        assert st["reason"] == "stop" and st["tokens"] == stop_at < load[stopper][1]
        # the plan's count: what was planned for it, past the stop (the discarded steps are waste)
        assert st["decode_steps"] > st["tokens"] and m["wasted_steps"] > 0

    # two cuts of one plan: the requests' sums are the dispatches', and the counters'
    total = lambda key: sum(st[key] for st in spans.values())  # noqa: E731
    planned = lambda key: sum(d[key] for d in seen)  # noqa: E731
    assert total("lead_steps") == planned("admit_lead_steps") == m["admit_lead_steps"]
    assert total("lead_phases") == planned("admit_lead_phases") == m["admit_lead_phases"]
    assert total("stall_phases") == planned("stall_lane_phases") == m["stall_lane_phases"]
    assert total("decode_steps") == planned("lane_steps") == m["useful_slot_steps"]
    assert total("tail_steps") == planned("finish_wait_steps")
    assert total("dispatches") >= len(reqs) and total("own_rows") >= planned("admit_rows")
    assert total("unseen_us") == m["plan_wait_us"] and total("lane_wait_us") == m["lane_wait_us"]
    # a request stalls by whole phases of somebody else's rows, and leads by rows run before its own
    for st in spans.values():
        assert (st["stall_rows"] > 0) == (st["stall_phases"] > 0)
        assert (st["lead_rows"] > 0) == (st["lead_phases"] > 0)
        assert st["own_rows"] > 0 and st["own_rows"] % 16 == 0
        assert (st["tail_rows"] > 0) == (st["tail_phases"] > 0)
    if scenario == "fewer_requests_than_lanes":
        assert total("stall_phases") == 0 == total("lead_steps")
        assert [spans[r.rid]["unseen_us"] > 0 for r in reqs] == [True, True]
        assert total("lane_wait_us") == 0
    if scenario == "one_lane":
        assert total("lane_wait_us") > 0 and total("lead_steps") > 0
        assert spans[reqs[1].rid]["decode_steps"] == 0  # a one-token answer: the prefill's own


def test_the_counts_of_a_hand_built_plan():
    """`test_engine_spans`' plan of four phases, read a request at a time: a
    program of (2, 16), so a phase of two admissions runs 32 rows and one of
    one 16."""
    def req(prompt_len, remaining, **kw):
        return types.SimpleNamespace(prompt=[0] * prompt_len, _start=0, _remaining=remaining,
                                     max_new_tokens=kw.get("max_new", 1), _t_submit=0.0,
                                     _t_seen=0.0, _t_admit=0.0, _acct=llm_engine._Account())

    a, b, c, d = req(9, 0, max_new=3), req(12, 7, max_new=20), req(20, 0, max_new=7), req(5, 0)
    phases = [
        {"steps": 2, "admissions": [(0, a), (1, b)], "takes": [(0, a, 2), (1, b, 2)]},
        {"steps": 4, "admissions": [(0, c)], "takes": [(0, c, 4), (1, b, 4)]},
        {"steps": 2, "admissions": [], "takes": [(0, c, 2), (1, b, 2)]},
        {"steps": 4, "admissions": [(0, d)], "takes": [(1, b, 4)]},
    ]
    counts = llm_engine._dispatch_counts(phases, variant=(2, 16))
    got = {name: {k: getattr(r._acct, k) for k in COUNTS} for name, r in zip("abcd", (a, b, c, d))}
    zero = dict.fromkeys(COUNTS, 0)
    assert got == {
        # its last token after 2 of 12 steps: phases 1 and 3 still admit, 16 rows each
        "a": {**zero, "dispatches": 1, "own_rows": 32, "decode_steps": 2,
              "tail_steps": 10, "tail_phases": 2, "tail_rows": 32},
        # live through c's admission and d's, and not finishing here
        "b": {**zero, "dispatches": 1, "own_rows": 32, "decode_steps": 12,
              "stall_phases": 2, "stall_rows": 32},
        # behind phase 0's 2 steps and 32 rows; its last token after 8 steps, d's phase to come
        "c": {**zero, "dispatches": 1, "lead_steps": 2, "lead_phases": 1, "lead_rows": 32,
              "own_rows": 16, "decode_steps": 6, "tail_steps": 4, "tail_phases": 1, "tail_rows": 16},
        # one token, the prefill's: behind 8 steps and two admitting phases, its own phase's 4 steps to wait
        "d": {**zero, "dispatches": 1, "lead_steps": 8, "lead_phases": 2, "lead_rows": 48,
              "own_rows": 16, "tail_steps": 4},
    }
    assert counts["admit_lead_steps"] == 2 + 8 and counts["finish_wait_steps"] == 10 + 4 + 4
    assert counts["stall_lane_phases"] == 2 and counts["admit_rows"] == 64
    # the next dispatch carries b on: its counts ADD, its lead stays its admitting dispatch's
    b._remaining = 0
    llm_engine._dispatch_counts([{"steps": 4, "admissions": [], "takes": [(1, b, 4)]},
                                 {"steps": 3, "admissions": [(0, req(7, 2, max_new=3))],
                                  "takes": [(1, b, 3)]}], variant=(2, 16))
    assert {k: getattr(b._acct, k) for k in COUNTS} == {
        **zero, "dispatches": 2, "own_rows": 32, "decode_steps": 19, "stall_phases": 3,
        "stall_rows": 48}


def test_an_answer_over_k_short_plans_and_a_later_dispatchs_admission():
    """The vacancy rule's set-up (`tests/test_vacant_plan.py`): four lanes, a
    quantum of 2. A lone resident's plans are one phase of two steps, so its
    answer of 11 tokens rides 5 dispatches; a second request arrives while it
    runs and a LATER dispatch admits it: the resident sits through that phase,
    which no count of its own admitting dispatch could show."""
    eng, caught = _engine(q=2, n_slots=4, chunk=8)
    first, second = _prompts(11, (9, 12))
    resident = eng.submit(first, 11, rid="short-resident")
    seen = []
    _iteration(eng, seen)   # admits the resident: 2 steps
    _iteration(eng, seen)   # 2 more, nobody else there
    arrival = eng.submit(second, 3, rid="short-arrival")
    _run(eng, seen)
    assert resident.error is None and arrival.error is None
    assert all(d["short"] == 1 and d["steps"] <= 2 for d in seen)
    spans = {st["rid"]: st for st in caught.requests()}
    st, late = spans["short-resident"], spans["short-arrival"]
    assert st["decode_steps"] == 10 and st["dispatches"] == 5 == st["seq_last"] - st["seq_first"] + 1
    # the arrival is admitted by the resident's third dispatch, alone in its phase: 16 rows
    assert late["seq_first"] == st["seq_first"] + 2 and late["own_rows"] == 16
    assert (st["stall_phases"], st["stall_rows"]) == (1, 16)
    admitting = next(d for d in seen if d["seq"] == late["seq_first"])
    assert admitting["stall_lane_phases"] == 1 and admitting["admissions"] == 1
    assert (late["lead_steps"], late["stall_phases"], late["dispatches"]) == (0, 0, 1)
    # every dispatch but the first was enqueued while the one before it was in flight
    assert late["ahead_us"] > 0 == st["ahead_us"]
    for r in (resident, arrival):
        _check_stations(r, spans[r.rid])


class _ReadyOrNot:
    """A dispatch's result as the host finds it when it comes to fetch it."""

    def __init__(self, array, ready):
        self._array, self._ready = array, ready

    def is_ready(self):
        return self._ready

    def __array__(self, *args, **kwargs):
        return np.asarray(self._array)


def test_late_counts_the_resolves_the_host_came_late_for():
    """`late` of a request is how many of ITS dispatches were ready before the
    host asked: the second and the fourth of the resident's five here, not
    the one that was resolved before the arrival was admitted."""
    eng, caught = _engine(q=2, n_slots=4, chunk=8)
    resident = eng.submit(_prompts(12, (9,))[0], 11, rid="late-resident")
    arrival = None
    for i in range(5):
        if i == 2:
            arrival = eng.submit(_prompts(13, (12,))[0], 5, rid="late-arrival")
        eng._drain_queue()
        phases = eng._plan()
        counts = llm_engine._dispatch_counts(phases, False, eng._ctx_chunk,
                                             variant=eng._variant(phases), n_slots=eng.n_slots)
        eng._dispatch_macro(phases, counts)
        entry = eng._pending.pop()
        eng._pending.append(entry[:2] + (_ReadyOrNot(entry[2], ready=i in (1, 3)),) + entry[3:])
        eng._resolve_next()
    assert resident.done.is_set() and arrival.done.is_set()
    spans = {st["rid"]: st for st in caught.requests()}
    assert spans["late-resident"]["late"] == 2 and spans["late-arrival"]["late"] == 1
    assert spans["late-resident"]["dispatches"] == 5 and spans["late-arrival"]["dispatches"] == 2
    assert eng.metrics()["late_resolves"] == 2
    resolves = [st for name, st in caught.spans if name == "engine.resolve"]
    assert [st["late"] for st in resolves] == [0, 1, 0, 1, 0]


def test_a_cancelled_and_a_shed_request_write_their_spans():
    """Both end in the queue, outside any resolve: the station they ended in
    runs to the end and the later ones read 0."""
    eng, caught = _engine(n_slots=1)
    prompts = _prompts(17, (9, 9, 9))
    running = eng.submit(prompts[0], 40, rid="end-running")  # three dispatches long
    queued = eng.submit(prompts[1], 4, rid="end-cancelled")
    doomed = eng.submit(prompts[2], 4, rid="end-shed",
                        sampling={"deadline": time.time() + 0.05})
    seen = []
    _iteration(eng, seen)  # admits `running`; the two others are seen and wait for the lane
    eng.cancel(queued)
    time.sleep(0.06)
    eng._shed_expired()
    _run(eng, seen)
    spans = {st["rid"]: st for st in caught.requests()}
    assert sorted(spans) == ["end-cancelled", "end-running", "end-shed"]
    assert len(caught.requests()) == 3  # one each, whatever the loop did after they ended
    assert spans["end-running"]["reason"] == "length"
    for rid, reason, req in (("end-cancelled", "cancelled", queued), ("end-shed", "shed", doomed)):
        st = spans[rid]
        assert (st["reason"], st["tokens"], st["dispatches"]) == (reason, 0, 0)
        assert (st["seq_first"], st["seq_last"]) == (-1, -1)
        assert st["unseen_us"] > 0 and st["lane_wait_us"] > 0  # seen by the first plan, never admitted
        assert st["plan_us"] == st["flight_us"] == st["deliver_us"] == 0
        assert st["unseen_us"] + st["lane_wait_us"] == st["done_us"] - st["submit_us"]
        assert req.done.is_set()
    # the lifeline's last event of each rid carries the same figures
    (ev,) = [e for e in lifeline.events("end-cancelled") if e["kind"] == "finish"]
    assert ev["reason"] == "cancelled" and ev["lane_wait_us"] == spans["end-cancelled"]["lane_wait_us"]
    (ev,) = [e for e in lifeline.events("end-shed") if e["kind"] == "shed"]
    assert ev["reason"] == "DeadlineExceededError" and ev["unseen_us"] == spans["end-shed"]["unseen_us"]


def test_a_request_cancelled_in_flight_keeps_what_it_had():
    eng, caught = _engine(n_slots=2)
    req = eng.submit(_prompts(19, (9,))[0], 30, rid="end-in-flight")
    seen = []
    _iteration(eng, seen)
    _iteration(eng, seen)
    eng.cancel(req)
    _run(eng, seen)
    (st,) = caught.requests()
    assert st["reason"] == "cancelled" and st["seq_first"] == seen[0]["seq"] and st["seq_last"] == -1
    assert st["dispatches"] == 2 and st["decode_steps"] == seen[0]["steps"] + seen[1]["steps"]
    assert st["plan_us"] > 0 and st["flight_us"] > 0 == st["deliver_us"]
    assert sum(st[k] for k in STATIONS) == st["done_us"] - st["submit_us"]


def test_the_speculative_plan_says_its_counts_are_estimates():
    """Verify rounds for steps and estimates for counts: the span says
    `spec` 1, and its `decode_steps` are the rounds planned for the request,
    not its tokens."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, chunk=4, macro_phases=4, max_len=64,
                                   block_size=8, draft_model="self", num_speculative_tokens=2)
    eng._span = caught = _Caught()
    try:
        load = ((9, 6), (12, 1), (7, 9), (10, 4))
        reqs = [eng.submit(p, n, rid=f"spec-{i}")
                for i, (p, (_, n)) in enumerate(zip(_prompts(4, [n for n, _ in load]), load))]
        assert all(r.done.wait(120) for r in reqs) and all(r.error is None for r in reqs)
    finally:
        eng.shutdown()
    spans = {st["rid"]: st for st in caught.requests()}
    assert sorted(spans) == [f"spec-{i}" for i in range(4)]
    for r in reqs:
        st = spans[r.rid]
        assert st["spec"] == 1 and st["reason"] == "length" and st["tokens"] == len(r.tokens)
        _check_stations(r, st)
        assert st["dispatches"] >= 1 and st["seq_first"] <= st["seq_last"]
    # verify rounds a lane rode, its own and the others' (a lane is freed only after delivery,
    # so even the one-token answer may ride some): estimates, which is what `spec` says
    assert spans["spec-2"]["decode_steps"] > 0


def test_one_request_span_a_finished_rid_in_a_profiler_trace(tmp_path):
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, chunk=4, macro_phases=4, max_len=64,
                                   block_size=8)
    eng._quantum = lambda: 2
    try:
        eng.generate(_prompts(0, (9,))[0], 5)  # the loop is up and a program compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            m0 = eng.metrics()
            load = ((9, 3), (17, 20), (12, 7), (9, 1), (30, 11))
            reqs = [eng.submit(p, n, rid=f"traced-{i}")
                    for i, (p, (_, n)) in enumerate(zip(_prompts(1, [n for n, _ in load]), load))]
            assert all(r.done.wait(120) for r in reqs) and all(r.error is None for r in reqs)
            m1 = eng.metrics()
            time.sleep(0.15)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (events,) = _engine_events(tmp_path).values()  # the loop thread's line alone
    assert {e[0] for e in events} == set(ENGINE_SPANS) | {REQUEST_SPAN}
    requests = [e for e in events if e[0] == REQUEST_SPAN]
    resolves = [e for e in events if e[0] == "engine.resolve"]
    dispatched = {int(e[3]["seq"]) for e in events if e[0] == "engine.dispatch"}
    assert sorted(e[3]["rid"] for e in requests) == sorted(r.rid for r in reqs)
    by_rid = {r.rid: r for r in reqs}
    for _, start, end, st in requests:
        assert set(st) == STATS
        # it lies inside the resolve of the dispatch that finished it
        (parent,) = [r for r in resolves if r[1] <= start and end <= r[2]]
        assert int(parent[3]["seq"]) == int(st["seq_last"])
        assert {int(st["seq_first"]), int(st["seq_last"])} <= dispatched
        req = by_rid[st["rid"]]
        assert sum(int(st[k]) for k in STATIONS) == round((req._t_done - req._t_submit) * 1e6)
        assert int(st["tokens"]) == len(req.tokens) and st["reason"] == "length"
        assert 0 <= int(st["late"]) <= int(st["dispatches"])
    # `late` on every resolve, and their sum the counter's
    assert sum(int(r[3]["late"]) for r in resolves) == m1["late_resolves"] - m0["late_resolves"]
    assert sum(int(st["decode_steps"]) for _, _, _, st in requests) == (
        m1["useful_slot_steps"] - m0["useful_slot_steps"])
