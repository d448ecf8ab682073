"""The five readers of a request's own account (ISSUE 54) on a hand-made trace
whose answers are worked out here: `engine.request` spans of the requests that
finish inside the window, device costs from the two scopes of the executions
whole in the stretch (`engine.dispatch_lead_ms.stretch`, unedited), the
client's records by request id."""
import pytest

from benchmark import common, program_spans as ps

ADMIT, DECODE = ps.ADMIT, ps.DECODE
READERS = ("engine.request_lead_ms", "engine.request_stall_ms", "engine.request_tail_ms",
           "engine.request_unexplained_ms", "engine.short_plan_pct")
OLD_READERS = ("engine.plan_wait_ms", "engine.lane_wait_ms", "engine.dispatch_lead_ms",
               "engine.admit_stall_ms", "engine.finish_wait_steps", "engine.deliver_lag_ms",
               "engine.starved_idle_pct")

# what dispatches 11 and 12 planned: 90 steps and 2,000 admitted token rows between them
PLAN = dict(steps=40, admissions=3, finishing=2, finish_wait_steps=10, lane_steps=120,
            prompt_tokens=600, admit_rows=1000, admit_phases=2, plan_wait_us=300_000,
            lane_wait_us=600_000, admitted_first_plan=1, admit_lead_steps=24, admit_lead_phases=2,
            stall_lane_phases=5, vacant_lane_steps=20, blocked_lane_steps=12, spent_lane_steps=8)
SEQ_11 = dict(PLAN, short=1, q=4, late=0)
SEQ_12 = dict(PLAN, steps=50, lane_steps=170, vacant_lane_steps=30, short=0, q=0, late=1)
OTHER = dict(PLAN, admissions=0, short=1, q=2, late=0)


def _request(rid, reason="length", **kw):
    """An `engine.request` span's stats; the engine's clock reads 5000 s at the
    client's 100 s (`submit_us`, `done_us` are absolute)."""
    st = dict(rid=rid, reason=reason, tokens=11, submit_us=5_000_000_000, done_us=5_003_000_000,
              seq_first=11, seq_last=12, unseen_us=100_000, lane_wait_us=0, plan_us=20_000,
              flight_us=2_860_000, deliver_us=20_000, dispatches=2, lead_steps=10, lead_phases=1,
              lead_rows=500, own_rows=500, decode_steps=10, stall_phases=2, stall_rows=1000,
              tail_steps=5, tail_phases=1, tail_rows=250, ahead_us=0, late=0, spec=0)
    st.update(kw)
    return st


# a device step is 2.7 s / 90 steps = 30 ms, an admitted row 1.8 s / 2000 rows = 0.9 ms
R1 = _request("r1")
R2 = _request("r2", submit_us=5_001_000_000, done_us=5_005_480_000, unseen_us=200_000,
              lane_wait_us=300_000, plan_us=40_000, flight_us=3_900_000, deliver_us=40_000,
              dispatches=4, lead_steps=0, lead_phases=0, lead_rows=0, own_rows=1000,
              decode_steps=30, stall_phases=0, stall_rows=0, tail_steps=15, tail_phases=0,
              tail_rows=0, ahead_us=700_000, late=2, tokens=31)
CANCELLED = _request("r5", reason="cancelled")
OUTSIDE = _request("r6")  # finished before the window


def _recorded(with_requests=True):
    """Window 1.0-9.0, four macro-steps back to back from 0.5 s; e1 and e2 lie
    whole in the window and are not the last."""
    execs = [(0.5, 1.0), (1.5, 2.0), (3.5, 2.5), (6.0, 2.0)]
    ops = [(0.5, 1.0, DECODE),
           (1.5, 0.8, ADMIT), (2.3, 1.2, DECODE),      # e1
           (3.5, 1.0, ADMIT), (4.5, 1.5, DECODE),      # e2
           (6.0, 0.5, ADMIT), (6.5, 1.5, DECODE)]
    spans = [
        ("engine.plan", 0.80, 0.05, {}), ("engine.dispatch", 0.86, 0.02, {"seq": 11, **SEQ_11}),
        ("engine.resolve", 0.90, 0.62, {"seq": 10, **OTHER}),   # begins before the window
        ("engine.plan", 1.55, 0.05, {}), ("engine.dispatch", 1.61, 0.02, {"seq": 12, **SEQ_12}),
        ("engine.resolve", 1.65, 1.87, {"seq": 11, **SEQ_11}),
        ("engine.plan", 3.55, 0.04, {}), ("engine.dispatch", 3.60, 0.02, {"seq": 13, **OTHER}),
        ("engine.resolve", 3.65, 2.37, {"seq": 12, **SEQ_12}),
        ("engine.resolve", 6.05, 1.97, {"seq": 13, **OTHER}),
    ]
    if with_requests:
        spans += [("engine.request", 0.95, 0.0, OUTSIDE), ("engine.request", 3.51, 0.0, R1),
                  ("engine.request", 6.01, 0.0, R2), ("engine.request", 6.02, 0.0, CANCELLED)]
    else:  # the parent of PR 54: no request span, no `late` on a resolve
        spans = [(n, s, d, {k: v for k, v in st.items() if k != "late"}) for n, s, d, st in spans]
    return {"devices": 1, "window": (1.0, 9.0), "spans": sorted(spans, key=lambda s: s[1]),
            "kernels": {}, "modules": [("jit_macro_step_slots_paged", s, d) for s, d in execs],
            "busy": [(s, s + d) for s, d, _ in ops], "ops": ops}


def _ctx():
    records = [  # client latencies 3.02 and 4.51 s; the engine's were 3.0 and 4.48
        {"rid": "r1", "ok": True, "t_due": 99.99, "t_done": 103.01, "tokens": [1] * 11},
        {"rid": "r2", "ok": True, "t_due": 100.98, "t_done": 105.49, "tokens": [1] * 31},
        {"rid": "r4", "ok": False, "t_due": 103.0, "t_done": 104.0, "tokens": []}]
    timelines = {"r1": [{"kind": "submit", "t": 1000.0}, {"kind": "finish", "t": 1002.99}],
                 "r2": [{"kind": "submit", "t": 1001.0}, {"kind": "finish", "t": 1005.48}]}
    return {"facts": {"reduced": {"busy_s": 7.5, "window_s": 8.0}, "records": records,
                      "timelines": timelines, "lanes": 4,
                      "engine": {"slot_steps": 400, "useful_slot_steps": 300}},
            "config": {}, "e2e": {}, "peaks": {}}


def _read(name, ctx):
    return common.load_module("layer_metrics", name).read(ctx)


def test_the_spans_counted_and_the_device_costs():
    shared = common.load_module("layer_metrics", "engine.request_lead_ms")
    trace = _recorded()
    assert [st["rid"] for st in shared.finished(trace)] == ["r1", "r2"]  # not r5, not r6
    acc = shared.account.stretch(trace)
    assert shared.units(acc) == (pytest.approx(30.0), pytest.approx(0.9))
    p1 = shared.parts(R1, 30.0, 0.9)
    # 10 steps and 500 rows ahead of it; 500 rows its own; 10 steps; 1,000 rows of others; 5 + 250 after
    assert p1["lead_device_ms"] == pytest.approx(750.0) and p1["lead_ms"] == pytest.approx(770.0)
    assert (p1["own_ms"], p1["decode_ms"], p1["stall_ms"]) == (
        pytest.approx(450.0), pytest.approx(300.0), pytest.approx(900.0))
    assert p1["tail_device_ms"] == pytest.approx(375.0) and p1["tail_ms"] == pytest.approx(395.0)
    assert p1["unexplained_ms"] == pytest.approx(2860 - 750 - 450 - 300 - 900 - 375)
    assert shared.reduce([], acc) is None and shared.reduce([R1], None) is None


def test_request_readers_on_the_hand_made_trace(monkeypatch):
    monkeypatch.setattr(ps, "run_trace", lambda facts: _recorded())
    ctx = _ctx()
    lead = _read("engine.request_lead_ms", ctx)
    # r1: 20 + 0 + 750 with the device idle; r2: 40 + 700 + 0 behind a dispatch
    assert lead["value"] == pytest.approx((770.0 + 740.0) / 2) and lead["requests"] == 2
    assert lead["device_idle_at_enqueue_pct"] == pytest.approx(50.0)
    assert lead["lead_ms_device_idle"] == pytest.approx(770.0)
    assert lead["lead_ms_behind_a_dispatch"] == pytest.approx(740.0)
    assert (lead["decode_step_ms"], lead["admitted_row_ms"], lead["executions"]) == (
        pytest.approx(30.0), pytest.approx(0.9), 2)
    stall = _read("engine.request_stall_ms", ctx)
    assert stall["value"] == pytest.approx(450.0)            # (1,000 + 0 rows) x 0.9 / 2
    assert stall["stall_phases_a_request"] == 1 and stall["dispatches_a_request"] == 3
    tail = _read("engine.request_tail_ms", ctx)
    assert tail["device_ms"] == pytest.approx((375.0 + 450.0) / 2)
    assert tail["value"] == pytest.approx((395.0 + 490.0) / 2) and tail["deliver_ms"] == 30.0
    rest = _read("engine.request_unexplained_ms", ctx)
    # r2: 3,900 - 700 - 0 - 900 - 900 - 0 - 450 = 950 over three seams; r1: 85 over one
    assert rest["value"] == pytest.approx((85.0 + 950.0) / 2)
    assert rest["unexplained_ms_a_seam"] == pytest.approx(517.5 / 2)
    assert rest["late_requests_pct"] == pytest.approx(50.0) and rest["dispatches_a_request"] == 3
    assert rest["flight_split"]["decode_ms"] == pytest.approx(600.0)
    assert sum(rest["flight_split"].values()) == pytest.approx(rest["flight_ms"]) == pytest.approx(3380.0)
    # the whole account: five stations that tile the engine's 3.0 and 4.48 s
    assert rest["stations"] == {
        "unseen_ms": pytest.approx(150.0), "lane_wait_ms": pytest.approx(150.0),
        "plan_ms": pytest.approx(30.0), "flight_ms": pytest.approx(3380.0),
        "deliver_ms": pytest.approx(30.0)}
    assert rest["engine_finish_minus_submit_ms"] == pytest.approx(3740.0)
    # the client's clock reads 100 s where the engine's reads 5000: both monotonic, one machine
    monkeypatch.setattr(ps, "run_trace", lambda facts: _shifted())
    rest = _read("engine.request_unexplained_ms", ctx)
    assert rest["serve_plane_before_ms"] == pytest.approx(15.0)   # 10 and 20 ms
    assert rest["serve_plane_after_ms"] == pytest.approx(10.0)
    assert rest["mean_client_latency_ms"] == pytest.approx(3765.0)
    assert rest["account_sum_ms"] == pytest.approx(3765.0) and rest["account_requests"] == 2
    assert rest["account_residual_pct"] == pytest.approx(0.0, abs=1e-6)
    # three resolves start in the window: 11 short with q 4, 12 long and late, 13 short with q 2
    short = _read("engine.short_plan_pct", ctx)
    assert short["value"] == pytest.approx(200.0 / 3) and short["dispatches"] == 3
    assert short["mean_q_steps"] == 3 and short["late_resolves_pct"] == pytest.approx(100.0 / 3)
    # records that hold none of the rids: the value stands without the whole account
    bare = _read("engine.request_unexplained_ms", {**ctx, "facts": {**ctx["facts"], "records": []}})
    assert bare["value"] == pytest.approx(517.5) and "stations" not in bare


def _shifted():
    """The same trace with the requests' absolute stamps on the client's clock."""
    trace = _recorded()
    shift = lambda st: {**st, "submit_us": st["submit_us"] - 4_900_000_000,  # noqa: E731
                        "done_us": st["done_us"] - 4_900_000_000}
    trace["spans"] = [(n, s, d, shift(st) if n == "engine.request" else st)
                      for n, s, d, st in trace["spans"]]
    return trace


@pytest.mark.parametrize("name", READERS)
def test_the_parents_trace_gives_none(name, monkeypatch):
    """A program that writes no `engine.request` span and no `late`: every new
    reader returns None, and so it does for an untraced run."""
    monkeypatch.setattr(ps, "run_trace", lambda facts: _recorded(with_requests=False))
    assert _read(name, _ctx()) is None
    monkeypatch.setattr(ps, "run_trace", lambda facts: None)
    assert _read(name, _ctx()) is None
    # a window that holds no finished request, and a trace with no window mark
    monkeypatch.setattr(ps, "run_trace", lambda facts: {**_recorded(), "window": (7.0, 9.0)})
    assert _read(name, _ctx()) is None
    monkeypatch.setattr(ps, "run_trace", lambda facts: {**_recorded(), "window": None})
    assert _read(name, _ctx()) is None


@pytest.mark.parametrize("name", OLD_READERS)
def test_the_new_span_breaks_no_reader_that_is_there(name, monkeypatch):
    """Every reader there filters the spans by name: with the `engine.request`
    events and `late` in the trace each reads what it read without them."""
    monkeypatch.setattr(ps, "run_trace", lambda facts: _recorded(with_requests=False))
    before = _read(name, _ctx())
    monkeypatch.setattr(ps, "run_trace", lambda facts: _recorded())
    assert _read(name, _ctx()) == before and before is not None
