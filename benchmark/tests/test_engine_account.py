"""The seven readers of the engine's accounts (ISSUE 41) on a hand-made trace
whose answers are worked out here: counts from the `engine.resolve` spans of
the executions whole in the stretch, device times from the two scopes."""
import pytest

from benchmark import common, program_spans as ps

ADMIT, DECODE = ps.ADMIT, ps.DECODE
READERS = ("engine.plan_wait_ms", "engine.lane_wait_ms", "engine.dispatch_lead_ms",
           "engine.admit_stall_ms", "engine.vacant_lane_pct", "engine.blocked_lane_pct",
           "engine.admit_real_pct")
NEW_STATS = ("admit_phases", "plan_wait_us", "lane_wait_us", "admitted_first_plan",
             "admit_lead_steps", "admit_lead_phases", "stall_lane_phases", "vacant_lane_steps",
             "blocked_lane_steps", "spent_lane_steps")

# four lanes; what dispatches 11 and 12 planned (4 x 40 = 120 + 20 + 12 + 8, 4 x 50 = 170 + 30)
SEQ_11 = dict(steps=40, admissions=3, finishing=2, finish_wait_steps=10, lane_steps=120,
              prompt_tokens=600, admit_rows=1024, admit_phases=2, plan_wait_us=300_000,
              lane_wait_us=600_000, admitted_first_plan=1, admit_lead_steps=24,
              admit_lead_phases=2, stall_lane_phases=5, vacant_lane_steps=20,
              blocked_lane_steps=12, spent_lane_steps=8)
SEQ_12 = dict(steps=50, admissions=2, finishing=3, finish_wait_steps=15, lane_steps=170,
              prompt_tokens=400, admit_rows=1024, admit_phases=1, plan_wait_us=200_000,
              lane_wait_us=0, admitted_first_plan=2, admit_lead_steps=16, admit_lead_phases=0,
              stall_lane_phases=3, vacant_lane_steps=30, blocked_lane_steps=0,
              spent_lane_steps=0)
OTHER = dict(SEQ_12, admissions=0)  # seq 10 began before the window, seq 13 is the trace's last


def _recorded():
    """Window 1.0-9.0, four macro-steps back to back from 0.5 s. Each is
    planned and dispatched while the one before it runs and resolved just
    after it ends; e1 and e2 lie whole in the window and are not the last."""
    execs = [(0.5, 1.0), (1.5, 2.0), (3.5, 2.5), (6.0, 2.0)]
    ops = [(0.5, 1.0, DECODE),
           (1.5, 0.8, ADMIT), (2.3, 1.2, DECODE),      # e1
           (3.5, 1.0, ADMIT), (4.5, 1.5, DECODE),      # e2
           (6.0, 0.5, ADMIT), (6.5, 1.5, DECODE)]
    spans = [
        ("engine.plan", 0.80, 0.05, {}), ("engine.dispatch", 0.86, 0.02, {"seq": 11, **SEQ_11}),
        ("engine.resolve", 0.90, 0.62, {"seq": 10, **OTHER}),
        ("engine.plan", 1.55, 0.05, {}), ("engine.dispatch", 1.61, 0.02, {"seq": 12, **SEQ_12}),
        ("engine.resolve", 1.65, 1.87, {"seq": 11, **SEQ_11}),
        # a dispatch that admits nobody: its lead is nobody's
        ("engine.plan", 3.55, 0.04, {}), ("engine.dispatch", 3.60, 0.02, {"seq": 13, **OTHER}),
        ("engine.resolve", 3.65, 2.37, {"seq": 12, **SEQ_12}),
        ("engine.resolve", 6.05, 1.97, {"seq": 13, **OTHER}),
    ]
    return {"devices": 1, "window": (1.0, 9.0), "spans": spans, "kernels": {},
            "modules": [("jit_macro_step_slots_paged", s, d) for s, d in execs],
            "busy": [(s, s + d) for s, d, _ in ops], "ops": ops}


def _ctx():
    records = [  # client latencies 3.0 and 4.5 s; answers of 11 and 31 tokens
        {"rid": "r1", "ok": True, "t_due": 100.0, "t_done": 103.0, "tokens": [1] * 11},
        {"rid": "r2", "ok": True, "t_due": 101.0, "t_done": 105.5, "tokens": [1] * 31},
        {"rid": "r3", "ok": True, "t_due": 102.0, "t_done": 109.0, "tokens": [1] * 5},   # aged out
        {"rid": "r4", "ok": False, "t_due": 103.0, "t_done": 104.0, "tokens": []}]
    timelines = {"r1": [{"kind": "submit", "t": 1000.0}, {"kind": "finish", "t": 1002.99}],
                 "r2": [{"kind": "submit", "t": 1001.0}, {"kind": "finish", "t": 1005.48}],
                 "r3": []}
    return {"facts": {"reduced": {"busy_s": 7.5, "window_s": 8.0}, "records": records,
                      "timelines": timelines, "lanes": 4,
                      "engine": {"slot_steps": 400, "useful_slot_steps": 300}},
            "config": {}, "e2e": {}, "peaks": {}}


def _read(name, ctx):
    return common.load_module("layer_metrics", name).read(ctx)


def test_stretch_counts_the_whole_executions_by_their_resolve(monkeypatch):
    lead = common.load_module("layer_metrics", "engine.dispatch_lead_ms")
    acc = lead.stretch(_recorded())
    assert acc["executions"] == 2 and acc["macro_s"] == pytest.approx(4.5)
    assert acc["admit_s"] == pytest.approx(1.8) and acc["decode_s"] == pytest.approx(2.7)
    assert acc["sums"] == {k: SEQ_11[k] + SEQ_12[k] for k in SEQ_11}
    # plan(11) at 0.80 to e1 at 1.5, plan(12) at 1.55 to e2 at 3.5; seq 13 admits nobody
    assert acc["host_lead_s"] == [pytest.approx(0.70), pytest.approx(1.95)]
    st = lead.stations(acc)
    assert st["decode_step_ms"] == pytest.approx(30.0) and st["admit_phase_ms"] == pytest.approx(600.0)
    assert lead.stretch({**_recorded(), "window": None}) is None
    assert lead.stretch({**_recorded(), "window": (1.6, 5.9)}) is None  # no execution whole in it


def test_account_readers_on_the_hand_made_trace(monkeypatch):
    monkeypatch.setattr(ps, "run_trace", lambda facts: _recorded())
    ctx = _ctx()
    plan = _read("engine.plan_wait_ms", ctx)
    assert plan["value"] == pytest.approx(100.0)            # 500,000 us over 5 admissions
    assert (plan["plan_wait_us"], plan["admissions"], plan["executions"]) == (500_000, 5, 2)
    lane = _read("engine.lane_wait_ms", ctx)
    assert lane["value"] == pytest.approx(120.0) and lane["admitted_first_plan"] == 3
    assert lane["admitted_first_plan_pct"] == pytest.approx(60.0)
    stall = _read("engine.admit_stall_ms", ctx)
    assert stall["value"] == pytest.approx(8 * 600.0 / 5)   # 8 lane-phases of 600 ms, 5 finishing
    assert stall["admit_phase_ms"] == pytest.approx(600.0) and stall["stalls_a_finishing"] == 1.6
    vacant, blocked = _read("engine.vacant_lane_pct", ctx), _read("engine.blocked_lane_pct", ctx)
    assert vacant["value"] == pytest.approx(100.0 * 50 / 360)
    assert blocked["value"] == pytest.approx(100.0 * 12 / 360)
    for got in (vacant, blocked):
        assert got["occupancy_pct"] == pytest.approx(100.0 * 290 / 360)
        assert got["spent_pct"] == pytest.approx(100.0 * 8 / 360)
        assert (got["occupancy_pct"] + got["vacant_pct"] + got["blocked_pct"] + got["spent_pct"]
                == pytest.approx(100.0))
        assert got["check_all_lane_steps_accounted"] is True      # 360 = 4 lanes x 90 steps
        assert got["window_lane_occupancy_pct"] == pytest.approx(75.0)
    real = _read("engine.admit_real_pct", ctx)
    assert real["value"] == pytest.approx(100.0 * 1000 / 2048) and real["admit_rows"] == 2048

    lead = _read("engine.dispatch_lead_ms", ctx)
    assert lead["host_ms"] == pytest.approx(1325.0)          # the median of 700 and 1,950
    assert lead["device_ms"] == pytest.approx((40 * 30.0 + 2 * 600.0) / 5)
    assert lead["value"] == pytest.approx(1325.0 + 480.0)
    assert lead["paired_admitting_dispatches"] == 2
    assert lead["stretch_lane_steps_a_finishing"] == pytest.approx(58.0)
    # the whole account over r1 and r2: r3's lifeline aged out, r4 failed
    assert lead["account_requests"] == 2 and lead["answer_decode_steps"] == 20
    assert lead["mean_client_latency_ms"] == pytest.approx(3750.0)
    assert lead["account"] == {
        "plan_wait_ms": pytest.approx(100.0), "lane_wait_ms": pytest.approx(120.0),
        "dispatch_lead_ms": pytest.approx(1805.0), "own_admit_phase_ms": pytest.approx(600.0),
        "decode_ms": pytest.approx(20 * 30.0), "admit_stall_ms": pytest.approx(960.0),
        "finish_wait_ms": pytest.approx(25 * 30.0 / 5),
        # resolve(11) and resolve(12) end 20 ms after their executions
        "deliver_lag_ms": pytest.approx(20.0),
        # 3.0 - 2.99 and 4.5 - 4.48 s
        "serve_plane_overhead_ms": pytest.approx(15.0)}
    assert lead["account_sum_ms"] == pytest.approx(4370.0)
    assert lead["account_residual_pct"] == pytest.approx(100.0 * (3750 - 4370) / 3750)
    assert lead["stations_without_a_reading"] == []
    # an untraced run's lifelines are not fetched: the value stands without the account
    bare = _read("engine.dispatch_lead_ms", {**ctx, "facts": {**ctx["facts"], "timelines": {}}})
    assert bare["value"] == pytest.approx(1805.0) and "account" not in bare


@pytest.mark.parametrize("name", READERS)
def test_an_account_reader_with_nothing_to_read_returns_none(name, monkeypatch):
    ctx = _ctx()
    monkeypatch.setattr(ps, "run_trace", lambda facts: None)       # untraced, or no trace file
    assert _read(name, ctx) is None
    # the parent of PR 41: the spans are there, with the counts they had
    old = _recorded()
    old["spans"] = [(n, s, d, {k: v for k, v in st.items() if k not in NEW_STATS})
                    for n, s, d, st in old["spans"]]
    monkeypatch.setattr(ps, "run_trace", lambda facts: old)
    got = _read(name, ctx)
    if name == "engine.admit_real_pct":   # existing stats, a new reader
        assert got["value"] == pytest.approx(100.0 * 1000 / 2048)
    else:
        assert got is None
    # the parent of PR 27: no span at all
    monkeypatch.setattr(ps, "run_trace", lambda facts: {**_recorded(), "spans": []})
    assert _read(name, ctx) is None


def test_the_seven_entries_are_the_issues():
    """Names, units, sources, `moves` and cells as ISSUE 41's table has them:
    the four stations where a latency is reported, the three shares in all six
    serve cells. (`test_sarvam_mla.py`'s exact set of `longdoc-qa`'s metrics
    predates the three shares and is a `benchmark` PR's to widen: this PR may
    edit no file the benchmark already has.)"""
    four = ["chat-steady", "docqa-saturate", "batch-generate-wide", "mixed-context-generate"]
    six = ["chat-steady", "docqa-saturate", "batch-generate-wide", "chat-burst",
           "mixed-context-generate", "longdoc-qa"]
    want = {"engine.plan_wait_ms": ("ms", "lower", "program_span", "latency_p50_ms", four),
            "engine.lane_wait_ms": ("ms", "lower", "program_span", "latency_p90_ms", four),
            "engine.dispatch_lead_ms": ("ms", "lower", "program_span", "latency_p50_ms", four),
            "engine.admit_stall_ms": ("ms", "lower", "program_span", "latency_p50_ms", four),
            "engine.vacant_lane_pct": ("%", "lower", "program_counter", "tok_s", six),
            "engine.blocked_lane_pct": ("%", "lower", "program_counter", "tok_s", six),
            "engine.admit_real_pct": ("%", "higher", "program_counter", "tok_s", six)}
    entries = common.load_benchmark()["per_layer"]
    assert [m["name"] for m in entries[-7:]] == list(want) == list(READERS)
    for m in entries[-7:]:
        assert (m["unit"], m["better"], m["source"], m["moves"], m["workloads"]) == want[m["name"]]
        assert m["layer"] == "engine"
    for cell in six:
        listed = {m["name"] for m in common.load_cell(cell)["per_layer"]}
        assert listed >= set(READERS[4:]) and (listed >= set(READERS[:4])) == (cell in four)
