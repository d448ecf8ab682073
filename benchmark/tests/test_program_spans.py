"""`program_spans` gives known figures: the read on a small hand-written
XSpace (event names, metadata stats and span stats where a chip trace has
them), the arithmetic on a recorded list of tuples whose answers are worked
out here, and each of the eight readers on hand-made `facts`."""
import os

import pytest

from benchmark import common, program_spans as ps

ADMIT, DECODE = ps.ADMIT, ps.DECODE


def test_span_names_are_the_programs():
    from ray_tpu.observability import ENGINE_SPANS

    assert ps.ENGINE_SPANS == ENGINE_SPANS
    assert ps.TOP_SPANS == tuple(s for s in ENGINE_SPANS if s != "engine.fetch")


def test_scopes_and_kernel_names_are_the_programs():
    from ray_tpu.models import llama_decode

    assert (ps.ADMIT, ps.DECODE) == (llama_decode.ADMIT_SCOPE, llama_decode.DECODE_SCOPE)
    assert ps.MACRO_STEP.match("jit_" + llama_decode.macro_step_slots_paged.__name__)
    with open(os.path.join(common.REPO, "ray_tpu", "ops", "flash_attention.py")) as f:
        source = f.read()
    assert all(f'name="{k}"' in source for k in ps.KERNELS)


# ------------------------------------------------------------------ the read
XSPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000000000 }
    events { metadata_id: 2 offset_ps: 3500000000000 duration_ps: 100000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000000000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 3000000000000 }
    events { metadata_id: 11 offset_ps: 0 duration_ps: 1000000000000 }
    events { metadata_id: 12 offset_ps: 1000000000000 duration_ps: 1500000000000 }
    events { metadata_id: 13 offset_ps: 2500000000000 duration_ps: 400000000000 }
    events { metadata_id: 14 offset_ps: 3500000000000 duration_ps: 100000000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_macro_step_slots_paged(123)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_gather_kv_blocks(9)" } }
  event_metadata { key: 10 value { id: 10 name: "%while.1 = (s32[]) while(%t), body=%b" } }
  event_metadata { key: 11 value { id: 11 name: "%fusion.1 = bf16[8] fusion(%p), kind=kLoop"
      stats { metadata_id: 1 str_value: "convolution fusion" }
      stats { metadata_id: 2 str_value: "jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/admit_prefill/dot_general:" } } }
  event_metadata { key: 12 value { id: 12 name: "%fusion.2 = bf16[8] fusion(%p), kind=kLoop"
      stats { metadata_id: 2 ref_value: 4 } stats { metadata_id: 5 double_value: 1.5 } } }
  event_metadata { key: 13 value { id: 13 name: "%copy.3 = bf16[8] copy(%p)"
      stats { metadata_id: 1 str_value: "data formatting" } } }
  event_metadata { key: 14 value { id: 14
      name: "%flash_bwd_dq.12 = bf16[8] custom-call(%p), custom_call_target=\\"tpu_custom_call\\""
      stats { metadata_id: 2 str_value: "jit(step_fn)/transpose(jvp(flash_bwd_dq))/pallas_call:" } } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 4 value { id: 4 name: "jit(f)/while/body/while/body/cond/branch_1_fun/decode_chunk/add:" } }
  stat_metadata { key: 5 value { id: 5 name: "flops" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 100000000000 duration_ps: 3000000000000 }
    events { metadata_id: 2 offset_ps: 200000000000 duration_ps: 100000000000
      stats { metadata_id: 1 int64_value: 31 } stats { metadata_id: 2 int64_value: 17 } }
    events { metadata_id: 3 offset_ps: 400000000000 duration_ps: 50000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "$llm_engine.py:1 _plan" } }
  stat_metadata { key: 1 value { id: 1 name: "seq" } }
  stat_metadata { key: 2 value { id: 2 name: "steps" } } }
'''


def test_load_reads_names_scopes_kernels_and_span_stats(tmp_path):
    from jax.profiler import ProfileData

    where = tmp_path / "plugins" / "profile" / "now"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    t = ps.load(str(tmp_path))
    assert t["devices"] == 1 and t["window"] == pytest.approx((1.1, 4.1))
    assert t["spans"] == [("engine.dispatch", pytest.approx(1.2), pytest.approx(0.1),
                           {"seq": 31, "steps": 17})]
    assert [(m[0], m[2]) for m in t["modules"]] == [
        ("jit_macro_step_slots_paged", pytest.approx(3.0)), ("jit_gather_kv_blocks", pytest.approx(0.1))]
    # the while holds the others: busy time, but no operation of its own
    assert len(t["busy"]) == 5
    assert [(round(d, 3), scope) for _, d, scope in t["ops"]] == [
        (1.0, ADMIT), (1.5, DECODE), (0.4, ""), (0.1, "")]
    assert t["kernels"] == {"flash_bwd_dq": [pytest.approx(0.1)]}
    # the same bytes through the wire reader alone
    stacks = ps.name_stacks((where / "host.xplane.pb").read_bytes())
    assert list(stacks) == ["/device:TPU:0"] and len(stacks["/device:TPU:0"]) == 3


def test_a_trace_without_the_programs_marks_reads_empty(tmp_path):
    """The parent of PR 27: an unnamed module, no span, no scope."""
    from jax.profiler import ProfileData

    where = tmp_path / "plugins" / "profile" / "now"
    where.mkdir(parents=True)
    bare = XSPACE.replace("jit_macro_step_slots_paged", "jit__unknown").replace(
        "engine.dispatch", "$llm_engine.py:9 _dispatch_macro").replace(
        "admit_prefill/", "").replace("decode_chunk/", "").replace("%flash_bwd_dq.12", "%closed_call.9")
    (where / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(bare))
    t = ps.load(str(tmp_path))
    assert t["spans"] == [] and t["kernels"] == {} and {s for _, _, s in t["ops"]} == {""}
    assert ps.serve_view(t) is None and ps.kernel_calls(t) == {}


# ------------------------------------------------------- the recorded list
def _recorded():
    """Window 1.0-9.0. e0 runs when the trace starts (its dispatch is not in
    it); three dispatches follow, each resolved one behind; between e2 and e3
    the device idles 1.6 s, most of it with the loop in `engine.idle`."""
    execs = [(0.6, 1.2), (1.8, 2.0), (3.8, 1.0), (6.4, 3.0)]
    ops = [
        (0.6, 1.2, DECODE),                                         # e0
        (1.8, 1.2, ADMIT), (3.0, 0.7, DECODE), (3.7, 0.1, ""),      # e1: one op under neither
        (3.8, 1.0, DECODE),                                         # e2
        (6.4, 1.5, ADMIT), (7.9, 1.5, DECODE),                      # e3
    ]
    d = lambda seq, **kw: {"seq": seq, "phases": 1, "admissions": 1, "A": 1, "P": 16,  # noqa: E731
                           "lane_steps": 0, **kw}
    spans = [
        ("engine.dispatch", 1.0, 0.1, d(5, steps=10, prompt_tokens=100, finishing=1, finish_wait_steps=6)),
        ("engine.resolve", 1.1, 0.75, {"seq": 4}), ("engine.fetch", 1.1, 0.72, {}),
        ("engine.intake", 1.85, 0.01, {}), ("engine.plan", 1.86, 0.04, {}),
        ("engine.dispatch", 1.9, 0.1, d(6, steps=20, prompt_tokens=0, finishing=2, finish_wait_steps=8)),
        ("engine.resolve", 2.0, 1.83, {"seq": 5}), ("engine.fetch", 2.0, 1.81, {}),
        ("engine.intake", 3.83, 0.01, {}),
        ("engine.resolve", 3.84, 0.98, {"seq": 6}), ("engine.fetch", 3.84, 0.97, {}),
        ("engine.idle", 4.83, 1.17, {}),
        ("engine.intake", 6.0, 0.01, {}), ("engine.plan", 6.01, 0.29, {}),
        ("engine.dispatch", 6.3, 0.1, d(7, steps=5, prompt_tokens=300, finishing=0, finish_wait_steps=0)),
    ]
    return {"devices": 1, "window": (1.0, 9.0), "spans": spans,
            "modules": [("jit_macro_step_slots_paged", s, d_) for s, d_ in execs]
            + [("jit_gather_kv_blocks", 5.0, 0.001)],
            "busy": [(s, s + d_) for s, d_, _ in ops], "ops": ops, "kernels": {}}


def test_pairing_with_an_execution_in_flight_at_the_traces_start():
    t = _recorded()
    dispatches = [s for s in t["spans"] if s[0] == "engine.dispatch"]
    execs = sorted((s, d) for n, s, d in t["modules"] if ps.MACRO_STEP.match(n))
    pairs, lone_exec, lone_dispatch = ps.pair_dispatches(dispatches, execs)
    assert lone_exec == [(0.6, 1.2)] and lone_dispatch == []
    assert [(d[3]["seq"], e) for d, e in pairs] == [(5, (1.8, 2.0)), (6, (3.8, 1.0)), (7, (6.4, 3.0))]
    # a dispatch whose execution the trace no longer holds stays unpaired
    pairs, lone_exec, lone_dispatch = ps.pair_dispatches(dispatches, execs[:3])
    assert len(pairs) == 2 and [d[3]["seq"] for d in lone_dispatch] == [7]


def test_idle_is_split_by_the_span_that_covers_it():
    t = _recorded()
    idle = ps.idle_by_span(t["busy"], t["spans"], t["window"])
    assert idle["window_s"] == 8.0 and idle["idle_s"] == pytest.approx(1.6)  # 4.8 to 6.4
    assert idle["by_span"] == {
        "engine.idle": pytest.approx(1.17),      # no request to serve
        "engine.intake": pytest.approx(0.01), "engine.plan": pytest.approx(0.29),
        "engine.dispatch": pytest.approx(0.1), "engine.resolve": pytest.approx(0.02)}
    assert idle["uncovered_s"] == pytest.approx(0.01)      # 4.82 to 4.83, between two spans
    assert idle["starved_s"] == pytest.approx(0.43)        # all of it but engine.idle's
    assert idle["edges_s"] == pytest.approx(0.0, abs=1e-9)  # the device is busy after the last span
    # a span open when the session stops is never written: idle after the last
    # recorded span is of unknown cause, and counts as neither starved nor uncovered
    cut = ps.idle_by_span(t["busy"], [s for s in t["spans"] if s[1] < 4.9], t["window"])
    assert cut["idle_s"] == pytest.approx(1.6) and cut["edges_s"] == pytest.approx(0.4)  # 6.0 to 6.4
    assert cut["by_span"]["engine.idle"] == pytest.approx(1.17)
    assert cut["starved_s"] == pytest.approx(0.03) and cut["uncovered_s"] == pytest.approx(0.01)


def test_serve_view_on_the_recorded_list():
    v = ps.serve_view(_recorded())
    # e3 is the trace's last execution: paired with its dispatch, but not among `paired`
    assert (v["executions"], v["paired"], v["unpaired_executions"], v["unpaired_dispatches"]) == (4, 2, 1, 0)
    assert v["macro_step_s"] == pytest.approx(7.2)
    assert v["admit_s"] == pytest.approx(2.7) and v["decode_s"] == pytest.approx(4.4)
    assert v["unscoped_ops_s"] == pytest.approx(0.1) and v["neither_s"] == pytest.approx(0.1)
    # decode time and steps over the PAIRED executions only: e0 has no dispatch to count steps by
    assert v["paired_decode_s"] == pytest.approx(1.7) and v["paired_steps"] == 30
    assert ps.decode_step_ms(v) == pytest.approx(1700 / 30)
    assert v["paired_prompt_tokens"] == 100
    assert v["deliver_lag_s"] == [pytest.approx(0.03), pytest.approx(0.02)]   # seq 7 never resolved
    assert v["fetch_lag_s"] == [pytest.approx(0.01), pytest.approx(0.01)]
    assert (v["dispatches"], v["finishing"], v["finish_wait_steps"]) == (3, 3, 14)


def test_the_traces_last_execution_is_clipped_and_counts_no_steps():
    """B2. The profiler stops in the middle of the last macro-step: its
    dispatch span says all 64 planned steps, the trace holds the operations
    of 20 of them. Counted among `paired` it makes a 20 ms step read 15.3."""
    step = 0.020
    spans = [("engine.dispatch", 0.9 + 1.3 * k, 0.01, {"seq": k, "steps": 64}) for k in range(3)]
    execs = [(1.0, 1.28), (2.3, 1.28), (3.6, 0.4)]  # the third is cut at 4.0, where the trace ends
    ops = [(s + step * i, step, DECODE) for s, d in execs for i in range(round(d / step))]
    trace = {"devices": 1, "window": (0.95, 4.0), "spans": spans, "kernels": {},
             "modules": [("jit_macro_step_slots_paged", s, d) for s, d in execs],
             "busy": [(s, s + d) for s, d, _ in ops], "ops": ops}
    v = ps.serve_view(trace)
    assert (v["executions"], v["paired"], v["paired_steps"]) == (3, 2, 128)
    assert ps.decode_step_ms(v) == pytest.approx(20.0)
    assert v["decode_s"] == pytest.approx(2.96)  # the window's device time still holds the clipped one
    assert 1e3 * v["decode_s"] / (3 * 64) == pytest.approx(15.4, abs=0.05)  # what it read before
    pairs, _, _ = ps.pair_dispatches(spans, execs)
    assert [ex for _, ex in ps.whole_in_window(pairs, execs, (0.95, 4.0))] == execs[:2]
    assert [ex for _, ex in ps.whole_in_window(pairs, execs, (2.0, 4.0))] == execs[1:2]


def test_a_kernel_the_compiler_named_takes_the_half_of_the_operation_before_it(tmp_path):
    """PERF.md section 7 (e), PR 33: a ragged product reaches the trace as a
    kernel named `ragged-dot-none` with no name stack of the program's. It
    lies between two scoped operations and counts under the first one's half;
    the compiler's own copy after it stays under neither."""
    base = "jit(macro_step_slots_paged)/while/body/"
    named = [(0.10, 0.01, "%fusion.1 = ...", base + "admit_prefill/while/body/moe_experts/sort"),
             (0.12, 0.01, "%ragged-dot-metadata.2 = ... custom-call", "ragged-dot-metadata"),
             (0.13, 0.05, "%ragged-dot-none.7 = bf16[32768,1024] custom-call", "ragged-dot-none"),
             (0.20, 0.01, "%copy-done.3 = ...", ""),
             (0.30, 0.01, "%fusion.10 = ...", base + "decode_chunk/while/body/moe_experts/gather"),
             (0.32, 0.02, "%ragged-dot-none.1 = bf16[64,1024] custom-call", "ragged-dot-none"),
             (0.40, 0.01, "%fusion.11 = ...", base + "decode_chunk/dot_general")]
    assert ps.halves(named) == [ADMIT, ADMIT, ADMIT, "", DECODE, DECODE, DECODE]
    assert ps.halves(named[1:3]) == ["", ""]  # nothing before it had a half: none to take

    # and through `load`: fusion.2 (decode_chunk), then a compiler-named kernel in place of the copy
    from jax.profiler import ProfileData

    where = tmp_path / "plugins" / "profile" / "now"
    where.mkdir(parents=True)
    ragged = XSPACE.replace('"%copy.3 = bf16[8] copy(%p)"',
                            '"%ragged-dot-none.3 = bf16[8] custom-call(%p)"')
    (where / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(ragged))
    t = ps.load(str(tmp_path))
    assert [(round(d, 3), scope) for _, d, scope in t["ops"]] == [
        (1.0, ADMIT), (1.5, DECODE), (0.4, DECODE), (0.1, "")]
    assert [(name.split(" ")[0], text.rsplit("/", 1)[-1]) for _, _, name, text in t["named_ops"]] == [
        ("%fusion.1", "dot_general:"), ("%fusion.2", "add:"), ("%ragged-dot-none.3", ""),
        ("%flash_bwd_dq.12", "pallas_call:")]


def test_interval_helpers():
    assert ps.complement([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert ps.complement([], 0, 5) == [(0, 5)] and ps.complement([(0, 5)], 0, 5) == []
    assert ps.clip([(0, 2), (3, 9), (10, 11)], 1, 8) == [(1, 2), (3, 8)]
    assert ps.overlap([(0, 2), (4, 6)], [(1, 5)]) == 2


# -------------------------------------------------------------- the readers
SERVE_READERS = ("engine.starved_idle_pct", "engine.deliver_lag_ms", "engine.finish_wait_steps",
                 "programs.prefill_share_pct", "programs.decode_step_ms")
KERNEL_READERS = ("kernels.flash_fwd_ms", "kernels.flash_dq_ms", "kernels.flash_dkdv_ms")


def _read(name, ctx):
    return common.load_module("layer_metrics", name).read(ctx)


def test_serve_readers_on_hand_made_facts(monkeypatch):
    monkeypatch.setattr(ps, "run_trace", lambda facts: _recorded())
    ctx = {"facts": {"reduced": {"busy_s": 6.4, "window_s": 8.0}}, "config": {}, "e2e": {}, "peaks": {}}
    starved = _read("engine.starved_idle_pct", ctx)
    assert starved["value"] == pytest.approx(100 * 0.43 / 8.0)
    assert starved["device_idle_pct"] == pytest.approx(20.0)
    assert starved["idle_for_want_of_traffic_pct"] == pytest.approx(100 * 1.17 / 8.0)
    assert starved["rest_of_device_idle_pct"] == pytest.approx(20.0 - 100 * 0.43 / 8.0)
    assert starved["idle_s_at_trace_edges"] == pytest.approx(0.0, abs=1e-9)
    assert starved["check_starved_at_most_device_idle"] and starved["check_spans_cover_idle_within_2pct"]
    lag = _read("engine.deliver_lag_ms", ctx)
    assert lag["value"] == pytest.approx(25.0) and lag["samples"] == 2
    assert lag["until_fetch_returned_ms"] == pytest.approx(10.0)
    assert lag["max_ms"] == pytest.approx(30.0) and lag["until_fetch_returned_max_ms"] == pytest.approx(10.0)
    assert lag["check_at_most_one_unpaired_at_each_end"]
    wait = _read("engine.finish_wait_steps", ctx)
    assert wait["value"] == pytest.approx(14 / 3)
    assert wait["wait_ms_at_decode_step_ms"] == pytest.approx(14 / 3 * 1700 / 30)
    share = _read("programs.prefill_share_pct", ctx)
    assert share["value"] == pytest.approx(37.5) and share["prompt_tokens"] == 100
    assert share["neither_s"] == pytest.approx(0.1) and share["check_neither_under_5pct"]
    assert _read("programs.decode_step_ms", ctx)["value"] == pytest.approx(1700 / 30)


@pytest.mark.parametrize("name", SERVE_READERS + KERNEL_READERS)
def test_a_reader_with_nothing_to_read_returns_none(name, monkeypatch):
    ctx = {"facts": {"reduced": None}, "config": {}, "e2e": {}, "peaks": {}}
    assert _read(name, ctx) is None                       # an untraced run
    bare = {**_recorded(), "spans": [], "kernels": {},
            "modules": [("jit__unknown", 1.8, 2.0)]}
    monkeypatch.setattr(ps, "run_trace", lambda facts: bare)    # the parent's trace
    ctx["facts"]["reduced"] = {"busy_s": 6.4, "window_s": 8.0, "ops": {}, "devices": 1}
    ctx["facts"]["traced_steps"] = 4
    assert _read(name, ctx) is None
    monkeypatch.setattr(ps, "run_trace", lambda facts: None)    # no trace file at all
    assert _read(name, ctx) is None


def test_kernel_readers_on_hand_made_facts(monkeypatch):
    cell = common.load_cell("pretrain-4k")
    layers, steps = cell["config_file"]["num_hidden_layers"], 4
    calls = layers * steps
    kernels = {"flash_fwd": [0.0030] * (2 * calls - 1) + [0.0040],   # one slow call moves no median
               "flash_bwd_dq": [0.0035] * calls, "flash_bwd_dkdv": [0.0044] * calls}
    monkeypatch.setattr(ps, "run_trace", lambda facts: {**_recorded(), "kernels": kernels})
    kernel_s = sum(sum(v) for v in kernels.values())
    ctx = {"cell": cell, "config": cell["config_file"], "e2e": {},
           "peaks": common.peaks_for("TPU v5 lite"),
           "facts": {"traced_steps": steps, "job": {"batch": 2, "seq_len": 4096},
                     "reduced": {"devices": 1, "busy_s": 2.3, "window_s": 2.33, "ops": {
                         "flash_fwd.18 tpu_custom_call": {"count": 2 * calls, "total_s": sum(kernels["flash_fwd"])},
                         "flash_bwd_dq.12 tpu_custom_call": {"count": calls, "total_s": 0.0035 * calls},
                         "flash_bwd_dkdv.12 tpu_custom_call": {"count": calls, "total_s": 0.0044 * calls},
                         "fusion.1 bf16[8] kLoop": {"count": 1, "total_s": 1.0}}}}}
    fwd = _read("kernels.flash_fwd_ms", ctx)
    assert fwd["value"] == pytest.approx(3.0) and fwd["calls"] == 2 * calls
    assert fwd["roofline_kernel_s"] == pytest.approx(kernel_s)
    assert fwd["from_medians_s"] == pytest.approx((2 * 0.003 + 0.0035 + 0.0044) * calls)
    assert fwd["check_within_5pct_of_roofline_kernel_s"]
    assert _read("kernels.flash_dq_ms", ctx)["value"] == pytest.approx(3.5)
    dkdv = _read("kernels.flash_dkdv_ms", ctx)
    assert dkdv["value"] == pytest.approx(4.4) and dkdv["calls"] == calls
