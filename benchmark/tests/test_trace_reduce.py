"""The trace reducer gives known busy, idle, per-module and gap figures: on a
hand-made trace whose answers are worked out here, and on a small trace
recorded on the chip (PR 26) whose reduction is pinned."""
import json
import os

import pytest

from benchmark import trace_reduce as tr

DEV, HOST = "/device:TPU:0", tr.HOST_PLANE


def test_hand_made_trace():
    ev = [
        (DEV, tr.MODULE_LINE, "jit_step(123)", 0.0, 1.0),
        (DEV, tr.MODULE_LINE, "jit_step(123)", 2.0, 1.0),
        (DEV, tr.OP_LINE, "fusion.1 bf16[8] kLoop", 0.0, 0.4),
        (DEV, tr.OP_LINE, "fusion.2 bf16[8] kLoop", 0.3, 0.7),     # overlaps fusion.1
        (DEV, tr.OP_LINE, "[container] while.3", 2.0, 1.0),        # holds the next two
        (DEV, tr.OP_LINE, "fusion.1 bf16[8] kLoop", 2.0, 0.5),
        (DEV, tr.OP_LINE, "k.9 tpu_custom_call", 2.5, 0.5),
        (HOST, "python", "$engine.py:1 _plan", 1.1, 0.8),
        (HOST, "python", "$threading.py:1 wait", 1.0, 1.0),        # a waiting thread
        (HOST, "python", "$main.py:1 run", 0.0, 100.0),            # outermost frame
    ]
    r = tr.reduce_events(ev)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(2.0) and r["window_s"] == pytest.approx(3.0)
    assert r["modules"]["jit_step"] == {"count": 2, "total_s": 2.0, "median_s": 1.0}
    assert r["ops"]["fusion.1 bf16[8] kLoop"] == {"count": 2, "total_s": pytest.approx(0.9)}
    assert "[container] while.3" not in r["ops"]
    assert r["idle_gap_count"] == 1
    assert r["idle_gaps"] == [["$engine.py:1 _plan", pytest.approx(1.0)]]
    assert r["device_ops"][0][0] == "fusion.1 bf16[8] kLoop"
    # with a window given from outside, idle time before and after counts too
    assert tr.reduce_events(ev, window_s=4.0)["window_s"] == 4.0


def test_window_marker_cuts_what_the_profiler_recorded_outside_it():
    """The profiler records before and after the window: with the mark in the
    trace, busy time cannot pass the window (PR 26's first check was refused
    over a saturated cell whose busy_s passed a window timed beside it)."""
    ev = [
        (HOST, "bench-trace", tr.WINDOW_MARKER, 1.0, 2.0),          # the window: 1.0 to 3.0
        (DEV, tr.MODULE_LINE, "jit_m(1)", 0.0, 1.4),                # middle 0.7: outside
        (DEV, tr.MODULE_LINE, "jit_m(1)", 1.5, 1.0),                # inside
        (DEV, tr.MODULE_LINE, "jit_m(1)", 2.6, 1.2),                # middle 3.2: outside
        (DEV, tr.OP_LINE, "a", 0.0, 1.4),                           # 0.4 s of it inside
        (DEV, tr.OP_LINE, "b", 1.5, 1.0),
        (DEV, tr.OP_LINE, "a", 2.6, 1.2),                           # 0.4 s of it inside
        (HOST, "python", "$x.py:1 plan", 1.4, 0.1),
    ]
    r = tr.reduce_events(ev, window_s=1.9)                          # the host's clock is not asked
    assert r["window_marked"] and r["window_s"] == pytest.approx(2.0)
    assert r["busy_s"] == pytest.approx(1.8) and r["busy_s"] <= r["window_s"]
    assert r["modules"]["jit_m"]["count"] == 1
    assert r["ops"] == {"b": {"count": 1, "total_s": pytest.approx(1.0)}}
    assert r["idle_gap_count"] == 2            # 1.4 to 1.5 and 2.5 to 2.6
    assert dict(map(tuple, r["idle_gaps"])) == {"$x.py:1 plan": pytest.approx(0.1),
                                                "no host event recorded": pytest.approx(0.1)}
    # a device that never idles, traced for longer than the window
    full = [(HOST, "t", tr.WINDOW_MARKER, 1.0, 2.0), (DEV, tr.OP_LINE, "a", 0.0, 4.0)]
    r = tr.reduce_events(full)
    assert r["busy_s"] == pytest.approx(2.0) and r["window_s"] == pytest.approx(2.0)
    # idle at both ends counts: the mark, not the first and last operation, is the window
    ends = [(HOST, "t", tr.WINDOW_MARKER, 0.0, 4.0), (DEV, tr.OP_LINE, "a", 1.0, 1.0)]
    r = tr.reduce_events(ends)
    assert r["busy_s"] == pytest.approx(1.0) and r["window_s"] == pytest.approx(4.0)
    assert r["idle_gap_count"] == 2
    # without a mark, a window given shorter than the trace's own span gives way to the span
    bare = [(DEV, tr.OP_LINE, "a", 0.0, 3.0)]
    r = tr.reduce_events(bare, window_s=2.0)
    assert not r["window_marked"] and r["busy_s"] <= r["window_s"] == pytest.approx(3.0)


def test_traced_window_leaves_its_mark(tmp_path):
    """`common.traced_window` on the CPU: the mark is in the trace the reducer
    reads, as long as the body and shorter than what the profiler recorded."""
    import time

    from benchmark import common

    with common.traced_window(str(tmp_path)):
        time.sleep(0.3)
    events = tr.read_events(tr.find_xplane(str(tmp_path)))
    lo, hi = tr.find_window(events)
    assert 0.3 <= hi - lo < 0.5
    r = tr.reduce_events(events)
    assert r["window_marked"] and r["window_s"] == pytest.approx(hi - lo) and r["busy_s"] == 0.0


def test_two_devices_are_averaged():
    ev = [("/device:TPU:0", tr.OP_LINE, "a", 0.0, 1.0), ("/device:TPU:1", tr.OP_LINE, "a", 0.0, 3.0)]
    r = tr.reduce_events(ev)
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(2.0)
    assert r["device_ops"] == [["a", pytest.approx(2.0)]]


def test_no_device_event_reads_zero_busy():
    assert tr.reduce_events([(HOST, "python", "x", 0.0, 1.0)])["busy_s"] == 0.0


def test_op_labels():
    text = ('%fusion.393 = bf16[2,4096,14336]{2,1,0:T(8,128)(2,1)} fusion(bf16[2,4096,4096]{1,2,0} '
            '%copy-done.7), kind=kOutput, calls=%fused_computation.125')
    assert tr.op_label(text) == "fusion.393 bf16[2,4096,14336] kOutput"
    call = ('%closed_call.9 = (bf16[64,4096,128]{2,1,0}, f32[64,4,1,1024]{3,2,1,0}) custom-call('
            'bf16[64,4096,128]{2,1,0} %bitcast.453), custom_call_target="tpu_custom_call"')
    assert tr.op_label(call) == "closed_call.9 tpu_custom_call"
    loop = "%while.11 = (s32[]{:T(128)}, bf16[2,4096]{1,0:T(8,128)(2,1)S(1)}) while(%tuple.1), body=%b"
    assert tr.is_container(loop) and not tr.is_container(text) and not tr.is_container(call)
    assert tr.module_name("jit_step_fn(4667250220568261245)") == "jit_step_fn"


def test_recorded_trace():
    path = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")
    assert os.path.getsize(path) < 1_000_000
    with open(path) as f:
        events = [tuple(e) for e in json.load(f)["events"]]
    r = tr.reduce_events(events)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(1.161614029, rel=1e-6)
    assert r["window_s"] == pytest.approx(1.164695641, rel=1e-6)
    assert r["modules"]["jit_step_fn"]["count"] == 2
    assert r["modules"]["jit_step_fn"]["median_s"] == pytest.approx(0.580827737, rel=1e-6)
    assert r["idle_gaps"] == [["ReadSyncFlag", pytest.approx(0.003054311, rel=1e-4)]]
    kernels = {k: v for k, v in r["ops"].items() if k.endswith("tpu_custom_call")}
    assert sorted(v["count"] for v in kernels.values()) == [10, 10, 10, 10]  # 5 layers x 2 steps
    assert sum(v["total_s"] for v in kernels.values()) == pytest.approx(0.139356542, rel=1e-6)
