"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, time per jitted module and per device operation, and the longest idle
gaps with what the host was doing in each.

Reads the file with nothing but JAX (`jax.profiler.ProfileData`). The
reduction is pure arithmetic on (plane, line, name, start, duration) tuples, so
`reduce_events` is checked in the tests on a small recorded trace kept beside
them as JSON. Raw traces from chip runs are never committed.

What the planes look like on a TPU v5e (read from my chip runs, PR 26): each
chip is a plane `/device:TPU:<n>`; its line `XLA Modules` holds one event per
execution of a jitted program, named `jit_<function>(<fingerprint>)`; its line
`XLA Ops` holds one event per device operation inside a module, named by its
whole HLO line (a Pallas kernel is a `custom-call` whose
`custom_call_target` is `tpu_custom_call`); `Async XLA Ops` holds copies that
overlap them and is not counted as busy time. Host threads
are lines of the plane `/host:CPU`; with the Python tracer on, their events are
Python calls, and a `jax.profiler.TraceAnnotation` is an event under its own
name.

The traced window is marked in the trace itself: the driver holds a
`TraceAnnotation(WINDOW_MARKER)` open over it, and the reducer counts device
time inside that event only. The profiler records from before `start_trace`
returns until some time after `stop_trace` is called (0.2 s and more), and its
clock starts at the session, not at the epoch, so a window timed on the host
beside it is shorter than what the trace holds: on a device that never idles
busy time then reads longer than the window (the driver's check of PR 26
refused exactly that in `docqa-saturate`).
"""
from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_MARKER = "bench_window"
TOP = 10
# an idle gap shorter than this is the device's own turn-around between two
# operations, not the host's doing
MIN_GAP_S = 50e-6

Event = Tuple[str, str, str, float, float]  # plane, line, name, start_s, duration_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_events(path: str, host_min_s: float = 20e-6) -> List[Event]:
    """Device module and op events, and host events of at least `host_min_s`
    (the Python tracer writes millions of shorter ones)."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                dur = ev.duration_ns * 1e-9
                if not device and dur < host_min_s:
                    continue
                name = ev.name
                if device and line.name == OP_LINE:
                    name = ("[container] " if is_container(name) else "") + op_label(name)
                out.append((plane.name, line.name, name, ev.start_ns * 1e-9, dur))
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def module_name(name: str) -> str:
    """`jit_step_fn(123456)` -> `jit_step_fn`."""
    return re.sub(r"\(\d+\)$", "", name)


_OP = re.compile(r"^%?(?P<name>[^ ]+) = (?P<type>\(?[a-z0-9]+\[[0-9,]*\])?")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k[A-Za-z]+)")
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
# operations that only hold others (their time is their bodies'): counted as
# busy time with everything else, left out of the per-operation table
CONTAINERS = ("while", "conditional", "call")


def is_container(text: str) -> bool:
    m = _OPCODE.search(text.split(" = ", 1)[-1])
    return bool(m) and m.group(1) in CONTAINERS


def op_label(text: str) -> str:
    """An op event's name is its whole HLO line. Keep the instruction's name,
    the type of its (first) result and what kind of operation it is:
    `fusion.393 bf16[2,4096,14336] kOutput`, `closed_call.9 tpu_custom_call`."""
    m = _OP.match(text)
    if not m:
        return text[:80]
    target, kind = _TARGET.search(text), _KIND.search(text)
    if target:
        return f"{m.group('name')} {target.group(1)}"
    shape = (m.group("type") or "").lstrip("(")
    return " ".join(x for x in (m.group("name"), shape, kind.group(1) if kind else "") if x)


# host events that are a thread waiting, not working: they label a gap only
# where nothing else was recorded in it
WAITING = re.compile(r"sleep|wait|select|poll|acquire|epoll|futex", re.I)


def _host_label(gap: Tuple[float, float], host: List[Event]) -> str:
    """The host event that covers most of the gap without being far longer
    than it (a thread's outermost frames cover everything and say nothing);
    a working thread is preferred to a waiting one."""
    gs, ge = gap
    best = {False: ("no host event recorded", 0.0), True: ("", 0.0)}
    for _, line, name, s, d in host:
        cover = min(ge, s + d) - max(gs, s)
        if cover <= 0 or d > 4 * (ge - gs) + 1e-3:
            continue
        waiting = bool(WAITING.search(name))
        if cover > best[waiting][1]:
            best[waiting] = (name, cover)
    return best[False][0] if best[False][1] > 0 or not best[True][0] else best[True][0]


def find_window(events: List[Event]) -> Optional[Tuple[float, float]]:
    """(start, end) of the longest WINDOW_MARKER event on the host, in the
    trace's own clock; None where the trace holds none."""
    marks = [(d, s) for p, _, name, s, d in events if p == HOST_PLANE and name == WINDOW_MARKER]
    if not marks:
        return None
    d, s = max(marks)
    return s, s + d


def reduce_events(events: List[Event], window_s: float = 0.0) -> Dict[str, Any]:
    """busy_s is the union of the intervals in which an operation ran on a
    device, averaged over the devices used. Where the trace holds a
    WINDOW_MARKER event, that event is the window: device intervals are cut to
    it (so busy_s cannot pass window_s), an execution or an operation is
    counted, whole, where its middle lies inside, and idle time at either end
    is a gap like any other. Without one, window_s is the span from the first
    to the last device operation, or the `window_s` given if that is longer."""
    mark = find_window(events)
    lo, hi = mark or (float("-inf"), float("inf"))
    planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    host = [e for e in events if e[0] == HOST_PLANE and e[2] != WINDOW_MARKER]
    busy, recorded, spans, gaps_all = [], [], [], []
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, List[float]] = {}
    for plane in planes:
        op_iv = [(s, s + d) for p, line, _, s, d in events if p == plane and line == OP_LINE]
        mod_iv = [(s, s + d) for p, line, _, s, d in events if p == plane and line == MODULE_LINE]
        merged = _union(op_iv or mod_iv)
        recorded.append(sum(e - s for s, e in merged))
        merged = [(max(s, lo), min(e, hi)) for s, e in merged if min(e, hi) > max(s, lo)]
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged))
        spans.append(merged[-1][1] - merged[0][0])
        edges = [(lo, lo)] + merged + [(hi, hi)] if mark else merged
        gaps_all += [(edges[i][1], edges[i + 1][0]) for i in range(len(edges) - 1)
                     if edges[i + 1][0] - edges[i][1] >= MIN_GAP_S]
        for p, line, name, s, d in events:
            if p != plane or not lo <= s + d / 2 <= hi:
                continue
            if line == MODULE_LINE:
                modules.setdefault(module_name(name), []).append(d)
            elif line == OP_LINE and not name.startswith("[container] "):
                ops.setdefault(name, []).append(d)
    marked = bool(mark)
    window = (mark[1] - mark[0]) if mark else max([window_s] + spans)
    if not busy:
        return {"devices": 0, "busy_s": 0.0, "window_s": window, "window_marked": marked,
                "modules": {}, "ops": {}, "device_ops": [], "idle_gaps": []}
    gap_by_label: Dict[str, float] = {}
    for gap in sorted(gaps_all, key=lambda g: g[0] - g[1])[:200]:
        label = _host_label(gap, host)
        gap_by_label[label] = gap_by_label.get(label, 0.0) + (gap[1] - gap[0])
    op_groups = {name: sum(ds) for name, ds in ops.items()}
    n = len(busy)
    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "window_s": window,
        "window_marked": marked,
        # device time in the whole trace, before and after the mark too
        "busy_recorded_s": sum(recorded) / len(recorded),
        "modules": {k: {"count": len(v), "total_s": sum(v), "median_s": statistics.median(v)}
                    for k, v in modules.items()},
        "ops": {k: {"count": len(v), "total_s": sum(v)} for k, v in ops.items()},
        "device_ops": [[k, v / n] for k, v in
                       sorted(op_groups.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gap_by_label.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gap_count": len(gaps_all),
    }


def reduce_dir(trace_dir: str, window_s: float = 0.0) -> Dict[str, Any]:
    return reduce_events(read_events(find_xplane(trace_dir)), window_s)
