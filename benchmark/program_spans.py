"""The program's own marks in a run's device trace, on the trace's clock: the
serving engine's loop spans (`engine.*`, `jax.profiler.TraceAnnotation`s on the
engine's loop thread, `ray_tpu.observability.ENGINE_SPANS`), the macro-step's
executions by the name the program gives its jitted function, the device
operations with the `jax.named_scope` each carries (`admit_prefill`,
`decode_chunk`), and the flash-attention kernels by the `name=` of their
Pallas calls. The per-layer readers `engine.starved_idle_pct`,
`engine.deliver_lag_ms`, `engine.finish_wait_steps`,
`programs.prefill_share_pct`, `programs.decode_step_ms` and
`kernels.flash_*_ms` are a few lines each on top of `serve_view` and
`kernel_calls`.

The `.xplane.pb` under `common.RUN_DIR/trace` is read once per process
(`load`). Everything after the read is arithmetic on tuples, checked in
`benchmark/tests/test_program_spans.py` on a small recorded list, like `trace_reduce`.

Where each mark lands on a TPU v5e (read from my chip runs, PR 27):
- a `TraceAnnotation("engine.dispatch", seq=3, ...)` is an event of that name
  on the line of the thread that made it, plane `/host:CPU`; its keyword
  arguments are the event's stats;
- a jitted function's name is the module event's name on the device's
  `XLA Modules` line: `jit_macro_step_slots_paged(<fingerprint>)`;
- an op event of the `XLA Ops` line is named by its whole HLO line, which
  holds no metadata, and its own stats are only its device time. The JAX name
  stack (`jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/
  admit_prefill/dot_general:`) is the stat `tf_op` of the event's METADATA,
  which `jax.profiler.ProfileData` does not hand out: `name_stacks` reads just
  that table from the file (a few fields of the protobuf wire format, the
  lines skipped). Operations the compiler adds itself (copies) have none;
- a Pallas call named `flash_fwd` is a `custom-call` op event whose HLO
  instruction carries the name: `%flash_fwd.18 = ... custom-call(...)`.

A program without these marks (the parent of PR 27) gives empty lists, and
every reader then returns None.
"""
from __future__ import annotations

import functools
import os
import re
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.trace_reduce import DEVICE_PLANE, HOST_PLANE, MODULE_LINE, OP_LINE, WINDOW_MARKER

# must equal ray_tpu.observability.ENGINE_SPANS (a test compares them; not
# imported, so that this file also runs over a program that has none)
ENGINE_SPANS = ("engine.idle", "engine.intake", "engine.plan", "engine.dispatch",
                "engine.resolve", "engine.fetch")
# the spans that tile one iteration of the engine's loop; engine.fetch lies inside engine.resolve
TOP_SPANS = ENGINE_SPANS[:5]
IDLE, DISPATCH, RESOLVE, FETCH = "engine.idle", "engine.dispatch", "engine.resolve", "engine.fetch"
MACRO_STEP = re.compile(r"^jit_macro_step_slots")
ADMIT, DECODE = "admit_prefill", "decode_chunk"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv")
# in the HLO name of a kernel the TPU compiler makes of a ragged product and
# names itself (`tf_op` `ragged-dot-none`, and the `ragged-dot-metadata`
# before it): the name stack the program gave the product is gone (PR 33)
COMPILER_NAMED = "ragged-dot"

Span = Tuple[str, float, float, Dict[str, Any]]  # name, start_s, duration_s, stats
Exec = Tuple[float, float]                       # start_s, duration_s of one macro-step execution
Op = Tuple[float, float, str]                    # start_s, duration_s, scope ("" = neither)
NamedOp = Tuple[float, float, str, str]          # start_s, duration_s, HLO name, name stack
Interval = Tuple[float, float]


# ------------------------------------------------------------------ the read
def scope_of(text: str) -> str:
    """The macro-step half an operation belongs to, from its name stack. A
    decode step never runs inside the admission branch, so the two never nest;
    the later one wins should that ever change."""
    a, d = text.rfind(ADMIT), text.rfind(DECODE)
    if a < 0 and d < 0:
        return ""
    return ADMIT if a > d else DECODE


def halves(named: Sequence[NamedOp]) -> List[str]:
    """The macro-step half of each operation of one device, given in the
    order they ran. A kernel the compiler named itself carries no name stack:
    it takes the half of the last operation before it that had one (the sort
    and the gather of rows that feed a ragged product run just before it, and
    the device runs one operation at a time). Other operations without a
    stack (the compiler's own copies) stay under neither."""
    out, last = [], ""
    for _, _, name, text in named:
        half = scope_of(text)
        if half:
            last = half
        elif COMPILER_NAMED in name:
            half = last
        out.append(half)
    return out


def kernel_of(hlo_line: str) -> Optional[str]:
    """`%flash_bwd_dq.12 = ... custom-call(...)` -> `flash_bwd_dq`."""
    m = re.match(r"%?([A-Za-z_]+)", hlo_line)
    return m.group(1) if m and m.group(1) in KERNELS and "custom-call" in hlo_line else None


# -- the one table ProfileData leaves out: an event's metadata stats --------
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an int, a
    length-delimited field as a slice of `buf`, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def name_stacks(xspace: bytes, stat: str = "tf_op") -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: its metadata's `stat`}} from a serialized
    XSpace (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name
    = 2, .event_metadata = 4 and .stat_metadata = 5, both maps whose entries
    are key = 1, value = 2; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7, a reference into the stat metadata's names)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        name, event_md, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                event_md.append(v)
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry[1]] = bytes(dict(_fields(entry[2])).get(2, b"")).decode()
        if not DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, n in stat_names.items() if n == stat}
        table = out.setdefault(name, {})
        for entry in event_md:
            event_name, value = "", None
            for f, v in _fields(dict(_fields(entry))[2]):
                if f == 2:
                    event_name = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        value = bytes(st[5]).decode() if 5 in st else stat_names.get(st.get(7), "")
            if value:
                table[event_name] = value
    return out


@functools.lru_cache(maxsize=2)
def load(trace_dir: str) -> Dict[str, Any]:
    """One pass over the newest `.xplane.pb` under `trace_dir`: what the
    arithmetic below needs, as plain tuples in seconds of the trace's clock."""
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(trace_dir)
    with open(path, "rb") as f:
        xspace = f.read()
    stacks = name_stacks(xspace)
    spans: List[Span] = []
    marks: List[Interval] = []
    modules: List[Tuple[str, float, float]] = []
    busy: List[Interval] = []
    ops: List[Tuple[float, float, str, str, str]] = []  # an Op, then its HLO name and name stack
    kernels: Dict[str, List[float]] = {}
    devices = 0
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == WINDOW_MARKER:
                        marks.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
                    elif name.startswith("engine."):
                        spans.append((name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                                      dict(ev.stats)))
        elif DEVICE_PLANE.match(plane.name):
            devices += 1
            stack = stacks.get(plane.name, {})
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules += [(trace_reduce.module_name(ev.name), ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9) for ev in line.events]
                elif line.name == OP_LINE:
                    named: List[NamedOp] = []
                    for ev in line.events:
                        start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                        busy.append((start, start + dur))
                        if trace_reduce.is_container(ev.name):
                            continue  # its time is its bodies'
                        named.append((start, dur, ev.name, stack.get(ev.name, "")))
                        kernel = kernel_of(ev.name)
                        if kernel:
                            kernels.setdefault(kernel, []).append(dur)
                    named.sort()
                    ops += [(s, d, half, name, text)
                            for (s, d, name, text), half in zip(named, halves(named))]
    ops.sort()
    window = max(marks, key=lambda m: m[1] - m[0]) if marks else None
    return {"path": path, "devices": devices, "window": window,
            "spans": sorted(spans, key=lambda s: s[1]), "modules": modules, "busy": busy,
            "ops": [op[:3] for op in ops], "named_ops": [op[:2] + op[3:] for op in ops],
            "kernels": kernels}


def run_trace(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """This run's trace, given the driver's `facts`: None for an untraced run
    (no `reduced`) and where the run left no trace file."""
    from benchmark import common

    if not facts.get("reduced"):
        return None
    try:
        return load(os.path.join(common.RUN_DIR, "trace"))
    except FileNotFoundError:
        return None


# ------------------------------------------------------- interval arithmetic
union = trace_reduce._union


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def complement(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What of [lo, hi] the merged, sorted intervals leave uncovered."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Seconds two sorted lists of disjoint intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


# ------------------------------------------------------------- the reductions
def pair_dispatches(dispatches: Sequence[Span], executions: Sequence[Exec]):
    """Each `engine.dispatch` enqueues one macro-step, and the device runs
    them in order: the k-th dispatch goes with the k-th execution that began
    after it did. An execution in flight when the trace starts has no dispatch
    in it and stays unpaired, as does a dispatch whose execution the trace no
    longer holds. Returns (pairs, unpaired executions, unpaired dispatches)."""
    pairs, lone_exec, j = [], [], 0
    dispatches = sorted(dispatches, key=lambda s: s[1])
    for ex in sorted(executions):
        if j < len(dispatches) and dispatches[j][1] <= ex[0]:
            pairs.append((dispatches[j], ex))
            j += 1
        else:
            lone_exec.append(ex)
    return pairs, lone_exec, list(dispatches[j:])


def whole_in_window(pairs, executions: Sequence[Exec], window: Interval):
    """The pairs whose execution lies in the window (counted whole by its
    middle, as `trace_reduce` does) and is whole in the trace: the last
    execution a trace holds is cut by the profiler's stop, and its dispatch
    plans more steps than the trace shows operations of. Counted, it made
    `programs.decode_step_ms` read 18.1 for 21.1 (B2, PR 30 to PR 35)."""
    lo, hi = window
    return [(dsp, ex) for dsp, ex in pairs
            if lo <= ex[0] + ex[1] / 2 <= hi and ex != executions[-1]]


def idle_by_span(busy: Sequence[Interval], spans: Sequence[Span],
                 window: Interval) -> Dict[str, Any]:
    """The window's device idle time (no operation running), split by the
    top-level span the engine's loop thread was in. `starved_s` is what lies
    outside `engine.idle`: the engine had something to do, or was doing it,
    and the device waited. A span still open when the profiler's session
    starts or stops is never written (up to a whole `engine.resolve`), so
    idle time before the first recorded span and after the last is set apart
    as `edges_s`, of unknown cause, and is in neither `starved_s` nor
    `uncovered_s` (idle time between two recorded spans that no span covers)."""
    lo, hi = window
    idle = complement(union(clip(busy, lo, hi)), lo, hi)
    idle_s = sum(e - s for s, e in idle)
    top = clip([(s, s + d) for n, s, d, _ in spans if n in TOP_SPANS], lo, hi)
    first, last = (min(s for s, _ in top), max(e for _, e in top)) if top else (hi, hi)
    edges_s = overlap(idle, [(lo, first), (last, hi)])
    by_span = {}
    for name in TOP_SPANS:
        covered = union(clip([(s, s + d) for n, s, d, _ in spans if n == name], lo, hi))
        by_span[name] = overlap(idle, covered)
    return {"window_s": hi - lo, "idle_s": idle_s, "by_span": by_span, "edges_s": edges_s,
            "uncovered_s": idle_s - sum(by_span.values()) - edges_s,
            "starved_s": idle_s - by_span[IDLE] - edges_s}


def serve_view(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Everything the serve readers share, from one trace: None where the
    trace has no window mark, no engine span or no named macro-step."""
    window, spans = trace["window"], trace["spans"]
    executions = sorted((s, d) for name, s, d in trace["modules"] if MACRO_STEP.match(name))
    if not window or not spans or not executions:
        return None
    lo, hi = window
    inside = lambda s, d: lo <= s + d / 2 <= hi  # noqa: E731  (counted whole by its middle, as trace_reduce does)
    pairs, lone_exec, lone_dispatch = pair_dispatches(
        [s for s in spans if s[0] == DISPATCH], executions)
    pairs = whole_in_window(pairs, executions, window)
    in_window = [ex for ex in executions if inside(*ex)]

    # device time of each execution's operations by scope, in one pass over both sorted lists
    by_exec = {ex: {ADMIT: 0.0, DECODE: 0.0, "": 0.0} for ex in executions}
    i = 0
    for s, d, scope in trace["ops"]:
        mid = s + d / 2
        while i < len(executions) and sum(executions[i]) < mid:
            i += 1
        if i < len(executions) and executions[i][0] <= mid:
            by_exec[executions[i]][scope] += d
    scoped = lambda execs, scope: sum(by_exec[ex][scope] for ex in execs)  # noqa: E731
    paired = [ex for _, ex in pairs]
    macro_s = sum(d for _, d in in_window)
    steps = sum(int(dsp[3].get("steps", 0)) for dsp, _ in pairs)

    resolves = {int(st["seq"]): (s, d) for n, s, d, st in spans if n == RESOLVE and "seq" in st}
    fetches = sorted((s, d) for n, s, d, _ in spans if n == FETCH)
    lags, fetch_lags = [], []
    for dsp, (es, ed) in pairs:
        r = resolves.get(int(dsp[3].get("seq", -1)))
        if r is None:
            continue
        lags.append((r[0] + r[1]) - (es + ed))
        inner = [fs + fd for fs, fd in fetches if r[0] <= fs and fs + fd <= r[0] + r[1] + 1e-9]
        if inner:
            fetch_lags.append(inner[-1] - (es + ed))

    counted = [dsp[3] for dsp in spans if dsp[0] == DISPATCH and lo <= dsp[1] <= hi]
    total = lambda key, rows: sum(int(r.get(key, 0)) for r in rows)  # noqa: E731
    return {
        "idle": idle_by_span(trace["busy"], spans, window),
        "executions": len(in_window), "paired": len(pairs),
        "unpaired_executions": len(lone_exec), "unpaired_dispatches": len(lone_dispatch),
        "macro_step_s": macro_s, "admit_s": scoped(in_window, ADMIT),
        "decode_s": scoped(in_window, DECODE), "unscoped_ops_s": scoped(in_window, ""),
        "neither_s": macro_s - scoped(in_window, ADMIT) - scoped(in_window, DECODE),
        "paired_decode_s": scoped(paired, DECODE), "paired_steps": steps,
        "paired_prompt_tokens": total("prompt_tokens", [d[3] for d, _ in pairs]),
        "deliver_lag_s": lags, "fetch_lag_s": fetch_lags,
        "dispatches": len(counted), "finishing": total("finishing", counted),
        "finish_wait_steps": total("finish_wait_steps", counted),
    }


def run_serve_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`serve_view` of this run's trace, worked out once for all its readers."""
    trace = run_trace(facts)
    if trace is None:
        return None
    if "serve_view" not in trace:
        trace["serve_view"] = serve_view(trace)
    return trace["serve_view"]


def decode_step_ms(view: Dict[str, Any]) -> Optional[float]:
    return 1e3 * view["paired_decode_s"] / view["paired_steps"] if view["paired_steps"] else None


def kernel_calls(trace: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Per named kernel: calls, their median and their summed device time."""
    return {k: {"calls": len(v), "median_s": statistics.median(v), "total_s": sum(v)}
            for k, v in trace["kernels"].items() if v}


def kernel_reading(facts: Dict[str, Any], kernel: str) -> Optional[Dict[str, Any]]:
    """What a `kernels.flash_*_ms` reader returns: one call's median in ms."""
    trace = run_trace(facts)
    calls = kernel_calls(trace).get(kernel) if trace else None
    if not calls:
        return None
    return {"value": 1e3 * calls["median_s"], "calls": calls["calls"], "total_s": calls["total_s"]}
