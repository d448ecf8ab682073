"""Device-step telemetry: always-on per-step counters for jitted hot paths.

The host-side planes (tasks, actors, RPC spans, the shm arena) have had
continuous observability since the seed; the DEVICE hot paths — the
llama train step, the MoE dispatch, the macro-step decode engine — were
observable only by re-running a benchmark (today `benchmark/run.py`:
`pretrain-4k` for the train step, the four serve cells for the
engine; the MoE dispatch is in no cell: not measured). Production TPU fleets live on
per-step telemetry (MegaScale attributes most of its recovered MFU to
always-on step/compile/straggler monitoring), so this layer wraps any
jitted callable and records, with near-zero host overhead:

- per-step wall time and the inter-step GAP (host time the device sat
  idle between dispatches) → goodput % = busy / wall over a window
- compile / retrace events, detected from the jit cache size (no
  device sync, no XLA hooks): a call during which `_cache_size()` grew
  was a compile, and its duration is the compile time
- FLOPs per call — passed explicitly, or read ONCE from XLA cost
  analysis after the first compile — rolled into a live MFU estimate
  against the device's peak (`peak_flops()` below)
- device memory high-water, sampled at SNAPSHOT time (never per step)
  from `device.memory_stats()` with a `live_arrays` fallback on
  backends that report none (CPU)

The recording path is append-a-tuple + a few float compares: no device
syncs, no allocations beyond the ring slot, nothing traced into the
wrapped function (the wrapper calls `fn` untouched, so the jaxpr is
bit-identical — tests/test_step_telemetry.py lints exactly that).

When the step executes under an active trace context (a traced task or
actor call), the step is ALSO recorded as a span through
`util/tracing.py` — parented under the enclosing RPC span — so
`observability.export_trace()` can lay device steps on the same
timeline as the task rows and RPC spans that dispatched them.
"""
from __future__ import annotations

import collections
import threading
import time
from bisect import bisect_left as _bisect
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.util import tracing as _tracing
from ray_tpu.util.metrics import metric_singletons as _metric_singletons

_registry_lock = threading.Lock()
_registry: "Dict[str, StepTelemetry]" = {}

# step-time histogram buckets (seconds); shared between the local
# per-telemetry counting arrays and the exported Prometheus Histogram
_STEP_BOUNDS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0)

# peak device FLOP/s by platform/kind for the live-MFU estimate.
# bf16 peaks; override per-telemetry via peak_flops_per_s=.
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_flops(device=None) -> Optional[float]:
    """Best-known peak FLOP/s for `device` (default: first local device);
    None when unknown (CPU) — MFU is then reported as None, flops/s
    still measured."""
    try:
        import jax

        d = device or jax.local_devices()[0]
        kind = getattr(d, "device_kind", "")
        for prefix, peak in _PEAK_FLOPS.items():
            if kind.startswith(prefix):
                return peak
    except Exception:
        pass
    return None


def _device_label() -> str:
    try:
        import jax

        d = jax.local_devices()[0]
        return f"{d.platform}:{d.id}"
    except Exception:
        return "device:?"


def _memory_stats() -> Dict[str, Any]:
    """Device memory occupancy; snapshot-time only (can walk buffers)."""
    out: Dict[str, Any] = {}
    try:
        import jax

        d = jax.local_devices()[0]
        stats = d.memory_stats()
        if stats:
            out["bytes_in_use"] = int(stats.get("bytes_in_use", 0))
            if "peak_bytes_in_use" in stats:
                out["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
            return out
        # CPU backend reports no allocator stats: approximate from the
        # live arrays the client still holds
        out["bytes_in_use"] = int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:
        pass
    return out


class StepTelemetry:
    """Counters + ring buffer for one instrumented hot path.

    All mutation happens on the caller's thread under a lock that is
    only ever contended by snapshot() readers — the step path itself is
    a handful of float ops.
    """

    def __init__(self, name: str, *, flops_per_call: Optional[float] = None,
                 window: int = 512, peak_flops_per_s: Optional[float] = None,
                 kind: str = "training"):
        self.name = name
        self.kind = kind
        self.flops_per_call = flops_per_call
        self.peak_flops_per_s = (
            peak_flops_per_s if peak_flops_per_s is not None else peak_flops()
        )
        self._lock = threading.Lock()
        self.steps = 0
        self.compiles = 0
        self.compile_time_s = 0.0
        self.busy_s = 0.0           # sum of per-call wall times (non-compile)
        self.gap_s = 0.0            # sum of inter-call gaps
        self._t_first: Optional[float] = None
        self._t_last_end: Optional[float] = None
        self._window: collections.deque = collections.deque(maxlen=window)
        # running window sums, maintained on append/evict: the gauge
        # and snapshot paths must never re-scan 512 entries (a ~100µs
        # spike on the step path at 4 Hz, visible in the overhead bench)
        self._w_busy = 0.0
        self._w_flops = 0.0
        self._w_flops_n = 0
        # bounded event ring for export_trace(): (t0, t1, step_idx,
        # compile?, trace_ctx) — ctx'd events also ship as spans, so the
        # ring only renders the ctx-less ones locally
        self._events: collections.deque = collections.deque(maxlen=4096)
        self._device = _device_label()
        self.mem_highwater_bytes = 0
        self._t_gauges = 0.0  # last gauge refresh (throttled)
        self._t_flush = 0.0   # last GCS snapshot push (throttled)
        # local step-time bucket counts, merged into the shared
        # Histogram at the gauge cadence (per-step observe() pays a
        # tags-merge + sort + lock; a local bisect+increment doesn't)
        self._hist_counts = [0] * (len(_STEP_BOUNDS) + 1)
        self._hist_sum = 0.0
        with _registry_lock:
            _registry[name] = self

    # ---------------------------------------------------------- recording
    def record(self, t0: float, t1: float, *, compiled: bool = False,
               ctx: Optional[Dict[str, str]] = None,
               links: Optional[List[Dict[str, str]]] = None,
               flops: Optional[float] = None) -> None:
        """One call of the instrumented fn: [t0, t1] in perf_counter
        time. Appends to counters only — nothing here touches the
        device."""
        dur = t1 - t0
        with self._lock:
            self.steps += 1
            if self._t_first is None:
                self._t_first = t0
            if self._t_last_end is not None and t0 > self._t_last_end:
                self.gap_s += t0 - self._t_last_end
            self._t_last_end = t1
            if compiled:
                self.compiles += 1
                self.compile_time_s += dur
            else:
                self.busy_s += dur
                f = flops if flops is not None else self.flops_per_call
                if len(self._window) == self._window.maxlen:
                    old_d, old_f = self._window.popleft()
                    self._w_busy -= old_d
                    if old_f:
                        self._w_flops -= old_f
                        self._w_flops_n -= 1
                self._window.append((dur, f))
                self._w_busy += dur
                if f:
                    self._w_flops += f
                    self._w_flops_n += 1
                self._hist_counts[_bisect(_STEP_BOUNDS, dur)] += 1
                self._hist_sum += dur
            self._events.append((t0, t1, self.steps, compiled, ctx, links))
        if ctx is not None:
            self._record_span(t0, t1, compiled, ctx, links)

    def _record_span(self, t0, t1, compiled, ctx, links) -> None:
        """Ship the step as a DEVICE-kind span parented under the
        enclosing RPC span, so it lands in the same collected trace.
        perf_counter times are rebased to wall clock at record time."""
        try:
            from ray_tpu._private.ids import hex_id, new_id
            from ray_tpu.util import tracing

            now_wall, now_perf = time.time(), time.perf_counter()
            span = {
                "trace_id": ctx["trace_id"],
                "span_id": hex_id(new_id())[:16],
                "parent_id": ctx["span_id"],
                "name": ("compile:" if compiled else "step:") + self.name,
                "start": now_wall - (now_perf - t0),
                "end": now_wall - (now_perf - t1),
                "kind": "DEVICE",
                "device": self._device,
                "step_name": self.name,
            }
            if links:
                span["links"] = [dict(l) for l in links]
            # defer_flush: the buffered-spans push must happen on the
            # span-flush thread, never inline here — this runs on the
            # instrumented step / engine dispatch path
            tracing._record(span, defer_flush=True)
        except Exception:
            pass

    # ----------------------------------------------------------- reading
    def snapshot(self, *, sample_memory: bool = True) -> Dict[str, Any]:
        """Latest telemetry as plain numbers (JSON-safe). Memory is
        sampled here — never on the step path."""
        with self._lock:
            steps = self.steps
            compiles = self.compiles
            compile_time_s = self.compile_time_s
            busy = self.busy_s
            gap = self.gap_s
            w_last = self._window[-1][0] if self._window else None
            w_n = len(self._window)
            w_busy = self._w_busy
            w_flops, w_flops_n = self._w_flops, self._w_flops_n
            t_first, t_last = self._t_first, self._t_last_end
        snap: Dict[str, Any] = {
            "name": self.name,
            "kind": self.kind,
            "device": self._device,
            "steps": steps,
            "compiles": compiles,
            "compile_time_s": round(compile_time_s, 6),
        }
        wall = (t_last - t_first) if (t_first is not None and t_last) else 0.0
        snap["wall_s"] = round(wall, 6)
        snap["gap_s"] = round(gap, 6)  # summed inter-step device idle
        n = steps - compiles
        snap["step_time_ms_avg"] = round(busy / n * 1e3, 4) if n else None
        if w_n:
            snap["step_time_ms_last"] = round(w_last * 1e3, 4)
            if w_flops_n and w_busy > 0:
                fps = w_flops / w_busy
                snap["flops_per_s"] = round(fps, 1)
                if self.peak_flops_per_s:
                    snap["mfu_pct"] = round(100.0 * fps / self.peak_flops_per_s, 2)
                else:
                    snap["mfu_pct"] = None
        # goodput: share of wall time the device had work dispatched
        # (compile time counts against goodput — it is exactly the kind
        # of stall this telemetry exists to surface)
        if wall > 0:
            snap["goodput_pct"] = round(100.0 * min(1.0, busy / wall), 2)
        if sample_memory:
            mem = _memory_stats()  # walks buffers — outside the lock
            if mem:
                seen = mem.get("peak_bytes_in_use", mem.get("bytes_in_use", 0))
                with self._lock:
                    # max-merge under the lock: concurrent snapshot()s
                    # (flusher thread vs a user call) must never let an
                    # older, lower reading roll the high-water back
                    hwm = max(self.mem_highwater_bytes, seen)
                    self.mem_highwater_bytes = hwm
                snap["device_bytes_in_use"] = mem.get("bytes_in_use")
                snap["device_mem_highwater_bytes"] = hwm
        return snap

    def events(self) -> List[Dict[str, Any]]:
        """Local step/compile events for export_trace(), perf_counter
        timebase rebased to wall clock. Events recorded under a trace
        ctx are EXCLUDED — they already shipped as spans and would
        render twice."""
        now_wall, now_perf = time.time(), time.perf_counter()
        with self._lock:
            evs = list(self._events)
        out = []
        for t0, t1, idx, compiled, ctx, links in evs:
            if ctx is not None:
                continue
            out.append({
                "name": ("compile:" if compiled else "step:") + self.name,
                "start": now_wall - (now_perf - t0),
                "end": now_wall - (now_perf - t1),
                "step": idx,
                "device": self._device,
                "compile": compiled,
            })
        return out

    def reset(self) -> None:
        with self._lock:
            self.steps = self.compiles = 0
            self.compile_time_s = self.busy_s = self.gap_s = 0.0
            self._t_first = self._t_last_end = None
            self._window.clear()
            self._events.clear()
            self._w_busy = self._w_flops = 0.0
            self._w_flops_n = 0
            self._hist_counts = [0] * (len(_STEP_BOUNDS) + 1)
            self._hist_sum = 0.0


def get(name: str) -> Optional[StepTelemetry]:
    with _registry_lock:
        return _registry.get(name)


def all_telemetries() -> List[StepTelemetry]:
    with _registry_lock:
        return list(_registry.values())


def _cost_analysis_flops(fn, args, kwargs) -> Optional[float]:
    """XLA cost-analysis FLOPs of fn at these args: read once, after the
    first compile (lowering is host-only; the executable comes from the
    cache XLA just filled)."""
    try:
        analysis = fn.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(analysis, list):
            analysis = analysis[0] if analysis else None
        if analysis:
            f = float(analysis.get("flops", 0.0))
            return f if f > 0 else None
    except Exception:
        pass
    return None


def instrument_step(fn: Callable, *, name: Optional[str] = None,
                    flops_per_call: Optional[float] = None,
                    peak_flops_per_s: Optional[float] = None,
                    telemetry: Optional[StepTelemetry] = None,
                    kind: str = "training") -> Callable:
    """Wrap a jitted hot-path callable with step telemetry.

        step = observability.instrument_step(
            jax.jit(train_step), flops_per_call=flops_per_token(cfg, T) * B * T)
        ...
        step.telemetry.snapshot()   # live MFU / goodput / compiles

    The wrapper adds host work only (two perf_counter reads, a cache-size
    probe, one ring append): the wrapped jaxpr — and therefore the HLO —
    is identical to `fn`'s. `flops_per_call` may be a number, a callable
    `(args, kwargs) -> flops`, or None (read once from XLA cost analysis
    after the first compile). Metrics gauges flush through the standard
    util/metrics pipeline when a cluster is up."""
    import functools

    tel = telemetry or StepTelemetry(
        name or getattr(fn, "__name__", "step"),
        flops_per_call=flops_per_call if isinstance(flops_per_call, (int, float)) else None,
        peak_flops_per_s=peak_flops_per_s, kind=kind,
    )
    flops_fn = flops_per_call if callable(flops_per_call) else None
    cache_size = getattr(fn, "_cache_size", None)
    state = {"cache": 0, "auto_flops_done": flops_per_call is not None}
    if cache_size is not None:
        try:
            # baseline at WRAP time: wrapping an already-compiled jit fn
            # must not misreport its first (cache-hit) call as a compile
            state["cache"] = cache_size()
        except Exception:
            pass

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        ctx = _tracing.current_context()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        t1 = time.perf_counter()
        compiled = False
        if cache_size is not None:
            try:
                n = cache_size()
                compiled, state["cache"] = n > state["cache"], max(n, state["cache"])
            except Exception:
                pass
        if compiled and not state["auto_flops_done"]:
            # first successful compile: one cost-analysis read (host-only
            # lowering; the executable is already in XLA's cache)
            state["auto_flops_done"] = True
            tel.flops_per_call = _cost_analysis_flops(fn, args, kwargs)
        tel.record(
            t0, t1, compiled=compiled, ctx=ctx,
            flops=flops_fn(args, kwargs) if flops_fn is not None else None,
        )
        _update_gauges(tel)
        return out

    wrapped.telemetry = tel
    wrapped.__wrapped__ = fn
    return wrapped


# ------------------------------------------------------------- metrics
def _metrics_factory():
    from ray_tpu.util import metrics

    return dict(
        step_time=metrics.Histogram(
            "ray_tpu_step_time_s", "device step wall time",
            boundaries=list(_STEP_BOUNDS), tag_keys=("step",)),
        goodput=metrics.Gauge(
            "ray_tpu_step_goodput_pct",
            "device busy time / wall time", tag_keys=("step",)),
        mfu=metrics.Gauge(
            "ray_tpu_step_mfu_pct",
            "live MFU estimate over the step window", tag_keys=("step",)),
        flops=metrics.Gauge(
            "ray_tpu_step_flops_per_s",
            "achieved FLOP/s over the step window", tag_keys=("step",)),
        compiles=metrics.Gauge(
            "ray_tpu_compiles_total",
            "compile/retrace events on this hot path", tag_keys=("step",)),
        compile_time=metrics.Gauge(
            "ray_tpu_compile_time_s_total",
            "cumulative compile time", tag_keys=("step",)),
        mem_hwm=metrics.Gauge(
            "ray_tpu_device_mem_highwater_bytes",
            "device memory high-water", tag_keys=("step",)),
    )


_metrics = _metric_singletons(_metrics_factory)


def _refresh_mem_gauges(snap_steps: Dict[str, Any]) -> None:
    """Memory high-water gauges from already-computed snapshots —
    called by observability.flush() on the flusher thread, never from
    the step path (snapshotting memory can walk live buffers)."""
    try:
        g = _metrics()
        for name, s in snap_steps.items():
            hwm = s.get("device_mem_highwater_bytes")
            if hwm is not None:
                g["mem_hwm"].set(hwm, tags={"step": name})
    except Exception:
        pass


def _update_gauges(tel: StepTelemetry) -> None:
    """Metric refresh on the step path, throttled to 4 Hz per hot path:
    the common call pays one perf_counter compare. Step times were
    already COUNTED into the telemetry's local bucket array by record()
    (no observation is dropped by the throttle); here they bulk-merge
    into the shared Histogram and the derived gauges (goodput / MFU /
    compiles) recompute over the window. The memory gauge refreshes only
    in flush()/snapshot() — it can walk buffers."""
    now = time.perf_counter()
    if now - tel._t_gauges < 0.25:
        return
    tel._t_gauges = now
    try:
        g = _metrics()
        tags = {"step": tel.name}
        with tel._lock:
            if not tel._window:
                return
            w_busy, w_flops = tel._w_busy, tel._w_flops
            busy = tel.busy_s
            compiles, compile_time = tel.compiles, tel.compile_time_s
            t_first, t_last = tel._t_first, tel._t_last_end
            hist_counts, tel._hist_counts = (
                tel._hist_counts, [0] * (len(_STEP_BOUNDS) + 1))
            hist_sum, tel._hist_sum = tel._hist_sum, 0.0
        if any(hist_counts):
            g["step_time"].merge_counts(hist_counts, hist_sum, tags=tags)
        wall = (t_last - t_first) if (t_first is not None and t_last) else 0.0
        if wall > 0:
            g["goodput"].set(100.0 * min(1.0, busy / wall), tags=tags)
        if w_flops and w_busy > 0:
            g["flops"].set(w_flops / w_busy, tags=tags)
            if tel.peak_flops_per_s:
                g["mfu"].set(100.0 * w_flops / w_busy / tel.peak_flops_per_s,
                             tags=tags)
        g["compiles"].set(compiles, tags=tags)
        g["compile_time"].set(compile_time, tags=tags)
        if now - tel._t_flush >= 2.0:
            # queue a snapshot push so /api/training|serve stays live
            # from any process. QUEUE, never push inline: the GCS RPC
            # (and the memory walk the snapshot takes) happen on the
            # telemetry flusher thread — a wedged GCS must not be able
            # to stall a train step or an engine decode loop
            tel._t_flush = now
            from ray_tpu import observability

            observability.flush_async(tel.kind)
    except Exception:
        pass
