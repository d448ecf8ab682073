"""ray_tpu.observability — unified TPU observability.

Three layers, one pipeline:

- `instrument_step(fn, flops_per_call=...)` wraps any jitted hot path
  with near-zero-overhead step telemetry (wall time, goodput, compile
  events, live MFU, device memory high-water) — `step_telemetry.py`.
- Telemetry snapshots flush through the existing GCS metrics path and
  surface as Prometheus gauges on the dashboard `/metrics` plus JSON
  snapshots on `/api/training`, `/api/serve` and `/api/data`.
- `export_trace(path)` merges the task timeline, RPC spans and device
  step/compile events into ONE Chrome/Perfetto trace with parent
  linkage from driver spans into the device steps they caused —
  `trace_export.py`.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from ray_tpu.observability.step_telemetry import (  # noqa: F401
    StepTelemetry,
    all_telemetries,
    get,
    instrument_step,
    peak_flops,
)
from ray_tpu.observability.trace_export import export_trace  # noqa: F401

# The spans of the serving engine's macro loop (serve/llm_engine.py), each a
# `jax.profiler.TraceAnnotation` on the engine's loop thread: they land in the
# profiler's own trace, on the device events' clock, and cost about a
# microsecond while no profiler session is open. The first five tile one loop
# iteration; `engine.fetch` lies inside `engine.resolve`.
ENGINE_SPANS = (
    "engine.idle",      # waiting on `_wake`: no request queued, resident or resuming, or nothing plannable
    "engine.intake",    # queue and job drains, deadline shedding, plan repair, the throttled snapshot push
    "engine.plan",      # `_plan()`: admissions, block tables and decode chunks for up to `macro_phases` phases
    "engine.dispatch",  # `_dispatch_macro()`: plan arrays built and the macro-step enqueued; stats: seq, phases,
                        # steps, admissions, A, P, prompt_tokens, lane_steps, finishing, finish_wait_steps, ctx_chunks,
                        # ctx_tokens, prompt_pairs (what the decode steps' and the admissions' attention has to do),
                        # past_window_lane_steps (a model with sliding-window layers only), admit_rows, admit_pieces, admit_phases
                        # (A is the lanes' power-of-two bucket in every dispatch and P names the program; admit_rows is what the
                        # device runs: P x the pieces of each admitting phase's count, `models/paged.admit_pieces`, 3 admissions
                        # as 2 + 1 rows, not A x P a phase; admit_pieces is the admission bodies run);
                        # the lane account (PR 41): vacant_lane_steps, blocked_lane_steps, spent_lane_steps, which with
                        # lane_steps are n_slots x steps; the wait account over its admissions: plan_wait_us, lane_wait_us,
                        # admitted_first_plan; the lead and the stall: admit_lead_steps, admit_lead_phases, stall_lane_phases;
                        # short, q (PR 47): 1 and the vacancy quantum in steps where a lane left vacant closed the plan
    "engine.resolve",   # `_resolve()` of dispatch `seq`: the fetch, then delivery of its tokens to the requests; stats:
                        # seq, the plan counts of its dispatch over again, the three accounts among them (a trace that
                        # starts after a dispatch still holds them), and the dispatch's device counters where the decode
                        # module names any
    "engine.fetch",     # inside resolve: the blocking device-to-host reads of the dispatch's tokens
)

# A request's own account (ISSUE 54), written ONCE, where the engine finishes the request: one more
# `TraceAnnotation` of no length from the same thread into the same trace, inside the `engine.resolve` that
# delivered its last token (a cancelled request's is written by the thread that cancelled it, a shed one's
# inside `engine.intake`). Not one of ENGINE_SPANS: those tile the loop's iterations, this one is a record.
# Every time is `time.perf_counter()` of the engine's process (CLOCK_MONOTONIC) in whole microseconds.
# Stats: `rid`; `reason` (`length`, `stop`, `cancelled`, `shed`, `migrated`, `error`); `tokens`;
#   `submit_us`, `done_us`: the two ends, absolute, to set against a client's stamps of the same rid;
#   `seq_first`, `seq_last`: the `seq` of its admitting dispatch and of the dispatch whose resolve finished it
#     (its parents: `engine.dispatch(seq)` / `engine.resolve(seq)`, and through them the device's executions);
#   the five HOST STATIONS, which tile [submit, done] exactly (each boundary is one stamp used on both sides):
#     `unseen_us` submit -> the start of the first plan that found it, `lane_wait_us` -> the start of the plan
#     that admits it, `plan_us` -> its admitting dispatch is enqueued, `flight_us` -> the fetch of the last
#     dispatch it rides has returned, `deliver_us` -> done (the requests delivered before it in that resolve);
#   the PLAN'S COUNTS, summed over every dispatch it rode (`llm_engine._dispatch_counts`' walk): `dispatches`;
#     `lead_steps`, `lead_phases`, `lead_rows` (decode steps, admitting phases and their token rows that its
#     admitting dispatch runs before its own phase), `own_rows` (the rows of its own admitting phase),
#     `decode_steps` (its takes), `stall_phases`, `stall_rows` (others' admitting phases it was live through, in
#     ANY dispatch of its life, and their rows), `tail_steps`, `tail_phases`, `tail_rows` (what its last dispatch
#     still runs after its last token exists);
#   `ahead_us` (enqueue of its admitting dispatch -> the return of the fetch of the dispatch in flight then; 0
#     where the device was idle), `late` (how many of its dispatches the host came to fetch after the result
#     was ready), `spec` (1 where the engine plans verify rounds of a draft model: the counts are estimates).
# The same figures are fields of the lifeline's `finish` event: `request_timeline(rid)` shows them with no profiler.
REQUEST_SPAN = "engine.request"

__all__ = [
    "ENGINE_SPANS",
    "REQUEST_SPAN",
    "StepTelemetry",
    "instrument_step",
    "export_trace",
    "peak_flops",
    "get",
    "all_telemetries",
    "publish_snapshot",
    "fetch_snapshots",
    "prune_snapshot_key",
    "reset_epoch",
    "flush",
    "flush_async",
    "snapshot",
]


def fetch_snapshots(kind: str, timeout: float = 5.0) -> Dict[str, Dict[str, Any]]:
    """Every live reporter's latest published snapshot for `kind` from
    the GCS telemetry table ({reporter_id12: snapshot} — the data the
    dashboard's /api/<kind> serves; stale reporters already pruned
    server-side). {} when no cluster is reachable. The read half of
    publish_snapshot: consumers (the serve autoscaler, the load
    harness) share this one contract with the table."""
    try:
        from ray_tpu._private.worker import get_global_core

        return get_global_core().gcs_request(
            "telemetry.get", {"kind": kind}, timeout=timeout
        ) or {}
    except Exception:
        return {}

def prune_snapshot_key(kind: str, key: str, timeout: float = 5.0) -> int:
    """Remove `key` from every reporter's published `kind` snapshot in
    the GCS telemetry table (and from this process's pending extras).
    The delete half of publish_snapshot: when a reporter is KNOWN dead
    (the serve controller detecting a replica crash), its last snapshot
    must stop feeding consumers instead of riding out the retention
    window. Returns the number of reporter snapshots pruned
    (best-effort; 0 when no cluster is reachable)."""
    with _extras_lock:
        d = _extras.get(kind)
        if d is not None:
            d.pop(key, None)
    try:
        from ray_tpu._private.worker import get_global_core

        return int(get_global_core().gcs_request(
            "telemetry.prune", {"kind": kind, "key": key}, timeout=timeout
        ) or 0)
    except Exception:
        return 0


def reset_epoch(kind: Optional[str] = None, timeout: float = 5.0) -> float:
    """Start a fresh telemetry epoch: bump the GCS table's generation
    fence so `fetch_snapshots` excludes every snapshot published BEFORE
    this call. `kind=None` fences all kinds.

    This is the A/B hygiene primitive: the table retains a dead
    reporter's last snapshot for up to 120s, so a paired run starting
    inside that window used to read the previous arm's corpses (the
    PR-8 loadgen worked around it by scraping live replicas directly —
    that workaround is now just a fallback). Returns the epoch
    timestamp (0.0 when no cluster is reachable)."""
    try:
        from ray_tpu._private.worker import get_global_core

        return float(get_global_core().gcs_request(
            "telemetry.epoch", {"kind": kind}, timeout=timeout
        ) or 0.0)
    except Exception:
        return 0.0


# driver-side extras merged into the published snapshot per kind
# (e.g. the trainer's per-report metrics, an engine's serving counters)
_extras_lock = threading.Lock()
_extras: Dict[str, Dict[str, Any]] = {}

# background snapshot flusher: hot paths (the engine decode loop, the
# instrumented train step) must NEVER block on the GCS round-trip — a
# stalled GCS would freeze serving/training through a telemetry push.
# They queue a kind here; one daemon thread drains, coalescing bursts.
_flush_lock = threading.Lock()
_flush_dirty: set = set()
_flush_wake = threading.Event()
_flush_thread: Optional[threading.Thread] = None


def publish_snapshot(kind: str, data: Dict[str, Any]) -> None:
    """Merge `data` into this process's `kind` ("training" / "serve")
    snapshot and queue a push to the GCS so the dashboard's /api/<kind>
    serves it. Values must be JSON-safe. The push happens on a
    background thread — call flush(kind) to force a synchronous one."""
    with _extras_lock:
        _extras.setdefault(kind, {}).update(data)
    flush_async(kind)


def flush_async(kind: Optional[str] = None) -> None:
    """Queue a GCS snapshot push on the background flusher thread."""
    global _flush_thread
    with _flush_lock:
        _flush_dirty.add(kind)
        if _flush_thread is None or not _flush_thread.is_alive():
            _flush_thread = threading.Thread(
                target=_flush_loop, daemon=True, name="telemetry-flush")
            _flush_thread.start()
    _flush_wake.set()


def _flush_loop() -> None:
    while True:
        _flush_wake.wait()
        _flush_wake.clear()
        with _flush_lock:
            kinds = list(_flush_dirty)
            _flush_dirty.clear()
        for k in kinds:
            try:
                flush(k)
            except Exception:
                pass


def snapshot(kind: Optional[str] = None) -> Dict[str, Any]:
    """This process's current telemetry snapshot: every registered
    StepTelemetry of `kind` (all kinds when None) plus published
    extras."""
    out: Dict[str, Any] = {"time": time.time(), "steps": {}}
    for tel in all_telemetries():
        if kind is None or tel.kind == kind:
            out["steps"][tel.name] = tel.snapshot()
    with _extras_lock:
        for k, d in _extras.items():
            if kind is None or k == kind:
                out.update(d)
    return out


def flush(kind: Optional[str] = None, *, timeout: float = 5.0) -> bool:
    """Push the latest snapshot(s) to the GCS synchronously
    (best-effort; no cluster → False). Hot paths go through
    flush_async instead; the timeout bounds the RPC so even a direct
    call can never hang its caller on a wedged GCS. Snapshot time is
    also when the memory high-water gauge refreshes — sampling device
    memory can walk live buffers, which must stay off the step path."""
    try:
        from ray_tpu._private.worker import get_global_core
        from ray_tpu.observability.step_telemetry import _refresh_mem_gauges

        core = get_global_core()
        kinds = [kind] if kind else sorted(
            {t.kind for t in all_telemetries()} | set(_extras)
        )
        for k in kinds:
            snap = snapshot(k)
            _refresh_mem_gauges(snap.get("steps", {}))
            core.gcs_request(
                "telemetry.report",
                {"kind": k, "reporter": core.worker_id, "snapshot": snap},
                timeout=timeout,
            )
        return True
    except Exception:
        return False
