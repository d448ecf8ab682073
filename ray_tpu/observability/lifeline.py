"""Request lifelines — per-request lifecycle events keyed by rid.

Every layer a request crosses (handle routing, the LLM engine, the KV
plane, the decode-pool resume path) drops typed, timestamped events
into the process-local store under the request's existing ``rid`` (the
PR-13 caller-generated id that already survives redispatch and the
prefill→decode migration). Three sinks fan out from ONE record call:

- an in-memory per-rid buffer (bounded LRU; finished rids age out —
  the leak-audit contract) serving ``events(rid)`` and the engine's
  ``request_timeline(rid)``;
- the crash-surviving flight recorder (fixed-size /dev/shm ring,
  observability/flight_recorder.py) so a SIGKILLed replica's last
  events are recoverable post-mortem;
- when the event carries a PR-4 trace context, a LIFELINE-kind span
  shipped through the deferred span-flush path — the GCS aggregates
  them cluster-wide and ``export_trace()`` renders each rid's hops as
  flow-linked spans parented under the task spans.

Per-REQUEST events may allocate (a dict per event); the per-TOKEN and
per-DISPATCH paths must not — those call the flight recorder directly
(ring write + counter bump only, lint-pinned).
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from ray_tpu.observability import flight_recorder
from ray_tpu.observability.flight_recorder import EV

__all__ = ["record", "events", "finish", "store", "set_process_label",
           "rid_bytes", "EV"]

# how many live rids a process buffers (LRU-evicted beyond this) and
# how many events each rid keeps
_MAX_RIDS = 512
_MAX_EVENTS_PER_RID = 128
# finished rids linger so late cross-process queries still see them,
# then age out — the leak audit pins this. Room for what one replica
# completes in a minute at the rates served (a benchmark window of 40 s
# asks afterwards for every request it completed, ~280 at the most:
# at 256 the oldest had aged out); ~1 KB a request of four events
_MAX_FINISHED = 1024

_proc_label: Optional[str] = None


def set_process_label(label: str) -> None:
    """Name this process's events (e.g. the serve replica name or the
    engine name) — stamped on every event as ``where``."""
    global _proc_label
    _proc_label = label


def rid_bytes(rid: str) -> bytes:
    """Pre-encode a rid for flight-recorder records (cached per request
    by callers; the hot path must not encode per event)."""
    return rid.encode("ascii", "replace")[:24]


class LifelineStore:
    """Bounded per-process rid → event-list map."""

    def __init__(self, max_rids: int = _MAX_RIDS,
                 max_finished: int = _MAX_FINISHED):
        self._lock = threading.Lock()
        self._live: "OrderedDict[str, List[dict]]" = OrderedDict()
        self._finished: "OrderedDict[str, List[dict]]" = OrderedDict()
        self._max_rids = max_rids
        self._max_finished = max_finished
        self._pid = os.getpid()

    def record(self, rid: str, kind: str, *, t: Optional[float] = None,
               ctx: Optional[Dict[str, str]] = None,
               rid_b: Optional[bytes] = None,
               a: float = 0.0, b: float = 0.0, **fields: Any) -> None:
        """Append one typed event to ``rid``'s lifeline (and the flight
        recorder; and, under a trace ctx, the span plane)."""
        if t is None:
            t = time.time()
        ev: Dict[str, Any] = {"t": t, "kind": kind, "pid": self._pid}
        if _proc_label:
            ev["where"] = _proc_label
        if fields:
            ev.update(fields)
        with self._lock:
            buf = self._live.get(rid)
            if buf is None:
                buf = self._finished.get(rid)  # post-finish stragglers
            if buf is None:
                buf = self._live[rid] = []
                if len(self._live) > self._max_rids:
                    self._live.popitem(last=False)
            if len(buf) < _MAX_EVENTS_PER_RID:
                buf.append(ev)
        kid = EV.get(kind)
        if kid is not None:
            flight_recorder.get_recorder().write(
                kid, rid_b if rid_b is not None else rid_bytes(rid),
                a=a, b=b)
        if ctx is not None:
            self._ship_span(rid, kind, t, ctx, ev)

    def _ship_span(self, rid: str, kind: str, t: float,
                   ctx: Dict[str, str], ev: Dict[str, Any]) -> None:
        """Ship one lifeline event as a LIFELINE-kind span through the
        DEFERRED flush path (never an inline GCS push — same rule as
        device-step spans). The rid rides the span so export_trace can
        chain a request's hops with flow arrows across processes."""
        try:
            from ray_tpu._private.ids import hex_id, new_id
            from ray_tpu.util import tracing

            span = {
                "trace_id": ctx["trace_id"],
                "span_id": hex_id(new_id())[:16],
                "parent_id": ctx.get("span_id"),
                "name": f"lifeline:{kind}",
                "start": t,
                "end": t,
                "kind": "LIFELINE",
                "rid": rid,
            }
            where = ev.get("where")
            if where:
                span["where"] = where
            replica = ev.get("replica")
            if replica:
                span["replica"] = replica
            tracing._record(span, defer_flush=True)
        except Exception:
            pass

    def events(self, rid: str) -> List[dict]:
        with self._lock:
            buf = self._live.get(rid) or self._finished.get(rid)
            return list(buf) if buf else []

    def finish(self, rid: str) -> None:
        """Move a rid to the bounded finished set — it ages out once
        ``_MAX_FINISHED`` newer requests finish after it."""
        with self._lock:
            buf = self._live.pop(rid, None)
            if buf is None:
                return
            self._finished[rid] = buf
            self._finished.move_to_end(rid)
            while len(self._finished) > self._max_finished:
                self._finished.popitem(last=False)

    def live_rids(self) -> List[str]:
        with self._lock:
            return list(self._live)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"live": len(self._live), "finished": len(self._finished)}


# ------------------------------------------------------------ module-level
_store: Optional[LifelineStore] = None
_store_lock = threading.Lock()


def store() -> LifelineStore:
    """The process-wide store (fork-safe)."""
    global _store
    s = _store
    if s is None or s._pid != os.getpid():
        with _store_lock:
            s = _store
            if s is None or s._pid != os.getpid():
                s = _store = LifelineStore()
    return s


def record(rid: str, kind: str, **kw: Any) -> None:
    if not rid:
        return
    store().record(rid, kind, **kw)


def events(rid: str) -> List[dict]:
    return store().events(rid)


def finish(rid: str) -> None:
    store().finish(rid)
