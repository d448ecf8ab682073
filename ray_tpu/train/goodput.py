"""Goodput accounting for preemption-tolerant training.

Goodput = the fraction of wall time that advanced the model. PR-4's
step telemetry already measures inter-step gaps for a HEALTHY loop;
this meter prices the UNHEALTHY part — what a preemption actually
cost, split into the phases the recovery pipeline goes through:

  detect     — dead/hung slice noticed (probe timeout, failed dispatch)
  regang     — membership change: generation bump, survivor re-plan
  restore    — state broadcast (survivor D2H → re-admitted slice H2D)
  recompile  — first-step warmup on the re-admitted slice
  checkpoint_stall — synchronous part of checkpoint saves (D2H snapshot)

The breakdown is what makes the bill actionable: a fat `restore` says
ship Gemini-style peer state transfer, a fat `recompile` says persist
the compilation cache, a fat `detect` says tighten probe timeouts.

`summary()` feeds `/api/training` (via observability.publish_snapshot).
Goodput under injected preemptions on the chip: not measured (no cell
of benchmark/run.py saves, loses a slice or resumes; PERF.md section
7, `pretrain-resume`).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterator, Optional

RECOVERY_PHASES = ("detect", "regang", "restore", "recompile", "checkpoint_stall")


class GoodputMeter:
    """Wall-clock ledger: everything not explicitly booked as lost is
    productive. Thread-safe — slice probes and the checkpoint writer
    report from their own threads."""

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        self._t_start: Optional[float] = None
        self._t_stop: Optional[float] = None
        self._lost: Dict[str, float] = {p: 0.0 for p in RECOVERY_PHASES}
        self._events: int = 0
        self._steps: int = 0
        self._degraded_steps: int = 0

    # ----------------------------------------------------------- running
    def start(self) -> "GoodputMeter":
        with self._lock:
            if self._t_start is None:
                self._t_start = self._clock()
        return self

    def stop(self) -> None:
        with self._lock:
            if self._t_start is not None and self._t_stop is None:
                self._t_stop = self._clock()

    def step_done(self, *, degraded: bool = False) -> None:
        with self._lock:
            self._steps += 1
            if degraded:
                self._degraded_steps += 1

    # -------------------------------------------------------------- lost
    def add_lost(self, phase: str, seconds: float) -> None:
        with self._lock:
            self._lost[phase] = self._lost.get(phase, 0.0) + max(0.0, seconds)

    @contextlib.contextmanager
    def lost(self, phase: str) -> Iterator[None]:
        t0 = self._clock()
        try:
            yield
        finally:
            self.add_lost(phase, self._clock() - t0)

    def recovery_event(self) -> None:
        """One preemption survived (a degrade or a re-admit cycle)."""
        with self._lock:
            self._events += 1

    # ----------------------------------------------------------- summary
    def summary(self) -> Dict[str, Any]:
        with self._lock:
            if self._t_start is None:
                return {"goodput_pct": None, "wall_s": 0.0}
            end = self._t_stop if self._t_stop is not None else self._clock()
            wall = max(end - self._t_start, 1e-9)
            lost = dict(self._lost)
            lost_total = sum(lost.values())
            return {
                "goodput_pct": round(100.0 * max(wall - lost_total, 0.0) / wall, 2),
                "wall_s": round(wall, 4),
                "lost_s": round(lost_total, 4),
                "recovery_breakdown_s": {k: round(v, 4) for k, v in lost.items()},
                "recovery_events": self._events,
                "steps": self._steps,
                "degraded_steps": self._degraded_steps,
            }

    def publish(self) -> Dict[str, Any]:
        """Push the summary into the "training" telemetry snapshot so
        the dashboard's /api/training serves it next to MFU/step-time.
        Best-effort: accounting must never fail training."""
        s = self.summary()
        try:
            from ray_tpu import observability

            observability.publish_snapshot("training", {"elastic": s})
        except Exception:
            pass
        return s
