"""Crash-surviving flight recorder — a bounded per-process event ring
in a /dev/shm mmap.

The serve plane's last line of evidence: every engine/plan/lifecycle/
error event lands as one fixed-size record in a file another process
can read AFTER this one is SIGKILLed. The PR-13 health loop does
exactly that — post-mortem, it reads the dead replica's tail and
attaches it to the deployment's ``lifecycle:`` snapshot, so "the
replica died" comes with "and here is what it was doing".

Ring discipline (the PR-6 RingChannel rules, simplified for a
single-writer-process crash log):

- fixed-size 64-byte records, 64-byte header;
- a CUMULATIVE head (total records ever written) in the header plus a
  per-record sequence number — the reader orders by sequence, so a
  torn head write (the writer died mid-update) costs nothing;
- no locks on the write path: slot assignment is one
  ``itertools.count`` bump (GIL-atomic), the record lands with a
  single ``pack_into``. Concurrent writers from different threads hit
  different slots.

The file is named ``ray_tpu_ring_<pid>_flightrec`` ON PURPOSE: the
existing dead-pid /dev/shm sweeps (node teardown + raylet init) match
``ray_tpu_ring_<pid>_*`` and reap it once the process is gone and the
session ends — but during a session a SIGKILLed replica's ring
persists, which is the post-mortem read window.

Knobs: ``RAY_TPU_FLIGHT_RECORDER_EVENTS`` (ring capacity in records,
default 1024) and ``RAY_TPU_FLIGHT_RECORDER=0`` (kill switch — write()
returns before touching any state, benched as the recorder-off arm of
the lifeline A/B).
"""
from __future__ import annotations

import itertools
import mmap
import os
import struct
import time
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------- wire format
_MAGIC = 0x52_54_46_4C_52_45_43_31  # "RTFLREC1"
_HDR = struct.Struct("<QIIQIId")  # magic, rec_size, capacity, head, pid, _, t0
_HDR_SIZE = 64
# t(f64) kind(u16) flags(u16) step(u32) rid(24s) a(f64) b(f64) seq(u32) pad
_REC = struct.Struct("<dHHI24sddI")
_REC_SIZE = 64
assert _HDR.size <= _HDR_SIZE and _REC.size <= _REC_SIZE

# event-kind registry (u16 on the wire). The lifeline layer uses the
# same ids, so one table decodes both the in-memory timeline and a
# post-mortem ring dump. Ids 4, 17 and 20 stay unused: their kinds were
# registered and never written (the engine loop's stages are spans in
# the profiler's trace now, observability.ENGINE_SPANS).
EV = {
    "submit": 1,
    "route": 2,
    "admit": 3,
    "dispatch": 5,
    "first_token": 6,
    "finish": 7,
    "shed": 8,
    "kv_export": 9,
    "kv_put": 10,
    "resume_fetch": 11,
    "kv_import": 12,
    "redispatch": 13,
    "migrate": 14,
    "error": 15,
    "inventory_probe": 16,
    "prefix_import": 18,
    "resume_submit": 19,
}
EV_NAMES = {v: k for k, v in EV.items()}


def _ring_path(pid: int) -> str:
    # the ray_tpu_ring_<pid>_ prefix opts us into the existing dead-pid
    # /dev/shm GC (node.py teardown sweep + raylet._gc_stale_arenas)
    return f"/dev/shm/ray_tpu_ring_{pid}_flightrec"


class FlightRecorder:
    """One per-process ring. Use the module-level :func:`get_recorder`;
    constructing directly is for tests."""

    def __init__(self, capacity: Optional[int] = None, enabled: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get("RAY_TPU_FLIGHT_RECORDER", "1") != "0"
        if capacity is None:
            try:
                capacity = int(os.environ.get("RAY_TPU_FLIGHT_RECORDER_EVENTS", "1024"))
            except ValueError:
                capacity = 1024
        self.capacity = max(32, capacity)
        self.enabled = bool(enabled)
        self.events_written = 0
        self._mm = None
        self._pid = os.getpid()
        self.path = _ring_path(self._pid)
        if not self.enabled:
            return  # kill switch: no file, no mmap, write() is a no-op
        size = _HDR_SIZE + self.capacity * _REC_SIZE
        fd = os.open(self.path, os.O_CREAT | os.O_RDWR, 0o600)
        try:
            os.ftruncate(fd, size)
            self._mm = mmap.mmap(fd, size)
        finally:
            os.close(fd)
        _HDR.pack_into(self._mm, 0, _MAGIC, _REC_SIZE, self.capacity, 0,
                       self._pid, 0, time.time())
        self._seq = itertools.count()

    # ------------------------------------------------------------ hot path
    def write(self, kind: int, rid: bytes = b"", step: int = 0,
              a: float = 0.0, b: float = 0.0, flags: int = 0) -> None:
        """One event → one ring record. Ring write + counter bump ONLY:
        no allocation beyond the GIL-atomic seq bump, no pickle, no RPC
        (lint-pinned, tests/test_lint_lifeline_path.py). ``rid`` must be
        pre-encoded bytes (callers cache it once per request)."""
        mm = self._mm
        if mm is None:
            return
        seq = next(self._seq)
        _REC.pack_into(mm, _HDR_SIZE + (seq % self.capacity) * _REC_SIZE,
                       time.time(), kind, flags, step, rid, a, b, seq)
        struct.pack_into("<Q", mm, 16, seq + 1)  # cumulative head
        self.events_written += 1

    # ---------------------------------------------------------- lifecycle
    def close(self, unlink: bool = False) -> None:
        if self._mm is not None:
            try:
                self._mm.close()
            except Exception:
                pass
            self._mm = None
        if unlink:
            try:
                os.unlink(self.path)
            except OSError:
                pass


# ------------------------------------------------------------- post-mortem
def read_tail(pid: Optional[int] = None, path: Optional[str] = None,
              n: int = 64) -> List[Dict[str, Any]]:
    """Read the last ``n`` events from a (possibly dead) process's ring.

    Orders by the per-record sequence number, so a head torn by a
    mid-write SIGKILL never loses the readable tail. Returns decoded
    dicts (oldest first); [] when the ring is missing/disabled/corrupt.
    """
    if path is None:
        if pid is None:
            raise ValueError("read_tail needs a pid or a path")
        path = _ring_path(pid)
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return []
    if len(raw) < _HDR_SIZE + _REC_SIZE:
        return []
    magic, rec_size, cap, head, wpid, _, t0 = _HDR.unpack_from(raw, 0)
    if magic != _MAGIC or rec_size != _REC_SIZE or cap <= 0:
        return []
    if len(raw) < _HDR_SIZE + cap * _REC_SIZE:
        return []
    recs = []
    for i in range(cap):
        t, kind, flags, step, rid, a, b, seq = _REC.unpack_from(
            raw, _HDR_SIZE + i * _REC_SIZE)
        if t <= 0.0 or kind not in EV_NAMES:
            continue  # never-written or torn slot
        recs.append((seq, t, kind, flags, step, rid, a, b))
    recs.sort()
    out = []
    for seq, t, kind, flags, step, rid, a, b in recs[-n:]:
        out.append({
            "seq": seq,
            "t": t,
            "kind": EV_NAMES.get(kind, str(kind)),
            "flags": flags,
            "step": step,
            "rid": rid.rstrip(b"\x00").decode("ascii", "replace"),
            "a": a,
            "b": b,
            "pid": wpid,
        })
    return out


# ------------------------------------------------------------- per-process
_recorder: Optional[FlightRecorder] = None


def get_recorder() -> FlightRecorder:
    """The process-wide recorder (created lazily; fork-safe — a child
    whose pid differs gets its own ring)."""
    global _recorder
    r = _recorder
    if r is None or r._pid != os.getpid():
        r = _recorder = FlightRecorder()
    return r
