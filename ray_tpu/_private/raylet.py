"""Raylet — per-node daemon: worker pool, task dispatch, object transfer.

Equivalent of the reference's raylet binary
(reference: src/ray/raylet/main.cc:119 — NodeManager + WorkerPool +
embedded plasma store). Here the node-local shared-memory arena
(shm_store.cc) is created by the raylet at startup (the reference embeds
plasma in the raylet the same way, reference:
src/ray/object_manager/plasma/store_runner.h:14).

Responsibilities:
  - WorkerPool (reference: src/ray/raylet/worker_pool.h:104): prestart,
    on-demand spawn, idle cache, process-exit supervision.
  - Dispatch: receive `raylet.dispatch` from the GCS scheduler, lease a
    worker, push `exec.task`; report finish/failure back.
  - Object transfer: serve chunked reads of local arena objects to other
    raylets and fetch remote objects into the local arena (reference:
    src/ray/object_manager/object_manager.h:130,139 Push/Pull).
  - Heartbeats to the GCS health manager.

Run: `python -m ray_tpu._private.raylet --gcs ... --session-dir ...`
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import logging
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from ray_tpu._private import protocol
from ray_tpu._private.config import RayConfig
from ray_tpu._private.ids import hex_id, new_id
from ray_tpu._private.shm_store import ShmStore

logger = logging.getLogger("ray_tpu.raylet")

CHUNK = 4 * 1024 * 1024


def _gc_stale_arenas():
    """Unlink /dev/shm arenas AND compiled-DAG channels whose owning pid
    is gone (defense against SIGKILLed clusters/drivers; names embed the
    creator pid)."""
    import glob
    import re

    for path in glob.glob("/dev/shm/ray_tpu_*"):
        m = re.match(r".*/ray_tpu_(?:chan_|ring_)?(\d+)_", path)
        if not m:
            continue
        pid = int(m.group(1))
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            try:
                os.unlink(path)
            except OSError:
                pass
        except PermissionError:
            pass


class WorkerHandle:
    def __init__(self, worker_id: str, proc: subprocess.Popen, log_path: Optional[str] = None):
        self.worker_id = worker_id
        self.proc = proc
        self.conn: Optional[protocol.Connection] = None
        self.addr: Optional[str] = None
        self.current_task: Optional[Dict[str, Any]] = None
        self.is_actor = False
        self.actor_id: Optional[str] = None
        self.lease_id: Optional[str] = None  # leased to an owner for direct dispatch
        self.registered = asyncio.Event()
        self.log_path = log_path
        self.log_offset = 0  # bytes already streamed to the driver
        self.idle_since = time.time()
        self.oom_killed = False  # set by the memory monitor before SIGKILL
        self.used = False  # has been handed a task or lease (may have loaded JAX)
        self.tpu_chips: List[int] = []  # chip indices granted; back on process exit


class Raylet:
    def __init__(self, gcs_addr: str, session_dir: str, resources: Dict[str, float],
                 shm_bytes: int, labels: Dict[str, str], node_ip: str = "127.0.0.1",
                 node_name: str = ""):
        self.gcs_addr = gcs_addr
        self.session_dir = session_dir
        self.resources = resources
        self.labels = labels
        self.node_ip = node_ip
        self.node_id: Optional[str] = None
        self.name = node_name or hex_id(new_id())[:8]

        _gc_stale_arenas()
        self.shm_path = f"/dev/shm/ray_tpu_{os.getpid()}_{self.name}"
        ShmStore.create(self.shm_path, shm_bytes)
        self.store = ShmStore(self.shm_path)
        # the arena dies with the raylet (plasma does the same: the store
        # lives inside the raylet process, store_runner.cc)
        import atexit

        atexit.register(self._cleanup)
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, lambda *_: (self._cleanup(), os._exit(0)))

        self.workers: Dict[str, WorkerHandle] = {}
        self.idle: collections.deque = collections.deque()
        self.starting = 0
        self.queued: collections.deque = collections.deque()
        self.max_workers = int(max(resources.get("CPU", 1), 1)) + 64  # actors beyond pool
        # the GCS counts `TPU`; which chips a grant means is decided here
        self._free_chips: List[int] = list(range(int(resources.get("TPU", 0))))

        self._gcs: Optional[protocol.Connection] = None
        self._peer_conns: Dict[str, protocol.Connection] = {}
        self._host_peer_stores: Dict[str, Any] = {}  # same-host arenas (read-mapped)
        self._conn_leases: Dict[protocol.Connection, set] = {}  # owner conn -> lease_ids

    def _cleanup(self):
        for h in list(getattr(self, "workers", {}).values()):
            try:
                h.proc.kill()
            except Exception:
                pass
        try:
            os.unlink(self.shm_path)
        except OSError:
            pass

    # ---------------------------------------------------------------- startup
    async def start(self):
        sock = os.path.join(self.session_dir, f"raylet-{self.name}.sock")
        self._unix_server, _ = await protocol.serve(f"unix:{sock}", self._handle, name="raylet")
        self._tcp_server, tcp_addr = await protocol.serve(f"tcp:0.0.0.0:0", self._handle, name="raylet-tcp")
        self.worker_sock = f"unix:{sock}"
        # advertise a reachable address, not the bind address
        port = tcp_addr.rsplit(":", 1)[1]
        self.addr = tcp_addr = f"tcp:{self.node_ip}:{port}"

        reply = await self._connect_and_register()
        self.node_id = reply["node_id"]
        RayConfig.load_json(reply["config"])
        # drop a discovery file so a colocated driver can find its node
        with open(os.path.join(self.session_dir, f"node-{self.name}.json"), "w") as f:
            import json

            json.dump({"node_id": self.node_id, "shm_path": self.shm_path, "raylet_sock": self.worker_sock,
                       "addr": tcp_addr}, f)
        asyncio.get_running_loop().create_task(self._heartbeat_loop())
        asyncio.get_running_loop().create_task(self._reap_loop())
        asyncio.get_running_loop().create_task(self._spill_loop())
        if RayConfig.log_to_driver:
            asyncio.get_running_loop().create_task(self._log_stream_loop())
        if RayConfig.memory_monitor_refresh_ms > 0:
            asyncio.get_running_loop().create_task(self._memory_monitor_loop())
        self._sync_event = asyncio.Event()
        asyncio.get_running_loop().create_task(self._resource_sync_loop())
        for _ in range(min(RayConfig.worker_pool_prestart, self.max_workers)):
            self._start_worker()
        logger.info("raylet %s node=%s up, %d prestarted", self.name, self.node_id, RayConfig.worker_pool_prestart)

    async def _log_stream_loop(self):
        """Tail every worker's log file and publish appended lines to the
        GCS 'worker_logs' pubsub channel so drivers can print them
        (reference: python/ray/_private/log_monitor.py — a per-node
        process tailing worker logs into GCS pubsub; here the raylet IS
        the per-node process, so the loop lives here)."""
        while True:
            await asyncio.sleep(0.5)
            try:
                batch = []
                for h in list(self.workers.values()):
                    entry = self._drain_worker_log(h)
                    if entry:
                        batch.append(entry)
                if batch and self._gcs is not None:
                    await self._gcs.push(
                        "pub.publish", {"channel": "worker_logs", "data": {"entries": batch}}
                    )
            except Exception:
                logger.exception("log stream iteration failed")

    def _drain_worker_log(self, h, final: bool = False):
        """Read NEW complete lines from one worker's log; returns a pubsub
        entry or None. Only whole lines are consumed (a partial trailing
        line would split a user print across publishes and defeat the
        framework-chatter filter); `final` drains everything including a
        trailing unterminated line (worker death)."""
        if not h.log_path:
            return None
        try:
            size = os.path.getsize(h.log_path)
        except OSError:
            return None
        if size <= h.log_offset:
            return None
        try:
            with open(h.log_path, "rb") as f:
                f.seek(h.log_offset)
                chunk = f.read(min(size - h.log_offset, 256 * 1024))
        except OSError:
            return None
        if not final:
            cut = chunk.rfind(b"\n")
            if cut < 0:
                if len(chunk) < 256 * 1024:
                    return None  # no complete line yet
                # the read window is FULL with no newline: a single line
                # >256 KiB would otherwise stall this worker's streaming
                # forever (offset never advances) — emit it as a partial
                # line so the window moves. Back off to a UTF-8 boundary
                # so a multi-byte char isn't split across publishes.
                while chunk and chunk[-1] & 0xC0 == 0x80:
                    chunk = chunk[:-1]
                if chunk and chunk[-1] >= 0xC0:
                    chunk = chunk[:-1]  # dangling lead byte
            else:
                chunk = chunk[: cut + 1]
        h.log_offset += len(chunk)
        text = chunk.decode("utf-8", "replace")
        # framework chatter (INFO/DEBUG from ray_tpu loggers) stays in
        # the file; user prints + warnings/tracebacks stream
        lines = [
            ln for ln in text.split("\n")
            if ln.strip() and not ln.startswith(("INFO:ray_tpu", "DEBUG:ray_tpu"))
        ]
        if not lines:
            return None
        job = (h.current_task or {}).get("job_id") or getattr(h, "job_id", None)
        return {"worker": h.worker_id[:12], "job": job, "text": "\n".join(lines)}

    # ------------------------------------------------------------- spilling
    @property
    def _spill_dir(self) -> str:
        # inside the session dir: spill files share the session's
        # lifecycle instead of accumulating under a global path
        d = os.path.join(self.session_dir, "spill", self.node_id or "node")
        os.makedirs(d, exist_ok=True)
        return d

    async def _spill_loop(self):
        """Proactive spill-to-disk under arena pressure (reference:
        LocalObjectManager::SpillObjects, local_object_manager.h:110 →
        external storage): once usage crosses the spilling threshold,
        write the coldest evictable objects out and free their arena
        space — the C++ LRU would otherwise DROP them, forcing lineage
        rebuilds. Spilled objects restore on demand. A writer that hits
        FULL kicks `_spill_wakeup` instead of waiting out the period."""
        self._spill_wakeup = asyncio.Event()
        self._spill_force = False
        while True:
            try:
                await asyncio.wait_for(self._spill_wakeup.wait(), timeout=1.0)
            except asyncio.TimeoutError:
                pass
            self._spill_wakeup.clear()
            force, self._spill_force = self._spill_force, False
            try:
                await self._spill_pass(force=force)
            except Exception:
                logger.exception("spill loop iteration failed")

    async def _spill_pass(self, force: bool = False):
        u = self.store.usage()
        cap = u["capacity_bytes"]
        if cap == 0:
            return
        if not force and u["used_bytes"] <= RayConfig.object_spilling_threshold * cap:
            return
        target = int(0.6 * cap)
        used = u["used_bytes"]
        for oid, size in self.store.list_spillable(256):
            if used <= target:
                break
            if await self._spill_one(oid):
                used -= size

    async def _spill_one(self, oid: bytes) -> bool:
        buf = self.store.get(oid, timeout_ms=0)
        if buf is None:
            return False
        path = os.path.join(self._spill_dir, oid.hex())
        try:
            with open(path, "wb") as f:
                f.write(bytes(buf.view))
            size = buf.size
        finally:
            buf.release()
        self.store.delete(oid)
        logger.info("spilled %s (%d bytes) to %s", oid.hex()[:12], size, path)
        await self._gcs.push(
            "obj.spilled", {"oid": oid, "node_id": self.node_id, "path": path, "size": size}
        )
        return True

    async def _restore_spilled(self, data) -> bool:
        """Read a spilled object back into the arena (reference:
        restore-on-demand from external storage)."""
        oid = bytes(data["oid"])
        if self.store.contains(oid):
            return True
        if self.store.undelete(oid):
            # the spilled entry was pending_delete (a pin released late):
            # its bytes never left the arena — resurrect in place and drop
            # the now-orphaned spill file (the GCS pops its spill record
            # on restore success, so nothing else would ever unlink it)
            try:
                os.unlink(data["path"])
            except OSError:
                pass
            return True
        path = data["path"]
        with open(path, "rb") as f:
            blob = f.read()
        # the arena may still be briefly full right after the pressure
        # that caused the spill — owner pin releases land on 0.1s gc
        # cycles, so ride a few of them before failing the restore
        from ray_tpu.exceptions import ObjectStoreFullError

        delay = 0.05
        for attempt in range(6):
            try:
                self.store.put_bytes(oid, blob)
                break
            except ObjectStoreFullError:
                if attempt == 5:
                    raise
                await asyncio.sleep(delay)
                delay = min(delay * 2, 0.5)
            except FileExistsError:
                # raced with a concurrent restore/undelete
                break
        try:
            os.unlink(path)
        except OSError:
            pass
        await self._gcs.push(
            "obj.add_location", {"oid": oid, "node_id": self.node_id, "size": len(blob)}
        )
        return True

    def _mark_sync(self):
        ev = getattr(self, "_sync_event", None)
        if ev is not None:
            ev.set()

    async def _resource_sync_loop(self):
        """Push-based load sync: the moment local state changes (worker
        started/died, queue moved), the new view is pushed to the GCS —
        heartbeats remain only as liveness (reference: ray_syncer bidi
        resource gossip, src/ray/common/ray_syncer/ray_syncer.h,
        replacing polling). Debounced 50ms so a worker-start storm is one
        message."""
        self._sync_last = None
        while True:
            await self._sync_event.wait()
            self._sync_event.clear()
            await asyncio.sleep(0.05)  # coalesce a burst into one push
            snap = {
                "num_workers": len(self.workers),
                "idle": len(self.idle),
                "queued": len(self.queued),
                "store": self.store.usage(),
            }
            if snap == self._sync_last:
                continue
            self._sync_last = snap
            try:
                await self._gcs.push("node.sync", {"node_id": self.node_id, "load": snap})
            except Exception:
                pass  # heartbeat reconnect logic owns GCS failures

    async def _memory_monitor_loop(self):
        """Kill a policy-chosen worker when node memory crosses the
        threshold (reference: MemoryMonitor → worker_killing_policy in the
        raylet; memory_monitor.py for the policy)."""
        from ray_tpu._private.memory_monitor import MemoryMonitor, pick_oom_victim

        monitor = MemoryMonitor()
        period = RayConfig.memory_monitor_refresh_ms / 1000.0
        while True:
            await asyncio.sleep(period)
            try:
                frac = monitor.usage_fraction()
                if frac < RayConfig.memory_usage_threshold:
                    continue
                victim = pick_oom_victim(list(self.workers.values()))
                if victim is None:
                    logger.warning(
                        "memory pressure %.2f above threshold but no retriable-task "
                        "worker to kill", frac,
                    )
                    await asyncio.sleep(1.0)
                    continue
                victim.oom_killed = True
                logger.warning(
                    "memory pressure %.2f: OOM-killing worker %s (task %s)",
                    frac, victim.worker_id[:12],
                    (victim.current_task or {}).get("name", "?"),
                )
                try:
                    victim.proc.kill()
                except ProcessLookupError:
                    pass
                # let the kill land + reap before sampling again
                await asyncio.sleep(max(period, 0.5))
            except Exception:
                logger.exception("memory monitor iteration failed")

    async def _connect_and_register(self):
        self._gcs = await protocol.connect(self.gcs_addr, self._handle_gcs, name="raylet-gcs")
        return await self._gcs.request(
            "register",
            {
                "kind": "raylet",
                "pid": os.getpid(),
                "addr": self.addr,
                "node_ip": self.node_ip,
                # keep our identity across GCS restarts: a persisted GCS
                # replays actor/PG records that reference this node_id
                "node_id": getattr(self, "node_id", None),
                "resources": self.resources,
                "labels": self.labels,
                "shm_path": self.shm_path,
            },
        )

    async def _heartbeat_loop(self):
        while True:
            await asyncio.sleep(RayConfig.health_check_period_s / 2)
            try:
                # liveness only — the load view travels on node.sync
                # pushes, which heartbeat payloads must not clobber
                await self._gcs.request("heartbeat", {"node_id": self.node_id})
            except protocol.ConnectionLost:
                # a restarted GCS listens on the same session socket: keep
                # trying to rejoin instead of dying (reference:
                # gcs_client_reconnection_test.cc — raylets survive GCS
                # restarts when the GCS is persisted)
                logger.warning("GCS connection lost; attempting to rejoin")
                deadline = time.monotonic() + RayConfig.health_check_timeout_s * 2
                while time.monotonic() < deadline:
                    try:
                        await self._connect_and_register()
                        logger.info("rejoined GCS as node %s", self.node_id)
                        # the restarted GCS has a fresh node record: force
                        # a load push even if our snapshot is unchanged
                        self._sync_last = None
                        self._mark_sync()
                        break
                    except (protocol.ConnectionLost, OSError, ConnectionError):
                        await asyncio.sleep(1.0)
                else:
                    logger.error("GCS gone for good; exiting")
                    os._exit(1)

    # ------------------------------------------------------------ worker pool
    def _start_worker(self) -> None:
        worker_id = hex_id(new_id())
        env = dict(os.environ)
        env.update(
            {
                "RAY_TPU_SESSION_DIR": self.session_dir,
                "RAY_TPU_GCS_ADDR": self.gcs_addr,
                "RAY_TPU_RAYLET_SOCK": self.worker_sock,
                "RAY_TPU_NODE_ID": self.node_id or "",
                "RAY_TPU_NODE_IP": self.node_ip,
                "RAY_TPU_SHM_PATH": self.shm_path,
                "RAY_TPU_WORKER_ID": worker_id,
                # a worker sees no chip until a task granted `TPU` lands
                # on it (worker_proc._apply_tpu_grant), whatever platform
                # the machine's environment names; only the explicit pin
                # overrides (reference: CUDA_VISIBLE_DEVICES plumbing in
                # _private/accelerators)
                "JAX_PLATFORMS": env.get("RAY_TPU_WORKER_JAX_PLATFORMS") or "cpu",
            }
        )
        log_path = os.path.join(self.session_dir, "logs", f"worker-{worker_id[:12]}.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        logf = open(log_path, "ab")
        # workers never outlive their raylet: the worker arms
        # PR_SET_PDEATHSIG itself at startup (node.arm_pdeathsig) instead
        # of via preexec_fn — a preexec_fn forces the fork through
        # Python's at-fork handlers, which can deadlock under a
        # multithreaded parent and trips JAX's os.fork() RuntimeWarning.
        # RAY_TPU_DETACHED is dropped: it detaches NODES from the CLI,
        # never workers from their raylet.
        env["RAY_TPU_DIE_WITH_PARENT"] = "1"
        env["RAY_TPU_PARENT_PID"] = str(os.getpid())
        env.pop("RAY_TPU_DETACHED", None)
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "ray_tpu._private.worker_proc"],
            env=env,
            stdout=logf,
            stderr=subprocess.STDOUT,
            start_new_session=True,
            close_fds=True,
        )
        h = WorkerHandle(worker_id, proc, log_path=log_path)
        self.workers[worker_id] = h
        self.starting += 1
        self._mark_sync()

    async def _reap_loop(self):
        """Supervise worker processes (reference: worker_pool.cc exit
        detection feeding NodeManager worker-failure handling)."""
        while True:
            await asyncio.sleep(0.5)
            for worker_id, h in list(self.workers.items()):
                code = h.proc.poll()
                if code is None:
                    continue
                self.workers.pop(worker_id, None)
                # the process is gone, so libtpu has let go of its chips
                self._free_chips = sorted(self._free_chips + h.tpu_chips)
                # final log drain BEFORE the handle disappears: the crash
                # traceback a worker wrote on its way down is exactly what
                # the driver needs to see
                if RayConfig.log_to_driver and self._gcs is not None:
                    entry = self._drain_worker_log(h, final=True)
                    if entry:
                        try:
                            await self._gcs.push(
                                "pub.publish",
                                {"channel": "worker_logs", "data": {"entries": [entry]}},
                            )
                        except Exception:
                            pass
                self._mark_sync()
                if not h.registered.is_set():
                    # died before registering — undo the startup slot
                    self.starting = max(0, self.starting - 1)
                try:
                    self.idle.remove(worker_id)
                except ValueError:
                    pass
                if h.conn and not h.conn.closed:
                    await h.conn.close()
                if h.current_task is not None:
                    spec = h.current_task
                    err = (
                        "worker killed by the memory monitor (node OOM defense)"
                        if h.oom_killed
                        else f"worker died (exit {code})"
                    )
                    await self._gcs.request(
                        "task.failed",
                        {"task_id": spec["task_id"], "error": err, "retriable": True,
                         "oom": h.oom_killed},
                    )
                elif h.is_actor and h.actor_id:
                    await self._gcs.request(
                        "actor.died", {"actor_id": h.actor_id, "reason": f"worker process exited ({code})"}
                    )
                if h.lease_id:
                    # leased worker died: credit the shape back; the owner
                    # notices via its broken conn and re-routes in-flight work
                    await self._gcs.request("lease.done", {"lease_id": h.lease_id})
                self._pump()

    def _pop_idle(self, unused: bool = False) -> Optional[WorkerHandle]:
        """Next live idle worker; `unused` only one that was never handed
        anything, so cannot have loaded JAX yet."""
        for wid in list(self.idle):
            h = self.workers.get(wid)
            if h is None or h.proc.poll() is not None or h.conn is None:
                self.idle.remove(wid)
            elif not (unused and h.used):
                self.idle.remove(wid)
                h.used = True
                return h
        return None

    def _pump(self):
        """Dispatch queued specs onto idle workers; spawn when short."""
        while self.queued:
            n_chips = int((self.queued[0].get("resources") or {}).get("TPU", 0))
            if n_chips > len(self._free_chips):
                # the GCS has the count back but the last holder's
                # process is still exiting; the reap loop pumps again
                return
            worker = self._pop_idle(unused=n_chips > 0)
            if worker is None:
                if self.starting == 0 and len(self.workers) < self.max_workers:
                    self._start_worker()
                return
            spec = self.queued.popleft()
            if n_chips:
                worker.tpu_chips = [self._free_chips.pop(0) for _ in range(n_chips)]
                spec["tpu_chips"] = worker.tpu_chips
            asyncio.get_running_loop().create_task(self._run_on_worker(worker, spec))

    async def _run_on_worker(self, h: WorkerHandle, spec: Dict[str, Any]):
        spec["_dispatched_at"] = time.monotonic()  # OOM policy: newest-first
        h.current_task = spec
        if spec.get("job_id"):
            h.job_id = spec["job_id"]  # log-stream attribution outlives the task
        try:
            await self._gcs.request("task.worker_assigned", {"task_id": spec["task_id"], "worker_id": h.worker_id})
            reply = await h.conn.request("exec.task", {"spec": spec})
        except protocol.ConnectionLost:
            return  # reap loop reports the failure
        except Exception as e:
            h.current_task = None
            await self._gcs.request(
                "task.failed", {"task_id": spec["task_id"], "error": f"dispatch error: {e}", "retriable": True}
            )
            self._return_worker(h)
            return
        h.current_task = None
        if spec.get("actor_creation"):
            if reply.get("ok"):
                h.is_actor = True
                h.actor_id = spec["actor_id"]
                await self._gcs.request(
                    "actor.ready",
                    {
                        "actor_id": spec["actor_id"],
                        "task_id": spec["task_id"],
                        "worker_id": h.worker_id,
                        "addr": reply["addr"],
                        "node_id": self.node_id,
                    },
                )
            else:
                await self._gcs.request(
                    "task.failed",
                    {"task_id": spec["task_id"], "error": reply.get("error", "actor init failed"), "retriable": False},
                )
                self._return_worker(h)
        else:
            await self._gcs.request("task.finished", {"task_id": spec["task_id"], "worker_id": h.worker_id})
            self._return_worker(h)

    def _return_worker(self, h: WorkerHandle):
        if h.tpu_chips:
            # it holds its chips until it exits and cannot be re-pointed
            # at others: one grant per process, the reap loop frees them
            try:
                h.proc.kill()
            except ProcessLookupError:
                pass
        elif h.worker_id in self.workers and not h.is_actor:
            h.idle_since = time.time()
            self.idle.append(h.worker_id)
        self._pump()
        self._mark_sync()  # queue drained / worker freed: refresh the view

    # ----------------------------------------------------------- GCS handlers
    async def _handle_gcs(self, method: str, data, conn):
        if method == "raylet.dispatch":
            self.queued.append(data["spec"])
            self._pump()
            self._mark_sync()
            return True
        if method == "raylet.kill_worker":
            h = self.workers.get(data["worker_id"])
            if h is not None:
                try:
                    h.proc.send_signal(signal.SIGKILL if data.get("force") else signal.SIGTERM)
                except ProcessLookupError:
                    pass
            return True
        if method == "raylet.cancel":
            for spec in self.queued:
                if spec["task_id"] == data["task_id"]:
                    spec["cancelled"] = True
            # forward to the executing worker if any
            for h in self.workers.values():
                if h.current_task and h.current_task["task_id"] == data["task_id"] and h.conn:
                    await h.conn.push("exec.cancel", {"task_id": data["task_id"]})
            return True
        if method == "raylet.fetch":
            return await self._fetch(data)
        if method == "raylet.restore_spilled":
            return await self._restore_spilled(data)
        if method == "raylet.spill_hint":
            # a writer hit FULL: wake the spill loop NOW with the force
            # flag — even if usage is below the proactive threshold,
            # everything left may be pinned. (One loop, not an ad-hoc
            # task: concurrent passes would double-spill candidates.)
            self._spill_force = True
            ev = getattr(self, "_spill_wakeup", None)
            if ev is not None:
                ev.set()
            return True
        if method == "raylet.unlink_spilled":
            try:
                os.unlink(data["path"])
            except OSError:
                pass
            return True
        if method == "raylet.delete_objects":
            for oid in data["oids"]:
                self.store.delete(bytes(oid))
            return True
        if method == "raylet.prestart":
            for _ in range(data.get("n", 1)):
                if len(self.workers) < self.max_workers:
                    self._start_worker()
            return True
        raise ValueError(f"unknown raylet method {method}")

    # -------------------------------------------- worker + peer-raylet server
    async def _handle(self, method: str, data, conn):
        if method == "worker.register":
            h = self.workers.get(data["worker_id"])
            if h is None:
                raise ValueError("unknown worker")
            h.conn = conn
            h.addr = data["addr"]
            self.starting = max(0, self.starting - 1)
            h.registered.set()
            self.idle.append(h.worker_id)
            self._pump()
            return {"node_id": self.node_id}
        if method == "lease.request":
            return await self._lease_request(data, conn)
        if method == "lease.release":
            return await self._lease_release(data, conn)
        if method == "fetch.meta":
            oid = bytes(data["oid"])
            buf = self.store.get(oid, timeout_ms=0)
            if buf is None:
                return {"found": False}
            size = len(buf)
            buf.release()
            # shm_path lets a same-host puller map this arena directly
            # and memcpy (multi-raylet-per-host topologies: tests, bench,
            # TPU hosts running several raylets)
            return {"found": True, "size": size, "shm_path": self.shm_path}
        if method == "fetch.read":
            oid = bytes(data["oid"])
            buf = self.store.get(oid, timeout_ms=0)
            if buf is None:
                raise KeyError("object gone")
            try:
                off, ln = data["off"], data["len"]
                return bytes(buf.view[off : off + ln])
            finally:
                buf.release()
        raise ValueError(f"unknown method {method}")

    # ------------------------------------------------------- worker leases
    async def _lease_request(self, data, conn) -> Dict[str, Any]:
        """Grant a worker lease for owner-side direct dispatch (reference:
        raylet lease grants consumed by direct_task_transport.cc:121-135 —
        the owner then pushes tasks straight to the leased worker and the
        scheduler never sees them). Leases are tied to the requesting
        connection: if the owner dies, its leased workers are reclaimed."""
        # install the reclaim hook BEFORE any await: if the owner dies while
        # we wait for an idle worker below, teardown must find it installed
        # or granted leases would leak the worker + GCS-deducted resources
        if conn.on_close is None:
            conn.on_close = self._on_owner_conn_close
        admit = await self._gcs.request(
            "lease.admit", {"node_id": self.node_id, "resources": data.get("resources") or {}}
        )
        if not admit.get("ok"):
            return {"ok": False, "reason": admit.get("reason", "denied")}
        lease_id = admit["lease_id"]
        deadline = time.monotonic() + 10.0
        while True:
            if conn.closed:
                await self._gcs.request("lease.done", {"lease_id": lease_id})
                return {"ok": False, "reason": "owner connection closed"}
            worker = self._pop_idle()
            if worker is not None:
                worker.lease_id = lease_id
                self._conn_leases.setdefault(conn, set()).add(lease_id)
                if conn.closed:
                    # teardown may have raced the grant; reclaim ourselves
                    # (lease.done is idempotent on the GCS side)
                    worker.lease_id = None
                    self._conn_leases.get(conn, set()).discard(lease_id)
                    self._return_worker(worker)
                    await self._gcs.request("lease.done", {"lease_id": lease_id})
                    return {"ok": False, "reason": "owner connection closed"}
                return {"ok": True, "lease_id": lease_id, "worker_id": worker.worker_id, "addr": worker.addr}
            if time.monotonic() > deadline:
                await self._gcs.request("lease.done", {"lease_id": lease_id})
                return {"ok": False, "reason": "no worker available"}
            if self.starting == 0 and len(self.workers) < self.max_workers:
                self._start_worker()
            await asyncio.sleep(0.02)

    async def _lease_release(self, data, conn=None) -> bool:
        lease_id = data["lease_id"]
        if conn is not None and conn in self._conn_leases:
            self._conn_leases[conn].discard(lease_id)
        for h in self.workers.values():
            if h.lease_id == lease_id:
                h.lease_id = None
                self._return_worker(h)
                break
        await self._gcs.request("lease.done", {"lease_id": lease_id})
        return True

    async def _on_owner_conn_close(self, conn):
        """Owner died holding leases: kill its leased workers (they may be
        mid-task for the dead owner) and credit the resources back."""
        for lease_id in self._conn_leases.pop(conn, set()):
            for h in list(self.workers.values()):
                if h.lease_id == lease_id:
                    h.lease_id = None
                    try:
                        h.proc.kill()
                    except Exception:
                        pass
            await self._gcs.request("lease.done", {"lease_id": lease_id})

    async def _fetch(self, data) -> bool:
        """Pull an object from a remote raylet into the local arena in
        chunks (reference: PullManager + chunked object transfer,
        src/ray/object_manager/object_manager.h:139)."""
        oid = bytes(data["oid"])
        if self.store.contains(oid):
            return True
        addr = data["from_addr"]
        conn = self._peer_conns.get(addr)
        if conn is None or conn.closed:
            conn = await protocol.connect(addr, self._handle, name="raylet-peer")
            self._peer_conns[addr] = conn
        meta = await conn.request("fetch.meta", {"oid": oid})
        if not meta["found"]:
            raise KeyError(f"object {oid.hex()} not at source")
        size = meta["size"]
        try:
            buf = self.store.create_buffer(oid, size)
        except FileExistsError:
            # present — or pending_delete (invisible to readers but still
            # blocking create): resurrect the intact bytes in that case
            if not self.store.contains(oid):
                self.store.undelete(oid)
            return True
        try:
            if await self._fetch_same_host(oid, meta, buf):
                pass
            else:
                await self._fetch_chunks(conn, oid, size, buf)
        except Exception:
            self.store.abort(oid)
            raise
        finally:
            buf.release()
        self.store.seal(oid)
        return True

    async def _fetch_same_host(self, oid: bytes, meta, buf) -> bool:
        """Same-host fast path: the source arena is a /dev/shm file this
        process can map — ONE memcpy at DRAM speed instead of a chunked
        socket round trip (source pinned via its refcount for the copy)."""
        src_path = meta.get("shm_path")
        if not src_path or src_path == self.shm_path:
            return False
        if not os.path.exists(src_path):
            # peer died and its arena was unlinked: DROP any cached
            # mapping (an open mmap pins the dead arena's tmpfs pages)
            dead = self._host_peer_stores.pop(src_path, None)
            if dead is not None:
                try:
                    dead.close()
                except Exception:
                    pass
            return False
        from ray_tpu._private.shm_store import ShmStore

        try:
            store = self._host_peer_stores.get(src_path)
            if store is None:
                # bounded cache: mapping a peer arena costs address space
                # and pins its pages — keep at most 8, dropping the OLDEST
                # insertion (dict.popitem() would drop the newest)
                while len(self._host_peer_stores) >= 8:
                    oldest = next(iter(self._host_peer_stores))
                    old = self._host_peer_stores.pop(oldest)
                    try:
                        old.close()
                    except Exception:
                        pass
                store = self._host_peer_stores[src_path] = ShmStore(src_path)
            src = store.get(oid, timeout_ms=0)
            if src is None:
                return False
            loop = asyncio.get_running_loop()

            def _copy():
                buf[: len(src.view)] = src.view

            try:
                # off-loop: a large memcpy must not stall heartbeats
                await loop.run_in_executor(None, _copy)
            finally:
                src.release()
            return True
        except Exception:
            logger.debug("same-host arena fetch failed; falling back", exc_info=True)
            return False

    async def _fetch_chunks(self, conn, oid: bytes, size: int, buf) -> None:
        """Remote pull, PIPELINED: a window of chunk requests stays in
        flight so wire/loop latency overlaps with arena writes (the
        serial request-per-chunk loop was latency-bound)."""
        window = 4
        futs = collections.deque()
        off = 0
        received = 0
        while received < size:
            while off < size and len(futs) < window:
                n = min(CHUNK, size - off)
                futs.append((off, n, await conn.request_send(
                    "fetch.read", {"oid": oid, "off": off, "len": n})))
                off += n
            coff, n, fut = futs.popleft()
            chunk = await fut
            if not chunk:
                raise OSError(f"empty fetch.read reply for {oid.hex()} at {coff}")
            buf[coff : coff + len(chunk)] = chunk
            received += len(chunk)
            if len(chunk) < n:
                # short reply: refetch the remainder at the corrected
                # offset (defensive — the server sends full slices today,
                # but sealing with an unwritten hole is silent corruption)
                futs.appendleft((coff + len(chunk), n - len(chunk), await conn.request_send(
                    "fetch.read", {"oid": oid, "off": coff + len(chunk), "len": n - len(chunk)})))


async def _amain(args):
    logging.basicConfig(level=logging.INFO)
    import json

    resources = json.loads(args.resources)
    labels = json.loads(args.labels)
    raylet = Raylet(
        gcs_addr=args.gcs,
        session_dir=args.session_dir,
        resources=resources,
        shm_bytes=args.shm_bytes,
        labels=labels,
        node_name=args.name,
    )
    await raylet.start()
    print("RAYLET_READY " + raylet.node_id, flush=True)
    await asyncio.Event().wait()


def main():
    from ray_tpu._private.node import arm_pdeathsig

    arm_pdeathsig()  # die with the spawning driver (see node.py)
    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs", required=True)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--resources", default='{"CPU": 1}')
    parser.add_argument("--labels", default="{}")
    parser.add_argument("--shm-bytes", type=int, default=RayConfig.object_store_memory_bytes)
    parser.add_argument("--name", default="")
    args = parser.parse_args()
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()
