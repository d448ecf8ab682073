"""Executor worker process.

Equivalent of the reference's default_worker.py + the C++ task execution
loop (reference: python/ray/_private/workers/default_worker.py and
core_worker_process.h:100 RunTaskExecutionLoop; the Python execution
callback is _raylet.pyx:2177 task_execution_handler).

One worker executes one normal task at a time, or hosts one actor
instance for its lifetime (actor workers serve `call.actor` directly —
the reference's direct actor transport). Actor calls from a given caller
run in submission order (reference:
src/ray/core_worker/transport/actor_scheduling_queue.cc); async actors
interleave up to max_concurrency like the reference's asyncio actors.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import ctypes
import inspect
import logging
import os
import sys
import threading
import traceback
from typing import Any, Dict, Optional

from ray_tpu import exceptions
from ray_tpu._private import protocol, serialization
from ray_tpu._private.config import RayConfig
from ray_tpu._private.core_worker import CoreWorker, _env_err, _env_inline
from ray_tpu._private.runtime_env import ensure_job_env, env_overlay

logger = logging.getLogger("ray_tpu.worker")


import contextlib

_NULL_OVERLAY = contextlib.nullcontext()


def _cancelled_envs(spec):
    """One TaskCancelledError envelope per return oid of `spec`."""
    name = spec.get("name", "")
    err = _env_err(exceptions.TaskCancelledError(name), name)
    err["t"] = "TaskCancelledError"
    return [err] * len(spec["returns"])


def _apply_tpu_grant(chips) -> None:
    """Make this process see exactly the chips its task was granted, and
    nothing but the TPU backend. Runs before the task body or actor
    constructor is even unpickled, so before anything here can
    `import jax`: libtpu and JAX read the environment once, when they
    load. An explicit RAY_TPU_WORKER_JAX_PLATFORMS pin keeps winning —
    the test suites schedule fake TPU counts on CPU nodes."""
    from ray_tpu._private.accelerator_detect import tpu_device_nodes
    from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager, compile_cache_dir

    TPUAcceleratorManager.set_visible_accelerator_ids([str(c) for c in chips])
    if os.environ.get("RAY_TPU_WORKER_JAX_PLATFORMS"):
        return
    if "jax" in sys.modules:
        raise exceptions.TPUGrantError(
            f"chips {chips} were granted to worker pid {os.getpid()} after it had "
            "loaded jax; its backend can no longer be chosen")
    nodes = tpu_device_nodes()
    if max(chips) >= len(nodes):
        raise exceptions.TPUGrantError(
            f"chips {chips} were granted but this host exposes {len(nodes)} TPU "
            f"device node(s) {nodes} (looked for /dev/accel* and /dev/vfio/<n>)")
    # pinned to the one platform, JAX raises at start-up if the chip
    # does not come up; it never falls back to the CPU
    os.environ["JAX_PLATFORMS"] = "tpu"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()


async def _traced_coro(span_cm, fn, args, kwargs):
    """Run an async-actor method under its tracing span: the span
    contextvar is set inside THIS coroutine's context, so it stays active
    across awaits and nested submissions parent correctly."""
    with span_cm:
        return await fn(*args, **kwargs)


class Executor:
    def __init__(self, core: CoreWorker):
        self.core = core
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.pool = concurrent.futures.ThreadPoolExecutor(max_workers=1, thread_name_prefix="exec")
        self.actor_instance = None
        self.actor_is_async = False
        self.actor_max_concurrency = 1
        self.actor_semaphore: Optional[asyncio.Semaphore] = None
        # user coroutines run on their OWN loop thread, never on the
        # CoreWorker IO loop: a blocking core API call (get/put/actor
        # create...) inside an async method would otherwise self-deadlock
        # — _call schedules onto the very loop the coroutine is holding
        # (reference analogue: async actors get a dedicated asyncio loop
        # separate from the C++ core, python/ray/_private/async_compat.py)
        self._user_loop: Optional[asyncio.AbstractEventLoop] = None
        self.actor_id: Optional[str] = None
        # direct (shm-ring) transport endpoints serving this actor, one
        # per connected caller (experimental/direct_transport.py)
        self.direct_servers: list = []
        # serial actors (sync, max_concurrency=1) must stay mutually
        # exclusive between the RPC pool thread and direct service
        # threads — both execution paths take this lock
        self._serial_lock = threading.Lock()
        self._serial_exec = False
        # per-caller ordering state
        self._order: Dict[str, Dict[str, Any]] = {}
        self._current_task_id: Optional[str] = None
        self._current_thread_ident: Optional[int] = None
        self._cancelled: set = set()
        self._coro_cache: Dict[str, bool] = {}  # method/fn_id -> iscoroutinefunction
        self._exec_prof = None
        if os.environ.get("RAY_TPU_PROFILE_DIR") and os.environ.get("RAY_TPU_PROFILE_WHAT") == "exec":
            import cProfile

            self._exec_prof = cProfile.Profile()

    # ------------------------------------------------------------- execution
    async def execute_task(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        """Normal task or actor-creation task pushed by the raylet."""
        if spec.get("cancelled") or spec["task_id"] in self._cancelled:
            await self._send_error(spec, exceptions.TaskCancelledError(spec.get("name", "")))
            return {"ok": True}
        if spec.get("tpu_chips"):
            try:
                _apply_tpu_grant(spec["tpu_chips"])
            except exceptions.TPUGrantError as e:
                logger.error("TPU grant refused: %s", e)
                if spec.get("actor_creation"):
                    return {"ok": False, "error": f"TPUGrantError: {e}"}
                await self._send_error(spec, e)
                return {"ok": True}
        if spec.get("actor_creation"):
            return await self._create_actor(spec)
        envs = await self._run_user_function(spec)
        await self._push_results(spec, envs)
        return {"ok": True}

    async def _create_actor(self, spec) -> Dict[str, Any]:
        try:
            def _construct():
                from ray_tpu._private.runtime_env import ensure_job_env, env_overlay

                job_env = ensure_job_env(self.core, self.core.session_dir, spec.get("job_id"))
                cls = self.core.load_function(spec["fn_id"])
                args, kwargs = self.core.unpack_args(spec.get("args"))
                # an actor worker is bound to its job for life: its env
                # may apply permanently (constructors often capture cwd)
                env_overlay(
                    job_env.get("env_vars"), cwd=job_env.get("cwd"),
                    sys_path=job_env.get("extra_sys_path"),
                ).__enter__()
                return cls(*args, **kwargs)

            instance = await asyncio.get_running_loop().run_in_executor(self.pool, _construct)
        except Exception as e:
            logger.exception("actor creation failed")
            return {"ok": False, "error": f"{type(e).__name__}: {e}\n{traceback.format_exc()}"}
        self.actor_instance = instance
        self.actor_id = spec["actor_id"]
        methods = [m for _, m in inspect.getmembers(type(instance), predicate=inspect.isfunction)]
        self.actor_is_async = any(inspect.iscoroutinefunction(m) for m in methods)
        max_conc = spec.get("max_concurrency") or (1000 if self.actor_is_async else 1)
        if not self.actor_is_async and max_conc > 1:
            self.pool = concurrent.futures.ThreadPoolExecutor(max_workers=max_conc, thread_name_prefix="actor")
        self.actor_max_concurrency = max_conc
        self.actor_semaphore = asyncio.Semaphore(max_conc)
        self._serial_exec = not self.actor_is_async and max_conc == 1
        return {"ok": True, "addr": self.core._listen_addr}

    async def handle_direct_task(self, data) -> Dict[str, Any]:
        """Normal task pushed directly by a lease-holding owner; results
        travel back in the reply (no raylet, no GCS on this path)."""
        spec = data["spec"]
        if spec.get("cancelled") or spec["task_id"] in self._cancelled:
            return {"o": spec["returns"], "e": _cancelled_envs(spec)}
        import time as _time

        t0 = _time.time()
        envs = await self._run_user_function(spec)
        # timings feed the owner's adaptive pipeline-depth classifier —
        # the single-spec path must report them like the batch path does
        return {"o": spec["returns"], "e": envs,
                "timings": {spec["task_id"]: (t0, _time.time())}}

    async def handle_direct_tasks(self, data, conn=None) -> Dict[str, Any]:
        """Batch of direct tasks from one lease drain: one executor hop
        runs them all sequentially (normal tasks are always sync here)."""
        oids, out_envs = [], []
        runnable = []
        for spec in data["specs"]:
            if spec.get("cancelled") or spec["task_id"] in self._cancelled:
                oids.extend(spec["returns"])
                out_envs.extend(_cancelled_envs(spec))
            else:
                runnable.append(spec)
        timings = {}
        if runnable:
            loop = asyncio.get_running_loop()
            env_lists, timings = await loop.run_in_executor(
                self.pool, self._exec_sync_batch, runnable, False, loop, conn
            )
            for spec, envs in zip(runnable, env_lists):
                oids.extend(spec["returns"])
                out_envs.extend(envs)
        # real execution windows so the owner can report honest timeline
        # events for the direct path
        return {"o": oids, "e": out_envs, "timings": timings}

    async def handle_actor_call(self, data, conn) -> Dict[str, Any]:
        """Direct actor invocation. Calls from one caller arrive in
        submission order on a single connection; the FIFO semaphore
        preserves that as execution start order (reference:
        actor_scheduling_queue.cc — ordering by sequence numbers there,
        by stream order here)."""
        spec = data["spec"]
        async with self.actor_semaphore:
            envs = await self._run_user_function(spec, actor=True)
        return {"o": spec["returns"], "e": envs}

    async def handle_actor_calls(self, data, conn) -> Dict[str, Any]:
        """Batched pipelined calls from one caller. A strictly-serial sync
        actor (max_concurrency=1) executes the whole batch in ONE executor
        hop — same serial semantics, 1/N the loop⇄thread round trips.
        Concurrent actors (async or threaded) interleave per spec through
        the semaphore, FIFO order preserved (gather creates tasks in list
        order). One reply carries every result."""
        specs = data["specs"]
        if self.actor_instance is not None and not self.actor_is_async and self.actor_max_concurrency == 1:
            loop = asyncio.get_running_loop()
            async with self.actor_semaphore:
                env_lists, _ = await loop.run_in_executor(
                    self.pool, self._exec_sync_batch, specs, True, loop, conn
                )
            return {
                "o": [oid for s in specs for oid in s["returns"]],
                "e": [env for envs in env_lists for env in envs],
            }
        replies = await asyncio.gather(
            *(self.handle_actor_call({"spec": spec}, conn) for spec in specs)
        )
        return {
            "o": [oid for r in replies for oid in r["o"]],
            "e": [env for r in replies for env in r["e"]],
        }

    def exec_direct(self, spec: Dict[str, Any]):
        """Execute one direct-transport call on the CALLING thread (the
        ring service thread, or a pool thread for reclassified-slow
        methods) and return result envelopes. Reuses the full sync
        execution path — overlays, tracing spans, error conversion,
        serial-actor locking — then registers retained borrows before
        the reply ships (the same contract the RPC reply path keeps).
        Not a cancel target (cancellable=False): cancel() routes over
        RPC and must keep aiming at the pool thread's current task."""
        envs = self._exec_sync_one(spec, True, self.loop, cancellable=False)
        if self.core._ref_events or self.core._borrows_to_flush:
            self.core.flush_borrows_sync()
        return envs

    def _ensure_user_loop(self) -> asyncio.AbstractEventLoop:
        if self._user_loop is None:
            self._user_loop = asyncio.new_event_loop()
            t = threading.Thread(target=self._user_loop.run_forever, daemon=True, name="actor-async")
            t.start()
        return self._user_loop

    async def _push_early(self, conn, results):
        try:
            await conn.push("task.result", {"results": results})
        except Exception:
            pass  # reply-path delivery still covers these results

    def _exec_sync_batch(self, specs, actor: bool, loop, conn=None):
        """Thread-side batch runner. cancel()'s PyThreadState_SetAsyncExc
        KeyboardInterrupt is asynchronous: it can land BETWEEN specs
        (outside any try), which must not fail the remaining tasks — the
        interrupt's target already returned, so swallow it and keep
        going.

        Each spec's results are STAGED into this worker's local object
        cache as they complete: a later task in the batch may block on a
        `get` of an earlier result (e.g. a ref captured in its closure),
        and the batch reply that would deliver it to the owner only ships
        after the whole batch — without staging that is a deadlock. The
        stage is dropped once the batch returns (the owner serves
        resolves from then on)."""
        import time as _time

        out = []
        staged = []
        timings = {}  # LOCAL: concurrent batch handlers must not share
        if self._exec_prof is not None:
            self._exec_prof.enable()
        try:
            last = len(specs) - 1
            unsent = []  # results finished but not yet streamed to the owner
            for i, spec in enumerate(specs):
                appended = False
                t0 = _time.time()
                try:
                    envs = self._exec_sync_one(spec, actor, loop)
                    out.append(envs)
                    appended = True
                    t1 = _time.time()
                    timings[spec.get("task_id") or spec["returns"][0]] = (t0, t1)
                    for oid, env in zip(spec["returns"], envs):
                        self.core._deliver(bytes(oid), env)
                        staged.append(bytes(oid))
                    # (returns, envs) pairs, NOT per-result dicts — the
                    # wire dicts are only built if a slow spec actually
                    # triggers an early push (never on the fast path)
                    unsent.append((spec["returns"], envs))
                    if conn is not None and i < last and t1 - t0 > 0.002:
                        # SLOW spec in a batch: stream EVERYTHING finished
                        # so far (this spec AND any fast predecessors still
                        # unsent) to the owner NOW instead of holding it
                        # hostage to the rest of the batch — head-of-line
                        # blocking would break wait()/pipelining semantics:
                        # a 5s task must not delay an already-finished 10ms
                        # task's result. The batch reply re-delivers them
                        # later, an idempotent no-op. Fast bursts (the
                        # fan-out hot path) never hit this branch.
                        pending, unsent = unsent, []
                        results = [
                            {"oid": oid, "env": env}
                            for rets, es in pending
                            for oid, env in zip(rets, es)
                        ]
                        loop.call_soon_threadsafe(
                            lambda r=results: loop.create_task(
                                self._push_early(conn, r)
                            )
                        )
                except KeyboardInterrupt:
                    # the interrupt's target already returned (its own try
                    # converts an in-task KI); landing here means it hit
                    # between specs or during staging — don't fail the
                    # rest of the batch
                    if not appended:
                        out.append(_cancelled_envs(spec))
            # BEFORE the reply ships: register any borrows this batch's
            # tasks retained (refs unpickled from args and stored). The
            # caller's arg pin is still held until it processes our reply,
            # so the directory learns of the borrow strictly before the
            # owner could release (reference: borrows ride the task
            # reply). Cheap guard keeps ref-free fan-out batches at zero
            # extra work.
            if self.core._ref_events or self.core._borrows_to_flush:
                self.core.flush_borrows_sync()
            return out, timings
        finally:
            if self._exec_prof is not None:
                self._exec_prof.disable()
                self._exec_batches = getattr(self, "_exec_batches", 0) + 1
                if self._exec_batches % 50 == 0:  # dumping per batch would swamp the run
                    self._exec_prof.dump_stats(
                        os.environ["RAY_TPU_PROFILE_DIR"] + f"/exec-{os.getpid()}.prof"
                    )
            while staged:
                try:
                    self.core._store.pop(staged.pop(), None)
                except KeyboardInterrupt:
                    continue

    def _exec_sync_one(self, spec, actor: bool, loop, cancellable: bool = True):
        """Thread-side: execute ONE spec fully — unpack → invoke →
        serialize → error conversion. Runs on a pool thread so pipelined
        batches can share a single loop⇄thread round trip."""
        name = spec.get("name") or spec.get("method", "?")
        # actor-call specs are slim (no task_id): the first return oid is
        # the call's identity for cancel bookkeeping and batch timings
        tid = spec.get("task_id") or spec["returns"][0]
        try:
            # the task that owns the pool thread is the one cancel() can
            # interrupt, so both fields are set HERE, on that thread.
            # Direct-transport threads run this concurrently with the
            # pool thread and are NOT cancel targets (cancel routes over
            # RPC) — they must not clobber the pool task's identity
            if cancellable:
                self._current_thread_ident = threading.get_ident()
                self._current_task_id = tid
            try:
                if tid in self._cancelled:
                    raise exceptions.TaskCancelledError(spec.get("name", ""))

                # job runtime_env: packages materialize once (lazily at
                # the job's first task — prestarted workers boot before
                # the publish); env_vars and working_dir overlay around
                # THIS execution only, since pooled workers serve many
                # jobs and nothing may leak across them. Actor workers are
                # bound to their job at CREATION (env applied permanently,
                # _create_actor) — per-call re-overlay would be redundant.
                job_env = (
                    {} if actor
                    else ensure_job_env(self.core, self.core.session_dir, spec.get("job_id"))
                )
                if actor:
                    if spec["method"] == "__ray_tpu_channel_loop__":
                        # compiled-DAG resident loop (experimental/
                        # compiled_dag.py): a framework method that runs
                        # ON the actor instance without the class
                        # declaring it (reference: compiled DAG installing
                        # do_exec_tasks on participating actors)
                        import functools

                        from ray_tpu.experimental.compiled_dag import run_channel_loop

                        fn = functools.partial(run_channel_loop, self.actor_instance)
                    elif spec["method"] == "__ray_tpu_direct_connect__":
                        # direct-transport negotiation (experimental/
                        # direct_transport.py): open the caller's rings
                        # and start the resident service thread — same
                        # framework-method interception as the DAG loop
                        import functools

                        from ray_tpu.experimental.direct_transport import accept_connect

                        fn = functools.partial(accept_connect, self)
                    else:
                        fn = getattr(self.actor_instance, spec["method"])
                else:
                    fn = self.core.load_function(spec["fn_id"])
                args, kwargs = self.core.unpack_args(spec.get("args"))
                merged_env = {**job_env.get("env_vars", {}),
                              **((spec.get("runtime_env") or {}).get("env_vars") or {})}

                extra_path = job_env.get("extra_sys_path")
                overlay = (
                    env_overlay(merged_env, cwd=job_env.get("cwd"), sys_path=extra_path)
                    if merged_env or job_env.get("cwd") or extra_path
                    else _NULL_OVERLAY  # hot path: nothing to apply/restore
                )
                fn_key = spec.get("method") if actor else spec["fn_id"]
                is_coro = self._coro_cache.get(fn_key)
                if is_coro is None:
                    is_coro = self._coro_cache[fn_key] = inspect.iscoroutinefunction(fn)
                if spec.get("trace"):
                    from ray_tpu.util import tracing as _tracing

                    span_cm = _tracing.execution_span(spec["trace"], name)
                else:
                    span_cm = contextlib.nullcontext()
                with overlay, span_cm:
                    if is_coro:
                        import asyncio as _a

                        # run on the user loop, not the CoreWorker loop: the
                        # coroutine may call blocking core APIs
                        result = _a.run_coroutine_threadsafe(
                            fn(*args, **kwargs), self._ensure_user_loop()
                        ).result()
                    elif actor and self._serial_exec:
                        # serial actor: direct-transport service threads
                        # execute user code too, so the single pool
                        # thread alone no longer implies serial — both
                        # paths take this (uncontended-cheap) lock
                        with self._serial_lock:
                            result = fn(*args, **kwargs)
                    else:
                        result = fn(*args, **kwargs)
                values = self._split_returns(spec, result)
                if values is None:
                    return [self._bad_arity_env(spec, name)] * len(spec["returns"])
                return [self._to_env_sync(oid, v) for oid, v in zip(spec["returns"], values)]
            finally:
                if cancellable:
                    self._current_thread_ident = None
                    self._current_task_id = None
        except (Exception, KeyboardInterrupt) as e:
            # KeyboardInterrupt is how cancel() interrupts the user thread
            # (PyThreadState_SetAsyncExc) — it is a BaseException, so a bare
            # `except Exception` would let it escape as a handler error and
            # the owner would retry a cancelled task instead of seeing
            # TaskCancelledError.
            tb = traceback.format_exc()
            logger.info("task %s failed: %s", name, tb)
            if isinstance(e, (KeyboardInterrupt,)) or tid in self._cancelled:
                return _cancelled_envs(spec)
            return [_env_err(e, name)] * len(spec["returns"])

    async def _run_user_function(self, spec, actor: bool = False):
        name = spec.get("name") or spec.get("method", "?")
        loop = asyncio.get_running_loop()
        is_async = actor and self.actor_is_async and inspect.iscoroutinefunction(
            getattr(type(self.actor_instance), spec["method"], None)
        )
        if not is_async:
            # sync path: ONE executor hop covering unpack → invoke →
            # serialize (each hop is a loop⇄thread round trip; the 1:1
            # sync actor-call benchmark lives and dies on these)
            envs = await loop.run_in_executor(self.pool, self._exec_sync_one, spec, actor, loop)
            if self.core._ref_events or self.core._borrows_to_flush:
                # the call touched ObjectRefs: register retained borrows
                # BEFORE the reply ships (cheap check keeps the ref-free
                # fan-out path at zero extra hops)
                await loop.run_in_executor(None, self.core.flush_borrows_sync)
            return envs
        try:
            # async actor: unpack off-loop, run the coroutine on the
            # dedicated user loop (awaited from here without blocking)
            if spec.get("trace"):
                from ray_tpu.util import tracing as _tracing

                span_cm = _tracing.execution_span(spec["trace"], name)
            else:
                import contextlib as _cl

                span_cm = _cl.nullcontext()
            args, kwargs = await loop.run_in_executor(self.pool, self.core.unpack_args, spec.get("args"))
            fn = getattr(self.actor_instance, spec["method"])
            cfut = asyncio.run_coroutine_threadsafe(
                _traced_coro(span_cm, fn, args, kwargs), self._ensure_user_loop()
            )
            result = await asyncio.wrap_future(cfut)
            values = self._split_returns(spec, result)
            if values is None:
                await self._flush_borrows_off_loop(loop)
                return [self._bad_arity_env(spec, name)] * len(spec["returns"])
            envs = [await self._to_env(oid, v) for oid, v in zip(spec["returns"], values)]
            await self._flush_borrows_off_loop(loop)
            return envs
        except (Exception, KeyboardInterrupt) as e:
            tb = traceback.format_exc()
            logger.info("task %s failed: %s", name, tb)
            # a FAILED call may still have retained borrows (self.ref = x
            # before raising) — same register-before-reply contract
            try:
                await self._flush_borrows_off_loop(loop)
            except Exception:
                pass
            tid = spec.get("task_id") or spec["returns"][0]
            if isinstance(e, (KeyboardInterrupt,)) or tid in self._cancelled:
                return _cancelled_envs(spec)
            return [_env_err(e, name)] * len(spec["returns"])

    async def _flush_borrows_off_loop(self, loop):
        """Guarded borrow flush for async-actor paths: zero extra hops on
        the ref-free hot path, one executor hop only when refs moved."""
        if self.core._ref_events or self.core._borrows_to_flush:
            await loop.run_in_executor(None, self.core.flush_borrows_sync)

    def _split_returns(self, spec, result):
        n = len(spec["returns"])
        if n == 1:
            return [result]
        values = list(result) if isinstance(result, (tuple, list)) else None
        if values is None or len(values) != n:
            return None
        return values

    def _bad_arity_env(self, spec, name):
        return _env_err(ValueError(f"task did not return {len(spec['returns'])} values"), name)

    def _to_env_sync(self, oid, value):
        """Serialize a result on the current (executor) thread."""
        pickled, buffers, refs = serialization.serialize(value)
        if refs:
            # refs nested in a RESULT escape to the caller: register them
            # with the directory, ESCROW them locally (a synthetic hold so
            # our owner-release can't fire before the caller becomes a
            # borrower), and advertise them in the envelope ("rf") so the
            # caller registers its borrow at DELIVERY, not at lazy decode
            # (reference: returned refs tracked through the reply,
            # reference_count.cc nested return ids)
            roids = [r.binary() for r in refs]
            self.core._ensure_registered(roids)
            self.core.escrow_refs(roids)
        # size computed ONCE: to_wire used to re-walk (and re-join) the
        # same buffers serialized_size just measured
        total = serialization.serialized_size(pickled, buffers)
        if total <= RayConfig.object_store_inline_max_bytes or self.core._shm is None:
            env = _env_inline(serialization.to_wire_sized(pickled, buffers, total))
        else:
            env = self.core.put_serialized_to_shm(bytes(oid), pickled, buffers)
        if refs:
            env["rf"] = roids
        return env

    async def _to_env(self, oid: bytes, value: Any):
        loop = asyncio.get_running_loop()

        def _ser():
            pickled, buffers, refs = serialization.serialize(value)
            roids = [r.binary() for r in refs]
            if refs:
                self.core._ensure_registered(roids)
                self.core.escrow_refs(roids)
            total = serialization.serialized_size(pickled, buffers)
            if total <= RayConfig.object_store_inline_max_bytes or self.core._shm is None:
                env = _env_inline(serialization.to_wire_sized(pickled, buffers, total))
            else:
                env = self.core.put_serialized_to_shm(bytes(oid), pickled, buffers)
            if refs:
                env["rf"] = roids
            return env

        try:
            return await loop.run_in_executor(self.pool, _ser)
        except Exception as e:
            return _env_err(e, "serialize-result")

    async def _push_results(self, spec, envs):
        msg = {
            "task_id": spec["task_id"],
            "results": [{"oid": oid, "env": env} for oid, env in zip(spec["returns"], envs)],
        }
        owner_addr = spec.get("owner_addr")
        try:
            conn = await self.core._peer(owner_addr)
            await conn.push("task.result", msg)
        except Exception:
            logger.warning("owner %s unreachable for task %s results", owner_addr, spec["task_id"])

    async def _send_error(self, spec, exc):
        envs = [_env_err(exc, spec.get("name", ""))] * len(spec["returns"])
        for e in envs:
            e["t"] = type(exc).__name__
        await self._push_results(spec, envs)

    def cancel(self, task_id: str, force: bool):
        self._cancelled.add(task_id)
        if task_id == self._current_task_id and self._current_thread_ident is not None:
            # cooperative interrupt of the running user thread (reference:
            # ray cancels running normal tasks by raising KeyboardInterrupt)
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_long(self._current_thread_ident), ctypes.py_object(KeyboardInterrupt)
            )


async def _amain():
    session_dir = os.environ["RAY_TPU_SESSION_DIR"]
    gcs_addr = os.environ["RAY_TPU_GCS_ADDR"]
    raylet_sock = os.environ["RAY_TPU_RAYLET_SOCK"]
    node_id = os.environ["RAY_TPU_NODE_ID"]
    shm_path = os.environ["RAY_TPU_SHM_PATH"]
    worker_id = os.environ["RAY_TPU_WORKER_ID"]

    # extend sys.path with driver-provided entries (reference: working_dir /
    # py_modules runtime_env; the driver publishes its sys.path via GCS KV)
    core = CoreWorker(
        mode="worker",
        gcs_addr=gcs_addr,
        session_dir=session_dir,
        node_id=node_id,
        shm_path=shm_path,
        worker_id=worker_id,
        raylet_addr=raylet_sock,
    )
    # CoreWorker.start spins its own loop thread; we are already in asyncio —
    # run start() in a thread to avoid blocking this loop.
    await asyncio.get_running_loop().run_in_executor(None, core.start)

    extra_path = core.gcs_request("kv.get", {"ns": "session", "key": "driver_sys_path"})
    if extra_path:
        for p in reversed(serialization.from_bytes(extra_path)):
            if p and p not in sys.path:
                sys.path.insert(0, p)

    executor = Executor(core)
    core.executor = executor
    # route ray_tpu.get/put/remote inside tasks through this worker's core
    from ray_tpu._private.worker import set_worker_process_core

    set_worker_process_core(core)

    # Bridge: the executor's async handlers must run on the CoreWorker IO
    # loop (where peer connections live).
    done = asyncio.Event()

    async def on_core_loop():
        conn = await protocol.connect(raylet_sock, _handle_raylet, name="worker-raylet")
        await conn.request("worker.register", {"worker_id": worker_id, "addr": core._listen_addr})
        return conn

    async def _handle_raylet(method, data, conn):
        if method == "exec.task":
            return await executor.execute_task(data["spec"])
        if method == "exec.cancel":
            executor.cancel(data["task_id"], data.get("force", False))
            return True
        if method == "exec.shutdown":
            prof = globals().get("_worker_profile")
            if prof is not None:  # WHAT=main mode; ioloop/exec modes dump on timers
                prof.disable()
                prof.dump_stats(os.environ["RAY_TPU_PROFILE_DIR"] + f"/worker-{os.getpid()}.prof")
            os._exit(0)
        raise ValueError(f"unknown method {method}")

    fut = asyncio.run_coroutine_threadsafe(on_core_loop(), core._loop)
    fut.result(timeout=RayConfig.worker_register_timeout_s)
    logger.info("worker %s registered", worker_id[:12])
    await done.wait()  # forever


def main():
    from ray_tpu._private.node import arm_pdeathsig

    arm_pdeathsig()  # die with the spawning raylet (see node.py)
    logging.basicConfig(level=logging.INFO)
    # fewer forced GIL handoffs between the IO loop and executor threads:
    # on 1-core hosts the default 5ms check interval costs measurable
    # throughput at fan-out rates (threads block on IO constantly, so
    # responsiveness is unaffected)
    sys.setswitchinterval(0.02)
    if os.environ.get("RAY_TPU_PROFILE_DIR") and os.environ.get("RAY_TPU_PROFILE_WHAT") == "main":
        # dev-only worker profiling: dump per-pid cProfile stats at
        # graceful shutdown (driven by bench/profiling scripts). Only one
        # cProfile may be active per process — RAY_TPU_PROFILE_WHAT picks
        # the thread (main | ioloop | exec).
        import cProfile

        globals()["_worker_profile"] = prof = cProfile.Profile()
        prof.enable()

        async def _amain_with_dumps():
            # workers die by SIGKILL at cluster stop: dump on a timer.
            # The dump callback runs ON the profiled (main/loop) thread —
            # cProfile's disable/enable are per-thread, so a separate
            # dump thread would both race the C-level stats and re-install
            # the profiler on itself instead of the profiled thread.
            loop = asyncio.get_running_loop()

            def _dump():
                prof.disable()
                try:
                    prof.dump_stats(
                        os.environ["RAY_TPU_PROFILE_DIR"] + f"/worker-{os.getpid()}.prof"
                    )
                except Exception:
                    pass
                prof.enable()
                loop.call_later(3.0, _dump)

            loop.call_later(3.0, _dump)
            await _amain()

        asyncio.run(_amain_with_dumps())
        return
    asyncio.run(_amain())


if __name__ == "__main__":
    main()
