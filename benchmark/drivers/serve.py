"""Driver `serve`: one cell of a serving configuration, through the normal
entry points: `serve.run(llm_deployment(continuous=True, ...))` with the
replica in a worker granted `TPU: 1`.

The parent (this module's `run`) never initialises a JAX backend. What only the
process that holds the chip can do (seed-made weights, warm-up bursts, compile
counts, the device trace, the reference check) are methods of `BenchLLMServer`,
the benchmark's subclass of the stock deployment callable, reached through the
routed handle like any other method.

`bring_up`, `measure` and `run` are written once, here, for every serving
model. What is a model's own (its config function, its server class, its
engine counters, the sample rule of its check and its further checks) is a
`Parts`, which `serve_hybrid.py` and `serve_afmoe.py` hand in.

**No call to the replica is in flight for more than a minute without a
reply.** The program's transport takes a stream on which a call in flight has
had no reply for 120 s for wedged, and breaks it
(`ray_tpu/experimental/direct_transport._STALL_BREAK_S`). So whatever can take
long on the replica (the warm-up, stopping and reducing the device trace, the
reference) runs in a thread of it and is polled: the replica waits for it at
most `REPLY_WITHIN_S` a call and answers `{"pending": True}` until it is done.
"""
from __future__ import annotations

import dataclasses
import importlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark import common, traffic
from benchmark.common import note, require

from ray_tpu.serve.llm import _LLMServer

APP = "bench"
K_PHASES, CHUNK = 8, 8  # llm_deployment's defaults: phases a dispatch, steps a phase
REPLY_WITHIN_S = 45.0   # the longest the replica waits for a job of its own before it answers
CALL_TIMEOUT_S = 110.0  # of one call: under the transport's 120 s, so that the error names the call
POLL_DEADLINE_S = 900   # of a polled job as a whole
ENGINE_COUNTERS = ("dispatches", "tokens_out", "slot_steps", "useful_slot_steps",
                   "prefill_tokens", "requests_completed")


class BenchLLMServer(_LLMServer):
    """The stock deployment callable, plus the replica's own account of its
    device, its compilations and its agreement with the reference. A model of
    another family subclasses it with its own `WEIGHTS` and `bench_logit_gaps`."""

    WEIGHTS = "benchmark.weights"  # the module that makes this model's weights from the seed

    def __init__(self, bench_seed: int = 0, lower_precision: Optional[str] = None, **kw):
        import jax

        weights = importlib.import_module(self.WEIGHTS)
        self._bench_compile_events = common.count_compilations()
        self._bench_jobs: Dict[str, Dict[str, Any]] = {}
        t0 = time.perf_counter()
        self._bench_key = weights.seed_key(bench_seed)
        params = weights.init_params(self._bench_key, kw["cfg"])
        if lower_precision:  # the control only: never set by a benchmark run
            params = weights.round_to_fewer_bits(params, lower_precision)
        jax.block_until_ready(params)
        self._bench_init_s = time.perf_counter() - t0
        super().__init__(params=params, **kw)

    # -- device and compilations ------------------------------------------
    def bench_device(self) -> Dict[str, Any]:
        return {**common.device_report(), "weights_s": self._bench_init_s}

    def bench_compiles(self) -> Dict[str, int]:
        """Programs the engine's one jitted entry, the paged macro-step, holds,
        and every program this process has handed to the compiler: the
        difference over a window is the number of compilations inside it."""
        return {"macro_paged": int(self.engine._macro_paged_fn._cache_size()),
                "backend_compiles": len(self._bench_compile_events)}

    def bench_warm_start(self, variants: List[List[int]], vocab: int, short: int) -> None:
        """Make the engine compile each (A, P) variant of the macro-step: a
        burst of A prompts of P tokens submitted from the engine's own loop
        thread, so that one plan admits them together. (1, 16) is the dispatch
        with no admission: one request of the traffic's shortest prompt
        (`short`) decoding past a whole dispatch. Runs in a thread of the
        replica; `bench_warm_poll` reports how far it is."""
        e = self.engine
        rng = np.random.default_rng(12345)
        self._warm = {"bursts": [], "done": False, "error": None}

        def body():
            try:
                for A, P in variants:
                    before = self.bench_compiles()["macro_paged"]
                    t0 = time.perf_counter()
                    if P <= 16:
                        n, plen, new = 1, short, K_PHASES * CHUNK + 8
                    else:
                        n, plen, new = A, P, 2
                    prompts = [rng.integers(0, vocab, plen).tolist() for _ in range(n)]
                    reqs = e.call_on_loop(
                        lambda: [e.submit(p, new) for p in prompts], timeout=60.0)
                    for r in reqs:
                        if not r.done.wait(900.0):
                            raise TimeoutError(f"warm-up burst {(A, P)} did not finish")
                        if r.error is not None:
                            raise RuntimeError(f"warm-up burst {(A, P)} failed: {r.error}")
                    self._warm["bursts"].append({
                        "variant": [A, P], "seconds": time.perf_counter() - t0,
                        "compiled": self.bench_compiles()["macro_paged"] - before})
            except Exception as exc:  # handed to the poller, which ends the run
                self._warm["error"] = f"{type(exc).__name__}: {exc}"
            self._warm["done"] = True

        threading.Thread(target=body, name="bench-warm", daemon=True).start()

    def bench_warm_poll(self) -> Dict[str, Any]:
        return {**self._warm, "compiles": self.bench_compiles()}

    def bench_metrics(self) -> Dict[str, Any]:
        return self.engine.metrics()

    def bench_timelines(self, rids: List[str]) -> Dict[str, List[Dict[str, Any]]]:
        from ray_tpu.observability import lifeline

        return {rid: [{"t": e["t"], "kind": e["kind"]} for e in lifeline.events(rid)]
                for rid in rids}

    # -- a job of the replica's own, started and polled ---------------------
    def _job_start(self, what: str, body: Callable[[], Dict[str, Any]]) -> None:
        """Run `body` in a thread of this process. `_job_poll(what)` fetches
        what it returned, or the error it raised (the run then fails)."""
        job: Dict[str, Any] = {"result": None}

        def run():
            try:
                job["result"] = body()
            except Exception as e:  # handed to the poller, which ends the run
                job["result"] = {"error": f"{type(e).__name__}: {e}"}

        job["thread"] = threading.Thread(target=run, name=f"bench-{what}", daemon=True)
        self._bench_jobs[what] = job
        job["thread"].start()

    def _job_poll(self, what: str, wait_s: float) -> Dict[str, Any]:
        """An answer within REPLY_WITHIN_S whatever the job's length:
        `{"pending": True}` until its thread is done."""
        job = self._bench_jobs[what]
        job["thread"].join(min(wait_s, REPLY_WITHIN_S))
        if job["thread"].is_alive():
            return {"pending": True}
        return job["result"] or {"error": f"the {what} thread left no result"}

    # -- device trace ------------------------------------------------------
    def bench_trace_schedule(self, start_at: float, duration_s: float, trace_dir: str) -> None:
        """Trace `duration_s` seconds of the window, starting at `start_at` on
        `common.clock()`, from a thread of this process: only the process that
        holds the chip can trace it, and the parent's one thread is busy
        offering load. `stop_reduce_s` is what came after the stretch: the
        profiler's stop and the reduction."""

        def body():
            from benchmark import trace_reduce

            time.sleep(max(0.0, start_at - common.clock()))
            with common.traced_window(trace_dir):
                t0, m0 = common.clock(), self.engine.metrics()
                time.sleep(duration_s)
                m1, t1 = self.engine.metrics(), common.clock()
            reduced = trace_reduce.reduce_dir(trace_dir, t1 - t0)
            reduced["counters"] = {k: m1[k] - m0[k] for k in (
                "dispatches", "tokens_out", "prefill_tokens", "slot_steps",
                "useful_slot_steps")}
            reduced["stop_reduce_s"] = common.clock() - t1
            return reduced

        self._job_start("trace", body)

    def bench_trace_result(self, wait_s: float = REPLY_WITHIN_S) -> Dict[str, Any]:
        return self._job_poll("trace", wait_s)

    # -- correctness -------------------------------------------------------
    def bench_reference_start(self, samples: List[Dict[str, Any]], rows: int, pad_to: int,
                              n_out: int) -> None:
        self._job_start("reference", lambda: self.bench_logit_gaps(samples, rows, pad_to, n_out))

    def bench_reference_poll(self, wait_s: float = REPLY_WITHIN_S) -> Dict[str, Any]:
        return self._job_poll("reference", wait_s)

    def bench_logit_gaps(self, samples: List[Dict[str, Any]], rows: int, pad_to: int,
                         n_out: int) -> Dict[str, Any]:
        """The reference over prompt + emitted tokens of each sample (on the
        chip, outside the window); weights regenerated from the seed."""
        import jax.numpy as jnp

        from benchmark import reference

        t0 = time.perf_counter()
        n = max(rows, len(samples))  # one shape, so one program, whatever completed
        toks = np.zeros((n, pad_to), np.int32)
        first = np.ones(n, np.int32)
        count = np.zeros(n, np.int32)
        for i, s in enumerate(samples):
            seq = list(s["prompt"]) + list(s["tokens"])
            toks[i, :len(seq)] = seq
            first[i], count[i] = len(s["prompt"]), len(s["tokens"])
        gaps, spread = reference.logit_gaps(
            self._bench_key, jnp.asarray(toks), jnp.asarray(first), jnp.asarray(count),
            self.cfg, n_out)
        out = reference.summarize_gaps(np.asarray(gaps))
        out["logit_std"] = float(np.asarray(spread)[count > 0].mean())
        out["seconds"] = time.perf_counter() - t0
        return out


# ------------------------------------------------------------- in the parent
def sample_for_check(records: List[Dict[str, Any]], requests: List[Dict[str, Any]],
                     seed: int, limit: int, cfg=None) -> List[Dict[str, Any]]:
    done = [r for r in records if r["ok"]]
    pick = np.random.default_rng([int(seed), 7]).permutation(len(done))[:limit]
    return [{"prompt": requests[done[i]["i"]]["prompt"], "tokens": done[i]["tokens"]}
            for i in sorted(pick)]


@dataclasses.dataclass(frozen=True)
class Parts:
    """What of a serve driver is one model's own."""
    config: Callable[..., Any] = common.llama_config  # configuration file -> the program's config
    server: type = BenchLLMServer                     # the deployment callable
    counters: Tuple[str, ...] = ENGINE_COUNTERS       # of `engine.metrics()`, differenced over the window
    # (records, requests, seed, limit, cfg) -> the completed requests the reference goes over
    sample: Callable[..., List[Dict[str, Any]]] = sample_for_check
    # (gaps, the file's `check`, samples, cfg) -> (further checks, what the window note says of the sample)
    checks: Callable[..., Tuple[List[Dict[str, Any]], Dict[str, Any]]] = lambda *_: ([], {})


LLAMA = Parts()


def macro_variants(traffic_file: Dict[str, Any], serve_cfg: Dict[str, Any],
                   span: int) -> List[List[int]]:
    """Every (A, P) macro-step variant the window can reach: A the powers of
    two up to n_slots, P the power-of-two buckets the prompt range covers
    (`llm_engine._dispatch_macro`, `_bucket_paged`), and (1, 16), the
    dispatch that admits nothing."""

    def bucket(n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return min(max(b, serve_cfg["block_size"]), span)

    lo, hi = traffic_file["prompt_len"]["min"], traffic_file["prompt_len"]["max"]
    buckets, b = [], bucket(lo)
    while b <= bucket(hi):
        buckets.append(b)
        b *= 2
    lanes, a = [], 1
    while a <= serve_cfg["n_slots"]:
        lanes.append(a)
        a *= 2
    # widest first: the largest program is compiled while memory is emptiest;
    # (1, 16) last: its request is admitted through an (1, P) already compiled
    last = [[1, 16]] if 16 not in buckets else []
    return [[a, p] for p in reversed(buckets) for a in reversed(lanes)] + last


def build_app(server: type, cfg, serve_cfg: Dict[str, Any], seed: int,
              lower_precision: Optional[str] = None):
    """llm_deployment's own application, with the benchmark's subclass as the
    callable (serve/llm.py invites subclassing) and its two extra arguments.
    `prefix_cache` is passed as the file has it (the engine refuses True for a
    model whose lanes hold state; it is not switched off here)."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    app = llm_deployment(
        num_replicas=1, max_new_tokens=serve_cfg["max_new_tokens"], cfg=cfg,
        continuous=serve_cfg["continuous"], n_slots=serve_cfg["n_slots"],
        block_size=serve_cfg["block_size"], prefix_cache=serve_cfg["prefix_cache"],
        ray_actor_options={"resources": {"TPU": 1}})
    stock = app.deployment
    return serve.deployment(
        server, name=stock.name, num_replicas=stock.num_replicas,
        ray_actor_options=stock.ray_actor_options, fault_config=stock.fault_config,
    ).bind(*app.init_args, bench_seed=seed, lower_precision=lower_precision, **app.init_kwargs)


def call(handle, method: str, *args, timeout: float = CALL_TIMEOUT_S):
    return handle.options(method_name=method).remote(*args).result(timeout=timeout)


def poll(handle, method: str, what: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Ask `method` until the replica's job is done, under one deadline.
    Returns (its result, how the wait went: calls made and the longest that
    one of them stayed without a reply)."""
    t0, polls, longest = common.clock(), 0, 0.0
    with common.deadline(POLL_DEADLINE_S, what):
        while True:
            t = common.clock()
            out = call(handle, method, REPLY_WITHIN_S)
            polls, longest = polls + 1, max(longest, common.clock() - t)
            if not out.get("pending"):
                return out, {"polls": polls, "longest_silent_call_s": longest,
                             "waited_s": common.clock() - t0}


def bring_up(cell: Dict[str, Any], seed: int, lower_precision: Optional[str] = None,
             parts: Parts = LLAMA):
    """Replica deployed on a running cluster, every variant warm.
    Returns (handle, cfg, info)."""
    import ray_tpu
    from ray_tpu import serve

    cf = cell["config_file"]
    cfg = parts.config(cf)
    info: Dict[str, Any] = {}
    require(ray_tpu.cluster_resources().get("TPU", 0) >= cell["chips"],
            f"the cluster advertises TPU={ray_tpu.cluster_resources().get('TPU', 0)}, "
            f"the cell needs {cell['chips']}")
    t0 = time.perf_counter()
    with common.deadline(900, "serve.run"):
        handle = serve.run(build_app(parts.server, cfg, cf["serve"], seed, lower_precision),
                           name=APP)
    info["deploy_s"] = time.perf_counter() - t0
    info["device"] = call(handle, "bench_device")
    variants = macro_variants(cell["traffic_file"], cf["serve"], cfg.max_seq_len)
    t0 = time.perf_counter()
    call(handle, "bench_warm_start", variants, cfg.vocab_size,
         cell["traffic_file"]["prompt_len"]["min"])
    with common.deadline(1000, "warm-up of the macro-step variants"):
        while True:
            time.sleep(1.0)
            info["warm"] = call(handle, "bench_warm_poll")
            if info["warm"]["done"]:
                break
    require(info["warm"]["error"] is None, f"warm-up failed: {info['warm']['error']}")
    require(len(info["warm"]["bursts"]) == len(variants), "warm-up skipped a variant")
    info["warm_s"] = time.perf_counter() - t0
    return handle, cfg, info


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_process_start: float, parts: Parts = LLAMA) -> Dict[str, Any]:
    """One run of one serve cell, in the shape run.py assembles a result from."""
    import ray_tpu

    with common.deadline(120, "ray_tpu.init"):
        ray_tpu.init()
    try:
        return measure(cell, seed, seconds, trace, t_process_start, parts=parts)
    finally:
        ray_tpu.shutdown()


def measure(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
            t_process_start: float, lower_precision: Optional[str] = None,
            parts: Parts = LLAMA) -> Dict[str, Any]:
    """`run` on a cluster that is already up (the tests bring their own)."""
    from ray_tpu import serve

    tf, cf = cell["traffic_file"], cell["config_file"]
    check = cf["check"]
    n_out = tf["output_len"]["max"]
    pad_to = -(-(tf["prompt_len"]["max"] + n_out) // 64) * 64
    reduced, gaps = None, {}
    try:
        handle, cfg, info = bring_up(cell, seed, lower_precision, parts)
        plan_ = traffic.plan(tf, seed, seconds, cfg.vocab_size)
        note(phase="setup", **{k: info[k] for k in ("deploy_s", "warm_s")},
             weights_s=info["device"]["weights_s"], warm=info["warm"]["bursts"],
             planned_requests=len(plan_["requests"]) if plan_["due"] else None)
        compiles0 = call(handle, "bench_compiles")
        metrics0 = call(handle, "bench_metrics")
        if trace:  # the stretch is the cell's datum; a trial shorter than the cell's run shortens it
            call(handle, "bench_trace_schedule", common.clock() + seconds / 3.0,
                 min(tf["trace_seconds"], seconds / 3.0), os.path.join(common.RUN_DIR, "trace"))
        setup_s = common.clock() - t_process_start
        window = traffic.run_window(handle, plan_, seconds)
        if trace:
            reduced, wait = poll(handle, "bench_trace_result", "the device trace's reduction")
            note(phase="trace_fetch", stop_reduce_s=reduced.get("stop_reduce_s"), **wait)
        metrics1 = call(handle, "bench_metrics")
        compiles1 = call(handle, "bench_compiles")
        summary = traffic.summarize(window)
        records = window["records"]
        timelines = (call(handle, "bench_timelines", [r["rid"] for r in records if r["ok"]])
                     if trace else {})
        samples = parts.sample(records, plan_["requests"], seed, check["max_requests"], cfg)
        if samples:
            call(handle, "bench_reference_start", samples, check["max_requests"], pad_to, n_out)
            gaps, wait = poll(handle, "bench_reference_poll", "the reference")
            gaps.update(wait)
        device = call(handle, "bench_device")
    finally:
        serve.shutdown()
    if reduced is not None:
        require("error" not in reduced, f"the device trace failed: {reduced.get('error')}")
    require("error" not in gaps, f"the reference failed: {gaps.get('error')}")
    compiled = sum(compiles1[k] - compiles0[k] for k in compiles1)
    unanswered = sum(1 for r in records if r["t_done"] is None)
    engine = {k: metrics1.get(k, 0) - metrics0.get(k, 0) for k in parts.counters}
    further, sampled = parts.checks(gaps, check, samples, cfg)
    note(phase="window", **summary, engine=engine, reference=gaps, **sampled)
    checks = [
        {"name": "logit_gap_mean", "value": gaps.get("gap_mean"), "limit": check["gap_mean_limit"],
         "ok": gaps.get("gap_mean") is not None and gaps["gap_mean"] <= check["gap_mean_limit"]},
        {"name": "tokens_checked", "value": gaps.get("tokens_checked", 0),
         "limit": f">= {check['min_tokens']}",
         "ok": gaps.get("tokens_checked", 0) >= check["min_tokens"]},
        *further,
        {"name": "compilations_in_window", "value": compiled, "limit": 0, "ok": compiled == 0},
        {"name": "requests_neither_answered_nor_failed", "value": unanswered, "limit": 0,
         "ok": unanswered == 0},
    ]
    e2e = {"setup_s": setup_s, "latency_p50_ms": summary["latency_p50_ms"],
           "latency_p90_ms": summary["latency_p90_ms"], "tok_s": summary["tok_s"]}
    facts = {
        "deploy_s": info["deploy_s"], "records": records, "timelines": timelines,
        "reduced": reduced, "engine": engine,
        "lanes": cf["serve"]["n_slots"],
        "state_bytes": metrics1.get("state_bytes", 0),  # 0 for a model whose lanes hold none
    }
    return {"e2e": e2e, "facts": facts, "checks": checks, "device": device,
            "attempted": summary["attempted"], "failed": summary["failed"]}
