"""Driver `serve_afmoe`: one cell of a serving configuration whose model has
sigmoid-routed experts and sliding-window layers, through the same entry
points as `serve`: `serve.run(llm_deployment(continuous=True, ...))` with the
replica in a worker granted `TPU: 1`.

It is `drivers/serve_hybrid.py` with this model's own parts: the configuration
file's `afmoe` keys become the program's `AfmoeConfig`, the weights and the
reference are `weights_afmoe` / `reference_afmoe`, and the engine's routing and
window counters are among the facts. Load generation, warm-up, compile counts,
the trace and the sample for the check are `serve.py`'s, by import and by
subclassing; `facts` has the same keys, so the serve readers that are there
read this cell too. The window note says how many of the checked requests'
contexts pass the sliding window inside the prompt and while they decode: the
comparison with the reference means what it should only if some do.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import common, traffic
from benchmark.common import note, require
from benchmark.drivers import serve_hybrid
from benchmark.drivers.serve import APP, call, macro_variants
from benchmark.drivers.serve_hybrid import HybridBenchLLMServer

# before ray_tpu.init(): a tree without the model fails here, in seconds
from ray_tpu.models import afmoe
from ray_tpu.serve.llm import _LLMServer

ROWS_AT_A_TIME = 8  # of the reference, so that it fits beside the system
# seconds of the window a traced run puts under the profiler. A decode step
# here is some 6 ms of a thousand small operations (sorts, ragged products, a
# ring write a lane), so 8 s of it, what `serve.py` traces, is a trace the
# replica needs over two minutes to stop and reduce (my chip run, PR 33: the
# one call that waited for it was silent that long, the transport took the
# stream for wedged and the traced run failed). 2.5 s holds six dispatches
# and four hundred steps; `bench_trace_result` below is polled besides
TRACE_S = 2.5
ENGINE_COUNTERS = ("dispatches", "tokens_out", "slot_steps", "useful_slot_steps",
                   "prefill_tokens", "requests_completed", "expert_rows", "experts_hit",
                   "expert_rows_max", "past_window_lane_steps")


def afmoe_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file with the
    source's (`afmoe`) keys. Touches no JAX backend."""
    import jax.numpy as jnp

    c = config_file
    require(c["model_type"] == "afmoe" and c["score_func"] == "sigmoid",
            "AfmoeConfig is the afmoe decoder with sigmoid routing")
    require(c["n_group"] == c["topk_group"] == c["num_expert_groups"] == c["num_limited_groups"] == 1,
            "the router has no group limit")
    require(c["hidden_act"] == "silu" and c["rope_scaling"] is None
            and not c["tie_word_embeddings"],
            "activation, rope and the untied head are the ones models/afmoe.py writes down")
    types = tuple(c["layer_types"])
    require(len(types) == c["num_hidden_layers"], "layer_types names every layer")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], layer_types=types,
        n_dense_layers=c["num_dense_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
        n_shared_experts=c["num_shared_experts"], sliding_window=c["sliding_window"],
        rope_theta=float(c["rope_theta"]), route_scale=float(c["route_scale"]),
        route_norm=bool(c["route_norm"]), mup_enabled=bool(c["mup_enabled"]),
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=c["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return afmoe.AfmoeConfig(**kw)


class AfmoeBenchLLMServer(HybridBenchLLMServer):
    """`HybridBenchLLMServer` with this model's weights and reference; its
    compile count, trace, warm-up, metrics and timelines are inherited."""

    def __init__(self, bench_seed: int = 0, lower_precision: Optional[str] = None, **kw):
        import jax

        from benchmark import weights_afmoe as weights

        self._bench_compile_events = common.count_compilations()
        t0 = time.perf_counter()
        self._bench_key = weights.seed_key(bench_seed)
        params = weights.init_params(self._bench_key, kw["cfg"])
        if lower_precision:  # the control only: never set by a benchmark run
            params = weights.round_to_fewer_bits(params, lower_precision)
        jax.block_until_ready(params)
        self._bench_init_s = time.perf_counter() - t0
        _LLMServer.__init__(self, params=params, **kw)

    def bench_trace_result(self) -> Dict[str, Any]:
        """`serve.py`'s, but an answer within a minute whatever the trace's
        size (`pending` until the trace thread is done): the transport
        breaks a stream on which a call in flight stays silent for two."""
        self._trace_thread.join(60.0)
        if self._trace_thread.is_alive():
            return {"pending": True}
        return self._trace_result or {"error": "the trace thread left no result"}

    def bench_logit_gaps(self, samples: List[Dict[str, Any]], rows: int, pad_to: int,
                         n_out: int) -> Dict[str, Any]:
        """The reference over prompt + emitted tokens of each sample (on the
        chip, outside the window), ROWS_AT_A_TIME samples a call of one shape;
        weights regenerated from the seed."""
        import jax.numpy as jnp

        from benchmark import reference_afmoe as reference

        t0 = time.perf_counter()
        n = -(-max(rows, len(samples)) // ROWS_AT_A_TIME) * ROWS_AT_A_TIME
        toks = np.zeros((n, pad_to), np.int32)
        first = np.ones(n, np.int32)
        count = np.zeros(n, np.int32)
        for i, s in enumerate(samples):
            seq = list(s["prompt"]) + list(s["tokens"])
            toks[i, :len(seq)] = seq
            first[i], count[i] = len(s["prompt"]), len(s["tokens"])
        gaps, spread = [], []
        for at in range(0, n, ROWS_AT_A_TIME):
            rows_ = slice(at, at + ROWS_AT_A_TIME)
            if not count[rows_].any():
                continue
            g, sp = reference.logit_gaps(
                self._bench_key, jnp.asarray(toks[rows_]), jnp.asarray(first[rows_]),
                jnp.asarray(count[rows_]), self.cfg, n_out)
            gaps.append(np.asarray(g))
            spread.append(np.asarray(sp)[np.arange(n_out)[None, :] < count[rows_][:, None]])
        gaps = np.concatenate(gaps)
        out = reference.summarize_gaps(gaps)
        checked = np.sort(gaps[gaps >= 0])
        # printed beside the mean: where the gaps lie (most are exactly 0)
        out.update({f"gap_p{q}": float(checked[min(len(checked) - 1, len(checked) * q // 100)])
                    for q in (80, 90, 95, 99)} if len(checked) else {})
        out["logit_std"] = float(np.concatenate(spread).mean())
        out["seconds"] = time.perf_counter() - t0
        return out


# ------------------------------------------------------------- in the parent
def build_app(cfg, serve_cfg: Dict[str, Any], seed: int, lower_precision: Optional[str] = None):
    """`serve_hybrid.build_app` with this model's callable."""
    from ray_tpu import serve

    app = serve_hybrid.build_app(cfg, serve_cfg, seed, lower_precision)
    stock = app.deployment
    return serve.deployment(
        AfmoeBenchLLMServer, name=stock.name, num_replicas=stock.num_replicas,
        ray_actor_options=stock.ray_actor_options, fault_config=stock.fault_config,
    ).bind(*app.init_args, **app.init_kwargs)


def bring_up(cell: Dict[str, Any], seed: int, lower_precision: Optional[str] = None):
    """Replica deployed on a running cluster, every variant warm.
    Returns (handle, cfg, info)."""
    import ray_tpu
    from ray_tpu import serve

    cf = cell["config_file"]
    cfg = afmoe_config(cf)
    info: Dict[str, Any] = {}
    require(ray_tpu.cluster_resources().get("TPU", 0) >= cell["chips"],
            f"the cluster advertises TPU={ray_tpu.cluster_resources().get('TPU', 0)}, "
            f"the cell needs {cell['chips']}")
    t0 = time.perf_counter()
    with common.deadline(900, "serve.run"):
        handle = serve.run(build_app(cfg, cf["serve"], seed, lower_precision), name=APP)
    info["deploy_s"] = time.perf_counter() - t0
    info["device"] = call(handle, "bench_device")
    variants = macro_variants(cell["traffic_file"], cf["serve"], cfg.max_seq_len)
    t0 = time.perf_counter()
    call(handle, "bench_warm_start", variants, cfg.vocab_size,
         cell["traffic_file"]["prompt_len"]["min"])
    with common.deadline(1000, "warm-up of the macro-step variants"):
        while True:
            time.sleep(1.0)
            info["warm"] = call(handle, "bench_warm_poll", timeout=60.0)
            if info["warm"]["done"]:
                break
    require(info["warm"]["error"] is None, f"warm-up failed: {info['warm']['error']}")
    require(len(info["warm"]["bursts"]) == len(variants), "warm-up skipped a variant")
    info["warm_s"] = time.perf_counter() - t0
    return handle, cfg, info


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_process_start: float) -> Dict[str, Any]:
    """One run of one cell, in the shape run.py assembles a result from."""
    import ray_tpu

    with common.deadline(120, "ray_tpu.init"):
        ray_tpu.init()
    try:
        return measure(cell, seed, seconds, trace, t_process_start)
    finally:
        ray_tpu.shutdown()


def sample_for_check(records: List[Dict[str, Any]], requests: List[Dict[str, Any]],
                     seed: int, limit: int, window: int) -> List[Dict[str, Any]]:
    """A seeded sample of the completed requests that keeps the ones the
    window's arithmetic depends on: up to a quarter of it from those whose
    prompt is longer than the window, up to a quarter from those that pass
    it while they decode, the rest from all the others (a plain random
    sample of 32 holds no decode-crosser once in fifty runs)."""
    done = [r for r in records if r["ok"]]
    rng = np.random.default_rng([int(seed), 7])
    kind = lambda r: (0 if len(requests[r["i"]]["prompt"]) > window else  # noqa: E731
                      1 if len(requests[r["i"]]["prompt"]) + len(r["tokens"]) > window else 2)
    groups = [[i for i in rng.permutation(len(done)) if kind(done[i]) == k] for k in range(3)]
    pick = groups[0][:limit // 4] + groups[1][:limit // 4]
    rest = groups[2] + groups[0][limit // 4:] + groups[1][limit // 4:]
    pick += rest[:limit - len(pick)]
    return [{"prompt": requests[done[i]["i"]]["prompt"], "tokens": done[i]["tokens"]}
            for i in sorted(pick)]


def window_crossings(samples: List[Dict[str, Any]], window: int) -> Dict[str, int]:
    """How many of the checked requests pass the sliding window, and where."""
    in_prompt = sum(1 for s in samples if len(s["prompt"]) > window)
    in_decode = sum(1 for s in samples
                    if len(s["prompt"]) <= window < len(s["prompt"]) + len(s["tokens"]))
    return {"checked": len(samples), "past_window_in_prompt": in_prompt,
            "past_window_during_decode": in_decode}


def measure(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
            t_process_start: float, lower_precision: Optional[str] = None) -> Dict[str, Any]:
    """`run` on a cluster that is already up (the tests bring their own)."""
    from ray_tpu import serve

    tf, cf = cell["traffic_file"], cell["config_file"]
    check = cf["check"]
    n_out = tf["output_len"]["max"]
    pad_to = -(-(tf["prompt_len"]["max"] + n_out) // 64) * 64
    try:
        handle, cfg, info = bring_up(cell, seed, lower_precision)
        plan_ = traffic.plan(tf, seed, seconds, cfg.vocab_size)
        note(phase="setup", **{k: info[k] for k in ("deploy_s", "warm_s")},
             weights_s=info["device"]["weights_s"], warm=info["warm"]["bursts"],
             planned_requests=len(plan_["requests"]) if plan_["due"] else None)
        compiles0 = call(handle, "bench_compiles")
        metrics0 = call(handle, "bench_metrics")
        if trace:
            call(handle, "bench_trace_schedule", common.clock() + seconds / 3.0,
                 min(TRACE_S, seconds / 3.0), os.path.join(common.RUN_DIR, "trace"))
        setup_s = common.clock() - t_process_start
        window = traffic.run_window(handle, plan_, seconds)
        reduced = None
        with common.deadline(900, "the device trace's reduction"):
            while trace and (reduced is None or reduced.get("pending")):
                reduced = call(handle, "bench_trace_result", timeout=110.0)
        metrics1 = call(handle, "bench_metrics")
        compiles1 = call(handle, "bench_compiles")
        summary = traffic.summarize(window)
        records = window["records"]
        timelines = (call(handle, "bench_timelines", [r["rid"] for r in records if r["ok"]])
                     if trace else {})
        samples = sample_for_check(records, plan_["requests"], seed, check["max_requests"],
                                   cfg.sliding_window)
        crossings = window_crossings(samples, cfg.sliding_window)
        gaps = call(handle, "bench_logit_gaps", samples, check["max_requests"], pad_to, n_out,
                    timeout=900.0) if samples else {}
        device = call(handle, "bench_device")
    finally:
        serve.shutdown()
    if reduced is not None:
        require("error" not in reduced, f"the device trace failed: {reduced.get('error')}")
    compiled = sum(compiles1[k] - compiles0[k] for k in compiles1)
    unanswered = sum(1 for r in records if r["t_done"] is None)
    engine = {k: metrics1.get(k, 0) - metrics0.get(k, 0) for k in ENGINE_COUNTERS}
    note(phase="window", **summary, engine=engine, reference=gaps, sample=crossings)
    within = lambda key: gaps.get(key) is not None and gaps[key] <= check[key + "_limit"]  # noqa: E731
    need = check.get("min_past_window", 0)
    checks = [
        {"name": "logit_gap_mean", "value": gaps.get("gap_mean"),
         "limit": check["gap_mean_limit"], "ok": within("gap_mean")},
        {"name": "logit_gap_p90", "value": gaps.get("gap_p90"),
         "limit": check["gap_p90_limit"], "ok": within("gap_p90")},
        {"name": "tokens_checked", "value": gaps.get("tokens_checked", 0),
         "limit": f">= {check['min_tokens']}",
         "ok": gaps.get("tokens_checked", 0) >= check["min_tokens"]},
        {"name": "checked_past_window_in_prompt", "value": crossings["past_window_in_prompt"],
         "limit": f">= {need}", "ok": crossings["past_window_in_prompt"] >= need},
        {"name": "checked_past_window_during_decode",
         "value": crossings["past_window_during_decode"], "limit": f">= {need}",
         "ok": crossings["past_window_during_decode"] >= need},
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
        "state_bytes": metrics1.get("state_bytes", 0),
    }
    return {"e2e": e2e, "facts": facts, "checks": checks, "device": device,
            "attempted": summary["attempted"], "failed": summary["failed"]}
