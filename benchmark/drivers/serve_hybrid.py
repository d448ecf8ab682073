"""Driver `serve_hybrid`: one cell of a serving configuration whose model has
recurrent (state-space) layers, through the same entry points as `serve`:
`serve.run(llm_deployment(continuous=True, ...))` with the replica in a worker
granted `TPU: 1`.

It is `drivers/serve.py` with the hybrid's own parts: the configuration file's
`granitemoehybrid` keys become the program's `GraniteHybridConfig`, the
weights and the reference are `weights_granite_hybrid` /
`reference_granite_hybrid`, and the engine's counter of state rows moved is
among the facts. Load generation, warm-up, compile counts, the trace and the
sample for the check are `serve.py`'s, by import and by subclassing; `facts`
has the same keys, so the serve readers that are there read this cell too.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import common, traffic
from benchmark.common import note, require
from benchmark.drivers.serve import APP, BenchLLMServer, call, macro_variants, sample_for_check

# before ray_tpu.init(): a tree without the model fails here, in seconds
from ray_tpu.models import granite_hybrid
from ray_tpu.serve.llm import _LLMServer

ROWS_AT_A_TIME = 8  # of the reference, so that it fits beside the system


def hybrid_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file with the
    source's (`granitemoehybrid`) keys. Touches no JAX backend."""
    import jax.numpy as jnp

    c = config_file
    require(c["model_type"] == "granitemoehybrid" and not c["num_local_experts"],
            "GraniteHybridConfig is the dense granitemoehybrid decoder")
    require(c["position_embedding_type"] == "nope", "the attention layers take no position term")
    require(c["tie_word_embeddings"], "GraniteHybridConfig ties the output head")
    require(c["mamba_expand"] * c["hidden_size"] == c["mamba_n_heads"] * c["mamba_d_head"],
            "mamba_expand * hidden_size is the Mamba heads' total width")
    require(c["hidden_act"] == "silu" and c["normalization_function"] == "rmsnorm"
            and c["mamba_conv_bias"] and not c["mamba_proj_bias"] and not c["attention_bias"],
            "activation, norm and biases are the ones models/granite_hybrid.py writes down")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=tuple(c["layer_types"]), n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["shared_intermediate_size"], mamba_n_heads=c["mamba_n_heads"],
        mamba_d_head=c["mamba_d_head"], mamba_d_state=c["mamba_d_state"],
        mamba_n_groups=c["mamba_n_groups"], mamba_d_conv=c["mamba_d_conv"],
        mamba_chunk_size=c["mamba_chunk_size"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]), rms_eps=float(c["rms_norm_eps"]),
        max_seq_len=c["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    require(len(kw["layer_types"]) == c["num_hidden_layers"], "layer_types names every layer")
    kw.update(overrides)
    return granite_hybrid.GraniteHybridConfig(**kw)


class HybridBenchLLMServer(BenchLLMServer):
    """`BenchLLMServer` with the hybrid's weights and reference; its trace,
    warm-up, metrics and timelines are inherited."""

    def __init__(self, bench_seed: int = 0, lower_precision: Optional[str] = None, **kw):
        import jax

        from benchmark import weights_granite_hybrid as weights

        self._bench_compile_events = common.count_compilations()
        t0 = time.perf_counter()
        self._bench_key = weights.seed_key(bench_seed)
        params = weights.init_params(self._bench_key, kw["cfg"])
        if lower_precision:  # the control only: never set by a benchmark run
            params = weights.round_to_fewer_bits(params, lower_precision)
        jax.block_until_ready(params)
        self._bench_init_s = time.perf_counter() - t0
        _LLMServer.__init__(self, params=params, **kw)

    def bench_compiles(self) -> Dict[str, int]:
        """The paged macro-step is this engine's only program."""
        return {"macro_paged": int(self.engine._macro_paged_fn._cache_size()),
                "backend_compiles": len(self._bench_compile_events)}

    def bench_logit_gaps(self, samples: List[Dict[str, Any]], rows: int, pad_to: int,
                         n_out: int) -> Dict[str, Any]:
        """The reference over prompt + emitted tokens of each sample (on the
        chip, outside the window), ROWS_AT_A_TIME samples a call of one shape;
        weights regenerated from the seed."""
        import jax.numpy as jnp

        from benchmark import reference_granite_hybrid as reference

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
        out = reference.summarize_gaps(np.concatenate(gaps))
        out["logit_std"] = float(np.concatenate(spread).mean())
        out["seconds"] = time.perf_counter() - t0
        return out


# ------------------------------------------------------------- in the parent
def build_app(cfg, serve_cfg: Dict[str, Any], seed: int, lower_precision: Optional[str] = None):
    """llm_deployment's own application with the benchmark's subclass as the
    callable, as `serve.build_app`; `prefix_cache` is passed as the file has
    it (the engine refuses True for this model, it is not switched off here)."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    app = llm_deployment(
        num_replicas=1, max_new_tokens=serve_cfg["max_new_tokens"], cfg=cfg,
        continuous=serve_cfg["continuous"], n_slots=serve_cfg["n_slots"],
        block_size=serve_cfg["block_size"], prefix_cache=serve_cfg["prefix_cache"],
        ray_actor_options={"resources": {"TPU": 1}})
    stock = app.deployment
    return serve.deployment(
        HybridBenchLLMServer, name=stock.name, num_replicas=stock.num_replicas,
        ray_actor_options=stock.ray_actor_options, fault_config=stock.fault_config,
    ).bind(*app.init_args, bench_seed=seed, lower_precision=lower_precision, **app.init_kwargs)


def bring_up(cell: Dict[str, Any], seed: int, lower_precision: Optional[str] = None):
    """Replica deployed on a running cluster, every variant warm.
    Returns (handle, cfg, info)."""
    import ray_tpu
    from ray_tpu import serve

    cf = cell["config_file"]
    cfg = hybrid_config(cf)
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


ENGINE_COUNTERS = ("dispatches", "tokens_out", "slot_steps", "useful_slot_steps",
                   "prefill_tokens", "requests_completed", "state_lane_steps")


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
                 min(8.0, seconds / 3.0), os.path.join(common.RUN_DIR, "trace"))
        setup_s = common.clock() - t_process_start
        window = traffic.run_window(handle, plan_, seconds)
        reduced = call(handle, "bench_trace_result", timeout=700.0) if trace else None
        metrics1 = call(handle, "bench_metrics")
        compiles1 = call(handle, "bench_compiles")
        summary = traffic.summarize(window)
        records = window["records"]
        timelines = (call(handle, "bench_timelines", [r["rid"] for r in records if r["ok"]])
                     if trace else {})
        samples = sample_for_check(records, plan_["requests"], seed, check["max_requests"])
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
    note(phase="window", **summary, engine=engine, reference=gaps)
    within = lambda key: gaps.get(key) is not None and gaps[key] <= check[key + "_limit"]  # noqa: E731
    checks = [
        {"name": "logit_gap_mean", "value": gaps.get("gap_mean"),
         "limit": check["gap_mean_limit"], "ok": within("gap_mean")},
        {"name": "tokens_checked", "value": gaps.get("tokens_checked", 0),
         "limit": f">= {check['min_tokens']}",
         "ok": gaps.get("tokens_checked", 0) >= check["min_tokens"]},
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
