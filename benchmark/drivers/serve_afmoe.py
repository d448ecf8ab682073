"""Driver `serve_afmoe`: one cell of a serving configuration whose model has
sigmoid-routed experts and sliding-window layers, through the same entry
points as `serve`: `serve.run(llm_deployment(continuous=True, ...))` with the
replica in a worker granted `TPU: 1`.

It is `drivers/serve.py` handed this model's own parts: the configuration
file's `afmoe` keys become the program's `AfmoeConfig`, the weights and the
reference are `weights_afmoe` / `reference_afmoe`, the engine's routing and
window counters are among the facts, and the sample for the check keeps the
requests whose contexts pass the sliding window inside the prompt and while
they decode (the window note says how many: the comparison with the reference
means what it should only if some do). Everything else is `serve.py`'s own
code; `facts` has the same keys, so the serve readers that are there read this
cell too.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.common import require
from benchmark.drivers import serve
from benchmark.drivers.serve_hybrid import HybridBenchLLMServer

# before ray_tpu.init(): a tree without the model fails here, in seconds
from ray_tpu.models import afmoe


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
    """`HybridBenchLLMServer` with this model's weights and reference."""

    WEIGHTS = "benchmark.weights_afmoe"
    REFERENCE = "benchmark.reference_afmoe"
    GAP_PERCENTILES = (80, 90, 95, 99)


# ------------------------------------------------------------- in the parent
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


def checks(gaps: Dict[str, Any], check: Dict[str, Any], samples: List[Dict[str, Any]],
           cfg) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """This model's further checks: the 90th percentile of the gaps (the
    measure that separates a lower precision here, PERF.md section 2) and
    that the sample holds requests past the window, both ways."""
    crossings = window_crossings(samples, cfg.sliding_window)
    need = check.get("min_past_window", 0)
    p90 = gaps.get("gap_p90")
    return [
        {"name": "logit_gap_p90", "value": p90, "limit": check["gap_p90_limit"],
         "ok": p90 is not None and p90 <= check["gap_p90_limit"]},
        {"name": "checked_past_window_in_prompt", "value": crossings["past_window_in_prompt"],
         "limit": f">= {need}", "ok": crossings["past_window_in_prompt"] >= need},
        {"name": "checked_past_window_during_decode",
         "value": crossings["past_window_during_decode"], "limit": f">= {need}",
         "ok": crossings["past_window_during_decode"] >= need},
    ], {"sample": crossings}


PARTS = serve.Parts(
    config=afmoe_config, server=AfmoeBenchLLMServer, checks=checks,
    sample=lambda records, requests, seed, limit, cfg: sample_for_check(
        records, requests, seed, limit, cfg.sliding_window),
    counters=serve.ENGINE_COUNTERS + ("expert_rows", "experts_hit", "expert_rows_max",
                                      "past_window_lane_steps"))
bring_up = functools.partial(serve.bring_up, parts=PARTS)  # (cell, seed, lower_precision=None)
# (cell, seed, seconds, trace, t_process_start[, lower_precision]), as `serve.py` has them
measure = functools.partial(serve.measure, parts=PARTS)
run = functools.partial(serve.run, parts=PARTS)
