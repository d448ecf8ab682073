"""Driver `serve_sarvam_mla`: one cell of a serving configuration whose model
attends through a latent (MLA) and holds a share of its routed experts,
through the same entry points as `serve`: `serve.run(llm_deployment(
continuous=True, ...))` with the replica in a worker granted `TPU: 1`.

It is `drivers/serve.py` handed this model's own parts: the configuration
file's `sarvam_mla` keys become the program's `SarvamMlaConfig` (the held range
of experts from `num_experts` of `router_num_experts`), the weights and the
reference are `weights_sarvam_mla` / `reference_sarvam_mla`, the engine's
routing counters and its two attention counts are among the facts, and a
percentile of the logit gaps is judged beside their mean, as `serve_afmoe`
judges one and for its reason (a top-8 choice that flips on a near-tie carries
the mean). Everything else is `serve.py`'s own code; `facts` has the same keys,
so the serve readers that are there read this cell too.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

from benchmark.common import require
from benchmark.drivers import serve
from benchmark.drivers.serve_hybrid import HybridBenchLLMServer

# before ray_tpu.init(): a tree without the model fails here, in seconds
from ray_tpu.models import sarvam_mla


def mla_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file with the
    source's (`sarvam_mla`) keys. Touches no JAX backend."""
    import jax.numpy as jnp

    c = config_file
    rs = c["rope_scaling"]
    require(c["model_type"] == "sarvam_mla" and c.get("q_lora_rank") is None,
            "SarvamMlaConfig is latent attention with no query compression")
    require(rs["type"] == "deepseek_yarn", "the rotary frequencies are deepseek_yarn's blend")
    require(c["q_head_dim"] == c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
            and c["head_dim"] == c["kv_lora_rank"] + c["qk_rope_head_dim"],
            "q_head_dim is nope + rope and head_dim the cached row, latent + rope")
    require(c["hidden_act"] == "silu" and not c["tie_word_embeddings"] and c["use_qk_norm"]
            and c["moe_router_enable_expert_bias"],
            "activation, the untied head, the norms and the router's bias are the ones "
            "models/sarvam_mla.py writes down")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"], n_heads=c["num_attention_heads"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_experts=c.get("router_num_experts", c["num_experts"]),
        held_first=c.get("held_experts_first", 0), held_count=c["num_experts"],
        top_k=c["num_experts_per_tok"], n_shared_experts=c["num_shared_experts"],
        route_scale=float(c["routed_scaling_factor"]), rope_theta=float(c["rope_theta"]),
        rope_factor=float(rs["factor"]), rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=c["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return sarvam_mla.SarvamMlaConfig(**kw)


class MlaBenchLLMServer(HybridBenchLLMServer):
    """`HybridBenchLLMServer` with this model's weights and reference."""

    WEIGHTS = "benchmark.weights_sarvam_mla"
    REFERENCE = "benchmark.reference_sarvam_mla"
    GAP_PERCENTILES = (80, 90, 95, 99)


# ------------------------------------------------------------- in the parent
def checks(gaps: Dict[str, Any], check: Dict[str, Any], samples: List[Dict[str, Any]],
           cfg) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """This model's further checks: each percentile of the gaps that the
    file gives a limit (`gap_p<q>_limit`, q of GAP_PERCENTILES; which one
    separates a lower precision here is PERF.md section 2's to say)."""
    out = []
    for q in MlaBenchLLMServer.GAP_PERCENTILES:
        if f"gap_p{q}_limit" in check:
            value, limit = gaps.get(f"gap_p{q}"), check[f"gap_p{q}_limit"]
            out.append({"name": f"logit_gap_p{q}", "value": value, "limit": limit,
                        "ok": value is not None and value <= limit})
    return out, {}


PARTS = serve.Parts(
    config=mla_config, server=MlaBenchLLMServer, checks=checks,
    counters=serve.ENGINE_COUNTERS + ("expert_rows", "experts_hit", "expert_rows_max",
                                      "ctx_tokens", "prompt_pairs"))
bring_up = functools.partial(serve.bring_up, parts=PARTS)  # (cell, seed, lower_precision=None)
# (cell, seed, seconds, trace, t_process_start[, lower_precision]), as `serve.py` has them
measure = functools.partial(serve.measure, parts=PARTS)
run = functools.partial(serve.run, parts=PARTS)
