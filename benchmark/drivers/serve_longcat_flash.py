"""Driver `serve_longcat_flash`: one cell of a serving configuration whose
model has a double layer of two latent attentions and two dense FFNs with the
expert layer on a shortcut, holds a share of its real experts and chooses
identity experts beside them, through the same entry points as `serve`:
`serve.run(llm_deployment(continuous=True, ...))` with the replica in a worker
granted `TPU: 1`.

It is `drivers/serve.py` handed this model's own parts: the configuration
file's keys (the source's) become the program's `LongcatFlashConfig` (the held
range of real experts from `n_routed_experts` of `router_num_experts`), the
weights and the reference are `weights_longcat_flash` /
`reference_longcat_flash`, the engine's routing counters (the two of the
choices among them) and its two attention counts are among the facts, and a
percentile of the logit gaps is judged beside their mean where the file gives
it a limit, as `serve_sarvam_mla` judges one and for its reason (a top-12
choice that flips on a near-tie carries the mean). Everything else is
`serve.py`'s own code; `facts` has the same keys, so the serve readers that
are there read this cell too.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

from benchmark.common import require
from benchmark.drivers import serve
from benchmark.drivers.serve_hybrid import HybridBenchLLMServer
from benchmark.drivers.serve_sarvam_mla import checks

# before ray_tpu.init(): a tree without the model fails here, in seconds
from ray_tpu.models import longcat_flash


def longcat_flash_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file with the
    source's keys. Touches no JAX backend."""
    import jax.numpy as jnp

    c = config_file
    require(c["attention_method"] == "MLA" and c["q_lora_rank"] and not c["attention_bias"],
            "LongcatFlashConfig is latent attention with a compressed query and no bias")
    require(c["zero_expert_type"] == "identity",
            "a chosen index past the real experts adds w x m: identity experts")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_layers"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["ffn_hidden_size"], moe_d_ff=c["expert_ffn_hidden_size"],
        n_routed_experts=c.get("router_num_experts", c["n_routed_experts"]),
        n_zero_experts=c["zero_expert_num"], held_first=c.get("held_experts_first", 0),
        held_count=c["n_routed_experts"], top_k=c["moe_topk"],
        route_scale=float(c["routed_scaling_factor"]),
        mla_scale_q_lora=bool(c["mla_scale_q_lora"]), mla_scale_kv_lora=bool(c["mla_scale_kv_lora"]),
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        max_seq_len=c["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return longcat_flash.LongcatFlashConfig(**kw)


class LongcatFlashBenchLLMServer(HybridBenchLLMServer):
    """`HybridBenchLLMServer` with this model's weights and reference."""

    WEIGHTS = "benchmark.weights_longcat_flash"
    REFERENCE = "benchmark.reference_longcat_flash"
    GAP_PERCENTILES = (80, 90, 95, 99)  # `serve_sarvam_mla.checks` judges those the file limits


PARTS = serve.Parts(
    config=longcat_flash_config, server=LongcatFlashBenchLLMServer, checks=checks,
    counters=serve.ENGINE_COUNTERS + ("expert_rows", "experts_hit", "expert_rows_max",
                                      "real_choices", "zero_choices", "ctx_tokens",
                                      "prompt_pairs"))
bring_up = functools.partial(serve.bring_up, parts=PARTS)  # (cell, seed, lower_precision=None)
# (cell, seed, seconds, trace, t_process_start[, lower_precision]), as `serve.py` has them
measure = functools.partial(serve.measure, parts=PARTS)
run = functools.partial(serve.run, parts=PARTS)
