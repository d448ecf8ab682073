"""Driver `serve_qwen3_next`: one cell of a serving configuration whose model
has gated-delta-rule linear-attention layers beside gated attention layers and
holds a share of its softmax-routed experts, through the same entry points as
`serve`: `serve.run(llm_deployment(continuous=True, ...))` with the replica in
a worker granted `TPU: 1`.

It is `drivers/serve.py` handed this model's own parts: the configuration
file's `qwen3_next` keys become the program's `Qwen3NextConfig` (the held range
of experts from `num_experts` of `router_num_experts`), the weights and the
reference are `weights_qwen3_next` / `reference_qwen3_next`, the engine's
counter of state rows moved and its routing counters are among the facts, and
a percentile of the logit gaps is judged beside their mean where the file
gives it a limit, as `serve_sarvam_mla` judges one and for its reason (a
top-10 choice that flips on a near-tie carries the mean). Everything else is
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
from ray_tpu.models import qwen3_next


def qwen3_next_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file with the
    source's (`qwen3_next`) keys. Touches no JAX backend."""
    import jax.numpy as jnp

    c = config_file
    require(c["model_type"] == "qwen3_next" and c["decoder_sparse_step"] == 1
            and not c["mlp_only_layers"],
            "Qwen3NextConfig has an expert layer in every layer")
    require(c["hidden_act"] == "silu" and not c["tie_word_embeddings"] and c["rope_scaling"] is None
            and not c["use_sliding_window"],
            "activation, the untied head, plain rotary frequencies and no window are the ones "
            "models/qwen3_next.py writes down")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        full_attention_interval=c["full_attention_interval"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        partial_rotary_factor=float(c["partial_rotary_factor"]), rope_theta=float(c["rope_theta"]),
        lin_k_heads=c["linear_num_key_heads"], lin_v_heads=c["linear_num_value_heads"],
        lin_k_dim=c["linear_key_head_dim"], lin_v_dim=c["linear_value_head_dim"],
        lin_conv=c["linear_conv_kernel_dim"], lin_chunk=c["linear_chunk_size"],
        moe_d_ff=c["moe_intermediate_size"], shared_d_ff=c["shared_expert_intermediate_size"],
        n_experts=c.get("router_num_experts", c["num_experts"]),
        held_first=c.get("held_experts_first", 0), held_count=c["num_experts"],
        top_k=c["num_experts_per_tok"], route_norm=bool(c["norm_topk_prob"]),
        rms_eps=float(c["rms_norm_eps"]), max_seq_len=c["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return qwen3_next.Qwen3NextConfig(**kw)


class Qwen3NextBenchLLMServer(HybridBenchLLMServer):
    """`HybridBenchLLMServer` with this model's weights and reference."""

    WEIGHTS = "benchmark.weights_qwen3_next"
    REFERENCE = "benchmark.reference_qwen3_next"
    GAP_PERCENTILES = (80, 90, 95, 99)  # `serve_sarvam_mla.checks` judges those the file limits


PARTS = serve.Parts(
    config=qwen3_next_config, server=Qwen3NextBenchLLMServer, checks=checks,
    counters=serve.ENGINE_COUNTERS + ("state_lane_steps", "expert_rows", "experts_hit",
                                      "expert_rows_max"))
bring_up = functools.partial(serve.bring_up, parts=PARTS)  # (cell, seed, lower_precision=None)
# (cell, seed, seconds, trace, t_process_start[, lower_precision]), as `serve.py` has them
measure = functools.partial(serve.measure, parts=PARTS)
run = functools.partial(serve.run, parts=PARTS)
