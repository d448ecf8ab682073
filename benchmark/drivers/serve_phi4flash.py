"""Driver `serve_phi4flash`: one cell of a serving configuration whose model is
a decoder-hybrid-decoder (Mamba-1 and window layers, ONE full-attention K/V
pool that the cross-decoder's attentions read, gated memory units,
differential attention), through the same entry points as `serve`:
`serve.run(llm_deployment(continuous=True, ...))` with the replica in a worker
granted `TPU: 1`.

It is `drivers/serve.py` handed this model's own parts: the configuration
file's keys (the source's, and under their names the sizes the file lists as
`assumed`) become the program's `Phi4FlashConfig`, the weights and the
reference are `weights_phi4flash` / `reference_phi4flash`, the engine's count
of state rows moved, its attention count and the device's two counts of
admission rows are among the facts, and a percentile of the logit gaps is
judged beside their mean where the file gives it a limit
(`serve_sarvam_mla.checks`). Everything else is `serve.py`'s own code; `facts`
has the same keys, so the serve readers that are there read this cell too.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

from benchmark.common import require
from benchmark.drivers import serve
from benchmark.drivers.serve_hybrid import HybridBenchLLMServer
from benchmark.drivers.serve_sarvam_mla import checks

# before ray_tpu.init(): a tree without the model fails here, in seconds
from ray_tpu.models import phi4flash


def phi4flash_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file with the
    source's keys. Touches no JAX backend."""
    import jax.numpy as jnp

    c = config_file
    require(c["model_type"] == "phi4flash" and c["hidden_act"] == "silu",
            "Phi4FlashConfig is the phi4flash decoder with SiLU")
    require(c["tie_word_embeddings"] and not c["mlp_bias"] and not c["lm_head_bias"],
            "Phi4FlashConfig ties the output head and has no bias in the MLP or the head")
    require(not c["embd_pdrop"] and not c["resid_pdrop"], "serving: no dropout")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"], d_ff=c["intermediate_size"],
        mb_per_layer=c["mb_per_layer"], sliding_window=c["sliding_window"],
        mamba_d_state=c["mamba_d_state"], mamba_d_conv=c["mamba_d_conv"],
        mamba_expand=c["mamba_expand"], mamba_dt_rank=c["mamba_dt_rank"],
        layer_norm_eps=float(c["layer_norm_eps"]), max_seq_len=c["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return phi4flash.Phi4FlashConfig(**kw)


class Phi4FlashBenchLLMServer(HybridBenchLLMServer):
    """`HybridBenchLLMServer` with this model's weights and reference."""

    WEIGHTS = "benchmark.weights_phi4flash"
    REFERENCE = "benchmark.reference_phi4flash"
    GAP_PERCENTILES = (80, 90, 95, 99)  # `serve_sarvam_mla.checks` judges those the file limits


PARTS = serve.Parts(
    config=phi4flash_config, server=Phi4FlashBenchLLMServer, checks=checks,
    counters=serve.ENGINE_COUNTERS + ("state_lane_steps", "ctx_tokens", "self_rows", "cross_rows",
                                      "admit_rows", "admit_pieces", "past_window_lane_steps"))
bring_up = functools.partial(serve.bring_up, parts=PARTS)  # (cell, seed, lower_precision=None)
# (cell, seed, seconds, trace, t_process_start[, lower_precision]), as `serve.py` has them
measure = functools.partial(serve.measure, parts=PARTS)
run = functools.partial(serve.run, parts=PARTS)
