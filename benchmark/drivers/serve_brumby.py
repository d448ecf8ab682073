"""Driver `serve_brumby`: one cell of a serving configuration whose model has
power retention in every layer and no attention layer at all (a lane's whole
context is a float32 state, the cache has no pool), through the same entry
points as `serve`: `serve.run(llm_deployment(continuous=True, ...))` with the
replica in a worker granted `TPU: 1`.

It is `drivers/serve.py` handed this model's own parts: the configuration
file's `brumby` keys become the program's `BrumbyConfig`, the weights and the
reference are `weights_brumby` / `reference_brumby`, the engine's counter of
state rows moved and the device's two counts of admission rows are among the
facts, and a percentile of the logit gaps is judged beside their mean where
the file gives it a limit (`serve_sarvam_mla.checks`). Everything else is
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
from ray_tpu.models import brumby


def brumby_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file with the
    source's (`brumby`) keys. Touches no JAX backend."""
    import jax.numpy as jnp

    c = config_file
    require(c["model_type"] == "brumby" and c["hidden_act"] == "silu" and not c["attention_bias"],
            "BrumbyConfig is the brumby decoder with SiLU and no bias in its projections")
    require(not c["tie_word_embeddings"] and c["rope_scaling"] is None
            and not c["use_sliding_window"] and c["sliding_window"] is None,
            "the untied head, plain rotary frequencies and no window are the ones "
            "models/brumby.py writes down")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), ret_eps=float(c["retention_eps"]),
        ret_chunk=c["retention_chunk_size"], max_seq_len=c["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return brumby.BrumbyConfig(**kw)


class BrumbyBenchLLMServer(HybridBenchLLMServer):
    """`HybridBenchLLMServer` with this model's weights and reference."""

    WEIGHTS = "benchmark.weights_brumby"
    REFERENCE = "benchmark.reference_brumby"
    GAP_PERCENTILES = (80, 90, 95, 99)  # `serve_sarvam_mla.checks` judges those the file limits


PARTS = serve.Parts(
    config=brumby_config, server=BrumbyBenchLLMServer, checks=checks,
    counters=serve.ENGINE_COUNTERS + ("state_lane_steps", "admit_rows", "admit_pieces"))
bring_up = functools.partial(serve.bring_up, parts=PARTS)  # (cell, seed, lower_precision=None)
# (cell, seed, seconds, trace, t_process_start[, lower_precision]), as `serve.py` has them
measure = functools.partial(serve.measure, parts=PARTS)
run = functools.partial(serve.run, parts=PARTS)
