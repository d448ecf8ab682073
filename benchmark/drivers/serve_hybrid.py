"""Driver `serve_hybrid`: one cell of a serving configuration whose model has
recurrent (state-space) layers, through the same entry points as `serve`:
`serve.run(llm_deployment(continuous=True, ...))` with the replica in a worker
granted `TPU: 1`.

It is `drivers/serve.py` handed the hybrid's own parts: the configuration
file's `granitemoehybrid` keys become the program's `GraniteHybridConfig`, the
weights and the reference are `weights_granite_hybrid` /
`reference_granite_hybrid`, and the engine's counter of state rows moved is
among the facts. Everything else (bring-up, warm-up, load generation, compile
counts, the trace and its polled fetch, the sample for the check) is
`serve.py`'s own code; `facts` has the same keys, so the serve readers that
are there read this cell too.
"""
from __future__ import annotations

import functools
import importlib
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.common import require
from benchmark.drivers import serve
from benchmark.drivers.serve import BenchLLMServer

# before ray_tpu.init(): a tree without the model fails here, in seconds
from ray_tpu.models import granite_hybrid

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
    """`BenchLLMServer` with the hybrid's weights and reference; everything
    else is inherited. A further model that needs its reference in blocks of
    rows subclasses this with its own two modules."""

    WEIGHTS = "benchmark.weights_granite_hybrid"
    REFERENCE = "benchmark.reference_granite_hybrid"
    GAP_PERCENTILES: Tuple[int, ...] = ()  # of the gaps, printed beside their mean

    def bench_logit_gaps(self, samples: List[Dict[str, Any]], rows: int, pad_to: int,
                         n_out: int) -> Dict[str, Any]:
        """The reference over prompt + emitted tokens of each sample (on the
        chip, outside the window), ROWS_AT_A_TIME samples a call of one shape;
        weights regenerated from the seed."""
        import jax.numpy as jnp

        reference = importlib.import_module(self.REFERENCE)
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
        if len(checked):  # where the gaps lie (most are exactly 0)
            out.update({f"gap_p{q}": float(checked[min(len(checked) - 1, len(checked) * q // 100)])
                        for q in self.GAP_PERCENTILES})
        out["logit_std"] = float(np.concatenate(spread).mean())
        out["seconds"] = time.perf_counter() - t0
        return out


PARTS = serve.Parts(config=hybrid_config, server=HybridBenchLLMServer,
                    counters=serve.ENGINE_COUNTERS + ("state_lane_steps",))
bring_up = functools.partial(serve.bring_up, parts=PARTS)  # (cell, seed, lower_precision=None)
# (cell, seed, seconds, trace, t_process_start[, lower_precision]), as `serve.py` has them
measure = functools.partial(serve.measure, parts=PARTS)
run = functools.partial(serve.run, parts=PARTS)
