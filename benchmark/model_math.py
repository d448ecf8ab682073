"""Parameters, bytes and operations of a configuration, from its shapes alone.

The yardstick's arithmetic: nothing here imports the program. A
configuration is the dict of a file under `benchmark/configs/` (the source's
own key names). A rate or a roofline share is always these counts over a time
measured on the chip; a count alone is not a device metric.
"""
from __future__ import annotations

from typing import Any, Dict

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, h, kvh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"d": d, "h": h, "kvh": kvh, "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights of one layer that a token is multiplied with."""
    s = shapes(cfg)
    attn = s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kvh"] * s["hd"] + s["h"] * s["hd"] * s["d"]
    return attn + 3 * s["d"] * s["f"]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """All weights a token is multiplied with: the layers and the output head
    (the embedding is a lookup; an untied head is a matrix of its own)."""
    s = shapes(cfg)
    return s["L"] * layer_matmul_params(cfg) + s["d"] * s["V"]


def num_params(cfg: Dict[str, Any]) -> int:
    s = shapes(cfg)
    embed = s["V"] * s["d"]
    head = 0 if cfg.get("tie_word_embeddings") else s["d"] * s["V"]
    return embed + s["L"] * (layer_matmul_params(cfg) + 2 * s["d"]) + s["d"] + head


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return num_params(cfg) * BYTES[cfg["torch_dtype"]]


def decode_read_bytes(cfg: Dict[str, Any]) -> int:
    """Least bytes one decode step reads whatever the number of lanes: every
    layer's weights and the output head once (the embedding is a lookup of a
    few rows)."""
    s = shapes(cfg)
    return (matmul_params(cfg) + s["L"] * 2 * s["d"] + s["d"]) * BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    s = shapes(cfg)
    return 2 * s["L"] * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]


def forward_flops_per_token(cfg: Dict[str, Any], context: float = 0.0) -> float:
    """Forward pass of one token over `context` earlier positions: two
    operations a weight, and QK^T plus PV over the context."""
    s = shapes(cfg)
    return 2.0 * matmul_params(cfg) + 4.0 * s["L"] * s["h"] * s["hd"] * context


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per trained token
    (model FLOP/s utilization counts these and not recomputation): 6 a weight,
    and causal attention, which needs half of the full T x T score matrix:
    forward 4*T*h*hd/2 a layer, backward twice that."""
    s = shapes(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * s["L"] * s["h"] * s["hd"] * seq_len


def flash_step_flops(cfg: Dict[str, Any], batch: int, seq_len: int) -> float:
    """Operations the attention of one train step requires, all layers: the
    forward's two matrix products and the backward's five (S again, dP, dV,
    dK, dQ), each 2*T*T*hd a head, halved because the mask is causal."""
    s = shapes(cfg)
    per_head = 2.0 * seq_len * seq_len * s["hd"] / 2.0
    return s["L"] * batch * s["h"] * per_head * (2 + 5)


def flash_step_bytes(cfg: Dict[str, Any], batch: int, seq_len: int) -> float:
    """Least bytes the same attention moves: forward reads Q, K, V and writes
    O; backward reads Q, K, V, O, dO and writes dQ, dK, dV (the row statistics
    are 1/hd of a tensor and left out)."""
    s = shapes(cfg)
    q = batch * seq_len * s["h"] * s["hd"] * BYTES[cfg["torch_dtype"]]
    kv = batch * seq_len * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]
    forward = 2 * q + 2 * kv
    backward = 4 * q + 4 * kv
    return float(s["L"] * (forward + backward))


def roofline(flops: float, nbytes: float, peak: Dict[str, float]) -> Dict[str, Any]:
    """Least seconds the chip could take for this work, and which bound it."""
    t_compute = flops / peak["flops_per_s_bf16"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return {"least_s": max(t_compute, t_memory),
            "bound": "compute" if t_compute >= t_memory else "memory",
            "compute_s": t_compute, "memory_s": t_memory}
