"""Parameters, bytes and operations of the hybrid (Mamba-2 + attention)
configuration, from its shapes alone: `model_math.py`'s contract for a
configuration file with the source's `granitemoehybrid` keys. Nothing here
imports the program.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark.model_math import BYTES, roofline  # noqa: F401  (roofline: for the readers)


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    H, P, N, G = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
                  cfg["mamba_n_groups"])
    types = cfg["layer_types"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
            "f": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
            "H": H, "P": P, "N": N, "di": H * P, "conv_dim": H * P + 2 * G * N,
            "K": cfg["mamba_d_conv"], "Q": cfg["mamba_chunk_size"],
            "Lm": types.count("mamba"), "La": types.count("attention")}


def mlp_params(cfg) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["f"]  # W_in is d x 2f (gate and value), W_out f x d


def mamba_mixer_matmul_params(cfg) -> int:
    s = shapes(cfg)
    return s["d"] * (s["di"] + s["conv_dim"] + s["H"]) + s["di"] * s["d"]


def attn_mixer_matmul_params(cfg) -> int:
    s = shapes(cfg)
    return 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kvh"] * s["hd"]


def mamba_layer_params(cfg) -> int:
    """in_proj, conv weight and bias, dt_bias / A_log / D, gated norm,
    out_proj, the MLP and the block's two norms."""
    s = shapes(cfg)
    small = s["conv_dim"] * s["K"] + s["conv_dim"] + 3 * s["H"] + s["di"]
    return mamba_mixer_matmul_params(cfg) + small + mlp_params(cfg) + 2 * s["d"]


def attn_layer_params(cfg) -> int:
    return attn_mixer_matmul_params(cfg) + mlp_params(cfg) + 2 * shapes(cfg)["d"]


def num_params(cfg) -> int:
    """Every parameter once: the tied matrix is the embedding and the head."""
    s = shapes(cfg)
    assert cfg["tie_word_embeddings"]
    return (s["Lm"] * mamba_layer_params(cfg) + s["La"] * attn_layer_params(cfg)
            + s["V"] * s["d"] + s["d"])


def matmul_params(cfg) -> int:
    """All weights a token is multiplied with: both mixers' projections, the
    MLPs and the (tied) output head."""
    s = shapes(cfg)
    return (s["Lm"] * mamba_mixer_matmul_params(cfg) + s["La"] * attn_mixer_matmul_params(cfg)
            + (s["Lm"] + s["La"]) * mlp_params(cfg) + s["d"] * s["V"])


def weight_bytes(cfg) -> int:
    return num_params(cfg) * BYTES[cfg["torch_dtype"]]


def decode_read_bytes(cfg) -> int:
    """Least bytes of WEIGHTS one decode step reads whatever the lanes: every
    parameter once (the tied matrix as the head; its embedding lookup is a
    few rows)."""
    return weight_bytes(cfg)


def state_bytes_per_lane(cfg) -> int:
    """Recurrent state a lane holds: each Mamba layer's conv tail (taps - 1
    inputs, activation type) and its H x P x N state in float32."""
    s = shapes(cfg)
    conv = (s["K"] - 1) * s["conv_dim"] * BYTES[cfg["torch_dtype"]]
    return s["Lm"] * (conv + s["H"] * s["P"] * s["N"] * 4)


def update_bytes_per_lane_step(cfg) -> int:
    """Least bytes the decode-side state update moves for one live lane in
    one step: its state and conv tail read once and written once."""
    return 2 * state_bytes_per_lane(cfg)


def kv_bytes_per_token(cfg) -> int:
    s = shapes(cfg)
    return 2 * s["La"] * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]


def scan_flops_per_token(cfg) -> float:
    """Operations of the chunked scan for one position, all Mamba layers:
    `C B^T` against the chunk (2 Q N), its product with x (2 Q H P), and the
    chunk's state in and out (2 N H P each)."""
    s = shapes(cfg)
    hp = s["H"] * s["P"]
    return float(s["Lm"] * (2 * s["Q"] * s["N"] + 2 * s["Q"] * hp + 2 * 2 * s["N"] * hp))


def scan_bytes_per_token(cfg) -> float:
    """Least bytes the scan (conv included) moves for one position, all Mamba
    layers: xBC read, dt read (float32), y written; the chunk states are
    1/Q of a position's and left out."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return float(s["Lm"] * (s["conv_dim"] * b + s["H"] * 4 + s["di"] * b))


def forward_flops_per_token(cfg, context: float = 0.0) -> float:
    """Forward pass of one token over `context` earlier positions: two
    operations a weight, the scan, and QK^T plus PV over the context in the
    attention layers only."""
    s = shapes(cfg)
    return (2.0 * matmul_params(cfg) + scan_flops_per_token(cfg)
            + 4.0 * s["La"] * s["h"] * s["hd"] * context)
