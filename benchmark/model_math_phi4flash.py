"""Parameters, bytes and operations of the Phi-4-mini-flash configuration,
from its shapes alone: `model_math.py`'s contract for a configuration file
with the source's `phi4flash` keys (and, under their names, the sizes the
file lists as `assumed`). Nothing here imports the program. Every count is OF
THE WORK, not of what implements it: a state update reads and writes a live
lane's state once; an attention reads the positions it attends once a layer.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark.model_math import BYTES, roofline  # noqa: F401  (roofline: for the readers)


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    half = n // 2
    return {"d": d, "h": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
            "hd": d // cfg["num_attention_heads"], "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "di": cfg["mamba_expand"] * d, "N": cfg["mamba_d_state"],
            "K": cfg["mamba_d_conv"], "r": cfg["mamba_dt_rank"], "W": cfg["sliding_window"],
            "n": n, "Lm": half // 2 + 1, "Lw": half // 2, "Lc": (n - half - 2) // 2}


def mlp_params(cfg) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["f"] + 2 * s["d"]      # fc1 d x 2f, fc2 f x d, the LayerNorm


def mamba_params(cfg) -> int:
    """in_proj, conv weight and bias, x_proj, dt_proj and dt_bias, A_log, D,
    out_proj, the LayerNorm."""
    s = shapes(cfg)
    d, di, N, K, r = s["d"], s["di"], s["N"], s["K"], s["r"]
    return (d * 2 * di + K * di + di + di * (r + 2 * N) + r * di + di + N * di + di + di * d
            + 2 * d)


def diff_params(cfg) -> int:
    """The four lambda vectors and the sub-norm's weight."""
    return 4 * shapes(cfg)["hd"] + 2 * shapes(cfg)["hd"]


def attn_params(cfg) -> int:
    """A window or the full layer: Wqkv and out_proj with bias."""
    s = shapes(cfg)
    hq, hkv = s["h"] * s["hd"], s["kvh"] * s["hd"]
    return s["d"] * (hq + 2 * hkv) + hq + 2 * hkv + hq * s["d"] + s["d"] + diff_params(cfg) + 2 * s["d"]


def cross_params(cfg) -> int:
    s = shapes(cfg)
    hq = s["h"] * s["hd"]
    return s["d"] * hq + hq + hq * s["d"] + s["d"] + diff_params(cfg) + 2 * s["d"]


def gmu_params(cfg) -> int:
    s = shapes(cfg)
    return 2 * s["d"] * s["di"] + 2 * s["d"]


def num_params(cfg) -> int:
    """Every parameter once: the tied matrix is the embedding and the head."""
    s = shapes(cfg)
    assert cfg["tie_word_embeddings"]
    return (s["n"] * mlp_params(cfg) + s["Lm"] * mamba_params(cfg)
            + (s["Lw"] + 1) * attn_params(cfg) + s["Lc"] * (gmu_params(cfg) + cross_params(cfg))
            + s["V"] * s["d"] + 2 * s["d"])


def weight_bytes(cfg) -> int:
    return num_params(cfg) * BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg) -> int:
    """A position's keys and values in the WHOLE model's paged cache: one layer's."""
    s = shapes(cfg)
    return 2 * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]


def state_bytes_per_lane(cfg) -> int:
    """What a lane holds beside its blocks: the K and V rings of every window
    layer, the conv tail and the float32 state of every Mamba layer."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return (s["Lw"] * s["W"] * kv_bytes_per_token(cfg)
            + s["Lm"] * ((s["K"] - 1) * s["di"] * b + s["N"] * s["di"] * 4))


# ---------------------------------------------------------- the state update
def s6_update_bytes_per_lane_step(cfg) -> int:
    """Least bytes the decode-side state update moves for ONE live lane in one
    step, all Mamba layers: its float32 state and its conv tail read once and
    written once, and its projections' outputs read (x, B and C in the served
    type, dt in float32) and y written."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    state = 2 * (s["N"] * s["di"] * 4 + (s["K"] - 1) * s["di"] * b)
    rows = s["di"] * b + s["di"] * 4 + 2 * s["N"] * b + s["di"] * b
    return s["Lm"] * (state + rows)


def s6_update_bytes_per_step(cfg) -> int:
    """What a step reads once whatever the lanes: A (N x d_inner float32), D,
    the conv's weights and bias, of every Mamba layer."""
    s = shapes(cfg)
    return s["Lm"] * (s["N"] * s["di"] * 4 + s["di"] * 4
                      + (s["K"] + 1) * s["di"] * BYTES[cfg["torch_dtype"]])


# ------------------------------------------------------- the admission's scan
def s6_scan_flops_per_token(cfg) -> float:
    """Operations of the recurrence and the conv for one position, all Mamba
    layers: for each of N x d_inner state entries dt A, its exp, the decay's
    product with h, (dt x) B, the sum, h C and the sum over N (7), and for each
    channel the conv's taps (2 K), dt x and D x (3)."""
    s = shapes(cfg)
    return float(s["Lm"] * (7 * s["N"] * s["di"] + (2 * s["K"] + 3) * s["di"]))


def s6_scan_bytes_per_token(cfg) -> float:
    """Least bytes the conv and the scan move for one position, all Mamba
    layers: x read before the conv and after it, dt read (float32), B and C
    read, y written; the state lives in the loop and a row's final state and
    tail are 1/length of a position's and left out."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return float(s["Lm"] * (3 * s["di"] * b + s["di"] * 4 + 2 * s["N"] * b))


# ---------------------------------------------------- the decode attentions
def cross_attn_decode_bytes(cfg, ctx_tokens: int, steps: int) -> float:
    """Least bytes the cross-decoder's attentions move in decode steps whose
    live lanes attend `ctx_tokens` positions in all: each reading layer reads
    every attended position's keys and values once, and its Wq and out_proj
    (with bias) once a step."""
    s = shapes(cfg)
    hq = s["h"] * s["hd"]
    weights = (2 * s["d"] * hq + hq + s["d"]) * BYTES[cfg["torch_dtype"]]
    return float(s["Lc"] * (ctx_tokens * kv_bytes_per_token(cfg) + steps * weights))


def diff_ring_decode_bytes(cfg, lane_steps: int, past_window_lane_steps: int, steps: int) -> float:
    """Least bytes the window layers' attentions move in decode steps: a
    lane-step whose context has passed the window reads the window's W
    positions of keys and values in every window layer, one that has not reads
    its context, counted here as ONE position (the plan does not say how many:
    the count is a floor); Wqkv and out_proj (with bias) once a layer and step."""
    s = shapes(cfg)
    hq, hkv = s["h"] * s["hd"], s["kvh"] * s["hd"]
    weights = (s["d"] * (hq + 2 * hkv) + hq + 2 * hkv + hq * s["d"] + s["d"]) * BYTES[cfg["torch_dtype"]]
    positions = past_window_lane_steps * s["W"] + (lane_steps - past_window_lane_steps)
    return float(s["Lw"] * (positions * kv_bytes_per_token(cfg) + steps * weights))


def decode_step_bytes(cfg, lanes: int, context: float) -> Dict[str, float]:
    """What one decode step of `lanes` live lanes at a mean context of
    `context` positions has to move, by part (PERF.md's account of the cell)."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return {
        "weights": float(weight_bytes(cfg)),
        "mlp_weights": float(s["n"] * 3 * s["d"] * s["f"] * b),
        "head": float(s["V"] * s["d"] * b),
        "rings": float(lanes * s["Lw"] * min(context, s["W"]) * kv_bytes_per_token(cfg)),
        "pool": float(lanes * context * kv_bytes_per_token(cfg) * (s["Lc"] + 1)),
        "state": float(lanes * s6_update_bytes_per_lane_step(cfg)),
    }
