"""Parameters, bytes and operations of the Brumby configuration, from its
shapes alone: `model_math.py`'s contract for a configuration file with the
source's `brumby` keys. Nothing here imports the program. Every count is OF
THE WORK, not of what implements it: the symmetric square of a `head_dim`-wide
key has d (d + 1) / 2 distinct products (8,256 at d = 128) however the
program lays them out or pads them; a state update reads and writes a live
lane's state once a layer; a scan's operations are the chunked form's at the
configuration's chunk size over real positions.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark.model_math import BYTES, roofline  # noqa: F401  (roofline: for the readers)

STATE_BYTES = 4  # the state, the gates and phi are float32 (`assumed.state_precision`)


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    hd = cfg["head_dim"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"], "hd": hd, "f": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "D": hd * (hd + 1) // 2,              # the symmetric square's width
            "C": cfg["retention_chunk_size"]}


def layer_params(cfg) -> int:
    """W_q, W_k, W_v, W_o; the gate's matrix and bias, the two head norms; the
    SwiGLU; the two RMS norms."""
    s = shapes(cfg)
    d, hq, hkv = s["d"], s["h"] * s["hd"], s["kvh"] * s["hd"]
    return (2 * d * hq + 2 * d * hkv) + (d * s["kvh"] + s["kvh"] + 2 * s["hd"]) + 3 * d * s["f"] + 2 * d


def num_params(cfg) -> int:
    """The layers, the embedding, the untied head, the final norm."""
    s = shapes(cfg)
    assert not cfg["tie_word_embeddings"]
    return s["L"] * layer_params(cfg) + 2 * s["V"] * s["d"] + s["d"]


def weight_bytes(cfg) -> int:
    return num_params(cfg) * BYTES[cfg["torch_dtype"]]


def state_bytes_per_lane_layer(cfg) -> int:
    """S (D x head_dim) and z (D) of every KV head, float32: a lane's whole
    context in one layer, whatever its length."""
    s = shapes(cfg)
    return s["kvh"] * (s["D"] * s["hd"] + s["D"]) * STATE_BYTES


def state_bytes_per_lane(cfg) -> int:
    return shapes(cfg)["L"] * state_bytes_per_lane_layer(cfg)


def decode_weight_bytes(cfg) -> int:
    """What a decode step reads of the weights whatever its lanes: every
    layer and the head (the embedding is a row a lane)."""
    s = shapes(cfg)
    return (s["L"] * layer_params(cfg) + s["V"] * s["d"] + s["d"]) * BYTES[cfg["torch_dtype"]]


# ----------------------------------------------------- the decode step's update
def retention_update_bytes_per_lane_step(cfg) -> int:
    """A live lane's state read and written once in every layer, with what
    goes in and out of the update: q, k, v, the gates, o (float32 there)."""
    s = shapes(cfg)
    rows = (2 * s["h"] * s["hd"] + 2 * s["kvh"] * s["hd"] + s["kvh"]) * STATE_BYTES
    return s["L"] * (2 * state_bytes_per_lane_layer(cfg) + rows)


def retention_update_flops_per_lane_step(cfg) -> int:
    """decay, outer product and sum over S and z; the query heads' products."""
    s = shapes(cfg)
    return s["L"] * (3 * s["kvh"] + 2 * s["h"]) * s["D"] * (s["hd"] + 1)


# ------------------------------------------------------- the admission's scan
def _a_request(cfg, prompt_tokens: float, admissions: int):
    """(mean prompt length, pairs of positions inside chunks, positions past
    the first chunk, chunks) of ONE request of the mean length: every count
    below is convex in a prompt's length, so the mean never overcounts."""
    C = shapes(cfg)["C"]
    T = prompt_tokens / max(admissions, 1)
    full, rest = int(T // C), T - int(T // C) * C
    return T, full * C * (C + 1) / 2 + rest * (rest + 1) / 2, max(T - C, 0.0), full + (rest > 0)


def retention_scan_flops(cfg, prompt_tokens: float, admissions: int) -> float:
    """The chunked form at chunk C over `admissions` prompts of `prompt_tokens`
    real positions in all, every layer: inside a chunk the scores and their
    products with [v | 1] for each pair (i, j <= i); past a prompt's first
    chunk the query of the state a chunk entered with (h heads x D x (head_dim
    + 1) a position: the first chunk enters with nothing); every position's
    share of the state's update (kvh heads alike); the expansions that feed
    the two."""
    s = shapes(cfg)
    T, pairs, past, _ = _a_request(cfg, prompt_tokens, admissions)
    inside = s["h"] * pairs * (2 * s["hd"] + 2 * (s["hd"] + 1))
    query = s["h"] * past * (2 * s["D"] * (s["hd"] + 1) + s["D"])
    build = s["kvh"] * T * (2 * s["D"] * (s["hd"] + 1) + s["D"])
    return s["L"] * admissions * (inside + query + build)


def retention_scan_bytes(cfg, prompt_tokens: float, admissions: int) -> float:
    """q, k, v in and o out in the served type, the gates float32; the state
    written once a chunk and read once a chunk behind the first."""
    s = shapes(cfg)
    T, _, _, n_chunks = _a_request(cfg, prompt_tokens, admissions)
    rows = (2 * s["h"] * s["hd"] + 2 * s["kvh"] * s["hd"]) * BYTES[cfg["torch_dtype"]]
    return s["L"] * admissions * (T * (rows + s["kvh"] * STATE_BYTES)
                                  + (2 * n_chunks - 1) * state_bytes_per_lane_layer(cfg))
