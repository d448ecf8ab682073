"""Parameters, bytes and operations of the `qwen3_next` configuration
(gated-delta-rule linear attention, a gated full-attention layer every
`full_attention_interval`-th, in every layer softmax-routed experts of which a
share may be held beside a gated shared one), from its shapes alone:
`model_math.py`'s contract for a configuration file with the source's keys.
Nothing here imports the program.

`num_experts` is the number of experts whose weights the configuration holds;
`router_num_experts`, where the file has it, is the router's width (the
published config has one number for both). The linear-attention layer has two
regimes. An admission runs the rule in chunks of `linear_chunk_size`
positions, matrix products whose operations are counted here by position
(`scan_flops_per_token`). A decode step reads and writes a lane's state, a
float32 (key, value) matrix a value head, once a layer, whatever computes the
update (`update_bytes_per_lane_step`).
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark.model_math import BYTES, roofline  # noqa: F401  (roofline: for the readers)


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    L, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    La = sum(1 for i in range(L) if (i + 1) % every == 0)
    Hk, K = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    H, V = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "Hk": Hk, "K": K, "H": H, "V": V, "taps": cfg["linear_conv_kernel_dim"],
            "conv_dim": 2 * Hk * K + H * V, "di": H * V, "C": cfg.get("linear_chunk_size", 64),
            "fe": cfg["moe_intermediate_size"], "fs": cfg["shared_expert_intermediate_size"],
            "E": cfg["num_experts"], "Er": cfg.get("router_num_experts", cfg["num_experts"]),
            "k": cfg["num_experts_per_tok"], "Vocab": cfg["vocab_size"],
            "L": L, "La": La, "Ll": L - La}


def linear_mixer_matmul_params(cfg) -> int:
    """in_proj_qkvz (d x (q + k + v + z)), in_proj_ba (d x 2 H), the conv
    (taps x (q + k + v)), out_proj (H V x d)."""
    s = shapes(cfg)
    return (s["d"] * (s["conv_dim"] + s["di"]) + s["d"] * 2 * s["H"] + s["taps"] * s["conv_dim"]
            + s["di"] * s["d"])


def linear_mixer_params(cfg) -> int:
    """... and dt_bias, A_log (a value head each), the gated norm's scale (V)
    and the norm before the mixer."""
    s = shapes(cfg)
    return linear_mixer_matmul_params(cfg) + 2 * s["H"] + s["V"] + s["d"]


def attn_mixer_matmul_params(cfg) -> int:
    """Wq (d x 2 h hd: query and gate), Wk and Wv (d x kvh hd), Wo (h hd x d)."""
    s = shapes(cfg)
    return 3 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kvh"] * s["hd"]


def attn_mixer_params(cfg) -> int:
    s = shapes(cfg)
    return attn_mixer_matmul_params(cfg) + 2 * s["hd"] + s["d"]


def expert_params(cfg) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["fe"]


def shared_params(cfg) -> int:
    """The shared expert and its gate vector."""
    s = shapes(cfg)
    return 3 * s["d"] * s["fs"] + s["d"]


def router_params(cfg) -> int:
    s = shapes(cfg)
    return s["d"] * s["Er"]


def moe_params(cfg, experts: int) -> int:
    """An expert layer with `experts` experts' weights: the norm before it,
    the router, the shared expert with its gate."""
    return shapes(cfg)["d"] + router_params(cfg) + shared_params(cfg) + experts * expert_params(cfg)


def embed_and_head_params(cfg, vocab: int) -> int:
    assert not cfg["tie_word_embeddings"]
    return 2 * vocab * shapes(cfg)["d"]


def _total(cfg, L: int, La: int, experts: int, vocab: int) -> int:
    s = shapes(cfg)
    return ((L - La) * linear_mixer_params(cfg) + La * attn_mixer_params(cfg)
            + L * moe_params(cfg, experts) + embed_and_head_params(cfg, vocab) + s["d"])


def num_params(cfg) -> int:
    """What this configuration holds."""
    s = shapes(cfg)
    return _total(cfg, s["L"], s["La"], s["E"], s["Vocab"])


def published_params(cfg) -> int:
    """The whole published model: the file's `published` depth and
    vocabulary, all of the router's experts."""
    p = cfg["published"]
    L = p["num_hidden_layers"]
    La = L // cfg["full_attention_interval"]
    return _total(cfg, L, La, p["num_experts"], p["vocab_size"])


def weight_bytes(cfg) -> int:
    """dt_bias and A_log are float32, everything else the served type."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return num_params(cfg) * b + s["Ll"] * 2 * s["H"] * (4 - b)


def expert_bytes(cfg) -> int:
    return expert_params(cfg) * BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg) -> int:
    s = shapes(cfg)
    return 2 * s["La"] * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]


def state_bytes_per_lane(cfg) -> int:
    """Recurrent state a lane holds: each linear layer's conv tail (taps - 1
    inputs, activation type) and its H x K x V state in float32."""
    s = shapes(cfg)
    conv = (s["taps"] - 1) * s["conv_dim"] * BYTES[cfg["torch_dtype"]]
    return s["Ll"] * (conv + s["H"] * s["K"] * s["V"] * 4)


def update_bytes_per_lane_step(cfg) -> int:
    """Least bytes the decode-side state update moves for one live lane in
    one step: its state and conv tail read once and written once."""
    return 2 * state_bytes_per_lane(cfg)


def scan_flops_per_token(cfg) -> float:
    """Operations of the chunked delta rule for one position, all linear
    layers, at the file's chunk size C, a value head: k k^T and q k^T against
    the chunk (2 C K each), W = T (beta k exp G) (2 C K), U = T (beta v) and
    the in-chunk outputs (2 C V each), and the state's four products, W S,
    q S and the state's update (2 K V each): 6 C K + 4 C V + 6 K V. How T =
    (I - A)^-1 is got is the implementation's (a substitution needs C^2 a
    column; the program's doubling ten C^3 products a chunk) and is NOT
    counted, nor is the causal half of a chunk's square taken off: the count
    is of the rule's own products."""
    s = shapes(cfg)
    C, K, V = s["C"], s["K"], s["V"]
    return float(s["Ll"] * s["H"] * (6 * C * K + 4 * C * V + 6 * K * V))


def expected_held_hit(cfg, rows: int) -> float:
    """Distinct HELD experts `rows` rows hit in one layer under uniform
    routing: E (1 - ((Er - k) / Er)^rows)."""
    s = shapes(cfg)
    return s["E"] * (1.0 - ((s["Er"] - s["k"]) / s["Er"]) ** rows)


def expert_decode_bytes(cfg, experts_hit: int, expert_rows: int) -> float:
    """Least bytes the routed experts' products of decode steps move: each
    HIT held expert's matrices once (`experts_hit`, summed over steps and
    layers) and each held (row, expert) pair's row in and out (`expert_rows`)."""
    s = shapes(cfg)
    return float(experts_hit * expert_bytes(cfg)
                 + expert_rows * 2 * s["d"] * BYTES[cfg["torch_dtype"]])


def decode_other_bytes(cfg) -> int:
    """What a decode step reads of the weights whatever the routing and the
    lanes: both mixers' matrices, routers, shared experts, the head."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return b * (s["Ll"] * linear_mixer_matmul_params(cfg) + s["La"] * attn_mixer_matmul_params(cfg)
                + s["L"] * (router_params(cfg) + shared_params(cfg)) + s["Vocab"] * s["d"])
