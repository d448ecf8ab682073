"""Parameters, bytes and operations of the AFMoE configuration (sigmoid-routed
experts beside a shared one; sliding-window and full attention layers), from
its shapes alone: `model_math.py`'s contract for a configuration file with the
source's `afmoe` keys. Nothing here imports the program.

The expert products have two regimes. A decode step is bound by the bytes of
the experts its rows HIT (each hit expert's three matrices read once, whatever
the rows it got); an admission by the operations of its real (row, expert)
pairs. Both are counted from what the routing did, which the engine's device
counters report, never from the number of experts.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark.model_math import BYTES, roofline  # noqa: F401  (roofline: for the readers)

SLIDING, FULL = "sliding_attention", "full_attention"


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    types = cfg["layer_types"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "ns": cfg["num_shared_experts"], "V": cfg["vocab_size"],
            "W": cfg["sliding_window"], "L": len(types), "Ld": cfg["num_dense_layers"],
            "Lm": len(types) - cfg["num_dense_layers"],
            "Lw": types.count(SLIDING), "Lf": types.count(FULL)}


def attn_matmul_params(cfg) -> int:
    """Wq, Wg and Wo (each d x h hd) and Wk, Wv (d x kvh hd)."""
    s = shapes(cfg)
    return 3 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kvh"] * s["hd"]


def layer_small_params(cfg) -> int:
    """A layer's four norms and its q and k norms."""
    s = shapes(cfg)
    return 4 * s["d"] + 2 * s["hd"]


def dense_ffn_params(cfg) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["f"]


def expert_params(cfg) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["fe"]


def shared_params(cfg) -> int:
    return expert_params(cfg) * shapes(cfg)["ns"]


def router_params(cfg) -> int:
    s = shapes(cfg)
    return s["d"] * s["E"]


def dense_layer_params(cfg) -> int:
    return attn_matmul_params(cfg) + layer_small_params(cfg) + dense_ffn_params(cfg)


def expert_layer_params(cfg) -> int:
    """Attention, norms, router and its choice bias, every expert, the shared expert."""
    s = shapes(cfg)
    return (attn_matmul_params(cfg) + layer_small_params(cfg) + router_params(cfg) + s["E"]
            + s["E"] * expert_params(cfg) + shared_params(cfg))


def embed_and_head_params(cfg) -> int:
    s = shapes(cfg)
    assert not cfg["tie_word_embeddings"]
    return 2 * s["V"] * s["d"]


def num_params(cfg) -> int:
    s = shapes(cfg)
    return (s["Ld"] * dense_layer_params(cfg) + s["Lm"] * expert_layer_params(cfg)
            + embed_and_head_params(cfg) + s["d"])


def weight_bytes(cfg) -> int:
    """The choice bias is float32, everything else the served type."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return num_params(cfg) * b + s["Lm"] * s["E"] * (4 - b)


def expert_bytes(cfg) -> int:
    return expert_params(cfg) * BYTES[cfg["torch_dtype"]]


def expected_experts_hit(cfg, rows: int) -> float:
    """Distinct experts `rows` rows hit in one layer under uniform routing:
    E (1 - ((E - k) / E)^rows)."""
    s = shapes(cfg)
    return s["E"] * (1.0 - ((s["E"] - s["k"]) / s["E"]) ** rows)


def decode_other_bytes(cfg) -> int:
    """What a decode step reads of the weights whatever the routing: the dense
    layers, every layer's attention, the routers and shared experts, the head
    (the embedding lookup is a few rows)."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    per_expert_layer = attn_matmul_params(cfg) + router_params(cfg) + shared_params(cfg)
    return b * (s["Ld"] * (attn_matmul_params(cfg) + dense_ffn_params(cfg))
                + s["Lm"] * per_expert_layer + s["V"] * s["d"])


def decode_step_bytes(cfg, experts_hit_a_layer: float) -> float:
    """Least bytes of WEIGHTS one decode step reads when each expert layer's
    rows hit that many distinct experts."""
    return decode_other_bytes(cfg) + shapes(cfg)["Lm"] * experts_hit_a_layer * expert_bytes(cfg)


def expert_decode_bytes(cfg, experts_hit: int, expert_rows: int) -> float:
    """Least bytes the routed experts' products of decode steps move: each
    HIT expert's matrices once (`experts_hit`, summed over steps and layers)
    and each (row, expert) pair's row in and out (`expert_rows`, summed alike)."""
    s = shapes(cfg)
    return float(experts_hit * expert_bytes(cfg)
                 + expert_rows * 2 * s["d"] * BYTES[cfg["torch_dtype"]])


def expert_flops_per_pair(cfg) -> float:
    """One row through one expert: gate, up and down, two operations a weight."""
    return 2.0 * expert_params(cfg)


def expert_prefill_flops(cfg, prompt_tokens: int) -> float:
    """The routed experts' operations of an admission of `prompt_tokens` REAL
    tokens: top_k pairs a token in every expert layer. Padding rows are work
    the program does and this does not count."""
    s = shapes(cfg)
    return prompt_tokens * s["Lm"] * s["k"] * expert_flops_per_pair(cfg)


def expert_prefill_bytes(cfg, prompt_tokens: int) -> float:
    """Least bytes of the same: each pair's row in and out and, once the rows
    outnumber the experts by far, every expert's matrices once a layer."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return float(s["Lm"] * (s["E"] * expert_bytes(cfg) + prompt_tokens * s["k"] * 2 * s["d"] * b))


def kv_bytes_per_token(cfg) -> int:
    """K and V of one position in the block pool: the full layers only."""
    s = shapes(cfg)
    return 2 * s["Lf"] * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]


def ring_bytes_per_lane(cfg) -> int:
    """The window layers' K and V rings of one lane, whatever its context."""
    s = shapes(cfg)
    return 2 * s["Lw"] * s["W"] * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]


def attn_decode_read_bytes(cfg, context: int) -> float:
    """Least K/V bytes one lane's decode step reads at `context` positions:
    min(context, window) positions in each window layer, all in each full one."""
    s = shapes(cfg)
    row = 2 * s["kvh"] * s["hd"] * BYTES[cfg["torch_dtype"]]
    return float(row * (s["Lw"] * min(context, s["W"]) + s["Lf"] * context))


def attn_flops_per_token(cfg, context: int) -> float:
    """QK^T and PV of one token over `context` earlier positions, the window
    layers over no more than the window."""
    s = shapes(cfg)
    return 4.0 * s["h"] * s["hd"] * (s["Lw"] * min(context, s["W"]) + s["Lf"] * context)


def matmul_params_per_token(cfg) -> int:
    """Weights one token is multiplied with: attention, dense FFNs, router,
    top_k experts and the shared one in each expert layer, the head."""
    s = shapes(cfg)
    return (s["L"] * attn_matmul_params(cfg) + s["Ld"] * dense_ffn_params(cfg)
            + s["Lm"] * (router_params(cfg) + s["k"] * expert_params(cfg) + shared_params(cfg))
            + s["d"] * s["V"])


def forward_flops_per_token(cfg, context: float = 0.0) -> float:
    return 2.0 * matmul_params_per_token(cfg) + attn_flops_per_token(cfg, int(context))
