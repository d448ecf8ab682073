"""Parameters, bytes and operations of the latent-attention configuration
(`sarvam_mla`: MLA over a cached row [c | k_r], a leading dense layer, then
sigmoid-routed experts of which a share may be held), from its shapes alone:
`model_math.py`'s contract for a configuration file with the source's keys.
Nothing here imports the program.

`num_experts` is the number of experts whose weights the configuration holds;
`router_num_experts`, where the file has it, is the router's width (the
published config has one number for both). Latent attention has two regimes.
An admission is bound by the operations of its causal (query, key) pairs, 2 x
heads x (nope + rope + v) a pair and layer whatever computes them. A decode
step's attention is bound by the bytes of the cached rows it reads, (latent +
rope) numbers a position and layer whatever the number of heads, plus W_kv_b
once a layer and step. Both are counted from what the plan says was attended
(`prompt_pairs`, `ctx_tokens` of the `engine.dispatch` spans).
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark.model_math import BYTES


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "f": cfg["intermediate_size"],
            "fe": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
            "Er": cfg.get("router_num_experts", cfg["num_experts"]),
            "k": cfg["num_experts_per_tok"], "ns": cfg["num_shared_experts"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "Ld": cfg["first_k_dense_replace"],
            "Lm": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]}


def attn_matmul_params(cfg) -> int:
    """Wq (d x h (nope + rope)), W_kv_a (d x (r + rope)), W_kv_b (r x h (nope
    + v)), Wo (h v x d)."""
    s = shapes(cfg)
    return (s["d"] * s["h"] * (s["nope"] + s["rope"]) + s["d"] * (s["r"] + s["rope"])
            + kv_b_params(cfg) + s["h"] * s["v"] * s["d"])


def kv_b_params(cfg) -> int:
    s = shapes(cfg)
    return s["r"] * s["h"] * (s["nope"] + s["v"])


def layer_small_params(cfg) -> int:
    """A layer's two norms, the latent's norm, the query heads' and k_r's."""
    s = shapes(cfg)
    return 2 * s["d"] + s["r"] + (s["nope"] + s["rope"]) + s["rope"]


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
    return s["d"] * s["Er"]


def dense_layer_params(cfg) -> int:
    return attn_matmul_params(cfg) + layer_small_params(cfg) + dense_ffn_params(cfg)


def expert_layer_params(cfg) -> int:
    """Attention, norms, router and its choice bias, the held experts, the shared one."""
    s = shapes(cfg)
    return (attn_matmul_params(cfg) + layer_small_params(cfg) + router_params(cfg) + s["Er"]
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
    return num_params(cfg) * b + s["Lm"] * s["Er"] * (4 - b)


def expert_bytes(cfg) -> int:
    return expert_params(cfg) * BYTES[cfg["torch_dtype"]]


def latent_bytes_per_token(cfg) -> int:
    """What one position leaves in the cache, all layers: [c | k_r] a layer
    (the program's pool may pad the row; the model's bytes are these)."""
    s = shapes(cfg)
    return s["L"] * (s["r"] + s["rope"]) * BYTES[cfg["torch_dtype"]]


def expected_held_hit(cfg, rows: int) -> float:
    """Distinct HELD experts `rows` rows hit in one layer under uniform
    routing: E (1 - ((Er - k) / Er)^rows)."""
    s = shapes(cfg)
    return s["E"] * (1.0 - ((s["Er"] - s["k"]) / s["Er"]) ** rows)


def decode_other_bytes(cfg) -> int:
    """What a decode step reads of the weights whatever the routing."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    per_expert_layer = attn_matmul_params(cfg) + router_params(cfg) + shared_params(cfg)
    return b * (s["Ld"] * (attn_matmul_params(cfg) + dense_ffn_params(cfg))
                + s["Lm"] * per_expert_layer + s["V"] * s["d"])


def decode_step_bytes(cfg, held_hit_a_layer: float, ctx_tokens: float = 0.0) -> float:
    """Least bytes one decode step reads: the weights above, each hit held
    expert's matrices, and the cached rows of `ctx_tokens` attended positions
    (summed over lanes)."""
    return (decode_other_bytes(cfg) + shapes(cfg)["Lm"] * held_hit_a_layer * expert_bytes(cfg)
            + ctx_tokens * latent_bytes_per_token(cfg))


def expert_decode_bytes(cfg, experts_hit: int, expert_rows: int) -> float:
    """Least bytes the routed experts' products of decode steps move: each
    HIT held expert's matrices once (`experts_hit`, summed over steps and
    layers) and each held (row, expert) pair's row in and out (`expert_rows`)."""
    s = shapes(cfg)
    return float(experts_hit * expert_bytes(cfg)
                 + expert_rows * 2 * s["d"] * BYTES[cfg["torch_dtype"]])


def held_pairs_per_token(cfg) -> float:
    """(row, expert) pairs a token has on HELD experts in one expert layer
    under even routing: top_k times the held share of the router's width."""
    s = shapes(cfg)
    return s["k"] * s["E"] / s["Er"]


def held_prefill_flops(cfg, prompt_tokens: int) -> float:
    """The held experts' operations of an admission of `prompt_tokens` REAL
    tokens: a pair costs three products of d x fe, two operations a number,
    in every expert layer."""
    s = shapes(cfg)
    return prompt_tokens * s["Lm"] * held_pairs_per_token(cfg) * 2.0 * expert_params(cfg)


def mla_prefill_flops(cfg, prompt_pairs: int) -> float:
    """The admission attention's operations over `prompt_pairs` causal (query,
    key) pairs: a pair costs each head one score product over nope + rope
    numbers and one value product over v, two operations a number, in every
    layer. The projections and the expansion of keys and values are not in it."""
    s = shapes(cfg)
    return float(prompt_pairs) * 2.0 * s["h"] * (s["nope"] + s["rope"] + s["v"]) * s["L"]


def mla_decode_bytes(cfg, ctx_tokens: int, steps: int) -> float:
    """Least bytes the decode steps' attention reads: the cached row of every
    attended position (`ctx_tokens`, summed over steps and live lanes) in
    every layer, and W_kv_b (absorbed into the query and applied to the
    attended latent) once a layer and step."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return float(ctx_tokens * latent_bytes_per_token(cfg) + steps * s["L"] * kv_b_params(cfg) * b)
