"""Parameters, bytes and operations of the LongCat-Flash configuration (a
double layer of two latent attentions with a compressed query and two dense
FFNs, a shortcut branch of softmax-routed experts of which a share may be held
and of identity experts without weights), from its shapes alone:
`model_math.py`'s contract for a configuration file with the source's keys.
Nothing here imports the program.

`n_routed_experts` is the number of real experts whose weights the
configuration holds; `router_num_experts`, where the file has it, is the
number of real experts the ROUTER scores (the published config has one number
for both); `zero_expert_num` identity experts follow them in the router's
outputs. A decode step is bound by bytes: the cached rows of every attended
position in BOTH planes of every layer and W_kv_b's halves once a sublayer
(`mla_pair_decode_bytes`), the dense FFNs' weights once a step
(`ffn_dense_decode_bytes`), and in the shortcut branch each hit held expert's
matrices, the router and the rows the identity term moves
(`moe_zero_decode_bytes`); the counts come from the `engine.resolve` spans.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmark.model_math import BYTES


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"], "r": cfg["kv_lora_rank"],
            "rq": cfg["q_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "f": cfg["ffn_hidden_size"], "fe": cfg["expert_ffn_hidden_size"],
            "E": cfg["n_routed_experts"],
            "Er": cfg.get("router_num_experts", cfg["n_routed_experts"]),
            "Z": cfg["zero_expert_num"], "k": cfg["moe_topk"], "V": cfg["vocab_size"],
            "L": cfg["num_layers"]}


def router_width(cfg) -> int:
    """The router's outputs: the real experts it scores, then the identity ones."""
    s = shapes(cfg)
    return s["Er"] + s["Z"]


def kv_b_params(cfg) -> int:
    s = shapes(cfg)
    return s["r"] * s["h"] * (s["nope"] + s["v"])


def attn_matmul_params(cfg) -> int:
    """W_qa (d x rq), W_qb (rq x h (nope + rope)), W_kv_a (d x (r + rope)),
    W_kv_b (r x h (nope + v)), Wo (h v x d): one attention."""
    s = shapes(cfg)
    return (s["d"] * s["rq"] + s["rq"] * s["h"] * (s["nope"] + s["rope"])
            + s["d"] * (s["r"] + s["rope"]) + kv_b_params(cfg) + s["h"] * s["v"] * s["d"])


def sublayer_small_params(cfg) -> int:
    """A half-layer's two norms, the compressed query's norm and the latent's."""
    s = shapes(cfg)
    return 2 * s["d"] + s["rq"] + s["r"]


def dense_ffn_params(cfg) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["f"]


def expert_params(cfg) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["fe"]


def router_params(cfg) -> int:
    return shapes(cfg)["d"] * router_width(cfg)


def layer_params(cfg, experts: int = None) -> int:
    """One DOUBLE layer: two attentions, two dense FFNs and their norms, the
    router and its choice bias, `experts` real experts (the held ones)."""
    n = shapes(cfg)["E"] if experts is None else experts
    return (2 * (attn_matmul_params(cfg) + sublayer_small_params(cfg) + dense_ffn_params(cfg))
            + router_params(cfg) + router_width(cfg) + n * expert_params(cfg))


def embed_and_head_params(cfg) -> int:
    s = shapes(cfg)
    return 2 * s["V"] * s["d"]


def num_params(cfg) -> int:
    s = shapes(cfg)
    return s["L"] * layer_params(cfg) + embed_and_head_params(cfg) + s["d"]


def weight_bytes(cfg) -> int:
    """The choice bias is float32, everything else the served type."""
    b = BYTES[cfg["torch_dtype"]]
    return num_params(cfg) * b + shapes(cfg)["L"] * router_width(cfg) * (4 - b)


def expert_bytes(cfg) -> int:
    return expert_params(cfg) * BYTES[cfg["torch_dtype"]]


def latent_bytes_per_token(cfg) -> int:
    """What one position leaves in the cache, all layers: [c | k_r] a
    SUBLAYER, two a layer (the program's pool may pad the row; the model's
    bytes are these)."""
    s = shapes(cfg)
    return 2 * s["L"] * (s["r"] + s["rope"]) * BYTES[cfg["torch_dtype"]]


def expected_held_hit(cfg, rows: int) -> float:
    """Distinct HELD experts `rows` rows hit in one layer under uniform
    routing over the router's outputs: E (1 - ((W - k) / W)^rows)."""
    s, W = shapes(cfg), router_width(cfg)
    return s["E"] * (1.0 - ((W - s["k"]) / W) ** rows)


def real_experts_per_token(cfg, real_choices: int, zero_choices: int) -> float:
    """Real experts a token and layer, from the device's counts of chosen
    indices under and past the real experts."""
    total = real_choices + zero_choices
    return shapes(cfg)["k"] * real_choices / total if total else 0.0


def mla_pair_decode_bytes(cfg, ctx_tokens: int, steps: int) -> float:
    """Least bytes the decode steps' attentions read: the cached row of every
    attended position (`ctx_tokens`, summed over steps and live lanes) in
    both planes of every layer, and W_kv_b (absorbed into the query and
    applied to the attended latent) once a sublayer and step."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return float(ctx_tokens * latent_bytes_per_token(cfg)
                 + steps * 2 * s["L"] * kv_b_params(cfg) * b)


def attn_other_bytes(cfg, steps: int) -> float:
    """The other projections (W_qa, W_qb, W_kv_a, Wo) once a sublayer and step."""
    s = shapes(cfg)
    return float(steps * 2 * s["L"] * (attn_matmul_params(cfg) - kv_b_params(cfg))
                 * BYTES[cfg["torch_dtype"]])


def ffn_dense_decode_bytes(cfg, steps: int) -> float:
    """Least bytes the decode steps' dense FFNs read: their weights once a
    sublayer and step (a step's rows are a thousandth of them)."""
    return float(steps * 2 * shapes(cfg)["L"] * dense_ffn_params(cfg) * BYTES[cfg["torch_dtype"]])


def moe_zero_decode_bytes(cfg, experts_hit: int, expert_rows: int, steps: int,
                          lane_steps: int) -> float:
    """Least bytes the decode steps' shortcut branch moves: each HIT held
    expert's matrices once and each held (row, expert) pair's row in and out
    (`experts_hit`, `expert_rows`, summed over steps and layers), the router
    once a layer and step, and a live row in and out of the identity term a
    layer (`lane_steps` live rows summed over steps)."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return float(experts_hit * expert_bytes(cfg) + expert_rows * 2 * s["d"] * b
                 + steps * s["L"] * router_params(cfg) * b
                 + lane_steps * s["L"] * 2 * s["d"] * b)


def decode_step_bytes(cfg, held_hit_a_layer: float, ctx_tokens: float = 0.0) -> float:
    """Least bytes one decode step reads: every sublayer's attention and dense
    FFN, the router, each hit held expert's matrices, the head, and the
    cached rows of `ctx_tokens` attended positions (summed over lanes)."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return (b * (s["L"] * (2 * (attn_matmul_params(cfg) + dense_ffn_params(cfg))
                           + router_params(cfg)) + s["V"] * s["d"])
            + s["L"] * held_hit_a_layer * expert_bytes(cfg)
            + ctx_tokens * latent_bytes_per_token(cfg))
