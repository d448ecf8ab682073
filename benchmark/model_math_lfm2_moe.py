"""Parameters, operations and bytes of an LFM2-MoE configuration, from its
shapes alone: the yardstick's arithmetic for `lfm2-8b-a1b.train`. Nothing here
imports the program. A configuration is the dict of a file under
`benchmark/configs/` (the source's own key names; `num_experts` the experts
HELD, `router_num_experts` the router's width where the two differ).

One layer (HF `Lfm2MoeForCausalLM`, d the hidden size):
- mixer of a `conv` layer: W_in d x 3d, W_out d x d, `conv_L_cache` taps a
  channel; of a `full_attention` layer: q and o d x d (heads x d / heads),
  k and v d x (KV heads x d / heads), a norm weight a head entry for q and k;
- FFN of the first `num_dense_layers`: SwiGLU, 3 x d x `intermediate_size`;
  of the others: a router d x `router_num_experts`, its choice bias, and
  SwiGLU experts of 3 x d x `moe_intermediate_size` each;
- two norm weights a layer; the embedding, tied to the head; a last norm.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmark.model_math import BYTES, roofline  # noqa: F401  (roofline: for the readers)

CONV, FULL = "conv", "full_attention"
DENSE, MOE = "dense", "moe"
# ragged products of one expert layer in one train step: three forward (gate,
# up, down), and for each of them backward one for its input's gradient and
# one for its matrices'. Remat makes the forward's three again: not counted
PRODUCTS = 9


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kvh": cfg["num_key_value_heads"], "hd": d // h,
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "V": cfg["vocab_size"], "held": cfg["num_experts"],
            "E": cfg.get("router_num_experts", cfg["num_experts"]),
            "k": cfg["num_experts_per_tok"], "taps": cfg["conv_L_cache"]}


def kinds(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    return [(op, DENSE if i < cfg["num_dense_layers"] else MOE)
            for i, op in enumerate(cfg["layer_types"])]


def published(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration with every reduced key at its published value: the
    whole model, all of the router's experts held."""
    out = {**cfg, **cfg.get("published", {})}
    out["router_num_experts"] = out["num_experts"]
    return out


def op_matmul_params(cfg: Dict[str, Any], op: str) -> int:
    s = shapes(cfg)
    if op == CONV:
        return 3 * s["d"] * s["d"] + s["d"] * s["d"]
    return 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kvh"] * s["hd"]


def expert_params(cfg: Dict[str, Any]) -> int:
    s = shapes(cfg)
    return 3 * s["d"] * s["fe"]


def layer_params(cfg: Dict[str, Any], kind: Tuple[str, str]) -> int:
    s = shapes(cfg)
    op, ffn = kind
    small = s["taps"] * s["d"] if op == CONV else 2 * s["hd"]
    if ffn == DENSE:
        width = 3 * s["d"] * s["f"]
    else:
        width = s["d"] * s["E"] + s["E"] + s["held"] * expert_params(cfg)
    return op_matmul_params(cfg, op) + small + width + 2 * s["d"]


def num_params(cfg: Dict[str, Any]) -> int:
    """Every leaf of the program's tree: the tied embedding once, the choice
    bias (a buffer) among them."""
    s = shapes(cfg)
    return s["V"] * s["d"] + sum(layer_params(cfg, k) for k in kinds(cfg)) + s["d"]


def state_bytes(cfg: Dict[str, Any]) -> int:
    """Weights, gradients and both Adam moments in the stated type."""
    return 4 * num_params(cfg) * BYTES[cfg["torch_dtype"]]


def matmul_params_outside_experts(cfg: Dict[str, Any]) -> int:
    """Matrix weights every token is multiplied with: the mixers, the dense
    FFN, the routers and the head (the embedding transposed; the lookup is no
    product). The experts are counted by the pairs they are given."""
    s = shapes(cfg)
    ffn = {DENSE: 3 * s["d"] * s["f"], MOE: s["d"] * s["E"]}
    return sum(op_matmul_params(cfg, op) + ffn[f] for op, f in kinds(cfg)) + s["d"] * s["V"]


def attention_layers(cfg: Dict[str, Any]) -> int:
    return sum(1 for op, _ in kinds(cfg) if op == FULL)


def expert_layers(cfg: Dict[str, Any]) -> int:
    return sum(1 for _, ffn in kinds(cfg) if ffn == MOE)


def flash_step_flops(cfg: Dict[str, Any], batch: int, seq_len: int) -> float:
    """Operations the attention of one train step requires, the ATTENTION
    layers alone (`model_math.flash_step_flops` counts `num_hidden_layers` of
    them, the dense decoder's): the forward's two matrix products and the
    backward's five (S again, dP, dV, dK, dQ), each 2 x T x T x head size a
    head, halved because the mask is causal. Remat's second forward is not
    counted as required."""
    s = shapes(cfg)
    per_head = 2.0 * seq_len * seq_len * s["hd"] / 2.0
    return attention_layers(cfg) * batch * s["h"] * per_head * (2 + 5)


def flash_step_bytes(cfg: Dict[str, Any], batch: int, seq_len: int) -> float:
    """Least bytes the same attention moves: forward reads Q, K, V and writes
    O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    q = batch * seq_len * s["h"] * s["hd"] * b
    kv = batch * seq_len * s["kvh"] * s["hd"] * b
    return float(attention_layers(cfg) * ((2 * q + 2 * kv) + (4 * q + 4 * kv)))


def ragged_flops(cfg: Dict[str, Any], held_pairs: float) -> float:
    """Operations the nine ragged products require for `held_pairs` (row,
    expert) pairs: 2 x d x `moe_intermediate_size` a pair and product."""
    s = shapes(cfg)
    return 2.0 * PRODUCTS * s["d"] * s["fe"] * held_pairs


def ragged_bytes(cfg: Dict[str, Any], held_pairs: float, layer_steps: int) -> float:
    """Least bytes the same products move: each reads its two operands and
    writes its result once. A pair's rows are d or `moe_intermediate_size`
    wide (every product has one of each among a row operand and its result,
    or two row operands); the held experts' matrix is read, or its gradient
    written, once a product, expert layer and step (`layer_steps` = expert
    layers x steps)."""
    s = shapes(cfg)
    b = BYTES[cfg["torch_dtype"]]
    rows = PRODUCTS * (s["d"] + s["fe"]) * held_pairs
    matrices = PRODUCTS * s["held"] * s["d"] * s["fe"] * layer_steps
    return float((rows + matrices) * b)


def train_flops(cfg: Dict[str, Any], tokens: float, seq_len: int, held_pairs: float) -> float:
    """Operations the forward and backward passes REQUIRE for `tokens` trained
    tokens of which the held experts were given `held_pairs` pairs in all: 6 a
    matrix weight outside the experts and a token; causal attention once
    (forward 4 x T x heads x head size / 2 a token and layer, backward twice
    that); 18 x d x `moe_intermediate_size` a held pair. Recomputation is not
    counted, so the number is the same whatever implements the layers."""
    s = shapes(cfg)
    attn = 6.0 * attention_layers(cfg) * s["h"] * s["hd"] * seq_len
    return (6.0 * matmul_params_outside_experts(cfg) + attn) * tokens + ragged_flops(cfg, held_pairs)
