"""LFM2-MoE decoder (HF `model_type` `lfm2_moe`: Liquid's LFM2-8B-A1B and
LFM2-24B-A2B), for TRAINING: gated short convolutions in most layers, a
grouped-query attention layer every few, and a routed expert layer with no
shared expert and no capacity behind each.

One layer, x the residual stream (eps `rms_eps`, two RMSNorms a layer):

    h = x + op(N1(x));   y = h + ffn(N2(h))

- `op` of a `conv` layer: B, C, z = the three thirds of a W_in (d x 3d, no
  bias); u = B * z; v_t = sum_j k_j u_{t - (L - 1) + j}, j = 0 .. L - 1, a
  channel (a depthwise causal convolution of `conv_taps` L = 3 taps, zeros
  before position 0, no bias, so k_{L-1} weighs the position itself);
  op = (C * v) W_out. No activation anywhere in it.
- `op` of a `full_attention` layer: q (heads x head size), k and v (KV heads
  x head size), no bias; q and k each RMS-normed over the head size and THEN
  rotated (RoPE, the whole head, half-split pairs); causal softmax at head
  size^-0.5; W_o. The head size is d_model / heads: the source's config has
  no key for it.
- `ffn` of the first `n_dense_layers` layers: SwiGLU of width `d_ff`.
- `ffn` of every other layer: models/afmoe.py's `route` (s = sigmoid(a W_r)
  in float32; the `top_k` largest of s + b chosen, b entering the choice
  only; w = s[chosen] / sum s[chosen], times `route_scale`) and
  `expert_ffn_train`, that model's expert products in the form that has a
  backward pass: sum_e w_e SwiGLU_e(a), experts of width `moe_d_ff`, no
  shared expert, no capacity, no dropped token, no balance loss.

The ends: x_0 = E[token]; a final RMSNorm; the head is E transposed (tied).
The loss is the mean next-token cross-entropy and nothing else.

The program may hold a PART of the router's experts, `cfg.held_experts` =
(first, count) of `cfg.n_experts` (one chip's share where a layer's experts
are divided over chips): the router keeps its width and its `top_k`, a pair
whose expert is not held adds nothing, and nothing stands in for it
(`afmoe.expert_ffn`). `b` is a BUFFER of the source, moved during training
by a rule its config does not give: here it takes no gradient (it enters a
choice), and `buffers` names it so that the train step gives it no update
and no decay either.

What a train-step builder asks of a model (`train/step.py`): `init_params`,
`logical_axes`, `loss_fn`, `flops_per_token`; and, where it has them,
`buffers` and `loss_and_metrics` (the loss with the step's counters).

Params are one pytree with one dict a LAYER (`params["layers"][i]`), not
stacks over layers: the layers are of four kinds and are walked in Python,
each under its own `jax.checkpoint` (`llama.remat_layer`), so every leaf's
gradient is made once, where it lies; a stack read through a dynamic index
would have its whole gradient made anew in every layer's backward (1.9 GB for
the experts at LFM2-8B-A1B's widths and twelve layers).

Precision as models/llama.py has it: weights and activations in `cfg.dtype`,
matrix products accumulate in float32; norms, the convolution's sum, softmax,
the router's scores and the logits in float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import afmoe
from ray_tpu.models.afmoe import _dense, make_swiglu
from ray_tpu.models.llama import _attention, remat_layer
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

F32 = jnp.float32
CONV, FULL = "conv", "full_attention"
DENSE, MOE = "dense", "moe"
# scopes of a device trace, forward and backward (benchmark/lfm2_moe_spans.py
# reads them); `optimizer` is train/step.py's
SCOPE_ROUTE, SCOPE_EXPERTS = afmoe.SCOPE_ROUTE, afmoe.SCOPE_EXPERTS
SCOPE_CONV, SCOPE_ATTN, SCOPE_DENSE, SCOPE_HEAD = "short_conv", "attn", "dense_ffn", "head_loss"
# the step's counters (`loss_and_metrics`), each over the step's expert layers
COUNTERS = ("held_pairs", "expert_rows_max", "experts_hit", "second_passes")
# rows of the logits alive at once in the loss
HEAD_ROWS = 2048
# spread of a fresh model's choice bias: a sixth of the gap between
# neighbouring scores near the fourth of 32 (0.03): it moves choices and
# leaves the experts' loads near balance
BIAS_STD = 0.005

_8B_LAYERS = tuple(FULL if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The source's fields under this repo's names; the defaults are
    LFM2-8B-A1B's published values. Nothing is derived from another width
    but the head size, which the source derives too."""
    vocab_size: int = 65536
    d_model: int = 2048
    layer_types: Tuple[str, ...] = _8B_LAYERS
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 7168                      # intermediate_size (dense layers)
    moe_d_ff: int = 1792                  # moe_intermediate_size
    n_experts: int = 32
    top_k: int = 4                        # num_experts_per_tok
    held_experts: Tuple[int, int] = (0, 32)  # (first, count) of the router's experts held here
    conv_taps: int = 3                    # conv_L_cache
    rope_theta: float = 1000000.0
    route_scale: float = 1.0              # routed_scaling_factor
    route_norm: bool = True               # norm_topk_prob
    rms_eps: float = 1e-5
    max_seq_len: int = 128000
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"               # models/llama.py's `_attention`
    remat: bool = True
    route_scoring = "sigmoid"             # a constant of the family, no field: afmoe.route

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "held_experts", tuple(self.held_experts))
        bad = set(self.layer_types) - {CONV, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers counts leading layers of layer_types")
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError("held_experts is (first, count) within n_experts")
        if self.top_k > self.n_experts:
            raise ValueError("top_k experts a token of n_experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer kind, FFN kind) of each layer, in order."""
        return tuple((op, DENSE if i < self.n_dense_layers else MOE)
                     for i, op in enumerate(self.layer_types))

    @staticmethod
    def tiny(**kw) -> "Lfm2MoeConfig":
        """Test-sized, with the real shape of things: a leading dense layer,
        both mixers before an expert layer, grouped-query heads, a quarter
        of the router's experts held."""
        return Lfm2MoeConfig(**{**dict(
            vocab_size=256, d_model=64, n_dense_layers=1,
            layer_types=(CONV, FULL, CONV, CONV), n_heads=4, n_kv_heads=2,
            d_ff=128, moe_d_ff=32, n_experts=16, top_k=4, held_experts=(0, 4),
            max_seq_len=256), **kw})


# ------------------------------------------------------------------- params
def make_op(k, kind: str, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 4)
    if kind == CONV:
        return {"w_in": _dense(ks[0], (d, 3 * d), d, cfg.dtype),
                "conv": _dense(ks[1], (cfg.conv_taps, d), cfg.conv_taps, cfg.dtype),
                "w_out": _dense(ks[2], (d, d), d, cfg.dtype)}
    return {"wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
            "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
            "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
            "wo": _dense(ks[3], (h * hd, d), h * hd, cfg.dtype),
            "q_norm": jnp.ones((hd,), cfg.dtype), "k_norm": jnp.ones((hd,), cfg.dtype)}


def make_ffn(k, kind: str, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """A dense SwiGLU, or an expert layer: the router, its choice bias (a
    buffer; a small normal draw here so that it does move choices, where a
    trained model has what training left) and the HELD experts stacked, each
    from the key of its index among the router's experts: the shares of a
    layer are pieces of ONE layer."""
    d = cfg.d_model
    if kind == DENSE:
        return make_swiglu(k, d, cfg.d_ff, cfg.dtype)
    k_r, k_b, k_e = jax.random.split(k, 3)
    first, count = cfg.held_experts
    return {"router": _dense(k_r, (d, cfg.n_experts), d, cfg.dtype),
            "bias": BIAS_STD * jax.random.normal(k_b, (cfg.n_experts,), F32),
            "experts": jax.vmap(lambda e: make_swiglu(jax.random.fold_in(k_e, e), d, cfg.moe_d_ff,
                                                      cfg.dtype))(first + jnp.arange(count))}


def make_layer(k, kinds: Tuple[str, str], cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    k_op, k_ffn = jax.random.split(k)
    one = lambda: jnp.ones((cfg.d_model,), cfg.dtype)  # noqa: E731  (two buffers: a step donates each)
    return {"op_norm": one(), "ffn_norm": one(),
            "op": make_op(k_op, kinds[0], cfg), "ffn": make_ffn(k_ffn, kinds[1], cfg)}


def part_keys(key, cfg: Lfm2MoeConfig):
    """(embedding key, one key a layer)."""
    k_embed, k_layers = jax.random.split(key)
    return k_embed, jax.random.split(k_layers, cfg.n_layers)


def init_params(key, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    k_embed, k_layers = part_keys(key, cfg)
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype),
        "layers": [make_layer(k, kinds, cfg) for k, kinds in zip(k_layers, cfg.kinds)],
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }


def logical_axes(cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """Twin tree of logical axis names (parallel/sharding.py)."""
    ops = {CONV: {"w_in": ("embed", "mlp"), "conv": (None, "embed"), "w_out": ("mlp", "embed")},
           FULL: {"wq": ("embed", "heads"), "wk": ("embed", "kv"), "wv": ("embed", "kv"),
                  "wo": ("heads", "embed"), "q_norm": (None,), "k_norm": (None,)}}
    swiglu = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    ffns = {DENSE: swiglu,
            MOE: {"router": ("embed", None), "bias": (None,),
                  "experts": {k: ("expert",) + v for k, v in swiglu.items()}}}
    return {"embed": ("vocab", "embed"),
            "layers": [{"op_norm": ("embed",), "ffn_norm": ("embed",),
                        "op": ops[op], "ffn": ffns[ffn]} for op, ffn in cfg.kinds],
            "final_norm": ("embed",)}


def buffers(cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """Twin tree of bools: True at a leaf that is a buffer and no parameter
    (each expert layer's choice bias). A train step gives such a leaf no
    update and no weight decay."""
    flags = jax.tree.map(lambda _: False, logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    for layer, (_, ffn) in zip(flags["layers"], cfg.kinds):
        if ffn == MOE:
            layer["ffn"]["bias"] = True
    return flags


def num_params(cfg: Lfm2MoeConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# ----------------------------------------------------------------- the mixers
def short_conv(u, taps):
    """v_t = sum_j taps[j] * u_{t - (L - 1) + j} a channel, zeros before
    position 0: u (B, T, d), taps (L, d); the sum in float32."""
    L, T = taps.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(F32), ((0, 0), (L - 1, 0), (0, 0)))
    v = sum(taps[j].astype(F32) * padded[:, j:j + T] for j in range(L))
    return v.astype(u.dtype)


def conv_op(p, a, cfg: Lfm2MoeConfig):
    b, c, z = jnp.split(a @ p["w_in"], 3, axis=-1)
    return (c * short_conv(b * z, p["conv"])) @ p["w_out"]


def attn_op(p, a, cos_sin, cfg: Lfm2MoeConfig, mesh=None, rules=None):
    B, T, _ = a.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = rms_norm((a @ p["wq"]).reshape(B, T, h, hd), p["q_norm"], cfg.rms_eps)
    k = rms_norm((a @ p["wk"]).reshape(B, T, kvh, hd), p["k_norm"], cfg.rms_eps)
    v = (a @ p["wv"]).reshape(B, T, kvh, hd)
    o = _attention(apply_rope(q, *cos_sin), apply_rope(k, *cos_sin), v, cfg, mesh, rules)
    return o.reshape(B, T, h * hd) @ p["wo"]


def pair_chunk(cfg: Lfm2MoeConfig, rows: int) -> int:
    """(row, expert) pairs of the first pass of `afmoe.expert_ffn_train` for
    `rows` rows: what the held experts draw from a router in balance (rows *
    top_k * held / all) and a quarter more, up to the next 128, so that such
    a step takes that ONE pass a layer. A router further out of balance takes
    the second pass too: this sizes a pass and caps nothing."""
    share = rows * cfg.top_k * cfg.held_experts[1] / cfg.n_experts
    return -(-int(1.25 * share) // 128) * 128


def moe_ffn(p, m, cfg: Lfm2MoeConfig):
    """The expert layer for m (B, T, d): (out, rows a held expert (E,))."""
    rows = m.reshape(-1, cfg.d_model)
    with jax.named_scope(SCOPE_ROUTE):
        chosen, w = afmoe.route(rows, p["router"], p["bias"], cfg)
    with jax.named_scope(SCOPE_EXPERTS):
        out, sizes = afmoe.expert_ffn_train(rows, chosen, w, p["experts"], cfg,
                                            pair_chunk(cfg, rows.shape[0]))
    return out.reshape(m.shape), sizes


def _layer(layer, x, cos_sin, kinds, cfg: Lfm2MoeConfig, mesh=None, rules=None):
    """One layer of `kinds` = (mixer, FFN): (y, rows a held expert (E,), all
    zero behind a dense FFN)."""
    op, ffn = kinds
    a = rms_norm(x, layer["op_norm"], cfg.rms_eps)
    if op == CONV:
        with jax.named_scope(SCOPE_CONV):
            x = x + conv_op(layer["op"], a, cfg)
    else:
        with jax.named_scope(SCOPE_ATTN):
            x = x + attn_op(layer["op"], a, cos_sin, cfg, mesh, rules)
    m = rms_norm(x, layer["ffn_norm"], cfg.rms_eps)
    if ffn == DENSE:
        with jax.named_scope(SCOPE_DENSE):
            y = afmoe.swiglu(m, layer["ffn"], cfg)
        sizes = jnp.zeros((cfg.held_experts[1],), jnp.int32)
    else:
        y, sizes = moe_ffn(layer["ffn"], m, cfg)
    return x + y, sizes


def hidden(params, tokens, cfg: Lfm2MoeConfig, mesh=None, rules=None):
    """Token rows (B, T) -> (the last layer's output (B, T, d), rows a held
    expert of each layer (layers, E))."""
    x = params["embed"][tokens].astype(cfg.dtype)
    if mesh is not None and rules is not None:
        from ray_tpu.parallel.sharding import constraint

        x = constraint(x, mesh, ("batch", "seq", "act_embed"), rules)
    cos_sin = rope_frequencies(cfg.head_dim, tokens.shape[1], cfg.rope_theta)
    sizes = []
    for layer, kinds in zip(params["layers"], cfg.kinds):
        fn = functools.partial(_layer, kinds=kinds, cfg=cfg, mesh=mesh, rules=rules)
        if cfg.remat:
            fn = remat_layer(fn)
        x, s = fn(layer, x, cos_sin)
        sizes.append(s)
    return x, jnp.stack(sizes)


def head_nll(params, x, targets, cfg: Lfm2MoeConfig):
    """Summed next-token negative log-likelihood of x (N, d) against targets
    (N,): the final norm, the tied head (E transposed) and a float32
    log-softmax, `HEAD_ROWS` rows of logits at a time and each piece made
    again in the backward pass, so that no (N, vocabulary) array is alive.
    The head is cast once, as models/llama.py casts its own: the pieces'
    gradients add up in float32."""
    head = params["embed"].astype(F32)

    @jax.checkpoint
    def piece(total, xt):
        xp, tp = xt
        xp = rms_norm(xp, params["final_norm"], cfg.rms_eps)
        logp = jax.nn.log_softmax(xp.astype(F32) @ head.T, axis=-1)
        return total - jnp.take_along_axis(logp, tp[:, None], axis=-1).sum(), None

    n = x.shape[0]
    rows = HEAD_ROWS if n % HEAD_ROWS == 0 else n
    total, _ = jax.lax.scan(piece, jnp.zeros((), F32),
                            (x.reshape(n // rows, rows, -1), targets.reshape(n // rows, rows)))
    return total


def loss_and_metrics(params, batch, cfg: Lfm2MoeConfig, mesh=None, rules=None):
    """(mean next-token cross-entropy, the step's counters): batch
    {"tokens": (B, T + 1)}. The counters, over the expert layers: `held_pairs`
    the (row, expert) pairs whose expert is held, `expert_rows_max` the most
    rows any one held expert took in a layer, `experts_hit` the held experts
    that took any, summed over layers, `second_passes` the layers whose held
    pairs passed the first pass of `afmoe.expert_ffn_train` (`pair_chunk`)."""
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    x, sizes = hidden(params, inputs, cfg, mesh, rules)
    with jax.named_scope(SCOPE_HEAD):
        loss = head_nll(params, x.reshape(-1, cfg.d_model), targets.reshape(-1), cfg) / targets.size
    return loss, {"held_pairs": sizes.sum(), "expert_rows_max": sizes.max(),
                  "experts_hit": (sizes > 0).sum(),
                  "second_passes": (sizes.sum(axis=1) > pair_chunk(cfg, inputs.size)).sum()}


def loss_fn(params, batch, cfg: Lfm2MoeConfig, mesh=None, rules=None):
    return loss_and_metrics(params, batch, cfg, mesh, rules)[0]


def matmul_params_per_token(cfg: Lfm2MoeConfig) -> float:
    """Matrix weights a token is multiplied with, the head among them; of the
    experts what a router in balance sends to those HELD: top_k * held / all
    experts a token and expert layer."""
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ops = {CONV: 3 * d * d + d * d, FULL: 2 * d * h * hd + 2 * d * kvh * hd}
    pairs = cfg.top_k * cfg.held_experts[1] / cfg.n_experts
    ffns = {DENSE: 3 * d * cfg.d_ff, MOE: d * cfg.n_experts + pairs * 3 * d * cfg.moe_d_ff}
    return sum(ops[op] + ffns[ffn] for op, ffn in cfg.kinds) + d * cfg.vocab_size


def flops_per_token(cfg: Lfm2MoeConfig, seq_len: int) -> float:
    """Training FLOPs a token the step REQUIRES under a router in balance:
    6 a matrix weight, and causal attention once (6 * heads * head size *
    seq_len an attention layer). The live estimate of `instrument_step`; the
    benchmark counts the pairs really held (`held_pairs`)."""
    attn = 6.0 * cfg.n_heads * cfg.head_dim * seq_len * cfg.layer_types.count(FULL)
    return 6.0 * matmul_params_per_token(cfg) + attn
