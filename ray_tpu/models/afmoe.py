"""AFMoE-style decoder (HF `model_type` `afmoe`: Arcee's Trinity family):
many small routed experts beside one shared expert, and sliding-window
attention layers with a full one every few layers.

One layer, x the residual stream (four RMSNorms a layer, one before and one
after each half):

    h = x + N2(Attn(N1(x)));   y = h + N4(FFN(N3(h)))

- `Attn(u)`: q = Wq u (heads x head size), k = Wk u, v = Wv u (KV heads),
  g = Wg u (as wide as q); q and k each RMS-normed over the head size; in a
  `sliding_attention` layer q and k take RoPE and position i attends j with
  0 <= i - j < `sliding_window`; in a `full_attention` layer there is NO
  position term and the mask is causal only; scores times head_dim^-0.5;
  out = Wo (concat(heads) * sigmoid(g)).
- `FFN` of the first `n_dense_layers` layers: SwiGLU of width `d_ff`.
- `FFN` of every other layer: s = sigmoid(Wr u) in float32, one score an
  expert (`route_scoring` "softmax", another family's: a softmax over all
  the experts, and no bias); the `top_k` largest of s + b are chosen, b a per-expert bias that
  enters the CHOICE only; w = s[chosen] / (sum s[chosen] + 1e-20) *
  `route_scale`; out = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u), experts of
  width `moe_d_ff`, the shared one `moe_d_ff * n_shared_experts`. No
  capacity, no dropped token: `route` and `expert_ffn` are the ONE router
  and the ONE expert product of the plain forward, the admission and the
  decode step; a train step (models/lfm2_moe.py) takes `route` as it is and
  `expert_ffn_train`, the same sort and products in a form with a backward. The expert products are over the (row, expert) pairs the
  router chose, sorted by expert, as ragged products (`ops/grouped_matmul`):
  work in proportion to `top_k`, never to the number of experts.

The ends: x_0 = E[token] (times sqrt(d_model) when `mup_enabled`), a final
RMSNorm, an untied head.

Which kind a layer is comes from `layer_types` (window or full, and with it
RoPE or none) and `n_dense_layers` (dense or expert), never from a name in
the code. Params are one pytree: `layers` stacked over all layers (norms and
attention), `dense` over the dense layers, `moe` over the expert layers;
`run_layers` walks the layers as runs of one kind, each run one `lax.scan`.

Precision as models/llama.py has it: weights and activations in `cfg.dtype`,
matrix products accumulate in float32, norms, softmax, the router's scores
and the logits in float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import _qkv
from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, MOE = "dense", "moe"
# scopes of a device trace (benchmark/afmoe_spans.py reads them); they lie
# inside the macro-step's admit_prefill / decode_chunk and name neither
SCOPE_ROUTE, SCOPE_EXPERTS, SCOPE_SHARED, SCOPE_WINDOW, SCOPE_FULL = (
    "moe_route", "moe_experts", "moe_shared", "attn_window", "attn_full")
# (row, expert) pairs of one pass of `expert_ffn`'s products: more (an
# admission) go through in chunks of this many, fewer (a decode step) at once
PAIR_CHUNK = 4096

_MINI_LAYERS = tuple(FULL if i % 4 == 3 else SLIDING for i in range(32))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The source's fields under this repo's names; the defaults are
    Trinity-Mini's published values. Nothing is derived from another
    width."""
    vocab_size: int = 200192
    d_model: int = 2048
    layer_types: Tuple[str, ...] = _MINI_LAYERS
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 6144                      # intermediate_size (dense layers)
    moe_d_ff: int = 1024                  # moe_intermediate_size
    n_experts: int = 128
    top_k: int = 8                        # num_experts_per_tok
    n_shared_experts: int = 1
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    route_scale: float = 2.826
    route_norm: bool = True
    mup_enabled: bool = True
    rms_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    route_scoring = "sigmoid"             # a constant of the family, no field: `route`

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers counts leading layers of layer_types")
        if self.top_k > self.n_experts:
            raise ValueError("top_k experts a token of n_experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_window_layers(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def n_full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def runs(self) -> Tuple[Tuple[str, str, int, int, int, int], ...]:
        """Maximal runs of one kind of layer: (attention kind, FFN kind,
        index of the run's first layer among all layers, among its attention
        kind, among its FFN kind, layers in the run)."""
        out, seen = [], {SLIDING: 0, FULL: 0, DENSE: 0, MOE: 0}
        for g, attn in enumerate(self.layer_types):
            ffn = DENSE if g < self.n_dense_layers else MOE
            if out and out[-1][:2] == [attn, ffn]:
                out[-1][5] += 1
            else:
                out.append([attn, ffn, g, seen[attn], seen[ffn], 1])
            seen[attn] += 1
            seen[ffn] += 1
        return tuple(tuple(r) for r in out)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the router's experts whose weights this
        program holds (`expert_ffn`): all of them."""
        return 0, self.n_experts

    @property
    def model_module(self):
        from ray_tpu.models import afmoe

        return afmoe

    @property
    def decode_module(self):
        from ray_tpu.models import afmoe_decode

        return afmoe_decode

    @staticmethod
    def tiny(**kw) -> "AfmoeConfig":
        """Test-sized, with the real shape of things: two leading dense
        layers, every pair of kinds, grouped-query heads, a window of 8."""
        return AfmoeConfig(**{**dict(
            vocab_size=512, d_model=64, n_dense_layers=2,
            layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, moe_d_ff=32,
            n_experts=16, top_k=4, sliding_window=8, max_seq_len=256), **kw})


# ------------------------------------------------------------------- params
def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) * (fan_in ** -0.5)).astype(dtype)


def make_layer(k, cfg: AfmoeConfig) -> Dict[str, Any]:
    """What every layer has: its four norms and its attention."""
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(k, 5)
    one = lambda n: jnp.ones((n,), cfg.dtype)  # noqa: E731
    return {
        "attn_norm": one(d), "attn_post_norm": one(d),
        "ffn_norm": one(d), "ffn_post_norm": one(d),
        "q_norm": one(hd), "k_norm": one(hd),
        "wq": _dense(ks[0], (d, h * hd), d, cfg.dtype),
        "wk": _dense(ks[1], (d, kvh * hd), d, cfg.dtype),
        "wv": _dense(ks[2], (d, kvh * hd), d, cfg.dtype),
        "wg": _dense(ks[3], (d, h * hd), d, cfg.dtype),
        "wo": _dense(ks[4], (h * hd, d), h * hd, cfg.dtype),
    }


def make_swiglu(k, d: int, f: int, dtype, lead: Tuple[int, ...] = ()) -> Dict[str, Any]:
    ks = jax.random.split(k, 3)
    return {"w_gate": _dense(ks[0], lead + (d, f), d, dtype),
            "w_up": _dense(ks[1], lead + (d, f), d, dtype),
            "w_down": _dense(ks[2], lead + (f, d), f, dtype)}


def make_moe(k, cfg: AfmoeConfig) -> Dict[str, Any]:
    """One expert layer: the router, its choice bias (a buffer of the
    source, zero in a fresh model), the experts stacked on a leading axis,
    the shared expert."""
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    k_r, k_e, k_s = jax.random.split(k, 3)
    return {"router": _dense(k_r, (d, E), d, cfg.dtype),
            "bias": jnp.zeros((E,), F32),
            "experts": make_swiglu(k_e, d, f, cfg.dtype, (cfg.held_experts[1],)),
            "shared": make_swiglu(k_s, d, f * cfg.n_shared_experts, cfg.dtype)}


def part_keys(key, cfg: AfmoeConfig):
    """(embedding key, head key, one key a layer, a dense FFN, an expert layer)."""
    k_embed, k_head, k_l, k_d, k_m = jax.random.split(key, 5)
    return (k_embed, k_head, jax.random.split(k_l, cfg.n_layers),
            jax.random.split(k_d, cfg.n_dense_layers), jax.random.split(k_m, cfg.n_moe_layers))


def init_params(key, cfg: AfmoeConfig) -> Dict[str, Any]:
    k_embed, k_head, k_l, k_d, k_m = part_keys(key, cfg)
    return {
        "embed": _dense(k_embed, (cfg.vocab_size, cfg.d_model), cfg.d_model, cfg.dtype),
        "layers": jax.vmap(functools.partial(make_layer, cfg=cfg))(k_l),
        DENSE: jax.vmap(lambda k: make_swiglu(k, cfg.d_model, cfg.d_ff, cfg.dtype))(k_d),
        MOE: jax.vmap(functools.partial(make_moe, cfg=cfg))(k_m),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": _dense(k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, cfg.dtype),
    }


def num_params(cfg: AfmoeConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


# ------------------------------------------------------------- the FFN half
def swiglu(m, p, cfg: AfmoeConfig):
    gate = jax.nn.silu((m @ p["w_gate"]).astype(F32)).astype(cfg.dtype)
    return (gate * (m @ p["w_up"])) @ p["w_down"]


def route(u, router, bias, cfg: AfmoeConfig):
    """The router, for rows u (N, d): (chosen experts (N, top_k) int32,
    their weights (N, top_k) float32). The scores, float32, are by
    `cfg.route_scoring` a sigmoid of each expert's logit or a softmax over
    all of them; the bias (None: the router has none) moves the choice and
    never the weight; the chosen scores normalised to sum 1 (`route_norm`)
    and scaled by `route_scale`."""
    logits = jnp.einsum("nd,de->ne", u, router, preferred_element_type=F32)
    if cfg.route_scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores if bias is None else scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.route_norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * cfg.route_scale


def _sorted_pairs(u, chosen, experts, at, cfg: AfmoeConfig, live=None):
    """What `expert_ffn` and `expert_ffn_train` share: the sort of the
    (row, expert) pairs into groups and the three ragged products over
    sorted pairs. Returns (order (N * top_k,) the pairs sorted by group, those
    in no group last; sizes (E,) pairs a held expert; held (N * top_k,) bool
    or None where every expert is held; products(pairs, sizes) -> SwiGLU of
    each pair's expert, the group sizes being layer `at`'s in the stack)."""
    N, k = chosen.shape
    first, E = cfg.held_experts
    n_layers = experts["w_gate"].shape[0]
    pair_expert = chosen.reshape(-1)
    held = None
    if (first, E) != (0, cfg.n_experts):
        held = (pair_expert >= first) & (pair_expert < first + E)
        pair_expert = jnp.where(held, pair_expert - first, E)
    if live is not None:
        pair_expert = jnp.where(jnp.repeat(live, k), pair_expert, E)
    order = jnp.argsort(pair_expert, stable=True)
    sizes = jnp.sum(pair_expert[:, None] == jnp.arange(E)[None, :], axis=0, dtype=jnp.int32)
    stack = lambda name: experts[name].reshape((n_layers * E,) + experts[name].shape[2:])  # noqa: E731

    def products(pairs, sizes, in_group=None):
        """SwiGLU of each pair's expert for `pairs` (indices into the N *
        top_k), sorted by group; `sizes` (E,) pairs a group of layer `at`.
        `in_group` (bool a pair; a backward pass gives it) cuts the pairs in
        no group off from u's gradient: a ragged product leaves their rows
        of its result unwritten, forward and backward alike."""
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * E,), jnp.int32), sizes, (at * E,))
        rows = u[pairs // k]
        if in_group is not None:
            rows = jnp.where(in_group[:, None], rows, 0)
        gate = jax.nn.silu(grouped_matmul(rows, stack("w_gate"), groups).astype(F32))
        act = gate.astype(cfg.dtype) * grouped_matmul(rows, stack("w_up"), groups)
        return grouped_matmul(act, stack("w_down"), groups)

    return order, sizes, held, products


def expert_ffn(u, chosen, w, experts, at, cfg: AfmoeConfig, live=None, chunk: int = PAIR_CHUNK):
    """sum_e w_e SwiGLU_e(u) over each row's chosen experts: u (N, d),
    chosen and w (N, top_k). The N * top_k (row, expert) pairs are sorted
    by expert and each expert multiplies its own rows and no others: three
    ragged products over the sorted rows. `live` (N,) bool takes rows out:
    their pairs sort behind every expert's and belong to no group, so
    they cost no product and no expert's weights are read for them.

    The program may hold a PART of the router's experts: `cfg.held_experts`
    is (first, count) of the router's `cfg.n_experts`, and the stacks have
    `count` experts a layer (one chip's share where a layer's experts are
    divided over chips). A pair whose expert is not held is out as a row
    that is not live is: behind every group, in none, no product, no weight
    read, and nothing added for it. `w` stays the router's, normalised over
    all the chosen, held or not: what the other chips' experts would add
    is left out and nothing stands in for it.

    `experts` is the STACK of every expert layer's experts, (layers, E, ...),
    and `at` says which layer's are meant: the layer index is folded into
    the group axis (layers * E groups, all but this layer's E empty), so
    the products read the stacked parameter where it lies. Slicing one
    layer's experts out first is a copy of all of them (1.6 GB a layer at
    Trinity-Mini's widths) in every decode step: a ragged product is a
    kernel, and no slice fuses into a kernel's operand.

    More than `chunk` pairs (an admission; a decode step has a few dozen)
    go through `_expert_ffn_in_chunks`: the same sums, and what belongs to
    no group is sorted and nothing else.
    Returns (out (N, d), rows a held expert (E,) int32)."""
    N, k = chosen.shape
    order, sizes, held, products = _sorted_pairs(u, chosen, experts, at, cfg, live)

    if N * k > chunk:
        out = _expert_ffn_in_chunks(w, order, sizes, products, u.shape[1], chunk)
        return out.astype(cfg.dtype), sizes
    y = products(order, sizes)                              # (N * k, d), sorted by expert
    # back to the rows' order, each pair beside its weight; a row of a
    # ragged product past the last group holds nothing meaningful
    back = jnp.argsort(order)
    y = y[back].reshape(N, k, -1)
    if live is not None:
        y = jnp.where(live[:, None, None], y, 0)
    if held is not None:
        y = jnp.where(held.reshape(N, k, 1), y, 0)
    out = jnp.einsum("nkd,nk->nd", y, w.astype(F32), preferred_element_type=F32)
    return out.astype(cfg.dtype), sizes


def _expert_ffn_in_chunks(w, order, sizes, products, d: int, chunk: int):
    """`expert_ffn`'s sum for any number of pairs, `chunk` sorted pairs at a
    time and ONLY as far as the pairs in a group reach (the sort puts them
    in front): each pass gathers its pairs' rows of u, multiplies them with
    the group sizes clipped to the chunk (a group that a chunk's edge cuts
    is read on both sides of it), weights each result by its pair's w and
    adds it to its row of a float32 (N, d) sum. Nothing top_k times as long
    as u is built: a pair in no group costs its sort key. `products(pairs,
    sizes)` is `expert_ffn`'s, d its width; returns the sum (N, d) float32."""
    N, k = w.shape
    # a whole last chunk, so that no slice starts early
    order = jnp.pad(order, (0, -(N * k) % chunk))
    w = w.astype(F32).reshape(-1)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    n_in = ends[-1]

    def add_chunk(c, total):
        lo = c * chunk
        pairs = jax.lax.dynamic_slice(order, (lo,), (chunk,))
        y = products(pairs, jnp.clip(ends - lo, 0, chunk) - jnp.clip(starts - lo, 0, chunk))
        # a row of a ragged product past the last group holds nothing
        # meaningful: it is sent to row N, which there is not
        rows = jnp.where(lo + jnp.arange(chunk) < n_in, pairs // k, N)
        return total.at[rows].add(y.astype(F32) * w[pairs][:, None], mode="drop")

    return jax.lax.fori_loop(0, -(-n_in // chunk), add_chunk, jnp.zeros((N, d), F32))


def _pairs_sum(u, chosen, w, experts, cfg: AfmoeConfig, lo: int, width: int):
    """`expert_ffn`'s sum over the sorted pairs [lo, lo + width) alone, by the
    same sort and the same products: (float32 (N, d), rows a held expert
    (E,)). `experts` is ONE layer's, (E, ...): a stack of one layer."""
    N, k = chosen.shape
    order, sizes, _, products = _sorted_pairs(
        u, chosen, {name: e[None] for name, e in experts.items()}, 0, cfg)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    pairs = order[lo:lo + width]
    # a row past the last group holds nothing meaningful, and on the chip
    # nothing finite: the product never writes it. It is cut off on both
    # sides, so that neither its value nor its cotangent (which would reach
    # u through the gather and w through the weighting) goes anywhere
    in_group = lo + jnp.arange(width) < ends[-1]
    y = products(pairs, jnp.clip(ends - lo, 0, width) - jnp.clip(starts - lo, 0, width), in_group)
    y = jnp.where(in_group[:, None], y.astype(F32), 0) * w.astype(F32).reshape(-1)[pairs][:, None]
    return jnp.zeros((N, u.shape[1]), F32).at[jnp.where(in_group, pairs // k, N)].add(y, mode="drop"), sizes


def _held_pairs(chosen, cfg: AfmoeConfig):
    first, count = cfg.held_experts
    return jnp.sum((chosen >= first) & (chosen < first + count))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pairs_sum_behind(u, chosen, w, experts, cfg: AfmoeConfig, chunk: int):
    """`_pairs_sum` over every sorted pair behind the first `chunk`, under a
    `lax.cond` that is not taken where no pair in a group lies there, forward
    or backward. The backward pass is written out (the branch makes its pass
    again and takes its vjp) so that what it keeps is its operands where
    they lie: `cond`'s own reverse mode kept a zero-filled copy of every
    intermediate AND of the experts for the branch not taken, 5.5 GB over
    twelve layers at LFM2-8B-A1B's widths (compiled only, PR 57)."""
    return _pairs_sum_behind_fwd(u, chosen, w, experts, cfg, chunk)[0]


def _pairs_sum_behind_fwd(u, chosen, w, experts, cfg, chunk):
    N, k = chosen.shape
    out = jax.lax.cond(
        _held_pairs(chosen, cfg) > chunk,
        lambda: _pairs_sum(u, chosen, w, experts, cfg, chunk, N * k - chunk)[0],
        lambda: jnp.zeros((N, u.shape[1]), F32))
    return out, (u, chosen, w, experts)


def _pairs_sum_behind_bwd(cfg, chunk, res, g):
    import numpy as np

    u, chosen, w, experts = res
    N, k = chosen.shape

    def grads():
        _, vjp = jax.vjp(lambda u, w, experts: _pairs_sum(
            u, chosen, w, experts, cfg, chunk, N * k - chunk)[0], u, w, experts)
        return vjp(g)

    du, dw, dexperts = jax.lax.cond(
        _held_pairs(chosen, cfg) > chunk, grads,
        lambda: jax.tree.map(jnp.zeros_like, (u, w, experts)))
    return du, np.zeros(chosen.shape, jax.dtypes.float0), dw, dexperts


_pairs_sum_behind.defvjp(_pairs_sum_behind_fwd, _pairs_sum_behind_bwd)


def expert_ffn_train(u, chosen, w, experts, cfg: AfmoeConfig, chunk: int):
    """`expert_ffn` for a train step: the same sum, by the same sort and the
    same products (`_sorted_pairs`), in a form reverse-mode differentiation
    takes. `_expert_ffn_in_chunks` loops as far as the pairs in a group reach,
    a trip count that is data, and a while loop has no transpose. Here the
    first `chunk` sorted pairs go through ONE straight pass, and whatever
    pairs in a group lie behind them through a second, over all the rest,
    that a step whose held pairs fit the first does not take
    (`_pairs_sum_behind`). Nothing is dropped: a router that sends every row
    to one held expert runs both. The caller sizes `chunk` so that a router
    in balance needs the first pass alone (models/lfm2_moe.py): a pass reads
    every held expert's three matrices and, backward, makes their gradients,
    so passes are few and long.

    `experts` is ONE layer's held experts, (E, ...): a train step walks its
    layers one tree a layer, and a product's gradient is then that layer's
    and no stack's. Held, not held and `w` as `expert_ffn` has them; a train
    step has no dead rows, so there is no `live`. Gradients reach u (through
    the gathered rows), w and the experts; `chosen` is a choice and has none.
    Returns (out (N, d), rows a held expert (E,) int32)."""
    N, k = chosen.shape
    chunk = min(chunk, N * k)
    out, sizes = _pairs_sum(u, chosen, w, experts, cfg, 0, chunk)
    if chunk < N * k:
        out = out + _pairs_sum_behind(u, chosen, w, experts, cfg, chunk)
    return out.astype(cfg.dtype), sizes


def moe_ffn(m, p, cfg: AfmoeConfig, live=None):
    """The expert layer's FFN for rows m (N, d): routed experts plus the
    shared expert; `p` as `run_layers` hands it on (this layer's router,
    its bias where it has one and shared expert, every layer's experts and
    this layer's index among them). Where the layer has a `shared_gate`
    (d,), the shared expert's output is multiplied by sigmoid(w_g . m), a
    number a row. Returns (out (N, d), rows an expert (E,) int32)."""
    with jax.named_scope(SCOPE_ROUTE):
        chosen, w = route(m, p["router"], p.get("bias"), cfg)
    with jax.named_scope(SCOPE_EXPERTS):
        out, sizes = expert_ffn(m, chosen, w, p["experts"], p["at"], cfg, live)
    with jax.named_scope(SCOPE_SHARED):
        shared = swiglu(m, p["shared"], cfg)
        if "shared_gate" in p:
            gate = jnp.einsum("nd,d->n", m, p["shared_gate"], preferred_element_type=F32)
            shared = (shared * jax.nn.sigmoid(gate)[:, None]).astype(cfg.dtype)
        out = out + shared
    return out, sizes


# ------------------------------------------------------- the attention half
def qkvg(layer, a, cfg: AfmoeConfig):
    """a (..., d) -> q (..., h, hd) and k (..., kvh, hd), each RMS-normed
    over the head size; v (..., kvh, hd); the output gate (..., h * hd).
    The three products are llama._qkv's, for its reason: the head
    split stays out of the product, so the stacked weights are read where
    they lie."""
    q, k, v = _qkv(a, layer, cfg)
    return (rms_norm(q, layer["q_norm"], cfg.rms_eps), rms_norm(k, layer["k_norm"], cfg.rms_eps),
            v, a @ layer["wg"])


def rope_tables(cfg: AfmoeConfig, span: int):
    return rope_frequencies(cfg.head_dim, span, cfg.rope_theta)


def gated_out(o, gate, layer, cfg: AfmoeConfig):
    """Wo (concat(heads) * sigmoid(g)): o and gate (..., h * hd)."""
    return (o * jax.nn.sigmoid(gate.astype(F32)).astype(cfg.dtype)) @ layer["wo"]


def sequence_attention(q, k, v, cfg: AfmoeConfig, window: Optional[int]):
    """Causal self-attention over whole rows (R, T, heads, hd): the flash
    forward (Pallas on the chip, blockwise XLA elsewhere), with the window
    mask only where a row can be longer than the window."""
    from ray_tpu.ops.flash_attention import flash_attention_fwd

    T = q.shape[1]
    o, _ = flash_attention_fwd(q, k, v, causal=True, sm_scale=cfg.head_dim ** -0.5,
                               window=window if window and T > window else None)
    return o.reshape(*q.shape[:2], cfg.n_heads * cfg.head_dim).astype(cfg.dtype)


# ----------------------------------------------------------- the layer loop
def _layer_at(stacked, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stacked)


def run_layers(params, x, carry, cfg: AfmoeConfig, mixers: Dict[str, Callable],
               experts: Optional[Callable] = None):
    """x (..., d) through every layer in order. `mixers[attention kind]
    (layer, index among its kind, normed x, carry) -> (attention output,
    carry)`; `experts(expert layer's params, normed rows (N, d), carry) ->
    (FFN output, carry)`, by default the expert layer over every row. The
    block around them (four norms, two residuals, the dense FFN) is the
    same for every caller: the full forward, admission and the decode
    step."""
    if experts is None:
        experts = lambda p, m, carry: (moe_ffn(m, p, cfg)[0], carry)  # noqa: E731
    eps = cfg.rms_eps

    for attn, ffn, g0, a0, f0, n in cfg.runs:
        def body(c, i, attn=attn, ffn=ffn, g0=g0, a0=a0, f0=f0):
            x, carry = c
            layer = _layer_at(params["layers"], g0 + i)
            if ffn == DENSE:
                p = _layer_at(params[DENSE], f0 + i)
            else:  # the experts stay stacked: expert_ffn says why
                own = {k: v for k, v in params[MOE].items() if k != "experts"}
                p = {**_layer_at(own, f0 + i), "experts": params[MOE]["experts"], "at": f0 + i}
            o, carry = mixers[attn](layer, a0 + i, rms_norm(x, layer["attn_norm"], eps), carry)
            x = x + rms_norm(o, layer["attn_post_norm"], eps)
            m = rms_norm(x, layer["ffn_norm"], eps)
            if ffn == DENSE:
                y = swiglu(m, p, cfg)
            else:
                y, carry = experts(p, m.reshape(-1, cfg.d_model), carry)
                y = y.reshape(m.shape)
            x = x + rms_norm(y, layer["ffn_post_norm"], eps)
            return (x, carry), None

        (x, carry), _ = jax.lax.scan(body, (x, carry), jnp.arange(n))
    return x, carry


def embed_tokens(params, tokens, cfg: AfmoeConfig):
    x = params["embed"][tokens]
    if cfg.mup_enabled:
        x = x * (cfg.d_model ** 0.5)
    return x.astype(cfg.dtype)


def logits_of(params, x, cfg: AfmoeConfig):
    """Final norm and the untied head, float32, for x (..., d): operands as
    they are stored, float32 accumulation (a float32 copy of the head would
    be 1.6 GB at 200k x 2048, made in every decode step)."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return jnp.einsum("...d,dv->...v", x, params["lm_head"], preferred_element_type=F32)


# ------------------------------------------------------------ full forward
def forward(params, tokens, cfg: AfmoeConfig):
    """Logits (R, T, V) float32 of token rows (R, T): the whole-sequence
    pass, no cache. A real position never sees a right-pad behind it."""
    R, T = tokens.shape
    cos, sin = rope_tables(cfg, T)

    def window_mixer(layer, _, a, carry):
        with jax.named_scope(SCOPE_WINDOW):
            q, k, v, gate = qkvg(layer, a, cfg)
            o = sequence_attention(apply_rope(q, cos, sin), apply_rope(k, cos, sin), v, cfg,
                                   cfg.sliding_window)
            return gated_out(o, gate, layer, cfg), carry

    def full_mixer(layer, _, a, carry):
        with jax.named_scope(SCOPE_FULL):
            q, k, v, gate = qkvg(layer, a, cfg)
            return gated_out(sequence_attention(q, k, v, cfg, None), gate, layer, cfg), carry

    x, _ = run_layers(params, embed_tokens(params, tokens, cfg), (), cfg,
                      {SLIDING: window_mixer, FULL: full_mixer})
    return logits_of(params, x, cfg)
