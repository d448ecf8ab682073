"""The plain reference of the LFM2-MoE configuration, for TRAINING: loss and
gradients in float32 `jax.numpy` under `default_matmul_precision("highest")`,
written from the layer equations (HF `Lfm2MoeForCausalLM`, d the hidden
size, eps `norm_eps`) and not by calling the program:

    h = x + op(RMSNorm(x));   y = h + ffn(RMSNorm(h))

- `op` of a `conv` layer: B, C, z = the three thirds of x W_in; u = B * z;
  v_t = k_0 u_{t-2} + k_1 u_{t-1} + k_2 u_t a channel, THREE EXPLICIT TAPS,
  zeros before position 0; op = (C * v) W_out.
- `op` of a `full_attention` layer: q (heads x d / heads), k, v (KV heads);
  RMSNorm over each head of q and of k, then rotary positions (half-split
  pairs, the whole head); the WHOLE causal score matrix of one head at a
  time, softmax at head size^-0.5; W_o.
- `ffn` of a dense layer: SwiGLU of width `intermediate_size`.
- `ffn` of an expert layer: s = sigmoid(x W_r); chosen = top-4 of s + b (b
  in the choice only); w = s[chosen] / (sum s[chosen] + EPS_R) times
  `routed_scaling_factor`; EVERY HELD expert is applied to EVERY row and
  weighted by w through a 0/1 mask of the choice: no sort, no ragged
  product. What the experts on the other chips would add is left out, as
  the program leaves it out (model-configs guide, section 4).
- the ends: the embedding, one last RMSNorm, the embedding transposed as the
  head; the mean next-token cross-entropy and no other term.

It takes its inputs from the SEED and nothing the program has made
(`grad_check`): each layer's weights are regenerated from the seed
(`weights_lfm2_moe.make_layer`, in the stated type bfloat16) and cast to
float32, one layer at a time, forward and again backward, so that no
float32 copy of the model or of its gradient is ever alive and the
reference fits beside the system at the cell's own size. The choice of
experts is the reference's OWN: a top-4 that flips on a near-tie between
bfloat16 and float32 moves whole rows of gradient between experts, and
through the residual stream every leaf's gradient with them (the leaves
outside the experts and routers read the same error as the whole tree: my
chip run, PR 57), which is why `grad_check` also gives the MEDIAN error over
the embedding's rows: a row's gradient is a few tokens', and most tokens flip
nothing. It gives the error of the experts' matrices alone and of the
routers alone as well, and of every single leaf: the experts are under a
hundredth of the gradient's squared norm, so a norm over the whole tree
would pass an expert gradient of zero. `forward_with_choices` hands out the
reference's choice for counting such flips (benchmark/check_lfm2_moe.py).
`plain_loss` is the same mathematics on a given parameter tree, whole: the
CPU tests' twin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights_lfm2_moe as W
from benchmark.reference import _rms_norm, _rope

F32 = jnp.float32
EPS_R = 1e-20  # beside the sum of the chosen scores (the configuration's `assumed.eps_r`)
# which part of the gradient a leaf of a layer belongs to
EXPERTS, ROUTER, OUTSIDE = "experts", "router", "outside"
PARTS = (EXPERTS, ROUTER, OUTSIDE)
ROW_QUANTILES = (0.1, 0.25, 0.5, 0.75, 0.9)


def _conv_op(a, w, cfg):
    """a [T, d]: the gated short convolution, its three taps written out."""
    assert cfg.conv_taps == 3, "the reference writes three taps out"
    T, d = a.shape
    b, c, z = jnp.split(a @ w["w_in"], 3, axis=-1)
    u = b * z
    zero = jnp.zeros((1, d), F32)
    u1 = jnp.concatenate([zero, u[:-1]])            # u_{t-1}
    u2 = jnp.concatenate([zero, zero, u[:-2]])      # u_{t-2}
    k = w["conv"]
    v = k[0] * u2 + k[1] * u1 + k[2] * u
    return (c * v) @ w["w_out"]


def _attn_op(a, w, cfg):
    """a [T, d]: grouped-query attention, one head's whole score matrix at a
    time (made again in the backward pass: all heads' would not fit at 8k)."""
    T, d = a.shape
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    hd = d // h
    q = _rope(_rms_norm((a @ w["wq"]).reshape(T, h, hd), w["q_norm"], cfg.rms_eps), cfg.rope_theta)
    k = _rope(_rms_norm((a @ w["wk"]).reshape(T, kvh, hd), w["k_norm"], cfg.rms_eps), cfg.rope_theta)
    v = (a @ w["wv"]).reshape(T, kvh, hd)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        qh, kh, vh = qkv  # [T, hd] each
        s = (qh @ kh.T) * (hd ** -0.5)
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ vh

    share = lambda t: jnp.repeat(t.transpose(1, 0, 2), h // kvh, axis=0)  # noqa: E731  [h, T, hd]
    o = jax.lax.map(jax.checkpoint(head), (q.transpose(1, 0, 2), share(k), share(v)))
    return o.transpose(1, 0, 2).reshape(T, h * hd) @ w["wo"]


def _swiglu(m, w):
    return (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


def route_weights(m, w, cfg):
    """[T, n_experts]: each row's weight for every expert of the router, zero
    where the row did not choose it."""
    s = jax.nn.sigmoid(m @ w["router"])
    _, chosen = jax.lax.top_k(s + w["bias"], cfg.top_k)
    mask = jax.nn.one_hot(chosen, cfg.n_experts, dtype=F32).sum(axis=1)
    picked = s * mask
    if cfg.route_norm:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + EPS_R)
    return picked * cfg.route_scale


def _moe_ffn_and_choice(m, w, cfg):
    """(the held experts' weighted sum, the 0/1 choice of each row over the
    router's experts)."""
    first, count = cfg.held_experts
    every = route_weights(m, w, cfg)
    weights = every[:, first:first + count]                        # [T, held]

    def add(total, expert_and_weight):
        expert, we = expert_and_weight
        return total + we[:, None] * _swiglu(m, expert), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(m), (w["experts"], weights.T))
    return out, every > 0


def _moe_ffn(m, w, cfg):
    return _moe_ffn_and_choice(m, w, cfg)[0]


def _layer(x, w, kind, cfg, with_choice=False):
    """One layer of `kind` = (mixer, FFN) on one sequence x [T, d]; w float32.
    `with_choice`: (y, the expert layer's choice [T, n_experts] bool, all
    False behind a dense FFN)."""
    a = _rms_norm(x, w["op_norm"], cfg.rms_eps)
    x = x + (_conv_op(a, w["op"], cfg) if kind[0] == W.CONV else _attn_op(a, w["op"], cfg))
    m = _rms_norm(x, w["ffn_norm"], cfg.rms_eps)
    if kind[1] == W.DENSE:
        y, choice = _swiglu(m, w["ffn"]), jnp.zeros((x.shape[0], cfg.n_experts), bool)
    else:
        y, choice = _moe_ffn_and_choice(m, w["ffn"], cfg)
    return (x + y, choice) if with_choice else x + y


def _nll_sum(x, norm_w, embed, targets, cfg):
    """Summed negative log-likelihood of sequences x [B, T, d], one at a time."""
    def one(xt):
        xb, tb = xt
        logp = jax.nn.log_softmax(_rms_norm(xb, norm_w, cfg.rms_eps) @ embed.T, axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0].sum()
    return jax.lax.map(one, (x, targets)).sum()


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


# ------------------------------------------------------- on a given tree, whole
def plain_loss(params, tokens, cfg):
    """Mean next-token cross-entropy of tokens [B, T + 1] under `params` (the
    program's tree, any type; computed in float32). Differentiate it for the
    reference's gradients at a small size."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = p["embed"][inputs]
        for w, kind in zip(p["layers"], W.kinds(cfg)):
            x = jax.lax.map(lambda xb, w=w, kind=kind: _layer(xb, w, kind, cfg), x)
        return _nll_sum(x, p["final_norm"], p["embed"], targets, cfg) / targets.size


# ---------------------------------------------------------------- from the seed
def part_of(path) -> str:
    """The part of the gradient a leaf of a LAYER's tree belongs to."""
    keys = [getattr(k, "key", None) for k in path]
    return EXPERTS if "experts" in keys else ROUTER if "router" in keys else OUTSIDE


def _sq_by_leaf(tree, other):
    """The tree with each leaf replaced by (sum of its squares, sum of the
    squares of `other`'s difference from it); an expert stack's leaf gives
    one pair an EXPERT (its leading axis), so that one expert's matrix is a
    leaf of its own."""
    def sq(path, g, o):
        axes = tuple(range(1, g.ndim)) if part_of(path) == EXPERTS else None
        g = g.astype(F32)
        return jnp.sum(jnp.square(g), axis=axes), jnp.sum(jnp.square(o.astype(F32) - g), axis=axes)
    return jax.tree_util.tree_map_with_path(sq, tree, other)


@functools.lru_cache(maxsize=2)
def _jitted_forward(cfg):
    def fn(key, inputs):
        with jax.default_matmul_precision("highest"):
            k_embed, layer_keys = W.part_keys(key, cfg)
            x, choices = W.make_embed(k_embed, cfg).astype(F32)[inputs], []
            for k, kind in zip(layer_keys, W.kinds(cfg)):
                w = _f32(W.make_layer(k, kind, cfg))
                x, choice = jax.lax.map(
                    lambda xb, w=w, kind=kind: _layer(xb, w, kind, cfg, with_choice=True), x)
                if kind[1] != W.DENSE:
                    choices.append(choice)
            return x, jnp.stack(choices)
    return jax.jit(fn)


def forward_with_choices(key, inputs, cfg):
    """From the seed, for token rows [B, T]: (the last layer's output
    [B, T, d] float32, the reference's OWN choice in every expert layer
    [expert layers, B, T, n_experts] bool). For counting the (token, layer)
    pairs whose choice differs from the program's (benchmark/check_lfm2_moe.py);
    no run of the benchmark calls it."""
    return _jitted_forward(cfg)(key, jnp.asarray(inputs, jnp.int32))


@functools.lru_cache(maxsize=4)
def _jitted_grad_check(cfg):
    """Mean loss of a batch, and LEAF BY LEAF the squared norm of its gradient
    and the squared distance of the SYSTEM's gradient from it, layer by
    layer: the forward pass keeps each layer's input; the backward pass
    regenerates one layer's weights, takes each sequence's vjp in float32,
    sums them, and keeps only the sums of squares (`_sq_by_leaf`); and the
    embedding's error row by row."""
    kinds = W.kinds(cfg)

    def fn(key, batch, sys_grads):
        with jax.default_matmul_precision("highest"):
            k_embed, layer_keys = W.part_keys(key, cfg)
            inputs, targets = batch[:, :-1], batch[:, 1:]
            embed = W.make_embed(k_embed, cfg).astype(F32)
            x, xs = embed[inputs], []
            for k, kind in zip(layer_keys, kinds):
                w = _f32(W.make_layer(k, kind, cfg))
                xs.append(x)
                x = jax.lax.map(lambda xb, w=w, kind=kind: _layer(xb, w, kind, cfg), x)

            loss, (gx, g_norm_w, g_embed) = jax.value_and_grad(
                lambda x, n, e: _nll_sum(x, n, e, targets, cfg) / targets.size,
                argnums=(0, 1, 2))(x, jnp.ones((cfg.d_model,), F32), embed)
            layers_sq = []
            for k, kind, x_in, g_sys in reversed(list(zip(layer_keys, kinds, xs, sys_grads["layers"]))):
                w32 = _f32(W.make_layer(k, kind, cfg))

                def one(gw_sum, xg, w32=w32, kind=kind):
                    xb, gb = xg
                    _, vjp = jax.vjp(lambda x_, w_: _layer(x_, w_, kind, cfg), xb, w32)
                    gxb, gw = vjp(gb)
                    return jax.tree.map(jnp.add, gw_sum, gw), gxb

                gw, gx = jax.lax.scan(one, jax.tree.map(jnp.zeros_like, w32), (x_in, gx))
                layers_sq.append(_sq_by_leaf(gw, g_sys))
            # the embedding is the lookup's table and the head at once
            g_embed = g_embed.at[inputs].add(gx)
            ends = _sq_by_leaf({"embed": g_embed, "final_norm": g_norm_w},
                               {"embed": sys_grads["embed"], "final_norm": sys_grads["final_norm"]})
            # row by row of the embedding: a row's gradient is a few tokens' (those
            # that carry its id, those whose target it is), so its error is theirs
            diff = sys_grads["embed"].astype(F32) - g_embed
            row_err = jnp.sqrt(jnp.sum(diff * diff, axis=1) / jnp.sum(g_embed * g_embed, axis=1))
            return loss, {**ends, "layers": layers_sq[::-1]}, row_err
    return jax.jit(fn)


def _leaf_name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def grad_check(key, batch_tokens, cfg, sys_grads) -> dict:
    """For the batch [B, T + 1] at the seed's initial weights: the reference's
    `loss` and `grad_norm`; `grad_rel_err` = ||g_system - g_reference|| /
    ||g_reference|| over the whole tree; `parts` the same ratio over each
    part alone (the experts' matrices, the routers, the leaves outside both:
    the experts are under a hundredth of the whole tree's squared norm, so
    the whole tree's ratio cannot see them) with its share of the reference
    gradient's squared norm; `worst_leaves` the largest ratios over single
    leaves, ONE expert's one matrix a leaf of its own (a leaf the reference
    gives no gradient, as the choice bias, is left out);
    `grad_row_err_median` the MEDIAN over the embedding's rows of the same
    ratio taken a row (a flipped choice is one token's and moves the few rows
    that token touches; a lower precision is every token's and moves the
    median row), with its `ROW_QUANTILES`; `row_err` every row's."""
    import numpy as np

    loss, sq, row_err = _jitted_grad_check(cfg)(key, jnp.asarray(batch_tokens, jnp.int32), sys_grads)
    row_err = np.asarray(row_err)
    row_q = [float(q) for q in np.quantile(row_err, ROW_QUANTILES)]
    ref_sq, err_sq = dict.fromkeys(PARTS, 0.0), dict.fromkeys(PARTS, 0.0)
    leaves = {}
    for path, pair in jax.tree_util.tree_flatten_with_path(sq, is_leaf=lambda t: isinstance(t, tuple))[0]:
        part, name = part_of(path), _leaf_name(path)
        ref, err = (np.atleast_1d(np.asarray(a, np.float64)) for a in pair)
        ref_sq[part] += float(ref.sum())
        err_sq[part] += float(err.sum())
        for e, (r, d) in enumerate(zip(ref, err)):
            if r > 0:
                leaves[name + (f"[{e}]" if ref.size > 1 else "")] = float((d / r) ** 0.5)
    total = sum(ref_sq.values())
    worst = sorted(leaves, key=leaves.get, reverse=True)[:5]
    return {
        "loss": float(loss), "grad_norm": total ** 0.5,
        "grad_rel_err": (sum(err_sq.values()) / total) ** 0.5,
        "grad_row_err_median": row_q[ROW_QUANTILES.index(0.5)],
        "grad_row_err_quantiles": dict(zip(map(str, ROW_QUANTILES), row_q)),
        "parts": {p: {"rel_err": (err_sq[p] / ref_sq[p]) ** 0.5 if ref_sq[p] else None,
                      "share_of_norm_sq": ref_sq[p] / total} for p in PARTS},
        "worst_leaves": {name: leaves[name] for name in worst}, "leaves": leaves,
        "row_err": row_err,
    }
