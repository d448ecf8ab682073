"""Expert parallelism: top-k gated MoE with sort-based dispatch.

Green-field (EP is absent from the reference — SURVEY.md §2.4). TPU-first
design: experts are sharded on the `ep` mesh axis; tokens are routed with
a capacity-bounded top-k gate and exchanged with two `all_to_all`s
(dispatch + combine).

Two dispatch strategies share the gate:

- "grouped" (default): the gate returns per-slot (expert_id, weight,
  queue position) computed in O(T·E) — a stable argsort by expert id
  gives each slot its rank within the expert's queue (segment offsets
  from a cumsum'd bincount), and capacity dropping is a position
  compare. Expert queues [E, C, D] are then built with ONE gather
  (`take` through a scattered slot→token index map) and combined with
  ONE gather weighted by the top-k scalars. No [T, E, C] tensor exists
  anywhere, so dispatch costs O(T·k·D) moved bytes instead of the
  O(T·E·C·D) FLOPs of the one-hot einsums (MegaBlocks-style routing,
  expressed with static shapes for XLA).
- "onehot": the Switch/GShard formulation — [T, E, C] combine/dispatch
  tensors contracted with `tec,td->ecd` einsums. Kept as the numerics
  reference and for A/B benchmarking.

`moe_layer_grouped` goes further for the dense/no-EP path: tokens are
sorted by expert and the expert matmuls run as ragged grouped GEMMs
(ray_tpu.ops.grouped_matmul, `jax.lax.ragged_dot`-backed), skipping
capacity padding entirely; capacity still zeroes overflow slots at
combine so numerics match the padded paths exactly.

Everything in `moe_layer` runs inside shard_map over the `ep` axis; the
grouped expert matmuls stay MXU-shaped: [experts_local, capacity*ep,
d_model], with capacity rounded up to a lane-aligned multiple of 8.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp



def compute_capacity(tokens: int, num_experts: int, capacity_factor: float) -> int:
    """Per-expert queue length: `capacity_factor * tokens / num_experts`,
    rounded UP to a multiple of 8 (MXU lane alignment for the [E, C, D]
    queues) and clamped to `tokens` (an expert can never hold more)."""
    cap = int(capacity_factor * tokens / num_experts)
    cap = ((max(cap, 1) + 7) // 8) * 8
    return max(1, min(tokens, cap))


# ---------------------------------------------------------------------------
# gating
# ---------------------------------------------------------------------------

class GateResult(NamedTuple):
    """One-hot gate output (reference path)."""
    combine_weights: jax.Array  # [tokens, experts, capacity]
    dispatch_mask: jax.Array    # [tokens, experts, capacity] bool
    aux_loss: jax.Array


class SortGate(NamedTuple):
    """Sort-based gate output: S = tokens * k slots in choice-major order
    (slot j*T + t is token t's j-th expert choice), no [T, E, C] tensor.
    """
    expert_id: jax.Array   # [S] int32
    weight: jax.Array      # [S] combine scalar (f32), 0 where dropped
    position: jax.Array    # [S] int32 rank within the expert's queue
    kept: jax.Array        # [S] bool, position < capacity
    sort_order: jax.Array  # [S] int32 argsort(expert_id, stable)
    counts: jax.Array      # [E] int32 slots per expert (incl. dropped)
    aux_loss: jax.Array    # load-balance + router-z (already weighted)


def _router(logits, k: int):
    """Shared top-k softmax routing: normalized weights (GShard) for k>1,
    load-balance aux (Switch eq. 4, first-choice density) + z-loss."""
    T, E = logits.shape
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)                 # [T, k]
    if k > 1:
        gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)

    density = jnp.zeros((E,), jnp.float32).at[experts[:, 0]].add(1.0) / T
    density_proxy = probs.mean(axis=0)
    aux = (density * density_proxy).sum() * E
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates, experts, aux, z


def topk_gate(logits, capacity: int, k: int = 1, router_z_weight: float = 0.0,
              aux_weight: float = 1.0) -> SortGate:
    """Sort-based top-k gating in O(T·E): positions come from a stable
    argsort by expert id plus cumsum'd bincount segment offsets; capacity
    dropping is `position < capacity`. Priority is choice-major — every
    token's first choice is enqueued before any second choice (GShard).

    logits: [tokens, num_experts]
    """
    T, E = logits.shape
    gates, experts, aux, z = _router(logits, k)
    S = T * k
    # choice-major flatten: slot j*T + t
    expert_id = experts.T.reshape(S)
    gate_w = gates.T.reshape(S)

    order = jnp.argsort(expert_id, stable=True)              # [S]
    counts = jnp.zeros((E,), jnp.int32).at[expert_id].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(S, dtype=jnp.int32) - starts[expert_id[order]]
    position = jnp.zeros((S,), jnp.int32).at[order].set(pos_sorted)

    kept = position < capacity
    weight = jnp.where(kept, gate_w, 0.0)
    return SortGate(expert_id, weight, position, kept, order, counts,
                    aux_weight * aux + router_z_weight * z)


def topk_gate_onehot(logits, capacity: int, k: int = 1,
                     router_z_weight: float = 0.0,
                     aux_weight: float = 1.0) -> GateResult:
    """One-hot top-k gating (Switch for k=1, GShard-normalized for k>1):
    identical routing decisions, weights, queue positions, and aux loss to
    `topk_gate`, expressed as [T, E, C] combine/dispatch tensors."""
    T, E = logits.shape
    gates, experts, aux, z = _router(logits, k)

    combine = jnp.zeros((T, E, capacity), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), bool)
    counts = jnp.zeros((E,), jnp.float32)
    for j in range(k):
        onehot = jax.nn.one_hot(experts[:, j], E, dtype=jnp.float32)   # [T, E]
        pos = ((jnp.cumsum(onehot, axis=0) - 1.0) + counts[None, :]) * onehot
        keep = (pos < capacity) & (onehot > 0)
        cap_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
        disp = keep[..., None] & (cap_onehot > 0)                      # [T, E, C]
        combine = combine + gates[:, j, None, None] * disp.astype(jnp.float32)
        dispatch = dispatch | disp
        counts = counts + onehot.sum(axis=0)
    return GateResult(combine, dispatch, aux_weight * aux + router_z_weight * z)


def top1_gate(logits, capacity: int):
    """Switch-style top-1 gating (back-compat alias for the one-hot path)."""
    return topk_gate_onehot(logits, capacity, k=1)


# ---------------------------------------------------------------------------
# sort-based dispatch/combine (no [T, E, C] anywhere)
# ---------------------------------------------------------------------------

def sort_dispatch(tokens, gate: SortGate, num_experts: int, capacity: int):
    """Build the [E, C, D] expert queues with ONE gather: scatter each kept
    slot's token index into a slot→source map (overflow slots land on an
    OOB sentinel and are dropped), then `take` token features through it.
    Empty queue slots read a zero row."""
    T, D = tokens.shape
    S = gate.expert_id.shape[0]
    dst = jnp.where(gate.kept, gate.expert_id * capacity + gate.position,
                    num_experts * capacity)
    src = jnp.tile(jnp.arange(T, dtype=jnp.int32), S // T)
    slot_src = jnp.full((num_experts * capacity,), T, jnp.int32).at[dst].set(
        src, mode="drop")
    tokens_p = jnp.concatenate([tokens, jnp.zeros((1, D), tokens.dtype)], axis=0)
    return jnp.take(tokens_p, slot_src, axis=0).reshape(num_experts, capacity, D)


def sort_combine(outputs, gate: SortGate, num_tokens: int):
    """Combine expert outputs [E, C, D] back to [T, D]: gather each slot's
    row, weight by the top-k scalar (0 for dropped slots), and sum a
    token's k choices (the choice-major layout makes that a reshape-sum,
    no scatter)."""
    E, C, D = outputs.shape
    flat = outputs.reshape(E * C, D)
    idx = gate.expert_id * C + jnp.minimum(gate.position, C - 1)
    gathered = jnp.take(flat, idx, axis=0)                   # [S, D]
    weighted = gathered * gate.weight[:, None].astype(outputs.dtype)
    k = weighted.shape[0] // num_tokens
    return weighted.reshape(k, num_tokens, D).sum(axis=0)


# ---------------------------------------------------------------------------
# MoE layers
# ---------------------------------------------------------------------------

def moe_layer(
    x,
    gate_w,
    expert_fn: Callable,
    expert_params,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    top_k: int = 1,
    dispatch: str = "grouped",
    router_z_weight: float = 0.0,
    aux_weight: float = 1.0,
):
    """Inside shard_map. x: [B, T_local... , D] flattened to tokens.

    expert_params leaves have leading dim experts_local (sharded on ep);
    expert_fn(params_e, tokens) applies one expert. `dispatch` picks the
    queue construction: "grouped" (gather, default) or "onehot" (einsum
    reference)."""
    ep = jax.lax.axis_size(axis_name)
    orig_shape = x.shape
    D = orig_shape[-1]
    tokens = x.reshape(-1, D)
    T = tokens.shape[0]
    e_local = jax.tree.leaves(expert_params)[0].shape[0]
    E = e_local * ep
    capacity = compute_capacity(T, E, capacity_factor)

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # [T, E]
    if dispatch == "grouped":
        gate = topk_gate(logits, capacity, k=top_k, router_z_weight=router_z_weight,
                         aux_weight=aux_weight)
        dispatched = sort_dispatch(tokens, gate, E, capacity)         # [E, C, D]
    elif dispatch == "onehot":
        gate = topk_gate_onehot(logits, capacity, k=top_k,
                                router_z_weight=router_z_weight,
                                aux_weight=aux_weight)
        dispatched = jnp.einsum("tec,td->ecd", gate.dispatch_mask.astype(x.dtype), tokens)
    else:
        raise ValueError(f"unknown dispatch={dispatch!r}")

    # tiled all_to_all over experts (its transpose is the reverse tiled
    # all_to_all, so autodiff is clean — the untiled form has a cotangent
    # layout mismatch): [E, C, D] -> [e_local, ep*C, D], block j along the
    # token axis holding device j's queue for each local expert
    received = jax.lax.all_to_all(dispatched, axis_name, split_axis=0, concat_axis=1, tiled=True)

    # apply local experts (vmapped over the expert dim)
    outputs = jax.vmap(expert_fn)(expert_params, received)   # [e_local, ep*C, D]

    # reverse exchange: [e_local, ep*C, D] -> [E, C, D] in global expert order
    returned = jax.lax.all_to_all(outputs, axis_name, split_axis=1, concat_axis=0, tiled=True)

    if dispatch == "grouped":
        combined = sort_combine(returned, gate, T).astype(x.dtype)
    else:
        combined = jnp.einsum("tec,ecd->td", gate.combine_weights.astype(x.dtype), returned)
    return combined.reshape(orig_shape), gate.aux_loss


def moe_layer_dense(
    x,
    gate_w,
    expert_fn,
    expert_params,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    dispatch: str = "grouped",
    router_z_weight: float = 0.0,
    aux_weight: float = 1.0,
):
    """Single-device MoE: IDENTICAL gating/dispatch math to moe_layer with
    ep=1 and no collectives — the fallback when no `ep` mesh axis exists
    (and, with dispatch="onehot", the numerics reference for every other
    path)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    tokens = x.reshape(-1, D)
    T = tokens.shape[0]
    E = jax.tree.leaves(expert_params)[0].shape[0]
    capacity = compute_capacity(T, E, capacity_factor)

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    if dispatch == "grouped":
        gate = topk_gate(logits, capacity, k=top_k, router_z_weight=router_z_weight,
                         aux_weight=aux_weight)
        dispatched = sort_dispatch(tokens, gate, E, capacity)
        outputs = jax.vmap(expert_fn)(expert_params, dispatched)       # [E, C, D]
        combined = sort_combine(outputs, gate, T).astype(x.dtype)
    elif dispatch == "onehot":
        gate = topk_gate_onehot(logits, capacity, k=top_k,
                                router_z_weight=router_z_weight,
                                aux_weight=aux_weight)
        dispatched = jnp.einsum("tec,td->ecd", gate.dispatch_mask.astype(x.dtype), tokens)
        outputs = jax.vmap(expert_fn)(expert_params, dispatched)       # [E, C, D]
        combined = jnp.einsum("tec,ecd->td", gate.combine_weights.astype(x.dtype), outputs)
    else:
        raise ValueError(f"unknown dispatch={dispatch!r}")
    return combined.reshape(orig_shape), gate.aux_loss


def moe_layer_grouped(
    x,
    gate_w,
    grouped_expert_fn: Callable,
    expert_params,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    router_z_weight: float = 0.0,
    aux_weight: float = 1.0,
):
    """Dense/no-EP MoE through ragged grouped GEMMs: tokens are sorted by
    expert and `grouped_expert_fn(expert_params, sorted_tokens [S, D],
    group_sizes [E]) -> [S, D]` runs the expert matmuls segment-wise
    (ray_tpu.ops.grouped_matmul) with NO capacity padding. Capacity still
    applies as numerics: overflow slots stay in their segment but their
    combine weight is zero, so outputs match the padded paths exactly."""
    orig_shape = x.shape
    D = orig_shape[-1]
    tokens = x.reshape(-1, D)
    T = tokens.shape[0]
    E = jax.tree.leaves(expert_params)[0].shape[0]
    capacity = compute_capacity(T, E, capacity_factor)

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    gate = topk_gate(logits, capacity, k=top_k,
                     router_z_weight=router_z_weight, aux_weight=aux_weight)
    S = gate.expert_id.shape[0]

    src = jnp.tile(jnp.arange(T, dtype=jnp.int32), S // T)   # slot -> token
    sorted_tokens = jnp.take(tokens, src[gate.sort_order], axis=0)     # [S, D]
    expert_out = grouped_expert_fn(expert_params, sorted_tokens, gate.counts)

    inv = jnp.zeros((S,), jnp.int32).at[gate.sort_order].set(
        jnp.arange(S, dtype=jnp.int32))
    unsorted = jnp.take(expert_out, inv, axis=0)             # [S, D]
    weighted = unsorted * gate.weight[:, None].astype(unsorted.dtype)
    combined = weighted.reshape(S // T, T, D).sum(axis=0).astype(x.dtype)
    return combined.reshape(orig_shape), gate.aux_loss


# ---------------------------------------------------------------------------
# shard_map wrappers
# ---------------------------------------------------------------------------

def expert_parallel_moe_inline(
    mesh,
    x,
    gate_w,
    expert_fn,
    expert_params,
    capacity_factor: float = 1.25,
    axis_name: str = "ep",
    x_spec=None,
    top_k: int = 1,
    dispatch: str = "grouped",
    router_z_weight: float = 0.0,
    aux_weight: float = 1.0,
):
    """EP MoE callable from INSIDE a jitted program (no inner jit): the
    shard_map inlines into the surrounding GSPMD computation, so a model's
    forward can drop this into its layer stack (llama MoE layers use it).

    `x_spec` is the activations' PartitionSpec on the mesh (e.g.
    P(('dp','fsdp'), None, None)); expert params ride sharded on
    `axis_name` along their leading expert dim. The aux loss is pmeant
    over every axis x is sharded on, so it leaves the shard_map truly
    replicated."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if x_spec is None:
        x_spec = P()
    batch_axes = tuple(
        a for entry in x_spec if entry is not None
        for a in ((entry,) if isinstance(entry, str) else tuple(entry))
    )

    def fn(x, gw, ps):
        out, aux = moe_layer(
            x, gw, expert_fn, ps, axis_name=axis_name,
            capacity_factor=capacity_factor, top_k=top_k,
            dispatch=dispatch, router_z_weight=router_z_weight,
            aux_weight=aux_weight,
        )
        if batch_axes:
            aux = jax.lax.pmean(aux, axis_name=batch_axes)
        return out, aux

    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(x_spec, P(), P(axis_name)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return mapped(x, gate_w, expert_params)


@functools.lru_cache(maxsize=64)
def _ep_moe_jitted(mesh, axis_name, capacity_factor, expert_fn, top_k, dispatch,
                   router_z_weight, aux_weight):
    """Cached jitted EP MoE: rebuilding shard_map + jit per call retraces
    every invocation; the callable is keyed on everything that changes the
    traced program. `expert_fn` keys by identity — pass a stable top-level
    function (a fresh lambda/partial per call misses every time); the
    bounded maxsize keeps that mistake from pinning compiled programs
    forever."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    fn = functools.partial(
        moe_layer, axis_name=axis_name, capacity_factor=capacity_factor,
        top_k=top_k, dispatch=dispatch, router_z_weight=router_z_weight,
        aux_weight=aux_weight,
    )

    mapped = shard_map(
        lambda x, gw, ps: fn(x, gw, expert_fn, ps),
        mesh=mesh,
        in_specs=(P(), P(), P(axis_name)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


def expert_parallel_moe(mesh, x, gate_w, expert_fn, expert_params,
                        capacity_factor=1.25, axis_name="ep", top_k=1,
                        dispatch="grouped", router_z_weight=0.0,
                        aux_weight=1.0):
    """shard_map wrapper: x replicated/batch-sharded; expert_params sharded
    on `ep` along their leading expert dim. The jitted program is cached on
    (mesh, axis, cf, expert_fn, k, dispatch, z, aw) — use a stable module-level
    `expert_fn` so repeat calls hit the cache instead of retracing."""
    jitted = _ep_moe_jitted(mesh, axis_name, float(capacity_factor), expert_fn,
                            int(top_k), dispatch, float(router_z_weight),
                            float(aux_weight))
    return jitted(x, gate_w, expert_params)
