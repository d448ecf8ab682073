"""Ring attention and Ulysses sequence/context parallelism.

Green-field (the reference has no sequence parallelism anywhere —
SURVEY.md §5 verified by tree-wide search). TPU-native design:

- **Ring attention** (blockwise attention over the ICI ring): KV shards
  rotate around the `sp` mesh axis via `lax.ppermute` while each device
  accumulates online-softmax partials for its local Q shard. Causality is
  handled by global block offsets, so devices never materialize a full
  attention matrix and sequence length scales linearly with the ring
  size. Compute/comm overlap comes from XLA's latency-hiding scheduler
  (the ppermute of step s+1 is independent of the attention of step s).

- **Ulysses**: all_to_all swaps the sharded axis (sequence ↔ heads), runs
  dense local attention with the pallas flash kernel, and swaps back.
  Cheaper for moderate contexts (2 collectives instead of sp-1 hops) but
  caps sp at num_heads.

Both are meant to be called inside `shard_map` over a mesh built by
ray_tpu.parallel.build_mesh — see sequence_parallel_attention() for the
wrapper that picks the right one and wires the shard_map.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


from ray_tpu.ops.blockwise_attention import _fwd_impl


def _combine(o1, lse1, o2, lse2):
    """Merge two normalized attention partials via their logsumexps."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (
        o1.astype(jnp.float32) * (w1 / denom_safe)[..., None]
        + o2.astype(jnp.float32) * (w2 / denom_safe)[..., None]
    )
    return o.astype(o1.dtype), m + jnp.log(denom_safe)


def ring_attention(
    q,
    k,
    v,
    axis_name: str = "sp",
    causal: bool = True,
    block_size: int = 512,
    sm_scale: Optional[float] = None,
):
    """Call inside shard_map; q/k/v are the local sequence shards
    [B, T_local, H, D]. Returns the local output shard."""
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step_attend(q, kv, src_idx):
        """One ring step: attend local q against the kv shard that
        originated on device src_idx."""
        kk, vv = kv
        o, lse = _fwd_impl(
            q,
            kk,
            vv,
            causal,
            block_size,
            sm_scale,
            q_offset=my * Tl,
            kv_offset=src_idx * Tl,
        )
        return o, lse

    step_attend = jax.checkpoint(step_attend)

    def body(carry, s):
        o_acc, lse_acc, kv = carry
        src_idx = (my - s) % sp
        o_s, lse_s = step_attend(q, kv, src_idx)
        o_new, lse_new = _combine(o_acc, lse_acc, o_s, lse_s)
        # rotate kv shards one hop around the ring (skip after last step)
        kv_next = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm), kv)
        return (o_new, lse_new, kv_next), None

    o0 = jnp.zeros_like(q)
    lse0 = jnp.full((B, Tl, H), -jnp.inf, jnp.float32)
    (o, lse, _), _ = jax.lax.scan(body, (o0, lse0, (k, v)), jnp.arange(sp))
    return o


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """All-to-all head/sequence swap (inside shard_map): gather the full
    sequence while sharding heads, run dense flash attention, swap back."""
    from ray_tpu.ops.flash_attention import flash_attention

    sp = jax.lax.axis_size(axis_name)
    B, Tl, H, D = q.shape
    assert H % sp == 0, f"heads {H} must divide sp {sp} for ulysses"

    def seq_to_heads(x):
        # [B, T/sp, H, D] -> [B, T, H/sp, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    og = flash_attention(qg, kg, vg, causal, sm_scale)
    return heads_to_seq(og)


def sequence_parallel_attention(
    mesh,
    q,
    k,
    v,
    causal: bool = True,
    mode: str = "ring",
    block_size: int = 512,
    sm_scale: Optional[float] = None,
    axis_name: str = "sp",
):
    """shard_map wrapper: q/k/v are global arrays sharded on `sp` along
    the sequence axis; returns the global output with the same sharding."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    spec = P(None, axis_name, None, None)

    if mode == "ring":
        fn = functools.partial(
            ring_attention, axis_name=axis_name, causal=causal, block_size=block_size, sm_scale=sm_scale
        )
    elif mode == "ulysses":
        fn = functools.partial(ulysses_attention, axis_name=axis_name, causal=causal, sm_scale=sm_scale)
    else:
        raise ValueError(f"unknown mode {mode}")

    mapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return jax.jit(mapped)(q, k, v)
