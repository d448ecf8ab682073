"""Grouped (ragged) expert GEMM: out[m] = lhs[m] @ rhs[group_of(m)].

The MoE grouped-dispatch path sorts tokens by expert and multiplies each
contiguous expert segment by that expert's weight matrix — one ragged
matmul instead of E capacity-padded dense ones. It lowers through
`jax.lax.ragged_dot`, which tiles the segments onto the MXU without
materializing any per-expert padding. `_grouped_matmul_segments` computes
the same contraction as E masked dense matmuls and is the tests'
reference, never a dispatch target.

lhs:         [M, K]    tokens, sorted so each group is contiguous
rhs:         [G, K, N] per-group weights
group_sizes: [G] int32 rows per group; MUST sum to M
out:         [M, N]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul(lhs, rhs, group_sizes):
    """Ragged grouped GEMM; differentiable on both operands.

    Rows of `lhs` beyond `sum(group_sizes)` are undefined — callers pass
    exact segment counts (the MoE path includes capacity-dropped slots in
    their expert's segment and zeroes them at combine instead).
    """
    M, K = lhs.shape
    G, K2, N = rhs.shape
    assert K == K2, f"lhs K={K} vs rhs K={K2}"
    assert group_sizes.shape == (G,)
    return _ragged_dot_safe(lhs, rhs, group_sizes.astype(jnp.int32))


def unshard_dim(arr, dim: int):
    """Gather one dimension of a CONCRETE sharded array (device_put with
    that spec entry forced to None); no-op on tracers (they carry no
    sharding — callers jitting over sharded operands must gather first,
    this guard cannot see through a trace) and on already-unsharded dims.

    Exists because jax<=0.4.x silently MISCOMPUTES ragged_dot when the
    rhs GROUP dim is sharded (each shard contracts against global group
    offsets; K/N-dim sharding is fine) — used here for rhs dim 0 and by
    llama's eval-flow guard for the stacked expert dim."""
    sharding = getattr(arr, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) <= dim or spec[dim] is None:
        return arr
    from jax.sharding import NamedSharding, PartitionSpec

    entries = tuple(spec)[:dim] + (None,) + tuple(spec)[dim + 1:]
    return jax.device_put(arr, NamedSharding(sharding.mesh, PartitionSpec(*entries)))


def _unshard_group_dim(rhs):
    return unshard_dim(rhs, 0)


# custom_vjp so the unshard guard sees CONCRETE arrays on the backward
# pass too: fwd/bwd of a custom_vjp execute on values (not tracers) under
# eager jax.grad, whereas ragged_dot's built-in VJP would replay the
# buggy sharded contraction.
@jax.custom_vjp
def _ragged_dot_safe(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, _unshard_group_dim(rhs), group_sizes)


def _ragged_dot_safe_fwd(lhs, rhs, group_sizes):
    rhs_r = _unshard_group_dim(rhs)
    return jax.lax.ragged_dot(lhs, rhs_r, group_sizes), (lhs, rhs_r, group_sizes)


def _ragged_dot_safe_bwd(res, dout):
    import numpy as np

    lhs, rhs_r, group_sizes = res
    _, vjp = jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, group_sizes),
                     lhs, rhs_r)
    dlhs, drhs = vjp(dout)
    return dlhs, drhs, np.zeros(group_sizes.shape, jax.dtypes.float0)


_ragged_dot_safe.defvjp(_ragged_dot_safe_fwd, _ragged_dot_safe_bwd)


def _grouped_matmul_segments(lhs, rhs, group_sizes):
    """Test reference: one masked dense matmul per group (O(G·M·K·N)
    FLOPs), independent of ragged_dot."""
    M = lhs.shape[0]
    G, _, N = rhs.shape
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(M)
    out = jnp.zeros((M, N), dtype=lhs.dtype)
    for g in range(G):
        mask = ((rows >= starts[g]) & (rows < ends[g])).astype(lhs.dtype)
        out = out + (lhs * mask[:, None]) @ rhs[g]
    return out
