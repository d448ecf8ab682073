"""Pipeline parallelism: GPipe-style microbatch schedule over the `pp` axis.

The reference has no pipeline parallelism in core/train (SURVEY.md §2.4 —
the compiled-DAG channel substrate was the intended future home). Here PP
is a collective program, TPU-style: every `pp`-axis device holds one
stage's params inside shard_map; activations hop stage-to-stage with
`lax.ppermute`; the M+P-1-step schedule is a `lax.scan`, so the whole
pipeline is one XLA program with static shapes (no host round-trips
between stages, unlike an actor-based pipeline).

Gradients flow by autodiff through scan+ppermute (reverse ppermute is the
reverse hop); `jax.checkpoint` on the stage fn bounds activation memory
to one microbatch per live stage.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp



def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_microbatches,
    axis_name: str = "pp",
):
    """Inside shard_map. stage_params: this device's stage params.
    x_microbatches: [M, mb, ...] (replicated input; stage 0 consumes it).
    Returns [M, mb, ...] outputs (valid on the last stage; replicated out
    by a final ppermute-broadcast)."""
    P = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    mb_shape = x_microbatches.shape[1:]

    fn = jax.checkpoint(stage_fn)
    shift_perm = [(i, i + 1) for i in range(P - 1)]

    def step(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (when in range)
        feed = jnp.where(t < M, t, M - 1)
        state = jnp.where(stage == 0, x_microbatches[feed], state)
        out = fn(stage_params, state)
        # last stage emits microbatch t-(P-1)
        emit_idx = t - (P - 1)
        do_emit = (stage == P - 1) & (emit_idx >= 0)
        outputs = jax.lax.cond(
            do_emit,
            lambda o: o.at[jnp.maximum(emit_idx, 0)].set(out),
            lambda o: o,
            outputs,
        )
        # hop activations to the next stage
        state = jax.lax.ppermute(out, axis_name, shift_perm)
        return (state, outputs), None

    state0 = jnp.zeros(mb_shape, x_microbatches.dtype)
    out0 = jnp.zeros((M,) + mb_shape, x_microbatches.dtype)
    (_, outputs), _ = jax.lax.scan(step, (state0, out0), jnp.arange(M + P - 1))

    # broadcast final outputs from the last stage to all stages (psum of a
    # one-hot-by-stage tensor == broadcast; ppermute can't fan out)
    outputs = jnp.where(stage == P - 1, outputs, jnp.zeros_like(outputs))
    outputs = jax.lax.psum(outputs, axis_name)
    return outputs


def pipelined(
    mesh,
    stage_fn,
    all_stage_params,
    x,
    num_microbatches: int,
    axis_name: str = "pp",
    data_spec=None,
):
    """shard_map wrapper. all_stage_params: pytree with leading dim P
    (one slice per stage, sharded on `pp`). x: [B, ...] global batch.

    `data_spec` optionally shards the microbatched input [M, mb, ...] on
    OTHER mesh axes (e.g. P(None, 'dp', ...) for pp+dp) — the pipeline
    then runs per data-parallel slice. Callable from inside jit (the
    shard_map inlines into the surrounding program)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    B = x.shape[0]
    assert B % num_microbatches == 0
    xm = x.reshape(num_microbatches, B // num_microbatches, *x.shape[1:])
    if data_spec is None:
        data_spec = P()

    def inner(params_stage, xm):
        # params arrive with leading dim 1 (this stage's slice)
        params_stage = jax.tree.map(lambda p: p[0], params_stage)
        return pipeline_apply(stage_fn, params_stage, xm, axis_name=axis_name)

    mapped = shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis_name), data_spec),
        out_specs=data_spec,
        check_vma=False,
    )
    # jit so the remat'd stage fn lowers even when called eagerly; under
    # an outer jit this inlines into the surrounding program
    out = jax.jit(mapped)(all_stage_params, xm)
    return out.reshape(B, *out.shape[2:])
