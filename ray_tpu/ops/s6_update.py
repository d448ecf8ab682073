"""One decode step of a Mamba-1 layer's state, in place in the stacked
state: a Pallas TPU kernel in the manner of ops/ssm_update.py.

The state of all Mamba-1 layers is one array `(layers, lanes, N, c)` float32
(models/phi4flash_decode.py): N state columns on the second-minor axis, the
d_inner channels on the minor one (N = 16 as a minor axis would be padded to
128). A decode step of layer `mi` is, for every LIVE lane, `h' = exp(dt A) * h
+ (dt x) B^T` and `y = sum_N(h' C)`, with ONE decay a channel AND state column
(`A` is (N, c)), so ops/ssm_update.py's body, whose decay is a number a head,
does not compute it. As there:

- the whole stack goes in and comes out aliased (`input_output_aliases`); the
  layer index and the compacted list of live lanes are scalar-prefetch
  arguments, and the index maps pick `(mi, lane, block of channels)`. Nothing
  slices a layer out of the stack and nothing writes one back;
- a grid step reads a live lane's block once, writes `h'` over it and gives
  `y` in the same pass; the steps past the live lanes repeat the last live
  block's index and do no work, so a lane that is not live costs no pass over
  its row and keeps it bit for bit;
- the arithmetic is `s6_step`'s, float32 on the vector unit; the decay's
  `exp` is taken in the kernel (handing it in would be a second array as
  large as the state). Only the order of the sum over N may differ.

A lane's block is the tile `(N, channels)`: `dt` and `dt x` come in as rows
`(1, channels)` and spread over the N sublanes, `B` and `C` as columns `(N, 1)`
and spread over the lanes, `y` leaves as a row.

The call does not ride `ssm_update._stacked_call`, though the protocol is
its: that call's state is (M, L, H, P, N) blocked over whole heads with
operands a lane (rows of a block's heads, the lane's N-vectors, a block's
heads), and this state is (M, L, N, c) blocked over the MINOR axis, with `A`
(N, c) an operand of the layer and no lane's, and B, C as columns. Seen as H =
1, P = N = 16 it fails that call's own `(hb * P) % 128` rule. So the grid, the
`at()` index map and the write-back for no live lane are written out here
for these specs; the backend test and the VMEM budget are imported.

`update_stacked_state` is the entry; `engages` says whether a step takes it (a
TPU, and shapes the tiles take), and the caller (models/phi4flash.py) keeps
`s6_step` as the definition and the path everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the backend test and what the blocks (a lane's block of state in and out and
# the layer's A, each double buffered) may take of a core's VMEM are the
# sibling's: the same chip, the same protocol
from ray_tpu.ops.ssm_update import _VMEM_FOR_BLOCKS, _VMEM_LIMIT, F32, _on_tpu


def channels_per_block(N: int, c: int) -> int:
    """The largest divisor of c that is whole lane-rows of 128 and whose
    block fits `_VMEM_FOR_BLOCKS` six times over (0: none does)."""
    fits = [cb for cb in range(128, c + 1, 128)
            if c % cb == 0 and 6 * N * cb * 4 <= _VMEM_FOR_BLOCKS]
    return max(fits, default=0)


def supported(N: int, c: int) -> bool:
    """The kernel's tiles are (8, 128): N whole groups of sublanes, the
    channels whole lane-rows, and some block of them fits the VMEM."""
    return N % 8 == 0 and channels_per_block(N, c) > 0


def engages(N: int, c: int) -> bool:
    """Whether a step of a state (.., N, c) takes the kernel: the backend is
    a TPU and the tiles take the shapes. Nothing else chooses the path."""
    return _on_tpu() and supported(N, c)


def _kernel(mi_ref, n_live_ref, order_ref, dt_ref, dtx_ref, b_ref, c_ref, a_ref, h_ref,
            y_ref, o_ref):
    i = pl.program_id(0)
    n_live = n_live_ref[0]

    @pl.when(i < n_live)
    def _():
        h = jnp.exp(dt_ref[0] * a_ref[...]) * h_ref[0, 0] + dtx_ref[0] * b_ref[0]  # (N, cb)
        o_ref[0, 0] = h
        y_ref[0] = jnp.sum(h * c_ref[0], axis=0, keepdims=True)

    # no lane is live: the grid's steps all name lane 0's last block, which
    # is written back once at the end, so it has to hold the row
    @pl.when(jnp.logical_and(i == 0, n_live == 0))
    def _():
        o_ref[...] = h_ref[...]


@jax.jit  # both layer walks of a macro-step share one lowering of the kernel
def _s6_update_pallas(ssm, mi, order, n_live, dt, dtx, A, B, C):
    """ssm (M, L, N, c) float32, aliased onto the second result; dt, dtx (L,
    c); A (N, c); B, C (L, N); all float32. Returns (sum_N(h' C) (L, c),
    meaningful on live lanes only, and the stack)."""
    M, L, N, c = ssm.shape
    cb = channels_per_block(N, c)
    nj = c // cb

    # a step past the live lanes repeats the last live step's block indices
    def at(i, j, n_live):
        return jnp.where(i < n_live[0], j, nj - 1)

    row = pl.BlockSpec((1, 1, cb), lambda i, j, mi, n, order: (order[i], 0, at(i, j, n)))
    col = pl.BlockSpec((1, N, 1), lambda i, j, mi, n, order: (order[i], 0, 0))
    y, ssm = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(L, nj),
            in_specs=[row, row, col, col,
                      pl.BlockSpec((N, cb), lambda i, j, mi, n, order: (0, at(i, j, n))),
                      pl.BlockSpec((1, 1, N, cb),
                                   lambda i, j, mi, n, order: (mi[0], order[i], 0, at(i, j, n)))],
            out_specs=[row,
                       pl.BlockSpec((1, 1, N, cb),
                                    lambda i, j, mi, n, order: (mi[0], order[i], 0, at(i, j, n)))]),
        out_shape=[jax.ShapeDtypeStruct((L, 1, c), F32), jax.ShapeDtypeStruct(ssm.shape, F32)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        name="s6_update",
    )(jnp.reshape(mi, (1,)).astype(jnp.int32), n_live, order,
      dt.reshape(L, 1, c), dtx.reshape(L, 1, c), B.reshape(L, N, 1), C.reshape(L, N, 1), A, ssm)
    return y.reshape(L, c), ssm


def update_stacked_state(ssm, mi, live, x, dt, A, B, C, D):
    """Layer `mi`'s recurrence for one position on the live lanes of the
    stacked state. ssm (M, L, N, c) float32; `live` = (the lanes' flags (L,)
    bool, the live lanes' indices in rising order with the last of them
    repeated to the end (L,) int32, their number (1,) int32); x (L, c); dt (L,
    c) float32; A (N, c); B, C (L, N); D (c,). Returns (y (L, c) float32, zero
    on a lane that is not live; the stack, that lane's row and every other
    layer untouched)."""
    active, order, n_live = live
    xf = x.astype(F32)
    y, ssm = _s6_update_pallas(ssm, mi, order, n_live, dt, dt * xf, A, B.astype(F32),
                               C.astype(F32))
    return jnp.where(active[:, None], y + D * xf, 0.0), ssm
