"""One decode step of a recurrent layer's state, in place in the stacked
state: a Pallas TPU kernel with two bodies, Mamba-2's update and the gated
delta rule's (at the end of this text).

The state of all Mamba layers is one array `(layers, lanes, H, P, N)` float32
(models/granite_hybrid_decode.py). A decode step of layer `mi` is, for every
LIVE lane, `h' = decay * h + (dt x) (x) B` and `y = sum_N(h' C)`: elementwise
float32 over the lane's `(H, P, N)` row. Plain XLA makes three passes over
the layer (read for the update, a `select` over all lanes and the write, a
third read for `h . C`) and pays for lanes that are not live in full. Here:

- the whole stack goes in and comes out aliased (`input_output_aliases`);
  the layer index and the compacted list of live lanes are scalar-prefetch
  arguments, and the index maps pick `(mi, lane, block of heads)`. Nothing
  slices a layer out of the stack and nothing writes one back;
- a grid step reads a live lane's block once, writes `h'` over it and gives
  `y` in the same pass. The grid has as many steps as there are lanes; the
  steps past the live ones repeat the last live block's index and do no
  work, so nothing is fetched or written back for them: a lane that is not
  live costs no pass over its row and keeps it bit for bit;
- the arithmetic is `ssm_step`'s, float32 on the vector unit (no matrix
  unit: its float32 products would round to bfloat16). Only the order of the
  sum over N may differ from XLA's.

A lane's block is worked as a 2-D tile `(heads * P, N)`: N on the lanes, one
row a (head, p) pair. `decay` and `dt x` come in as rows `(1, heads * P)` and
are turned into columns in the kernel (as the flash kernels turn their
log-sum-exp), `y` leaves as such a row.

`update_stacked_state` is the entry; `engages` says whether a step takes it
(a TPU, and shapes the tiles take), and the caller (models/granite_hybrid.py)
keeps `ssm_step` as the definition and the path everywhere else.

The gated delta rule (models/qwen3_next.py) keeps a MATRIX a head, `(K, V)`
float32 in a stack `(layers, lanes, H, K, V)`, and its step reads the state
before it writes it: `S' = a S; d = b (v - S'^T k); S'' = S' + k (x) d;
o = S''^T q`. Same grid, index maps and aliasing (`_stacked_call`), another
body: a live lane's block is read once, decayed, `S'^T k` taken as a multiply
and a sum over the key rows (float32 on the vector unit), the outer product
added, `S''^T q` taken the same way and `S''` written over the block. The
tile is `(heads * K, V)`: V on the lanes, one row a (head, key) pair; `a`, `k`
and `q` come in as rows `(1, heads * K)` and are turned into columns, `v`, `b`
(a number a head, laid over the V lanes) and `o` are `(heads, V)`.
`delta_update_stacked_state` is that entry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# The kernel's blocks (a lane's block of state in and out, each double
# buffered) may take this much of a core's VMEM. A v5e core has 128 MiB and
# the compiler gives a kernel 16 MiB unless told otherwise; 64 heads x 64 x
# 128 float32 is 2 MiB a block, 8 MiB for the four.
_VMEM_FOR_BLOCKS = 12 * 2**20
_VMEM_LIMIT = 32 * 2**20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def heads_per_block(H: int, P: int, N: int) -> int:
    """The largest divisor of H whose block fits `_VMEM_FOR_BLOCKS` four
    times over and whose rows are whole lane-rows of 128 (0: none does).
    The fewer grid steps, the nearer a bare copy: one v5e, 36 layers x 32
    lanes, 16 / 32 / 64 heads a block 9.39 / 8.34 / 8.00 ms, the copy 7.53
    (my chip run, PR 36)."""
    fits = [hb for hb in range(1, H + 1)
            if H % hb == 0 and (hb * P) % 128 == 0 and 4 * hb * P * N * 4 <= _VMEM_FOR_BLOCKS]
    return max(fits, default=0)


def supported(H: int, P: int, N: int) -> bool:
    """The kernel's tiles are (8, 128): N fills the lanes, P whole groups of
    sublanes, and some block of heads fits the VMEM."""
    return N % 128 == 0 and P % 8 == 0 and heads_per_block(H, P, N) > 0


def engages(H: int, P: int, N: int) -> bool:
    """Whether a step of a state (.., H, P, N) takes the kernel: the backend
    is a TPU and the tiles take the shapes. Nothing else chooses the path."""
    return _on_tpu() and supported(H, P, N)


def _kernel(mi_ref, n_live_ref, order_ref, decay_ref, dtx_ref, b_ref, c_ref, h_ref,
            y_ref, o_ref):
    i = pl.program_id(0)
    n_live = n_live_ref[0]
    hb, P, N = h_ref.shape[2:]

    @pl.when(i < n_live)
    def _():
        decay = jnp.transpose(decay_ref[0], (1, 0))          # (hb * P, 1)
        dtx = jnp.transpose(dtx_ref[0], (1, 0))
        h = decay * h_ref[0, 0].reshape(hb * P, N) + dtx * b_ref[0]
        o_ref[0, 0] = h.reshape(hb, P, N)
        y_ref[0] = jnp.transpose(jnp.sum(h * c_ref[0], axis=-1, keepdims=True), (1, 0))

    # no lane is live: the grid's steps all name lane 0's last block, which
    # is written back once at the end, so it has to hold the row
    @pl.when(jnp.logical_and(i == 0, n_live == 0))
    def _():
        o_ref[...] = h_ref[...]


def _stacked_call(kernel, name, ssm, mi, order, n_live, rows, vecs, heads, y_is_row: bool):
    """The one `pallas_call` of both bodies over ssm (M, L, H, P, N) float32,
    aliased onto the second result. Grid (lanes, blocks of heads); the layer
    `mi` and the compacted live lanes (`order`, `n_live`) are scalar
    prefetch. Operands by how a grid step sees them: `rows` (L, 1, H * P),
    a block of heads' columns; `vecs` (L, 1, N), the lane's whole; `heads`
    (L, H, N), a block of heads' rows. The first result is such a row
    (`y_is_row`) or such a block of heads."""
    M, L, H, P, N = ssm.shape
    hb = heads_per_block(H, P, N)
    nj, R = H // hb, hb * P

    # a step past the live lanes repeats the last live step's block indices
    def at(i, j, n_live):
        return jnp.where(i < n_live[0], j, nj - 1)

    row = pl.BlockSpec((1, 1, R), lambda i, j, mi, n, order: (order[i], 0, at(i, j, n)))
    vec = pl.BlockSpec((1, 1, N), lambda i, j, mi, n, order: (order[i], 0, 0))
    head = pl.BlockSpec((1, hb, N), lambda i, j, mi, n, order: (order[i], at(i, j, n), 0))
    state = pl.BlockSpec((1, 1, hb, P, N),
                         lambda i, j, mi, n, order: (mi[0], order[i], at(i, j, n), 0, 0))
    n_in = len(rows) + len(vecs) + len(heads)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(L, nj),
            in_specs=[row] * len(rows) + [vec] * len(vecs) + [head] * len(heads) + [state],
            out_specs=[row if y_is_row else head, state]),
        out_shape=[jax.ShapeDtypeStruct((L, 1, H * P) if y_is_row else (L, H, N), F32),
                   jax.ShapeDtypeStruct(ssm.shape, F32)],
        input_output_aliases={3 + n_in: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        name=name,
    )(jnp.reshape(mi, (1,)).astype(jnp.int32), n_live, order, *rows, *vecs, *heads, ssm)


@jax.jit  # the five layer scans of a macro-step share one lowering of the kernel (`setup_s`)
def _ssm_update_pallas(ssm, mi, order, n_live, decay, dtx, B, C):
    """ssm (M, L, H, P, N) float32, aliased onto the second result; decay
    (L, H), dtx (L, H, P), B and C (L, N), all float32. Returns (sum_N(h' C)
    (L, H, P), meaningful on live lanes only, and the stack)."""
    M, L, H, P, N = ssm.shape
    y, ssm = _stacked_call(
        _kernel, "ssm_update", ssm, mi, order, n_live,
        rows=(jnp.repeat(decay, P, axis=-1).reshape(L, 1, H * P), dtx.reshape(L, 1, H * P)),
        vecs=(B.reshape(L, 1, N), C.reshape(L, 1, N)), heads=(), y_is_row=True)
    return y.reshape(L, H, P), ssm


def update_stacked_state(ssm, mi, live, x, dt, A, B, C, D):
    """Layer `mi`'s recurrence for one position on the live lanes of the
    stacked state. ssm (M, L, H, P, N) float32; `live` = (the lanes' flags
    (L,) bool, the live lanes' indices in rising order with the last of
    them repeated to the end (L,) int32, their number (1,) int32); x (L, H,
    P); dt (L, H) float32; A, D (H,); B, C (L, N). Returns (y (L, H, P)
    float32, zero on a lane that is not live; the stack, that lane's row and
    every other layer untouched)."""
    active, order, n_live = live
    xf = x.astype(F32)
    y, ssm = _ssm_update_pallas(
        ssm, mi, order, n_live, jnp.exp(dt * A), dt[:, :, None] * xf, B.astype(F32),
        C.astype(F32))
    y = y + D[None, :, None] * xf
    return jnp.where(active[:, None, None], y, 0.0), ssm


# ------------------------------------------------------ the gated delta rule
def _delta_kernel(mi_ref, n_live_ref, order_ref, a_ref, k_ref, q_ref, v_ref, b_ref, h_ref,
                  o_ref, s_ref):
    i = pl.program_id(0)
    n_live = n_live_ref[0]
    hb, K, V = h_ref.shape[2:]
    column = lambda ref: jnp.transpose(ref[0], (1, 0)).reshape(hb, K, 1)  # noqa: E731

    @pl.when(i < n_live)
    def _():
        k = column(k_ref)
        s = column(a_ref) * h_ref[0, 0]                                  # (hb, K, V)
        d = b_ref[0] * (v_ref[0] - jnp.sum(s * k, axis=1))               # (hb, V)
        s = s + k * d[:, None, :]
        s_ref[0, 0] = s
        o_ref[0] = jnp.sum(s * column(q_ref), axis=1)

    @pl.when(jnp.logical_and(i == 0, n_live == 0))  # `_kernel` says why
    def _():
        s_ref[...] = h_ref[...]


@jax.jit  # every layer scan of a macro-step shares one lowering of the kernel
def _delta_update_pallas(state, mi, order, n_live, a, k, q, v, b):
    """state (M, L, H, K, V) float32, aliased onto the second result; a, b
    (L, H); k, q (L, H, K); v (L, H, V); all float32. Returns (S''^T q (L, H,
    V), meaningful on live lanes only, and the stack)."""
    M, L, H, K, V = state.shape
    row = lambda x: x.reshape(L, 1, H * K)  # noqa: E731
    return _stacked_call(
        _delta_kernel, "gdn_update", state, mi, order, n_live,
        rows=(row(jnp.repeat(a, K, axis=-1)), row(k), row(q)), vecs=(),
        heads=(v, jnp.broadcast_to(b[:, :, None], v.shape)), y_is_row=False)


def delta_update_stacked_state(state, mi, live, q, k, v, a, b):
    """Layer `mi`'s gated delta rule for one position on the live lanes of
    the stacked state (M, L, H, K, V) float32: `live` as
    `update_stacked_state` takes it; q, k (L, H, K) (a key head repeated for
    each of its value heads); v (L, H, V); a = exp(g), b = beta (L, H)
    float32. Returns (o (L, H, V) float32, zero on a lane that is not live;
    the stack, that lane's row and every other layer untouched)."""
    active, order, n_live = live
    f = lambda x: x.astype(F32)  # noqa: E731
    o, state = _delta_update_pallas(state, mi, order, n_live, a, f(k), f(q), f(v), b)
    return jnp.where(active[:, None, None], o, 0.0), state
