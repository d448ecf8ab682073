"""One decode step of power retention's state (models/brumby.py), in place in
the stacked state: the Pallas TPU kernel `retention_update`.

The state of all layers is one array `(layers, lanes, KV, d + pad, W)`
float32: a KV head's matrix is the transpose of S with the normaliser z as
row d (`brumby.STATE_PAD` rows behind the d value rows), W = (d/2 + 1) d the
width of `phi`, 4.5 MB a head and 36 MB a lane and layer at d = 128. A decode
step of layer `li` is, for every LIVE lane and KV head,
`S' = g S + [v | 1 | 0..] (x) phi(k)` and, for each of the head's G query
heads, `[num | den | ..] = S' phi(q)`, `o = num / (den + eps)`: elementwise
float32. Plain XLA makes three passes over the layer (the update, a select
over all lanes and the write, the read for the query) and pays for lanes that
are not live; ops/ssm_update.py's kernels want a head's state in one block,
and no block holds this one four times over. Here:

- the whole stack goes in and comes out aliased; the layer index and the
  compacted list of live lanes are scalar prefetch (ops/ssm_update.py's
  scheme: a grid step past the live lanes repeats the last live block's index
  and does no work, so a lane that is not live costs no pass and keeps its
  state bit for bit);
- the grid is (lanes, KV heads, blocks of W). A step reads a block (d + pad,
  W / blocks) of a live head's state once, writes S' over it, and while the
  block is in fast memory adds its share of S' phi(q) for the head's G query
  heads into a scratch accumulator; the last block of a head reduces the
  accumulator over its lanes, divides and writes o;
- the arithmetic is `brumby.retention_step`'s, float32 on the vector unit
  (no matrix unit: its float32 products would round to bfloat16). The wide
  axis is the minor one, so a lane-row of phi(k) or phi(q) broadcasts over a
  block's sublanes; the value row is turned into a column once a head. Only
  the order of the sum over W may differ from XLA's;
- `phi` is made IN the kernel, a block's lane-rows at a time into scratch:
  row s of phi(x) is c_s x rotated by s against x itself (`brumby.phi`), one
  `pltpu.roll` and a product. A rotation's amount is static, so the block's
  index chooses among as many straight-line branches as a head has blocks.
  Made outside by XLA, phi(q) and phi(k) are 25 MB a layer and step written
  and read again beside 1.16 GB of state: a decode step of six layers 18.30
  ms for 17.92 (my chip runs, PR 52).

`update_stacked_state` is the entry; `engages` says whether a step takes it
(a TPU, and shapes the tiles take), and the caller (models/brumby.py) keeps
`retention_step` as the definition and the path everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128  # of a vector register
# a block of state, in and out and each double buffered, may take this much
# of a core's VMEM (a v5e core has 128 MiB; the compiler gives a kernel 16
# unless told otherwise)
_VMEM_FOR_BLOCKS = 8 * 2**20
_VMEM_LIMIT = 32 * 2**20
_QUERY_ROWS = 8  # phi(q) and o of a KV head's query heads, padded to a sublane group


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def blocks_of(rows: int, W: int) -> int:
    """The fewest blocks of whole lane-rows that W splits into evenly with a
    block (rows, W / blocks) float32 fitting `_VMEM_FOR_BLOCKS` four times
    over (0: none does)."""
    groups = W // LANES
    fits = [n for n in range(1, groups + 1)
            if groups % n == 0 and 4 * rows * (W // n) * 4 <= _VMEM_FOR_BLOCKS]
    return min(fits, default=0)


def supported(rows: int, W: int, G: int = 1) -> bool:
    """The kernel's tiles are (8, 128): a head's d value rows ONE lane-row
    wide (a row of phi is a rotation of the head's 128 lanes; the value row is
    transposed into a column), the rows behind them one sublane group, phi's
    width the d / 2 + 1 lane-rows of `brumby.phi`, at most eight query heads
    a KV head."""
    return (rows - 8 == LANES and W == (LANES // 2 + 1) * LANES and G <= _QUERY_ROWS
            and blocks_of(rows, W) > 0)


def engages(rows: int, W: int, G: int) -> bool:
    """Whether a step of a state (.., rows, W) with G query heads a KV head
    takes the kernel: the backend is a TPU and the tiles take the shapes.
    Nothing else chooses the path."""
    return _on_tpu() and supported(rows, W, G)


def _phi_rows(x, first: int, n: int):
    """Lane-rows first .. first + n - 1 of phi(x), x (rows, d): row s is c_s x
    times x rotated by s, c_0 = c_(d/2) = 1 and sqrt 2 between (`brumby.phi`),
    side by side (rows, n d)."""
    d = x.shape[-1]
    return jnp.concatenate(
        [(x if s in (0, d // 2) else x * 2.0 ** 0.5) * (x if s == 0 else pltpu.roll(x, d - s, 1))
         for s in range(first, first + n)], axis=-1)


def _kernel(li_ref, n_live_ref, order_ref, g_ref, v_ref, k_ref, q_ref, s_ref,
            o_ref, out_ref, acc_ref, pk_ref, pq_ref, *, G: int, eps: float, nb: int):
    i, b = pl.program_id(0), pl.program_id(2)
    n_live = n_live_ref[0]
    rows, cb = s_ref.shape[3:]
    d = rows - 8

    @pl.when(i < n_live)
    def _():
        @pl.when(b == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for at_b in range(nb):
            @pl.when(b == at_b)  # the block's lane-rows of phi: static rotations
            def _(at_b=at_b):
                pk_ref[...] = _phi_rows(k_ref[0, 0], at_b * (cb // d), cb // d)
                pq_ref[...] = _phi_rows(q_ref[0, 0], at_b * (cb // d), cb // d)

        g = jnp.broadcast_to(g_ref[0, 0], (8, LANES))
        one = (jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 0).astype(F32)
        vx = jnp.concatenate([jnp.transpose(v_ref[0, 0], (1, 0)), one], axis=0)  # (rows, 1)
        for r in range(rows // 8):          # a sublane group of the block
            at = slice(8 * r, 8 * r + 8)
            vr = jnp.broadcast_to(vx[at], (8, LANES))
            part = [jnp.zeros((8, LANES), F32)] * G
            for t in range(cb // LANES):    # a lane-row of it
                cols = slice(LANES * t, LANES * t + LANES)
                s = g * s_ref[0, 0, 0, at, cols] + vr * pk_ref[:, cols]
                out_ref[0, 0, 0, at, cols] = s
                part = [p + s * pq_ref[h:h + 1, cols] for h, p in enumerate(part)]
            for h in range(G):
                acc_ref[h, at, :] += part[h]

        @pl.when(b == nb - 1)
        def _():
            for h in range(G):
                acc = acc_ref[h]
                num = jnp.transpose(jnp.sum(acc[:d], axis=-1, keepdims=True), (1, 0))  # (1, d)
                den = jnp.sum(acc[d:])  # z's row; the rows behind it are zero
                o_ref[0, 0, h:h + 1, :] = num / (den + eps)

    # no lane is live: the grid's steps all name one block, which is written
    # back once at the end, so it has to hold the state (ops/ssm_update.py)
    @pl.when(jnp.logical_and(i == 0, n_live == 0))
    def _():
        out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("G", "eps"))  # one lowering for every caller
def _retention_update_pallas(state, li, order, n_live, g, v, k, q, *, G: int, eps: float):
    """state (M, L, KV, rows, W) float32, aliased onto the second result; g
    (L, KV, 1, 128) the decay over a lane-row; v and k (L, KV, 1, d); q (L,
    KV, 8, d), the KV head's G query heads times d^-0.5, zero rows behind
    them; all float32. Returns (o (L, KV, 8, d), rows G.. and lanes that are
    not live meaningless, and the stack)."""
    M, L, KV, rows, W = state.shape
    d = rows - 8
    nb = blocks_of(rows, W)
    cb = W // nb

    # a step past the live lanes repeats the last live step's block indices
    def at(i, x, n_live, last):
        return jnp.where(i < n_live[0], x, last)

    def head(i, j, b, li, n, order):
        return (order[i], at(i, j, n, KV - 1), 0, 0)

    def block(i, j, b, li, n, order):
        return (li[0], order[i], at(i, j, n, KV - 1), 0, at(i, b, n, nb - 1))

    row = pl.BlockSpec((1, 1, 1, d), head)
    heads = pl.BlockSpec((1, 1, _QUERY_ROWS, d), head)
    out_s = pl.BlockSpec((1, 1, 1, rows, cb), block)
    return pl.pallas_call(
        functools.partial(_kernel, G=G, eps=eps, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(L, KV, nb),
            in_specs=[pl.BlockSpec((1, 1, 1, LANES), head), row, row, heads, out_s],
            out_specs=[heads, out_s],
            scratch_shapes=[pltpu.VMEM((G, rows, LANES), F32), pltpu.VMEM((1, cb), F32),
                            pltpu.VMEM((_QUERY_ROWS, cb), F32)]),
        out_shape=[jax.ShapeDtypeStruct((L, KV, _QUERY_ROWS, d), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="retention_update",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), n_live, order, g, v, k, q, state)


def update_stacked_state(state, li, live, q, k, v, g, eps: float):
    """Layer `li`'s power retention for one position on the live lanes of the
    stacked state (M, L, KV, d + 8, W) float32: `live` as
    `ssm_update.update_stacked_state` takes it; q (L, H, d) times d^-0.5
    already; k, v (L, KV, d); g (L, KV) float32, the decay. Returns (o (L, H,
    d) float32, zero on a lane that is not live; the stack, that lane's state
    and every other layer untouched)."""
    active, order, n_live = live
    L, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    f = lambda x: x.astype(F32)  # noqa: E731
    q = jnp.pad(f(q).reshape(L, KV, G, d), ((0, 0), (0, 0), (0, _QUERY_ROWS - G), (0, 0)))
    o, state = _retention_update_pallas(
        state, li, order, n_live, jnp.broadcast_to(g[:, :, None, None], (L, KV, 1, LANES)),
        f(v)[:, :, None, :], f(k)[:, :, None, :], q, G=G, eps=eps)
    return jnp.where(active[:, None, None], o[:, :, :G].reshape(L, H, d), 0.0), state
