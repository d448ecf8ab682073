"""Decode attention over a flat K/V pool layer read where it lies, each lane
for its own blocks only: a Pallas TPU kernel that is
`models/paged.attend_decode_paged` for pools of flat rows.

`attend_decode_paged` (the definition, and the path everywhere else) gathers a
chunk of 128 positions of EVERY lane out of the pool, attends the copy, and
does so up to the LONGEST live lane's chunk: a lane of 300 positions beside
one of 1,200 reads 1,280. With answers that finish in waves the longest lane
holds about twice the mean, and a pool layer that eight layers of one step
read is gathered eight times (or once into a scratch as large as the pool,
which seven then loop over: PR 50). Here ONE call a reader reads the pool in
place:

- the pools `(L, n_blocks, bs, row)` go in whole and stay in main memory
  (`pl.ANY`), never sliced by layer; the layer index, the block tables
  `(B, MB)`, `pos` and `active` are scalar-prefetch arguments;
- lane b walks ITS OWN blocks, `pos[b] // bs + 1` of them and no more, a
  GROUP of `GROUP_CHUNKS` chunks of `CHUNK` positions at a time: a block of K
  and of V (`bs` x `row`, contiguous: 40 KB at 16 x 1,280 bfloat16, the tile
  ops/ring_write.py moves) comes by manual DMA at the index the table names,
  all of a group's blocks in flight at once, into one of two buffers; the
  next group (the lane's own, or the next live lane's first) is started
  before this one is waited for, so a lane's start-up hides behind the lane
  before it;
- a group is attended a CHUNK at a time under ONE online softmax a lane with
  the arithmetic of `paged.attend_decode_paged`: operands as stored,
  float32 scores, softmax and accumulation, positions past `pos[b]` masked,
  probabilities cast to the value type for the PV product. The query is laid
  out flat IN the kernel, once a lane (each head's vector in its KV head's
  columns, zeros elsewhere: the flat form of `attend_decode_paged`, never in
  main memory), so a chunk is one product over all `row` columns each way;
- a lane that is not live fetches nothing and returns zeros (the caller
  discards it). V's buffers start as zeros and hold nothing but pool rows
  after: a masked position's probability is 0 against a finite value.

`CHUNK` is the definition's chunk, so the online softmax takes the same steps
in the same order (a chunk past a lane's context changes nothing: every
probability 0, every correction 1), and at `reasoning-generate`'s shapes the
kernel's results were the loop's bit for bit on a v5e (PERF.md, PR 53); the
matrix unit sums a product's terms in an order of its own, so that is a
reading and no promise.

`attend` is the entry; `engages` says whether a step takes it (a TPU, and
shapes the tiles take), and the caller (models/phi4flash_decode.py) keeps
`attend_decode_paged` as the definition and the path everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.blockwise_attention import NEG_INF
from ray_tpu.ops.ring_write import slots_per_tile
from ray_tpu.ops.ssm_update import _on_tpu  # the sibling's backend test: the same chip

# positions attended in one step of a lane's online softmax:
# models/paged.DECODE_CHUNK, the definition's
CHUNK = 128
# chunks of one lane fetched together. On a v5e at `reasoning-generate`'s
# shapes (64 lanes of 685 positions in the mean, 16 x 1,280 bfloat16 a block;
# ms a reader, PR 53): 0.371 at 1 (16 blocks of K and V in flight behind the
# group attended), 0.339 at 2, 0.350 at 3, 0.351 at 4; the DMAs alone 0.310
GROUP_CHUNKS = 2
# what a call may take of a core's VMEM, of the 16 MiB the compiler gives a
# kernel: the queries and the result whole (twice: the pipeline's buffers),
# two groups of K and of V, a lane's accumulator
_VMEM = 12 * 2**20


def group_blocks(bs: int) -> int:
    """Blocks of `bs` positions in a group: whole chunks of whole blocks."""
    return GROUP_CHUNKS * max(CHUNK // bs, 1)


def _vmem_bytes(B: int, h: int, hd: int, bs: int, row: int, dtype) -> int:
    item = jnp.dtype(dtype).itemsize
    return 2 * B * h * hd * (item + 4) + 4 * group_blocks(bs) * bs * row * item + 4 * h * row


def supported(q_shape, pool_shape, dtype) -> bool:
    """The kernel moves whole blocks of a flat pool as sublane tiles and lays
    a head's query into 128-column lane-rows: pools of rank 4, a block ONE
    sublane tile of the type, heads of whole lane-rows that divide the row,
    grouped queries, and everything within the VMEM."""
    if len(q_shape) != 3 or len(pool_shape) != 4:
        return False
    (B, h, hd), (_, _, bs, row) = q_shape, pool_shape
    return (0 < slots_per_tile(dtype) == bs and hd % 128 == 0 and row % hd == 0
            and h % (row // hd) == 0 and _vmem_bytes(B, h, hd, bs, row, dtype) <= _VMEM)


def engages(q, k_full, v_full) -> bool:
    """Whether a step's read of pools (L, n_blocks, bs, row) by queries
    (B, h, hd) takes the kernel: the backend is a TPU, both pools are given
    and alike, and the tiles take the shapes. Nothing else chooses the path."""
    return (_on_tpu() and v_full is not None and k_full.shape == v_full.shape
            and k_full.dtype == v_full.dtype == q.dtype
            and supported(q.shape, k_full.shape, k_full.dtype))


def _kernel(li_ref, tables_ref, pos_ref, active_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, acc_ref, sem, *, scale: float, bs: int):
    """All lanes in one grid step: q_ref (B, h, hd) and o_ref (B, h, hd)
    float32 whole in VMEM; the pools whole in main memory; kbuf, vbuf (2,
    group_blocks x bs, row) the two groups' buffers, sem (2, 2) theirs;
    acc_ref (h, row) the lane's accumulator."""
    B, h, hd = q_ref.shape
    MB, row = tables_ref.shape[1], kbuf.shape[2]
    G, C = group_blocks(bs), kbuf.shape[1] // GROUP_CHUNKS  # blocks a group, positions a chunk
    kvh = row // hd
    li = li_ref[0]
    # query head r reads KV head r // (h // kvh): its columns of a flat row
    own = [jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0) // (h // kvh) == k for k in range(kvh)]
    vbuf[...] = jnp.zeros_like(vbuf)

    def n_blocks(b):
        return jnp.where(active_ref[b] != 0, jnp.minimum(pos_ref[b] // bs + 1, MB), 0)

    def next_live(b):
        """The first live lane at or after b (B: none)."""
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(i < B, active_ref[jnp.minimum(i, B - 1)] == 0),
            lambda i: i + 1, b)

    def each_block(b, g, slot, do):
        """`do` on the DMAs of group g of lane b into buffer `slot`: K's and
        V's of each block the lane holds there."""
        def block(j, _):
            blk = tables_ref[b, g * G + j]
            at = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for i, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                do(pltpu.make_async_copy(pool.at[li, blk], buf.at[slot, at], sem.at[slot, i]))

        jax.lax.fori_loop(0, jnp.minimum(G, n_blocks(b) - g * G), block, None)

    def start(b, g, slot):
        each_block(b, g, slot, lambda dma: dma.start())

    first = next_live(0)

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    def lane(b, slot):
        groups = (n_blocks(b) + G - 1) // G
        after = next_live(b + 1)
        q = q_ref[b]
        qx = jnp.concatenate([jnp.where(mine, q, jnp.zeros_like(q)) for mine in own], axis=1)  # (h, row)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(first_pos, kc, vc, m, l):
            """One step of the lane's online softmax: positions first_pos ..
            first_pos + C - 1, their keys and values kc, vc (C, row)."""
            s = jax.lax.dot_general(qx, kc, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale  # (h, C)
            live = first_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) <= pos_ref[b]
            m_new = jnp.maximum(m, jnp.where(live, s, NEG_INF).max(axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                p.astype(vc.dtype), vc, preferred_element_type=jnp.float32)
            return m_new, l * corr + p.sum(axis=-1, keepdims=True)

        def group(g, carry):
            slot, m, l = carry
            last = g + 1 == groups
            then_b, then_g = jnp.where(last, after, b), jnp.where(last, 0, g + 1)

            @pl.when(then_b < B)
            def _():
                start(then_b, then_g, 1 - slot)

            each_block(b, g, slot, lambda dma: dma.wait())
            for c in range(GROUP_CHUNKS):
                at = pl.ds(c * C, C)
                m, l = chunk((g * GROUP_CHUNKS + c) * C, kbuf[slot, at], vbuf[slot, at], m, l)
            return 1 - slot, m, l

        slot, _, l = jax.lax.fori_loop(
            0, groups, group,
            (slot, jnp.full((h, 1), NEG_INF, jnp.float32), jnp.zeros((h, 1), jnp.float32)))
        acc = acc_ref[...]
        o = sum(jnp.where(mine, acc[:, k * hd:(k + 1) * hd], 0.0) for k, mine in enumerate(own))
        o_ref[b] = o / jnp.where(l == 0.0, 1.0, l)  # no group ran: zeros
        return slot

    jax.lax.fori_loop(0, B, lane, 0)


@functools.partial(jax.jit, static_argnames=("scale",))  # one lowering for the full layer and the rolled cross layers
def _paged_decode_attention_pallas(q, k_full, v_full, li, tables, pos, active, *, scale: float):
    """q (B, h, hd); the pools (L, n_blocks, bs, row); li a scalar; tables (B,
    MB), pos (B,) int32; active (B,) bool. Returns (B, h, hd) float32."""
    B, h, hd = q.shape
    bs, row = k_full.shape[2:]
    whole = pl.BlockSpec((B, h, hd), lambda i, *_: (0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    group = pltpu.VMEM((2, group_blocks(bs) * bs, row), k_full.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[whole, pool, pool], out_specs=whole,
            scratch_shapes=[group, group, pltpu.VMEM((h, row), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, h, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), tables.astype(jnp.int32), pos.astype(jnp.int32),
      active.astype(jnp.int32), q, k_full, v_full)


def attend(q, k_full, v_full, li, tables, pos, active, scale: float):
    """`paged.attend_decode_paged(q, k_full, v_full, li, tables, pos, active,
    scale)` for flat pools: q (B, h, hd), lane b attending positions [0,
    pos[b]] of layer `li` through its row of `tables`. Returns (B, h * hd) in
    q's dtype; a lane that is not live comes out zeros."""
    o = _paged_decode_attention_pallas(q, k_full, v_full, li, tables, pos, active, scale=float(scale))
    return o.reshape(q.shape[0], -1).astype(q.dtype)
