"""Decode attention over a flat pool layer read where it lies, each lane for
its own blocks only: a Pallas TPU kernel that is
`models/paged.attend_decode_paged` for pools of flat rows, in two forms: a K
and a V pool (`phi4flash_decode.attend_pool`: one layer, eight readers a
step), and the SINGLE-POOL form, ONE pool whose rows hold their own values
(`sarvam_mla_decode.decode_mixer`: a latent cache, [c | rotary part | 0s], a
plane a sublayer).

`attend_decode_paged` (the definition, and the path everywhere else) gathers a
chunk of 128 positions of EVERY lane out of the pool, attends the copy, and
does so up to the LONGEST live lane's chunk: a lane of 300 positions beside
one of 1,200 reads 1,280. With answers that finish in waves the longest lane
holds about twice the mean, and a pool layer that eight layers of one step
read is gathered eight times (or once into a scratch as large as the pool,
which seven then loop over: PR 50). Here ONE call a reader reads the pool in
place:

- the pools `(L, n_blocks, bs, row)` go in whole and stay in main memory
  (`pl.ANY`), never sliced by layer; the layer (or plane) index, the block
  tables `(B, MB)`, `pos` and `active` are scalar-prefetch arguments;
- lane b walks ITS OWN blocks, `pos[b] // bs + 1` of them and no more, a
  GROUP of `group_chunks` chunks of `CHUNK` positions at a time: a block
  (`bs` x `row`, contiguous, ONE sublane tile, the tile ops/ring_write.py
  moves: 40 KB at 16 x 1,280 bfloat16, 20 KB at 16 x 640) comes by manual DMA
  at the index the table names, of K and of V, or ONE a block of the single
  pool, all of a group's blocks in flight at once, into one of two buffers;
  the next group (the lane's own, or the next live lane's first) is started
  before this one is waited for, so a lane's start-up hides behind the lane
  before it;
- a group is attended under ONE online softmax a lane with the arithmetic of
  `paged.attend_decode_paged`: operands as stored, float32 scores, softmax and
  accumulation, positions past `pos[b]` masked, probabilities cast to the
  value type for the PV product, a CHUNK a step. A group's scores are ONE
  product (a score is the sum of its own row's and column's terms whatever
  stands beside it; on a v5e a sixth off the single-pool form's call and 4 %
  off the flat form's against a product a chunk, PR 55), the steps after it a
  chunk at a time. The flat form's query is laid out flat IN the kernel, once
  a lane (each head's vector in its KV head's columns, zeros elsewhere: the
  flat form of `attend_decode_paged`, never in main memory), so a chunk is
  one product over all `row` columns each way; the single pool's query is one
  "KV head" as wide as a row already, and a position's value is the first
  `v_cols` columns of its own key row, sliced from the key's buffer in VMEM:
  nothing is fetched twice;
- a lane that is not live fetches nothing and returns zeros (the caller
  discards it). The values' buffers (the single pool's keys') start as zeros
  and hold nothing but pool rows after: a masked position's probability is 0
  against a finite value;
- the flat form holds all lanes' queries and results in VMEM (64 lanes of 40 x
  128: 2 MB); the single pool's are as wide as its rows (32 lanes of 64 x 640
  and their float32 results: 6.7 MB, twice that buffered), so they move a
  grid step of `lanes_per_step` lanes at a time, the next step's behind this
  one's lanes, while the walk runs on across the steps (buffers, semaphores
  and the DMAs in flight outlive a grid step).

`CHUNK` is the definition's chunk, so the online softmax takes the same steps
in the same order (a chunk past a lane's context changes nothing: every
probability 0, every correction 1), and the kernel's results were the loop's
bit for bit on a v5e at `reasoning-generate`'s shapes (PR 53, and again with
a group's scores in one product, PR 55) and at `agent-fanout-generate`'s and
`longdoc-qa`'s (PR 55; PERF.md); the matrix unit sums a product's terms in an
order of its own, so that is a reading and no promise.

`attend` is the entry; `engages` says whether a step takes it (a TPU, and
shapes the tiles take), and the callers keep `attend_decode_paged` as the
definition and the path everywhere else.
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
# the same for ONE pool of 16 x 640 bfloat16 blocks, 20 KB a DMA (ms a call on
# two draws of contexts, PR 55): `agent-fanout-generate`'s 32 lanes of ~371
# positions 0.107 / 0.110 at 1, 0.089 / 0.093 at 2, 0.085 / 0.086 at 3, 0.079 /
# 0.085 at 4 (a lane's whole context in one group, a chunk or two of it past
# the context); `longdoc-qa`'s 6 live lanes of ~2,800 0.114 / 0.131, 0.088 /
# 0.102, 0.082 / 0.093, 0.077 / 0.088. A group's cost is its start more than
# its chunks
LATENT_GROUP_CHUNKS = 4
# what a call may take of a core's VMEM, of the 16 MiB the compiler gives a
# kernel: a grid step's queries and results (twice: the pipeline's buffers),
# two groups of each pool, a lane's accumulator
_VMEM = 12 * 2**20


def group_chunks(v_cols: int = 0) -> int:
    """Chunks in a group: the flat form's, or the single pool's (`v_cols`)."""
    return LATENT_GROUP_CHUNKS if v_cols else GROUP_CHUNKS


def group_blocks(bs: int, v_cols: int = 0) -> int:
    """Blocks of `bs` positions in a group: whole chunks of whole blocks."""
    return group_chunks(v_cols) * max(CHUNK // bs, 1)


def _vmem_bytes(lanes: int, h: int, hd: int, bs: int, row: int, dtype, v_cols: int = 0) -> int:
    """What a grid step of `lanes` lanes holds: their queries and float32
    results (twice: the pipeline's buffers), two groups of each pool, a lane's
    accumulator."""
    item = jnp.dtype(dtype).itemsize
    pools, hv, acc = (1, v_cols, v_cols) if v_cols else (2, hd, row)
    return (2 * lanes * h * (hd * item + hv * 4) + 2 * pools * group_blocks(bs, v_cols) * bs * row * item
            + 4 * h * acc)


def lanes_per_step(B: int, h: int, hd: int, bs: int, row: int, dtype, v_cols: int = 0) -> int:
    """Lanes whose queries and results a grid step holds in VMEM (0: the call
    does not fit). The flat form holds all B or is not taken, as it was
    measured (PR 53: 64 lanes of 40 x 128, 2 MB); a latent pool's queries are
    as wide as its rows (32 lanes of 64 x 640 and their results are 6.7 MB,
    twice that buffered), so they come the largest divisor of B at a time
    that fits, the next step's behind this one's lanes."""
    fits = [g for g in (range(1, B + 1) if v_cols else (B,))
            if B % g == 0 and _vmem_bytes(g, h, hd, bs, row, dtype, v_cols) <= _VMEM]
    return max(fits, default=0)


def supported(q_shape, pool_shape, dtype, v_cols: int = 0) -> bool:
    """The kernel moves whole blocks of a flat pool as sublane tiles and lays
    a head's query into 128-column lane-rows: pools of rank 4, a block ONE
    sublane tile of the type, heads of whole lane-rows that divide the row,
    grouped queries, and everything within the VMEM. The single-pool form
    (`v_cols`): a query as wide as a row, the value whole lane-rows of it."""
    if len(q_shape) != 3 or len(pool_shape) != 4:
        return False
    (B, h, hd), (_, _, bs, row) = q_shape, pool_shape
    if v_cols and not (row == hd and v_cols <= row and v_cols % 128 == 0):
        return False
    return (0 < slots_per_tile(dtype) == bs and hd % 128 == 0 and row % hd == 0
            and h % (row // hd) == 0 and lanes_per_step(B, h, hd, bs, row, dtype, v_cols) > 0)


def engages(q, k_full, v_full, v_cols: int = 0) -> bool:
    """Whether a step's read of pools (L, n_blocks, bs, row) by queries
    (B, h, hd) takes the kernel: the backend is a TPU, the pools are two and
    alike, or ONE whose rows hold their own values (`v_full` None and `v_cols`
    given), and the tiles take the shapes. Nothing else chooses the path."""
    pools = (v_cols > 0 if v_full is None else
             v_cols == 0 and k_full.shape == v_full.shape and k_full.dtype == v_full.dtype)
    return (_on_tpu() and pools and k_full.dtype == q.dtype
            and supported(q.shape, k_full.shape, k_full.dtype, v_cols))


def _kernel(li_ref, tables_ref, pos_ref, active_ref, q_ref, *refs, scale: float, bs: int, v_cols: int):
    """A grid step of `lanes` lanes (all B in the flat form): q_ref (lanes, h,
    hd) and o_ref (lanes, h, hv) float32 in VMEM; the pools whole in main
    memory; a buffer (2, group_blocks x bs, row) of two groups a pool and
    sem (2, pools) theirs; acc_ref the lane's accumulator; slot_ref the buffer
    the step's first lane finds its first group in (the walk runs on across
    grid steps: buffers, semaphores and DMAs in flight outlive a step)."""
    n_pools = 1 if v_cols else 2
    pools, (o_ref, *bufs, acc_ref, sem, slot_ref) = refs[:n_pools], refs[n_pools:]
    kbuf, vbuf = bufs[0], bufs[-1]  # the single pool's values lie in its keys' buffer
    lanes, h, hd = q_ref.shape
    (B, MB), row = tables_ref.shape, kbuf.shape[2]
    GC, G = group_chunks(v_cols), group_blocks(bs, v_cols)  # chunks and blocks a group
    C = kbuf.shape[1] // GC  # positions a chunk
    kvh = row // hd
    step = pl.program_id(0)
    li = li_ref[0]
    # query head r reads KV head r // (h // kvh): its columns of a flat row
    # (one KV head, as the single pool always is: the query and the result as they are)
    own = [jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0) // (h // kvh) == k for k in range(kvh)]

    def n_blocks(b):
        return jnp.where(active_ref[b] != 0, jnp.minimum(pos_ref[b] // bs + 1, MB), 0)

    def next_live(b):
        """The first live lane at or after b (B: none)."""
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(i < B, active_ref[jnp.minimum(i, B - 1)] == 0),
            lambda i: i + 1, b)

    def each_block(b, g, slot, do):
        """`do` on the DMAs of group g of lane b into buffer `slot`: each
        pool's, of each block the lane holds there."""
        def block(j, _):
            blk = tables_ref[b, g * G + j]
            at = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                do(pltpu.make_async_copy(pool.at[li, blk], buf.at[slot, at], sem.at[slot, i]))

        jax.lax.fori_loop(0, jnp.minimum(G, n_blocks(b) - g * G), block, None)

    def start(b, g, slot):
        each_block(b, g, slot, lambda dma: dma.start())

    @pl.when(step == 0)
    def _():
        # the values' buffers start as zeros and hold nothing but pool rows after
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        first = next_live(0)

        @pl.when(first < B)
        def _():
            start(first, 0, 0)

    def lane(j, slot):
        b = step * lanes + j
        groups = (n_blocks(b) + G - 1) // G
        after = next_live(b + 1)
        q = q_ref[j]
        qx = q if kvh == 1 else jnp.concatenate(
            [jnp.where(mine, q, jnp.zeros_like(q)) for mine in own], axis=1)  # (h, row)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(first_pos, s, vc, m, l):
            """One step of the lane's online softmax: positions first_pos ..
            first_pos + C - 1, their scores s (h, C) and values vc."""
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
            # the group's scores in ONE product (a score is its own row's and
            # column's sum, whatever stands beside it), then the definition's
            # steps a chunk at a time
            s = jax.lax.dot_general(qx, kbuf[slot], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale  # (h, GC x C)
            for c in range(GC):
                vc = vbuf[slot, pl.ds(c * C, C)]
                m, l = chunk((g * GC + c) * C, s[:, c * C:(c + 1) * C],
                             vc[:, :v_cols] if v_cols else vc, m, l)
            return 1 - slot, m, l

        slot, _, l = jax.lax.fori_loop(
            0, groups, group,
            (slot, jnp.full((h, 1), NEG_INF, jnp.float32), jnp.zeros((h, 1), jnp.float32)))
        acc = acc_ref[...]
        o = acc if kvh == 1 else sum(
            jnp.where(mine, acc[:, k * hd:(k + 1) * hd], 0.0) for k, mine in enumerate(own))
        o_ref[j] = o / jnp.where(l == 0.0, 1.0, l)  # no group ran: zeros
        return slot

    slot_ref[0] = jax.lax.fori_loop(0, lanes, lane, slot_ref[0])


# one lowering for the full layer and the rolled cross layers, and for a latent model's every sublayer
@functools.partial(jax.jit, static_argnames=("scale", "v_cols"))
def _paged_decode_attention_pallas(q, k_full, v_full, li, tables, pos, active, *, scale: float,
                                   v_cols: int = 0):
    """q (B, h, hd); the pools (L, n_blocks, bs, row), `v_full` None in the
    single-pool form; li a scalar; tables (B, MB), pos (B,) int32; active (B,)
    bool. Returns (B, h, hd) float32, (B, h, v_cols) in the single-pool form."""
    B, h, hd = q.shape
    bs, row = k_full.shape[2:]
    hv = v_cols or hd
    lanes = lanes_per_step(B, h, hd, bs, row, k_full.dtype, v_cols)
    pools = [k_full] if v_cols else [k_full, v_full]
    group = pltpu.VMEM((2, group_blocks(bs, v_cols) * bs, row), k_full.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, v_cols=v_cols),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B // lanes,),
            in_specs=[pl.BlockSpec((lanes, h, hd), lambda i, *_: (i, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((lanes, h, hv), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[group] * len(pools) + [
                pltpu.VMEM((h, v_cols or row), jnp.float32), pltpu.SemaphoreType.DMA((2, len(pools))),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, h, hv), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), tables.astype(jnp.int32), pos.astype(jnp.int32),
      active.astype(jnp.int32), q, *pools)


def attend(q, k_full, v_full, li, tables, pos, active, scale: float, v_cols: int = 0):
    """`paged.attend_decode_paged(q, k_full, v_full, li, tables, pos, active,
    scale, v_cols)` for flat pools: q (B, h, hd), lane b attending positions
    [0, pos[b]] of layer `li` through its row of `tables`. Returns (B, h * hd)
    in q's dtype, (B, h * v_cols) in the single-pool form; a lane that is not
    live comes out zeros."""
    o = _paged_decode_attention_pallas(q, k_full, v_full, li, tables, pos, active,
                                       scale=float(scale), v_cols=v_cols)
    return o.reshape(q.shape[0], -1).astype(q.dtype)
