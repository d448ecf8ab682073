"""A decode step's token write into the window layers' rings, in place in the
ring stacks: a Pallas TPU kernel in the manner of ops/s6_update.py, with no
arithmetic.

A window layer keeps each lane's last `window` keys and values in two stacks
`(window layers, lanes, window, row)` (models/afmoe_decode.py): position p of
a lane lies in slot p % window. A decode step of layer `wi` puts one new row
into each: `wk[wi, b, pos[b] % window] = k[b]`, `wv[...] = v[b]`, for every
lane b. As a loop of `dynamic_update_slice`s (`afmoe_decode.write_ring_token`,
the definition) that is 2 x lanes small sequential operations a layer, each
with its index slices (276 us a layer at 64 lanes on a v5e; this kernel 20);
nothing in it is work. Here it is ONE call a layer:

- both stacks go in and come out aliased (`input_output_aliases`) and stay
  in main memory (`pl.ANY`); the layer index (traced: the layers stay
  rolled) and the lanes' slots are scalar-prefetch arguments. Nothing slices
  a layer out of a stack and nothing writes one back;
- a row cannot be put down alone: a ring's tile holds T slots (T the dtype's
  sublane tile; two bfloat16 rows share a 32-bit sublane) and the chip's
  compiler refuses a DMA of less. So a lane's write is a read-modify-write
  of the ONE tile of K's ring and of V's that holds its slot: the tiles of a
  group of lanes are fetched together (two DMAs a lane, all started before
  any is waited for), row `slot % T` of each is replaced as its tile
  arrives, and the tile goes back over itself while the next is taken up.
  Every other tile, lane and layer is never touched;
- a lane that is not live is written as the loop writes it (a slot nothing
  reads: its next admission writes the whole ring), so the stacks after a
  step are the loop's byte for byte.

`write_rows` is the entry; `engages` says whether a step takes it (a TPU, and
shapes the tiles take), and the caller (models/afmoe_decode.py) keeps
`write_ring_token` as the definition and the path everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.ssm_update import _on_tpu  # the sibling's backend test: the same chip

# what the tiles of one grid step's lanes (K's and V's, T slots each) may take
# of a core's VMEM, of the 16 MiB the compiler gives a kernel: 64 lanes of 16 x
# 1,280 bfloat16 are 5 MiB
_VMEM_FOR_TILES = 8 * 2**20


def slots_per_tile(dtype) -> int:
    """Rows of a sublane tile: 8 of 32 bits, 16 of bfloat16 (0: a type the
    kernel does not take)."""
    return {4: 8, 2: 16}.get(jnp.dtype(dtype).itemsize, 0)


def lanes_per_step(lanes: int, row: int, dtype) -> int:
    """The largest divisor of `lanes` whose K and V tiles fit
    `_VMEM_FOR_TILES` (0: not one lane's do)."""
    a_lane = 2 * slots_per_tile(dtype) * row * jnp.dtype(dtype).itemsize
    return max((g for g in range(1, lanes + 1)
                if lanes % g == 0 and g * a_lane <= _VMEM_FOR_TILES), default=0)


def supported(window: int, row: int, dtype) -> bool:
    """The kernel moves whole sublane tiles of a ring over all its columns:
    the columns whole lane-rows of 128, the window whole tiles, and a lane's
    two tiles within the VMEM."""
    T = slots_per_tile(dtype)
    return T > 0 and row % 128 == 0 and window % T == 0 and lanes_per_step(1, row, dtype) == 1


def engages(window: int, row: int, dtype) -> bool:
    """Whether a step's write into rings (.., window, row) takes the kernel:
    the backend is a TPU and the tiles take the shapes. Nothing else chooses
    the path."""
    return _on_tpu() and supported(window, row, dtype)


def _kernel(wi_ref, slot_ref, k_ref, v_ref, wk_ref, wv_ref, ok_ref, ov_ref, kt, vt, sem):
    """One group of G lanes: k_ref, v_ref (G, 1, row) the new rows, kt, vt (G,
    T, row) the tiles' scratch; the four stacks whole, in main memory, wk_ref
    and ok_ref one buffer, as wv_ref and ov_ref."""
    G, T, _ = kt.shape
    wi, first = wi_ref[0], pl.program_id(0) * G

    def copies(g, back: bool):
        """The two DMAs of lane `first + g`: its tile of K's ring and of V's
        into the scratch, or back over itself."""
        lane = first + g
        tile = pl.ds(pl.multiple_of(slot_ref[lane] // T * T, T), T)
        for i, (ring, out, held) in enumerate(((wk_ref, ok_ref, kt), (wv_ref, ov_ref, vt))):
            src, dst = (held.at[g], out.at[wi, lane, tile]) if back else (ring.at[wi, lane, tile], held.at[g])
            yield pltpu.make_async_copy(src, dst, sem.at[i, g])

    def each_lane(do):
        jax.lax.fori_loop(0, G, lambda g, _: do(g), None)

    def fetch(g):
        for dma in copies(g, back=False):
            dma.start()

    def put(g):
        for dma in copies(g, back=False):
            dma.wait()
        here = jax.lax.broadcasted_iota(jnp.int32, kt.shape[1:], 0) == slot_ref[first + g] % T
        kt[g] = jnp.where(here, k_ref[g], kt[g])
        vt[g] = jnp.where(here, v_ref[g], vt[g])
        for dma in copies(g, back=True):
            dma.start()

    def settle(g):
        for dma in copies(g, back=True):
            dma.wait()

    each_lane(fetch)
    each_lane(put)
    each_lane(settle)


@jax.jit  # every window layer and both macro-step bodies share one lowering of the kernel
def _ring_write_pallas(wk, wv, wi, slots, k, v):
    """wk, wv (W, L, window, row), aliased onto the results; slots (L,) int32
    inside the window; k, v (L, row) of the rings' type. Returns the stacks."""
    W, L, window, row = wk.shape
    T, G = slots_per_tile(wk.dtype), lanes_per_step(L, row, wk.dtype)
    new = pl.BlockSpec((G, 1, row), lambda i, wi, slots: (i, 0, 0))
    stack = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(L // G,),
            in_specs=[new, new, stack, stack], out_specs=[stack, stack],
            scratch_shapes=[pltpu.VMEM((G, T, row), wk.dtype), pltpu.VMEM((G, T, row), wv.dtype),
                            pltpu.SemaphoreType.DMA((2, G))]),
        out_shape=[jax.ShapeDtypeStruct(wk.shape, wk.dtype), jax.ShapeDtypeStruct(wv.shape, wv.dtype)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="ring_write",
    )(jnp.reshape(wi, (1,)).astype(jnp.int32), slots, k.reshape(L, 1, row), v.reshape(L, 1, row),
      wk, wv)


def write_rows(wk, wv, wi, k, v, pos):
    """`wk[wi, b, pos[b] % window] = k[b]` and the same of wv and v, for every
    lane b. wk, wv (W, L, window, row); k, v (L, row); pos (L,) int32. Returns
    (wk, wv): every other slot, lane and layer untouched."""
    slots = (pos % wk.shape[2]).astype(jnp.int32)
    return _ring_write_pallas(wk, wv, wi, slots, k.astype(wk.dtype), v.astype(wv.dtype))
