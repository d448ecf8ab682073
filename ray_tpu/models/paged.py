"""The paged serving skeleton that every architecture's decode module runs.

The device cache is a global pool of fixed-size blocks,
(L, n_blocks, block_size, *row), and each lane's sequence lives in the
blocks its BLOCK TABLE names: PagedAttention (Kwon et al., SOSP '23)
restated for static shapes. Tables are host-planned i32 arrays that ride
every dispatch as program arguments exactly like prompt tokens do, so the
number of lanes decouples from sequence length with zero recompiles. Block 0
is the NULL block: inactive lanes and plan-padding rows aim their writes at
it, which is what makes speculative macro plans safe when blocks are freed
and reused mid-plan (a stopped lane cannot corrupt its block's next owner).
Sampling (temperature/top-k/top-p via jax.random.categorical) and stop-token
detection run INSIDE the decode scan with a per-lane rng threaded through
the cache, so scheduling stays host-plannable: the host plans speculatively
and repairs when resolved tokens reveal early stops (serve/llm_engine.py).

This module holds what does not depend on the model: the macro-step's phase
and step skeleton (`macro_step_slots_paged`, `admit_phase`, `admit_pieces`),
the pool's writes and reads (`write_decode_kv`, `attend_decode_paged`,
`write_admission_kv`, `_attend_admission`, `write_lane_rows`;
`attend_decode_paged` is the DEFINITION of a decode step's read of the pool,
and the one read path here: ops/paged_decode_attention.py is the same
attention as a kernel that reads a flat pool in place, each lane for its
own blocks, which a decode module may take where it engages: phi4flash_decode,
whose K/V pool layer has eight readers a step, and sarvam_mla_decode, whose
latent pool is its single-pool form), what an
admission and a decode step end with (`finish_admission`,
`finish_decode_step`, `sample_tokens`), the block movers of the KV plane
(`gather_kv_blocks`, `import_kv_blocks`, `scatter_kv_blocks`,
`copy_kv_blocks`) and `generate_through_paged_cache`. It imports no model:
a decode module (`models/<model>_decode.py`) imports this one, a model
definition (`models/<model>.py`) imports no decode module, and serve/
reaches a decode module only through `cfg.decode_module`
(tests/test_lint_paged_kv.py holds all three).

WHAT A DECODE MODULE OFFERS THE ENGINE (tests/test_decode_modules.py holds
the eight to it). `cfg.decode_module` of a model's config names the module;
`ContinuousBatchingEngine` and `serve/llm.py` take from it, by name:

  init_paged_cache(cfg, n_slots, n_blocks, block_size) -> cache
      The model's device state, a dict of arrays with at least `pos` and
      `remaining` (n_slots,) int32 and `rng` (n_slots, 2) uint32. What else
      it holds (K/V pools, a latent pool, rings, recurrent rows, `counts`;
      brumby_decode holds lane state and NO pool) is the module's own: the
      engine hands the dict back to the macro-step and never looks inside,
      except through the block movers below.
  jitted_macro_step_slots_paged(cfg, chunk, sampled=True) -> jitted program
      Memoised (`functools.lru_cache`) `jax.jit` of the module's own
      `macro_step_slots_paged` under that name (`_bind`; a device trace
      finds the program by it) with the cache, argument 1, donated. The
      program is `macro_step_slots_paged` below with the module's
      `admit_slots_paged` and `decode_step_slots_paged` as its halves:
      (params, cache, feed, *plan) -> (toks, firsts, feed, cache), and a
      fifth return, (len(DEVICE_COUNTERS),) int32, where the module names
      DEVICE_COUNTERS.
  state_bytes_per_lane(cfg) -> int
      Bytes a lane holds beside its blocks (recurrent rows, rings). Not 0:
      the engine refuses what needs a lane's state from blocks alone
      (prefix reuse, speculation, migration, the cluster cache) and counts
      `state_lane_steps`.
  generate(params, prompt, cfg, max_new_tokens) -> (R, max_new_tokens) int32
      Greedy static generation for prompts (R, T) of one length, one device
      program: what `serve/llm.py` runs with `continuous=False` and what
      tests compare the engine's tokens with.

and, where the module has them:

  DEVICE_COUNTERS  tuple of names. The macro-step returns their sums over
      the dispatch as a fifth value; the engine fetches it one dispatch
      behind, beside the tokens, and adds each to the metric of its name.
      They steer nothing. A module that shares another's expert layer
      re-imports the tuple (`# noqa: F401`): the name in the module's own
      namespace IS the interface.
  LATENT_POOL  True: the cache is one pool of latent rows, not a K and a V
      pool, so the block movers below do not fit it; the engine refuses the
      same features as for a module with lane state.
  init_spec_cache, jitted_macro_step_slots_spec  the draft pool and the
      speculative macro-step; asked for only with a `draft_model`, which the
      engine refuses for a module with lane state or a latent pool
      (llama_decode alone has them).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.blockwise_attention import NEG_INF


# The two halves of a macro-step phase, as `jax.named_scope`s: every device
# operation of the admission branch carries ADMIT_SCOPE in its name stack and
# every one of a decode step DECODE_SCOPE, so a device trace splits one
# dispatch's time into prefill and decode (benchmark/program_spans.py reads
# them). Metadata only: the compiled program is the same without them.
ADMIT_SCOPE = "admit_prefill"
DECODE_SCOPE = "decode_chunk"


def copy_kv_blocks(cache: Dict[str, Any], src, dst) -> Dict[str, Any]:
    """Copy-on-write block copies: rows dst[i] <- src[i] across every
    layer, K and V. src/dst are (N,) i32 block ids (host-planned by
    BlockAllocator.ensure_writable)."""
    out = dict(cache)
    out["k"] = cache["k"].at[:, dst].set(cache["k"][:, src])
    out["v"] = cache["v"].at[:, dst].set(cache["v"][:, src])
    return out


def gather_kv_blocks(cache, blocks):
    """Lift `blocks` (N,) i32 out of the pool as contiguous device
    slices: -> (k (L, N, bs, kvh, hd), v (...)). The KV-plane export
    kernel — a migrating request's blocks leave the pool as ONE pair of
    arrays (the object plane ships them zero-copy), never block by
    block. Callers bucket-pad `blocks` with the null block; its slices
    are garbage the importer writes straight back into ITS null block."""
    return cache["k"][:, blocks], cache["v"][:, blocks]


def import_kv_blocks(cache, dst, k, v, slot, pos, remaining, rng):
    """KV-plane import: scatter gathered slices into this pool's `dst`
    (N,) i32 blocks and arm `slot` to resume decoding mid-stream at
    absolute position `pos` with `remaining` tokens owed and the
    request's carried rng key (2,) u32. dst's bucket-padding entries
    are the null block — duplicate index-0 writes race only over which
    garbage lands in the garbage block. One fused dispatch per
    migration; the pool buffers are donated."""
    out = dict(cache)
    out["k"] = cache["k"].at[:, dst].set(k)
    out["v"] = cache["v"].at[:, dst].set(v)
    out["pos"] = cache["pos"].at[slot].set(pos)
    out["remaining"] = cache["remaining"].at[slot].set(remaining)
    out["rng"] = cache["rng"].at[slot].set(rng)
    return out


def scatter_kv_blocks(cache, dst, k, v):
    """Prefix-import scatter: land fetched cluster-cache KV slices in
    this pool's `dst` blocks WITHOUT arming any slot — the blocks go to
    the radix prefix cache, not a resuming request, so pos/remaining/rng
    stay untouched (a slot-armed variant would corrupt slot 0 for
    imports that have no slot). dst's padding entries are the null
    block."""
    out = dict(cache)
    out["k"] = cache["k"].at[:, dst].set(k)
    out["v"] = cache["v"].at[:, dst].set(v)
    return out


def _split_slot_keys(keys):
    """(B, 2) u32 raw keys -> (carried (B, 2), subkeys (B, 2))."""
    pairs = jax.vmap(jax.random.split)(keys)  # (B, 2, 2)
    return pairs[:, 0], pairs[:, 1]


def _topk_topp_mask(scaled, top_ks, top_ps):
    """Mask `scaled` logits (B, V) to the per-row top-k / nucleus
    (top-p) support: entries outside it go to -inf. top_k == 0 and
    top_p == 1.0 disable their filters; ties at the cutoff are kept."""
    V = scaled.shape[-1]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_eff = jnp.where(top_ks > 0, jnp.minimum(top_ks, V), V)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = cum_before < top_ps[:, None]  # the argmax column is always kept
    pth = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
    cutoff = jnp.maximum(kth, pth)
    return jnp.where(scaled >= cutoff, scaled, -jnp.inf)


def sample_tokens(logits, temps, top_ks, top_ps, keys):
    """Per-slot sampling: logits (B, V) f32, temps/top_ps (B,) f32,
    top_ks (B,) i32, keys (B, 2) u32 raw PRNG keys -> (B,) i32.
    temperature == 0 lanes take the argmax (bit-identical to the greedy
    path); sampled lanes draw jax.random.categorical over the
    temperature-scaled, top-k/top-p-masked logits with their OWN key."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0.0, temps, 1.0)
    masked = _topk_topp_mask(logits / safe_t[:, None], top_ks, top_ps)
    sampled = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def _online_softmax_update(carry, s, live, vc, pv: str):
    """One chunk of an online softmax: carry (acc, m, l) in f32, the
    chunk's scores s (..., C) f32 with `live` (broadcastable to s) marking
    the positions that count, its values vc, and the einsum `pv` of
    probabilities (cast to the value dtype) with values."""
    acc, m, l = carry
    m_new = jnp.maximum(m, jnp.where(live, s, NEG_INF).max(axis=-1))
    p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    acc = acc * corr[..., None] + jnp.einsum(
        pv, p.astype(vc.dtype), vc, preferred_element_type=jnp.float32)
    return acc, m_new, l * corr + p.sum(axis=-1)


# positions of context one iteration of the decode attention's loop reads
# from the pool for every lane (rounded to whole blocks). Chosen once on a
# v5e (PR 30, the attention alone, ms a layer at a longest context of 560 /
# 4096): 32 lanes of flat 512-column rows 0.17 / 1.04 at 128, 0.22 / 1.13 at
# 256, 0.28 / 1.08 at 512 (a chunk's gather is its bytes three times over, so
# whole chunks past the longest lane cost); 4 lanes of (8, 128) rows 0.035 /
# 0.22, 0.040 / 0.20, 0.043 / 0.16: an iteration's own overhead is small
DECODE_CHUNK = 128


def decode_chunk_positions(block_size: int, max_blocks: int) -> int:
    """Positions a chunk of attend_decode_paged covers, given the pool's
    block size and the tables' width: what the engine's `ctx_chunks` and
    `span_chunks` count in."""
    return min(max(DECODE_CHUNK // block_size, 1), max_blocks) * block_size


def attend_decode_paged(q, k_full, v_full, li, tables, pos, active, scale,
                        v_cols: int = 0):
    """Decode attention in proportion to the context the lanes hold: one
    query a lane, q (B, h, hd), lane b attending positions [0, pos[b]] of
    layer `li` of the pools AFTER the step's own K/V write; tables
    (B, MB), active (B,) bool. Returns (B, h * hd) in q's dtype.

    The context is read straight out of the pool, a chunk of blocks at a
    time under an online softmax (the arithmetic of _attend_admission's
    prefix loop with one query a row), for ceil((longest live context) /
    chunk) iterations: a trip count that is data in the program, so the
    step's attention follows what the lanes hold and never the table
    span. The gather carries the layer index (slicing the layer off the
    pool first copies it whole, every step). An inactive lane does not
    lengthen the loop; its output is whatever the live lanes' chunks
    covered of it (all zeros when no lane is live) and is discarded by
    the caller. bf16 operands, f32 scores, softmax and accumulation,
    probabilities cast to the value dtype for the PV product.

    Both pool layouts: rows of (kvh, hd), pools of rank 5, take the GQA
    products; flat rows of kvh * hd columns, rank 4 (a head size under
    128 would be padded to it on a TPU), keep the gathered chunk as it
    lies and lay the QUERY out flat instead: each query head's vector in
    its KV head's columns, zeros elsewhere, the products over all
    kvh * hd columns (kvh times the operations, on one query nothing;
    splitting the chunk's minor axis into heads would relayout it).

    The single-pool form, `v_full` None: the pool's rows are ONE key a
    position, as wide as a query (flat, one "KV head"), and a position's
    value is the first `v_cols` columns of its own key row (a latent cache:
    [c | rotary part], values c). A chunk is gathered once and read twice;
    the return is (B, h * v_cols)."""
    B, h, hd = q.shape
    bs, MB = k_full.shape[2], tables.shape[1]
    row = k_full.shape[3:]
    flat = len(row) == 1
    kvh = row[0] // hd if flat else row[0]
    hv = v_cols or hd  # a head's value columns
    if flat:
        own = jnp.eye(kvh, dtype=q.dtype)[None, :, None, :, None]  # head k's columns
        qx = (q.reshape(B, kvh, h // kvh, 1, hd) * own).reshape(B, h, kvh * hd)
        qk, pv = "bhc,bsc->bhs", "bhs,bsc->bhc"
    else:
        qx = q.reshape(B, kvh, h // kvh, hd)
        qk, pv = "bkgd,bskd->bkgs", "bkgs,bskd->bkgd"
    stat = qx.shape[:-1]
    C = decode_chunk_positions(bs, MB)
    cb = C // bs  # blocks a chunk
    # whole chunks only: the tail names the null block and is never live
    chunked = jnp.pad(tables, ((0, 0), (0, -MB % cb)))
    longest = jnp.max(jnp.where(active, pos + 1, 0))

    def chunk(i, carry):
        blocks = jax.lax.dynamic_slice_in_dim(chunked, i * cb, cb, axis=1)
        kc = k_full[li, blocks].reshape((B, C) + row)
        vc = kc[..., :v_cols] if v_full is None else v_full[li, blocks].reshape((B, C) + row)
        s = jnp.einsum(qk, qx, kc, preferred_element_type=jnp.float32) * scale
        live = (i * C + jnp.arange(C))[None, :] <= pos[:, None]  # (B, C)
        live = live.reshape((B,) + (1,) * (len(stat) - 1) + (C,))
        return _online_softmax_update(carry, s, live, vc, pv)

    acc, _, l = jax.lax.fori_loop(
        0, (longest + C - 1) // C, chunk,
        (jnp.zeros(stat + (qx.shape[-1] // hd * hv,), jnp.float32),
         jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32)),
    )
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]  # no chunk ran: zeros
    if flat:
        o = (o.reshape(B, kvh, h // kvh, kvh, hv) * own.astype(jnp.float32)).sum(axis=3)
    return o.reshape(B, h * hv).astype(q.dtype)


def write_decode_kv(k_full, v_full, li, k, v, tables, pos, active):
    """One decode step's K/V (B, 1, *row) into layer `li` of a pool
    (L, n_blocks, bs, *row): per-slot write into the slot's CURRENT
    block at its own offset, a fori_loop of small dynamic_update_slices
    (the advanced-index scatter form measured ~25 ms/step on TPU).
    Inactive lanes write the null block. A cache of ONE pool (a latent
    row a position) passes `v_full` and `v` None and gets None back."""
    B = k.shape[0]
    bs = k_full.shape[2]
    row0 = (0,) * (k_full.ndim - 3)
    pools, new = _pools(k_full, v_full), _pools(k, v)

    def write_slot(b, pools):
        rows = [jax.lax.dynamic_slice_in_dim(n, b, 1, axis=0)[None] for n in new]
        pb = jax.lax.dynamic_index_in_dim(pos, b, keepdims=False)
        ab = jax.lax.dynamic_index_in_dim(active, b, keepdims=False)
        row = jax.lax.dynamic_index_in_dim(tables, b, 0, keepdims=False)
        blk = jax.lax.dynamic_index_in_dim(row, pb // bs, keepdims=False)
        blk = jnp.where(ab, blk, 0)  # inactive lanes write the null block
        off = jnp.where(ab, pb % bs, 0)
        return tuple(jax.lax.dynamic_update_slice(f, r, (li, blk, off) + row0)
                     for f, r in zip(pools, rows))

    return _k_and_v(jax.lax.fori_loop(0, B, write_slot, pools))


def _pools(k, v):
    """(k, v), or (k,) for a cache of one pool."""
    return (k,) if v is None else (k, v)


def _k_and_v(pools):
    return pools if len(pools) == 2 else (pools[0], None)


def finish_decode_step(logits, cache, active, temps, top_ks, top_ps, stop_ids,
                       sampled: bool):
    """What every model's paged decode step ends with: the next token
    of each lane from its logits (B, V) f32 and the per-slot scalars
    after the step (`active` = remaining > 0 before it).
    Returns (next tokens, pos, remaining, rng)."""
    if sampled:
        new_rng, sub = _split_slot_keys(cache["rng"])
        nxt = sample_tokens(logits, temps, top_ks, top_ps, sub)
    else:
        new_rng = cache["rng"]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    stopped = jnp.any(nxt[:, None] == stop_ids, axis=-1) & active
    pos = cache["pos"] + active.astype(jnp.int32)
    remaining = jnp.where(stopped, 0, jnp.maximum(cache["remaining"] - 1, 0))
    return nxt, pos, remaining, new_rng


# positions of reused prefix one iteration of the admission's prefix loop
# gathers from the pool and scores (rounded to whole blocks)
PREFIX_CHUNK = 512


def _attend_admission(q, k, v, k_layer, v_layer, adm_tables, starts, cfg):
    """Admission attention, in proportion to the context a row has: q
    (A, P, h, hd) and the rows' own just-projected k/v (A, P, kvh, hd) at
    positions starts[n] + t; k_layer/v_layer (n_blocks, bs, kvh, hd) the
    layer's pool AFTER every row's suffix write; adm_tables (A, MB).

    A row's context has two parts. Its own SUFFIX is causal P x P on k/v
    as they are, no pool read (the flash forward: Pallas on the chip,
    blockwise XLA elsewhere); a real query at t < length never sees a
    right-pad key at s > t. Its reused PREFIX, positions s < starts[n],
    is read from the pool PREFIX_CHUNK positions at a time under an
    online softmax, with a trip count ceil(max(starts) / chunk) that is
    data in the program; the two merge by their log-sum-exp. When no row
    has a prefix the loop and the merge are skipped (a cond on the same
    plan array). Nowhere is there a (P x table span) score, nor a gather
    of the span. bf16 operands, f32 accumulation and softmax,
    probabilities cast to the value dtype for the PV product."""
    # imported where it is traced, as models/llama.py does: Pallas takes a
    # second to import, and only a process that traces a program needs it
    from ray_tpu.ops.flash_attention import flash_attention_fwd

    A, P, h, hd = q.shape
    kvh = cfg.n_kv_heads
    bs = k_layer.shape[1]
    MB = adm_tables.shape[1]
    o_s, lse_s = flash_attention_fwd(q, k, v, causal=True)  # (A,P,h,hd), (A,P,h)

    cb = min(max(PREFIX_CHUNK // bs, 1), MB)  # blocks a chunk
    C = cb * bs
    # whole chunks only: the tail names the null block and is never live
    chunked = jnp.pad(adm_tables, ((0, 0), (0, -MB % cb)))
    qg = q.reshape(A, P, kvh, h // kvh, hd)
    longest = jnp.max(starts)

    def chunk(i, carry):
        blocks = jax.lax.dynamic_slice_in_dim(chunked, i * cb, cb, axis=1)
        kc = k_layer[blocks].reshape(A, C, kvh, hd)
        vc = v_layer[blocks].reshape(A, C, kvh, hd)
        s = jnp.einsum(
            "apkgd,ackd->akgpc", qg, kc, preferred_element_type=jnp.float32
        ) * (hd**-0.5)
        live = (i * C + jnp.arange(C))[None, :] < starts[:, None]  # (A, C)
        live = live[:, None, None, None, :]
        return _online_softmax_update(carry, s, live, vc, "akgpc,ackd->akgpd")

    def with_prefix(o_s):
        stat = (A, kvh, h // kvh, P)
        acc, m, l = jax.lax.fori_loop(
            0, (longest + C - 1) // C, chunk,
            (jnp.zeros(stat + (hd,), jnp.float32),
             jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32)),
        )
        # a row without prefix keeps l = 0, lse_p = NEG_INF: its weight is 0
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_p = (acc / l_safe[..., None]).transpose(0, 3, 1, 2, 4).reshape(A, P, h, hd)
        lse_p = (m + jnp.log(l_safe)).transpose(0, 3, 1, 2).reshape(A, P, h)
        lse = jnp.logaddexp(lse_s, lse_p)
        out = (o_s.astype(jnp.float32) * jnp.exp(lse_s - lse)[..., None]
               + o_p * jnp.exp(lse_p - lse)[..., None])
        return out.astype(o_s.dtype)

    # no row with a prefix (every admission without a radix-cache hit):
    # the suffix part is the answer, no accumulator and no merge
    out = jax.lax.cond(longest > 0, with_prefix, lambda o_s: o_s, o_s)
    return out.reshape(A, P, h * hd).astype(cfg.dtype)


def write_admission_kv(k_full, v_full, li, k, v, adm_tables, starts, valid):
    """Every valid admission row's K/V (A, P, *row) into layer `li` of
    a pool (L, n_blocks, bs, *row), block by block from block
    starts[n] // bs of the row's table; blocks past the table's edge go
    to the null block. `v_full` and `v` None: a cache of one pool, as
    write_decode_kv takes it."""
    A, P = k.shape[:2]
    row = k.shape[2:]
    row0 = (0,) * len(row)
    bs = k_full.shape[2]
    MB = adm_tables.shape[1]
    n_chunks = P // bs
    new = _pools(k, v)

    def write_row(n, kv):
        def wr(kv):
            s0 = jax.lax.dynamic_index_in_dim(starts, n, keepdims=False) // bs
            table = jax.lax.dynamic_index_in_dim(adm_tables, n, 0, keepdims=False)

            def write_block(j, kv):
                idx = s0 + j
                blk = jax.lax.dynamic_index_in_dim(
                    table, jnp.minimum(idx, MB - 1), keepdims=False
                )
                blk = jnp.where(idx < MB, blk, 0)  # pad overshoot -> null
                cs = [jax.lax.dynamic_slice(
                    r, (n, j * bs) + row0, (1, bs) + row)[0][None, None] for r in new]
                return tuple(jax.lax.dynamic_update_slice(f, c, (li, blk, 0) + row0)
                             for f, c in zip(kv, cs))

            # a loop, eight blocks an iteration: spelled out as P // bs
            # blocks in Python, the (4, 1024) program at 16 layers took
            # twice as long to lower, to compile (78 s against 32 on a
            # v5e host, PR 28) and to load from the compile cache
            return jax.lax.fori_loop(0, n_chunks, write_block, kv,
                                     unroll=min(8, n_chunks))

        return jax.lax.cond(valid[n], wr, lambda kv: kv, kv)

    return _k_and_v(jax.lax.fori_loop(0, A, write_row, _pools(k_full, v_full)))


def finish_admission(last, cache, feed, valid, lengths, starts, slots, rems,
                     seeds, temps, top_ks, top_ps, stop_ids, sampled: bool):
    """What every model's paged admission ends with: each row's first
    output token from its true-last-position logits `last` (A, V) f32
    (sampled with a key seeded from `seeds[n]`), and the per-slot
    scalars armed for the `valid` rows (length > 0). Returns (first
    tokens, pos, remaining, feed, rng)."""
    A = last.shape[0]
    if sampled:
        row_keys = jax.vmap(jax.random.PRNGKey)(seeds)
        carried, sub = _split_slot_keys(row_keys)
        first = sample_tokens(
            last, temps[slots], top_ks[slots], top_ps[slots], sub
        )
    else:
        carried = None  # greedy plans never consume slot keys
        first = jnp.argmax(last, axis=-1).astype(jnp.int32)
    first_stopped = jnp.any(first[:, None] == stop_ids[slots], axis=-1)

    def write_one(n, state):
        def wr(st):
            pos, rem, fd, rng = st
            s = jax.lax.dynamic_index_in_dim(slots, n, keepdims=False)
            pos = pos.at[s].set(starts[n] + lengths[n])
            rem = rem.at[s].set(jnp.where(first_stopped[n], 0, rems[n]))
            fd = fd.at[s].set(first[n])
            if sampled:
                rng = rng.at[s].set(carried[n])
            return (pos, rem, fd, rng)

        return jax.lax.cond(valid[n], wr, lambda st: st, state)

    pos, rem, feed, rng = jax.lax.fori_loop(
        0, A, write_one,
        (cache["pos"], cache["remaining"], feed, cache["rng"]),
    )
    return first, pos, rem, feed, rng


# Tokens a pass over bfloat16 weights is worth on a v5e: 197 TFLOP/s over
# 819 GB/s (`benchmark/peaks.json`) is 240 operations a byte, at two
# operations a token and two bytes a weight ~240 tokens, taken at the next
# power of two. An admission of fewer tokens waits for the weights and
# costs a pass whatever its rows; one of more is bound by its rows, and
# the pass hides behind them (on the chip, PR 46: Mistral admits 1, 2 and
# 4 rows of 256 in 15.2, 30.8 and 53.4 ms where a pass is ~9; PERF.md
# section 6).
RIDGE_TOKENS = 256


def admit_pieces(n: int, lanes: int, P: int) -> Tuple[int, ...]:
    """The widths, widest first, of the admissions that a phase of `n`
    prompts runs in a program `lanes` admission rows wide and `P` tokens
    long: the binary pieces of its count (3 = 2 + 1, 7 = 4 + 2 + 1) where
    the rows left out are worth the further admissions, one piece of a
    phase that admits nobody. The candidates keep the top j bits of `n`
    and round what is left up to a power of two (n = 11: (16,), (8, 4),
    (8, 2, 1); `lanes` caps the single piece), so the widths of a choice
    are distinct. A piece costs its tokens, or a pass over the weights
    where it has fewer than `RIDGE_TOKENS`; the candidate of the least
    cost is taken, the fewest pieces among equals: from rows of 256 up
    every piece is worth its rows and the pieces are the count's own
    bits, at 16 a phase runs one piece as it did before PR 46. The device
    runs its bodies by this function (`admit_phase`) and the engine
    counts `admit_rows` and `admit_pieces` by it (`_dispatch_counts`):
    plain Python on host integers."""
    def cost(pieces):
        return sum(max(w * P, RIDGE_TOKENS) for w in pieces)

    n = max(n, 1)
    bits = [1 << b for b in reversed(range(n.bit_length())) if n >> b & 1]
    best = (min(1 << (n - 1).bit_length(), lanes),)
    for j in range(1, len(bits)):
        rest = n - sum(bits[:j])
        pieces = (*bits[:j], 1 << (rest - 1).bit_length())
        # (4, 4) is (8,), a candidate already; a sum past `lanes` has no rows
        if pieces[-1] < bits[j - 1] and sum(pieces) <= lanes and cost(pieces) < cost(best):
            best = pieces
    return best


def admit_phase(admit_rows, has_admit, rows, carry):
    """A phase's admission as the pieces of its count. `rows` are the
    phase's per-row plan arrays, each with a leading A, the prompts (A, P)
    first and their true lengths second (0 = a padding row);
    `admit_rows(rows, carry) -> (first (w,), carry)` is the admission
    proper, row-independent, for any leading w. One `lax.cond` a width w
    = A, .., 4, 2, 1, of which an admitting phase takes those that
    `admit_pieces(reach, A, P)` names, widest first, `reach` the phase's
    last non-empty row (for a plan that fills rows 0 .. n - 1,
    `_dispatch_macro`, its count n): each runs `admit_rows` under
    ADMIT_SCOPE on the w rows behind those of the pieces before it, so
    rows are admitted in plan order (a row whose table names blocks that
    an earlier row of its phase fills reads them written), and the rows
    behind the last piece are padding and are not computed. Which widths
    run and where they start is a static table the device indexes by
    `reach`. A chain of two-way conds and not one `lax.switch`: under a
    switch of three or more branches the TPU compiler copies the K/V pool
    (the hybrid's state) twice a layer in every branch but the widest
    (compiled only, PR 42); through a cond that either admits or hands
    its operands on, as the skeleton always had, they stay in place, and
    the branches share one set of temporaries, the widest's.
    -> (first (A,), carry)."""
    A, P = rows[0].shape
    reach = jnp.max(jnp.where(rows[1] > 0, jnp.arange(1, A + 1), 0))
    begins: Dict[int, List[int]] = {}  # width -> its first row by reach, -1 where it does not run
    for n in range(A + 1):
        at = 0
        for w in admit_pieces(n, A, P):
            begins.setdefault(w, [-1] * (A + 1))[n] = at
            at += w
    first = jnp.zeros((A,), jnp.int32)
    for w in sorted(begins, reverse=True):
        at = jnp.asarray(begins[w], jnp.int32)[reach]

        def run(carry, w=w, at=at):
            with jax.named_scope(ADMIT_SCOPE):
                got, carry = admit_rows(
                    tuple(jax.lax.dynamic_slice_in_dim(r, at, w) for r in rows), carry)
            return jax.lax.dynamic_update_slice(jnp.zeros((A,), jnp.int32), got, (at,)), carry

        got, carry = jax.lax.cond(
            has_admit & (at >= 0), run,
            lambda carry: (jnp.zeros((A,), jnp.int32), carry), carry)
        first = first + got
    return first, carry


def macro_step_slots_paged(params, cache, feed, steps, has_admit, prompts,
                           lengths, starts, slots, rems, seeds, tables, temps,
                           top_ks, top_ps, stop_ids, chunk: int,
                           cfg, sampled: bool = True, *, admit, decode_step):
    """Execute a K-phase macro plan as ONE jitted dispatch: a lax.scan
    over host-planned phases, each phase a cond-guarded fused admission
    prefill and up to `chunk` decode steps. Scheduling never depends on
    token values until a stop token is sampled, so the host plans K
    phases of admissions and evictions ahead from counters alone and
    ships the whole plan (with the raw prompt tokens) as arguments of
    this single program. The phase and step skeleton is every model's:
    `admit` and `decode_step` are the model's own admission and one-token
    step over its own cache pytree, with the signatures of
    llama_decode.admit_slots_paged and llama_decode.decode_step_slots_paged;
    each decode module binds its two under this function's name. A is the
    widest a phase can admit (the engine passes its lanes' bucket, `_variant`),
    not the width an admission runs at: each admitting phase runs the
    model's `admit` on the rows up to its last non-empty one as the
    pieces of its count, 3 rows as 2 + 1 (`admit_phase`: a plan fills a
    phase's rows from 0 up, and what lies behind them is not computed),
    each piece through the body of its width, so the program holds one
    admission body a width 1, 2, 4, .., A and one decode body. The
    per-phase plan arrays (K phases, B slots, A admission lanes, P the
    padded prompt width, MB table width, NS stop width; A and P are
    host-bucketed so that the jit cache stays small):
      steps    (K,)          real decode steps this phase (<= chunk);
                             steps beyond it are skipped via lax.cond, so
                             a phase shrunk to its event costs only its
                             real steps
      has_admit (K,) bool    the phase opens with an admission prefill
      prompts  (K, A, P)     right-padded admission prompts (suffixes)
      lengths  (K, A)        true lengths (0 = a padding row)
      starts   (K, A)        cached-prefix length per admission row
                             (block-aligned; its blocks are reused, not
                             re-prefilled)
      slots    (K, A)        target lane of each admission row
      rems     (K, A)        decode tokens owed after the prefill token
      seeds    (K, A) u32    per-request sampling seeds
      tables   (K, B, MB)    per-phase block tables — admissions and
                             plan-time evictions swap tables at exactly
                             the phase boundary they were planned for
      temps    (K, B) f32    0.0 => greedy argmax for that slot
      top_ks   (K, B) i32    0 => disabled
      top_ps   (K, B) f32    1.0 => disabled
      stop_ids (K, B, NS)    -1-padded device-side stop sets

    The plan is SPECULATIVE under sampling: a slot that samples a stop
    token goes inactive device-side (writes aim at the null block, pos
    freezes) while later planned phases still burn its lane — the host
    bills those steps as speculative waste and repairs its plan when
    the tokens resolve. `sampled` is STATIC (two compiled variants):
    the host knows at plan time whether any resident request samples,
    and an all-greedy plan must not pay the per-step sort/softmax/rng
    pipeline. Returns (toks (K, chunk, B), firsts (K, A), feed,
    cache): toks[k, t] is garbage for t >= steps[k] and for lanes whose
    `remaining` hit zero; the host's plan knows which entries are real."""

    def phase(carry, xs):
        (steps_k, admit_k, prompts_k, lengths_k, starts_k, slots_k, rems_k,
         seeds_k, tables_k, temps_k, topk_k, topp_k, stop_k) = xs

        def admit_rows(rows, op):
            first, c, fd = admit(
                params, *rows, *op, tables_k, temps_k, topk_k, topp_k, stop_k,
                cfg, sampled=sampled,
            )
            return first, (c, fd)

        first, (cache, feed) = admit_phase(
            admit_rows, admit_k,
            (prompts_k, lengths_k, starts_k, slots_k, rems_k, seeds_k), carry)

        def step(c, t):
            def run(op):
                cc, fd = op
                with jax.named_scope(DECODE_SCOPE):
                    _, nxt, cc = decode_step(
                        params, cc, fd, tables_k, temps_k, topk_k, topp_k,
                        stop_k, cfg, sampled=sampled,
                    )
                return cc, nxt

            cc, fd = jax.lax.cond(t < steps_k, run, lambda op: op, c)
            return (cc, fd), fd

        (cache, feed), toks = jax.lax.scan(step, (cache, feed), jnp.arange(chunk))
        return (cache, feed), (toks, first)

    (cache, feed), (toks, firsts) = jax.lax.scan(
        phase, (cache, feed),
        (steps, has_admit, prompts, lengths, starts, slots, rems, seeds,
         tables, temps, top_ks, top_ps, stop_ids),
    )
    return toks, firsts, feed, cache


def write_lane_rows(full, li, rows, slots, valid, lane_axis: int = 1):
    """full[li, ..., slots[n], ...] = rows[n] for the valid rows (lanes on
    `lane_axis` of `full`), one in-place update a row; invalid rows all
    name lane 0 and write nothing."""
    rest = rows.shape[1:]
    shape = (1,) + rest[:lane_axis - 1] + (1,) + rest[lane_axis - 1:]

    def write(n, full):
        def wr(full):
            row = jax.lax.dynamic_index_in_dim(rows, n, 0, keepdims=False)
            at = [0] * full.ndim
            at[0], at[lane_axis] = li, slots[n]
            return jax.lax.dynamic_update_slice(
                full, row.reshape(shape).astype(full.dtype), at)

        return jax.lax.cond(valid[n], wr, lambda full: full, full)

    return jax.lax.fori_loop(0, rows.shape[0], write, full)


def rows_a_piece(R: int, T: int, tokens: int) -> int:
    """How many of an admission's R rows of T positions one pass of a mixer
    takes so that it holds `tokens` tokens at the most (one row at the
    least): a divisor of R, so that the pieces are of one shape (rows are
    independent sequences, so a mixer may walk them in pieces)."""
    n = max(1, min(R, tokens // T))
    while R % n:
        n -= 1
    return n


def generate_through_paged_cache(init_cache, admit, decode_step, params, prompt,
                                 cfg, n_new: int, block: int = 16):
    """Greedy tokens (R, n_new) for prompts (R, T) of one length, for a
    model that has only the paged halves: one admission and n_new - 1
    decode steps through a paged cache that holds exactly these rows
    (`init_cache`, `admit`, `decode_step`: the model's init_paged_cache,
    admit_slots_paged and decode_step_slots_paged)."""
    R, T = prompt.shape
    mb = -(-(T + n_new) // block)
    P = -(-T // block) * block
    cache = init_cache(cfg, R, R * mb + 1, block)
    tables = 1 + jnp.arange(R * mb, dtype=jnp.int32).reshape(R, mb)
    zeros = jnp.zeros((R,), jnp.int32)
    plan = dict(temps=jnp.zeros((R,), jnp.float32), top_ks=zeros,
                top_ps=jnp.ones((R,), jnp.float32),
                stop_ids=jnp.full((R, 1), -1, jnp.int32))
    first, cache, feed = admit(
        params, jnp.pad(prompt, ((0, 0), (0, P - T))), jnp.full((R,), T, jnp.int32), zeros,
        jnp.arange(R, dtype=jnp.int32), jnp.full((R,), n_new - 1, jnp.int32),
        zeros.astype(jnp.uint32), cache, zeros, tables, cfg=cfg, sampled=False, **plan)

    def step(carry, _):
        cache, feed = carry
        _, nxt, cache = decode_step(
            params, cache, feed, tables, cfg=cfg, sampled=False, **plan)
        return (cache, nxt), nxt

    _, rest = jax.lax.scan(step, (cache, feed), None, length=n_new - 1)
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def _bind(f, **static):
    """`functools.partial(f, **static)` under `f`'s own name. `jax.jit`
    names a program after its function's `__name__`, and a bare partial
    has none: every program below would read `jit__unknown` in a device
    trace, where a reader has to find the macro-step by name."""
    bound = functools.partial(f, **static)
    bound.__name__ = f.__name__
    return bound


@functools.lru_cache(maxsize=4)
def jitted_gather_kv_blocks():
    """KV-plane export gather. Shape-polymorphic: jit re-specializes
    per bucketed block count, so callers pad block-id arrays to
    power-of-2 buckets (null-block padding) to bound the variant set."""
    return jax.jit(gather_kv_blocks)


@functools.lru_cache(maxsize=4)
def jitted_import_kv_blocks():
    """KV-plane import scatter; the pool is donated (the engine swaps
    its cache handle for the return value)."""
    return jax.jit(import_kv_blocks, donate_argnums=(0,))


@functools.lru_cache(maxsize=4)
def jitted_scatter_kv_blocks():
    """Slot-less prefix-import scatter (cluster prefix cache); donated
    pool, same bucketing discipline as the gather."""
    return jax.jit(scatter_kv_blocks, donate_argnums=(0,))

