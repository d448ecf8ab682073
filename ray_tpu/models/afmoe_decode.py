"""The AFMoE decoder on the paged serving path: window layers whose cache
stops growing, an expert layer in the decode step.

The macro-step is models/paged.macro_step_slots_paged, handed this
module's admission and decode step and this module's cache pytree:

  k, v      (full layers, n_blocks, bs, kvh * hd)  the block pool, for the
            `full_attention` layers only; tables are host state as ever.
            Heads and head size share the minor axis (four KV heads on a
            second-minor axis would be padded to a whole tile on a TPU)
  wk, wv    (window layers, lanes, window, kvh * hd)  each lane's RING of the
            last `sliding_window` positions of every `sliding_attention`
            layer: position p lies in slot p % window, keys with their RoPE
            on (so nothing is rotated again when a slot is reused). These
            are the lane's own rows beside its blocks, as the hybrid
            decoder's recurrent rows are, and their size does not depend on
            the context
  counts    (3,) int32  DEVICE_COUNTERS, summed over the dispatch's decode
            steps and expert layers; the macro-step zeroes them, and hands
            them back beside the tokens
  pos, remaining, rng   per-lane scalars

Why a ring and not a second block table whose blocks behind the window go
back to the allocator: the ring touches neither `attend_decode_paged` nor
the allocator, so no other model's program can move, and a window layer's
bytes are a constant a lane. What it costs is what the hybrid's state costs:
a ring's content at a block boundary is not kept, so nothing here can resume
a sequence from blocks alone (serve/llm_engine.py refuses what needs that
when `state_bytes_per_lane` is not 0: prefix reuse, speculation, migration).

Admission leaves in a row's ring the last `window` positions of its prompt
(a padded admission row writes nothing); the decode step writes the new
position over the oldest and reads the ring whole, masked to the slots that
hold a position; a full layer writes and reads the pool as every model's
does. The step's write is `write_ring_tokens`: on a TPU ONE call a window
layer of the kernel of ops/ring_write.py, which puts K's and V's row of
every lane where they lie in the aliased stacks; elsewhere, and for a ring
its tiles do not take, `write_ring_token`, a loop of in-place updates over
the lanes, which is the definition. Release needs no device work: the next
admission overwrites the ring.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import afmoe as M
from ray_tpu.models import paged
from ray_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig
from ray_tpu.ops.blockwise_attention import NEG_INF
from ray_tpu.ops.rope import apply_rope

F32 = jnp.float32
# what a dispatch counts on the device, in the order of cache["counts"]:
# (row, expert) pairs of live rows, distinct experts with a live row, and
# the fullest expert's rows, each summed over decode steps and expert layers
DEVICE_COUNTERS = ("expert_rows", "experts_hit", "expert_rows_max")


def init_paged_cache(cfg: AfmoeConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    row = cfg.n_kv_heads * cfg.head_dim
    pool = (cfg.n_full_layers, n_blocks, block_size, row)
    ring = (cfg.n_window_layers, n_slots, cfg.sliding_window, row)
    return {
        "k": jnp.zeros(pool, cfg.dtype),
        "v": jnp.zeros(pool, cfg.dtype),
        "wk": jnp.zeros(ring, cfg.dtype),
        "wv": jnp.zeros(ring, cfg.dtype),
        "counts": jnp.zeros((len(DEVICE_COUNTERS),), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: AfmoeConfig) -> int:
    """Bytes a lane holds beside its K/V blocks: the K and V rings of every
    window layer, whatever the context."""
    row = cfg.n_kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    return cfg.n_window_layers * 2 * cfg.sliding_window * row


def ring_rows(kv, lengths, window: int):
    """What a ring holds after a prompt: kv (A, P, C) -> (A, window, C),
    slot s the last position <= lengths - 1 that is s modulo the window
    (any position where there is none yet: the decode step's mask never
    shows such a slot before it is written)."""
    last = jnp.maximum(lengths, 1)[:, None] - 1
    at = last - (last - jnp.arange(window)[None, :]) % window
    return jnp.take_along_axis(kv, jnp.clip(at, 0, kv.shape[1] - 1)[:, :, None], axis=1)


def write_ring_token(ring, wi, kv, pos):
    """ring[wi, b, pos[b] % window] = kv[b] for every lane, one in-place
    update a lane (a lane that is not live overwrites a slot nothing will
    read: its next admission writes the whole ring)."""
    window = ring.shape[2]

    def write(b, ring):
        row = jax.lax.dynamic_slice_in_dim(kv, b, 1, axis=0)[None, :, None, :]
        return jax.lax.dynamic_update_slice(ring, row.astype(ring.dtype),
                                            (wi, b, pos[b] % window, 0))

    return jax.lax.fori_loop(0, kv.shape[0], write, ring)


def write_ring_tokens(wk, wv, wi, k, v, pos):
    """A decode step's write into BOTH rings of window layer `wi`: k, v (B,
    row) into slot pos[b] % window of every lane. On a TPU, for rings its
    tiles take, ONE call of the kernel of ops/ring_write.py, which puts each
    row where it lies in the aliased stacks; elsewhere `write_ring_token`, the
    definition, on each. The stacks come out the same byte for byte."""
    from ray_tpu.ops import ring_write  # Pallas: imported where it is traced

    if ring_write.engages(wk.shape[2], wk.shape[3], wk.dtype):
        return ring_write.write_rows(wk, wv, wi, k, v, pos)
    return write_ring_token(wk, wi, k, pos), write_ring_token(wv, wi, v, pos)


def ring_slots_held(pos, window: int):
    """(B, window) bool: the slots of a lane's ring that hold a position
    once position pos[b] is written. Slot s holds pos - (pos - s) % window,
    inside the window by construction, and there when it is not negative."""
    return (pos[:, None] - jnp.arange(window)[None, :]) % window <= pos[:, None]


def attend_decode_ring(q, wk, wv, wi, pos, scale):
    """Decode attention of a window layer: one query a lane, q (B, h, hd),
    over the slots of the lane's ring of layer `wi` that hold a position
    AFTER the step's own write (`ring_slots_held`). The ring's rows lie
    flat (kvh * hd columns) and stay so: the QUERY is laid out flat
    instead, each head's vector in its KV head's columns, zeros elsewhere
    (attend_decode_paged's flat form). Returns (B, h * hd) in q's type."""
    B, h, hd = q.shape
    window = wk.shape[2]
    kvh = wk.shape[3] // hd
    own = jnp.eye(kvh, dtype=q.dtype)[None, :, None, :, None]
    qx = (q.reshape(B, kvh, h // kvh, 1, hd) * own).reshape(B, h, kvh * hd)
    kc = jax.lax.dynamic_index_in_dim(wk, wi, 0, keepdims=False)
    vc = jax.lax.dynamic_index_in_dim(wv, wi, 0, keepdims=False)
    s = jnp.einsum("bhc,bsc->bhs", qx, kc, preferred_element_type=F32) * scale
    held = ring_slots_held(pos, window)
    p = jax.nn.softmax(jnp.where(held[:, None, :], s, NEG_INF), axis=-1)
    o = jnp.einsum("bhs,bsc->bhc", p.astype(vc.dtype), vc, preferred_element_type=F32)
    o = (o.reshape(B, kvh, h // kvh, kvh, hd) * own.astype(F32)).sum(axis=3)
    return o.reshape(B, h * hd).astype(q.dtype)


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: AfmoeConfig, sampled: bool = True):
    """Fused paged admission of A right-padded prompts (A, P), with
    llama_decode.admit_slots_paged's arguments and returns. `starts` is all
    zeros here: without the rings at a block boundary no prefix is reused."""
    A, P = prompts.shape
    adm_tables = tables[slots]
    valid = lengths > 0
    cos, sin = M.rope_tables(cfg, P)
    window = cfg.sliding_window
    # the rows that are a prompt's: a padded row chooses no expert (what it
    # leaves is read by nothing: attention is causal, the head reads each
    # row's last real position, the pool's padded positions are masked)
    real = (jnp.arange(P)[None, :] < lengths[:, None]).reshape(-1)

    def window_mixer(layer, wi, a, carry):
        k_full, v_full, wk, wv = carry
        with jax.named_scope(M.SCOPE_WINDOW):
            q, k, v, gate = M.qkvg(layer, a, cfg)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            wk = paged.write_lane_rows(wk, wi, ring_rows(k.reshape(A, P, -1), lengths, window),
                                       slots, valid)
            wv = paged.write_lane_rows(wv, wi, ring_rows(v.reshape(A, P, -1), lengths, window),
                                       slots, valid)
            out = M.gated_out(M.sequence_attention(q, k, v, cfg, window), gate, layer, cfg)
        return out, (k_full, v_full, wk, wv)

    def full_mixer(layer, fi, a, carry):
        k_full, v_full, wk, wv = carry
        with jax.named_scope(M.SCOPE_FULL):
            q, k, v, gate = M.qkvg(layer, a, cfg)
            k_full, v_full = paged.write_admission_kv(
                k_full, v_full, fi, k.reshape(A, P, -1), v.reshape(A, P, -1),
                adm_tables, starts, valid)
            out = M.gated_out(M.sequence_attention(q, k, v, cfg, None), gate, layer, cfg)
        return out, (k_full, v_full, wk, wv)

    x, (k_full, v_full, wk, wv) = M.run_layers(
        params, M.embed_tokens(params, prompts, cfg),
        (cache["k"], cache["v"], cache["wk"], cache["wv"]), cfg,
        {SLIDING: window_mixer, FULL: full_mixer},
        lambda p, m, carry: (M.moe_ffn(m, p, cfg, live=real)[0], carry))
    # the head at each row's last real position only: all P positions in
    # float32 over this vocabulary would be gigabytes
    x_last = jnp.take_along_axis(
        x, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1)[:, 0, :]
    first, pos, rem, feed, rng = paged.finish_admission(
        M.logits_of(params, x_last, cfg), cache, feed, valid, lengths, starts,
        slots, rems, seeds, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "wk": wk, "wv": wv, "counts": cache["counts"],
             "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: AfmoeConfig,
                            sampled: bool = True):
    """One token on every lane, with llama_decode.decode_step_slots_paged's
    arguments and returns. The expert layers route the live lanes' rows
    only: a lane that is not live chooses nothing, so no expert's weights
    are read for it and the counters do not see it."""
    B = tokens.shape[0]
    pos = cache["pos"]
    active = cache["remaining"] > 0
    scale = cfg.head_dim ** -0.5
    cos, sin = M.rope_tables(cfg, tables.shape[1] * cache["k"].shape[2])

    def window_mixer(layer, wi, a, carry):
        k_full, v_full, wk, wv, counts = carry
        with jax.named_scope(M.SCOPE_WINDOW):
            q, k, v, gate = M.qkvg(layer, a[:, None, :], cfg)
            q = apply_rope(q, cos, sin, pos[:, None])
            k = apply_rope(k, cos, sin, pos[:, None])
            wk, wv = write_ring_tokens(wk, wv, wi, k.reshape(B, -1), v.reshape(B, -1), pos)
            out = M.gated_out(attend_decode_ring(q[:, 0], wk, wv, wi, pos, scale),
                              gate[:, 0], layer, cfg)
        return out, (k_full, v_full, wk, wv, counts)

    def full_mixer(layer, fi, a, carry):
        k_full, v_full, wk, wv, counts = carry
        with jax.named_scope(M.SCOPE_FULL):
            q, k, v, gate = M.qkvg(layer, a[:, None, :], cfg)
            k_full, v_full = paged.write_decode_kv(
                k_full, v_full, fi, k.reshape(B, 1, -1), v.reshape(B, 1, -1),
                tables, pos, active)
            out = M.gated_out(
                paged.attend_decode_paged(q[:, 0], k_full, v_full, fi, tables, pos, active, scale),
                gate[:, 0], layer, cfg)
        return out, (k_full, v_full, wk, wv, counts)

    def experts(p, m, carry):
        out, sizes = M.moe_ffn(m, p, cfg, live=active)
        seen = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()]).astype(jnp.int32)
        return out, carry[:4] + (carry[4] + seen,)

    x, (k_full, v_full, wk, wv, counts) = M.run_layers(
        params, M.embed_tokens(params, tokens, cfg),
        (cache["k"], cache["v"], cache["wk"], cache["wv"], cache["counts"]), cfg,
        {SLIDING: window_mixer, FULL: full_mixer}, experts)
    logits = M.logits_of(params, x, cfg)
    nxt, new_pos, remaining, rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "wk": wk, "wv": wv, "counts": counts,
             "pos": new_pos, "remaining": remaining, "rng": rng}
    return logits, nxt, cache


def macro_step_slots_paged(params, cache, feed, *plan, chunk: int, cfg: AfmoeConfig,
                           sampled: bool = True):
    """models/paged.py's macro-step skeleton with this model's two halves, under
    the skeleton's name (a device trace finds the program by it), and one
    return more: DEVICE_COUNTERS of this dispatch alone, (3,) int32, for the
    engine to fetch beside the tokens."""
    cache = {**cache, "counts": jnp.zeros_like(cache["counts"])}
    toks, firsts, feed, cache = paged.macro_step_slots_paged(
        params, cache, feed, *plan, chunk=chunk, cfg=cfg, sampled=sampled,
        admit=admit_slots_paged, decode_step=decode_step_slots_paged)
    return toks, firsts, feed, cache, cache["counts"] + 0


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: AfmoeConfig, chunk: int, sampled: bool = True):
    return jax.jit(
        paged._bind(macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled),
        donate_argnums=(1,),
    )


# ------------------------------------------------------- static generation
def _generate(params, prompt, cfg: AfmoeConfig, n_new: int):
    return paged.generate_through_paged_cache(
        init_paged_cache, admit_slots_paged, decode_step_slots_paged, params, prompt, cfg, n_new)


@functools.lru_cache(maxsize=64)
def _jitted_generate(cfg: AfmoeConfig, n_new: int):
    return jax.jit(paged._bind(_generate, cfg=cfg, n_new=n_new))


def generate(params, prompt, cfg: AfmoeConfig, max_new_tokens: int):
    """Greedy static generation: prompt (R, T) int32 -> (R, max_new_tokens)
    int32, one device program."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.shape[1] == 0:
        raise ValueError("generate() requires a non-empty prompt")
    return np.asarray(_jitted_generate(cfg, max_new_tokens)(params, prompt))
