"""Phi-4-mini-flash on the paged serving path: a pool ONE layer deep with
eight readers, rings and recurrent rows beside it, and an admission that runs
two depths.

The macro-step is models/paged.macro_step_slots_paged, handed this module's
admission and decode step and this module's cache pytree:

  k, v      (1, n_blocks, bs, kv_row)  THE block pool: layer `half + 1`
            writes it; tables are host state as ever. A row is n_kv_heads / 2
            pairs of 2 x head_dim, read as such (models/phi4flash.py says why).
            A decode step has EIGHT readers of this one layer, the full layer
            behind its own write and the seven cross-decoder attentions, and
            each reads it where it lies (`attend_pool`): on a TPU the kernel
            of ops/paged_decode_attention.py, every lane for its own blocks
            through its table; elsewhere `paged.attend_decode_paged`, the
            definition
  wk, wv    (window layers, lanes, window, kv_row)  each lane's RING of the
            last `sliding_window` positions of every window layer, as
            models/afmoe_decode.py keeps them; a decode step's new row goes
            in through that module's `write_ring_tokens`: on a TPU the
            kernel of ops/ring_write.py, one call a window layer for K and V
            of all lanes, elsewhere its loop of in-place updates
  conv      (Mamba layers, taps - 1, lanes, d_inner)  each lane's conv tail
  ssm       (Mamba layers, lanes, N, d_inner) float32  each lane's SSM state
            (N on the second-minor axis: 16 as a minor one would be padded to
            128), stepped in place in the stack: on a TPU by the kernel of
            ops/s6_update.py
  counts    (2,) int32  DEVICE_COUNTERS, summed over the dispatch's admissions
  pos, remaining, rng   per-lane scalars

AN ADMISSION RUNS TWO DEPTHS. Nothing a cross-decoder layer computes at a
prompt position is read by anything but that position's own logits: it writes
no cache (its keys and values are layer `half + 1`'s, its memory layer
`half`'s). So the self-decoder runs over the (A, P) rows, writing rings, the
pool, conv tails and final states, and the cross-decoder over (A, 1): each
row's last real position, its memory, its queries against the row's own
just-projected keys and values of layer `half + 1`. That is exact: the logits
at every EMITTED position are those of the full forward.

A padded admission row writes nothing; a decode step updates the lanes that
are live and leaves the others' state bit for bit (their ring slot and null
block take garbage nothing reads). Nothing here can resume a sequence from
blocks alone (rings and state at a block boundary are not kept), so
`state_bytes_per_lane` is not 0 and serve/llm_engine.py refuses prefix reuse,
speculation and migration.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import afmoe_decode as rings
from ray_tpu.models import paged
from ray_tpu.models import phi4flash as M
from ray_tpu.models.granite_hybrid import live_rows
from ray_tpu.models.phi4flash import Phi4FlashConfig
from ray_tpu.ops.blockwise_attention import NEG_INF

F32 = jnp.float32
# what a dispatch counts on the device, in the order of cache["counts"]: token
# rows the self-decoder ran in the dispatch's admissions (rows x P of every
# admission body run) and token rows the cross-decoder ran (one a row)
DEVICE_COUNTERS = ("self_rows", "cross_rows")


def init_paged_cache(cfg: Phi4FlashConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    pool = (1, n_blocks, block_size, cfg.kv_row)
    ring = (cfg.n_window_layers, n_slots, cfg.sliding_window, cfg.kv_row)
    return {
        "k": jnp.zeros(pool, cfg.dtype),
        "v": jnp.zeros(pool, cfg.dtype),
        "wk": jnp.zeros(ring, cfg.dtype),
        "wv": jnp.zeros(ring, cfg.dtype),
        "conv": jnp.zeros((cfg.n_mamba_layers, cfg.mamba_d_conv - 1, n_slots, cfg.d_inner),
                          cfg.dtype),
        "ssm": jnp.zeros((cfg.n_mamba_layers, n_slots, cfg.mamba_d_state, cfg.d_inner), F32),
        "counts": jnp.zeros((len(DEVICE_COUNTERS),), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: Phi4FlashConfig) -> int:
    """Bytes a lane holds beside its K/V blocks: the K and V rings of every
    window layer, the conv tail and the float32 SSM state of every Mamba layer."""
    item = jnp.dtype(cfg.dtype).itemsize
    ring = cfg.n_window_layers * 2 * cfg.sliding_window * cfg.kv_row * item
    conv = (cfg.mamba_d_conv - 1) * cfg.d_inner * item
    return ring + cfg.n_mamba_layers * (conv + cfg.mamba_d_state * cfg.d_inner * 4)


def attend_last(q, k, v, lengths, cfg: Phi4FlashConfig):
    """`diff_attention`'s `attend` for ONE query a row, the row's last real
    position, over the row's own keys and values (A, P, kv_row) of positions
    under `lengths`: q (A, h, 2 hd) -> (A, h, 2 hd) float32."""
    A, P, _ = k.shape
    g, w = cfg.n_kv_heads // 2, 2 * cfg.head_dim
    qg = q.reshape(A, g, cfg.n_heads // g, w)
    s = jnp.einsum("akgd,askd->akgs", qg, k.reshape(A, P, g, w),
                   preferred_element_type=F32) * cfg.head_dim ** -0.5
    real = (jnp.arange(P)[None, :] < lengths[:, None])[:, None, None, :]
    p = jax.nn.softmax(jnp.where(real, s, NEG_INF), axis=-1)
    o = jnp.einsum("akgs,askd->akgd", p.astype(v.dtype), v.reshape(A, P, g, w),
                   preferred_element_type=F32)
    return o.reshape(A, cfg.n_heads, w)


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: Phi4FlashConfig, sampled: bool = True):
    """Fused paged admission of A right-padded prompts (A, P), with
    llama_decode.admit_slots_paged's arguments and returns. `starts` is all
    zeros here: without rings and state at a block boundary no prefix is
    reused. The self-decoder over (A, P), the cross-decoder over (A, 1)."""
    A, P = prompts.shape
    adm_tables = tables[slots]
    valid = lengths > 0
    window = cfg.sliding_window

    def mamba(layer, mi, li, a, carry):
        k_full, v_full, wk, wv, conv, ssm = carry
        out, y, tail, h = M.mamba_sequence(layer, a, lengths, cfg)
        conv = paged.write_lane_rows(conv, mi, tail, slots, valid, lane_axis=2)
        ssm = paged.write_lane_rows(ssm, mi, h, slots, valid)
        return out, y, (k_full, v_full, wk, wv, conv, ssm)

    def window_mixer(layer, wi, li, a, carry):
        k_full, v_full, wk, wv, conv, ssm = carry
        with jax.named_scope(M.SCOPE_WINDOW):
            q, k, v = M.qkv(layer, a, cfg)
            wk = paged.write_lane_rows(wk, wi, rings.ring_rows(k, lengths, window), slots, valid)
            wv = paged.write_lane_rows(wv, wi, rings.ring_rows(v, lengths, window), slots, valid)
            o = M.diff_attention(layer, q, M.sequence_attend(k, v, cfg, window), li, cfg)
            return o @ layer["wo"] + layer["bo"], None, (k_full, v_full, wk, wv, conv, ssm)

    def full_mixer(layer, ai, li, a, carry):
        k_full, v_full, wk, wv, conv, ssm = carry
        with jax.named_scope(M.SCOPE_FULL):
            q, k, v = M.qkv(layer, a, cfg)
            k_full, v_full = paged.write_admission_kv(k_full, v_full, 0, k, v, adm_tables,
                                                      starts, valid)
            o = M.diff_attention(layer, q, M.sequence_attend(k, v, cfg, None), li, cfg)
            # the row's keys and values go on to the cross-decoder as they are
            return o @ layer["wo"] + layer["bo"], (k, v), (k_full, v_full, wk, wv, conv, ssm)

    x, m, (k, v), (k_full, v_full, wk, wv, conv, ssm) = M.self_decoder(
        params, M.embed_tokens(params, prompts, cfg),
        (cache["k"], cache["v"], cache["wk"], cache["wv"], cache["conv"], cache["ssm"]), cfg,
        mamba, window_mixer, full_mixer)
    # the cross-decoder, the final norm and the head at each row's last real
    # position only: (A, .) and never (A x P, .)
    last = (jnp.maximum(lengths, 1) - 1)[:, None, None]
    x_last = jnp.take_along_axis(x, last, axis=1)[:, 0, :]
    m_last = jnp.take_along_axis(m, last, axis=1)[:, 0, :]
    x_last = M.cross_decoder(params, x_last, m_last, cfg,
                             lambda q: attend_last(q, k, v, lengths, cfg))
    first, pos, rem, feed, rng = paged.finish_admission(
        M.logits_of(params, x_last, cfg), cache, feed, valid, lengths, starts,
        slots, rems, seeds, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "wk": wk, "wv": wv, "conv": conv, "ssm": ssm,
             "counts": cache["counts"] + jnp.asarray([A * P, A], jnp.int32),
             "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


def attend_pool(k_full, v_full, tables, pos, active, scale):
    """`diff_attention`'s `attend` over the ONE pool layer, for each of a
    decode step's eight readers: q (B, h, 2 hd) -> (B, h, 2 hd),
    `paged.attend_decode_paged`'s attention. On a TPU, for pools its tiles
    take, the kernel of ops/paged_decode_attention.py, which reads the pool in
    place, each lane for its own blocks; elsewhere the definition, which
    gathers every lane's chunks up to the longest live lane's."""
    from ray_tpu.ops import paged_decode_attention as kernel  # Pallas: imported where it is traced

    def attend(q):
        read = kernel.attend if kernel.engages(q, k_full, v_full) else paged.attend_decode_paged
        return read(q, k_full, v_full, 0, tables, pos, active, scale).reshape(q.shape)

    return attend


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: Phi4FlashConfig,
                            sampled: bool = True):
    """One token on every lane, with llama_decode.decode_step_slots_paged's
    arguments and returns: all layers on (lanes, 1). A lane that is not live
    (remaining == 0) keeps its conv tail and state as they are and aims its
    K/V write at the null block; its logits mean nothing. The full layer,
    behind its own write, and the cross-decoder's seven read the pool where
    it lies (`attend_pool`)."""
    pos = cache["pos"]
    active = cache["remaining"] > 0
    live = live_rows(active)  # one list for the step's every layer
    scale = cfg.head_dim ** -0.5

    def mamba(layer, mi, li, a, carry):
        k_full, v_full, wk, wv, conv, ssm = carry
        tail = jax.lax.dynamic_index_in_dim(conv, mi, 0, keepdims=False)
        out, y, new_tail, ssm = M.mamba_token(layer, mi, a, tail, ssm, live, cfg)
        with jax.named_scope(M.SCOPE_UPDATE):
            new_tail = jnp.where(active[None, :, None], new_tail, tail)
            conv = jax.lax.dynamic_update_index_in_dim(conv, new_tail, mi, 0)
        return out, y, (k_full, v_full, wk, wv, conv, ssm)

    def window_mixer(layer, wi, li, a, carry):
        k_full, v_full, wk, wv, conv, ssm = carry
        with jax.named_scope(M.SCOPE_WINDOW):
            q, k, v = M.qkv(layer, a, cfg)
            wk, wv = rings.write_ring_tokens(wk, wv, wi, k, v, pos)
            o = M.diff_attention(
                layer, q, lambda q: rings.attend_decode_ring(q, wk, wv, wi, pos, scale).reshape(q.shape),
                li, cfg)
            return o @ layer["wo"] + layer["bo"], None, (k_full, v_full, wk, wv, conv, ssm)

    def full_mixer(layer, ai, li, a, carry):
        k_full, v_full, wk, wv, conv, ssm = carry
        with jax.named_scope(M.SCOPE_FULL):
            q, k, v = M.qkv(layer, a, cfg)
            k_full, v_full = paged.write_decode_kv(
                k_full, v_full, 0, k[:, None, :], v[:, None, :], tables, pos, active)
            o = M.diff_attention(layer, q, attend_pool(k_full, v_full, tables, pos, active, scale), li, cfg)
            return o @ layer["wo"] + layer["bo"], None, (k_full, v_full, wk, wv, conv, ssm)

    x, m, _, (k_full, v_full, wk, wv, conv, ssm) = M.self_decoder(
        params, M.embed_tokens(params, tokens, cfg),
        (cache["k"], cache["v"], cache["wk"], cache["wv"], cache["conv"], cache["ssm"]), cfg,
        mamba, window_mixer, full_mixer)
    x = M.cross_decoder(params, x, m, cfg, attend_pool(k_full, v_full, tables, pos, active, scale))
    logits = M.logits_of(params, x, cfg)
    nxt, new_pos, remaining, rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "wk": wk, "wv": wv, "conv": conv, "ssm": ssm,
             "counts": cache["counts"], "pos": new_pos, "remaining": remaining, "rng": rng}
    return logits, nxt, cache


def macro_step_slots_paged(params, cache, feed, *plan, chunk: int, cfg: Phi4FlashConfig,
                           sampled: bool = True):
    """models/paged.py's macro-step skeleton with this model's two halves,
    under the skeleton's name (a device trace finds the program by it), and
    one return more: DEVICE_COUNTERS of this dispatch alone, (2,) int32, for
    the engine to fetch beside the tokens."""
    cache = {**cache, "counts": jnp.zeros_like(cache["counts"])}
    toks, firsts, feed, cache = paged.macro_step_slots_paged(
        params, cache, feed, *plan, chunk=chunk, cfg=cfg, sampled=sampled,
        admit=admit_slots_paged, decode_step=decode_step_slots_paged)
    return toks, firsts, feed, cache, cache["counts"] + 0


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: Phi4FlashConfig, chunk: int, sampled: bool = True):
    return jax.jit(
        paged._bind(macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled),
        donate_argnums=(1,),
    )


# ------------------------------------------------------- static generation
def _generate(params, prompt, cfg: Phi4FlashConfig, n_new: int):
    return paged.generate_through_paged_cache(
        init_paged_cache, admit_slots_paged, decode_step_slots_paged, params, prompt, cfg, n_new)


@functools.lru_cache(maxsize=64)
def _jitted_generate(cfg: Phi4FlashConfig, n_new: int):
    return jax.jit(paged._bind(_generate, cfg=cfg, n_new=n_new))


def generate(params, prompt, cfg: Phi4FlashConfig, max_new_tokens: int):
    """Greedy static generation: prompt (R, T) int32 -> (R, max_new_tokens)
    int32, one device program."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.shape[1] == 0:
        raise ValueError("generate() requires a non-empty prompt")
    return np.asarray(_jitted_generate(cfg, max_new_tokens)(params, prompt))
