"""The Brumby decoder on the paged serving path: a cache with NO pool. Every
layer is power retention, so a lane's whole context is its float32 state and
the blocks its table names back nothing.

The macro-step is models/paged.macro_step_slots_paged, handed this module's
admission and decode step and this module's cache pytree:

  state     (layers, lanes, KV, d + pad, phi's width) float32  each lane's
            retention state, a KV head's matrix the transpose of S with the
            normaliser z as row d (models/brumby.py says how it lies),
            stepped in place in the stack: on a TPU by ops/retention_update.py,
            which is handed the whole stack, the layer's index and the live
            lanes; no layer is sliced out or written back
  pos, remaining, rng                                          per-lane scalars

and no `k`, no `v`: the tables ride every dispatch as the skeleton's plan
arguments and are read by nothing (the engine's allocator still counts a
lane's blocks against `max_seq_len`; what memory holds is lanes, 36 MB a
layer each at the published sizes, whatever their contexts' lengths).

Admission computes a row's final state from zero (the chunked form,
`brumby.retention_chunked`), rows a piece at a time, and writes it to the
row's lane (a padded admission row writes nothing); the decode step updates
the lanes that are active (the one-position form) and leaves the others bit
for bit alone; release needs no device work, the next admission overwrites
the state. Past a row's length log g = 0 and the position adds nothing (the
state stands), and the head is applied at the last real position only.

A lane's state at a block boundary is not kept, so nothing here can resume a
sequence from blocks (there are none to resume from): serve/llm_engine.py
refuses what needs that when `state_bytes_per_lane` is not 0 (prefix reuse,
speculation, migration).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import brumby as M
from ray_tpu.models import paged
from ray_tpu.models.brumby import BrumbyConfig
from ray_tpu.models.granite_hybrid import live_rows
from ray_tpu.ops.normalization import rms_norm


def init_paged_cache(cfg: BrumbyConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    """`n_blocks` and `block_size` size nothing: there is no pool."""
    return {
        "state": jnp.zeros((cfg.n_layers, n_slots, cfg.n_kv_heads, cfg.state_rows, cfg.phi_dim),
                           jnp.float32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: BrumbyConfig) -> int:
    """Bytes a lane holds: the float32 retention state of every layer (its
    whole context; it holds no blocks)."""
    return cfg.n_layers * cfg.n_kv_heads * cfg.state_rows * cfg.phi_dim * 4


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: BrumbyConfig, sampled: bool = True):
    """Fused paged admission of A right-padded prompts (A, P), with
    llama_decode.admit_slots_paged's arguments and returns. `starts` is all
    zeros (no prefix is reused) and `tables` is read by nothing."""
    A, P = prompts.shape
    valid = lengths > 0
    cos, sin = M.rope_tables(cfg, P)

    def mixer(layer, li, x, ffn, state):
        def piece(state, x, lengths, slots, valid):
            y, S = M.sequence_block(layer, x, lengths, cos, sin, ffn, cfg)
            return paged.write_lane_rows(state, li, S, slots, valid), y

        return M.over_row_pieces(piece, state, M.rows_of_a_step(A, P, cfg), x, lengths, slots,
                                 valid)[::-1]

    x, state = M.run_layers(params, M.embed_tokens(params, prompts, cfg), cache["state"], cfg,
                            mixer)
    # the head at each row's last real position only
    x_last = jnp.take_along_axis(
        x, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1)[:, 0, :]
    first, pos, rem, feed, rng = paged.finish_admission(
        M.logits_of(params, x_last, cfg), cache, feed, valid, lengths, starts,
        slots, rems, seeds, temps, top_ks, top_ps, stop_ids, sampled)
    return first, {"state": state, "pos": pos, "remaining": rem, "rng": rng}, feed


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: BrumbyConfig,
                            sampled: bool = True):
    """One token on every lane, with llama_decode.decode_step_slots_paged's
    arguments and returns. An inactive lane (remaining == 0) keeps its state
    as it is, and its logits mean nothing."""
    pos = cache["pos"]
    active = cache["remaining"] > 0
    live = live_rows(active)  # one list for the step's every layer
    cos, sin = M.rope_tables(cfg, cfg.max_seq_len)

    def mixer(layer, li, x, ffn, state):
        o, state = M.retention_token(
            layer, li, rms_norm(x, layer["attn_norm"], cfg.rms_eps), pos, state, live, cos, sin,
            cfg)
        return ffn(x + o), state

    x, state = M.run_layers(params, M.embed_tokens(params, tokens, cfg), cache["state"], cfg,
                            mixer)
    logits = M.logits_of(params, x, cfg)
    nxt, new_pos, remaining, rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    return logits, nxt, {"state": state, "pos": new_pos, "remaining": remaining, "rng": rng}


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: BrumbyConfig, chunk: int, sampled: bool = True):
    """models/paged.py's macro-step skeleton with this model's two halves;
    the program keeps the skeleton's name."""
    return jax.jit(
        paged._bind(paged.macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled,
                    admit=admit_slots_paged, decode_step=decode_step_slots_paged),
        donate_argnums=(1,),
    )


# ------------------------------------------------------- static generation
def _generate(params, prompt, cfg: BrumbyConfig, n_new: int):
    return paged.generate_through_paged_cache(
        init_paged_cache, admit_slots_paged, decode_step_slots_paged, params, prompt, cfg, n_new)


@functools.lru_cache(maxsize=64)
def _jitted_generate(cfg: BrumbyConfig, n_new: int):
    return jax.jit(paged._bind(_generate, cfg=cfg, n_new=n_new))


def generate(params, prompt, cfg: BrumbyConfig, max_new_tokens: int):
    """Greedy static generation: prompt (R, T) int32 -> (R, max_new_tokens)
    int32, one device program."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.shape[1] == 0:
        raise ValueError("generate() requires a non-empty prompt")
    return np.asarray(_jitted_generate(cfg, max_new_tokens)(params, prompt))
