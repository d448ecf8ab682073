"""The LongCat-Flash decoder on the paged serving path: a latent pool of TWO
planes a layer, an expanded admission and an absorbed decode step over each
sublayer's own plane, and the shortcut branch's output carried across the
second half of every layer.

The macro-step is models/paged.macro_step_slots_paged, handed this
module's admission and decode step and this module's cache pytree:

  latent    (2 x layers, n_blocks, bs, ROW)  models/sarvam_mla_decode.py's
            pool (one row [c | RoPE(k_r) | 0s] a position, padded to whole
            128-column tiles) with a plane a SUBLAYER: attention s of layer
            i writes and reads plane 2 i + s. Tables, allocator and planner
            are every model's
  counts    (5,) int32  DEVICE_COUNTERS, summed over the dispatch's decode
            steps and expert layers: afmoe_decode's three, of HELD experts,
            then the live rows' chosen indices under and past the real
            experts (`real_choices`, `zero_choices`: real experts a token
            is their ratio times top_k)
  pos, remaining, rng   per-lane scalars

The attention halves are sarvam_mla_decode's `admit_mixer` and `decode_mixer`
(this model's `project` cases come with its layers and config), the block
around them models/longcat_flash.run_layers. LATENT_POOL makes the engine
refuse what copies, ships or re-reads K and V blocks, as for that model.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import afmoe, afmoe_decode
from ray_tpu.models import longcat_flash as M
from ray_tpu.models import paged
from ray_tpu.models import sarvam_mla_decode as S
from ray_tpu.models.longcat_flash import LongcatFlashConfig

LATENT_POOL = True
DEVICE_COUNTERS = afmoe_decode.DEVICE_COUNTERS + ("real_choices", "zero_choices")


def init_paged_cache(cfg: LongcatFlashConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    return {
        "latent": jnp.zeros((cfg.n_sublayers, n_blocks, block_size, S.pool_row(cfg)), cfg.dtype),
        "counts": jnp.zeros((len(DEVICE_COUNTERS),), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: LongcatFlashConfig) -> int:
    """A lane's whole state is its block table."""
    return 0


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: LongcatFlashConfig, sampled: bool = True):
    """Fused paged admission of A right-padded prompts (A, P), with
    llama_decode.admit_slots_paged's arguments and returns. `starts` is all
    zeros here: no prefix is reused over latent blocks."""
    P = prompts.shape[1]
    adm_tables = tables[slots]
    valid = lengths > 0
    cos, sin = M.rope_tables(cfg, P)
    # a padded row chooses no real expert (sarvam_mla_decode says why it may)
    real = (jnp.arange(P)[None, :] < lengths[:, None]).reshape(-1)

    def mixer(layer, plane, a, pool):
        return S.admit_mixer(layer, plane, a, pool, cos, sin, adm_tables, starts, valid, cfg)

    x, pool = M.run_layers(
        params, M.embed_tokens(params, prompts, cfg), cache["latent"], cfg, mixer,
        lambda p, m, carry: (M.moe_ffn(m, p, cfg, live=real)[0], carry))
    # the head at each row's last real position only
    x_last = jnp.take_along_axis(
        x, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1)[:, 0, :]
    first, pos, rem, feed, rng = paged.finish_admission(
        afmoe.logits_of(params, x_last, cfg), cache, feed, valid, lengths, starts,
        slots, rems, seeds, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"latent": pool, "counts": cache["counts"], "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: LongcatFlashConfig,
                            sampled: bool = True):
    """One token on every lane, with llama_decode.decode_step_slots_paged's
    arguments and returns; both attentions of a layer the absorbed way, the
    real experts over the live lanes' rows only."""
    pos = cache["pos"]
    active = cache["remaining"] > 0
    cos, sin = M.rope_tables(cfg, tables.shape[1] * cache["latent"].shape[2])

    def mixer(layer, plane, a, carry):
        out, pool = S.decode_mixer(layer, plane, a, carry[0], cos, sin, tables, pos, active, cfg)
        return out, (pool, carry[1])

    def experts(p, m, carry):
        out, sizes, choices = M.moe_ffn(m, p, cfg, live=active)
        seen = jnp.concatenate([
            jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()]).astype(jnp.int32), choices])
        return out, (carry[0], carry[1] + seen)

    x, (pool, counts) = M.run_layers(
        params, M.embed_tokens(params, tokens, cfg), (cache["latent"], cache["counts"]), cfg,
        mixer, experts)
    logits = afmoe.logits_of(params, x, cfg)
    nxt, new_pos, remaining, rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"latent": pool, "counts": counts, "pos": new_pos, "remaining": remaining, "rng": rng}
    return logits, nxt, cache


def macro_step_slots_paged(params, cache, feed, *plan, chunk: int, cfg: LongcatFlashConfig,
                           sampled: bool = True):
    """models/paged.py's macro-step skeleton with this model's two halves, under
    the skeleton's name (a device trace finds the program by it), and
    DEVICE_COUNTERS of this dispatch alone as a fifth return."""
    cache = {**cache, "counts": jnp.zeros_like(cache["counts"])}
    toks, firsts, feed, cache = paged.macro_step_slots_paged(
        params, cache, feed, *plan, chunk=chunk, cfg=cfg, sampled=sampled,
        admit=admit_slots_paged, decode_step=decode_step_slots_paged)
    return toks, firsts, feed, cache, cache["counts"] + 0


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: LongcatFlashConfig, chunk: int, sampled: bool = True):
    return jax.jit(
        paged._bind(macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled),
        donate_argnums=(1,),
    )


# ------------------------------------------------------- static generation
def _generate(params, prompt, cfg: LongcatFlashConfig, n_new: int):
    return paged.generate_through_paged_cache(
        init_paged_cache, admit_slots_paged, decode_step_slots_paged, params, prompt, cfg, n_new)


@functools.lru_cache(maxsize=64)
def _jitted_generate(cfg: LongcatFlashConfig, n_new: int):
    return jax.jit(paged._bind(_generate, cfg=cfg, n_new=n_new))


def generate(params, prompt, cfg: LongcatFlashConfig, max_new_tokens: int):
    """Greedy static generation: prompt (R, T) int32 -> (R, max_new_tokens)
    int32, one device program."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.shape[1] == 0:
        raise ValueError("generate() requires a non-empty prompt")
    return np.asarray(_jitted_generate(cfg, max_new_tokens)(params, prompt))
