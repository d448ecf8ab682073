"""What a decode module offers the engine (models/paged.py's docstring), held
against the eight `cfg.decode_module`s.

Nothing is compiled: shapes come from `jax.eval_shape`, which traces the
model's macro-step at its tiny config (one phase, one admission row of one
block) and allocates nothing.
"""
import inspect

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import (afmoe, brumby, granite_hybrid, llama, longcat_flash, paged, phi4flash,
                            qwen3_next, sarvam_mla)
from ray_tpu.serve._internal.sampling import MAX_STOP_TOKENS

CONFIGS = {
    "llama": lambda: llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False),
    "granite_hybrid": lambda: granite_hybrid.GraniteHybridConfig.tiny(dtype=jnp.float32),
    "afmoe": lambda: afmoe.AfmoeConfig.tiny(dtype=jnp.float32),
    "sarvam_mla": lambda: sarvam_mla.SarvamMlaConfig.tiny(dtype=jnp.float32),
    "qwen3_next": lambda: qwen3_next.Qwen3NextConfig.tiny(dtype=jnp.float32),
    "longcat_flash": lambda: longcat_flash.LongcatFlashConfig.tiny(dtype=jnp.float32),
    "phi4flash": lambda: phi4flash.Phi4FlashConfig.tiny(dtype=jnp.float32),
    "brumby": lambda: brumby.BrumbyConfig.tiny(dtype=jnp.float32),
}
LANES, BLOCKS, BLOCK, K, A, CHUNK = 2, 5, 8, 1, 1, 2


def _parameters(f):
    return list(inspect.signature(f).parameters)


def _plan_shapes(MB):
    """The macro-step's plan arguments behind `feed`, as shapes."""
    s = jax.ShapeDtypeStruct
    i32 = lambda *shape: s(shape, jnp.int32)  # noqa: E731
    return (i32(K), s((K,), jnp.bool_), i32(K, A, BLOCK), i32(K, A), i32(K, A), i32(K, A), i32(K, A),
            s((K, A), jnp.uint32), i32(K, LANES, MB), s((K, LANES), jnp.float32), i32(K, LANES),
            s((K, LANES), jnp.float32), i32(K, LANES, MAX_STOP_TOKENS))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_decode_module_offers_what_the_engine_takes(name):
    cfg = CONFIGS[name]()
    D = cfg.decode_module
    assert D.__name__ == f"ray_tpu.models.{name}_decode"
    assert hash(cfg) == hash(CONFIGS[name]())  # the factories memoise on it

    # the four required names, by their signatures
    assert _parameters(D.init_paged_cache) == ["cfg", "n_slots", "n_blocks", "block_size"]
    assert _parameters(D.state_bytes_per_lane) == ["cfg"]
    assert _parameters(D.generate)[:4] == ["params", "prompt", "cfg", "max_new_tokens"]
    factory = D.jitted_macro_step_slots_paged
    assert _parameters(factory) == ["cfg", "chunk", "sampled"]
    assert inspect.signature(factory).parameters["sampled"].default is True
    assert hasattr(factory, "cache_clear"), "the factory is not memoised"
    assert factory(cfg, CHUNK, sampled=False) is factory(cfg, CHUNK, sampled=False)
    assert isinstance(D.state_bytes_per_lane(cfg), int) and D.state_bytes_per_lane(cfg) >= 0

    # the cache: a dict of arrays with the per-lane scalars the skeleton arms
    cache = jax.eval_shape(lambda: D.init_paged_cache(cfg, LANES, BLOCKS, BLOCK))
    assert isinstance(cache, dict)
    assert (cache["pos"].shape, cache["pos"].dtype) == ((LANES,), jnp.int32)
    assert (cache["remaining"].shape, cache["remaining"].dtype) == ((LANES,), jnp.int32)
    assert (cache["rng"].shape, cache["rng"].dtype) == ((LANES, 2), jnp.uint32)

    # the program: (params, cache, feed, *plan) -> (toks, firsts, feed, cache[, counts])
    counters = getattr(D, "DEVICE_COUNTERS", ())
    assert isinstance(counters, tuple) and all(isinstance(c, str) for c in counters)
    params = jax.eval_shape(lambda: cfg.model_module.init_params(jax.random.PRNGKey(0), cfg))
    feed = jax.ShapeDtypeStruct((LANES,), jnp.int32)
    MB = BLOCKS - 1
    toks, firsts, feed_out, cache_out, *counted = jax.eval_shape(
        factory(cfg, CHUNK, sampled=False), params, cache, feed, *_plan_shapes(MB))
    assert (toks.shape, firsts.shape, feed_out.shape) == ((K, CHUNK, LANES), (K, A), (LANES,))
    assert jax.tree.structure(cache_out) == jax.tree.structure(cache)
    assert jax.tree.leaves(cache_out) == jax.tree.leaves(cache)  # donated: the same shapes back
    if counters:
        assert [(c.shape, c.dtype) for c in counted] == [((len(counters),), jnp.int32)]
    else:
        assert counted == []

    # the optional names and what they stand for
    if hasattr(D, "LATENT_POOL"):
        assert D.LATENT_POOL is True and not {"k", "v"} & set(cache)
    elif {"k", "v"} & set(cache):
        assert {"k", "v"} <= set(cache)  # what the block movers of models/paged.py move
    else:  # no pool at all: a lane's context is its state, and the engine refuses the block movers
        assert D.state_bytes_per_lane(cfg) > 0
    speculation = {"init_spec_cache", "jitted_macro_step_slots_spec"} & set(vars(D))
    if D.state_bytes_per_lane(cfg) or hasattr(D, "LATENT_POOL"):
        assert not speculation  # the engine refuses a draft model for it
    else:
        assert len(speculation) in (0, 2)

    # the skeleton is imported, never re-exported: a name of models/paged.py
    # is found there and nowhere else
    theirs = [n for n, v in vars(D).items() if getattr(v, "__module__", None) == paged.__name__]
    assert theirs == []
