"""The engine's loop runs the cyclic collector at its first idle moment after
a dispatch that traced and compiled a program (PR 29).

Left to its own counters, CPython's full pass (50-70 ms with every thread
stopped in a serving replica) came 1.5 s into `docqa-saturate`'s window in
every run that compiled, inside a plan, and the closed loop settled into
another cycle (PERF.md section 6).
"""
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.serve import llm_engine as E


def _engine(model: str):
    if model == "hybrid":
        from ray_tpu.models import granite_hybrid as M

        cfg = M.GraniteHybridConfig.tiny(dtype=jnp.float32)
        kw = dict(block_size=16, prefix_cache=False, max_len=128)
    else:
        from ray_tpu.models import llama as M

        cfg = M.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
        kw = dict(block_size=8, max_len=64)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return E.ContinuousBatchingEngine(params, cfg, n_slots=2, chunk=4,
                                      macro_phases=4, **kw)


@pytest.mark.parametrize("model", ["hybrid", "llama"], ids=["hybrid-macro", "llama-macro"])
def test_loop_collects_once_it_idles_after_a_compile(monkeypatch, model):
    """One explicit pass after a dispatch that compiled, none after a dispatch
    of a program that is warm."""
    passes = []
    monkeypatch.setattr(E.gc, "collect", lambda *a: passes.append(time.perf_counter()))
    eng = _engine(model)

    def serve_then_idle():
        eng.generate([5, 6, 7], 3)
        deadline = time.perf_counter() + 30.0
        while eng._collect_when_idle and time.perf_counter() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)  # a few more idle iterations

    try:
        serve_then_idle()
        assert eng._jit_cache_sizes and not eng._collect_when_idle
        first = len(passes)
        assert first >= 1
        serve_then_idle()  # the same programs, warm
        assert len(passes) == first
    finally:
        eng.shutdown()
