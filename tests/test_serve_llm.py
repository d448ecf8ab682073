"""Batched LLM serving deployment (serve/llm.py)."""
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


def test_llm_deployment_batched_generation(ray_start_regular):
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import llm_deployment

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
    app = llm_deployment(num_replicas=1, max_new_tokens=6, cfg=cfg)
    handle = serve.run(app, name="llm_app")
    try:
        # mixed prompt lengths in flight at once: the batcher groups by
        # length and still answers every request correctly
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10]]
        responses = [handle.remote(p) for p in prompts]
        outs = [r.result(timeout=120) for r in responses]
        assert all(len(o) == 6 for o in outs)
        assert all(all(0 <= t < cfg.vocab_size for t in o) for o in outs)

        # determinism: same prompt, same greedy output, batched or not
        again = handle.remote([1, 2, 3]).result(timeout=60)
        assert again == outs[0]
    finally:
        serve.delete("llm_app")


_SIX = ([[1, 2, 3], [4, 5], [6, 7, 8, 9], [10], [11, 12], [13, 14, 15]],
        [7, 2, 11, 1, 5, 4])
# name -> (engine options, prompts, generation lengths): 2 slots under 5 or
# 6 requests of mixed prompt and generation lengths force queueing,
# mid-chunk finishes, eviction and slot reuse
EVICTION_CASES = {
    "defaults": (dict(n_slots=2, chunk=4),
                 [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10], [11, 12]], [6, 3, 9, 1, 5]),
    "plans-of-4-phases": (dict(n_slots=2, chunk=4, macro_phases=4), *_SIX),
    "blocks-of-8": (dict(n_slots=2, chunk=4, macro_phases=4, max_len=64,
                         block_size=8), *_SIX),
}


@pytest.mark.parametrize("case", EVICTION_CASES)
def test_continuous_engine_eviction_correctness(case):
    """Mixed-length sequences decoded concurrently through the
    continuous-batching engine must produce EXACTLY the tokens the
    static path produces for each prompt alone — admission, chunked
    decode, mid-chunk freezing, eviction and slot reuse change nothing,
    whatever the plan's length and the pool's block size (reference:
    vLLM-style iteration-level scheduling; here the TPU-native engine
    in serve/llm_engine.py)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode

    options, prompts, lens = EVICTION_CASES[case]
    engine, params, cfg = _tiny_engine(**options)
    try:
        reqs = [engine.submit(p, n) for p, n in zip(prompts, lens)]
        outs = []
        for r in reqs:
            assert r.done.wait(180), "engine request timed out"
            assert r.error is None, r.error
            outs.append(r.tokens)
        for p, n, got in zip(prompts, lens, outs):
            want = llama_decode.generate(
                params, jnp.asarray([p], jnp.int32), cfg, max_new_tokens=n
            )[0].tolist()
            assert got == want, (p, n, got, want)
    finally:
        engine.shutdown()


def _tiny_engine(**kw):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return ContinuousBatchingEngine(params, cfg, **kw), params, cfg


@pytest.mark.parametrize("block_size", [8, 16])
def test_engine_non_power_of_two_max_len(block_size):
    """A prompt whose power-of-two bucket exceeds a table span that is no
    power of two (6 blocks of 8, 3 of 16) must decode correctly instead
    of crashing the engine thread at prefill trace time (bucket 64 >
    span 48: `_bucket_paged` clamps to the span)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode

    engine, params, cfg = _tiny_engine(n_slots=2, chunk=4, max_len=48,
                                       macro_phases=4, block_size=block_size)
    try:
        # empty prompts are rejected up front (length 0 is the macro
        # plan's padding sentinel)
        with pytest.raises(ValueError, match="non-empty"):
            engine.submit([], 4)
        prompt = list(range(1, 34))  # len 33: buckets to 64 without the clamp
        assert engine._bucket_paged(len(prompt)) == 48
        got = engine.generate(prompt, 6, timeout=120)
        want = llama_decode.generate(
            params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=6
        )[0].tolist()
        assert got == want
    finally:
        engine.shutdown()


def _poison_macro_step(monkeypatch, engine, wrap):
    """Put `wrap(real_program, sampled)` where the engine takes its program
    from: `_dispatch_macro` asks the decode module for the greedy or the
    sampled variant at every dispatch."""
    real = engine._D.jitted_macro_step_slots_paged
    monkeypatch.setattr(
        engine._D, "jitted_macro_step_slots_paged",
        lambda cfg, chunk, sampled: wrap(real(cfg, chunk, sampled=sampled), sampled))


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_engine_poisoned_dispatch_fails_fast(monkeypatch, temperature):
    """A poisoned device program (either variant the dispatch binds) must
    surface a diagnostic error on every in-flight request and kill the
    engine — not N generic 120s timeouts."""
    engine, _, _ = _tiny_engine(n_slots=2, chunk=4, macro_phases=4)
    try:
        bound = []

        def wrap(_real, sampled):
            def boom(*a, **k):
                bound.append(sampled)
                raise ValueError("poisoned device program")
            return boom

        _poison_macro_step(monkeypatch, engine, wrap)
        with pytest.raises(RuntimeError, match="poisoned device program"):
            engine.generate([1, 2, 3], 6, timeout=30,
                            sampling={"temperature": temperature, "seed": 3})
        assert bound == [temperature > 0]
        # engine is dead: submit refuses immediately with the diagnostic
        with pytest.raises(RuntimeError, match="engine is dead"):
            engine.submit([4, 5], 3)
    finally:
        engine.shutdown()


def test_engine_poisoned_fetch_fails_fast(monkeypatch):
    """Dispatch is async, so device faults usually surface at the
    blocking token FETCH, one macro-step behind — requests referenced
    only by the in-flight plan must still get the diagnostic."""
    class _Boom:
        def __array__(self, *a, **k):
            raise ValueError("poisoned device buffer")

    engine, _, _ = _tiny_engine(n_slots=2, chunk=4, macro_phases=4)
    try:
        def wrap(real_fn, _sampled):
            def corrupting(*a, **k):
                toks, firsts, feed, cache = real_fn(*a, **k)
                return _Boom(), firsts, feed, cache
            return corrupting

        _poison_macro_step(monkeypatch, engine, wrap)
        with pytest.raises(RuntimeError, match="poisoned device buffer"):
            engine.generate([1, 2, 3], 6, timeout=30)
        with pytest.raises(RuntimeError, match="engine is dead"):
            engine.submit([4, 5], 3)
    finally:
        engine.shutdown()


def test_removed_engine_modes_are_errors():
    """The dense slot mode and the per-chunk loop are gone, not defaulted
    away: `paged=` is an unknown argument on all three signatures, and
    a plan of no phases is refused by name."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import _LLMServer, llm_deployment
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    for paged in (False, True):
        with pytest.raises(TypeError, match="paged"):
            ContinuousBatchingEngine(params, cfg, paged=paged)
        with pytest.raises(TypeError, match="paged"):
            _LLMServer(cfg=cfg, params=params, continuous=True, paged=paged)
        with pytest.raises(TypeError, match="paged"):
            llm_deployment(cfg=cfg, continuous=True, paged=paged)
    with pytest.raises(ValueError, match="macro_phases"):
        ContinuousBatchingEngine(params, cfg, macro_phases=0)
    with pytest.raises(ValueError, match="macro_phases"):
        _LLMServer(cfg=cfg, params=params, continuous=True, macro_phases=0)


def test_adaptive_chunk_bookkeeping_skewed():
    """Skewed generation lengths: adaptive phases shrink to the next
    scheduling event, so freed lanes re-admit immediately — tokens stay
    exact and the occupancy/dispatch bookkeeping stays consistent."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode

    engine, params, cfg = _tiny_engine(n_slots=4, chunk=8, macro_phases=4)
    try:
        # 3 short generations per long one: constant admission churn
        prompts = [[i + 1, i + 2] for i in range(12)]
        lens = [3 if i % 4 else 20 for i in range(12)]
        reqs = [engine.submit(p, n) for p, n in zip(prompts, lens)]
        for r in reqs:
            assert r.done.wait(180), "engine request timed out"
        for p, n, r in zip(prompts, lens, reqs):
            want = llama_decode.generate(
                params, jnp.asarray([p], jnp.int32), cfg, max_new_tokens=n
            )[0].tolist()
            assert r.tokens == want, (p, n, r.tokens, want)
        m = engine.metrics()
        assert m["tokens_out"] == sum(lens)
        assert 0 < m["useful_slot_steps"] <= m["slot_steps"]
        assert 0 < m["lane_occupancy_pct"] <= 100.0
        # every request finished, so tokens delivered == tokens planned
        assert m["useful_slot_steps"] == sum(n - 1 for n in lens)
        assert m["dispatches_per_token"] < 1.0
    finally:
        engine.shutdown()


def test_macro_dispatch_amortization_smoke():
    """CI smoke invariant: the macro-step engine issues <= 1 dispatch per
    K chunks (driven synchronously so the count is deterministic)."""
    import math

    from ray_tpu.serve.llm_engine import _dispatch_counts

    engine, _, _ = _tiny_engine(n_slots=2, chunk=4, macro_phases=4)
    engine.shutdown()  # drive the scheduler synchronously below
    reqs = [engine.submit([1 + i, 2 + i, 3 + i], 8) for i in range(4)]
    engine._drain_queue()
    while engine._waiting or any(r is not None for r in engine._slots):
        phases = engine._plan()
        engine._dispatch_macro(phases, _dispatch_counts(phases))
    while engine._pending:
        engine._resolve(engine._pending.popleft())
    assert all(r.done.is_set() and len(r.tokens) == 8 for r in reqs)
    m = engine.metrics()
    steps_total = m["slot_steps"] // engine.n_slots
    chunks = math.ceil(steps_total / engine.chunk)
    assert m["dispatches"] <= max(1, math.ceil(chunks / engine.macro_phases)), m


def test_continuous_llm_deployment(ray_start_regular):
    """The serve deployment surface with continuous=True answers
    concurrent mixed-length requests correctly."""
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import llm_deployment

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
    app = llm_deployment(num_replicas=1, max_new_tokens=5, cfg=cfg, continuous=True)
    handle = serve.run(app, name="llm_cont")
    try:
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
        outs = [h.result(timeout=180) for h in [handle.remote(p) for p in prompts]]
        assert all(len(o) == 5 for o in outs)
        again = handle.remote([1, 2, 3]).result(timeout=120)
        assert again == outs[0]
    finally:
        serve.delete("llm_cont")


def test_continuous_llm_deployment_sampling_request_path(ray_start_regular):
    """Dict requests carry SamplingParams through the serve surface:
    greedy list requests behave as before, seeded sampled requests are
    reproducible, stop-token requests truncate — all on the paged
    engine (the continuous default)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import llm_deployment

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
    app = llm_deployment(num_replicas=1, max_new_tokens=6, cfg=cfg,
                         continuous=True, block_size=8)
    handle = serve.run(app, name="llm_sampled")
    try:
        greedy = handle.remote([1, 2, 3]).result(timeout=180)
        assert len(greedy) == 6
        s1 = handle.remote({"prompt": [1, 2, 3], "temperature": 0.9,
                            "seed": 11}).result(timeout=120)
        s2 = handle.remote({"prompt": [1, 2, 3], "temperature": 0.9,
                            "seed": 11}).result(timeout=120)
        s3 = handle.remote({"prompt": [1, 2, 3], "temperature": 0.9,
                            "seed": 12, "max_new_tokens": 4}).result(timeout=120)
        assert s1 == s2 and len(s1) == 6
        assert len(s3) == 4
        # stop on the greedy stream's 2nd token: truncation at its
        # FIRST occurrence in the stream
        stopped = handle.remote({"prompt": [1, 2, 3],
                                 "stop": [greedy[1]]}).result(timeout=120)
        assert stopped == greedy[: greedy.index(greedy[1])], (stopped, greedy)
    finally:
        serve.delete("llm_sampled")


def test_engine_latency_histograms_and_concurrent_metrics():
    """TTFT/TPOT percentiles come from the real latency histograms
    (p50/p95/p99 present, ordered, finite) and metrics() stays safe
    while the engine loop appends concurrently — the histogram lock
    replaces the PR 2 retry-the-deque-copy dance."""
    import threading

    engine, _, _ = _tiny_engine(n_slots=2, chunk=4, macro_phases=4)
    # telemetry objects are shared per engine NAME within a process —
    # zero the counters so earlier engines in this module don't bleed in
    engine.reset_metrics()
    try:
        errors = []

        def hammer():
            try:
                for _ in range(300):
                    m = engine.metrics()
                    for k in ("ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99",
                              "tpot_ms_p50", "tpot_ms_p95", "tpot_ms_p99"):
                        assert k in m
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        t = threading.Thread(target=hammer)
        t.start()
        reqs = [engine.submit([1 + i, 2 + i], 6) for i in range(8)]
        for r in reqs:
            assert r.done.wait(180), "engine request timed out"
        t.join(timeout=120)
        assert not errors, errors

        m = engine.metrics()
        assert m["ttft_ms_p50"] is not None and m["ttft_ms_p50"] > 0
        assert m["ttft_ms_p50"] <= m["ttft_ms_p95"] <= m["ttft_ms_p99"]
        assert m["tpot_ms_p50"] is not None and m["tpot_ms_p50"] > 0
        assert m["tpot_ms_p50"] <= m["tpot_ms_p95"] <= m["tpot_ms_p99"]
        # dispatch telemetry rode along: every dispatch left a device
        # step event for the unified trace
        assert engine._tel.steps + engine._tel.compiles >= 1
        assert engine._tel.steps == m["dispatches"]
        engine.reset_metrics()
        m2 = engine.metrics()
        assert m2["ttft_ms_p50"] is None and m2["tokens_out"] == 0
    finally:
        engine.shutdown()
