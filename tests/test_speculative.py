"""Draft-model speculative decoding: LOSSLESS acceptance end to end.

The contract under test: speculation changes THROUGHPUT, never
RESULTS. Greedy output must be bit-identical to non-speculative decode
(accept iff draft == target argmax, correction = the target argmax the
plain path would have emitted); seeded sampled output must be
deterministic regardless of co-scheduling (per-slot key chains, one
split per round); rejections must leave the paged pools clean (pos
rollback + write-before-gather makes rejected KV invisible, and the
allocator/radix audit must balance after rejection-heavy traffic).

Engines here share one tiny geometry so the jitted spec variants
compile once per module run (lru-cached by (cfg, draft_cfg, chunk,
n_spec, sampled))."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

N_SPEC = 2


def _tiny():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _engine(params, cfg, draft="self", **kw):
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    kw.setdefault("n_slots", 2)
    kw.setdefault("chunk", 2)
    kw.setdefault("macro_phases", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    if draft is not None:
        kw.setdefault("num_speculative_tokens", N_SPEC)
    return ContinuousBatchingEngine(params, cfg,
                                    draft_model=draft, **kw)


def _prompts(rng, cfg, sizes):
    return [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
            for n in sizes]


def test_greedy_self_draft_accepts_every_proposal():
    """Self-drafting greedy lanes: the draft argmax IS the target
    argmax, so every proposal is accepted — accepted-tokens/round hits
    the n_spec + 1 ceiling with zero rejections — and the emitted
    stream is bit-identical to target-only greedy decode."""
    from ray_tpu.models import llama_decode as D

    cfg, params = _tiny()
    eng = _engine(params, cfg)
    try:
        rng = np.random.default_rng(0)
        for p in _prompts(rng, cfg, (5, 9, 3)):
            ref = D.generate(params, jnp.asarray([p], jnp.int32), cfg,
                             max_new_tokens=10)[0].tolist()
            assert eng.generate(p, 10, timeout=300) == ref
        m = eng.metrics()
        assert m["draft_rejection_pct"] == 0.0, m
        assert m["accepted_tokens_per_dispatch"] == float(N_SPEC + 1), m
        assert m["draft_accepted_tokens"] == N_SPEC * m["spec_verify_rounds"]
    finally:
        eng.shutdown()


def test_greedy_parity_speculative_on_vs_off():
    """Speculation on vs off, same greedy workload: identical token
    streams and finish reasons — including a max_new that isn't a
    multiple of the round size (the delivery-capping path: a round can
    verify past the request's budget; the host truncates) and a
    max_new=1 admission-only request (zero rounds planned)."""
    cfg, params = _tiny()
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, cfg, (4, 7, 11, 6))
    max_news = [9, 1, 12, 5]  # 9, 5: not multiples of N_SPEC + 1
    on = _engine(params, cfg)
    off = _engine(params, cfg, draft=None)
    try:
        for p, mn in zip(prompts, max_news):
            a = on.generate(p, mn, timeout=300)
            b = off.generate(p, mn, timeout=300)
            assert a == b, (p, mn, a, b)
            assert len(a) == mn
    finally:
        on.shutdown()
        off.shutdown()


def test_stop_token_parity_speculative():
    """Device-side stop detection inside a verify round: the stream
    truncates AT the stop (stop token not delivered), identically to
    the non-speculative engine, even when the stop lands mid-row among
    accepted draft tokens."""
    cfg, params = _tiny()
    rng = np.random.default_rng(2)
    (p,) = _prompts(rng, cfg, (6,))
    on = _engine(params, cfg)
    off = _engine(params, cfg, draft=None)
    try:
        from ray_tpu.serve._internal.sampling import SamplingParams

        full = off.generate(p, 12, timeout=300)
        stop = full[4]  # stops mid-stream, mid-round for N_SPEC=2
        sp = SamplingParams(stop=(stop,))
        a = on.generate(p, 12, sampling=sp, timeout=300)
        b = off.generate(p, 12, sampling=sp, timeout=300)
        assert a == b
        assert stop not in a
        assert len(a) < 12
    finally:
        on.shutdown()
        off.shutdown()


def test_sampled_stream_deterministic_under_coscheduling():
    """A seeded sampled request's token stream is a function of its
    seed alone: one rng split per verify round + per-stage fold_ins
    mean co-scheduled traffic (which changes plan shapes, admission
    timing, and which static variant runs) cannot perturb it."""
    cfg, params = _tiny()
    rng = np.random.default_rng(3)
    p, noise1, noise2 = _prompts(rng, cfg, (6, 5, 8))
    from ray_tpu.serve._internal.sampling import SamplingParams

    sp = SamplingParams(temperature=0.9, top_k=0, top_p=1.0, seed=5)
    eng = _engine(params, cfg)
    try:
        alone = eng.generate(p, 10, sampling=sp, timeout=300)
    finally:
        eng.shutdown()
    eng = _engine(params, cfg)
    try:
        # different co-scheduled mix: a greedy lane and another seed
        h1 = eng.submit(noise1, 12)
        h2 = eng.submit(noise2, 8,
                        sampling=SamplingParams(temperature=0.7, seed=99))
        crowded = eng.generate(p, 10, sampling=sp, timeout=300)
        for h in (h1, h2):
            assert h.done.wait(300)
    finally:
        eng.shutdown()
    assert alone == crowded


def test_greedy_lane_exact_in_sampled_program():
    """A greedy request co-scheduled WITH sampled requests rides the
    sampled speculative variant — its stream must still be bit-exact
    greedy (temperature==0 lanes take the argmax acceptance path inside
    the sampled program)."""
    from ray_tpu.models import llama_decode as D
    from ray_tpu.serve._internal.sampling import SamplingParams

    cfg, params = _tiny()
    rng = np.random.default_rng(4)
    p, other = _prompts(rng, cfg, (7, 5))
    ref = D.generate(params, jnp.asarray([p], jnp.int32), cfg,
                     max_new_tokens=10)[0].tolist()
    eng = _engine(params, cfg)
    try:
        h = eng.submit(other, 10,
                       sampling=SamplingParams(temperature=1.1, seed=17))
        got = eng.generate(p, 10, timeout=300)
        assert h.done.wait(300)
    finally:
        eng.shutdown()
    assert got == ref


def test_rejection_heavy_runs_stay_lossless_and_leak_free():
    """An INDEPENDENT draft (different random weights) disagrees with
    the target constantly — the worst case for the rejection path:
    near-every round rolls positions back and overwrites rejected KV.
    Greedy output must STILL be bit-identical to target-only decode
    (losslessness doesn't depend on the draft being any good), and the
    paged pools must balance: every non-cache block reference returned,
    allocator zero after the radix cache clears."""
    from ray_tpu.models import llama_decode as D
    from ray_tpu.serve._internal.sampling import SamplingParams

    cfg, params = _tiny()
    eng = _engine(params, cfg, draft={"cfg": cfg, "seed": 123})
    try:
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, cfg, (6, 4, 9, 5, 7))
        ref = D.generate(params, jnp.asarray([prompts[0]], jnp.int32), cfg,
                         max_new_tokens=12)[0].tolist()
        assert eng.generate(prompts[0], 12, timeout=300) == ref
        reqs = [eng.submit(prompts[1], 10),
                eng.submit(prompts[2], 8,
                           sampling=SamplingParams(temperature=0.8, seed=2)),
                eng.submit(prompts[3], 10,
                           sampling=SamplingParams(stop=(ref[2],))),
                eng.submit(prompts[4], 6,
                           sampling=SamplingParams(temperature=1.0, seed=3))]
        for r in reqs:
            assert r.done.wait(300), "rejection-heavy workload stalled"
            assert r.error is None, r.error
        m = eng.metrics()
        assert m["draft_rejection_pct"] > 0.0, m  # the draft IS bad
        assert m["spec_verify_rounds"] > 0
        leaked = eng._alloc.leaked()
        assert all(r == 1 for r in leaked.values()), leaked
    finally:
        eng.shutdown()
    eng._prefix.clear()
    assert eng._alloc.check_zero(), eng._alloc.leaked()


def test_reference_acceptance_math():
    """The numpy reference the kernel is argued against: the residual
    construction normalize(max(p - q, 0)) plus min(1, p/q) acceptance
    reconstructs p exactly — P[emit = t] = q(t)min(1, p(t)/q(t)) +
    P[reject] * residual(t) = p(t) (Leviathan et al. 2023, Thm 1)."""
    from ray_tpu.serve._internal import speculative as S

    rng = np.random.default_rng(0)
    for _ in range(50):
        p = rng.dirichlet(np.full(16, 0.3))
        q = rng.dirichlet(np.full(16, 0.3))
        resid = S.residual_distribution(p, q)
        assert resid.shape == p.shape
        np.testing.assert_allclose(resid.sum(), 1.0, atol=1e-12)
        assert np.all(resid[p <= q] == 0.0)
        p_reject = 1.0 - S.expected_accept_prob(p, q)
        emit = np.minimum(p, q) + p_reject * resid
        np.testing.assert_allclose(emit, p, atol=1e-12)
    # degenerate case p == q: zero residual mass falls back to p itself
    np.testing.assert_allclose(S.residual_distribution(p, p), p, atol=1e-12)
    assert S.greedy_accept_len(np.array([3, 5, 7]),
                               np.array([3, 5, 2, 9])) == 2
    assert S.accept_token(p_d=0.5, q_d=0.25, u=0.999)   # p > q: always
    assert not S.accept_token(p_d=0.1, q_d=0.9, u=0.5)  # p/q = 1/9 < u


def test_speculation_config_validation():
    """Config errors are loud: speculation needs a positive token count
    and a vocab-matched draft."""
    import dataclasses

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg, params = _tiny()
    with pytest.raises(ValueError, match="num_speculative_tokens"):
        _engine(params, cfg, num_speculative_tokens=0)
    with pytest.raises(ValueError, match="draft_model"):
        _engine(params, cfg, draft=None, num_speculative_tokens=2)
    with pytest.raises(ValueError, match="vocab"):
        bad = dataclasses.replace(cfg, vocab_size=256)
        _engine(params, cfg, draft=bad)
    with pytest.raises(ValueError, match="self"):
        _engine(params, cfg, draft="other-model")
