"""The hybrid decoder (Mamba-2 layers + NoPE attention layers) against its plain
reference, and its lanes of recurrent state in the paged engine (ISSUE 29).

CPU, a tiny config with the real shape of things: `layer_types`
[m, m, a, m, m], 4 Mamba heads x 8, state 16, chunk 8, GQA 4 / 2, a tied head,
the four multipliers not 1. The reference is benchmark/reference_granite_hybrid
(float32, the recurrence one position at a time); weights come from the
benchmark's seed-made generator, so nothing compared shares an algorithm.

Tolerances. float32: 1e-4 relative to the largest logit (measured 2e-6: both
sides are float32, they differ by the order of sums: chunked against
sequential, blockwise softmax against plain). bfloat16: 0.1 absolute on logits
of spread 0.5 (measured 0.05): every activation is rounded to 8 bits of
mantissa some twenty times on the way.
"""
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark import reference_granite_hybrid as R
from benchmark import weights_granite_hybrid as W
from ray_tpu.models import granite_hybrid as G
from ray_tpu.models import granite_hybrid_decode as D
from ray_tpu.ops import ssm_update as SU
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.conftest import static_answers

F32_RTOL = 1e-4
BF16_ATOL = 0.1
BLOCK = 16


# Mamba widths the decode-side kernel's tiles take (ops/ssm_update.supported)
KERNEL_WIDTHS = (("mamba_n_heads", 16), ("mamba_d_state", 128))


@functools.lru_cache(maxsize=8)
def _model(dtype=jnp.float32, seed=2**31 + 29, widths=()):
    cfg = G.GraniteHybridConfig.tiny(dtype=dtype, **dict(widths))
    key = W.seed_key(seed)
    return cfg, key, W.init_params(key, cfg)


def _tokens(n, length, seed=0, vocab=512):
    return np.random.default_rng([seed, length]).integers(0, vocab, (n, length)).astype(np.int32)


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        assert np.abs(got - want).max() <= F32_RTOL * np.abs(want).max()
    else:
        assert np.abs(got - want).max() <= BF16_ATOL


def test_config_is_hashable_and_names_its_own_modules():
    cfg = G.GraniteHybridConfig()
    assert hash(cfg) == hash(G.GraniteHybridConfig()) and cfg.n_layers == 40
    assert [i for i, t in enumerate(cfg.layer_types) if t == G.ATTENTION] == [5, 15, 25, 35]
    assert (cfg.d_inner, cfg.conv_dim, cfg.head_dim, cfg.mamba_d_head) == (4096, 4352, 64, 64)
    assert cfg.model_module is G and cfg.decode_module is D
    assert G.num_params(cfg) == 3_191_396_096
    assert D.state_bytes_per_lane(cfg) == 36 * (3 * 4352 * 2 + 64 * 64 * 128 * 4)
    with pytest.raises(ValueError, match="mamba_n_groups"):
        G.GraniteHybridConfig(mamba_n_groups=2)


# ------------------------------------------------- (a) forward vs reference
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [5, 8, 19])  # under, at and across chunk boundaries
def test_forward_matches_the_reference(T, dtype):
    cfg, key, params = _model(dtype)
    toks = jnp.asarray(_tokens(2, T))
    forward = jax.jit(functools.partial(G.forward, cfg=cfg))
    _close(forward(params, toks), R.logits(key, toks, cfg), dtype)


# --------------------------------------- (b) chunked scan vs one-step update
@pytest.mark.parametrize("T", [5, 8, 19, 32])
def test_ssd_chunked_is_ssm_step_iterated(T):
    rng = np.random.default_rng(T)
    R_, H, P, N = 3, 4, 8, 16
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, B, C = f(R_, T, H, P), f(R_, T, N), f(R_, T, N)
    dt = jax.nn.softplus(f(R_, T, H) - 2.0)
    A, Dp = -jnp.exp(f(H)), f(H)
    y, h = G.ssd_chunked(x, dt, A, B, C, Dp, chunk=8)
    hs, ys = jnp.zeros((R_, H, P, N), jnp.float32), []
    for t in range(T):
        yt, hs = G.ssm_step(hs, x[:, t], dt[:, t], A, B[:, t], C[:, t], Dp)
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h, hs, rtol=1e-4, atol=1e-5)


# ------------------------------ the paged cache driven by hand (c, d, e)
@pytest.fixture(params=["xla", "kernel"])
def update_path(request, monkeypatch):
    """The two paths of a decode step's state update, as the config's widths
    to take: `ssm_step` + select + write (what the CPU runs), and the Pallas
    kernel a TPU runs, here in the TPU interpret mode at widths it takes."""
    if request.param == "xla":
        yield ()
        return
    monkeypatch.setattr(SU, "_on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        yield KERNEL_WIDTHS


@functools.lru_cache(maxsize=4)
def _jitted_halves(cfg):
    return (jax.jit(functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False)),
            jax.jit(functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False)))


class Lanes:
    """The model's admission and decode step on a paged cache of `n` lanes:
    lane b owns blocks 1 + b * mb .. of the pool."""

    def __init__(self, cfg, params, n=3, span=64):
        self.cfg, self.params, self.n = cfg, params, n
        self.mb = span // BLOCK
        self.cache = D.init_paged_cache(cfg, n, n * self.mb + 1, BLOCK)
        self.tables = 1 + jnp.arange(n * self.mb, dtype=jnp.int32).reshape(n, self.mb)
        self.feed = jnp.zeros((n,), jnp.int32)
        z = jnp.zeros((n,), jnp.int32)
        self.plan = dict(temps=jnp.zeros((n,), jnp.float32), top_ks=z,
                         top_ps=jnp.ones((n,), jnp.float32),
                         stop_ids=jnp.full((n, 1), -1, jnp.int32))
        self._admit, self._step = _jitted_halves(cfg)

    def admit(self, rows, bucket, new=8, width=None):
        """rows: [(lane, prompt)]; the admission is `width` rows wide (the
        rest padding rows of length 0) and `bucket` positions long."""
        A = width or len(rows)
        prompts = np.zeros((A, bucket), np.int32)
        lengths, slots = np.zeros(A, np.int32), np.zeros(A, np.int32)
        for i, (lane, p) in enumerate(rows):
            prompts[i, :len(p)], lengths[i], slots[i] = p, len(p), lane
        z = jnp.zeros((A,), jnp.int32)
        first, self.cache, self.feed = self._admit(
            self.params, jnp.asarray(prompts), jnp.asarray(lengths), z, jnp.asarray(slots),
            jnp.where(jnp.asarray(lengths) > 0, new - 1, 0), z.astype(jnp.uint32), self.cache,
            self.feed, self.tables, **self.plan)
        return np.asarray(first)

    def step(self):
        logits, nxt, self.cache = self._step(self.params, self.cache, self.feed, self.tables,
                                             **self.plan)
        self.feed = nxt
        return np.asarray(logits), np.asarray(nxt)

    def state(self, lane):
        return (np.asarray(self.cache["conv"][:, :, lane]), np.asarray(self.cache["ssm"][:, lane]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_admission_then_decode_matches_the_reference_at_every_position(dtype, update_path):
    """(c) prefill then decode through the cache against the reference's
    full forward over prompt + emitted tokens, logits at every emitted
    position (the first token's too, through the state it leaves)."""
    cfg, key, params = _model(dtype, widths=update_path)
    lanes = Lanes(cfg, params, n=2)
    prompts = [_tokens(1, 19, seed=3)[0], _tokens(1, 7, seed=4)[0]]
    n_new = 9
    first = lanes.admit(list(enumerate(prompts)), bucket=32, new=n_new)
    steps = [lanes.step() for _ in range(n_new - 1)]
    seqs = np.zeros((2, 19 + n_new), np.int32)  # right-padded: causal, so harmless there
    for b, p in enumerate(prompts):
        emitted = [first[b]] + [nxt[b] for _, nxt in steps]
        seqs[b, :len(p) + n_new] = np.concatenate([p, emitted])
    refs = np.asarray(R.logits(key, jnp.asarray(seqs), cfg))
    for b, p in enumerate(prompts):
        assert int(refs[b, len(p) - 1].argmax()) == first[b] or dtype != jnp.float32
        for t, (logits, _) in enumerate(steps):
            _close(logits[b], refs[b, len(p) + t], dtype)


def test_padding_is_exact_in_the_recurrence():
    """(d), the mixer: right-padding a row from 16 to 32 positions leaves its
    conv tail, its outputs at the real positions and its final state bit for
    bit the same: past the length the step is 0, so the decay is exp(0) = 1
    and the input 0, and the tail is gathered at the last real positions."""
    rng = np.random.default_rng(11)
    R_, H, P, N, C, n = 2, 4, 8, 16, 4 * 8 + 2 * 16, 11
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    layer = {"conv_w": f(4, C), "conv_b": f(C)}
    lengths = jnp.asarray([n, 16], jnp.int32)
    xBC, raw_dt = f(R_, 32, C), jax.nn.softplus(f(R_, 32, H))
    A, Dp = -jnp.exp(f(H)), f(H)

    def run(T):
        conv, tail = G.causal_conv(xBC[:, :T], layer, lengths)
        x, B, Cc = conv[..., :H * P].reshape(R_, T, H, P), conv[..., H * P:H * P + N], \
            conv[..., H * P + N:]
        dt = jnp.where(jnp.arange(T)[None, :, None] < lengths[:, None, None], raw_dt[:, :T], 0.0)
        y, h = G.ssd_chunked(x, dt, A, B, Cc, Dp, chunk=8)
        return tail, y[0, :n], h

    for short, long in zip(run(16), run(32)):
        np.testing.assert_array_equal(short, long)
    np.testing.assert_array_equal(run(16)[0][0], xBC[0, n - 3:n])  # the last 3 real inputs


@pytest.mark.parametrize("bucket,beside", [(16, True), (32, False), (32, True)],
                         ids=["P16-beside-longer", "P32-alone", "P32-beside-longer"])
def test_padding_changes_nothing_through_admission(bucket, beside):
    """(d), the whole admission: the same prompt at buckets 16 and 32, alone
    and beside longer rows, gives the same state, conv tail and first decode
    logits, float32. To 1e-5 of the largest value and not bit for bit: the
    backend's matrix products and the attention layer's softmax order their
    sums by the shape of the whole batch (16, 48 or 96 rows here); the
    recurrence itself is exact (the test above)."""
    cfg, _, params = _model()
    prompt = _tokens(1, 11, seed=5)[0]

    def run(bucket, rows, lane):
        lanes = Lanes(cfg, params)
        lanes.admit(rows, bucket)
        logits, _ = lanes.step()
        return lanes.state(lane) + (logits[lane],)

    want = run(16, [(0, prompt)], 0)
    others = [(0, _tokens(1, min(29, bucket), seed=6)[0]),
              (2, _tokens(1, 15, seed=7)[0])] if beside else []
    got = run(bucket, others[:1] + [(1, prompt)] + others[1:], 1)
    for w, g in zip(want, got):
        assert np.abs(w - g).max() <= 1e-5 * np.abs(w).max()


def test_a_lane_is_untouched_by_the_others(update_path):
    """(e) a lane's state is unchanged by other lanes' admissions (padding
    rows included) and by steps taken while it is inactive; a lane reused by
    a second request gives what a fresh cache gives."""
    cfg, _, params = _model(widths=update_path)
    assert SU.supported(cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state) == bool(update_path)
    lanes = Lanes(cfg, params)
    a, b, c = (_tokens(1, n, seed=s)[0] for n, s in ((13, 8), (21, 9), (9, 10)))
    lanes.admit([(1, a)], 16, new=3)                 # lane 1 owes 2 decode steps
    lanes.step(), lanes.step()
    assert int(lanes.cache["remaining"][1]) == 0     # inactive from here on
    frozen = lanes.state(1)
    lanes.admit([(0, b)], 32, new=6, width=2)        # one real row, one padding row (lane 0)
    for _ in range(3):
        lanes.step()                                  # lanes 0 active, 1 and 2 not
    for w, g in zip(frozen, lanes.state(1)):
        np.testing.assert_array_equal(w, g)
    assert not lanes.state(2)[1].any()               # never admitted: still zeros

    lanes.admit([(1, c)], 16, new=5)                 # lane 1 reused
    reused = [lanes.step()[0][1] for _ in range(4)]
    fresh_lanes = Lanes(cfg, params)
    fresh_lanes.admit([(1, c)], 16, new=5)
    fresh = [fresh_lanes.step()[0][1] for _ in range(4)]
    np.testing.assert_array_equal(np.stack(reused), np.stack(fresh))


# ------------------------------------------------------ the engine (f, g, h)
def _engine(**kw):
    cfg, _, params = _model()
    return ContinuousBatchingEngine(params, cfg, **{**dict(
        n_slots=3, chunk=4, macro_phases=4, max_len=128, block_size=BLOCK,
        prefix_cache=False), **kw})


def test_engine_serves_more_requests_than_lanes_like_the_static_path(tmp_path):
    """(f) mixed lengths through three lanes: greedy tokens equal the static
    `generate`; (h) `state_lane_steps` is the sum of the dispatch spans'
    `state_lanes`, and `state_bytes` the lane's constant."""
    cfg, _, params = _model()
    eng = _engine()
    try:
        # three prompt lengths and two answer lengths: the static path compiles each pair
        lengths, answers = (9, 30, 21, 9, 30, 21, 9), (6, 6, 11, 11, 6, 6, 1)
        prompts = [_tokens(1, n, seed=20 + i)[0].tolist() for i, n in enumerate(lengths)]
        eng.generate(prompts[0], 2)  # the loop is up, a program compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            m0 = eng.metrics()
            reqs = [eng.submit(p, n) for p, n in zip(prompts, answers)]
            assert all(r.done.wait(180) for r in reqs)
            m1 = eng.metrics()
        finally:
            jax.profiler.stop_trace()
        for want, r in zip(static_answers(D.generate, params, cfg, prompts, answers), reqs):
            assert r.error is None
            assert r.tokens == want
    finally:
        eng.shutdown()
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    dispatches = [dict(e.stats) for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for e in line.events if e.name == "engine.dispatch"]
    moved = m1["state_lane_steps"] - m0["state_lane_steps"]
    assert moved == m1["useful_slot_steps"] - m0["useful_slot_steps"] > 0
    assert sum(d["state_lanes"] for d in dispatches) == moved
    assert all(d["state_lanes"] == d["lane_steps"] for d in dispatches)
    assert m1["state_bytes"] == D.state_bytes_per_lane(cfg) > 0


REFUSED_AT_CONSTRUCTION = {
    "prefix_cache": dict(prefix_cache=True),
    "draft_model": dict(draft_model="self", num_speculative_tokens=2),
    "num_speculative_tokens": dict(num_speculative_tokens=2),
    "role": dict(role="decode"),
    "cluster_cache": dict(cluster_cache=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED_AT_CONSTRUCTION))
def test_what_needs_a_state_snapshot_is_refused_at_construction(option):
    """(g) each by name, with the reason; nothing is switched off silently."""
    with pytest.raises(ValueError) as refusal:
        _engine(**REFUSED_AT_CONSTRUCTION[option])
    assert option in str(refusal.value) and "recurrent state" in str(refusal.value)


@pytest.mark.parametrize("call", ["export_prefix", "import_prefix", "submit_resumed"])
def test_block_transfer_is_refused_at_the_call(call):
    eng = _engine()
    try:
        args = {"export_prefix": ("digest",), "import_prefix": ([1, 2], None, None, 1),
                "submit_resumed": ([1, 2], 3, 4, None, None, 1)}[call]
        before = eng.metrics()
        with pytest.raises(ValueError, match=f"{call} is refused.*recurrent state"):
            getattr(eng, call)(*args)
        after = eng.metrics()  # a refusal counts nothing
        assert {k: after[k] for k in eng._m} == {k: before[k] for k in eng._m}
    finally:
        eng.shutdown()


def test_llm_deployment_passes_the_options_through():
    """The deployment callable builds config-default params through the
    config's own module and hands the refused options on unchanged."""
    from ray_tpu.serve.llm import _LLMServer

    cfg = G.GraniteHybridConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache=True is refused"):
        _LLMServer(cfg=cfg, continuous=True, n_slots=2)  # prefix_cache defaults to True
    server = _LLMServer(cfg=cfg, continuous=True, n_slots=2, prefix_cache=False, seed=3)
    try:
        want = D.generate(server.params, np.asarray([[5, 6, 7]]), cfg, 4)[0].tolist()
        assert server.engine.generate([5, 6, 7], 4) == want
    finally:
        server.engine.shutdown()


def test_llama_lanes_hold_no_recurrent_state():
    from ray_tpu.models import llama, llama_decode

    cfg = llama.LlamaConfig.tiny()
    assert cfg.decode_module is llama_decode and cfg.model_module is llama
    assert llama_decode.state_bytes_per_lane(cfg) == 0
