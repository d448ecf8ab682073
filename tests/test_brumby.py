"""The Brumby decoder (power retention of degree 2 in every layer, no
attention layer, a cache with no pool) against its plain reference, and its
state in the paged engine (ISSUE 52).

CPU, a tiny config with the real shape of things: three layers, two query
heads a KV head, heads of 16 (the symmetric square: 136 products, laid out in
144 columns), a chunk of 8, blocks of 4. The reference is
benchmark/reference_brumby (float32, the ATTENTION form: whole matrices of
squared scores times the decays of one cumulative sum, no phi, no state, no
chunk); weights come from the benchmark's seed-made generator, so nothing
compared shares an algorithm: that the program's expansion and recurrence
agree with a reference that never expands is the point.

Tolerances. float32: 1e-4 relative to the largest logit (measured 2e-6), as
tests/test_qwen3_next.py has it: the algebra of all three forms is held there,
and every wrong variant below (degree 1, the decay on the current position,
the wrong KV head, no normaliser, sqrt 2 left out of phi, the state kept in
bfloat16) misses it by two orders or more. bfloat16 guards against gross
faults only: 0.25 on the median over positions of a position's r.m.s. error
over the vocabulary, logits of spread 1 (measured 0.02 to 0.04): a square
doubles a score's relative rounding, which the float32 carried state and
normaliser do not amplify.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark import reference_brumby as R
from benchmark import weights_brumby as W
from ray_tpu.models import brumby as M
from ray_tpu.models import brumby_decode as D
from ray_tpu.models.granite_hybrid import live_rows
from ray_tpu.ops import retention_update as RU
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.conftest import static_answers

F32_RTOL = 1e-4
BF16_RMS = 0.25
BLOCK = 4
SEED = 2**31 + 52


@functools.lru_cache(maxsize=8)
def _model(dtype=jnp.float32):
    cfg = M.BrumbyConfig.tiny(dtype=dtype)
    key = W.seed_key(SEED)
    return cfg, key, W.init_params(key, cfg)


def _tokens(n, length, seed=0, vocab=512):
    return np.random.default_rng([seed, length]).integers(0, vocab, (n, length)).astype(np.int32)


def _worst(got, want, dtype):
    """The comparison's error in units of its tolerance, for logits
    (..., V): <= 1 passes."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        return np.abs(got - want).max() / (F32_RTOL * np.abs(want).max())
    return np.median(np.sqrt(np.square(got - want).mean(-1))) / BF16_RMS


# ------------------------------------------------------------ the config
def test_config_is_hashable_and_names_its_own_modules():
    cfg = M.BrumbyConfig()
    assert hash(cfg) == hash(M.BrumbyConfig()) and cfg.model_module is M and cfg.decode_module is D
    assert (cfg.phi_dim, cfg.state_rows) == (65 * 128, 136)
    tiny = M.BrumbyConfig.tiny()
    assert (tiny.phi_dim, tiny.state_rows, tiny.n_heads // tiny.n_kv_heads) == (9 * 16, 24, 2)
    with pytest.raises(ValueError):
        M.BrumbyConfig(n_heads=40, n_kv_heads=7)
    # the benchmark's tree is the program's
    cfg, key, params = _model()
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: M.init_params(key, cfg)))


def test_num_params_at_the_published_sizes_is_the_issues_table():
    layer = (5120 * 5120 * 2 + 5120 * 1024 * 2) + (5120 * 8 + 8 + 2 * 128) + 3 * 5120 * 17408 + 2 * 5120
    assert layer == 62_914_560 + 41_224 + 267_386_880 + 10_240 == 330_352_904
    assert M.num_params(M.BrumbyConfig()) == 40 * layer + 2 * 151_936 * 5120 + 5120 == 14_769_945_920
    assert M.num_params(M.BrumbyConfig(n_layers=6)) == 6 * layer + 2 * 151_936 * 5120 + 5120
    assert D.state_bytes_per_lane(M.BrumbyConfig(n_layers=1)) == 8 * 136 * 8320 * 4


@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_is_the_symmetric_square(d):
    """phi(x) . phi(y) == (x . y)^2, in (d / 2 + 1) d columns that hold each
    of the d (d + 1) / 2 distinct products (row d / 2 twice at weight 1)."""
    rng = np.random.default_rng(d)
    x, y = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32) for _ in range(2))
    px, py = M.phi(x), M.phi(y)
    assert px.shape == (5, (d // 2 + 1) * d)
    # float32 products summed in float64: what is left is the products' own rounding
    got = (np.asarray(px, np.float64) * np.asarray(py, np.float64)).sum(-1)
    want = (np.asarray(x, np.float64) * np.asarray(y, np.float64)).sum(-1) ** 2
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(np.asarray(px) * np.asarray(py)).sum(-1).max())
    # one-hot inputs name the products: e_i + e_j has x_i x_j at weight sqrt 2, twice in all
    e = np.zeros((1, d), np.float32)
    e[0, 0] = e[0, d - 1] = 1.0
    assert float((M.phi(jnp.asarray(e)) ** 2).sum()) == pytest.approx(4.0)  # (|e|^2)^2


# ------------------------------------------------- (a) forward vs reference
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_forward_matches_the_reference(dtype):
    cfg, key, params = _model(dtype)
    tokens = _tokens(2, 37, seed=1)
    want = R.logits(key, jnp.asarray(tokens), cfg)
    got = M.forward(params, jnp.asarray(tokens), cfg)
    assert _worst(got, want, dtype) <= 1.0
    assert float(np.asarray(want).std()) > 0.5  # logits of spread 1: the tolerance means something


# --------------------------------------- (b) the three forms of the retention
def _qkvg(R_, T, H=4, KV=2, d=16, seed=0):
    rng = np.random.default_rng([seed, T])
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    log_g = jax.nn.log_sigmoid(f(R_, T, KV) + 2.0)
    return f(R_, T, H, d) * d ** -0.5, f(R_, T, KV, d), f(R_, T, KV, d), log_g


def _recurrence(q, k, v, log_g, step=M.retention_step, carried=jnp.float32):
    """One position at a time from a zero state, the state carried in `carried`."""
    R_, T, H, d = q.shape
    S = jnp.zeros((R_, k.shape[2], d + M.STATE_PAD, (d // 2 + 1) * d), carried)
    out = []
    for t in range(T):
        o, S = step(S.astype(jnp.float32), q[:, t], k[:, t], v[:, t], jnp.exp(log_g[:, t]), 1e-6)
        S = S.astype(carried)
        out.append(o)
    return jnp.stack(out, axis=1), S.astype(jnp.float32)


def _attention_form(q, k, v, log_g):
    """The reference's, a row at a time; q comes scaled, the reference scales itself."""
    d = q.shape[-1]
    return jnp.stack([R.retention(q[r] * d ** 0.5, k[r], v[r], log_g[r]) for r in range(q.shape[0])])


@pytest.mark.parametrize("chunk", [4, 7, 8, 32], ids=lambda c: f"chunk{c}")
def test_chunked_equals_the_recurrence_and_the_attention_form(chunk):
    """T = 21 in chunks that do and do not divide it (and one chunk for all):
    outputs across chunk boundaries and the final state; past a row's length
    (13 of 21) the state stands and the outputs before it are unchanged."""
    q, k, v, log_g = _qkvg(2, 21, seed=chunk)
    full = jnp.asarray([21, 21])
    o, S = M.retention_chunked(q, k, v, log_g, full, chunk, 1e-6)
    o_step, S_step = _recurrence(q, k, v, log_g)
    scale = float(jnp.abs(o_step).max())
    assert float(jnp.abs(o - _attention_form(q, k, v, log_g)).max()) <= 1e-4 * scale  # measured 1.5e-6
    # the float32 recurrence itself is the looser of the three: phi(q) . z sums 144 signed
    # products to a sum of squares, and where that is small digits cancel (measured 2e-4)
    assert float(jnp.abs(o - o_step).max()) <= 2e-3 * scale
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_step), rtol=1e-4, atol=1e-5)
    o_cut, S_cut = M.retention_chunked(q, k, v, log_g, jnp.asarray([13, 21]), chunk, 1e-6)
    _, S_13 = _recurrence(q[:1, :13], k[:1, :13], v[:1, :13], log_g[:1, :13])
    np.testing.assert_allclose(np.asarray(S_cut[0]), np.asarray(S_13[0]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_cut[0, :13]), np.asarray(o[0, :13]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S_cut[1]), np.asarray(S[1]), rtol=1e-5, atol=1e-6)


def _weights_form(q, k, v, log_g, power=2, inclusive=False, by_modulo=False, normalise=True):
    """The attention form over all rows and heads at once, with the switches
    that make it wrong: the scores' power, the decay applied to the current
    position too (S_t = g_t (S_(t-1) + phi(k_t) v_t^T)), query head h reading
    KV head h % n_kv, no normaliser. q comes scaled."""
    H, KV = q.shape[2], k.shape[2]
    head = jnp.arange(H) % KV if by_modulo else jnp.arange(H) // (H // KV)
    k, v, log_g = k[:, :, head], v[:, :, head], log_g[:, :, head]
    c = jnp.cumsum(log_g, axis=1)
    reach = c[:, :, None] - (c - log_g if inclusive else c)[:, None, :]      # (R, t, j, H)
    seen = jnp.tril(jnp.ones((q.shape[1],) * 2, bool))[None, :, :, None]
    w = jnp.where(seen, jnp.einsum("rthd,rjhd->rtjh", q, k) ** power * jnp.exp(reach * seen), 0.0)
    o = jnp.einsum("rtjh,rjhd->rthd", w, v)
    return o / (w.sum(axis=2)[..., None] + 1e-6) if normalise else o


def _wrong(name):
    """The model with one thing wrong: a switch of `_weights_form`, or the
    program's own forms with `phi` or the carried state's type changed."""
    q, k, v, log_g = _qkvg(2, 21, seed=9)
    switches = {"degree_1": dict(power=1), "decay_on_the_current_position": dict(inclusive=True),
                "kv_head_is_h_mod_n_kv": dict(by_modulo=True), "no_normaliser": dict(normalise=False)}
    if name in switches:
        return _weights_form(q, k, v, log_g, **switches[name])
    if name == "sqrt_2_left_out_of_phi":
        def unweighted(x):
            d = x.shape[-1]
            xx = jnp.concatenate([x, x], axis=-1)
            return jnp.concatenate([x * xx[..., s:s + d] for s in range(d // 2 + 1)], axis=-1)

        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(M, "phi", unweighted)
            return M.retention_chunked(q, k, v, log_g, jnp.asarray([21, 21]), 8, 1e-6)[0]
    assert name == "state_in_bfloat16"
    return _recurrence(q, k, v, log_g, carried=jnp.bfloat16)[0]


@pytest.mark.parametrize("name", ["degree_1", "decay_on_the_current_position", "kv_head_is_h_mod_n_kv",
                                  "no_normaliser", "sqrt_2_left_out_of_phi", "state_in_bfloat16"])
def test_a_wrong_variant_fails(name):
    """The float32 comparison with the attention form, 1e-4 of the largest
    output, tells each of these from the model: each misses it by 50 times
    or more, where the chunked form and the recurrence pass it."""
    q, k, v, log_g = _qkvg(2, 21, seed=9)
    want = _attention_form(q, k, v, log_g)
    scale = 1e-4 * float(jnp.abs(want).max())
    right = M.retention_chunked(q, k, v, log_g, jnp.asarray([21, 21]), 8, 1e-6)[0]
    assert float(jnp.abs(right - want).max()) <= scale
    assert float(jnp.abs(_weights_form(q, k, v, log_g) - want).max()) <= scale  # no switch: the model
    assert float(jnp.abs(_wrong(name) - want).max()) > 50 * scale


# ------------------------------ (c) the paged cache driven by hand
@functools.lru_cache(maxsize=4)
def _jitted_halves(cfg):
    return (jax.jit(functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False)),
            jax.jit(functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False)))


class Lanes:
    """The model's admission and decode step on a paged cache of `n` lanes
    (the tables name blocks that nothing backs)."""

    def __init__(self, cfg, params, n=3, span=64):
        self.cfg, self.params, self.n = cfg, params, n
        mb = span // BLOCK
        self.cache = D.init_paged_cache(cfg, n, n * mb + 1, BLOCK)
        self.tables = 1 + jnp.arange(n * mb, dtype=jnp.int32).reshape(n, mb)
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
        return np.asarray(self.cache["state"][:, lane])


def test_the_cache_has_no_pool():
    cfg, _, _ = _model()
    cache = D.init_paged_cache(cfg, 3, 49, BLOCK)
    assert sorted(cache) == ["pos", "remaining", "rng", "state"]
    assert cache["state"].shape == (3, 3, 2, 24, 144) and cache["state"].dtype == jnp.float32
    assert D.state_bytes_per_lane(cfg) == cache["state"][:, 0].size * 4
    # blocks size nothing
    assert jax.tree.map(jnp.shape, D.init_paged_cache(cfg, 3, 5, 16)) == jax.tree.map(jnp.shape, cache)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_admission_then_decode_matches_the_reference_at_every_position(dtype):
    """The chunked admission leaves a state; the decode step goes on from it
    one position at a time: logits at every emitted position against the
    reference's full forward over prompt + emitted (the attention form from
    position 0, nothing cached). Prompts of 19 and 5 tokens in a bucket of 32
    (chunks of 8: the shorter ends inside the first), a padded row between
    them."""
    cfg, key, params = _model(dtype)
    lanes = Lanes(cfg, params)
    prompts = {0: _tokens(1, 19, seed=3)[0], 2: _tokens(1, 5, seed=4)[0]}
    n_new = 13
    untouched = lanes.state(1)
    first = lanes.admit([(0, prompts[0]), (2, prompts[2])], bucket=32, new=n_new, width=4)
    steps = [lanes.step() for _ in range(n_new - 1)]
    seqs = np.zeros((2, 19 + n_new), np.int32)  # right-padded: causal, so harmless there
    for i, (b, p) in enumerate(prompts.items()):
        seqs[i, :len(p) + n_new] = np.concatenate([p, [first[i]] + [nxt[b] for _, nxt in steps]])
    refs = np.asarray(R.logits(key, jnp.asarray(seqs), cfg))
    got = np.stack([[logits[b] for logits, _ in steps] for b in prompts])
    want = np.stack([refs[i, len(p):len(p) + n_new - 1] for i, p in enumerate(prompts.values())])
    assert _worst(got, want, dtype) <= 1.0
    if dtype == jnp.float32:
        assert all(int(refs[i, len(p) - 1].argmax()) == first[i]
                   for i, p in enumerate(prompts.values()))
    # lane 1 was never admitted and never live: bit for bit what it was
    np.testing.assert_array_equal(untouched, lanes.state(1))


def test_a_lane_is_untouched_by_the_others():
    """A lane's state is unchanged, bit for bit, by other lanes' admissions
    (padding rows included) and by steps taken while it is inactive; a lane
    reused by a second request gives what a fresh cache gives."""
    cfg, _, params = _model()
    lanes = Lanes(cfg, params)
    a, b, c = (_tokens(1, n, seed=s)[0] for n, s in ((13, 8), (21, 9), (9, 10)))
    lanes.admit([(1, a)], 16, new=3)                 # lane 1 owes 2 decode steps
    lanes.step(), lanes.step()
    assert int(lanes.cache["remaining"][1]) == 0     # inactive from here on
    frozen = lanes.state(1)
    assert frozen.any()
    lanes.admit([(0, b)], 32, new=6, width=2)        # one real row, one padding row (lane 0)
    for _ in range(3):
        lanes.step()                                  # lane 0 active, 1 and 2 not
    np.testing.assert_array_equal(frozen, lanes.state(1))
    assert not lanes.state(2).any()                  # never admitted: still zeros

    lanes.admit([(1, c)], 16, new=5)                 # lane 1 reused
    reused = [lanes.step()[0][1] for _ in range(4)]
    fresh_lanes = Lanes(cfg, params)
    fresh_lanes.admit([(1, c)], 16, new=5)
    fresh = [fresh_lanes.step()[0][1] for _ in range(4)]
    np.testing.assert_array_equal(np.stack(reused), np.stack(fresh))


def test_an_admission_walks_its_rows_in_pieces_to_the_same_numbers(monkeypatch):
    """Eight rows, two a piece (`SCAN_TOKENS`), against all eight at once:
    rows are independent sequences."""
    cfg, _, params = _model()
    tokens = jnp.asarray(_tokens(8, 24, seed=5))
    lengths = jnp.asarray([24, 3, 17, 24, 9, 1, 24, 12])
    want = M.forward(params, tokens, cfg, lengths)
    monkeypatch.setattr(M, "SCAN_TOKENS", 2 * cfg.ret_chunk)
    assert M.rows_of_a_step(8, 24, cfg) == 2
    got = M.forward(params, tokens, cfg, lengths)
    real = np.arange(24)[None, :] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=1e-3, atol=5e-4)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_the_stacked_step_is_the_one_position_form(path, monkeypatch):
    """`retention_step_stacked` on a stack of two layers against
    `retention_step` on the layer: the live rows' outputs and states, the
    others and the other layer bit for bit. `kernel`: ops/retention_update.py
    as a TPU runs it, here in the TPU interpret mode at a head size its tiles
    take (128: a state of 136 x 8,320 a KV head, five blocks of 1,664)."""
    L, KV, G, d = (2, 1, 2, 128) if path == "kernel" else (5, 2, 2, 16)
    W_ = (d // 2 + 1) * d
    assert RU.supported(d + 8, W_, G) == (path == "kernel")
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    state = f(2, L, KV, d + 8, W_).at[:, :, :, d + 1:].set(0.0)
    state = state.at[:, :, :, d].set(jnp.abs(state[:, :, :, d]))
    q, k, v = f(L, KV * G, d) * d ** -0.5, f(L, KV, d), f(L, KV, d)
    g = jax.nn.sigmoid(f(L, KV) + 3.0)
    active = jnp.asarray(([True, False] if path == "kernel" else [True, False, True, True, False]))
    step = jax.jit(functools.partial(M.retention_step_stacked, eps=1e-6))
    want_o, want_S = M.retention_step(state[1], q, k, v, g, 1e-6)
    live = np.asarray(active)

    def run(flags):
        if path == "xla":
            return step(state, jnp.int32(1), live_rows(flags), q, k, v, g)
        monkeypatch.setattr(RU, "_on_tpu", lambda: True)
        with pltpu.force_tpu_interpret_mode():
            return step(state, jnp.int32(1), live_rows(flags), q, k, v, g)

    o, new = run(active)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(new[1])[live], np.asarray(want_S)[live], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new[1])[~live], np.asarray(state[1])[~live])
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    # no lane live: nothing moves
    _, same = run(jnp.zeros((L,), bool))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))


# ------------------------------------------------------------- (d) the engine
def _engine(**kw):
    cfg, _, params = _model()
    return ContinuousBatchingEngine(params, cfg, **{**dict(
        n_slots=3, chunk=4, macro_phases=4, max_len=128, block_size=BLOCK,
        prefix_cache=False), **kw})


def test_static_generation_is_the_plain_forwards_argmax():
    cfg, _, params = _model()
    prompt = _tokens(2, 21, seed=12)
    out = D.generate(params, prompt, cfg, 12)
    seq = np.concatenate([prompt, out], axis=1)
    logits = np.asarray(M.forward(params, jnp.asarray(seq), cfg))
    np.testing.assert_array_equal(out, logits[:, 20:-1].argmax(-1))


def test_engine_serves_more_requests_than_lanes_with_no_pool_in_the_cache():
    """Mixed lengths through three lanes: greedy tokens equal the static
    `generate`, lanes are reused, the engine's counters say what moved, and
    the cache it holds has no K/V pool at all."""
    cfg, _, params = _model()
    eng = _engine()
    try:
        assert not {"k", "v"} & set(eng.cache) and "state" in eng.cache
        lengths, answers = (9, 30, 21, 9, 30, 21, 5), (6, 20, 11, 11, 6, 6, 1)
        prompts = [_tokens(1, n, seed=20 + i)[0].tolist() for i, n in enumerate(lengths)]
        m0 = eng.metrics()
        reqs = [eng.submit(p, n) for p, n in zip(prompts, answers)]
        assert all(r.done.wait(240) for r in reqs)
        m1 = eng.metrics()
        for want, r in zip(static_answers(D.generate, params, cfg, prompts, answers), reqs):
            assert r.error is None
            assert r.tokens == want
    finally:
        eng.shutdown()
    lane_steps = m1["useful_slot_steps"] - m0["useful_slot_steps"]
    assert lane_steps == sum(n - 1 for n in answers)
    assert m1["state_lane_steps"] - m0["state_lane_steps"] == lane_steps
    assert m1["state_bytes"] == D.state_bytes_per_lane(cfg) > 0
    assert not hasattr(D, "DEVICE_COUNTERS")


REFUSED_AT_CONSTRUCTION = {
    "prefix_cache": dict(prefix_cache=True),
    "draft_model": dict(draft_model="self", num_speculative_tokens=2),
    "num_speculative_tokens": dict(num_speculative_tokens=2),
    "role": dict(role="decode"),
    "cluster_cache": dict(cluster_cache=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED_AT_CONSTRUCTION))
def test_what_needs_a_state_snapshot_is_refused_at_construction(option):
    """Each by name, with the recurrent state's reason; nothing is switched
    off silently."""
    with pytest.raises(ValueError) as refusal:
        _engine(**REFUSED_AT_CONSTRUCTION[option])
    assert option in str(refusal.value) and "recurrent state" in str(refusal.value)


def test_llm_deployment_serves_the_model_through_the_normal_path():
    """The deployment callable builds config-default params through the
    config's own module and hands the refused options on unchanged; no new
    option, no engine mode."""
    from ray_tpu.serve.llm import _LLMServer

    cfg = M.BrumbyConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache=True is refused"):
        _LLMServer(cfg=cfg, continuous=True, n_slots=2)  # prefix_cache defaults to True
    server = _LLMServer(cfg=cfg, continuous=True, n_slots=2, prefix_cache=False, seed=3)
    try:
        want = D.generate(server.params, np.asarray([[5, 6, 7]]), cfg, 12)[0].tolist()
        assert server.engine.generate([5, 6, 7], 12) == want
        assert type(server.engine) is ContinuousBatchingEngine
    finally:
        server.engine.shutdown()
