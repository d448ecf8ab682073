"""The AFMoE decoder (sigmoid-routed experts beside a shared one; sliding-window
layers with a full one between them) against its plain reference, and its
lanes of window rings in the paged engine (ISSUE 33).

CPU, a tiny config with the real shape of things: two leading dense layers,
`layer_types` [s, s, s, f, s] (so every pair of kinds occurs), 16 experts
top-4, GQA 4 / 2, a window of 8, blocks of 4. The reference is
benchmark/reference_afmoe (float32, every expert applied to every row and
weighted, whole score matrices); weights come from the benchmark's seed-made
generator, choice bias included, so nothing compared shares an algorithm.

Tolerances. float32: 1e-4 relative to the largest logit (measured 3e-6: both
sides are float32 and differ by the order of sums: ragged products over sorted
pairs against a weighted sum over all experts, blockwise softmax against
plain). bfloat16: 0.15 absolute on logits of spread 1, at the 80th percentile
over positions of each position's largest error (measured 0.06; the median
0.04): every activation is rounded to 8 bits of mantissa some thirty times on
the way. The percentile and not the maximum, because a top-4 choice flips on a
near-tie between bfloat16 and float32 scores at 6-8 positions in a hundred
here, and a flipped expert moves that position's logits by 0.25 to 1.2: a real
term of the distance between the two precisions, and not an error of either.
The wrong variants below are told apart in float32, where nothing flips.
"""
import dataclasses
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_afmoe as R
from benchmark import weights_afmoe as W
from ray_tpu.models import afmoe as M
from ray_tpu.models import afmoe_decode as D
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.conftest import static_answers

F32_RTOL = 1e-4
BF16_ATOL = 0.15
BLOCK = 4
SEED = 2**31 + 33


@functools.lru_cache(maxsize=4)
def _model(dtype=jnp.float32):
    cfg = M.AfmoeConfig.tiny(dtype=dtype)
    key = W.seed_key(SEED)
    return cfg, key, W.init_params(key, cfg)


def _tokens(n, length, seed=0, vocab=512):
    return np.random.default_rng([seed, length]).integers(0, vocab, (n, length)).astype(np.int32)


def _worst(got, want, dtype):
    """The comparison's error in units of its tolerance, for logits
    (..., V): <= 1 passes."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.abs(got - want).max(-1)  # each position's largest
    if dtype == jnp.float32:
        return err.max() / (F32_RTOL * np.abs(want).max())
    return np.percentile(err, 80) / BF16_ATOL


def _close(got, want, dtype):
    assert _worst(got, want, dtype) <= 1.0


# ------------------------------------------------------------ the config
def test_config_is_hashable_and_names_its_own_modules():
    cfg = M.AfmoeConfig()
    assert hash(cfg) == hash(M.AfmoeConfig()) and cfg.n_layers == 32
    assert (cfg.n_window_layers, cfg.n_full_layers, cfg.n_dense_layers, cfg.n_moe_layers) == (
        24, 8, 2, 30)
    assert [i for i, t in enumerate(cfg.layer_types) if t == M.FULL] == list(range(3, 32, 4))
    assert cfg.model_module is M and cfg.decode_module is D
    # published: 26B parameters, 128 experts of 3 x 2048 x 1024
    assert M.num_params(cfg) == 26_123_974_400


def test_runs_cover_every_layer_once_by_kind():
    """`runs` is how the layer loop finds each layer's parameters: the
    indices among all layers, among its attention kind and among its FFN
    kind advance together, from `layer_types` and `n_dense_layers` alone."""
    cfg = M.AfmoeConfig.tiny()
    assert cfg.runs == ((M.SLIDING, M.DENSE, 0, 0, 0, 2), (M.SLIDING, M.MOE, 2, 2, 0, 1),
                        (M.FULL, M.MOE, 3, 0, 1, 1), (M.SLIDING, M.MOE, 4, 3, 2, 1))
    big = M.AfmoeConfig()
    seen = [(attn, ffn, g0 + i) for attn, ffn, g0, _, _, n in big.runs for i in range(n)]
    assert [g for _, _, g in seen] == list(range(32))
    assert all(attn == big.layer_types[g] and (ffn == M.DENSE) == (g < 2) for attn, ffn, g in seen)
    with pytest.raises(ValueError, match="unknown layer types"):
        M.AfmoeConfig.tiny(layer_types=("attention",) * 5)


# --------------------------------------------- the plain forward (a), (b)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [5, 8, 19, 37])  # under, at and several times past the window
def test_forward_matches_the_reference(T, dtype):
    cfg, key, params = _model(dtype)
    tokens = _tokens(2, T, seed=1)
    got = jax.jit(functools.partial(M.forward, cfg=cfg))(params, jnp.asarray(tokens))
    assert got.dtype == jnp.float32 and got.shape == (2, T, cfg.vocab_size)
    _close(got, R.logits(key, jnp.asarray(tokens), cfg), dtype)


def _all_window_with_rope(cfg):
    """The full layer given RoPE (at T <= window nothing else changes)."""
    return dataclasses.replace(cfg, layer_types=(M.SLIDING,) * cfg.n_layers)


def _bias_in_the_weight(u, router, bias, cfg):
    scores = jax.nn.sigmoid(jnp.einsum("nd,de->ne", u, router)) + bias
    w, chosen = jax.lax.top_k(scores, cfg.top_k)
    return chosen.astype(jnp.int32), w / w.sum(-1, keepdims=True) * cfg.route_scale


MUTATIONS = {
    # name: (T, what is patched on the SYSTEM's side only)
    "window-mask-dropped": (19, dict(attr=("sequence_attention", lambda orig: (
        lambda q, k, v, cfg, window: orig(q, k, v, cfg, None))))),
    "rope-in-a-full-layer": (8, dict(cfg=_all_window_with_rope)),
    "output-gate-dropped": (8, dict(attr=("gated_out", lambda orig: (
        lambda o, gate, layer, cfg: o @ layer["wo"])))),
    "bias-in-the-weight": (8, dict(attr=("route", lambda orig: _bias_in_the_weight))),
    "bias-left-out-of-the-choice": (8, dict(attr=("route", lambda orig: (
        lambda u, router, bias, cfg: orig(u, router, jnp.zeros_like(bias), cfg))))),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_variant_of_the_system_fails_the_comparison(name, monkeypatch):
    """The comparison above is tight enough to tell: each of these variants
    of the program's forward misses the float32 tolerance by a factor of 100
    at least, on the very tokens on which the sound program passes."""
    cfg, key, params = _model()
    T, change = MUTATIONS[name]
    tokens = jnp.asarray(_tokens(2, T, seed=1))
    want = R.logits(key, tokens, cfg)
    _close(M.forward(params, tokens, cfg), want, jnp.float32)
    if "attr" in change:
        attr, make = change["attr"]
        monkeypatch.setattr(M, attr, make(getattr(M, attr)))
    wrong = M.forward(params, tokens, change.get("cfg", lambda c: c)(cfg))
    assert _worst(wrong, want, jnp.float32) > 100.0


# ------------------------------------------------------- the router alone
def _router(seed=0, n=64):
    cfg = M.AfmoeConfig.tiny(dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(n, cfg.d_model)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(cfg.d_model, cfg.n_experts)) / 8.0, jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(cfg.n_experts,)), jnp.float32)
    return cfg, u, router, bias


@pytest.mark.parametrize("case", ["sums-to-route-scale", "bias-moves-the-choice-only",
                                  "no-bias-is-top-k-of-scores", "unnormalised"])
def test_router(case):
    cfg, u, router, bias = _router()
    scores = np.asarray(jax.nn.sigmoid(u @ router))
    chosen, w = (np.asarray(a) for a in M.route(u, router, bias, cfg))
    assert chosen.shape == w.shape == (64, cfg.top_k) and w.dtype == np.float32
    if case == "sums-to-route-scale":
        np.testing.assert_allclose(w.sum(-1), cfg.route_scale, rtol=1e-6)
        assert all(len(set(row)) == cfg.top_k for row in chosen)
    elif case == "bias-moves-the-choice-only":
        plain, _ = M.route(u, router, jnp.zeros_like(bias), cfg)
        assert (np.sort(chosen, -1) != np.sort(np.asarray(plain), -1)).any()  # the choice moved
        want = np.sort(np.argsort(-(scores + np.asarray(bias)), -1)[:, :cfg.top_k], -1)
        np.testing.assert_array_equal(np.sort(chosen, -1), want)
        picked = np.take_along_axis(scores, chosen, -1)  # the weights are the bare scores'
        np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True) * cfg.route_scale,
                                   rtol=1e-6)
    elif case == "no-bias-is-top-k-of-scores":
        plain, _ = M.route(u, router, jnp.zeros_like(bias), cfg)
        want = np.sort(np.argsort(-scores, -1)[:, :cfg.top_k], -1)
        np.testing.assert_array_equal(np.sort(np.asarray(plain), -1), want)
    else:
        raw = dataclasses.replace(cfg, route_norm=False)
        _, w_raw = M.route(u, router, bias, raw)
        np.testing.assert_allclose(np.asarray(w_raw),
                                   np.take_along_axis(scores, chosen, -1) * cfg.route_scale,
                                   rtol=1e-6)


# ----------------------------------------------------- the expert products
def _loop_over_chosen(u, chosen, w, layer_experts, live, first=0):
    """The definition: a NumPy float64 loop over rows and over each row's
    chosen experts (those of `layer_experts`, which start at the router's
    expert `first`); a row that is not live gets nothing and counts in no
    group. Returns (out (N, d), rows an expert (E,))."""
    e64 = jax.tree.map(lambda a: np.asarray(a, np.float64), layer_experts)
    E = e64["w_gate"].shape[0]
    want, count = np.zeros(u.shape, np.float64), np.zeros(E, int)
    for n in range(u.shape[0]):
        for e, w_e in zip(chosen[n] - first, w[n]):
            if 0 <= e < E and (live is None or live[n]):
                g, up = u[n] @ e64["w_gate"][e], u[n] @ e64["w_up"][e]
                want[n] += w_e * ((g / (1.0 + np.exp(-g)) * up) @ e64["w_down"][e])
                count[e] += 1
    return want, count


def _pairs(case, cfg, rng):
    """(chosen (N, top_k), live (N,) or None, chunk) of a case of
    `test_expert_products_...`: what the sorted pairs look like beside the
    chunks' edges. None for the chunk is `expert_ffn`'s own (all of these
    are fewer pairs than that: the straight-line path)."""
    E, k = cfg.n_experts, cfg.top_k
    any_k = lambda n: np.stack([rng.permutation(E)[:k] for _ in range(n)])  # noqa: E731
    if case in ("all-rows", "some-rows-not-live"):  # 7 rows, 28 pairs, at once
        return any_k(7), (None if case == "all-rows" else np.array([1, 0, 1, 1, 0, 1, 1], bool)), None
    if case == "groups-cut-by-chunk-edges":  # 4 groups of 23 pairs, edges at 16, 32, ...
        return np.tile(np.array([2, 5, 6, 11]), (23, 1)), None, 16
    if case == "most-groups-empty":  # and the chunk no divisor of anything
        return np.stack([rng.permutation([3, 4, 9, 15, 0])[:k] for _ in range(19)]), None, 7
    if case == "no-pair-in-a-group":  # no row live: the loop runs no pass
        return any_k(9), np.zeros(9, bool), 8
    if case == "pairs-fill-whole-chunks":  # 8 live rows of 13: 32 pairs, two chunks of 16
        return any_k(13), np.arange(13) % 13 < 8, 16
    if case == "live-among-padded":  # an admission: each row's tail is padding
        return any_k(24), (np.arange(24) % 8) < np.repeat([5, 0, 8], 8), 16
    if case == "one-chunk-past-all-pairs":  # the last chunk reaches past N * top_k
        return any_k(11), None, 32
    raise KeyError(case)


@pytest.mark.parametrize("case", [
    "all-rows", "some-rows-not-live", "groups-cut-by-chunk-edges", "most-groups-empty",
    "no-pair-in-a-group", "pairs-fill-whole-chunks", "live-among-padded",
    "one-chunk-past-all-pairs"])
def test_expert_products_are_a_loop_over_each_rows_chosen_experts(case):
    """`expert_ffn` against the definition (`_loop_over_chosen`); rows that
    are not live get nothing, hit no expert and count in no group. With more
    pairs than a chunk the sorted pairs go through a chunk at a time, as far
    as the pairs in a group reach: the same sums as all pairs at once, with
    groups cut by a chunk's edge, empty groups, no pair at all, pairs that
    fill whole chunks, padding among the rows."""
    cfg, _, params = _model()
    experts = params[M.MOE]["experts"]  # the stack of all three layers'; the second is meant
    rng = np.random.default_rng(5)
    chosen, live, chunk = _pairs(case, cfg, rng)
    N = chosen.shape[0]
    u = rng.normal(size=(N, cfg.d_model)).astype(np.float32)
    w = rng.uniform(0.1, 1.0, size=(N, cfg.top_k)).astype(np.float32)
    args = (jnp.asarray(u), jnp.asarray(chosen.astype(np.int32)), jnp.asarray(w), experts, 1, cfg,
            None if live is None else jnp.asarray(live))
    at_once, sizes = M.expert_ffn(*args)
    want, count = _loop_over_chosen(u, chosen, w, jax.tree.map(lambda a: a[1], experts), live)
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(np.asarray(at_once) - want).max() <= 1e-5 * scale
    np.testing.assert_array_equal(np.asarray(sizes), count)
    if chunk is not None:
        assert N * cfg.top_k > chunk
        got, sizes = jax.jit(lambda *a: M.expert_ffn(*a, args[-1], chunk=chunk),
                             static_argnums=(5,))(*args[:-1])
        assert np.abs(np.asarray(got) - want).max() <= 1e-5 * scale
        assert np.abs(np.asarray(got) - np.asarray(at_once)).max() <= 2e-6 * scale
        np.testing.assert_array_equal(np.asarray(sizes), count)
        if not count.sum():
            assert not np.asarray(got).any()


# --------------------------------------------------------- the window mask
@pytest.mark.parametrize("T,window", [(16, 8), (37, 8), (37, 5), (8, 8), (24, 100)])
def test_window_mask_of_the_blockwise_forward(T, window):
    """ops/blockwise_attention with a window against the whole score matrix
    under the mask 0 <= i - j < window; blocks of 8, so key blocks lie wholly
    behind some rows' windows."""
    from ray_tpu.ops.blockwise_attention import _fwd_impl

    rng = np.random.default_rng(T * 100 + window)
    q, k, v = (jnp.asarray(rng.normal(size=(2, T, h, 16)), jnp.float32) for h in (4, 2, 2))
    got, _ = _fwd_impl(q, k, v, True, 8, None, 0, 0, window)
    kf, vf = (np.repeat(np.asarray(a), 2, axis=2) for a in (k, v))
    s = np.einsum("bthd,bshd->bhts", np.asarray(q), kf) / 4.0
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    s = np.where((j <= i) & (i - j < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhts,bshd->bthd", p / p.sum(-1, keepdims=True), vf)
    assert np.abs(np.asarray(got) - want).max() <= 1e-5


def test_window_mask_of_the_flash_kernel_interpreted():
    """The Pallas forward with a window, interpreted on the CPU, against the
    blockwise forward: blocks of 128 over 512 positions and a window of 200,
    so that whole key blocks are skipped on both sides of the band."""
    from ray_tpu.ops.blockwise_attention import _fwd_impl
    from ray_tpu.ops.flash_attention import _flash_fwd_pallas

    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 512, h, 128)), jnp.float32) for h in (2, 1, 1))
    got, lse = _flash_fwd_pallas(q, k, v, True, None, 128, 128, interpret=True, window=200)
    want, want_lse = _fwd_impl(q, k, v, True, 128, None, 0, 0, 200)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5
    assert np.abs(np.asarray(lse) - np.asarray(want_lse)).max() <= 1e-4


# --------------------------------- admission and decode through the cache
@functools.lru_cache(maxsize=4)
def _jitted_halves(cfg):
    return (jax.jit(functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False)),
            jax.jit(functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False)))


class Lanes:
    """The model's admission and decode step on a paged cache of `n` lanes:
    lane b owns blocks 1 + b * mb .. of the pool."""

    def __init__(self, cfg, params, n=3, span=64, halves=None):
        self.cfg, self.params, self.n = cfg, params, n
        self.mb = span // BLOCK
        self.cache = D.init_paged_cache(cfg, n, n * self.mb + 1, BLOCK)
        self.tables = 1 + jnp.arange(n * self.mb, dtype=jnp.int32).reshape(n, self.mb)
        self.feed = jnp.zeros((n,), jnp.int32)
        z = jnp.zeros((n,), jnp.int32)
        self.plan = dict(temps=jnp.zeros((n,), jnp.float32), top_ks=z,
                         top_ps=jnp.ones((n,), jnp.float32),
                         stop_ids=jnp.full((n, 1), -1, jnp.int32))
        self._admit, self._step = halves or _jitted_halves(cfg)

    def admit(self, rows, bucket, new=8):
        """rows: [(lane, prompt)], one admission row each, `bucket` positions long."""
        A = len(rows)
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


def _through_the_cache(cfg, key, params, halves=None, dtype=jnp.float32):
    """Two prompts, one longer than the window (19 of 8) and one shorter (5)
    that passes it while it decodes; 21 new tokens, so each ring wraps more
    than twice. Returns the worst error, in tolerances, of the decode steps'
    logits against the reference's full forward over prompt + emitted."""
    lanes = Lanes(cfg, params, n=2, halves=halves)
    prompts = [_tokens(1, 19, seed=3)[0], _tokens(1, 5, seed=4)[0]]
    n_new = 21
    first = lanes.admit(list(enumerate(prompts)), bucket=32, new=n_new)
    steps = [lanes.step() for _ in range(n_new - 1)]
    seqs = np.zeros((2, 19 + n_new), np.int32)  # right-padded: causal, so harmless there
    for b, p in enumerate(prompts):
        emitted = [first[b]] + [nxt[b] for _, nxt in steps]
        seqs[b, :len(p) + n_new] = np.concatenate([p, emitted])
    refs = np.asarray(R.logits(key, jnp.asarray(seqs), cfg))
    firsts_agree = all(int(refs[b, len(p) - 1].argmax()) == first[b] for b, p in enumerate(prompts))
    got = np.stack([[logits[b] for logits, _ in steps] for b in range(2)])
    want = np.stack([refs[b, len(p):len(p) + n_new - 1] for b, p in enumerate(prompts)])
    return _worst(got, want, dtype), firsts_agree


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_admission_then_decode_matches_the_reference_at_every_position(dtype):
    """Prefill then decode through the pool and the rings against the
    reference's full forward, logits at every emitted position (the first
    token's too, through the cache it leaves), on contexts several windows
    long."""
    cfg, key, params = _model(dtype)
    worst, firsts_agree = _through_the_cache(cfg, key, params, dtype=dtype)
    assert worst <= 1.0
    assert firsts_agree or dtype != jnp.float32


@pytest.mark.parametrize("name", ["ring-read-one-slot-off", "decode-mask-shows-every-slot",
                                  "admission-keeps-the-first-window"])
def test_a_wrong_ring_fails_the_comparison(name, monkeypatch):
    """The same comparison against three wrong rings: the decode step's
    write one slot off, its mask showing slots that hold no position yet,
    and an admission that keeps a long prompt's FIRST window of positions."""
    cfg, key, params = _model()
    if name == "ring-read-one-slot-off":
        orig = D.write_ring_token
        monkeypatch.setattr(D, "write_ring_token", lambda ring, wi, kv, pos: orig(ring, wi, kv, pos + 1))
    elif name == "decode-mask-shows-every-slot":
        monkeypatch.setattr(D, "ring_slots_held",
                            lambda pos, window: jnp.ones((pos.shape[0], window), bool))
    else:
        monkeypatch.setattr(D, "ring_rows", lambda kv, lengths, window: kv[:, :window])
    halves = (jax.jit(functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False)),
              jax.jit(functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False)))
    worst, _ = _through_the_cache(cfg, key, params, halves=halves)
    assert worst > 100.0


def test_a_window_layers_bytes_a_lane_do_not_grow_with_the_context():
    """The rings are sized by the window alone: the same shape whatever the
    pool and the table span, a constant the engine reports, while the pool
    (the full layer's) follows the blocks."""
    cfg = M.AfmoeConfig.tiny()
    small, large = (D.init_paged_cache(cfg, 3, n_blocks, BLOCK) for n_blocks in (17, 4097))
    ring = (cfg.n_window_layers, 3, cfg.sliding_window, cfg.n_kv_heads * cfg.head_dim)
    assert small["wk"].shape == large["wk"].shape == small["wv"].shape == ring
    assert small["k"].shape[0] == cfg.n_full_layers == 1 and large["k"].shape[1] == 4097
    per_lane = sum(small[k].nbytes for k in ("wk", "wv")) // 3
    assert D.state_bytes_per_lane(cfg) == per_lane == 4 * 2 * 8 * 32 * 2
    big = M.AfmoeConfig()  # published: 24 window layers x 2 x 2048 positions x 1 KB
    assert D.state_bytes_per_lane(big) == 24 * 2 * 2048 * 1024
    assert D.state_bytes_per_lane(dataclasses.replace(big, max_seq_len=8192)) == \
        D.state_bytes_per_lane(big)


def test_ring_rows_hold_the_last_window_of_a_prompt():
    kv = jnp.arange(2 * 20, dtype=jnp.float32).reshape(2, 20, 1)  # row 1: 20 + position
    rows = np.asarray(D.ring_rows(kv, jnp.asarray([19, 5]), 8))[..., 0]
    # 19 positions, window 8: positions 11..18, each at its slot (p % 8)
    assert sorted(rows[0]) == list(range(11, 19)) and all(rows[0][p % 8] == p for p in range(11, 19))
    # 5 positions: slots 0..4 hold them, the rest anything (never shown)
    np.testing.assert_array_equal(rows[1][:5], 20 + np.arange(5))


# ------------------------------------------------------------- the engine
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


def test_engine_serves_more_requests_than_lanes_and_counts_what_it_routed(tmp_path):
    """Mixed lengths through three lanes, contexts up to six windows long:
    greedy tokens equal the static `generate`; the device counters the
    macro-step hands back are summed in `metrics()` and written on each
    dispatch's `engine.resolve` span, the plan's window count on
    `engine.dispatch`, and the spans' sums are the metrics' own."""
    cfg, _, params = _model()
    eng = _engine()
    try:
        lengths, answers = (9, 30, 21, 9, 30, 21, 5), (6, 20, 11, 11, 6, 6, 1)
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
    events = [(e.name, dict(e.stats)) for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events if e.name.startswith("engine.")]
    dispatches = [st for name, st in events if name == "engine.dispatch"]
    resolves = [st for name, st in events if name == "engine.resolve"]
    moved = {k: m1[k] - m0[k] for k in D.DEVICE_COUNTERS + ("past_window_lane_steps",
                                                            "useful_slot_steps")}
    lane_steps = moved["useful_slot_steps"]
    assert lane_steps == sum(n - 1 for n in answers)
    # top_k pairs a live row in each of the three expert layers, and no others
    assert moved["expert_rows"] == lane_steps * cfg.top_k * cfg.n_moe_layers
    assert moved["expert_rows"] >= moved["experts_hit"] >= moved["expert_rows_max"] > 0
    for key in D.DEVICE_COUNTERS:
        assert sum(int(st[key]) for st in resolves) == moved[key]
    assert sorted(int(st["seq"]) for st in resolves) == sorted(int(st["seq"]) for st in dispatches)
    assert sum(int(st["past_window_lane_steps"]) for st in dispatches) == \
        moved["past_window_lane_steps"]
    # a context passes the window of 8 from its first decode step on, but for
    # the prompt of 5: its 0 decode steps; so every live lane-step is past it
    assert moved["past_window_lane_steps"] == lane_steps
    assert m1["state_bytes"] == D.state_bytes_per_lane(cfg) > 0


def test_past_window_lane_steps_counts_from_the_plan():
    """A prompt of 3 and 12 new tokens at a window of 8: the decode steps
    feed positions 3..13, contexts 4..14, and 6 of the 11 are longer than 8."""
    eng = _engine(n_slots=1)
    try:
        m0 = eng.metrics()
        eng.generate([1, 2, 3], 12)
        m1 = eng.metrics()
    finally:
        eng.shutdown()
    assert m1["useful_slot_steps"] - m0["useful_slot_steps"] == 11
    assert m1["past_window_lane_steps"] - m0["past_window_lane_steps"] == 6


REFUSED_AT_CONSTRUCTION = {
    "prefix_cache": dict(prefix_cache=True),
    "draft_model": dict(draft_model="self", num_speculative_tokens=2),
    "num_speculative_tokens": dict(num_speculative_tokens=2),
    "role": dict(role="decode"),
    "cluster_cache": dict(cluster_cache=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED_AT_CONSTRUCTION))
def test_what_needs_a_ring_snapshot_is_refused_at_construction(option):
    """Each by name, with the reason; nothing is switched off silently."""
    with pytest.raises(ValueError) as refusal:
        _engine(**REFUSED_AT_CONSTRUCTION[option])
    assert option in str(refusal.value) and "window layer's ring" in str(refusal.value)


def test_llm_deployment_serves_the_model_through_the_normal_path():
    """The deployment callable builds config-default params through the
    config's own module and hands the refused options on unchanged; no new
    option, no engine mode."""
    from ray_tpu.serve.llm import _LLMServer

    cfg = M.AfmoeConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache=True is refused"):
        _LLMServer(cfg=cfg, continuous=True, n_slots=2)  # prefix_cache defaults to True
    server = _LLMServer(cfg=cfg, continuous=True, n_slots=2, prefix_cache=False, seed=3)
    try:
        want = D.generate(server.params, np.asarray([[5, 6, 7]]), cfg, 12)[0].tolist()
        assert server.engine.generate([5, 6, 7], 12) == want
        assert type(server.engine) is ContinuousBatchingEngine
    finally:
        server.engine.shutdown()


def test_other_models_dispatches_carry_no_window_or_routing_counts():
    from ray_tpu.models import llama, llama_decode
    from ray_tpu.serve.llm_engine import _dispatch_counts

    assert not hasattr(llama_decode, "DEVICE_COUNTERS")
    assert not hasattr(llama.LlamaConfig.tiny(), "sliding_window")
    assert "past_window_lane_steps" not in _dispatch_counts([], False, 16)
    assert _dispatch_counts([], False, 16, window=8)["past_window_lane_steps"] == 0
