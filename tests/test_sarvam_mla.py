"""The latent-attention decoder (MLA with a cached row [c | RoPE(k_r)], YaRN,
sigmoid-routed experts of which the program holds a part) against its plain
reference, and its latent block pool in the paged engine (ISSUE 39).

CPU, a tiny config with the real shape of things: a leading dense layer and
two expert layers, 4 heads of 16 + 8 over a latent of 32, 16 experts top-4 of
which experts 4-7 are held, YaRN at factor 8 over an original span of 32,
blocks of 4. The reference is benchmark/reference_sarvam_mla (float32, every
key and value expanded from the latent for every position, every held expert
applied to every row and weighted, whole score matrices); weights come from
the benchmark's seed-made generator, choice bias included, so nothing
compared shares an algorithm.

Tolerances as tests/test_afmoe.py has them and for its reasons. float32: 1e-4
relative to the largest logit (measured 2e-6). bfloat16: 0.15 absolute on
logits of spread 1 at the 80th percentile over positions of each position's
largest error (a top-4 choice flips on a near-tie at a few positions in a
hundred). The wrong variants are told apart in float32, where nothing flips.
"""
import dataclasses
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_sarvam_mla as R
from benchmark import weights_sarvam_mla as W
from ray_tpu.models import afmoe
from ray_tpu.models import paged
from ray_tpu.models import sarvam_mla as M
from ray_tpu.models import sarvam_mla_decode as D
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.conftest import latent_decode_steps_by_each_reader, static_answers

F32_RTOL = 1e-4
BF16_ATOL = 0.15
BLOCK = 4
SEED = 2**31 + 39


@functools.lru_cache(maxsize=4)
def _model(dtype=jnp.float32):
    cfg = M.SarvamMlaConfig.tiny(dtype=dtype)
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


# ------------------------------------------------------------ the config
def test_config_is_hashable_and_names_its_own_modules():
    cfg = M.SarvamMlaConfig()
    assert hash(cfg) == hash(M.SarvamMlaConfig()) and cfg.held_experts == (0, 128)
    assert (cfg.q_head_dim, cfg.latent_row, D.pool_row(cfg)) == (192, 576, 640)
    assert abs(cfg.sm_scale - 0.13523) < 1e-5  # 192^-0.5 x (0.1 ln 40 + 1)^2
    assert cfg.model_module is M and cfg.decode_module is D
    assert M.num_params(cfg) == 106_031_775_616  # published: 105B
    # the benchmark's generator makes the program's tree, held experts only
    tiny = M.SarvamMlaConfig.tiny()
    shapes = lambda init: jax.tree.map(lambda a: (a.shape, a.dtype),  # noqa: E731
                                       jax.eval_shape(lambda: init(jax.random.PRNGKey(0), tiny)))
    assert shapes(M.init_params) == shapes(W._init)
    assert shapes(M.init_params)[M.MOE]["experts"]["w_up"][0] == (2, 4, 64, 32)
    with pytest.raises(ValueError, match="held experts"):
        M.SarvamMlaConfig.tiny(held_first=14, held_count=4)


# ----------------------------------------------------------- the forward
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [9, 37])  # inside and past YaRN's original span of 32
def test_forward_matches_the_reference(T, dtype):
    cfg, key, params = _model(dtype)
    tokens = _tokens(2, T, seed=1)
    got = jax.jit(functools.partial(M.forward, cfg=cfg))(params, jnp.asarray(tokens))
    assert got.dtype == jnp.float32 and got.shape == (2, T, cfg.vocab_size)
    assert _worst(got, R.logits(key, jnp.asarray(tokens), cfg), dtype) <= 1.0


def test_the_forward_in_pieces_of_rows_is_the_forward(monkeypatch):
    """An admission wider than ATTN_TOKENS goes through the mixer a few rows
    at a time: the same logits, whatever the split."""
    cfg, _, params = _model()
    tokens = jnp.asarray(_tokens(4, 16, seed=2))
    whole = M.forward(params, tokens, cfg)
    monkeypatch.setattr(M, "ATTN_TOKENS", 32)  # two rows a piece
    pieces = M.forward(params, tokens, cfg)
    assert np.abs(np.asarray(pieces) - np.asarray(whole)).max() <= 1e-5 * np.abs(whole).max()


def test_absorbed_attention_is_the_expanded_attention_in_float32():
    """One layer's attention over the same cached rows both ways: every
    head's keys and values expanded from c, against W_uk absorbed into the
    query and W_uv applied to the attended latent."""
    cfg, _, params = _model()
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    T = 21
    a = jnp.asarray(np.random.default_rng(3).normal(size=(1, T, cfg.d_model)), jnp.float32)
    cos, sin = M.rope_tables(cfg, T)
    q_nope, q_rope, row = M.project(layer, a, cos, sin, None, cfg)
    expanded = M.expanded_attention(q_nope, q_rope, row, layer, cfg)[0]      # (T, h * v)
    r = cfg.kv_lora_rank
    q = jnp.concatenate([M.absorb_q(layer, q_nope[0]), q_rope[0]], axis=-1)  # (T, h, latent_row)
    s = jnp.einsum("thc,jc->htj", q, row[0]) * cfg.sm_scale
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    absorbed = M.absorbed_out(layer, jnp.einsum("htj,jc->thc", p, row[0, :, :r]), cfg)
    assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() <= 1e-5 * np.abs(expanded).max()


def test_the_flash_kernel_with_a_shared_key_part_interpreted():
    """The Pallas forward with the second score product (128-wide own keys,
    a 64-wide part shared by the heads, values of their own size),
    interpreted on the CPU, against the blockwise forward over the
    concatenated keys."""
    from ray_tpu.ops.blockwise_attention import _fwd_impl
    from ray_tpu.ops.flash_attention import _flash_fwd_pallas

    rng = np.random.default_rng(9)
    make = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q, k, v = make(2, 256, 3, 128), make(2, 256, 3, 128), make(2, 256, 3, 128)
    q2, k2 = make(2, 256, 3, 64), make(2, 256, 64)
    got, lse = _flash_fwd_pallas(q, k, v, True, 0.07, 128, 128, interpret=True,
                                 q_shared=q2, k_shared=k2)
    k_cat = jnp.concatenate([k, jnp.broadcast_to(k2[:, :, None, :], (2, 256, 3, 64))], axis=-1)
    want, want_lse = _fwd_impl(jnp.concatenate([q, q2], -1), k_cat, v, True, 128, 0.07, 0, 0)
    assert got.shape == (2, 256, 3, 128)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5
    assert np.abs(np.asarray(lse) - np.asarray(want_lse)).max() <= 1e-4


# ------------------------------------------------------- the router alone
@pytest.mark.parametrize("case", ["sums-to-route-scale", "bias-moves-the-choice-only"])
def test_router(case):
    """`afmoe.route` under this config: 2.5 a row, and a bias in the choice
    alone."""
    cfg = M.SarvamMlaConfig.tiny(dtype=jnp.float32)
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(64, cfg.d_model)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(cfg.d_model, cfg.n_experts)) / 8.0, jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(cfg.n_experts,)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(u @ router))
    chosen, w = (np.asarray(a) for a in afmoe.route(u, router, bias, cfg))
    if case == "sums-to-route-scale":
        assert cfg.route_scale == 2.5
        np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    else:
        plain, _ = afmoe.route(u, router, jnp.zeros_like(bias), cfg)
        assert (np.sort(chosen, -1) != np.sort(np.asarray(plain), -1)).any()
        picked = np.take_along_axis(scores, chosen, -1)
        np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True) * 2.5, rtol=1e-6)


# -------------------------------------------------- the held share of experts
def _expert_layer_params(cfg, key, at=0):
    """`moe_ffn`'s params of expert layer `at`, from the benchmark's generator."""
    moe = W.init_params(key, cfg)[M.MOE]
    own = {k: v for k, v in moe.items() if k != "experts"}
    return {**jax.tree.map(lambda a: a[at], own), "experts": moe["experts"], "at": at}


@pytest.mark.parametrize("chunk", [None, 8, 5], ids=["at-once", "chunks-of-8", "chunks-of-5"])
@pytest.mark.parametrize("live", [None, (True, False, True, True, False, True, True)],
                         ids=["all-rows", "some-rows-not-live"])
def test_held_expert_products_are_a_loop_over_the_held_chosen_experts(live, chunk):
    """`expert_ffn` with a held range against the definition, a NumPy float64
    loop over rows and each row's chosen experts that are HELD: a pair whose
    expert is elsewhere adds nothing and counts in no group. In chunks of
    the sorted pairs (28 pairs, about a quarter of them held: the loop ends
    where the held pairs do) the sums are the same."""
    cfg, _, params = _model()
    first, count = cfg.held_experts
    experts = params[M.MOE]["experts"]
    rng = np.random.default_rng(5)
    u = rng.normal(size=(7, cfg.d_model)).astype(np.float32)
    chosen = np.stack([rng.permutation(cfg.n_experts)[:cfg.top_k] for _ in range(7)]).astype(np.int32)
    w = rng.uniform(0.1, 1.0, size=(7, cfg.top_k)).astype(np.float32)
    mask = None if live is None else jnp.asarray(live)
    got, sizes = afmoe.expert_ffn(jnp.asarray(u), jnp.asarray(chosen), jnp.asarray(w), experts, 1,
                                  cfg, mask, **({} if chunk is None else {"chunk": chunk}))
    e64 = jax.tree.map(lambda a: np.asarray(a[1], np.float64), experts)
    want, rows = np.zeros((7, cfg.d_model)), np.zeros(count, int)
    for n in range(7):
        for e, w_e in zip(chosen[n] - first, w[n]):
            if 0 <= e < count and (live is None or live[n]):
                g, up = u[n] @ e64["w_gate"][e], u[n] @ e64["w_up"][e]
                want[n] += w_e * ((g / (1.0 + np.exp(-g)) * up) @ e64["w_down"][e])
                rows[e] += 1
    assert rows.sum() < 7 * cfg.top_k  # some pairs are elsewhere
    assert np.abs(np.asarray(got) - want).max() <= 1e-5 * np.abs(want).max()
    np.testing.assert_array_equal(np.asarray(sizes), rows)


@pytest.mark.parametrize("chunk", [None, 16], ids=["at-once", "chunks-of-16"])
def test_the_four_shares_add_up_to_the_whole_layer(chunk, monkeypatch):
    """Four programs that each hold a quarter of a layer's experts: their
    routed parts plus the shared expert counted once are the uncut
    reference's whole expert layer (router weights normalised over all the
    chosen, held or not; an expert's matrices keyed by its index among the
    router's experts). 23 rows are 92 pairs: in chunks of 16 each share's
    loop passes over its own quarter of them."""
    if chunk is not None:
        monkeypatch.setattr(afmoe, "expert_ffn", functools.partial(afmoe.expert_ffn, chunk=chunk))
    key = W.seed_key(SEED)
    whole = M.SarvamMlaConfig.tiny(dtype=jnp.float32, held_first=0, held_count=16)
    m = jnp.asarray(np.random.default_rng(7).normal(size=(23, whole.d_model)), jnp.float32)
    k_moe = W.part_keys(key, whole)[4][0]
    want = R.expert_layer(m, k_moe, whole)
    total = 0.0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(whole, held_first=first, held_count=4)
        p = _expert_layer_params(share, key)
        total = total + afmoe.moe_ffn(m, p, share)[0] - afmoe.swiglu(m, p["shared"], share)
        # each share alone is the reference of that share
        own = np.asarray(R.expert_layer(m, k_moe, share))
        assert np.abs(np.asarray(afmoe.moe_ffn(m, p, share)[0]) - own).max() <= 1e-5 * np.abs(own).max()
    total = total + afmoe.swiglu(m, p["shared"], whole)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() <= 1e-5 * np.abs(want).max()
    # and the shares differ: no share is the whole
    assert np.abs(own - np.asarray(want)).max() > 1e-2 * np.abs(want).max()


# --------------------------------- admission and decode through the cache
@functools.lru_cache(maxsize=4)
def _jitted_halves(cfg):
    return (jax.jit(functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False)),
            jax.jit(functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False)))


class Lanes:
    """The model's admission and decode step on a paged cache of `n` lanes:
    lane b owns blocks 1 + b * mb .. of the pool."""

    def __init__(self, cfg, params, n=2, span=64, halves=None):
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
    """Two prompts (19 and 5 tokens) and 21 new tokens each, so the contexts
    pass YaRN's original span of 32 while they decode. Returns the worst
    error, in tolerances, of the decode steps' logits against the reference's
    full forward over prompt + emitted."""
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
    """The expanded admission writes the latent rows; the absorbed decode
    step reads them: logits at every emitted position against the reference's
    full forward (which expands every position and caches nothing)."""
    cfg, key, params = _model(dtype)
    worst, firsts_agree = _through_the_cache(cfg, key, params, dtype=dtype)
    assert worst <= 1.0
    assert firsts_agree or dtype != jnp.float32
    # one pool, one row a position and layer, no K and no V
    cache = D.init_paged_cache(cfg, 2, 9, BLOCK)
    assert "k" not in cache and "v" not in cache
    assert cache["latent"].shape == (cfg.n_layers, 9, BLOCK, D.pool_row(cfg))
    assert D.state_bytes_per_lane(cfg) == 0 and D.LATENT_POOL


def test_decode_mixer_takes_the_kernel_where_it_engages_and_the_loop_elsewhere(monkeypatch):
    """Where `engages` says so (patched; the TPU interpret mode takes any
    shape) every layer's decode attention is the single-pool form of the
    kernel of ops/paged_decode_attention.py, and six steps' logits and the
    pool's written rows are the definition's (the same chunks in the same
    order under the same online softmax: float32's last digits). On a TPU with
    this pool of blocks of 4, which the tiles do not take, the definition runs
    and the kernel is not called: the CPU's bits."""
    cfg, _, params = _model()
    ways, seen = latent_decode_steps_by_each_reader(
        Lanes, D, cfg, params, [_tokens(1, 19, seed=3)[0], _tokens(1, 5, seed=4)[0]], monkeypatch)
    # traced once a layer loop (the dense layer, the rolled expert layers): ONE pool, values the latent's columns
    assert seen and set(seen) == {((2, cfg.n_heads, D.pool_row(cfg)), None, cfg.kv_lora_rank)}
    (logits, pool), (k_logits, k_pool) = ways["loop"], ways["kernel"]
    assert np.abs(pool).max() > 0 and np.abs(k_logits - logits).max() <= 1e-5 * np.abs(logits).max()
    np.testing.assert_allclose(k_pool, pool, rtol=1e-5, atol=1e-5 * np.abs(pool).max())
    np.testing.assert_array_equal(ways["tiles-refuse"][0], logits)
    np.testing.assert_array_equal(ways["tiles-refuse"][1], pool)


def _no_rope_on_the_shared_key(orig):
    return lambda x, cos, sin, positions=None: x if x.shape[2] == 1 else orig(x, cos, sin, positions)


def _values_from_every_column(orig):
    """The decode attention's values taken from the whole row: the rotary
    columns leak into the latent's first columns."""
    def attend(q, pool, v_full, li, tables, pos, active, scale, v_cols=0):
        row = pool.shape[-1]
        o = orig(q, pool, v_full, li, tables, pos, active, scale, v_cols=row)
        o = o.reshape(q.shape[0], q.shape[1], row)
        tail = min(row - v_cols, v_cols)
        return o[..., :v_cols].at[..., :tail].add(o[..., v_cols:v_cols + tail]).reshape(q.shape[0], -1)
    return attend


def _bias_in_the_weight(orig):
    def route(u, router, bias, cfg):
        scores = jax.nn.sigmoid(jnp.einsum("nd,de->ne", u, router)) + bias
        w, chosen = jax.lax.top_k(scores, cfg.top_k)
        return chosen.astype(jnp.int32), w / w.sum(-1, keepdims=True) * cfg.route_scale
    return route


def _absent_pairs_computed(orig):
    """A pair whose expert is elsewhere goes through a held expert anyway."""
    def expert_ffn(u, chosen, w, experts, at, cfg, live=None):
        all_held = dataclasses.replace(cfg, n_experts=cfg.held_count, held_first=0, top_k=1)
        return orig(u, chosen % cfg.held_count, w, experts, at, all_held, live)
    return expert_ffn


MUTATIONS = {
    # name: (module, attribute, wrong version of it), on the SYSTEM's side only
    "no-yarn-in-the-softmax-scale": (M.SarvamMlaConfig, "sm_scale",
                                     lambda orig: property(lambda c: c.q_head_dim ** -0.5)),
    "no-rope-on-the-shared-key": (M, "apply_rope", _no_rope_on_the_shared_key),
    "values-from-all-the-row's-columns": (paged, "attend_decode_paged", _values_from_every_column),
    "bias-in-the-weight": (afmoe, "route", _bias_in_the_weight),
    "an-absent-expert's-pair-computed": (afmoe, "expert_ffn", _absent_pairs_computed),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_variant_of_the_system_fails_the_comparison(name, monkeypatch):
    """The comparison through the cache is tight enough to tell: each of
    these variants misses the float32 tolerance by a factor of 50 at least
    (the bias in the weights by 72: the generator's bias is small, 0.005;
    the others by hundreds), on the very tokens on which the sound program
    passes."""
    cfg, key, params = _model()
    module, attr, make = MUTATIONS[name]
    monkeypatch.setattr(module, attr, make(getattr(module, attr)))
    halves = (functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False),
              functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False))
    worst, _ = _through_the_cache(cfg, key, params, halves=tuple(map(jax.jit, halves)))
    assert worst > 50.0


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


def test_engine_serves_more_requests_than_lanes_and_its_spans_sum_to_its_counters(tmp_path):
    """Mixed lengths through three lanes: greedy tokens equal the static
    `generate`; the plan's `ctx_tokens` and `prompt_pairs` on each
    `engine.dispatch` span and the device's counts of HELD experts on each
    `engine.resolve` span sum to `metrics()`' own, and to what the requests'
    lengths say they must be."""
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
    moved = {k: m1[k] - m0[k] for k in D.DEVICE_COUNTERS + ("ctx_tokens", "prompt_pairs",
                                                            "useful_slot_steps")}
    lane_steps = moved["useful_slot_steps"]
    assert lane_steps == sum(n - 1 for n in answers)
    # a request of n prompt tokens attends n + 1 .. n + k - 1 in its k - 1 decode steps
    assert moved["ctx_tokens"] == sum(sum(range(n + 1, n + k)) for n, k in zip(lengths, answers))
    assert moved["prompt_pairs"] == sum(n * (n + 1) // 2 for n in lengths)
    for key in ("ctx_tokens", "prompt_pairs"):
        assert sum(int(st[key]) for st in dispatches) == moved[key]
    # held experts only: fewer than top_k pairs a live row and expert layer
    assert 0 < moved["expert_rows"] < lane_steps * cfg.top_k * cfg.n_moe_layers
    assert moved["expert_rows"] >= moved["experts_hit"] >= moved["expert_rows_max"] > 0
    for key in D.DEVICE_COUNTERS:
        assert sum(int(st[key]) for st in resolves) == moved[key]
    assert sorted(int(st["seq"]) for st in resolves) == sorted(int(st["seq"]) for st in dispatches)
    assert m1["state_bytes"] == 0 and "past_window_lane_steps" not in dispatches[0]


REFUSED_AT_CONSTRUCTION = {
    "prefix_cache": dict(prefix_cache=True),
    "draft_model": dict(draft_model="self", num_speculative_tokens=2),
    "num_speculative_tokens": dict(num_speculative_tokens=2),
    "role": dict(role="decode"),
    "cluster_cache": dict(cluster_cache=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED_AT_CONSTRUCTION))
def test_what_needs_a_k_and_a_v_pool_is_refused_at_construction(option):
    """Each by name, with the latent pool's reason (not the recurrent
    state's); nothing is switched off silently."""
    with pytest.raises(ValueError) as refusal:
        _engine(**REFUSED_AT_CONSTRUCTION[option])
    assert option in str(refusal.value) and "one pool of latent rows" in str(refusal.value)
    assert "recurrent state" not in str(refusal.value)


def test_llm_deployment_serves_the_model_through_the_normal_path():
    """The deployment callable builds config-default params through the
    config's own module and hands the refused options on unchanged; no new
    option, no engine mode."""
    from ray_tpu.serve.llm import _LLMServer

    cfg = M.SarvamMlaConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache=True is refused"):
        _LLMServer(cfg=cfg, continuous=True, n_slots=2)  # prefix_cache defaults to True
    server = _LLMServer(cfg=cfg, continuous=True, n_slots=2, prefix_cache=False, seed=3)
    try:
        want = D.generate(server.params, np.asarray([[5, 6, 7]]), cfg, 12)[0].tolist()
        assert server.engine.generate([5, 6, 7], 12) == want
        assert type(server.engine) is ContinuousBatchingEngine
    finally:
        server.engine.shutdown()


def test_other_models_dispatches_and_refusals_are_what_they_were():
    from ray_tpu.models import afmoe_decode, granite_hybrid_decode, llama_decode
    from ray_tpu.serve.llm_engine import _dispatch_counts, _refuse_what_reuses_kv_blocks

    for module in (llama_decode, granite_hybrid_decode, afmoe_decode):
        assert not getattr(module, "LATENT_POOL", False)
    # every model's dispatch: what it carried, and the two plan-only counts
    assert set(_dispatch_counts([], False, 16)) == {
        "phases", "steps", "admissions", "prompt_tokens", "prefix_tokens", "lane_steps",
        "finishing", "finish_wait_steps", "ctx_chunks", "ctx_tokens", "prompt_pairs",
        "admit_rows", "admit_pieces",
        # the wait and lead accounts of every model's dispatch (PR 41); the lane
        # account's three keys come with an engine's `n_slots`
        "admit_phases", "plan_wait_us", "lane_wait_us", "admitted_first_plan",
        "admit_lead_steps", "admit_lead_phases", "stall_lane_phases",
        # whether a vacant lane closed the plan, and the quantum it decoded by (PR 47)
        "short", "q"}
    assert set(_dispatch_counts([], False, 16, n_slots=4)) - set(_dispatch_counts([], False, 16)) == {
        "vacant_lane_steps", "blocked_lane_steps", "spent_lane_steps"}
    assert set(_dispatch_counts([], True, 16, window=8)) - set(_dispatch_counts([], False, 16)) == {
        "state_lanes", "past_window_lane_steps"}
    with pytest.raises(ValueError, match="recurrent state"):
        _refuse_what_reuses_kv_blocks(False, prefix_cache=True)
    _refuse_what_reuses_kv_blocks(True)  # nothing asked, nothing refused
    assert afmoe.AfmoeConfig.tiny().held_experts == (0, 16)
