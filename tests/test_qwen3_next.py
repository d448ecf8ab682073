"""The Qwen3-Next decoder (gated-delta-rule linear attention, a gated
full-attention layer every fourth, softmax-routed experts of which the program
holds a part beside a gated shared one) against its plain reference, and its
matrix state in the paged engine (ISSUE 43).

CPU, a tiny config with the real shape of things: L L L A L, two value heads
a key head, half of an attention head rotary, 16 experts top-4 of which
experts 4-7 are held, a chunk of 8, blocks of 4. The reference is
benchmark/reference_qwen3_next (float32, the recurrence one position at a
time, four explicit conv taps, whole score matrices, every held expert applied
to every row and weighted); weights come from the benchmark's seed-made
generator, so nothing compared shares an algorithm.

Tolerances. float32: 1e-4 relative to the largest logit (measured 1e-5), as
tests/test_sarvam_mla.py has it: the algebra of both forms is held there.
bfloat16 guards against gross faults only: 0.25 on the median over positions
of a position's r.m.s. error over the vocabulary, logits of spread 1 (measured
0.03 to 0.13 over T = 5..64 and three seeds). The other models' measure, each
position's LARGEST error at the 80th percentile, reads 0.2 to 1.4 here and
tells nothing: one logit in 512 is off by 2 to 4 wherever a top-4 choice
flipped in one of five expert layers, and the linear layers carry the
activations' rounding further than attention does (heads of 8 entries under an
L2 norm, a decay read through softplus and exp and multiplied up over the
positions; with the linear layers taken out the same measure reads 0.04). It
is the activations' rounding, not the chunked form's: with the chunked rule's
operands kept in float32 the readings are the same. At the published head size
the chunked form's rounding (0.41 % of the output's r.m.s.) stands beside the
activations' own (0.45 %) and under an int8 rounding of the two input
projections (1.3 %): PERF.md section 6.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark import reference_qwen3_next as R
from benchmark import weights_qwen3_next as W
from ray_tpu.models import afmoe, sarvam_mla
from ray_tpu.models import qwen3_next as M
from ray_tpu.models import qwen3_next_decode as D
from ray_tpu.ops import ssm_update as SU
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.conftest import static_answers

F32_RTOL = 1e-4
BF16_RMS = 0.25
BLOCK = 4
SEED = 2**31 + 43
# widths the decode-side kernel's tiles take (ops/ssm_update.supported)
KERNEL_WIDTHS = (("lin_k_heads", 8), ("lin_v_heads", 16), ("lin_v_dim", 128))


@functools.lru_cache(maxsize=8)
def _model(dtype=jnp.float32, widths=()):
    cfg = M.Qwen3NextConfig.tiny(dtype=dtype, **dict(widths))
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
    cfg = M.Qwen3NextConfig()
    assert hash(cfg) == hash(M.Qwen3NextConfig()) and cfg.model_module is M and cfg.decode_module is D
    assert cfg.layer_types[:8] == (M.LINEAR,) * 3 + (M.FULL,) + (M.LINEAR,) * 3 + (M.FULL,)
    assert (cfg.n_linear_layers, cfg.n_full_layers) == (36, 12)
    assert (cfg.conv_dim, cfg.lin_d_inner, cfg.rotary_dim) == (8192, 4096, 64)
    assert cfg.held_experts == (0, 512) and cfg.route_scoring == "softmax"
    # a lane and layer: a float32 (32, 128, 128) state and a 3 x 8192 conv tail
    assert D.state_bytes_per_lane(cfg) == 36 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    tiny = M.Qwen3NextConfig.tiny()
    shapes = lambda init: jax.tree.map(lambda a: (a.shape, a.dtype),  # noqa: E731
                                       jax.eval_shape(lambda: init(jax.random.PRNGKey(0), tiny)))
    assert shapes(M.init_params) == shapes(W._init)
    assert tiny.runs == ((M.LINEAR, 0, 0, 3), (M.FULL, 0, 3, 1), (M.LINEAR, 3, 4, 1))
    with pytest.raises(ValueError, match="range of the router"):
        M.Qwen3NextConfig.tiny(held_first=14, held_count=4)


# ------------------------------------------- (a) the forward and the reference
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [5, 8, 21])  # under, at and across chunk boundaries
def test_forward_matches_the_reference(T, dtype):
    cfg, key, params = _model(dtype)
    tokens = _tokens(2, T, seed=1)
    got = M.forward(params, jnp.asarray(tokens), cfg)
    assert got.dtype == jnp.float32 and got.shape == (2, T, cfg.vocab_size)
    assert _worst(got, R.logits(key, jnp.asarray(tokens), cfg), dtype) <= 1.0


def test_the_linear_mixer_in_pieces_of_rows_is_the_mixer(monkeypatch):
    """A long admission's rows go through the linear mixer in pieces
    (`LIN_TOKENS`): the same output, tails and states, ragged lengths."""
    cfg, key, params = _model()
    layer = jax.tree.map(lambda a: a[1], params[M.LINEAR])
    a = jnp.asarray(np.random.default_rng(3).normal(size=(4, 16, cfg.d_model)), jnp.float32)
    lengths = jnp.asarray([16, 9, 0, 3], jnp.int32)
    want = M.linear_sequence(layer, a, lengths, cfg)
    monkeypatch.setattr(M, "LIN_TOKENS", 32)  # two rows a piece
    for w, g in zip(want, M.linear_sequence(layer, a, lengths, cfg)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5)


# ------------------------------------------------ (b) two forms, one result
def _rule_inputs(R_, T, Hk, K, H, V, lengths, seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = M._l2norm(jax.random.normal(ks[0], (R_, T, Hk, K))) * K ** -0.5
    k = M._l2norm(jax.random.normal(ks[1], (R_, T, Hk, K)))
    v = jax.random.normal(ks[2], (R_, T, H, V))
    g = -jax.random.uniform(ks[3], (R_, T, H)) * 0.7
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (R_, T, H)))
    real = (jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None])[:, :, None]
    return q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)


# (T, chunk, lengths): the first six with `T, T - 7, 0`; then what splitting
# the chunk's work from the loop's could break: a sequence shorter than one
# chunk, a length that is no multiple of the chunk, one chunk exactly, many
# chunks, a first row that is all padding, every row ending inside another chunk
RULE_CASES = [(T, chunk, (T, max(T - 7, 1), 0))
              for T, chunk in [(5, 8), (8, 8), (21, 8), (32, 8), (21, 64), (19, 5)]] + [
    (3, 8, (3, 1, 0)), (1, 8, (1, 0, 1)), (13, 4, (13, 6, 0)), (8, 8, (8, 8, 0)),
    (64, 64, (64, 33, 0)), (40, 4, (0, 40, 17)), (27, 8, (9, 27, 0)), (16, 16, (5, 0, 16))]


@pytest.fixture(params=["one group", "groups of two chunks"])
def chunk_groups(request, monkeypatch):
    """`gdn_chunked` prepares its chunks a group at a time (`GROUP_TOKENS`):
    at these sizes all of them in one, or, with the constant set for the
    case, two chunks of the three rows a group (an odd count of chunks then
    ends in a chunk of padding)."""
    def set_for(chunk):
        if request.param != "one group":
            monkeypatch.setattr(M, "GROUP_TOKENS", 3 * 2 * chunk)
    return set_for


@pytest.mark.parametrize("T,chunk,lengths", RULE_CASES)
def test_gdn_chunked_is_gdn_step_iterated(T, chunk, lengths, chunk_groups):
    """Float32, three rows of ragged lengths, one of them all padding (length
    0): the chunked form's outputs at every real position and its final
    states are the one-position form's, iterated; the padded row's state
    stays zero, bit for bit."""
    chunk_groups(min(chunk, T))
    q, k, v, g, beta = _rule_inputs(3, T, 2, 8, 4, 8, lengths)
    o, S = M.gdn_chunked(q, k, v, g, beta, chunk)
    assert o.shape == v.shape and o.dtype == v.dtype and S.shape == (3, 4, 8, 8)
    S2, os_ = jnp.zeros((3, 4, 8, 8)), []
    for t in range(T):
        ot, S2 = M.gdn_step(S2, jnp.repeat(q[:, t], 2, 1), jnp.repeat(k[:, t], 2, 1), v[:, t],
                            jnp.exp(g[:, t]), beta[:, t])
        os_.append(ot)
    real = np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(np.asarray(o)[real], np.asarray(jnp.stack(os_, 1))[real],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S, S2, rtol=1e-4, atol=1e-5)
    empty = lengths.index(0)
    assert np.array_equal(np.asarray(S[empty]), np.zeros((4, 8, 8), np.float32))
    assert np.delete(np.asarray(S), empty, 0).any(axis=(1, 2, 3)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gdn_chunked_rows_and_chunks_do_not_mix(dtype, chunk_groups):
    """What is made for a group's chunks of all rows at once is still each
    chunk's own: a row's outputs and state are the same whether it goes
    through alone or beside other rows, and the positions before a chunk
    boundary the same whether or not chunks follow; to a rounding of the type
    (a product's sum may be ordered otherwise in another batch), where a
    mixed-up chunk or row would differ in the first digit. The bfloat16 case
    rounds the products' operands as the served model does."""
    chunk_groups(8)
    q, k, v, g, beta = _rule_inputs(3, 24, 2, 8, 4, 8, (24, 11, 0))
    v = v.astype(dtype)
    same = functools.partial(np.testing.assert_allclose, atol=1e-6,
                             rtol=1e-5 if dtype == jnp.float32 else 2.0 ** -7)
    o, S = M.gdn_chunked(q, k, v, g, beta, 8)
    for r in range(3):
        o1, S1 = M.gdn_chunked(*(x[r:r + 1] for x in (q, k, v, g, beta)), 8)
        same(np.asarray(o1[0], np.float32), np.asarray(o[r], np.float32))
        same(np.asarray(S1[0]), np.asarray(S[r]), rtol=1e-5)
    o2, _ = M.gdn_chunked(*(x[:, :16] for x in (q, k, v, g, beta)), 8)
    same(np.asarray(o2, np.float32), np.asarray(o[:, :16], np.float32))


def test_the_delta_rule_is_no_added_outer_product():
    """What separates it from the hybrid's recurrence: writing the same key
    twice with beta 1 leaves the second value, not their sum."""
    k = M._l2norm(jnp.ones((1, 1, 8)))
    S = jnp.zeros((1, 1, 8, 8))
    one = jnp.ones((1, 1))
    v1, v2 = jnp.full((1, 1, 8), 3.0), jnp.full((1, 1, 8), -2.0)
    _, S = M.gdn_step(S, k, k, v1, one, one)
    o, S = M.gdn_step(S, k, k, v2, one, one)
    np.testing.assert_allclose(o, v2, rtol=1e-5)


# ------------------------------ (c) the paged cache driven by hand
@pytest.fixture(params=["xla", "kernel"])
def update_path(request, monkeypatch):
    """The two paths of a decode step's state update: `gdn_step` + select +
    write (what the CPU runs), and the Pallas kernel a TPU runs, here in the
    TPU interpret mode at widths it takes."""
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
        return (np.asarray(self.cache["conv"][:, :, lane]), np.asarray(self.cache["state"][:, lane]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_admission_then_decode_matches_the_reference_at_every_position(dtype, update_path):
    """The chunked admission leaves a conv tail, a state and K/V blocks; the
    decode step goes on from them one position at a time: logits at every
    emitted position against the reference's full forward over prompt +
    emitted (one recurrence from position 0, nothing cached). Prompts of 19
    and 5 tokens in a bucket of 32, a padded row between them."""
    cfg, key, params = _model(dtype, widths=update_path)
    assert SU.supported(cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim) == bool(update_path)
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
    for w, g in zip(untouched, lanes.state(1)):
        np.testing.assert_array_equal(w, g)


def test_a_lane_is_untouched_by_the_others(update_path):
    """A lane's state and conv tail are unchanged, bit for bit, by other
    lanes' admissions (padding rows included) and by steps taken while it is
    inactive; a lane reused by a second request gives what a fresh cache
    gives."""
    cfg, _, params = _model(widths=update_path)
    lanes = Lanes(cfg, params)
    a, b, c = (_tokens(1, n, seed=s)[0] for n, s in ((13, 8), (21, 9), (9, 10)))
    lanes.admit([(1, a)], 16, new=3)                 # lane 1 owes 2 decode steps
    lanes.step(), lanes.step()
    assert int(lanes.cache["remaining"][1]) == 0     # inactive from here on
    frozen = lanes.state(1)
    assert frozen[1].any()
    lanes.admit([(0, b)], 32, new=6, width=2)        # one real row, one padding row (lane 0)
    for _ in range(3):
        lanes.step()                                  # lane 0 active, 1 and 2 not
    for w, g in zip(frozen, lanes.state(1)):
        np.testing.assert_array_equal(w, g)
    assert not lanes.state(2)[1].any()               # never admitted: still zeros

    lanes.admit([(1, c)], 16, new=5)                 # lane 1 reused
    reused = [lanes.step()[0][1] for _ in range(4)]
    fresh_lanes = Lanes(cfg, params)
    fresh_lanes.admit([(1, c)], 16, new=5)
    fresh = [fresh_lanes.step()[0][1] for _ in range(4)]
    np.testing.assert_array_equal(np.stack(reused), np.stack(fresh))


def test_the_kernel_is_the_one_position_form(update_path):
    """`gdn_step_stacked` on a stack of three layers against `gdn_step` on
    the layer: the live rows' outputs and states, the others and the other
    layers bit for bit."""
    from ray_tpu.models.granite_hybrid import live_rows

    H, K, V = (16, 8, 128) if update_path else (4, 8, 8)
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    state = f(3, 5, H, K, V)
    q, k, v = f(5, H, K), M._l2norm(f(5, H, K)), f(5, H, V)
    a, b = jnp.exp(-jnp.abs(f(5, H))), jax.nn.sigmoid(f(5, H))
    active = jnp.asarray([True, False, True, True, False])
    o, new = jax.jit(M.gdn_step_stacked)(state, jnp.int32(1), live_rows(active), q, k, v, a, b)
    want_o, want_S = M.gdn_step(state[1], q, k, v, a, b)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[1])[live], np.asarray(want_S)[live], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new[1])[~live], np.asarray(state[1])[~live])
    np.testing.assert_array_equal(np.asarray(new[jnp.asarray([0, 2])]), np.asarray(state[jnp.asarray([0, 2])]))
    # no lane live: nothing moves
    none = live_rows(jnp.zeros((5,), bool))
    _, same = jax.jit(M.gdn_step_stacked)(state, jnp.int32(2), none, q, k, v, a, b)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))


# -------------------------------------------------- (d) the held share of experts
def _expert_layer_params(cfg, key, at=0):
    """`moe_ffn`'s params of expert layer `at`, from the benchmark's generator."""
    moe = W.init_params(key, cfg)[M.MOE]
    own = {k: v for k, v in moe.items() if k != "experts"}
    return {**jax.tree.map(lambda a: a[at], own), "experts": moe["experts"], "at": at}


def _gated_shared(m, p, cfg):
    gate = jax.nn.sigmoid(m @ p["shared_gate"])
    return gate[:, None] * afmoe.swiglu(m, p["shared"], cfg)


@pytest.mark.parametrize("chunk", [None, 16], ids=["at-once", "chunks-of-16"])
def test_the_four_shares_add_up_to_the_whole_layer(chunk, monkeypatch):
    """Four programs that each hold a quarter of a layer's experts (tiny: 4
    of 16; the cell: 128 of 512): their routed parts plus the gated shared
    expert counted once are the uncut reference's whole expert layer (router
    weights normalised over all the chosen, held or not; an expert's matrices
    keyed by its index among the router's experts)."""
    if chunk is not None:
        monkeypatch.setattr(afmoe, "expert_ffn", functools.partial(afmoe.expert_ffn, chunk=chunk))
    key = W.seed_key(SEED)
    whole = M.Qwen3NextConfig.tiny(dtype=jnp.float32, held_first=0, held_count=16)
    m = jnp.asarray(np.random.default_rng(7).normal(size=(23, whole.d_model)), jnp.float32)
    k_moe = W.part_keys(key, whole)[4][0]
    want = R.expert_layer(m, k_moe, whole)
    total = 0.0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(whole, held_first=first, held_count=4)
        p = _expert_layer_params(share, key)
        got = afmoe.moe_ffn(m, p, share)[0]
        total = total + got - _gated_shared(m, p, share)
        # each share alone is the reference of that share
        own = np.asarray(R.expert_layer(m, k_moe, share))
        assert np.abs(np.asarray(got) - own).max() <= 1e-5 * np.abs(own).max()
    total = total + _gated_shared(m, p, whole)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() <= 1e-5 * np.abs(want).max()
    # and the shares differ: no share is the whole
    assert np.abs(own - np.asarray(want)).max() > 1e-2 * np.abs(want).max()


# --------------------------------------------------------------- (e) the router
def _route_inputs(cfg):
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(64, cfg.d_model)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(cfg.d_model, cfg.n_experts)) / 4.0, jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(cfg.n_experts,)), jnp.float32)
    return u, router, bias


def test_the_softmax_router_is_a_written_out_softmax_and_top_k():
    cfg = M.Qwen3NextConfig.tiny(dtype=jnp.float32)
    u, router, _ = _route_inputs(cfg)
    logits = np.asarray(u @ router, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, axis=-1, kind="stable")[:, :cfg.top_k]
    chosen, w = (np.asarray(a) for a in afmoe.route(u, router, None, cfg))
    np.testing.assert_array_equal(chosen, want)
    picked = np.take_along_axis(p, want, -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)  # no scale
    # and it is not the sigmoid's weights: the softmax couples the experts
    _, w_sigmoid = afmoe.route(u, router, None, afmoe.AfmoeConfig.tiny(dtype=jnp.float32))
    assert np.abs(np.asarray(w_sigmoid) - w).max() > 1e-3


@pytest.mark.parametrize("family", ["trinity", "sarvam"])
def test_the_other_models_router_is_bit_for_bit_what_it_was(family):
    """`route` as it stood before this model (PR 42's text, written out
    here): the sigmoid case gives the same bits for Trinity's and sarvam's
    configs."""
    cfg = (afmoe.AfmoeConfig if family == "trinity" else sarvam_mla.SarvamMlaConfig).tiny(
        dtype=jnp.float32)
    assert cfg.route_scoring == "sigmoid"
    u, router, bias = _route_inputs(cfg)

    def route_as_it_was(u, router, bias, cfg):
        scores = jax.nn.sigmoid(jnp.einsum("nd,de->ne", u, router,
                                           preferred_element_type=jnp.float32))
        _, chosen = jax.lax.top_k(scores + bias, cfg.top_k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.route_norm:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w * cfg.route_scale

    for fn in (lambda f: f, jax.jit):
        got = fn(functools.partial(afmoe.route, cfg=cfg))(u, router, bias)
        want = fn(functools.partial(route_as_it_was, cfg=cfg))(u, router, bias)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_the_shared_experts_gate_multiplies_its_output_and_only_where_there_is_one():
    cfg = M.Qwen3NextConfig.tiny(dtype=jnp.float32)
    p = _expert_layer_params(cfg, W.seed_key(SEED))
    m = jnp.asarray(np.random.default_rng(1).normal(size=(9, cfg.d_model)), jnp.float32)
    gated = afmoe.moe_ffn(m, p, cfg)[0]
    plain = afmoe.moe_ffn(m, {k: v for k, v in p.items() if k != "shared_gate"}, cfg)[0]
    shared = afmoe.swiglu(m, p["shared"], cfg)
    np.testing.assert_allclose(np.asarray(plain - gated),
                               np.asarray(shared - _gated_shared(m, p, cfg)), rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------- (f) the engine
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


def test_engine_serves_more_requests_than_lanes_like_the_static_path():
    """Mixed lengths through three lanes: greedy tokens equal the static
    `generate`, lanes are reused, and the engine's counters say what moved."""
    cfg, _, params = _model()
    eng = _engine()
    try:
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
    moved = {k: m1[k] - m0[k] for k in D.DEVICE_COUNTERS}
    # held experts only: fewer than top_k pairs a live row and layer
    assert 0 < moved["expert_rows"] < lane_steps * cfg.top_k * cfg.n_layers
    assert moved["expert_rows"] >= moved["experts_hit"] >= moved["expert_rows_max"] > 0


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

    cfg = M.Qwen3NextConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache=True is refused"):
        _LLMServer(cfg=cfg, continuous=True, n_slots=2)  # prefix_cache defaults to True
    server = _LLMServer(cfg=cfg, continuous=True, n_slots=2, prefix_cache=False, seed=3)
    try:
        want = D.generate(server.params, np.asarray([[5, 6, 7]]), cfg, 12)[0].tolist()
        assert server.engine.generate([5, 6, 7], 12) == want
        assert type(server.engine) is ContinuousBatchingEngine
    finally:
        server.engine.shutdown()
