"""The LongCat-Flash decoder (a double layer of two latent attentions with a
compressed query and two dense FFNs, the expert layer on a shortcut across the
second half, identity experts among those the router chooses from) against
its plain reference, and its two-plane latent pool in the paged engine
(ISSUE 45).

CPU, a tiny config with the real shape of things: two double layers, 4 heads
of 16 + 8 over a latent of 32 and a compressed query of 24, a router of 8 real
+ 4 identity experts top-3 of which real experts 4-7 are held, blocks of 4.
The reference is benchmark/reference_longcat_flash (float32, every key and
value expanded from the latent for every position, both attentions and both
dense FFNs written out, every held expert applied to every row and weighted,
the identity term as sum_w x m, whole score matrices); weights come from the
benchmark's seed-made generator, choice bias included, so nothing compared
shares an algorithm.

Tolerances as tests/test_sarvam_mla.py has them and for its reasons. float32:
1e-4 relative to the largest logit (measured 3e-6: float32 sums in another
order). bfloat16: 0.15 absolute on logits of spread 1 at the 80th percentile
over positions of each position's largest error (measured 0.05 for the whole
forward and 0.044 through the cache; a top-3 choice flips on a near-tie at one
position in forty to seventy, and a flip between a real and an identity expert
moves a whole term: 0.1 to 0.3 there). With W_qb, W_uk and W_uv drawn at
rank^-0.5, where the generator draws them at d_model^-0.5
(`weights_longcat_flash.make_sublayer` says why), the same comparison read 0.16
through the cache: scores of spread 5.7 make the softmax all but one-hot, and
a hard attention carries bfloat16's rounding from choice to choice. The wrong
variants are told apart in float32, where nothing flips.
"""
import dataclasses
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark import model_math_longcat_flash as mm
from benchmark import reference_longcat_flash as R
from benchmark import weights_longcat_flash as W
from ray_tpu.models import afmoe
from ray_tpu.models import longcat_flash as M
from ray_tpu.models import longcat_flash_decode as D
from ray_tpu.models import sarvam_mla
from ray_tpu.models import sarvam_mla_decode
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_rope
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.conftest import latent_decode_steps_by_each_reader, static_answers

F32_RTOL = 1e-4
BF16_ATOL = 0.15
BLOCK = 4
SEED = 2**31 + 45


@functools.lru_cache(maxsize=4)
def _model(dtype=jnp.float32):
    cfg = M.LongcatFlashConfig.tiny(dtype=dtype)
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
    cfg = M.LongcatFlashConfig()
    assert hash(cfg) == hash(M.LongcatFlashConfig()) and cfg.held_experts == (0, 512)
    assert (cfg.n_experts, cfg.n_sublayers, cfg.q_head_dim, cfg.latent_row) == (768, 56, 192, 576)
    assert sarvam_mla_decode.pool_row(cfg) == 640
    assert abs(cfg.sm_scale - 192 ** -0.5) < 1e-9  # no YaRN term
    assert (cfg.mla_q_scale, round(cfg.mla_kv_scale, 4)) == (2.0, 3.4641)
    off = dataclasses.replace(cfg, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    assert (off.mla_q_scale, off.mla_kv_scale) == (1.0, 1.0)
    assert (cfg.route_scoring, cfg.route_norm, cfg.route_scale, cfg.top_k) == ("softmax", False, 6.0, 12)
    assert cfg.model_module is M and cfg.decode_module is D
    # the benchmark's generator makes the program's tree, held experts only
    tiny = M.LongcatFlashConfig.tiny()
    shapes = lambda init: jax.tree.map(lambda a: (a.shape, a.dtype),  # noqa: E731
                                       jax.eval_shape(lambda: init(jax.random.PRNGKey(0), tiny)))
    assert shapes(M.init_params) == shapes(W._init)
    assert shapes(M.init_params)[M.MOE]["experts"]["w_up"][0] == (2, 4, 64, 32)
    assert shapes(M.init_params)[M.MOE]["router"][0] == (2, 64, 12)   # 8 real + 4 identity
    assert shapes(M.init_params)["layers"]["w_qa"][0] == (4, 64, 24)  # two sublayers a layer
    with pytest.raises(ValueError, match="held experts"):
        M.LongcatFlashConfig.tiny(held_first=6, held_count=4)  # identity indices are not held


@pytest.mark.parametrize("which", ["published", "the-cell's", "tiny"])
def test_num_params_is_the_model_arithmetics(which):
    """The program's parameter tree against benchmark/model_math_longcat_flash,
    which counts from the configuration file's shapes alone: 560.66 B whole
    (published: 560 B), 5,172,749,312 = 10.35 GB in bfloat16 as the cell holds
    it (four double layers, 16 of 512 real experts, an eighth of the
    vocabulary), and the tiny preset."""
    from benchmark.drivers.serve_longcat_flash import longcat_flash_config

    file = common.load_json(f"{common.BENCH_DIR}/configs/longcat-flash-chat.serve.json")
    if which == "published":
        file = {**file, **file["published"]}
        file["router_num_experts"] = file["n_routed_experts"]
        assert M.num_params(M.LongcatFlashConfig()) == mm.num_params(file)
        assert round(mm.num_params(file) / 1e9, 2) == 560.66
    elif which == "tiny":
        file = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.longcat_flash.json")
        assert longcat_flash_config(file) == M.LongcatFlashConfig.tiny(dtype=jnp.float32)
    else:
        assert mm.num_params(file) == 5_172_749_312
        assert round(mm.weight_bytes(file) / 1e9, 2) == 10.35
        assert round(mm.layer_params(file) / 1e6, 1) == 1242.9
        assert (round(mm.attn_matmul_params(file) / 1e6, 2), round(mm.dense_ffn_params(file) / 1e6, 2),
                round(mm.router_params(file) / 1e6, 2), round(mm.expert_params(file) / 1e6, 2)) == (
            90.57, 226.49, 4.72, 37.75)
        assert mm.latent_bytes_per_token(file) == 8 * 576 * 2
    assert M.num_params(longcat_flash_config(file)) == mm.num_params(file)


# ----------------------------------------------------------- the forward
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [9, 37])
def test_forward_matches_the_reference(T, dtype):
    cfg, key, params = _model(dtype)
    tokens = _tokens(2, T, seed=1)
    got = jax.jit(functools.partial(M.forward, cfg=cfg))(params, jnp.asarray(tokens))
    assert got.dtype == jnp.float32 and got.shape == (2, T, cfg.vocab_size)
    assert _worst(got, R.logits(key, jnp.asarray(tokens), cfg), dtype) <= 1.0


def test_absorbed_attention_is_the_expanded_attention_with_the_compressed_query_and_both_scales():
    """One sublayer's attention over the same cached rows both ways, through
    `sarvam_mla`'s functions with this model's `project` cases: every head's
    keys and values expanded from the (scaled) c, against W_uk absorbed into
    the (scaled) query and W_uv applied to the attended latent. And the cases
    are taken: q is (64 / 24)^0.5 times and c (64 / 32)^0.5 times what the
    config without the scales gives (2 and 3.4641 at the published widths),
    k_r the same."""
    cfg, _, params = _model()
    layer = jax.tree.map(lambda a: a[3], params["layers"])  # the second attention of layer 1
    assert "w_qa" in layer and "q_norm" not in layer and "k_rope_norm" not in layer
    T = 21
    a = jnp.asarray(np.random.default_rng(3).normal(size=(1, T, cfg.d_model)), jnp.float32)
    cos, sin = M.rope_tables(cfg, T)
    q_nope, q_rope, row = sarvam_mla.project(layer, a, cos, sin, None, cfg)
    expanded = sarvam_mla.expanded_attention(q_nope, q_rope, row, layer, cfg)[0]      # (T, h * v)
    r = cfg.kv_lora_rank
    q = jnp.concatenate([sarvam_mla.absorb_q(layer, q_nope[0]), q_rope[0]], axis=-1)  # (T, h, row)
    s = jnp.einsum("thc,jc->htj", q, row[0]) * cfg.sm_scale
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    absorbed = sarvam_mla.absorbed_out(layer, jnp.einsum("htj,jc->thc", p, row[0, :, :r]), cfg)
    assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() <= 1e-5 * np.abs(expanded).max()
    plain = dataclasses.replace(cfg, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    qn1, qr1, row1 = sarvam_mla.project(layer, a, cos, sin, None, plain)
    np.testing.assert_allclose(np.asarray(q_nope), (64 / 24) ** 0.5 * np.asarray(qn1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(q_rope), (64 / 24) ** 0.5 * np.asarray(qr1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(row[..., :r]), (64 / 32) ** 0.5 * np.asarray(row1[..., :r]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(row[..., r:]), np.asarray(row1[..., r:]))


def _project_as_it_was(layer, a, cos, sin, positions, cfg):
    """`sarvam_mla.project` as PR 44 left it, before it gained its cases."""
    q, ckr = jax.lax.optimization_barrier((a @ layer["wq"], a @ layer["w_kv_a"]))
    q = rms_norm(q.reshape(*a.shape[:2], cfg.n_heads, cfg.q_head_dim), layer["q_norm"], cfg.rms_eps)
    q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    c = rms_norm(ckr[..., :cfg.kv_lora_rank], layer["kv_norm"], cfg.rms_eps)
    k_r = rms_norm(ckr[..., cfg.kv_lora_rank:], layer["k_rope_norm"], cfg.rms_eps)
    k_r = apply_rope(k_r[:, :, None, :], cos, sin, positions)[:, :, 0, :]
    return q_nope, apply_rope(q_rope, cos, sin, positions), jnp.concatenate([c, k_r], axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_other_latent_models_projection_is_bit_for_bit_what_it_was(dtype):
    """`sarvam_mla.project` gained the compressed query and the two scales as
    cases that model's own layers and config do not take: on its tiny preset
    the three outputs are the old body's, bit for bit, and a whole forward is
    the forward with the old body patched in. (`afmoe.route` / `expert_ffn`
    gained no case: this model reaches its identity experts through
    `held_experts`, a range of fewer than the router's `n_experts`.)"""
    from benchmark import weights_sarvam_mla

    cfg = sarvam_mla.SarvamMlaConfig.tiny(dtype=dtype)
    params = weights_sarvam_mla.init_params(weights_sarvam_mla.seed_key(SEED), cfg)
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    a = jnp.asarray(np.random.default_rng(5).normal(size=(2, 13, cfg.d_model)), dtype)
    cos, sin = sarvam_mla.rope_tables(cfg, 64)
    pos = jnp.asarray(np.random.default_rng(6).integers(0, 64, (2, 13)), jnp.int32)
    for positions in (None, pos):
        now = jax.jit(functools.partial(sarvam_mla.project, cfg=cfg))(layer, a, cos, sin, positions)
        was = jax.jit(functools.partial(_project_as_it_was, cfg=cfg))(layer, a, cos, sin, positions)
        for x, y in zip(now, was):
            np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    tokens = jnp.asarray(_tokens(2, 19, seed=8))
    now = np.asarray(sarvam_mla.forward(params, tokens, cfg))
    with pytest.MonkeyPatch.context() as m:
        m.setattr(sarvam_mla, "project", _project_as_it_was)
        np.testing.assert_array_equal(now, np.asarray(sarvam_mla.forward(params, tokens, cfg)))


# ------------------------------------------------------- the expert layer
def _expert_layer_params(cfg, key, at=0):
    """Layer `at`'s expert-layer params as `run_layers` hands them on."""
    moe = W.init_params(key, cfg)[W.MOE]
    own = {k: v[at] for k, v in moe.items() if k != "experts"}
    return {**own, "experts": moe["experts"], "at": at}


def _loops(m, p, cfg, live=None):
    """The shortcut branch by loops in float64: (out, rows a held expert,
    (real, identity) choices of the live rows)."""
    m64 = np.asarray(m, np.float64)
    router, bias = np.asarray(p["router"], np.float64), np.asarray(p["bias"], np.float64)
    e64 = jax.tree.map(lambda a: np.asarray(a[p["at"]], np.float64), p["experts"])
    logits = m64 @ router
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    first, count = cfg.held_experts
    out, rows, real, zero = np.zeros_like(m64), np.zeros(count, np.int64), 0, 0
    for n in range(m64.shape[0]):
        alive = live is None or live[n]
        for j in np.argsort(-(prob[n] + bias))[:cfg.top_k]:
            w = cfg.route_scale * prob[n, j]
            if j >= cfg.n_routed_experts:
                out[n] += w * m64[n]
                zero += alive
                continue
            real += alive
            if first <= j < first + count and alive:
                e = j - first
                g, up = m64[n] @ e64["w_gate"][e], m64[n] @ e64["w_up"][e]
                out[n] += w * ((g / (1.0 + np.exp(-g)) * up) @ e64["w_down"][e])
                rows[e] += 1
    return out, rows, (real, zero)


@pytest.mark.parametrize("case", ["as-drawn", "every-choice-an-identity-expert", "no-identity-expert",
                                  "lanes-out"])
def test_the_shortcut_branch_is_a_loop_over_the_chosen_experts_real_and_identity(case):
    """`moe_ffn` against the definition by loops: the router's softmax over all
    12 outputs, its top-3 by p + b, 6 p unnormalised, a held real expert's
    SwiGLU or m itself. With a bias that puts the identity experts first
    every row's every choice is one: no product, out = (sum w) m exactly and
    the count says 0 real choices; with one that puts them last no row has
    one, and the branch is the real experts' alone. A row that is not live
    gets no real expert's product and is not counted, but its identity term
    is there (it costs a weighted copy, and nothing reads it)."""
    cfg = M.LongcatFlashConfig.tiny(dtype=jnp.float32)
    p = _expert_layer_params(cfg, W.seed_key(SEED), at=1)
    m = jnp.asarray(np.random.default_rng(11).normal(size=(7, cfg.d_model)), jnp.float32)
    live = None
    if case == "every-choice-an-identity-expert":
        p["bias"] = p["bias"].at[cfg.n_routed_experts:].add(2.0)
    elif case == "no-identity-expert":
        p["bias"] = p["bias"].at[:cfg.n_routed_experts].add(2.0)
    elif case == "lanes-out":
        live = np.array([True, False, True, True, False, True, True])
    got, sizes, choices = M.moe_ffn(m, p, cfg, None if live is None else jnp.asarray(live))
    want, rows, (real, zero) = _loops(m, p, cfg, live)
    assert np.abs(np.asarray(got) - want).max() <= 1e-5 * np.abs(want).max()
    np.testing.assert_array_equal(np.asarray(sizes), rows)
    assert tuple(np.asarray(choices)) == (real, zero)
    n_live = 7 if live is None else int(live.sum())
    assert real + zero == n_live * cfg.top_k
    if case == "every-choice-an-identity-expert":
        assert (real, zero) == (0, 21) and rows.sum() == 0
        _, w = afmoe.route(m, p["router"], p["bias"], cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(w.sum(-1, keepdims=True) * m), rtol=1e-6)
    elif case == "no-identity-expert":
        assert (real, zero) == (21, 0) and 0 < rows.sum() < 21  # half of the real ones are held
    else:
        assert 0 < zero < n_live * cfg.top_k and rows.sum() > 0
    # the reference's expert layer, made from its key, is the same layer
    if case == "as-drawn":
        k_moe = W.part_keys(W.seed_key(SEED), cfg)[4][1]
        ref = np.asarray(R.expert_layer(m, k_moe, cfg))
        assert np.abs(ref - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_and_the_identity_term_counted_once_add_up_to_the_whole_layer(shares):
    """The guide's shares test: programs that each hold one range of a layer's
    8 real experts. The held-range parts of all of them, plus the identity
    experts' term counted ONCE (every chip computes it alike), are the uncut
    reference's whole shortcut branch (router weights over all 12 outputs,
    unnormalised; an expert's matrices keyed by its index among the 8 real).
    Each share alone is the reference of that share, and no share is the
    whole."""
    key = W.seed_key(SEED)
    whole = M.LongcatFlashConfig.tiny(dtype=jnp.float32, held_first=0, held_count=8)
    m = jnp.asarray(np.random.default_rng(7).normal(size=(23, whole.d_model)), jnp.float32)
    k_moe = W.part_keys(key, whole)[4][0]
    want = np.asarray(R.expert_layer(m, k_moe, whole))
    p_whole = _expert_layer_params(whole, key)
    chosen, w = afmoe.route(m, p_whole["router"], p_whole["bias"], whole)
    identity = np.asarray(jnp.where(chosen >= whole.n_routed_experts, w, 0.0).sum(-1, keepdims=True) * m)
    assert np.abs(identity).max() > 0.1 * np.abs(want).max()  # it is a real part of the layer
    total = identity.copy()
    per = 8 // shares
    for first in range(0, 8, per):
        share = dataclasses.replace(whole, held_first=first, held_count=per)
        out = np.asarray(M.moe_ffn(m, _expert_layer_params(share, key), share)[0])
        own = np.asarray(R.expert_layer(m, k_moe, share))
        assert np.abs(out - own).max() <= 1e-5 * np.abs(own).max()
        total += out - identity
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()
    assert np.abs(own - want).max() > 1e-2 * np.abs(want).max()
    # the whole layer in one program is the whole layer
    assert np.abs(np.asarray(M.moe_ffn(m, p_whole, whole)[0]) - want).max() <= 1e-5 * np.abs(want).max()


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
    """Two lanes of different lengths (19 and 5 tokens) and 21 new tokens
    each, across block boundaries (blocks of 4: the first lane's first decode
    step opens a block, the second's fourth). Returns the worst error, in
    tolerances, of the decode steps' logits against the reference's full
    forward over prompt + emitted, and whether the admissions' first tokens
    are its argmax."""
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
    return _worst(got, want, dtype), firsts_agree, lanes


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_admission_then_decode_matches_the_reference_at_every_position(dtype):
    """The expanded admissions write each sublayer's plane; the absorbed
    decode steps read them, and `e` crosses the second half in both: logits at
    every emitted position against the reference's full forward (which
    expands every position and caches nothing)."""
    cfg, key, params = _model(dtype)
    worst, firsts_agree, lanes = _through_the_cache(cfg, key, params, dtype=dtype)
    assert worst <= 1.0
    assert firsts_agree or dtype != jnp.float32
    # one pool, TWO planes a layer, one row a position and plane, no K and no V
    cache = D.init_paged_cache(cfg, 2, 9, BLOCK)
    assert "k" not in cache and "v" not in cache
    assert cache["latent"].shape == (2 * cfg.n_layers, 9, BLOCK, 128)  # 40 columns in one tile
    assert cache["counts"].shape == (5,) and D.DEVICE_COUNTERS[3:] == ("real_choices", "zero_choices")
    assert D.state_bytes_per_lane(cfg) == 0 and D.LATENT_POOL
    # every plane was written, each with rows of its own
    pool = np.asarray(lanes.cache["latent"], np.float32)[:, 1:1 + lanes.mb].reshape(4, -1, 128)[:, :19]
    assert all(np.abs(pool[s]).max() > 0 for s in range(4))
    assert all(np.abs(pool[s] - pool[t]).max() > 0.1 for s in range(4) for t in range(s))
    assert np.abs(pool[..., cfg.latent_row:]).max() == 0  # the zero tail


def test_decode_mixer_takes_the_kernel_where_it_engages_and_the_loop_elsewhere(monkeypatch):
    """tests/test_sarvam_mla.py's, on this model's pool of two planes a layer:
    both sublayers' decode attentions are the single-pool form of the kernel
    of ops/paged_decode_attention.py where `engages` says so (patched; the TPU
    interpret mode), each over its own plane, and six steps' logits and all
    four planes' written rows are the definition's; on a TPU with this pool of
    blocks of 4 the definition runs, to the CPU's bits."""
    cfg, _, params = _model()
    ways, seen = latent_decode_steps_by_each_reader(
        Lanes, D, cfg, params, [_tokens(1, 19, seed=3)[0], _tokens(1, 5, seed=4)[0]], monkeypatch)
    assert seen and set(seen) == {((2, cfg.n_heads, 128), None, cfg.kv_lora_rank)}
    (logits, pool), (k_logits, k_pool) = ways["loop"], ways["kernel"]
    assert all(np.abs(pool[s]).max() > 0 for s in range(4))
    assert np.abs(k_logits - logits).max() <= 1e-5 * np.abs(logits).max()
    np.testing.assert_allclose(k_pool, pool, rtol=1e-5, atol=1e-5 * np.abs(pool).max())
    np.testing.assert_array_equal(ways["tiles-refuse"][0], logits)
    np.testing.assert_array_equal(ways["tiles-refuse"][1], pool)


def _identity_term_dropped(orig):
    def moe_ffn(m, p, cfg, live=None):
        chosen, w = afmoe.route(m, p["router"], p["bias"], cfg)
        out, sizes = afmoe.expert_ffn(m, chosen, w, p["experts"], p["at"], cfg, live)
        return out, sizes, jnp.zeros((2,), jnp.int32)
    return moe_ffn


def _one_plane_a_layer(orig):
    """Both attentions of a layer write and read the first one's plane."""
    def decode_mixer(layer, plane, *rest):
        return orig(layer, plane - plane % 2, *rest)
    return decode_mixer


def _shortcut_joined_early(orig):
    """e added where the expert layer is computed, not at the layer's end: the
    second attention and dense FFN read a stream that already holds it."""
    def run_layers(params, x, carry, cfg, mixer, experts=None):
        if experts is None:
            experts = lambda p, m, carry: (M.moe_ffn(m, p, cfg)[0], carry)  # noqa: E731
        sub = lambda tree, j: jax.tree.map(lambda a: a[j], tree)  # noqa: E731
        norm = lambda v, w: rms_norm(v, w, cfg.rms_eps)  # noqa: E731
        own = {k: v for k, v in params[M.MOE].items() if k != "experts"}
        for i in range(cfg.n_layers):
            l0, l1 = sub(params["layers"], 2 * i), sub(params["layers"], 2 * i + 1)
            o, carry = mixer(l0, 2 * i, norm(x, l0["attn_norm"]), carry)
            h1 = x + o
            m = norm(h1, l0["ffn_norm"])
            p = {**sub(own, i), "experts": params[M.MOE]["experts"], "at": i}
            e, carry = experts(p, m.reshape(-1, cfg.d_model), carry)
            h2 = h1 + afmoe.swiglu(m, sub(params[M.DENSE], 2 * i), cfg) + e.reshape(m.shape)
            o, carry = mixer(l1, 2 * i + 1, norm(h2, l1["attn_norm"]), carry)
            h3 = h2 + o
            x = h3 + afmoe.swiglu(norm(h3, l1["ffn_norm"]), sub(params[M.DENSE], 2 * i + 1), cfg)
        return x, carry
    return run_layers


MUTATIONS = {
    # name: (module, attribute, wrong version of it), on the SYSTEM's side only
    "no-scale-on-the-latent": (M.LongcatFlashConfig, "mla_kv_scale", lambda orig: property(lambda c: 1.0)),
    "no-scale-on-the-query": (M.LongcatFlashConfig, "mla_q_scale", lambda orig: property(lambda c: 1.0)),
    "chosen-weights-normalised": (M.LongcatFlashConfig, "route_norm", lambda orig: True),
    "identity-term-dropped": (M, "moe_ffn", _identity_term_dropped),
    "one-plane-a-layer": (sarvam_mla_decode, "decode_mixer", _one_plane_a_layer),
    "shortcut-joined-early": (M, "run_layers", _shortcut_joined_early),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_variant_of_the_system_fails_the_comparison(name, monkeypatch):
    """The comparison through the cache is tight enough to tell: each of these
    variants misses the float32 tolerance by a factor of 50 at least, on the
    very tokens on which the sound program passes."""
    cfg, key, params = _model()
    module, attr, make = MUTATIONS[name]
    monkeypatch.setattr(module, attr, make(getattr(module, attr)))
    halves = (functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False),
              functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False))
    worst, _, _ = _through_the_cache(cfg, key, params, halves=tuple(map(jax.jit, halves)))
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
    `engine.dispatch` span and the device's five counts on each
    `engine.resolve` span sum to `metrics()`' own, and to what the requests'
    lengths say they must be: a live row chooses top_k of the router's outputs
    in each of the layers, real or identity."""
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
    assert moved["ctx_tokens"] == sum(sum(range(n + 1, n + k)) for n, k in zip(lengths, answers))
    assert moved["prompt_pairs"] == sum(n * (n + 1) // 2 for n in lengths)
    for key in ("ctx_tokens", "prompt_pairs"):
        assert sum(int(st[key]) for st in dispatches) == moved[key]
    assert moved["real_choices"] + moved["zero_choices"] == lane_steps * cfg.top_k * cfg.n_layers
    assert 0.2 < moved["zero_choices"] / (lane_steps * cfg.top_k * cfg.n_layers) < 0.5  # 4 of 12
    # held experts only: half of the real choices, more or less
    assert 0 < moved["expert_rows"] < moved["real_choices"]
    assert moved["expert_rows"] >= moved["experts_hit"] >= moved["expert_rows_max"] > 0
    for key in D.DEVICE_COUNTERS:
        assert sum(int(st[key]) for st in resolves) == moved[key]
    assert sorted(int(st["seq"]) for st in resolves) == sorted(int(st["seq"]) for st in dispatches)
    assert m1["state_bytes"] == 0


REFUSED_AT_CONSTRUCTION = {
    "prefix_cache": dict(prefix_cache=True),
    "draft_model": dict(draft_model="self", num_speculative_tokens=2),
    "role": dict(role="decode"),
    "cluster_cache": dict(cluster_cache=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED_AT_CONSTRUCTION))
def test_what_needs_a_k_and_a_v_pool_is_refused_at_construction(option):
    with pytest.raises(ValueError) as refusal:
        _engine(**REFUSED_AT_CONSTRUCTION[option])
    assert option in str(refusal.value) and "one pool of latent rows" in str(refusal.value)


def test_llm_deployment_serves_the_model_through_the_normal_path():
    """The deployment callable builds config-default params through the
    config's own module and hands the refused options on unchanged; no new
    option, no engine mode."""
    from ray_tpu.serve.llm import _LLMServer

    cfg = M.LongcatFlashConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache=True is refused"):
        _LLMServer(cfg=cfg, continuous=True, n_slots=2)  # prefix_cache defaults to True
    server = _LLMServer(cfg=cfg, continuous=True, n_slots=2, prefix_cache=False, seed=3)
    try:
        want = D.generate(server.params, np.asarray([[5, 6, 7]]), cfg, 12)[0].tolist()
        assert server.engine.generate([5, 6, 7], 12) == want
        assert type(server.engine) is ContinuousBatchingEngine
    finally:
        server.engine.shutdown()
