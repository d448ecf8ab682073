"""Phi-4-mini-flash (models/phi4flash.py, phi4flash_decode.py, ops/s6_update.py)
on the CPU at a tiny size that keeps every kind of layer: 12 layers = 3 x
(Mamba-1, window), the memory's Mamba layer, the full layer, 2 x (gated memory
unit, cross attention); two query pairs to a key pair as published; a window
of 8, shorter than the prompts.

The oracle is `benchmark/reference_phi4flash.py`: float32, no cache, ALL layers
at EVERY position, the recurrence one position at a time, the head pairs by
the source's reshape and its four attentions a layer: written from the
equations and independent of the program.

Tolerances, each beside its reason. float32: the program and the reference do
the same sums in another order (chunks of the scan, online softmax, laid-out
queries), so the largest error of a position's logits is held to F32_RTOL =
2e-5 of the largest logit (measured 4e-6 through the cache, 3e-6 in the full
forward: five times of room). bfloat16:
activations and weights are rounded to 8 bits of mantissa at every layer and
the reference keeps float32, so the 80th percentile over positions of each
position's largest error is held to BF16_ATOL = 0.25 (measured 0.13 on logits
of standard deviation 1); computing the STATE or the SCORES in bfloat16 where
the program keeps float32 misses the float32 tolerance 25 and 40 times over
(`test_a_wrong_variant...`).
"""
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import model_math_phi4flash as mm
from benchmark import reference_phi4flash as R
from benchmark import weights_phi4flash as W
from ray_tpu.models import afmoe_decode
from ray_tpu.models import paged
from ray_tpu.models import phi4flash as M
from ray_tpu.models import phi4flash_decode as D
from ray_tpu.ops import s6_update
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine
from tests.conftest import static_answers

F32_RTOL = 2e-5
BF16_ATOL = 0.25
BLOCK = 4
SEED = 2**31 + 49


@functools.lru_cache(maxsize=4)
def _model(dtype=jnp.float32):
    cfg = M.Phi4FlashConfig.tiny(dtype=dtype)
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
    cfg = M.Phi4FlashConfig()
    assert hash(cfg) == hash(M.Phi4FlashConfig()) and cfg == M.Phi4FlashConfig()
    assert cfg.model_module is M and cfg.decode_module is D
    # the published layer kinds, from mb_per_layer 2 and 32 layers
    assert (cfg.n_mamba_layers, cfg.n_window_layers, cfg.n_cross_layers) == (9, 8, 7)
    assert (cfg.half, cfg.d_inner, cfg.kv_row, cfg.mamba_dt_rank) == (16, 5120, 1280, 160)
    tiny = M.Phi4FlashConfig.tiny()
    assert (tiny.n_mamba_layers, tiny.n_window_layers, tiny.n_cross_layers) == (4, 3, 2)
    for bad in (dict(n_layers=10), dict(mb_per_layer=1), dict(n_heads=6, n_kv_heads=4)):
        with pytest.raises(ValueError):
            M.Phi4FlashConfig.tiny(**bad)


PUBLISHED_FILE = {"hidden_size": 2560, "intermediate_size": 10240, "num_hidden_layers": 32,
                  "num_attention_heads": 40, "num_key_value_heads": 20, "vocab_size": 200064,
                  "sliding_window": 512, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
                  "mamba_dt_rank": 160, "tie_word_embeddings": True, "torch_dtype": "bfloat16"}


@pytest.mark.parametrize("which", ["published", "tiny"])
def test_num_params_is_the_model_arithmetics(which):
    """ISSUE 49's table, to the parameter: 32 MLPs of 78.6 M, 9 Mamba mixers of
    41.2 M, 9 attention mixers of 19.7 M, 7 memory units of 26.2 M, 7 cross
    attentions of 13.1 M, the tied matrix 512 M: 3.85 B."""
    if which == "published":
        cfg, f = M.Phi4FlashConfig(), PUBLISHED_FILE
        assert mm.mlp_params(f) - 2 * 2560 == 78_643_200
        assert mm.mamba_params(f) - 2 * 2560 == 41_241_600
        assert mm.attn_params(f) - 2 * 2560 == 19_668_864
        assert mm.gmu_params(f) - 2 * 2560 == 26_214_400
        assert mm.cross_params(f) - 2 * 2560 == 13_112_704
        assert mm.num_params(f) == 3_852_562_944
    else:
        cfg = M.Phi4FlashConfig.tiny()
        f = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 12,
             "num_attention_heads": 8, "num_key_value_heads": 4, "vocab_size": 512,
             "sliding_window": 8, "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
             "mamba_dt_rank": 4, "tie_word_embeddings": True, "torch_dtype": "float32"}
    assert M.num_params(cfg) == mm.num_params(f)
    assert D.state_bytes_per_lane(cfg) == mm.state_bytes_per_lane(
        {**f, "torch_dtype": "bfloat16"})
    shapes = jax.eval_shape(lambda: W.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(a.size) for a in jax.tree.leaves(shapes)) == mm.num_params(f)


# ------------------------------------------------------- the full forward
@functools.lru_cache(maxsize=4)
def _jitted_forward(cfg):
    return jax.jit(functools.partial(M.forward, cfg=cfg))


@functools.lru_cache(maxsize=4)
def _jitted_reference(cfg):
    return jax.jit(functools.partial(R.logits, cfg=cfg))


@pytest.mark.parametrize("T,dtype", [(7, jnp.float32), (37, jnp.float32), (37, jnp.bfloat16)],
                         ids=["7-f32", "37-f32", "37-bf16"])
def test_forward_matches_the_reference(T, dtype):
    """Rows shorter and longer than the window of 8 and than the scan's chunk of 16."""
    cfg, key, params = _model(dtype)
    toks = jnp.asarray(_tokens(2, T, seed=1))
    assert _worst(_jitted_forward(cfg)(params, toks), _jitted_reference(cfg)(key, toks), dtype) <= 1.0


def _recurrence(x, dt, A, B, C, D):
    h = np.zeros((x.shape[0], A.shape[0], x.shape[2]), np.float64)
    ys = []
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t, None, :] * A) * h + (dt[:, t] * x[:, t])[:, None, :] * B[:, t, :, None]
        ys.append((h * C[:, t, :, None]).sum(1) + D * x[:, t])
    return np.stack(ys, 1), h


@pytest.mark.parametrize("T,chunk", [(11, 4), (8, 4), (3, 16), (13, 1)])
def test_selective_scan_is_the_recurrence_across_chunks_and_past_a_rows_length(T, chunk):
    """The chunked scan against the recurrence one position at a time in
    float64: a length that is and is not a multiple of the chunk, a chunk
    longer than the row; past a row's length (dt zeroed, as `mamba_sequence`
    does) the state stands still, so the final state is the one at the length."""
    rng = np.random.default_rng(T)
    R_, c, N = 3, 16, 4
    x = rng.normal(size=(R_, T, c)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (R_, T, c))).astype(np.float32)
    A = -np.exp(rng.normal(size=(N, c))).astype(np.float32)
    B, C = (rng.normal(size=(R_, T, N)).astype(np.float32) for _ in range(2))
    Dv = rng.normal(size=(c,)).astype(np.float32)
    lengths = np.array([T, max(T - 2, 1), 1])
    dt = np.where(np.arange(T)[None, :, None] < lengths[:, None, None], dt, 0.0).astype(np.float32)
    y, h = M.selective_scan(*map(jnp.asarray, (x, dt, A, B, C, Dv)), chunk)
    want_y, want_h = _recurrence(*(a.astype(np.float64) for a in (x, dt, A, B, C, Dv)))
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=2e-5, atol=2e-5)
    f64 = lambda a: a.astype(np.float64)  # noqa: E731
    for r, n in enumerate(lengths):  # frozen past the length: the state at the length
        _, h_n = _recurrence(f64(x[r:r + 1, :n]), f64(dt[r:r + 1, :n]), f64(A), f64(B[r:r + 1, :n]),
                             f64(C[r:r + 1, :n]), f64(Dv))
        np.testing.assert_allclose(np.asarray(h)[r], h_n[0], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_stacked_state_update_steps_live_lanes_only(path, monkeypatch):
    """`s6_step_stacked`: the live lanes of ONE layer stepped as `s6_step`
    steps them, every other lane and layer bit for bit; off the TPU by
    `s6_step`, a select and the write, and by the Pallas kernel of
    ops/s6_update.py (what a TPU runs, here in the TPU interpret mode at widths
    it takes: only the order of the sum over N may differ)."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.models.granite_hybrid import live_rows

    rng = np.random.default_rng(5)
    Mn, L, N, c = 3, 5, 8, 256
    ssm = jnp.asarray(rng.normal(size=(Mn, L, N, c)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(L, c)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(L, c)), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.normal(size=(N, c)), jnp.float32))
    B, C = (jnp.asarray(rng.normal(size=(L, N)), jnp.float32) for _ in range(2))
    Dv = jnp.asarray(rng.normal(size=(c,)), jnp.float32)
    assert s6_update.supported(N, c) and s6_update.supported(16, 5120)
    assert not s6_update.supported(4, 128) and not s6_update.engages(16, 5120)  # no TPU here
    assert s6_update.channels_per_block(16, 5120) == 5120  # a lane's whole row a block
    want_y, want_h = M.s6_step(ssm[1], x, dt, A, B, C, Dv)
    for flags in ([True, False, True, True, False], [False] * 5, [True] * 5):
        active = jnp.asarray(flags)
        on = np.asarray(flags)
        if path == "kernel":
            monkeypatch.setattr(s6_update, "_on_tpu", lambda: True)
            with pltpu.force_tpu_interpret_mode():
                y, out = M.s6_step_stacked(ssm, 1, live_rows(active), x, dt, A, B, C, Dv)
        else:
            y, out = M.s6_step_stacked(ssm, 1, live_rows(active), x, dt, A, B, C, Dv)
        if path == "xla":
            np.testing.assert_array_equal(np.asarray(out[1])[on], np.asarray(want_h)[on])
        else:  # the interpreter's exp and fused multiply-adds: an ulp
            np.testing.assert_allclose(np.asarray(out[1])[on], np.asarray(want_h)[on], rtol=2e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out[1])[~on], np.asarray(ssm[1])[~on])
        np.testing.assert_array_equal(np.asarray(out[::2]), np.asarray(ssm[::2]))
        np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on], rtol=1e-5, atol=1e-5)


# --------------------------------------------------------- the pair map
def _plain_attend(k, v, heads_of):
    """`attend` by a loop over query heads: query head q (laid out, 2 hd
    wide) over the KV head `heads_of(q)` of 2 hd, causal, float64."""
    def attend(q):
        q, k_, v_ = (np.asarray(a, np.float64) for a in (q, k, v))
        T, h, w = q.shape
        k_, v_ = k_.reshape(T, -1, w), v_.reshape(T, -1, w)
        out = np.zeros((T, h, w))
        for head in range(h):
            s = q[:, head] @ k_[:, heads_of(head)].T / np.sqrt(w / 2)
            s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[:, head] = (p / p.sum(-1, keepdims=True)) @ v_[:, heads_of(head)]
        return jnp.asarray(out, jnp.float32)
    return attend


@pytest.mark.parametrize("head_map", ["pairs", "gqa"])
def test_the_pair_map_is_the_sources_reshape_and_grouped_query_heads_are_not(head_map):
    """`diff_attention` with keys and values read as pairs of 2 hd and query
    head q reading pair q // 4 (query pair j = q // 2 reads KV pair j // 2, part
    i = q % 2 its own half) equals the reference's reshape (h / 2, 2, hd) with
    its four attentions; GQA's own map over single heads (query head q reads
    key head q // 2 and that head's value alone) does NOT."""
    cfg, key, params = _model()
    layer = jax.tree.map(lambda a: a[1], params[M.ATTN])
    rng = np.random.default_rng(7)
    T = 9
    q, k, v = (jnp.asarray(rng.normal(size=(T, n * cfg.head_dim)), jnp.float32)
               for n in (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads))
    want = R.diff_attention(q, k, v, layer, 3.0, R._mask(T, None), cfg)
    if head_map == "pairs":
        attend = _plain_attend(k, v, lambda head: head // 4)
        got = M.diff_attention(layer, q.reshape(T, cfg.n_heads, cfg.head_dim), attend, 3, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
    else:
        # grouped-query attention as every other model here has it: single
        # heads of hd, query head q -> key AND value head q // (h / kvh)
        hd, rep = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
        qh, kh, vh = (np.asarray(a, np.float64).reshape(T, -1, hd) for a in (q, k, v))
        a = np.zeros((T, cfg.n_heads, hd))
        for head in range(cfg.n_heads):
            s = qh[:, head] @ kh[:, head // rep].T / np.sqrt(hd)
            s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            a[:, head] = (p / p.sum(-1, keepdims=True)) @ vh[:, head // rep]
        assert np.abs(a.reshape(T, -1) - np.asarray(want)).max() > 0.1


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
        """rows: [(lane, prompt)], one admission row each, `bucket` positions
        long; a lane of -1 is a padding row (length 0)."""
        A = len(rows)
        prompts = np.zeros((A, bucket), np.int32)
        lengths, slots = np.zeros(A, np.int32), np.zeros(A, np.int32)
        for i, (lane, p) in enumerate(rows):
            if lane >= 0:
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
    """Two lanes of unequal lengths in ONE admission, 19 tokens (past the
    window of 8 and across the scan's chunk of 16) and 5 (inside the window), with
    a padding row between them, and 21 new tokens each, across block
    boundaries and, for the short lane, across the window's edge. Returns the
    worst error, in tolerances, of the decode steps' logits against the
    reference's full forward over prompt + emitted, whether the admissions'
    first tokens are its argmax, and the lanes."""
    lanes = Lanes(cfg, params, n=2, halves=halves)
    prompts = [_tokens(1, 19, seed=3)[0], _tokens(1, 5, seed=4)[0]]
    n_new = 21
    first = lanes.admit([(0, prompts[0]), (-1, None), (1, prompts[1]), (-1, None)], bucket=32,
                        new=n_new)[[0, 2]]
    steps = [lanes.step() for _ in range(n_new - 1)]
    seqs = np.zeros((2, 19 + n_new), np.int32)  # right-padded: causal, so harmless there
    for b, p in enumerate(prompts):
        emitted = [first[b]] + [nxt[b] for _, nxt in steps]
        seqs[b, :len(p) + n_new] = np.concatenate([p, emitted])
    refs = np.asarray(_jitted_reference(cfg)(key, jnp.asarray(seqs)))
    firsts_agree = all(int(refs[b, len(p) - 1].argmax()) == first[b] for b, p in enumerate(prompts))
    got = np.stack([[logits[b] for logits, _ in steps] for b in range(2)])
    want = np.stack([refs[b, len(p):len(p) + n_new - 1] for b, p in enumerate(prompts)])
    return _worst(got, want, dtype), firsts_agree, lanes


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_admission_then_decode_matches_the_reference_at_every_position(dtype):
    """The two-depth admission writes rings, the ONE pool layer, conv tails
    and states; the decode steps run all layers on (lanes, 1) and read the
    pool eight times: logits at every emitted position against the
    reference's full forward (which runs every layer at every position and
    caches nothing)."""
    cfg, key, params = _model(dtype)
    worst, firsts_agree, lanes = _through_the_cache(cfg, key, params, dtype=dtype)
    assert worst <= 1.0
    assert firsts_agree or dtype != jnp.float32
    # what the cache holds: ONE pool layer, a ring a window layer, a tail and
    # a float32 state a Mamba layer, the two counts
    cache = D.init_paged_cache(cfg, 2, 9, BLOCK)
    assert cache["k"].shape == cache["v"].shape == (1, 9, BLOCK, cfg.kv_row)
    assert cache["wk"].shape == cache["wv"].shape == (3, 2, 8, cfg.kv_row)
    assert cache["conv"].shape == (4, 3, 2, cfg.d_inner)
    assert (cache["ssm"].shape, cache["ssm"].dtype) == ((4, 2, 4, cfg.d_inner), jnp.float32)
    assert cache["counts"].shape == (2,) and D.DEVICE_COUNTERS == ("self_rows", "cross_rows")
    assert D.state_bytes_per_lane(cfg) > 0 and not hasattr(D, "LATENT_POOL")
    # the padding rows of the admission wrote nothing: the same two rows
    # admitted without them leave every array of the cache bit for bit
    plain = Lanes(cfg, params, n=2)
    prompts = [_tokens(1, 19, seed=3)[0], _tokens(1, 5, seed=4)[0]]
    plain.admit(list(enumerate(prompts)), bucket=32, new=21)
    padded = Lanes(cfg, params, n=2)
    padded.admit([(0, prompts[0]), (-1, None), (1, prompts[1]), (-1, None)], bucket=32, new=21)
    for name in ("k", "v", "wk", "wv", "conv", "ssm", "pos", "remaining"):
        got, want = np.asarray(padded.cache[name]), np.asarray(plain.cache[name])
        if name in ("k", "v"):  # the null block takes what nothing reads
            got, want = got[:, 1:], want[:, 1:]
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert np.abs(np.asarray(padded.cache["ssm"], np.float32)).max() > 0


def test_the_two_depth_admissions_first_token_is_the_all_rows_ones():
    """The admission runs the cross-decoder at each row's last position only;
    its first tokens are the argmax of the program's own full `forward`
    (every layer at every position) there, for rows of unequal length."""
    cfg, key, params = _model()
    lanes = Lanes(cfg, params, n=3)
    prompts = [_tokens(1, n, seed=30 + n)[0] for n in (23, 2, 11)]
    first = lanes.admit(list(enumerate(prompts)), bucket=32)
    rows = np.zeros((3, 32), np.int32)
    for i, p in enumerate(prompts):
        rows[i, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    logits = np.asarray(_jitted_forward(cfg)(params, jnp.asarray(rows), lengths=lengths))
    for i, (p, got) in enumerate(zip(prompts, first)):
        assert int(logits[i, len(p) - 1].argmax()) == got
    # the device's own count of what ran: 3 rows x 32 positions in the
    # self-decoder, 3 rows of ONE position in the cross-decoder
    assert np.asarray(lanes.cache["counts"]).tolist() == [3 * 32, 3]


# ------------------------------------ the pool read in place by its eight readers
def test_decode_steps_through_the_kernel_hold_the_references_tolerance(monkeypatch):
    """The comparison through the cache with every one of the pool's eight
    readers a step through the kernel of ops/paged_decode_attention.py (what a
    TPU runs; here in the TPU interpret mode, which takes any shape, `engages`
    patched): the same tolerance with the same room, and the cache without a
    fetched context (tests/test_paged_decode_attention.py holds the kernel to
    `attend_decode_paged` lane case by lane case)."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import paged_decode_attention as PDA

    calls = []
    monkeypatch.setattr(PDA, "engages", lambda q, k, v: calls.append(q.shape) or True)
    cfg, key, params = _model()
    halves = (functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False),
              functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False))
    with pltpu.force_tpu_interpret_mode():
        worst, firsts_agree, lanes = _through_the_cache(cfg, key, params, halves=tuple(map(jax.jit, halves)))
    assert worst <= 1.0 and firsts_agree
    # traced once: the full layer's call and the rolled cross-decoder's
    assert calls == [(2, cfg.n_heads, 2 * cfg.head_dim)] * 2
    assert sorted(lanes.cache) == ["conv", "counts", "k", "pos", "remaining", "rng", "ssm", "v", "wk", "wv"]


# ------------------------------------------------------- wrong variants
def _no_one_minus_lambda_init(orig):
    def diff_attention(layer, q, attend, li, cfg):
        return (orig(layer, q, attend, li, cfg).astype(jnp.float32)
                / (1.0 - M.lambda_init(li))).astype(cfg.dtype)
    return diff_attention


def _memory_after_the_gate(orig):
    def mamba_token(layer, mi, a, tail, ssm, live, cfg):
        out, y, tail, ssm = orig(layer, mi, a, tail, ssm, live, cfg)
        z = jnp.split(a @ layer["in_proj"], 2, axis=-1)[1]
        return out, y * jax.nn.silu(z), tail, ssm
    return mamba_token


def _window_edge_off_by_one(orig):
    def ring_slots_held(pos, window):
        held = orig(pos, window)
        oldest = (pos[:, None] + 1) % window == jnp.arange(window)[None, :]
        return held & ~(oldest & (pos[:, None] >= window - 1))
    return ring_slots_held


def _state_in_bfloat16(orig):
    def s6_step(h, x, dt, A, B, C, D_):
        y, h = orig(h.astype(jnp.bfloat16).astype(jnp.float32), x, dt, A, B, C, D_)
        return y, h
    return s6_step


def _scores_in_bfloat16(orig):
    def attend_decode_paged(q, k_full, v_full, li, tables, pos, active, scale):
        # the scores rounded to bfloat16 before the softmax: as a bfloat16
        # score product would leave them
        s_round = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        orig_update = paged._online_softmax_update
        paged._online_softmax_update = lambda c, s, live, vc, pv: orig_update(c, s_round(s), live, vc, pv)
        try:
            return orig(q, k_full, v_full, li, tables, pos, active, scale)
        finally:
            paged._online_softmax_update = orig_update
    return attend_decode_paged


MUTATIONS = {
    "no-one-minus-lambda-init": (M, "diff_attention", _no_one_minus_lambda_init),
    "memory-taken-after-the-gate": (M, "mamba_token", _memory_after_the_gate),
    "window-edge-off-by-one": (afmoe_decode, "ring_slots_held", _window_edge_off_by_one),
    "state-in-bfloat16": (M, "s6_step", _state_in_bfloat16),
    # the pool's eight readers attend through this one off the TPU (PR 53)
    "scores-in-bfloat16": (paged, "attend_decode_paged", _scores_in_bfloat16),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_variant_of_the_system_fails_the_comparison(name, monkeypatch):
    """The comparison through the cache is tight enough to tell: each of
    these variants misses the float32 tolerance by a factor of 10 at least
    (the state in bfloat16 by 25, the pool's scores in bfloat16 by 40, the
    others by thousands), on the very tokens on which the sound program passes
    with five times of room: the precision the configuration states is held."""
    cfg, key, params = _model()
    module, attr, make = MUTATIONS[name]
    monkeypatch.setattr(module, attr, make(getattr(module, attr)))
    halves = (functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False),
              functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False))
    worst, _, _ = _through_the_cache(cfg, key, params, halves=tuple(map(jax.jit, halves)))
    assert worst > 10.0


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
    logits = np.asarray(_jitted_forward(cfg)(params, jnp.asarray(seq)))
    np.testing.assert_array_equal(out, logits[:, 20:-1].argmax(-1))


def test_engine_serves_more_requests_than_lanes_and_its_spans_sum_to_its_counters(tmp_path):
    """Mixed lengths through three lanes: greedy tokens equal the static
    `generate`; the device's two counts on each `engine.resolve` span sum to
    `metrics()`' own and to the plan's: `self_rows` is the plan's `admit_rows`
    (P x the rows of every admission body run), `cross_rows` the rows of those
    bodies, ONE token row each, whatever P."""
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
    moved = {k: m1[k] - m0[k] for k in D.DEVICE_COUNTERS + (
        "admit_rows", "useful_slot_steps", "state_lane_steps", "past_window_lane_steps")}
    assert moved["useful_slot_steps"] == moved["state_lane_steps"] == sum(n - 1 for n in answers)
    assert moved["self_rows"] == moved["admit_rows"] > 0
    # every dispatch's self rows are its cross rows x its own P
    assert sum(int(st["self_rows"]) for st in resolves) == moved["self_rows"]
    assert sum(int(st["cross_rows"]) for st in resolves) == moved["cross_rows"]
    by_seq = {int(st["seq"]): int(st["P"]) for st in dispatches}
    assert all(int(st["self_rows"]) == int(st["cross_rows"]) * by_seq[int(st["seq"])]
               for st in resolves)
    assert moved["cross_rows"] >= len(prompts)  # a row a request, and a piece's padding
    # contexts past the window of 8: the engine counts them for this model too
    assert moved["past_window_lane_steps"] == sum(
        max(0, n + k - 1 - max(n, 8)) for n, k in zip(lengths, answers))
    assert sorted(int(st["seq"]) for st in resolves) == sorted(int(st["seq"]) for st in dispatches)
    assert m1["state_bytes"] == D.state_bytes_per_lane(cfg)


REFUSED_AT_CONSTRUCTION = {
    "prefix_cache": dict(prefix_cache=True),
    "draft_model": dict(draft_model="self", num_speculative_tokens=2),
    "role": dict(role="decode"),
    "cluster_cache": dict(cluster_cache=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED_AT_CONSTRUCTION))
def test_what_needs_a_lanes_state_from_blocks_alone_is_refused_at_construction(option):
    with pytest.raises(ValueError) as refusal:
        _engine(**REFUSED_AT_CONSTRUCTION[option])
    assert option in str(refusal.value) and "recurrent state" in str(refusal.value)


def test_llm_deployment_serves_the_model_through_the_normal_path():
    """The deployment callable builds config-default params through the
    config's own module and hands the refused options on unchanged; no new
    option, no engine mode."""
    from ray_tpu.serve.llm import _LLMServer

    cfg = M.Phi4FlashConfig.tiny(dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefix_cache=True is refused"):
        _LLMServer(cfg=cfg, continuous=True, n_slots=2)  # prefix_cache defaults to True
    server = _LLMServer(cfg=cfg, continuous=True, n_slots=2, prefix_cache=False, seed=3)
    try:
        want = D.generate(server.params, np.asarray([[5, 6, 7]]), cfg, 12)[0].tolist()
        assert server.engine.generate([5, 6, 7], 12) == want
        assert type(server.engine) is ContinuousBatchingEngine
    finally:
        server.engine.shutdown()
