"""Disaggregated prefill/decode serving and the cluster-wide KV plane
(serve/_internal/kv_plane.py, engine roles + migration in
serve/llm_engine.py, pool routing in serve/handle.py, pool_config in
serve/api.py + controller.py, per-pool autoscaling signals).

Unit tests cover the pure seams (digests, padding, rng recompute,
config validation, role routing on fake replicas); device tests check
the gather/import/scatter kernels roundtrip; engine tests run a REAL
migration across two in-process tiny engines and hold it to the
bit-exactness + allocator-leak bars; cluster tests run the pooled
deployment end to end and the mid-handoff decode-kill gate.
"""
import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve._internal import kv_plane
from ray_tpu.serve.errors import ReplicaDiedError, classify_error
from ray_tpu.serve.handle import DeploymentHandle


def _tiny_engine(**kw):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    kw.setdefault("n_slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("macro_phases", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("n_blocks", 64)
    return ContinuousBatchingEngine(params, cfg, **kw), params, cfg


def _prompt(n=19, seed=0):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, 400, size=n)]


# ------------------------------------------------------------ pure seams
def test_prefix_digest_matches_handle_affinity_digest():
    """The cluster cache key IS the router's affinity key: same tokens,
    same prefix window, bit-identical digest — so inventory routing
    costs zero extra hashing on the request path."""
    tokens = _prompt(40)
    h = DeploymentHandle("dep", "app")
    h._affinity = {"prefix_len": 16, "mode": "prefix"}
    want = h._affinity_digest(({"prompt": tokens},))
    assert kv_plane.prefix_digest(tokens, 16) == want
    # and the digest only sees the window
    assert kv_plane.prefix_digest(tokens[:16] + [999], 16) == want


def test_pad_block_ids_pow2_null_padded():
    for n, width in [(1, 1), (2, 2), (3, 4), (5, 8), (8, 8), (9, 16)]:
        out = kv_plane.pad_block_ids(list(range(7, 7 + n)))
        assert out.dtype == np.int32 and len(out) == width
        assert list(out[:n]) == list(range(7, 7 + n))
        assert all(b == kv_plane.NULL_BLOCK for b in out[n:])
    # empty still yields one null slot (a degenerate but valid wire shape)
    assert list(kv_plane.pad_block_ids([])) == [kv_plane.NULL_BLOCK]


def test_carried_rng_matches_admission_split():
    """Migration never ships device rng state: the decode side
    recomputes the carried key as a pure function of the seed, exactly
    the split admit_slots_paged performs."""
    import jax

    for seed in (0, 1234, 2**32 - 1, 2**32 + 5):
        want = np.asarray(
            jax.random.split(
                jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF)))[0],
            np.uint32)
        got = kv_plane.carried_rng_for_seed(seed)
        assert got.dtype == np.uint32 and np.array_equal(got, want)


def test_resume_body_roundtrip():
    from ray_tpu.serve._internal.sampling import SamplingParams

    sp = SamplingParams(temperature=0.7, top_k=8, seed=42)
    body = kv_plane.make_resume_body(
        prompt=[1, 2, 3], first_token=9, max_new_tokens=5, sampling=sp,
        ref_hex="ab" * 8, n_data_blocks=2, block_size=8, rid="r-7",
        t_export=123.0)
    assert kv_plane.is_resume_body(body)
    assert not kv_plane.is_resume_body({"prompt": [1]})
    assert not kv_plane.is_resume_body([1, 2, 3])
    # prompt rides top-level so the handle's affinity digest works
    assert body["prompt"] == [1, 2, 3] and body["first"] == 9
    back = SamplingParams.from_request(body["sampling"])
    assert back.temperature == 0.7 and back.seed == 42


def test_cluster_cache_kill_switch(monkeypatch):
    assert kv_plane.cluster_cache_enabled(True) is True
    assert kv_plane.cluster_cache_enabled(False) is False
    monkeypatch.delenv("RAY_TPU_SERVE_CLUSTER_CACHE", raising=False)
    assert kv_plane.cluster_cache_enabled(None) is True
    for off in ("0", "false", "off"):
        monkeypatch.setenv("RAY_TPU_SERVE_CLUSTER_CACHE", off)
        assert kv_plane.cluster_cache_enabled(None) is False
    # explicit knob beats the env kill switch
    assert kv_plane.cluster_cache_enabled(True) is True


def test_prefix_inventory_registers_only_full_windows():
    inv = kv_plane.PrefixInventory(prefix_len=16, cap=2)
    tokens = _prompt(40)
    inv.register(tokens, 8)  # shorter than the digest window: not a key
    assert not inv.published()
    inv.register(tokens, 16)
    d = str(kv_plane.prefix_digest(tokens, 16))
    assert d in inv and inv.published() == [d]
    assert inv.tokens_for(d) == tuple(tokens[:16])
    # LRU cap evicts the oldest digest
    inv.register(_prompt(40, seed=1), 16)
    inv.register(_prompt(40, seed=2), 16)
    assert len(inv.published()) == 2 and d not in inv


# ----------------------------------------------------- config validation
def test_pool_config_validation():
    from ray_tpu.serve._internal.autoscaler import validate_pool_config

    assert validate_pool_config(None) is None
    assert validate_pool_config({"prefill": 2, "decode": 3}) == {
        "prefill": 2, "decode": 3}
    with pytest.raises(ValueError, match="unknown pool"):
        validate_pool_config({"prefill": 1, "decode": 1, "verify": 1})
    with pytest.raises(ValueError, match="missing pool"):
        validate_pool_config({"prefill": 2})
    with pytest.raises(ValueError, match="int >= 1"):
        validate_pool_config({"prefill": 0, "decode": 1})
    with pytest.raises(ValueError, match="int >= 1"):
        validate_pool_config({"prefill": 1, "decode": "two"})


def test_autoscaling_pools_validation():
    from ray_tpu.serve._internal.autoscaler import validate_autoscaling_config

    ok = validate_autoscaling_config({
        "pools": {
            "prefill": {"target_queued_prefill_tokens": 256,
                        "max_replicas": 4},
            "decode": {"target_decode_lanes": 2, "min_replicas": 1},
        }})
    assert ok["pools"]["prefill"]["target_queued_prefill_tokens"] == 256
    with pytest.raises(ValueError, match="unknown pool"):
        validate_autoscaling_config({"pools": {"draft": {}}})
    with pytest.raises(ValueError, match="unknown key"):
        validate_autoscaling_config(
            {"pools": {"prefill": {"target_tokens": 1}}})
    with pytest.raises(ValueError, match="must be positive"):
        validate_autoscaling_config(
            {"pools": {"prefill": {"target_queued_prefill_tokens": 0}}})
    with pytest.raises(ValueError, match="must be positive"):
        validate_autoscaling_config(
            {"pools": {"decode": {"target_decode_lanes": -1}}})
    # each pool names its OWN signal; naming the other is a config error
    with pytest.raises(ValueError, match="not target_decode_lanes"):
        validate_autoscaling_config(
            {"pools": {"prefill": {"target_decode_lanes": 2}}})
    with pytest.raises(ValueError, match="not target_queued_prefill_tokens"):
        validate_autoscaling_config(
            {"pools": {"decode": {"target_queued_prefill_tokens": 64}}})


def test_pool_autoscaler_config_projection():
    from ray_tpu.serve._internal.autoscaler import (
        AutoscalingConfig,
        pool_autoscaler_config,
    )

    cfg = {
        "min_replicas": 1, "max_replicas": 8,
        "target_ongoing_requests": 2.0, "initial_replicas": 2,
        "pools": {
            "prefill": {"target_queued_prefill_tokens": 512,
                        "max_replicas": 4, "upscale_delay_s": 0.5},
            "decode": {"target_decode_lanes": 3},
        },
    }
    p = pool_autoscaler_config(cfg, "prefill")
    assert p["target_ongoing_requests"] == 512.0
    assert p["max_replicas"] == 4 and p["upscale_delay_s"] == 0.5
    assert "pools" not in p and "initial_replicas" not in p
    d = pool_autoscaler_config(cfg, "decode")
    assert d["target_ongoing_requests"] == 3.0 and d["max_replicas"] == 8
    # both project onto plain AutoscalingConfigs the shared engine runs
    AutoscalingConfig(**p), AutoscalingConfig(**d)


def test_deployment_rejects_pool_autoscaling_without_pools():
    @serve.deployment
    class D:
        def __call__(self, x):
            return x

    with pytest.raises(ValueError, match="requires pool_config"):
        D.options(autoscaling_config={
            "pools": {"decode": {"target_decode_lanes": 2}}})
    # and pool_config itself is validated at deployment() time
    with pytest.raises(ValueError, match="missing pool"):
        D.options(pool_config={"decode": 1})


def test_llm_deployment_pools_requires_continuous():
    from ray_tpu.serve.llm import llm_deployment

    with pytest.raises(ValueError, match="continuous"):
        llm_deployment(pools={"prefill": 1, "decode": 1})


def test_engine_role_is_validated_and_needs_a_shared_draft():
    import dataclasses

    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="role"):
        ContinuousBatchingEngine(params, cfg, role="verify")
    # a draft pool of its own cannot follow a migration
    with pytest.raises(ValueError, match="shared-pool draft"):
        ContinuousBatchingEngine(
            params, cfg, role="prefill", num_speculative_tokens=2,
            draft_model=dataclasses.replace(cfg, n_layers=1))


# ------------------------------------------------- role routing (fakes)
class _FakeMethod:
    def __init__(self, log=None):
        self.log = log if log is not None else []

    def options(self, **kw):
        return self

    def remote(self, method, args, kwargs):
        self.log.append((method, args, kwargs))
        return f"ref-{len(self.log)}"


class _FakeActor:
    def __init__(self, log):
        self.handle_request = _FakeMethod(log)


def _pool_handle(monkeypatch, roles, affinity=None):
    log = []
    monkeypatch.setattr(ray_tpu, "get_actor", lambda n: _FakeActor(log))
    h = DeploymentHandle("dep", "app")
    h._ensure_poller = lambda: None
    h._inv = False  # no cluster inventory in the fake
    h._apply_replicas({"replicas": list(roles), "affinity": affinity,
                       "fault": None, "roles": dict(roles)}, 1)
    return h, log


def test_reserve_restricts_to_pool_role(monkeypatch):
    roles = {"p1": "prefill", "p2": "prefill", "d1": "decode"}
    h, _ = _pool_handle(monkeypatch, roles)
    for _ in range(8):
        name, _sub, _kind = h._reserve(role="prefill")
        assert roles[name] == "prefill"
        h._outstanding[name] = 0
    for _ in range(8):
        name, _sub, _kind = h._reserve(role="decode")
        assert name == "d1"
        h._outstanding[name] = 0


def test_reserve_degrades_when_pool_empty(monkeypatch):
    """A pool momentarily empty (replica death mid-restart) degrades to
    any survivor instead of parking: paged engines serve resumes
    role-agnostically, so degrading beats losing the request."""
    h, _ = _pool_handle(monkeypatch, {"p1": "prefill"})
    name, _sub, _kind = h._reserve(role="decode")
    assert name == "p1"


def test_role_rings_split_affinity_by_pool(monkeypatch):
    aff = {"prefix_len": 8, "vnodes": 16, "spill_threshold": 8,
           "mode": "prefix", "cluster": False}
    roles = {"p1": "prefill", "p2": "prefill", "d1": "decode"}
    h, _ = _pool_handle(monkeypatch, roles, affinity=aff)
    assert set(h._role_rings) == {"prefill", "decode"}
    # every affinity key routed within a role lands in that role's pool
    for akey in range(0, 2**64, 2**59):
        idx, kind = h._route_affinity(akey, role="prefill", eligible=None)
        assert kind == "hits" and roles[h._replica_names[idx]] == "prefill"
        idx, kind = h._route_affinity(akey, role="decode", eligible=None)
        assert kind == "hits" and h._replica_names[idx] == "d1"


def test_inventory_probe_wins_before_ring(monkeypatch):
    """With the cluster cache on, the inventory owner takes the request
    ahead of the consistent-hash ring — the prefix is already resident
    there — and the hit is counted separately (inv_hits)."""
    aff = {"prefix_len": 8, "vnodes": 16, "spill_threshold": 8,
           "mode": "prefix", "cluster": True}
    roles = {"p1": "prefill", "p2": "prefill", "d1": "decode"}
    h, _ = _pool_handle(monkeypatch, roles, affinity=aff)

    class _Inv:
        def owner_of(self, digest):
            return "p2"

    h._inv = _Inv()
    idx, kind = h._route_affinity(12345, role="prefill", eligible=[0, 1])
    assert kind == "inv_hits" and h._replica_names[idx] == "p2"
    # an owner outside the eligible pool falls back to the role ring
    idx, kind = h._route_affinity(12345, role="decode", eligible=[2])
    assert kind == "hits" and h._replica_names[idx] == "d1"


def test_remote_resolves_role_from_body(monkeypatch):
    """Per-request role resolution: resume bodies go to the decode
    pool, fresh prompts to the prefill pool, options(pool=...) wins."""
    roles = {"p1": "prefill", "d1": "decode"}
    h, log = _pool_handle(monkeypatch, roles)
    h.remote({"prompt": [1, 2, 3]})
    h.remote({"__kv_resume__": True, "ref": "00", "prompt": [1, 2, 3],
              "first": 1, "max_new_tokens": 2, "sampling": {},
              "n_data_blocks": 1, "block_size": 8})
    h.options(pool="decode").remote({"prompt": [4, 5]})
    assert len(log) == 3
    # outstanding charges tell which replica each request landed on
    assert h._outstanding["p1"] >= 1 and h._outstanding["d1"] >= 1


# ------------------------------------------------------- device kernels
def test_gather_import_scatter_roundtrip():
    """gather -> wire -> import lands the exact slices in the dst
    blocks, arms the slot row, and leaves every other block untouched;
    the slot-less scatter variant moves blocks without touching any
    slot state."""
    import jax.numpy as jnp

    from ray_tpu.models import paged

    L, n_blocks, bs, kvh, hd, n_slots = 2, 8, 4, 2, 6, 2
    rng = np.random.default_rng(0)
    cache = {
        "k": jnp.asarray(rng.normal(size=(L, n_blocks, bs, kvh, hd)),
                         jnp.float32),
        "v": jnp.asarray(rng.normal(size=(L, n_blocks, bs, kvh, hd)),
                         jnp.float32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }
    src = kv_plane.pad_block_ids([2, 5, 3])
    k, v = paged.gather_kv_blocks(cache, jnp.asarray(src))
    assert k.shape == (L, 4, bs, kvh, hd)  # padded to the pow-2 bucket
    np.testing.assert_array_equal(np.asarray(k)[:, 0], np.asarray(cache["k"])[:, 2])
    np.testing.assert_array_equal(np.asarray(v)[:, 2], np.asarray(cache["v"])[:, 3])

    dst_cache = {
        "k": jnp.zeros((L, n_blocks, bs, kvh, hd), jnp.float32),
        "v": jnp.zeros((L, n_blocks, bs, kvh, hd), jnp.float32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }
    dst = kv_plane.pad_block_ids([6, 1, 4])
    out = paged.import_kv_blocks(
        dst_cache, jnp.asarray(dst), k, v, jnp.int32(1), jnp.int32(11),
        jnp.int32(7), jnp.asarray(np.array([3, 4], np.uint32)))
    np.testing.assert_array_equal(np.asarray(out["k"])[:, 6],
                                  np.asarray(cache["k"])[:, 2])
    np.testing.assert_array_equal(np.asarray(out["k"])[:, 1],
                                  np.asarray(cache["k"])[:, 5])
    np.testing.assert_array_equal(np.asarray(out["v"])[:, 4],
                                  np.asarray(cache["v"])[:, 3])
    # the slot row armed; block 7 (untargeted) untouched
    assert int(out["pos"][1]) == 11 and int(out["remaining"][1]) == 7
    assert int(out["pos"][0]) == 0
    assert not np.asarray(out["k"])[:, 7].any()

    # slot-less scatter: blocks move, slot state does NOT
    zero_cache = {
        "k": jnp.zeros((L, n_blocks, bs, kvh, hd), jnp.float32),
        "v": jnp.zeros((L, n_blocks, bs, kvh, hd), jnp.float32),
        "pos": jnp.full((n_slots,), 99, jnp.int32),
        "remaining": jnp.full((n_slots,), 99, jnp.int32),
        "rng": jnp.ones((n_slots, 2), jnp.uint32),
    }
    out2 = paged.scatter_kv_blocks(zero_cache, jnp.asarray(dst), k, v)
    np.testing.assert_array_equal(np.asarray(out2["k"])[:, 6],
                                  np.asarray(cache["k"])[:, 2])
    assert int(out2["pos"][0]) == 99 and int(out2["remaining"][1]) == 99


# --------------------------------------------- engine-level migration
def _glue_migrate(pe, de, prompt, max_new, sampling=None):
    """Manually run one prefill->decode handoff between two in-process
    engines (what the deployment layer's pump does over the handle)."""
    req = pe.submit(prompt, max_new, sampling=sampling)
    assert req.done.wait(180), "prefill request timed out"
    assert req.error is None, req.error
    assert req.finish_reason == "migrated", req.finish_reason
    exp = req.export
    payload = kv_plane.fetch_kv_payload(exp["ref_hex"])
    r2 = de.submit_resumed(
        prompt, req.tokens[0], max_new, payload["k"], payload["v"],
        exp["n_data_blocks"], sampling=sampling, t_export=exp["t_export"])
    assert r2.done.wait(180), "resumed request timed out"
    assert r2.error is None, r2.error
    return r2.tokens


def _assert_no_leaks(engine):
    """The allocator-leak bar at migration seams: every block still
    referenced is pinned by the radix cache, nothing else."""
    assert engine._alloc.used_blocks == engine._prefix.nodes, (
        engine._alloc.used_blocks, engine._prefix.nodes)


def test_migration_bit_exact_greedy_and_sampled(ray_start_regular):
    """The tentpole exactness gate: a request prefilled on a prefill
    engine and resumed on a decode engine emits EXACTLY the tokens a
    unified engine produces — greedy and seeded-sampled — and neither
    engine leaks a block across the handoff."""
    from ray_tpu.serve._internal.sampling import SamplingParams

    pe, params, cfg = _tiny_engine(role="prefill")
    de, _, _ = _tiny_engine(role="decode")
    ue, _, _ = _tiny_engine()
    prompt = _prompt(19)
    try:
        want = ue.generate(prompt, 8, timeout=180)
        got = _glue_migrate(pe, de, prompt, 8)
        assert got == want, (got, want)

        sp = SamplingParams(temperature=0.8, top_k=8, seed=1234)
        want_s = ue.generate(_prompt(19, seed=3), 8, timeout=180, sampling=sp)
        got_s = _glue_migrate(pe, de, _prompt(19, seed=3), 8, sampling=sp)
        assert got_s == want_s, (got_s, want_s)

        m_p, m_d = pe.metrics(), de.metrics()
        assert m_p["pool"] == "prefill" and m_d["pool"] == "decode"
        assert m_p["migrations_out"] == 2 and m_d["migrations_in"] == 2
        assert m_p["migrated_blocks_out"] == m_d["migrated_blocks_in"] > 0
        assert m_d["migration_ms_p99"] >= 0.0
        _assert_no_leaks(pe)
        _assert_no_leaks(de)
    finally:
        pe.shutdown(), de.shutdown(), ue.shutdown()


def test_prefill_engine_never_decodes_and_single_put(ray_start_regular):
    """A prefill-role engine emits exactly ONE token per migrated
    request (the admission sample) and ships the KV with ONE object
    put; max_new_tokens=1 requests finish locally without migrating."""
    pe, _, _ = _tiny_engine(role="prefill")
    try:
        req = pe.submit(_prompt(12), 6)
        assert req.done.wait(180) and req.finish_reason == "migrated"
        assert len(req.tokens) == 1  # no decode steps ran here
        one = pe.submit(_prompt(12, seed=5), 1)
        assert one.done.wait(180) and one.error is None
        assert one.finish_reason != "migrated" and len(one.tokens) == 1
        assert pe.metrics()["migrations_out"] == 1
        _assert_no_leaks(pe)
    finally:
        pe.shutdown()


def test_export_failure_is_typed_retryable_and_leak_free(monkeypatch):
    """The export seam: if the object-plane put fails mid-handoff the
    request fails with a RETRYABLE ReplicaDiedError(started=False) —
    no output escaped, a handle may redispatch — and the prefill
    engine frees every block."""
    pe, _, _ = _tiny_engine(role="prefill")

    def _boom(cache, blocks):
        raise RuntimeError("object plane unreachable")

    # the engine imports kv_plane at call time, so patching the module
    # attribute reaches the seam
    monkeypatch.setattr(kv_plane, "export_kv_blocks", _boom)
    try:
        req = pe.submit(_prompt(12), 6)
        assert req.done.wait(180)
        assert isinstance(req.exc, ReplicaDiedError)
        assert req.exc.started is False
        category, retryable, _after = classify_error(req.exc)
        assert category == "replica-death" and retryable
        _assert_no_leaks(pe)
    finally:
        pe.shutdown()


def test_resume_queue_counts_in_load_and_signals(ray_start_regular):
    pe, _, _ = _tiny_engine(role="prefill")
    de, _, _ = _tiny_engine(role="decode")
    try:
        sig = pe.pool_signals()
        assert sig["pool"] == "prefill"
        assert sig["queued_prefill_tokens"] == 0
        _glue_migrate(pe, de, _prompt(19), 4)
        sig_d = de.pool_signals()
        assert sig_d["pool"] == "decode" and sig_d["resume_queue"] == 0
    finally:
        pe.shutdown(), de.shutdown()


# ------------------------------------------------- cluster prefix cache
def test_cluster_prefix_export_import(ray_start_regular):
    """A prefix prefilled on one engine is fetched and grafted into
    another's radix cache over the object plane; the importer then
    reuses it like a local hit and re-import is a no-op."""
    e1, params, cfg = _tiny_engine(cluster_cache=True, digest_prefix_len=16)
    e2, _, _ = _tiny_engine(cluster_cache=True, digest_prefix_len=16)
    prompt = _prompt(19)
    try:
        want = e1.generate(prompt, 4, timeout=180)
        dig = kv_plane.prefix_digest(prompt, 16)
        assert e1.has_local_prefix(dig)
        assert str(dig) in e1.kv_inventory()
        exp = e1.export_prefix(dig)
        assert exp is not None and exp["n_data_blocks"] == 2
        payload = kv_plane.fetch_kv_payload(exp["ref"].hex()
                                            if hasattr(exp["ref"], "hex")
                                            and not isinstance(exp["ref"], str)
                                            else exp["ref"])
        added = e2.import_prefix(list(exp["tokens"]), payload["k"],
                                 payload["v"], exp["n_data_blocks"])
        assert added == 2
        assert e2.has_local_prefix(dig)
        # idempotent: a second import of the same prefix is a no-op
        assert e2.import_prefix(list(exp["tokens"]), payload["k"],
                                payload["v"], exp["n_data_blocks"]) == 0
        got = e2.generate(prompt, 4, timeout=180)
        assert got == want
        # the import was a real cache hit, not a silent re-prefill
        assert e2._prefix.hit_tokens >= 16
        _assert_no_leaks(e1)
        _assert_no_leaks(e2)
    finally:
        e1.shutdown(), e2.shutdown()


def test_export_prefix_unknown_digest_returns_none():
    e1, _, _ = _tiny_engine(cluster_cache=True, digest_prefix_len=16)
    try:
        assert e1.export_prefix(123456789) is None
    finally:
        e1.shutdown()


# --------------------------------------------------- pooled deployment
@pytest.fixture
def _cleanup_serve(ray_start_regular):
    yield
    try:
        serve.shutdown()
    except Exception:
        pass


@pytest.mark.slow
def test_pooled_deployment_end_to_end(_cleanup_serve):
    """serve.run with pools={...}: requests enter the prefill pool,
    migrate over the KV plane, finish on the decode pool, and the
    output is bit-exact vs a unified engine."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import llm_deployment
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    app = llm_deployment(cfg=cfg, continuous=True, n_slots=2, chunk=4,
                         macro_phases=4, block_size=8, n_blocks=64,
                         max_new_tokens=8, pools={"prefill": 1, "decode": 1})
    h = serve.run(app, name="llm_pools")
    prompt = _prompt(19)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    ref = ContinuousBatchingEngine(params, cfg, n_slots=2, chunk=4,
                                   macro_phases=4, block_size=8,
                                   n_blocks=64)
    try:
        want = ref.generate(prompt, 8, timeout=180)
    finally:
        ref.shutdown()
    got = h.remote({"prompt": prompt, "max_new_tokens": 8}).result(timeout=300)
    assert got == want, (got, want)
    st = serve.status()["llm_pools"]["LLMServer"]
    assert st["pools"]["prefill"]["replicas"] == 1
    assert st["pools"]["decode"]["replicas"] == 1


@pytest.mark.slow
@pytest.mark.chaos
def test_decode_kill_mid_handoff_zero_lost(_cleanup_serve):
    """The KV-plane failure gate: SIGKILL a decode replica while
    handoffs are in flight. Every accepted request completes — the
    prefill side holds the exported payload until decode acks, the
    death classifies retryable (started=False: no output escaped), and
    the internal handle redispatches the resume body to the surviving
    decode replica. Zero lost output."""
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import llm_deployment

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    app = llm_deployment(cfg=cfg, continuous=True, n_slots=2, chunk=4,
                         macro_phases=2, block_size=8, n_blocks=64,
                         max_new_tokens=6,
                         pools={"prefill": 1, "decode": 2})
    h = serve.run(app, name="llm_kvchaos")
    # warm all replicas' compiles out of the kill window
    warm = [h.remote({"prompt": _prompt(10, seed=i), "max_new_tokens": 4})
            for i in range(4)]
    for r in warm:
        r.result(timeout=300)

    info = ray_tpu.get(
        serve.api._get_controller().get_replicas_versioned.remote(
            "llm_kvchaos", "LLMServer"))
    roles = info["data"]["roles"]
    victims = sorted(n for n, r in roles.items() if r == "decode")
    assert len(victims) == 2, roles
    victim = victims[0]
    pid = ray_tpu.get(ray_tpu.get_actor(victim).stats.remote())["pid"]

    resps = [h.remote({"prompt": _prompt(12, seed=100 + i),
                       "max_new_tokens": 6}) for i in range(8)]
    time.sleep(0.3)  # let handoffs get in flight
    os.kill(pid, signal.SIGKILL)

    lost = 0
    for r in resps:
        try:
            out = r.result(timeout=120)
            assert len(out) == 6
        except ReplicaDiedError as e:
            # typed retryable is the only acceptable failure: one
            # explicit caller retry must land on the survivor
            category, retryable, _ = classify_error(e)
            assert retryable, e
            out = h.remote({"prompt": _prompt(12, seed=200),
                            "max_new_tokens": 6}).result(timeout=120)
            assert len(out) == 6
        except Exception:
            lost += 1
    assert lost == 0, "lost output through a mid-handoff decode kill"
