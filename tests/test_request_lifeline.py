"""Request lifelines: one rid end-to-end across migration and
redispatch, bounded (leak-audited) event buffers, the crash-surviving
flight recorder, telemetry epoch fencing, and the SLO plane math
(ray_tpu/observability/lifeline.py, observability/flight_recorder.py,
serve/_internal/slo.py, the record sites in serve/llm_engine.py +
serve/handle.py + serve/_internal/kv_plane.py).

Unit tests cover the pure seams (SloConfig validation, burn-rate
windows, restart clamping, engine-metric folding, store bounds);
engine tests run a REAL prefill→decode migration threading ONE rid
through every layer; the SIGKILL test proves the /dev/shm ring
survives its writer's death.
"""
import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.observability import flight_recorder, lifeline
from ray_tpu.serve._internal import kv_plane
from ray_tpu.serve._internal.slo import (
    SloState,
    fold_engine_metrics,
    validate_slo_config,
)
from ray_tpu.serve.errors import ReplicaDiedError
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
    return ContinuousBatchingEngine(params, cfg, **kw)


def _prompt(n=19, seed=0):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, 400, size=n)]


# -------------------------------------------------------- slo: validation
def test_slo_config_validation():
    ok = validate_slo_config({"ttft_p99_ms": 500.0, "availability": 0.99})
    assert ok["ttft_p99_ms"] == 500.0 and ok["availability"] == 0.99
    assert ok["tpot_p99_ms"] is None
    assert validate_slo_config(None) is None
    with pytest.raises(ValueError, match="unknown key"):
        validate_slo_config({"ttft_p50_ms": 10.0})
    with pytest.raises(ValueError, match="must be > 0"):
        validate_slo_config({"ttft_p99_ms": 0.0})
    with pytest.raises(ValueError, match="availability"):
        validate_slo_config({"availability": 1.5})
    with pytest.raises(ValueError, match="at least one objective"):
        validate_slo_config({})
    with pytest.raises(ValueError, match="must be a dict"):
        validate_slo_config([0.99])


def test_slo_config_raises_at_deployment_time():
    """Bad objectives fail when @serve.deployment builds — before any
    record ships to the controller (same contract as autoscaling/
    affinity/fault/pool configs)."""
    with pytest.raises(ValueError, match="unknown key"):
        serve.deployment(slo_config={"tpot_ms": 5.0})(object)
    with pytest.raises(ValueError, match="availability"):
        serve.deployment(slo_config={"availability": 0.0})(object)
    dep = serve.deployment(slo_config={"availability": 0.999})(object)
    assert dep.slo_config["availability"] == 0.999
    # options() round-trips and re-validates
    with pytest.raises(ValueError, match="must be > 0"):
        dep.options(slo_config={"ttft_p99_ms": -1})
    assert dep.options().slo_config == dep.slo_config


# ------------------------------------------------- slo: evaluator math
def test_slo_state_attainment_and_burn_rates():
    t0 = 1_000_000.0
    st = SloState({"ttft_p99_ms": 100.0, "availability": 0.99},
                  windows_s=(60.0, 300.0))
    st.observe(0, 0, ttft_p99_ms=None, now=t0)
    st.observe(90, 10, ttft_p99_ms=50.0, now=t0 + 30)
    snap = st.snapshot(now=t0 + 30)
    assert snap["ttft_p99_ms"]["attained"] is True
    assert snap["ttft_p99_ms"]["headroom_pct"] == 50.0
    av = snap["availability"]
    assert av["good"] == 90 and av["bad"] == 10
    assert av["observed"] == 0.9 and av["attained"] is False
    # 10% errors against a 1% budget: burning 10x over both windows
    assert av["burn_rate"]["60s"] == pytest.approx(10.0)
    assert av["burn_rate"]["300s"] == pytest.approx(10.0)
    assert snap["attained"] is False

    # blown-latency arm: observed p99 over target reads negative headroom
    st.observe(90, 10, ttft_p99_ms=150.0, now=t0 + 35)
    snap = st.snapshot(now=t0 + 35)
    assert snap["ttft_p99_ms"]["attained"] is False
    assert snap["ttft_p99_ms"]["headroom_pct"] == -50.0


def test_slo_state_burn_rate_windows_age_out():
    """Errors older than the window stop burning it: a burst at t0
    reads burn 0 on the fast window 2 minutes later while the slow
    window still remembers."""
    t0 = 2_000_000.0
    st = SloState({"availability": 0.99}, windows_s=(60.0, 300.0))
    st.observe(0, 10, now=t0)           # burst: 10 bad
    st.observe(100, 10, now=t0 + 120)   # 100 good since, no new bad
    snap = st.snapshot(now=t0 + 120)
    burn = snap["availability"]["burn_rate"]
    assert burn["60s"] == 0.0
    assert burn["300s"] == pytest.approx((10 / 110) / 0.01, rel=1e-3)


def test_slo_state_clamps_counter_restarts():
    """A replica restart steps cumulative counters backwards; deltas
    clamp at zero so the restart reads as no NEW traffic — never
    negative traffic."""
    t0 = 3_000_000.0
    st = SloState({"availability": 0.9})
    st.observe(50, 5, now=t0)
    st.observe(2, 0, now=t0 + 5)  # fresh engine restarted near zero
    snap = st.snapshot(now=t0 + 5)
    assert snap["availability"]["good"] == 50
    assert snap["availability"]["bad"] == 5
    st.observe(12, 1, now=t0 + 10)  # resumed counting: +10 good, +1 bad
    snap = st.snapshot(now=t0 + 10)
    assert snap["availability"]["good"] == 60
    assert snap["availability"]["bad"] == 6


def test_fold_engine_metrics_worst_case_and_lost_ledger():
    engines = {
        "llm-1": {"requests_completed": 40, "shed_requests": 2,
                  "deadline_expired": 1, "ttft_ms_p99": 80.0,
                  "tpot_ms_p99": 9.0},
        "llm-2": {"requests_completed": 60, "shed_queue_full": 1,
                  "shed_eta": 2, "ttft_ms_p99": 120.0,
                  "tpot_ms_p99": None},
        "bogus": "not-a-dict",
    }
    out = fold_engine_metrics(engines, lost_requests=3)
    assert out["good"] == 100
    # 2 shed + 1 deadline + (1+2 sheds from the counter pair) + 3 lost
    assert out["bad"] == 9
    # an SLO is blown if ANY replica blows it: worst (max) p99 wins
    assert out["ttft_p99_ms"] == 120.0
    assert out["tpot_p99_ms"] == 9.0
    empty = fold_engine_metrics({}, lost_requests=0)
    assert empty == {"good": 0.0, "bad": 0.0, "ttft_p99_ms": None,
                     "tpot_p99_ms": None}


# ------------------------------------------- lifeline store: leak audit
def test_lifeline_store_bounds_and_finish_aging():
    st = lifeline.LifelineStore(max_rids=4, max_finished=2)
    for i in range(6):
        st.record(f"r-{i}", "submit", t=float(i))
    # LRU bound: oldest live rids evicted beyond max_rids
    assert st.stats()["live"] == 4
    assert st.events("r-0") == [] and st.events("r-5") != []

    st.finish("r-5")
    assert "r-5" not in st.live_rids()
    assert st.events("r-5")  # finished rids stay queryable...
    st.finish("r-4")
    st.finish("r-3")
    # ...until max_finished newer requests finish after them
    assert st.stats() == {"live": 1, "finished": 2}
    assert st.events("r-5") == []

    # post-finish stragglers (a late cross-process event landing after
    # the engine finished the rid) append into the finished buffer
    st.record("r-3", "kv_put", t=9.0)
    kinds = [e["kind"] for e in st.events("r-3")]
    assert kinds == ["submit", "kv_put"]
    assert "r-3" not in st.live_rids()


def test_lifeline_per_rid_event_cap():
    st = lifeline.LifelineStore(max_rids=4)
    for i in range(lifeline._MAX_EVENTS_PER_RID + 50):
        st.record("big", "route", t=float(i))
    assert len(st.events("big")) == lifeline._MAX_EVENTS_PER_RID


# ------------------------------------- rid continuity: engine migration
def test_migration_threads_one_rid_through_every_layer(ray_start_regular):
    """The tentpole continuity gate: a request prefilled on a prefill
    engine and resumed on a decode engine keeps ONE rid, and
    `lifeline.events(rid)` shows the whole chain — submit, admission,
    the KV export/put hop, the resume fetch/import, first token and
    finish — in time order. After the finish the rid has aged out of
    the live set (the leak audit)."""
    pe = _tiny_engine(role="prefill")
    de = _tiny_engine(role="decode")
    rid = "lifeline-mig-1"
    prompt = _prompt(19)
    try:
        req = pe.submit(prompt, 6, rid=rid)
        assert req.done.wait(180) and req.error is None
        assert req.finish_reason == "migrated"
        exp = req.export
        payload = kv_plane.fetch_kv_payload(exp["ref_hex"], rid=rid)
        r2 = de.submit_resumed(prompt, req.tokens[0], 6, payload["k"],
                               payload["v"], exp["n_data_blocks"],
                               rid=rid, t_export=exp["t_export"])
        assert r2.done.wait(180) and r2.error is None

        evs = lifeline.events(rid)
        kinds = [e["kind"] for e in evs]
        for want in ("submit", "admit", "kv_export", "kv_put", "migrate",
                     "resume_fetch", "resume_submit", "kv_import",
                     "first_token", "finish"):
            assert want in kinds, (want, kinds)
        # the hop ordering is the migration contract: the prefill side's
        # export/put land before the decode side's fetch, the fetch
        # before the resumed admission's import, the import before finish
        assert (max(kinds.index("kv_export"), kinds.index("kv_put"))
                < kinds.index("resume_fetch")
                < kinds.index("kv_import") < kinds.index("finish"))
        ts = [e["t"] for e in evs]
        assert ts == sorted(ts)
        # every event rides the SAME rid — the decode hop did not mint one
        assert all(isinstance(e.get("t"), float) for e in evs)

        # the engine-side timeline joins the macro-step dispatches the
        # lane rode from the flight ring at read time
        tl = de.request_timeline(rid)
        assert any(e["kind"] == "dispatch" for e in tl), (
            "request_timeline must join ring dispatch records")
        d = next(e for e in tl if e["kind"] == "dispatch")
        assert d["engine"] == de.name and d["dispatch_ms"] >= 0.0

        # leak audit: finished rids leave the live set
        assert rid not in lifeline.store().live_rids()
        assert pe._alloc.used_blocks == pe._prefix.nodes
        assert de._alloc.used_blocks == de._prefix.nodes
    finally:
        pe.shutdown(), de.shutdown()


# --------------------------------- rid continuity: redispatch marks loser
class _FakeMethod:
    def __init__(self, log):
        self.log = log

    def options(self, **kw):
        return self

    def remote(self, method, args, kwargs):
        self.log.append((method, args, kwargs))
        return f"ref-{len(self.log)}"


class _FakeActor:
    def __init__(self, log):
        self.handle_request = _FakeMethod(log)


def test_redispatch_keeps_rid_and_marks_loser(monkeypatch):
    """A replica death mid-flight requeues the request under the SAME
    rid, and the lifeline carries both attempts: the original `route`
    event and a `redispatch` event naming the loser replica and the
    survivor it moved to."""
    log = []
    monkeypatch.setattr(ray_tpu, "get_actor", lambda n: _FakeActor(log))
    h = DeploymentHandle("dep", "app")
    h._ensure_poller = lambda: None
    h._inv = False
    h._apply_replicas(
        {"replicas": ["ra", "rb"], "affinity": None,
         "fault": {"redispatch": True, "max_redispatches": 2}}, 1)
    rid = "lifeline-redisp-1"
    resp = h.remote({"prompt": [1, 2, 3], "request_id": rid})
    record = resp._record
    assert record["rid"] == rid
    loser = record["replica"]
    assert loser in ("ra", "rb")

    newref = h._on_failure(record, ReplicaDiedError("ra died",
                                                    started=False))
    assert newref is not None, "redispatch-enabled death must requeue"
    assert record["attempts"] == 1
    survivor = record["replica"]
    assert survivor != loser
    assert len(log) == 2  # original submit + verbatim resubmit
    assert log[0][1] == log[1][1]  # same args, byte-for-byte

    evs = lifeline.events(rid)
    routes = [e for e in evs if e["kind"] == "route"]
    redis = [e for e in evs if e["kind"] == "redispatch"]
    assert len(routes) == 1 and routes[0]["replica"] == loser
    assert routes[0]["attempt"] == 0
    assert len(redis) == 1
    assert redis[0]["lost_replica"] == loser
    assert redis[0]["replica"] == survivor
    assert redis[0]["attempt"] == 1

    # a started request NEVER redispatches — _on_failure declines the
    # requeue (None = re-raise the original typed death) and its rid
    # gains no redispatch event
    rid2 = "lifeline-redisp-2"
    resp2 = h.remote({"prompt": [4, 5], "request_id": rid2})
    out = h._on_failure(resp2._record,
                        ReplicaDiedError("rb died", started=True))
    assert out is None
    assert resp2._record["attempts"] == 0
    assert not [e for e in lifeline.events(rid2)
                if e["kind"] == "redispatch"]


# --------------------------------------- flight recorder: crash survival
def _ring_victim(n_events):
    """Child body: write `n_events` then park until SIGKILLed."""
    rec = flight_recorder.FlightRecorder(capacity=64)
    rid = lifeline.rid_bytes("victim-rid-1")
    for i in range(n_events - 1):
        rec.write(flight_recorder.EV["dispatch"], rid, step=i, a=float(i))
    rec.write(flight_recorder.EV["error"], rid, a=float(n_events))
    time.sleep(120)


@pytest.mark.chaos
def test_flight_ring_survives_sigkill_of_writer():
    """The post-mortem contract: after the writer dies by SIGKILL (no
    atexit, no flush), `read_tail(pid=victim)` recovers its last events
    from /dev/shm — ordered, decoded, rid intact."""
    import multiprocessing

    ctx = multiprocessing.get_context("fork")
    p = ctx.Process(target=_ring_victim, args=(40,), daemon=True)
    p.start()
    path = flight_recorder._ring_path(p.pid)
    deadline = time.time() + 30
    tail = []
    while time.time() < deadline:
        try:
            tail = flight_recorder.read_tail(pid=p.pid, n=64)
        except Exception:
            tail = []
        if len(tail) >= 40:
            break
        time.sleep(0.05)
    assert len(tail) >= 40, f"victim never filled its ring ({len(tail)})"

    os.kill(p.pid, signal.SIGKILL)
    p.join(timeout=10)
    try:
        post = flight_recorder.read_tail(pid=p.pid, n=32)
        assert len(post) == 32, "post-mortem tail short"
        seqs = [e["seq"] for e in post]
        assert seqs == sorted(seqs)
        assert post[-1]["kind"] == "error"  # the victim's LAST event
        assert post[-1]["rid"] == "victim-rid-1"
        assert all(e["pid"] == p.pid for e in post)
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


# ------------------------------------------------- telemetry epoch fence
def test_reset_epoch_fences_stale_snapshots(ray_start_regular):
    """`reset_epoch` excludes every snapshot published before it — the
    A/B hygiene primitive replacing the PR-8 live-scrape workaround —
    while fresh publishes flow through immediately after."""
    from ray_tpu import observability as obs

    key = "engine:epoch-ghost"

    def _visible(k):
        return any(k in snap for snap in obs.fetch_snapshots("serve").values())

    obs.publish_snapshot("serve", {key: {"t": time.time(), "ghost": 1}})
    obs.flush("serve")
    deadline = time.time() + 10
    while time.time() < deadline and not _visible(key):
        time.sleep(0.05)
    assert _visible(key), "published snapshot never became visible"

    assert obs.reset_epoch("serve") > 0.0
    assert not _visible(key), "pre-epoch snapshot leaked past the fence"

    obs.publish_snapshot("serve", {key: {"t": time.time(), "ghost": 2}})
    obs.flush("serve")
    deadline = time.time() + 10
    while time.time() < deadline and not _visible(key):
        time.sleep(0.05)
    assert _visible(key), "post-epoch publish should be visible again"
    obs.prune_snapshot_key("serve", key)


# ------------------------------------- acceptance: chaos + full stack
@pytest.fixture
def _cleanup_serve(ray_start_regular):
    yield
    try:
        serve.shutdown()
    except Exception:
        pass


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_kill_lifeline_postmortem_slo_and_trace(_cleanup_serve,
                                                      tmp_path):
    """The round-20 acceptance gate, end to end: a pooled deployment
    with an slo_config under load, a decode replica SIGKILLed
    mid-burst. Afterwards (1) a migrated request's cluster-wide
    timeline spans the prefill replica, the KV hop and the decode
    replica, and the merged Perfetto trace carries its lifeline row
    with flow links; (2) the victim's flight-recorder tail (≥ 32
    events) is recovered post-mortem into serve.status(); (3) the SLO
    snapshot reports TTFT/TPOT attainment and availability burn."""
    import jax.numpy as jnp

    from ray_tpu import observability as obs
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import llm_deployment
    from ray_tpu.util import tracing

    tracing.enable()
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    app = llm_deployment(cfg=cfg, continuous=True, n_slots=2, chunk=4,
                         macro_phases=2, block_size=8, n_blocks=64,
                         max_new_tokens=6,
                         pools={"prefill": 1, "decode": 2},
                         slo_config={"ttft_p99_ms": 120_000.0,
                                     "tpot_p99_ms": 120_000.0,
                                     "availability": 0.5})
    h = serve.run(app, name="llm_lifeline")
    try:
        # warm traffic: compiles out of the kill window AND enough
        # decode-side events to fill the victim's ring past the 32-event
        # post-mortem bar
        warm = [h.remote({"prompt": _prompt(10, seed=i),
                          "max_new_tokens": 4,
                          "request_id": f"warm-{i}"}) for i in range(16)]
        for r in warm:
            r.result(timeout=300)

        info = ray_tpu.get(
            serve.api._get_controller().get_replicas_versioned.remote(
                "llm_lifeline", "LLMServer"))
        roles = info["data"]["roles"]
        victims = sorted(n for n, r in roles.items() if r == "decode")
        assert len(victims) == 2, roles
        victim = victims[0]
        pid = ray_tpu.get(
            ray_tpu.get_actor(victim).stats.remote())["pid"]

        rids = [f"chaos-rid-{i}" for i in range(8)]
        resps = [h.remote({"prompt": _prompt(12, seed=100 + i),
                           "max_new_tokens": 6, "request_id": rid})
                 for i, rid in enumerate(rids)]
        time.sleep(0.3)  # let handoffs get in flight
        os.kill(pid, signal.SIGKILL)

        ok_rids = []
        for rid, r in zip(rids, resps):
            try:
                out = r.result(timeout=120)
                assert len(out) == 6
                ok_rids.append(rid)
            except Exception:
                pass
        assert ok_rids, "every chaos request failed"

        # (2) the victim's last acts recovered post-mortem
        pm = None
        deadline = time.time() + 90
        while time.time() < deadline:
            st = serve.status()["llm_lifeline"]["LLMServer"]
            pm = st.get("postmortem")
            if pm and pm.get("replica") == victim:
                break
            time.sleep(1.0)
        assert pm and pm["replica"] == victim, f"no post-mortem: {pm}"
        assert pm["pid"] == pid
        assert len(pm["events"]) >= 32, (
            f"post-mortem tail too short: {len(pm['events'])}")
        pm_kinds = {e["kind"] for e in pm["events"]}
        assert pm_kinds & {"dispatch", "resume_submit", "kv_import",
                           "finish"}, pm_kinds

        # (3) the SLO snapshot: attainment per objective + burn rates
        slo = None
        deadline = time.time() + 60
        while time.time() < deadline:
            st = serve.status()["llm_lifeline"]["LLMServer"]
            slo = st.get("slo")
            if slo and (slo.get("availability") or {}).get("good"):
                break
            time.sleep(1.0)
        assert slo, "controller never published an slo snapshot"
        assert slo["config"]["availability"] == 0.5
        av = slo["availability"]
        assert av["good"] > 0 and "attained" in av
        assert set(av["burn_rate"]) == {"60s", "300s"}
        for key in ("ttft_p99_ms", "tpot_p99_ms"):
            assert slo[key]["target"] == 120_000.0
            assert "attained" in slo[key], f"{key} never observed"

        # (1) one migrated rid, one cluster-wide timeline
        rid = ok_rids[0]
        tl = serve.request_timeline(rid)
        kinds = [e["kind"] for e in tl]
        assert "kv_export" in kinds, kinds
        assert "kv_import" in kinds or "resume_submit" in kinds, kinds
        assert "finish" in kinds, kinds
        wheres = {e["where"] for e in tl if e.get("where")}
        assert len(wheres) >= 2, (
            f"timeline should span prefill AND decode replicas: {wheres}")
        ts = [e.get("t", 0.0) for e in tl]
        assert ts == sorted(ts)

        # ...and the merged Perfetto trace carries its lifeline row with
        # flow links chaining the hops
        events = obs.export_trace(str(tmp_path / "trace.json"))
        life = [e for e in events
                if e.get("pid") == "lifeline" and e.get("ph") == "X"
                and (e.get("args") or {}).get("rid") == rid]
        assert life, "no lifeline spans for the migrated rid in the trace"
        names = {e["name"] for e in life}
        assert any("kv_export" in n for n in names), names
        flows = [e for e in events
                 if str(e.get("id", "")).startswith(f"lifeline:{rid}:")]
        assert any(e["ph"] == "s" for e in flows), "no flow-link starts"
        assert any(e["ph"] == "f" for e in flows), "no flow-link ends"
        assert (tmp_path / "trace.json").stat().st_size > 0
    finally:
        tracing.disable()


# ------------------------------------------------ torn-read consistency
def test_metrics_and_routing_stats_are_consistent_copies():
    """Satellite: multi-counter reads are one locked copy, derived
    totals computed from the COPY — a concurrent writer can't tear
    hits+spills+misses against `total` (source-pinned + behavioral)."""
    import inspect

    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine as _Eng

    src = inspect.getsource(_Eng.metrics)
    assert "with self._m_lock" in src, (
        "engine.metrics() must snapshot counters under _m_lock")
    src = inspect.getsource(DeploymentHandle.routing_stats)
    assert "with self._lock" in src

    h = DeploymentHandle("dep", "app")
    out = h.routing_stats()
    assert out["total"] == (out["hits"] + out["spills"] + out["misses"]
                            + out["inv_hits"])
    out["hits"] += 999  # mutating the copy must not poison the handle
    assert h.routing_stats()["hits"] != out["hits"]
    h.close()
