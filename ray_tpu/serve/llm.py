"""Batched LLM serving deployment.

The packaged form of the TPU LLM-serving shape (the reference serves
LLMs through external engines inside replicas — vLLM in its examples;
here the engine is the jitted prefill + device-side decode loop of the
model's decode module, `cfg.decode_module.generate`: models/llama_decode's
for a LlamaConfig). Concurrent requests coalesce through
@serve.batch; within a batch, prompts are grouped by length so each
group runs one prefill + one lax.scan decode with static shapes and no
padding/masking complications. Shape churn is bounded by rounding
prompt-group lengths up to a bucket multiple, so the jit cache stays
small and warm.

Requests on the continuous path are either a bare token list (greedy,
engine defaults) or a dict carrying per-request SamplingParams fields:

    handle.remote({"prompt": [1, 2, 3], "temperature": 0.7,
                   "top_p": 0.9, "seed": 42, "stop": [2],
                   "max_new_tokens": 64, "session_id": "user-7"})

`session_id` is routing-only: with an `affinity_config` on the
deployment, the handle hashes it (or the prompt prefix) so a session's
repeat traffic lands on the replica whose radix cache is hot.

temperature/top-k/top-p sampling and stop tokens need the engine
(`continuous=True`): they run device-side inside the decode scan
(models/paged.sample_tokens).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.serve._internal.sampling import SamplingParams
from ray_tpu.serve.api import batch, deployment


def _parse_request(req, default_max_new: int):
    """Request-path coercion: bare prompt list or dict with sampling
    fields -> (prompt, max_new_tokens, SamplingParams, request_id)."""
    if isinstance(req, dict):
        body = dict(req)
        if "prompt" not in body:
            raise ValueError(
                f"dict request must carry a 'prompt' field "
                f"(got keys {sorted(body)})"
            )
        prompt = [int(t) for t in body.pop("prompt")]
        max_new = int(body.pop("max_new_tokens", default_max_new))
        # routing-only field: the handle/proxy affinity layer hashes it
        # to pick a cache-hot replica; the engine itself ignores it
        body.pop("session_id", None)
        # caller-generated request id (redispatch bookkeeping / logs)
        rid = body.pop("request_id", None)
        # relative deadline form: the handle normally stamps the
        # absolute `deadline` at submit (so a redispatch can't reset
        # the clock); direct engine callers may still pass deadline_s
        deadline_s = body.pop("deadline_s", None)
        if deadline_s is not None and body.get("deadline") is None:
            import time

            body["deadline"] = time.time() + float(deadline_s)
        known = {f.name for f in dataclasses.fields(SamplingParams)}
        unknown = set(body) - known
        if unknown:
            raise ValueError(
                f"unknown request field(s) {sorted(unknown)}; valid "
                f"sampling fields: {sorted(known)}"
            )
        return prompt, max_new, SamplingParams(**body), rid
    return [int(t) for t in req], default_max_new, SamplingParams(), None


class _LLMServer:
    """The deployment callable. Wrap with serve.deployment via
    `llm_deployment(...)` or subclass for custom param loading."""

    def __init__(self, cfg=None, params=None, max_new_tokens: int = 32,
                 checkpoint_dir: Optional[str] = None, seed: int = 0,
                 continuous: bool = False, n_slots: int = 8, chunk: int = 8,
                 macro_phases: int = 8, block_size: int = 16,
                 n_blocks: int = 0, prefix_cache: bool = True,
                 max_queue: Optional[int] = None,
                 draft_model=None, num_speculative_tokens: int = 0,
                 pool: Optional[str] = None,
                 cluster_cache: Optional[bool] = None,
                 digest_prefix_len: int = 32):
        import jax

        if pool is not None and not continuous:
            raise ValueError(
                "pool roles require the continuous engine "
                "(llm_deployment(continuous=True, pools=...))")

        if cfg is None:
            from ray_tpu.models.llama import LlamaConfig

            cfg = LlamaConfig.tiny()
        self.cfg = cfg
        if params is not None:
            self.params = params
        elif checkpoint_dir is not None:
            from ray_tpu.train.orbax_utils import load_pytree_from_checkpoint

            self.params = load_pytree_from_checkpoint(checkpoint_dir)
        else:
            # the config's own model module (llama, granite_hybrid, ...)
            self.params = cfg.model_module.init_params(
                jax.random.PRNGKey(seed), cfg)
        self.max_new_tokens = max_new_tokens
        self.engine = None
        self.pool = pool
        self._digest_prefix_len = digest_prefix_len
        # KV-plane state (disaggregated serving): exported payload refs
        # pinned until the decode pool acks, the lazy handle back into
        # this deployment's decode pool, the migration pump threads, and
        # the prefetch memo that rate-limits cluster-cache fetch attempts
        self._export_refs: Any = None
        self._decode_h: Any = None
        self._pump: Any = None
        self._prefetch_memo: Dict[str, float] = {}
        if continuous:
            # continuous batching: requests admit/evict per decode chunk,
            # with macro-step scheduling batching K chunks per dispatch
            # over a paged K/V pool (sampling, stop tokens, prefix reuse)
            import os

            from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

            self.engine = ContinuousBatchingEngine(
                self.params, self.cfg, n_slots=n_slots, chunk=chunk,
                macro_phases=macro_phases, block_size=block_size,
                n_blocks=n_blocks,
                prefix_cache=prefix_cache, max_queue=max_queue,
                # lossless draft-model speculation: draft_model is None
                # (off — the engine compiles the exact pre-speculation
                # program), "self", a LlamaConfig, or a dict with cfg +
                # params/checkpoint_dir/seed (see _internal/speculative)
                draft_model=draft_model,
                num_speculative_tokens=num_speculative_tokens,
                # disaggregated pool role + cluster-wide prefix cache
                role=pool, cluster_cache=cluster_cache,
                digest_prefix_len=digest_prefix_len,
                # pid-unique name: each replica's engine publishes its
                # own `engine:<name>` telemetry entry, so /api/serve
                # shows PER-REPLICA serving metrics (same-named engines
                # collide last-write-wins in the merged table)
                name=f"llm-{os.getpid()}",
            )
            # label this process's lifeline events with the replica
            # coordinates when serving (the engine name otherwise) —
            # request_timeline shows WHERE each hop ran
            try:
                from ray_tpu.observability import lifeline
                from ray_tpu.serve._internal import kv_plane

                lifeline.set_process_label(
                    kv_plane.current_replica_name()
                    or f"llm-{os.getpid()}")
            except Exception:
                pass

    def metrics(self) -> Dict[str, Any]:
        """Engine serving metrics (dispatches/token, lane occupancy,
        TTFT/TPOT percentiles); empty for the static-batching path."""
        return self.engine.metrics() if self.engine is not None else {}

    def request_timeline(self, rid: str) -> List[Dict[str, Any]]:
        """This replica's slice of one request's lifeline — the
        controller fans this RPC out across replicas and merges by rid
        into the cluster-wide timeline (serve.request_timeline)."""
        if self.engine is not None:
            return self.engine.request_timeline(rid)
        from ray_tpu.observability import lifeline

        return lifeline.events(rid)

    def __serve_load__(self) -> int:
        """Autoscaling load signal: the engine's resident + queued
        request count. The Replica wrapper publishes this through the
        telemetry path — with the direct-transport deferred-completion
        path, `handle_request` returns before generation finishes, so
        the replica's own in-flight counter can't see engine load."""
        return self.engine.load() if self.engine is not None else 0

    # -- KV plane (disaggregated pools + cluster prefix cache) ----------
    def __serve_pool_signals__(self) -> Optional[Dict[str, Any]]:
        """Per-pool autoscaling signals (queued prefill tokens / decode
        lane occupancy) published by the replica's report loop."""
        if self.engine is None:
            return None
        return self.engine.pool_signals()

    def __serve_kv_inventory__(self) -> List[str]:
        """Digests of prompt prefixes whose KV blocks live in this
        replica's radix cache — the telemetry payload other replicas'
        InventoryViews read to resolve cluster prefix-cache owners."""
        if self.engine is None:
            return []
        return self.engine.kv_inventory()

    def export_prefix_kv(self, digest) -> Optional[Dict[str, Any]]:
        """Peer RPC: gather the cached prefix behind `digest` and put it
        on the object plane. Returns {"tokens", "ref" (hex),
        "n_data_blocks", "block_size"} or None when the prefix was
        evicted since it was advertised. The ObjectRef is pinned in a
        bounded deque so the payload survives until the peer fetches it
        (ring eviction after 64 exports is a re-fetchable miss, not a
        correctness problem — the peer just sees a get timeout and skips
        the import)."""
        if self.engine is None:
            return None
        d = self.engine.export_prefix(digest)
        if d is None:
            return None
        if self._export_refs is None:
            from collections import deque

            self._export_refs = deque(maxlen=64)
        self._export_refs.append(d.pop("_ref"))
        return d

    def _decode_handle(self):
        """Lazy handle back into THIS deployment, pinned to the decode
        pool — the migration pump resubmits finished prefills through it
        so decode-replica death reuses the handle's classify/redispatch
        machinery instead of growing a second failure path."""
        if self._decode_h is None:
            from ray_tpu.serve._internal import kv_plane
            from ray_tpu.serve.handle import DeploymentHandle

            ctx = kv_plane.current_replica_context()
            if not ctx:
                raise RuntimeError(
                    "prefill replica has no serve context; cannot route "
                    "to the decode pool")
            h = DeploymentHandle(ctx["deployment"], ctx["app"])
            h._pool = "decode"
            self._decode_h = h
        return self._decode_h

    def _resume_body(self, req, rid) -> Dict[str, Any]:
        from ray_tpu.serve._internal import kv_plane

        exp = req.export
        return kv_plane.make_resume_body(
            prompt=req.prompt, first_token=req.tokens[0],
            max_new_tokens=req.max_new_tokens, sampling=req.sampling,
            ref_hex=exp["ref_hex"], n_data_blocks=exp["n_data_blocks"],
            block_size=exp["block_size"], rid=rid,
            t_export=exp["t_export"])

    def _chain_decode(self, req, rid) -> List[int]:
        """Synchronous second hop: ship the migrated request's resume
        body to the decode pool and wait for the full token list. Holds
        `req` (and so the exported ObjectRef) alive until the decode
        side replied — the put must outlive the peer's get."""
        resp = self._decode_handle().remote(self._resume_body(req, rid))
        try:
            return resp.result(timeout=120.0)
        finally:
            del req  # release the KV payload ref only after the reply

    def _pump_migration(self, req, rid, deferred) -> None:
        """Deferred-path second hop, off the engine loop thread: the
        handle call blocks on the decode pool, so it runs on the pump
        executor and completes the caller's deferred when decode
        finishes (or fails it with the typed error so the CALLER's
        handle can classify — by then the prefill output already
        escaped, so only the decode hop is retried, internally)."""
        if self._pump is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pump = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="kv-migrate")

        def _run():
            try:
                deferred.complete(self._chain_decode(req, rid))
            except Exception as e:
                deferred.fail(e)

        self._pump.submit(_run)

    def _maybe_prefetch_prefix(self, prompt: List[int],
                               rid: Optional[str] = None) -> None:
        """Cluster prefix-cache read path: ONE digest + ONE inventory
        probe per request (lint-pinned). If another replica advertises
        this prompt's prefix and it is not cached locally, fetch its KV
        blocks over the object plane and graft them into the local radix
        cache BEFORE submit, so admission's ordinary lookup() hits.
        Strictly best-effort: every failure path degrades to a local
        prefill, and a per-digest memo rate-limits repeat attempts."""
        import time as _time

        from ray_tpu.serve._internal import kv_plane

        eng = self.engine
        if eng is None or not getattr(eng, "_cluster_cache", False):
            return
        if self.pool == "decode" or len(prompt) < self._digest_prefix_len:
            return
        dig = kv_plane.prefix_digest(prompt, self._digest_prefix_len)
        if eng.has_local_prefix(dig):
            return
        owner = kv_plane.InventoryView.instance().owner_of(dig)
        if rid:
            # the probe's lifeline record: STILL one dict probe per
            # request — the event is per-request bookkeeping, not a
            # second lookup
            try:
                from ray_tpu.observability import lifeline

                lifeline.record(rid, "inventory_probe",
                                owner=owner or "", hit=owner is not None)
            except Exception:
                pass
        if owner is None or owner == kv_plane.current_replica_name():
            return
        now = _time.monotonic()
        last = self._prefetch_memo.get(str(dig))
        if last is not None and now - last < 5.0:
            return
        if len(self._prefetch_memo) > 512:
            self._prefetch_memo.clear()
        self._prefetch_memo[str(dig)] = now
        try:
            import ray_tpu

            peer = ray_tpu.get_actor(owner)
            exp = ray_tpu.get(
                peer.handle_request.remote("export_prefix_kv", (dig,), {}),
                timeout=10.0)
            if not exp:
                return
            payload = kv_plane.fetch_kv_payload(exp["ref"], timeout=10.0)
            eng.import_prefix(exp["tokens"], payload["k"], payload["v"],
                              exp["n_data_blocks"])
            if rid:
                from ray_tpu.observability import lifeline

                lifeline.record(rid, "prefix_import", owner=owner,
                                blocks=int(exp["n_data_blocks"]))
        except Exception:
            pass  # cluster cache is an optimization, never a failure

    @batch(max_batch_size=32, batch_wait_timeout_s=0.02)
    def _generate(self, prompts: List[List[int]]) -> List[List[int]]:
        # group by prompt length: each group is one static-shape
        # prefill + one device-side decode scan
        groups: Dict[int, List[int]] = {}
        for i, p in enumerate(prompts):
            groups.setdefault(len(p), []).append(i)
        out: List[Any] = [None] * len(prompts)
        for length, idxs in groups.items():
            arr = np.asarray([prompts[i] for i in idxs], np.int32)
            toks = self.cfg.decode_module.generate(
                self.params, arr, self.cfg, max_new_tokens=self.max_new_tokens
            )
            for row, i in enumerate(idxs):
                out[i] = toks[row].tolist()
        return out

    def _call_resume(self, body) -> Optional[List[int]]:
        """Decode-pool entry for a migrated request: ONE object-plane
        get resolves the prefill side's KV payload, then the request
        resumes mid-stream via submit_resumed (no admission control —
        the prefill pool already admitted it; shedding here would lose
        a request whose first token was already produced)."""
        from ray_tpu.serve._internal import kv_plane
        from ray_tpu.experimental.direct_transport import maybe_defer

        if self.engine is None:
            raise ValueError("__kv_resume__ requires the continuous engine")
        payload = kv_plane.fetch_kv_payload(body["ref"],
                                            rid=body.get("rid"))
        sampling = SamplingParams.from_request(body.get("sampling"))
        kw = dict(
            prompt=[int(t) for t in body["prompt"]],
            first_token=int(body["first"]),
            max_new_tokens=int(body["max_new_tokens"]),
            k=payload["k"], v=payload["v"],
            n_data_blocks=int(body["n_data_blocks"]),
            sampling=sampling, rid=body.get("rid"),
            t_export=body.get("t_export"),
        )
        deferred = maybe_defer()
        if deferred is not None:
            def _complete(req):
                if req.error is None:
                    deferred.complete(req.tokens)
                else:
                    deferred.fail(req.exc or RuntimeError(
                        f"generation failed: {req.error}"))

            self.engine.submit_resumed(on_done=_complete, **kw)
            return None
        req = self.engine.submit_resumed(**kw)
        if not req.done.wait(120.0):
            self.engine.cancel(req, "cancelled: resume timed out")
            raise TimeoutError("resumed generation timed out")
        if req.error is not None:
            raise req.exc or RuntimeError(f"generation failed: {req.error}")
        return req.tokens

    def __call__(self, request) -> Optional[List[int]]:
        from ray_tpu.serve._internal import kv_plane

        if kv_plane.is_resume_body(request):
            return self._call_resume(request)
        if self.engine is not None:
            prompt, max_new, sampling, rid = _parse_request(
                request, self.max_new_tokens
            )
            from ray_tpu.experimental.direct_transport import maybe_defer

            self._maybe_prefetch_prefix(prompt, rid=rid)
            deferred = maybe_defer()
            if deferred is not None:
                # direct-transport fast path: submit() enqueues onto the
                # engine loop and the completion notification rides the
                # reply ring FROM the engine loop thread — no replica
                # thread parks on the done event and the completion costs
                # one ring write instead of an object-store round trip
                def _complete(req):
                    if req.error is not None:
                        # typed failure when the engine recorded one
                        # (shed / deadline / replica-death) — the class
                        # crosses the ring pickled, so the handle's
                        # redispatch policy classifies by isinstance
                        deferred.fail(req.exc or RuntimeError(
                            f"generation failed: {req.error}"))
                    elif req.finish_reason == "migrated":
                        # prefill pool: the prompt pass is done and the
                        # KV payload is on the object plane — hand off
                        # to the decode pool off-loop; the caller's
                        # deferred completes when decode finishes
                        self._pump_migration(req, rid, deferred)
                    else:
                        deferred.complete(req.tokens)

                # a submit() raise (dead engine, shed, bad request)
                # propagates: the transport surfaces it and disarms the
                # deferred
                self.engine.submit(
                    prompt, max_new, on_done=_complete, sampling=sampling,
                    rid=rid,
                )
                return None
            req = self.engine.submit(prompt, max_new, sampling=sampling,
                                     rid=rid)
            if not req.done.wait(120.0):
                self.engine.cancel(req, "cancelled: generation timed out")
                raise TimeoutError(
                    "generation timed out (request cancelled)")
            if req.error is not None:
                raise req.exc or RuntimeError(
                    f"generation failed: {req.error}")
            if req.finish_reason == "migrated":
                return self._chain_decode(req, rid)
            return req.tokens
        if isinstance(request, dict):
            raise ValueError(
                "per-request sampling needs the continuous engine "
                "(llm_deployment(continuous=True))"
            )
        return self._generate([int(t) for t in request])


def llm_deployment(num_replicas: int = 1, max_new_tokens: int = 32,
                   cfg=None, checkpoint_dir: Optional[str] = None,
                   continuous: bool = False, n_slots: int = 8,
                   chunk: int = 8, macro_phases: int = 8,
                   block_size: int = 16, n_blocks: int = 0,
                   prefix_cache: bool = True,
                   max_queue: Optional[int] = None, draft_model=None,
                   num_speculative_tokens: int = 0,
                   pools: Optional[Dict[str, int]] = None,
                   cluster_cache: Optional[bool] = None,
                   digest_prefix_len: int = 32,
                   **deploy_kw):
    """A ready-to-run LLM generation application:

        app = llm_deployment(num_replicas=2, max_new_tokens=16)
        handle = serve.run(app, name="llm")
        handle.remote([1, 2, 3]).result()

    With continuous=True the replica runs the paged continuous-batching
    engine: requests may be dicts carrying SamplingParams fields
    (temperature/top_k/top_p/seed/stop/max_new_tokens, plus the
    relative `deadline_s` budget); `block_size` / `n_blocks` size the
    paged KV pool, `prefix_cache` toggles radix prompt-prefix reuse and
    `max_queue` bounds admission (excess requests shed with a typed
    retryable error instead of queueing unboundedly).

    `draft_model` + `num_speculative_tokens` turn on LOSSLESS
    draft-model speculative decoding (continuous=True only): a small draft
    model proposes num_speculative_tokens tokens per lane each round
    and the target verifies them all in one batched dispatch, emitting
    every accepted token plus one correction/bonus token. Greedy output
    is bit-identical to non-speculative decoding and sampled output
    draws from the exact same distribution — the knob trades draft
    FLOPs for fewer target dispatches, it never changes results.
    `draft_model` accepts "self" (the target drafts for itself — only
    useful for testing), "self:N" (self-speculative truncation: the
    target's own first N layers draft, zero extra weights), a
    LlamaConfig (random init), or a dict of
    {"cfg": LlamaConfig, "checkpoint_dir"/"params"/"seed": ...}. With
    draft_model=None the replica compiles a program with zero draft
    FLOPs — speculation off costs nothing.

    `pools={"prefill": P, "decode": D}` turns on DISAGGREGATED serving
    (continuous=True only): the deployment runs P prefill
    replicas (admission + prompt pass, compute-bound) and D decode
    replicas (the token loop, bandwidth-bound); finished prefills ship
    their KV blocks to a decode replica over the object plane and the
    request resumes mid-stream there. With pools set, `num_replicas` is
    ignored (the pool counts ARE the replica counts) and per-pool
    autoscaling targets can ride autoscaling_config={"pools": {...}}.
    `cluster_cache` (default: on, kill switch
    RAY_TPU_SERVE_CLUSTER_CACHE=0) makes the radix prefix cache
    cluster-wide: replicas advertise committed prefix digests through
    telemetry, the router prefers the owning replica, and misses fetch
    the owner's KV blocks instead of re-prefilling;
    `digest_prefix_len` is the token window the cluster cache keys on.

    Generation is side-effect-free, so the deployment opts into
    replica-death REDISPATCH by default: a request in flight on a
    SIGKILLed/wedged replica (from which no output can have escaped —
    results deliver only at completion) is requeued onto a survivor by
    the handle; pass fault_config={"redispatch": False} to disable."""
    deploy_kw.setdefault("fault_config", {"redispatch": True})
    if pools is not None:
        if not continuous:
            raise ValueError(
                "pools= requires continuous=True (disaggregated serving "
                "runs on the continuous-batching engine)")
        deploy_kw["pool_config"] = dict(pools)
    dep = deployment(
        _LLMServer, name="LLMServer", num_replicas=num_replicas, **deploy_kw
    )
    return dep.bind(cfg=cfg, max_new_tokens=max_new_tokens,
                    checkpoint_dir=checkpoint_dir, continuous=continuous,
                    n_slots=n_slots, chunk=chunk, macro_phases=macro_phases,
                    block_size=block_size, n_blocks=n_blocks,
                    prefix_cache=prefix_cache, max_queue=max_queue,
                    draft_model=draft_model,
                    num_speculative_tokens=num_speculative_tokens,
                    cluster_cache=cluster_cache,
                    digest_prefix_len=digest_prefix_len)
