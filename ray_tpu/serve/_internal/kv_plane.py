"""Cluster-wide KV plane: prefill→decode migration + prefix inventory.

Disaggregated serving (DistServe, Zhong et al.; Mooncake, Qin et al.)
splits one logical LLM deployment into two replica pools with opposite
resource profiles: PREFILL replicas run admission + prompt prefill only
(compute-bound, bursty), DECODE replicas run the token loop
(memory-bandwidth-bound, steady). The seam between them is KV state,
and this module is that seam:

- MIGRATION: a prefill replica finishes a request's prompt pass (one
  macro-step admission that samples the first token), lifts the
  request's KV blocks out of the paged pool as ONE pair of device
  arrays (models/paged.gather_kv_blocks), and ships them through
  the PR-12 zero-copy object plane with ONE put per handoff —
  never per-block serialization. The decode replica fetches with ONE
  get (dlpack, zero-copy on colocated hosts), scatters the slices into
  its own pool (import_kv_blocks), and the request resumes mid-stream
  in the paged macro-step engine with its first token, position,
  remaining budget and rng key intact. Sampled streams stay
  reproducible across the hop because the carried rng key is a pure
  function of the request seed (carried_rng_for_seed mirrors
  admit_slots_paged's split), not device state that would have to ride
  the payload.
- FAILURE SEMANTICS: the prefill replica holds the exported ObjectRef
  until the decode replica's reply lands, so a decode replica SIGKILLed
  mid-handoff surfaces as a typed ReplicaDiedError(started=False) at
  the internal handle — no output escaped (results deliver only at
  completion), the resume body redispatches to a surviving decode
  replica, and the payload is still fetchable from the exporter-owned
  object store.
- CLUSTER-WIDE PREFIX CACHE: every engine registers the digests of the
  prompt prefixes its radix cache committed; the Replica stat reporter
  publishes that inventory through the PR-4 telemetry path, and the
  process-wide InventoryView polls the merged table so (a) the PR-8
  affinity router can consult the inventory BEFORE consistent-hashing
  (a prefix prefilled anywhere routes its repeat traffic to the replica
  that owns it) and (b) a replica that misses locally can fetch the
  committed blocks from the owner (export→put→get→scatter, the same
  one-put/one-get discipline) instead of re-prefilling them. The digest
  is bit-identical to the handle's affinity digest, so the router's key
  IS the inventory key.

Everything here is host-side policy over PR-7 primitives: the pool
stays (L, n_blocks, bs, kvh, hd), block 0 stays the garbage-safe null
block (bucket padding aims at it on both ends of the wire), and
allocator/trie mutation stays on the engine loop thread.
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

NULL_BLOCK = 0

# replica-name context: ONE serve replica actor lives per worker
# process, so the controller's Replica wrapper records its actor name
# here before constructing the user instance — the LLM server reads it
# back to learn its own (app, deployment, replica) coordinates without
# threading them through user init kwargs
_replica_name: List[Optional[str]] = [None]


def set_replica_name(name: Optional[str]) -> None:
    _replica_name[0] = name


def current_replica_name() -> Optional[str]:
    return _replica_name[0]


def current_replica_context() -> Dict[str, str]:
    """Parse this process's ``SERVE_REPLICA::<app>::<dep>::<n>`` actor
    name into {replica, app, deployment}; {} outside a replica."""
    name = _replica_name[0]
    if not name:
        return {}
    parts = name.split("::")
    if len(parts) < 4 or parts[0] != "SERVE_REPLICA":
        return {}
    return {"replica": name, "app": parts[1], "deployment": parts[2]}


def cluster_cache_enabled(knob: Optional[bool]) -> bool:
    """Resolve the cluster-cache kill switch: an explicit deployment
    knob wins; otherwise the RAY_TPU_SERVE_CLUSTER_CACHE env var
    (default on). The off state must cost zero RPCs — callers gate
    every inventory/fetch path on this."""
    if knob is not None:
        return bool(knob)
    return os.environ.get("RAY_TPU_SERVE_CLUSTER_CACHE", "1") not in (
        "0", "false", "off")


# ------------------------------------------------------------- digests
def prefix_digest(tokens: Sequence[int], prefix_len: int) -> int:
    """The cluster cache key for a prompt prefix — BIT-IDENTICAL to the
    handle's affinity digest (serve/handle.py _affinity_digest), so the
    router's hash key doubles as the inventory lookup key with zero
    extra hashing."""
    data = b" ".join(str(int(t)).encode() for t in tokens[:prefix_len])
    return int.from_bytes(hashlib.md5(data).digest()[:8], "big")


def carried_rng_for_seed(seed: int):
    """Host-side recompute of the rng key a sampled slot carries after
    admission: admit_slots_paged seeds PRNGKey(seed), splits once, uses
    pair[1] for the first token and stores pair[0] ("carried") in the
    slot. Recomputing it from the seed is exact — so a migration never
    ships device rng state (which could already belong to a reused
    slot by export time)."""
    import jax
    import numpy as np

    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    carried = jax.random.split(key)[0]
    return np.asarray(carried, np.uint32)


# ---------------------------------------------------------- block wire
def pad_block_ids(blocks: Sequence[int]) -> "Any":
    """Bucket block-id lists to powers of two (null-block padded) so
    the gather/scatter jit variants stay bounded: exporter and importer
    call the same function, so the shipped array shape always matches
    the importer's scatter plan."""
    import numpy as np

    n = max(1, len(blocks))
    b = 1
    while b < n:
        b *= 2
    out = np.full(b, NULL_BLOCK, np.int32)
    out[: len(blocks)] = blocks
    return out


def export_kv_blocks(cache: Dict[str, Any], blocks: Sequence[int],
                     rid: Optional[str] = None):
    """Lift `blocks` out of a paged pool and publish them to the object
    plane. ONE fused gather dispatch + ONE ray_tpu.put per call — the
    migration hot path's pinned cost (tests/test_lint_kv_plane.py).
    Returns (ObjectRef, padded_width). The put serializes via the
    dlpack path, which synchronizes on the gather's result, so callers
    may free the source blocks the moment this returns. `rid` stamps a
    ``kv_put`` event on the request's lifeline (per-handoff, never
    per-block — the lint budget is unchanged)."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.models import paged

    ids = pad_block_ids(blocks)
    k, v = paged.jitted_gather_kv_blocks()(cache, jnp.asarray(ids))
    ref = ray_tpu.put({"k": k, "v": v, "n": len(blocks)})
    if rid:
        try:
            from ray_tpu.observability import lifeline

            lifeline.record(rid, "kv_put", blocks=len(blocks),
                            ref=ref.hex()[:16], a=float(len(blocks)))
        except Exception:
            pass
    return ref, len(ids)


def fetch_kv_payload(ref_hex: str, timeout: float = 30.0,
                     rid: Optional[str] = None) -> Dict[str, Any]:
    """The import side's ONE object-plane get: resolve the exporter's
    ref (hex form — refs ride request bodies as strings) into the
    {"k", "v", "n"} payload of device arrays. `rid` stamps a
    ``resume_fetch`` event on the request's lifeline."""
    import ray_tpu
    from ray_tpu._private.object_ref import ObjectRef

    t0 = time.perf_counter()
    payload = ray_tpu.get(ObjectRef(bytes.fromhex(ref_hex)), timeout=timeout)
    if rid:
        try:
            from ray_tpu.observability import lifeline

            lifeline.record(rid, "resume_fetch", ref=ref_hex[:16],
                            fetch_ms=round(
                                (time.perf_counter() - t0) * 1e3, 3),
                            a=(time.perf_counter() - t0) * 1e3)
        except Exception:
            pass
    return payload


# ---------------------------------------------------------- resume body
def make_resume_body(prompt: Sequence[int], first_token: int,
                     max_new_tokens: int, sampling, ref_hex: str,
                     n_data_blocks: int, block_size: int,
                     rid: Optional[str] = None,
                     t_export: Optional[float] = None) -> Dict[str, Any]:
    """The migration handoff request: a plain dict the decode replica's
    __call__ recognizes by the __kv_resume__ marker. `prompt` rides at
    the top level so the internal handle's affinity digest (and thus
    the decode pool's cache-affinity routing) works unchanged on resume
    bodies."""
    import dataclasses

    return {
        "__kv_resume__": True,
        "ref": ref_hex,
        "prompt": [int(t) for t in prompt],
        "first": int(first_token),
        "max_new_tokens": int(max_new_tokens),
        "sampling": dataclasses.asdict(sampling),
        "n_data_blocks": int(n_data_blocks),
        "block_size": int(block_size),
        "rid": rid,
        "t_export": t_export,
    }


def is_resume_body(request) -> bool:
    return isinstance(request, dict) and bool(request.get("__kv_resume__"))


# ------------------------------------------------------------ inventory
class InventoryView:
    """Process-wide read model of every replica's published block
    inventory (prefix digests), refreshed from the merged GCS `serve`
    telemetry table on a background thread. Consumers pay ONE dict
    probe per lookup (`owner_of`) — never an RPC on the request path;
    the refresher's single fetch_snapshots round trip per period is the
    entire cluster-wide cost, identical in shape to the controller's
    autoscaler feed.

    Staleness is bounded by the refresh period + the reporters' publish
    cadence (~0.5–2 s): a stale positive costs one failed fetch that
    falls back to a local prefill, a stale negative costs one re-route
    through the plain affinity ring — both safe."""

    _instance: Optional["InventoryView"] = None
    _instance_lock = threading.Lock()

    def __init__(self, period_s: float = 1.0):
        self.period_s = period_s
        self._owners: Dict[str, str] = {}   # str(digest) -> replica name
        self._pools: Dict[str, str] = {}    # replica name -> pool role
        self._t_refresh = 0.0
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    @classmethod
    def instance(cls) -> "InventoryView":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def _ensure_thread(self) -> None:
        if self._thread is not None:
            return
        with self._lock:
            if self._thread is None:
                t = threading.Thread(
                    target=self._poll_loop, daemon=True,
                    name="kv-plane-inventory")
                self._thread = t
                t.start()

    def _poll_loop(self) -> None:
        while True:
            try:
                self.refresh_now()
            except Exception:
                pass
            time.sleep(self.period_s)

    def refresh_now(self) -> None:
        """One merged-table fetch -> atomic swap of the lookup dicts
        (readers never take the lock: dict replacement is atomic)."""
        from ray_tpu.observability import fetch_snapshots

        owners: Dict[str, str] = {}
        pools: Dict[str, str] = {}
        for snap in fetch_snapshots("serve", timeout=2.0).values():
            if not isinstance(snap, dict):
                continue
            for key, val in snap.items():
                if (not isinstance(key, str)
                        or not key.startswith("replica:")
                        or not isinstance(val, dict)):
                    continue
                name = key[len("replica:"):]
                pool = val.get("pool")
                if pool:
                    pools[name] = pool
                for d in val.get("kv_inventory") or ():
                    # first writer wins per refresh; any owner works —
                    # the payload is the same prefix KV everywhere
                    owners.setdefault(str(d), name)
        self._owners = owners
        self._pools = pools
        self._t_refresh = time.monotonic()

    def owner_of(self, digest) -> Optional[str]:
        """Replica name owning `digest`'s prefix blocks — ONE dict
        probe (the request-path budget the lint test pins)."""
        self._ensure_thread()
        return self._owners.get(str(digest))

    def pool_of(self, replica_name: str) -> Optional[str]:
        return self._pools.get(replica_name)


# -------------------------------------------------- engine-side ledger
class PrefixInventory:
    """An engine's OWN registry of committed prefix digests: digest ->
    the exact committed token prefix (what a peer needs to walk this
    engine's radix trie for the export). Capped LRU; the publishable
    digest list is what rides the telemetry payload. Mutated only on
    the engine loop thread; published via an atomic list snapshot."""

    def __init__(self, prefix_len: int = 32, cap: int = 512):
        self.prefix_len = prefix_len
        self.cap = cap
        self._entries: "OrderedDict[str, Tuple[int, ...]]" = OrderedDict()
        self._digests: List[str] = []

    def register(self, tokens: Sequence[int], n_committed_tokens: int) -> None:
        """Record a committed prefix if it covers at least one full
        digest window (shorter commits can't be cluster keys — the
        router hashes prefix_len tokens)."""
        if n_committed_tokens < self.prefix_len:
            return
        d = str(prefix_digest(tokens, self.prefix_len))
        committed = tuple(int(t) for t in tokens[:n_committed_tokens])
        self._entries.pop(d, None)
        self._entries[d] = committed
        while len(self._entries) > self.cap:
            self._entries.popitem(last=False)
        self._digests = list(self._entries)

    def tokens_for(self, digest) -> Optional[Tuple[int, ...]]:
        return self._entries.get(str(digest))

    def __contains__(self, digest) -> bool:
        return str(digest) in self._entries

    def published(self) -> List[str]:
        """JSON-safe digest list for the replica's telemetry payload
        (atomic snapshot — the stat reporter runs off-loop)."""
        return self._digests
