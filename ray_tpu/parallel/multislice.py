"""Multislice training: device islands joined by host-mediated DCN
collectives.

The reference scales past one machine with multi-node process groups
(reference: python/ray/train/torch/config.py:47-99 — TCP rendezvous +
NCCL over the inter-node fabric). The TPU equivalent of "many machines"
is MULTISLICE: each slice is an ICI domain where XLA emits fast
collectives from sharding annotations; between slices there is only
DCN, which XLA cannot schedule over without megascale support — so the
inter-slice hop is HOST-MEDIATED, exactly where the reference's NCCL
allreduce sat (SURVEY §2.4 comm row; §7 phase 7).

Shape of a step (data parallel across slices, any strategy within):

  1. per slice: one jitted SPMD program computes loss + gradients on
     that slice's mesh — intra-slice reductions are XLA ICI ops
  2. gradients cross slices leaf-by-leaf through the host: D2H fetch,
     float32-accumulated mean across slices, H2D push in the leaf's
     own dtype — streamed so a leaf's DCN transfer overlaps the next
     leaf's D2H (and, multi-host, each leaf rides
     `ray_tpu.util.collective.allreduce` between slice leaders over the
     object plane)
  3. per slice: a jitted apply step (optimizer update, state donated)

Gradient parity: a dcn_dp=N split of a batch produces bit-comparable
updates to one mesh over all devices, because mean-over-slices of
per-slice mean-gradients equals the global mean. test_multislice
asserts this on the 8-device virtual CPU mesh (2 islands of 4).

ELASTIC MODE (round 9): slices are PREEMPTIBLE. With `elastic=True`
the step survives a slice dying mid-run:

  degrade   — each slice's work runs under a bounded-timeout probe
              (`probe_timeout_s`; a slice's FIRST dispatch — cold jit
              cache, compilation in flight — is judged against
              max(probe_timeout_s, compile_grace_s) instead, so
              a compiling slice never reads as hung); a slice that
              raises SlicePreempted or times out is marked dead, the
              membership GENERATION is bumped, and the DCN mean's
              denominator rescales to the survivors. Contributions are generation-stamped at
              dispatch: a hung slice's gradients arriving AFTER it was
              declared dead belong to a stale generation and are
              rejected, never mixed into an update.
  re-admit  — `readmit(s, states)` (or the injector's revive schedule)
              broadcasts a survivor's full state D2H → H2D onto the
              returning slice's meshes/shardings, re-stamps its
              generation, and optionally warms its programs back up.
  accounting— every phase (detect / regang / restore / recompile) is
              billed to a GoodputMeter (train/goodput.py) surfaced via
              /api/training (on the chip: not measured, no cell of
              benchmark/run.py loses a slice).

Within a slice, rank-level failures remain the ElasticCoordinator's
job (train/elastic.py): each slice's host gang regangs ranks behind
this class's back; this class only sees the slice-level outcome (the
slice answers its probe or it doesn't). The two compose: rank death →
coordinator regang inside the slice; slice death → degrade here.
"""
from __future__ import annotations

import functools
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import LogicalAxisRules
from ray_tpu.train.fault_injection import SlicePreempted


def split_devices(devices: Sequence, n_slices: int) -> List[List]:
    """Partition the device list into contiguous islands (contiguous
    blocks share ICI on real hardware; the virtual CPU mesh just needs
    determinism)."""
    if len(devices) % n_slices:
        raise ValueError(f"{len(devices)} devices not divisible into {n_slices} slices")
    per = len(devices) // n_slices
    return [list(devices[i * per : (i + 1) * per]) for i in range(n_slices)]


class MultisliceTrainStep:
    """Drives N slice meshes through grad / DCN-allreduce / apply.

    `collective_group` switches the inter-slice hop: None (default)
    means the slices are co-hosted in this process and the mean runs in
    numpy; a group name means each slice leader calls
    `ray_tpu.util.collective.allreduce` per leaf (multi-host mode — the
    veneer chunks through the object plane).

    `elastic=True` arms slice-granular preemption tolerance (see module
    docstring): per-slice bounded-timeout probes, degrade-to-survivors
    with a generation-stamped DCN denominator, `readmit()` recovery,
    and goodput accounting. `injector` (train/fault_injection.py) is
    the deterministic chaos hook the tests and bench drive.
    """

    def __init__(
        self,
        cfg,
        slice_meshes: List,
        strategy: str = "dp",
        learning_rate: float = 3e-4,
        weight_decay: float = 0.1,
        grad_clip: float = 1.0,
        model=None,
        collective_group: Optional[str] = None,
        elastic: bool = False,
        probe_timeout_s: float = 5.0,
        compile_grace_s: float = 120.0,
        injector=None,
        goodput_meter=None,
        on_membership_change: Optional[Callable[[int, List[bool]], None]] = None,
    ):
        from ray_tpu.models import llama as L

        self.model = model or L
        self.cfg = cfg
        self.meshes = slice_meshes
        self.n_slices = len(slice_meshes)
        self.collective_group = collective_group
        rules = LogicalAxisRules.for_strategy(strategy)
        self.rules = rules
        axes = self.model.logical_axes(cfg)

        # ---- elastic membership state
        self.elastic = elastic
        self.probe_timeout_s = probe_timeout_s
        self.compile_grace_s = compile_grace_s
        self.injector = injector
        self.alive: List[bool] = [True] * self.n_slices
        # a COLD slice's first dispatch pays XLA compilation (tens of
        # seconds on real TPU) — judged by the steady-state probe
        # timeout it would read as hung, so cold dispatches get
        # max(probe_timeout_s, compile_grace_s) instead
        self._warm: List[bool] = [False] * self.n_slices
        self.generation = 0
        # generation each slice's state was last stamped at: a grad
        # contribution is accepted only if its slice's stamp is current
        self._slice_gen: List[int] = [0] * self.n_slices
        self._host_step = 0
        self.recovery_log: List[Dict[str, Any]] = []
        self._on_membership_change = on_membership_change
        self._last_batches: Optional[List[Any]] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        if elastic:
            from ray_tpu.train.goodput import GoodputMeter

            self.goodput = (goodput_meter or GoodputMeter()).start()
            self._pool = ThreadPoolExecutor(
                max_workers=self.n_slices, thread_name_prefix="slice"
            )
        else:
            self.goodput = goodput_meter

        self.tx = optax.chain(
            optax.clip_by_global_norm(grad_clip),
            optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=weight_decay),
        )

        is_axes_leaf = lambda x: isinstance(x, tuple) and all(
            isinstance(i, (str, type(None))) for i in x
        )
        self._param_shardings = [
            jax.tree.map(lambda ax: rules.named_sharding(m, ax), axes, is_leaf=is_axes_leaf)
            for m in slice_meshes
        ]
        self._batch_shardings = [
            rules.named_sharding(m, ("batch", None)) for m in slice_meshes
        ]

        model_loss = self.model.loss_fn

        def loss(params, batch, mesh):
            return model_loss(params, batch, cfg, mesh, rules)

        # one grad program and one apply program PER SLICE mesh: the
        # gradient leaves surface on the host between them — that seam
        # IS the DCN hop
        self._grad_fns = [
            jax.jit(functools.partial(jax.value_and_grad(loss), mesh=m))
            for m in self.meshes
        ]
        tx = self.tx

        @functools.partial(jax.jit, donate_argnums=(0,))
        def apply_fn(state, grads):
            updates, opt = tx.update(grads, state["opt"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            return {"params": params, "opt": opt, "step": state["step"] + 1}

        self._apply_fn = apply_fn

    # ------------------------------------------------------------ state
    def init_states(self, rng) -> List[Dict[str, Any]]:
        """Identical initial params on every slice, each laid out on its
        own mesh — ONE host-side init, n_slices device_puts."""
        host_params = self.model.init_params(rng, self.cfg)
        states = []
        for shardings in self._param_shardings:
            params = jax.tree.map(lambda p, sh: jax.device_put(p, sh), host_params, shardings)
            opt = jax.jit(self.tx.init)(params)
            states.append({"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)})
        return states

    def shard_batches(self, batch) -> List[Any]:
        """Split the global batch along axis 0 into EQUAL per-slice
        shards, each placed on its slice's mesh. Equal split is a
        correctness requirement, not a convenience: the DCN hop averages
        per-slice mean gradients with equal weight, so uneven shards
        would silently bias the update away from the single-mesh
        reference. Dead slices still get their shard carved out (and
        dropped at dispatch) so the surviving updates stay comparable
        run-to-run at fixed global batch."""
        sizes = {int(np.asarray(x).shape[0]) for x in jax.tree.leaves(batch)}
        for n in sizes:
            if n % self.n_slices:
                raise ValueError(
                    f"batch axis 0 ({n}) not divisible by dcn_dp={self.n_slices}"
                )
        splits = jax.tree.map(lambda x: np.array_split(np.asarray(x), self.n_slices), batch)
        out = []
        for i, sharding in enumerate(self._batch_shardings):
            host_shard = jax.tree.map(
                lambda parts: parts[i], splits, is_leaf=lambda x: isinstance(x, list)
            )
            if not self.alive[i]:
                # dead slice: keep its shard HOST-resident (no device to
                # place it on); readmit() puts it on the returning mesh
                out.append(host_shard)
                continue
            out.append(jax.tree.map(lambda p: jax.device_put(p, sharding), host_shard))
        return out

    def _place_batch(self, s: int, batch: Any) -> Any:
        """Device_put a (possibly host-resident) batch shard onto slice
        `s`'s mesh; already-placed jax arrays pass through untouched."""
        if batch is None:
            return None
        sharding = self._batch_shardings[s]
        return jax.tree.map(
            lambda x: x if isinstance(x, jax.Array) else jax.device_put(x, sharding),
            batch,
        )

    # ---------------------------------------------------- DCN allreduce
    def _dcn_mean(self, grads_per_slice: List[Any], slice_ids: Optional[List[int]] = None) -> List[Any]:
        """Leaf-streamed host allreduce across the contributing slices.
        Every leaf is fetched (D2H), accumulated in FLOAT32 (bf16
        accumulation loses mantissa bits as the slice count grows —
        mean-of-8 bf16 slices drifted past 1e-2 relative), averaged,
        and pushed back to each contributor (H2D) cast to the leaf's
        own dtype; jax's async dispatch lets leaf k+1's device work
        overlap leaf k's host mean. Multi-host mode replaces the numpy
        mean with the collective veneer's allreduce between slice
        leaders (also in float32). `slice_ids` names the contributing
        slices (default: all) — in elastic mode the denominator is the
        SURVIVOR count, which keeps the update the unbiased mean of
        the gradients that were actually computed."""
        n = len(grads_per_slice)
        flats, treedef = [], None
        for g in grads_per_slice:
            leaves, treedef = jax.tree.flatten(g)
            flats.append(leaves)
        n_leaves = len(flats[0])
        reduced: List[List[Any]] = [[] for _ in range(n)]
        for k in range(n_leaves):
            host = [np.asarray(flats[s][k]) for s in range(n)]
            leaf_dtype = host[0].dtype
            acc_dtype = np.float64 if leaf_dtype == np.float64 else np.float32
            acc = host[0].astype(acc_dtype)
            for h in host[1:]:
                acc = acc + h.astype(acc_dtype)
            acc /= n
            if self.collective_group is not None:
                # multi-host: the local mean joins the cross-process
                # MEAN through the object plane (every participant must
                # host the same number of local slices for mean-of-means
                # to equal the global mean)
                from ray_tpu.util import collective

                acc = collective.allreduce(acc, self.collective_group, op="MEAN")
            mean = acc.astype(leaf_dtype)
            # push the reduced leaf back onto each slice with the leaf's
            # original sharding so the apply step needs no reshard
            for s in range(n):
                reduced[s].append(jax.device_put(mean, flats[s][k].sharding))
        return [jax.tree.unflatten(treedef, reduced[s]) for s in range(n)]

    # ------------------------------------------------- elastic internals
    def _live_slices(self) -> List[int]:
        return [s for s in range(self.n_slices) if self.alive[s]]

    def _mark_dead(self, s: int, kind: str, detect_s: float) -> None:
        """Membership change: slice `s` is out. Bumping the generation
        invalidates any in-flight contribution stamped before the
        change (the stale-grad rejection the module docstring
        promises)."""
        if not self.alive[s]:
            return
        self.alive[s] = False
        self._warm[s] = False  # a returning slice process compiles afresh
        if kind == "hung" and self._pool is not None:
            # the wedged worker thread never frees its pool slot; a
            # fixed-size pool would queue healthy work behind it after a
            # readmit and falsely time IT out too. Replace the pool —
            # shutdown(wait=False) leaves in-flight futures (this step's
            # other slices) running to completion on the old threads.
            old = self._pool
            self._pool = ThreadPoolExecutor(
                max_workers=self.n_slices, thread_name_prefix="slice"
            )
            old.shutdown(wait=False)
        if self.goodput is not None:
            self.goodput.add_lost("detect", detect_s)
        t0 = time.perf_counter()
        self.generation += 1
        if self._on_membership_change is not None:
            try:
                self._on_membership_change(self.generation, list(self.alive))
            except Exception:
                pass
        if self.goodput is not None:
            self.goodput.add_lost("regang", time.perf_counter() - t0)
            self.goodput.recovery_event()
        self.recovery_log.append(
            {"event": "degrade", "slice": s, "kind": kind, "step": self._host_step,
             "generation": self.generation, "survivors": self._live_slices()}
        )
        if self.goodput is not None:
            self.goodput.publish()
        if not any(self.alive):
            raise RuntimeError(
                "all slices preempted — no survivor holds the state; "
                "restore from the latest disk checkpoint "
                "(train/checkpoint_manager.py)"
            )

    def readmit(self, s: int, states: List[Dict[str, Any]], *, warmup: bool = True) -> None:
        """Bring a recovered slice back into the gang: broadcast a
        survivor's params/opt state onto `s`'s mesh (D2H → H2D), stamp
        its generation current, and (optionally) warm its step program
        so the recompile cost is billed to recovery, not to the next
        training step."""
        if self.alive[s]:
            return
        donor = self._live_slices()[0]
        meter = self.goodput
        t0 = time.perf_counter()
        self.generation += 1
        self.alive[s] = True
        if self._on_membership_change is not None:
            try:
                self._on_membership_change(self.generation, list(self.alive))
            except Exception:
                pass
        if meter is not None:
            meter.add_lost("regang", time.perf_counter() - t0)

        t0 = time.perf_counter()
        mesh_s = self.meshes[s]

        def _broadcast(x):
            from jax.sharding import NamedSharding

            spec = x.sharding.spec
            return jax.device_put(np.asarray(x), NamedSharding(mesh_s, spec))

        states[s] = jax.tree.map(_broadcast, states[donor])
        jax.block_until_ready(states[s])
        if meter is not None:
            meter.add_lost("restore", time.perf_counter() - t0)

        t0 = time.perf_counter()
        if warmup and self._last_batches is not None and self._last_batches[s] is not None:
            # first dispatch on a returning slice pays compilation (a
            # fresh slice process has a cold jit cache); running it here
            # books that cost as `recompile` recovery, and the grads are
            # discarded — state is untouched
            try:
                self._last_batches[s] = self._place_batch(s, self._last_batches[s])
                l, g = self._grad_fns[s](states[s]["params"], self._last_batches[s])
                jax.block_until_ready(l)
                self._warm[s] = True  # compile paid here, not by the next step
            except Exception:
                pass
        if meter is not None:
            meter.add_lost("recompile", time.perf_counter() - t0)
            meter.recovery_event()
        self._slice_gen[s] = self.generation
        self.recovery_log.append(
            {"event": "readmit", "slice": s, "donor": donor, "step": self._host_step,
             "generation": self.generation, "survivors": self._live_slices()}
        )
        if meter is not None:
            meter.publish()

    def probe_slices(self, timeout_s: Optional[float] = None) -> Dict[int, bool]:
        """Bounded-timeout health probe: a trivial jitted op per live
        slice must complete within `timeout_s`. Hung slices (device
        wedged, host thread stuck) show up here without blocking the
        caller forever — the detection primitive behind elastic mode."""
        timeout_s = timeout_s or self.probe_timeout_s
        pool = self._pool or ThreadPoolExecutor(max_workers=self.n_slices)
        out: Dict[int, bool] = {}

        def _probe(idx):
            if self.injector is not None:
                self.injector.check(idx, self._host_step)
            x = jax.device_put(np.ones((), np.float32), self.meshes[idx].devices.flat[0])
            return float(jnp.asarray(x) + 1.0)

        futs = {s: pool.submit(_probe, s) for s in self._live_slices()}
        for s, f in futs.items():
            try:
                f.result(timeout=timeout_s)
                out[s] = True
            except Exception:  # timeout, SlicePreempted, device error
                out[s] = False
        if self._pool is None:
            pool.shutdown(wait=False)
        return out

    def maintenance_notice(self) -> List[int]:
        """Slices with an advance maintenance notice pending at the
        current step (injector-fed; on real TPU this is the maintenance
        event API). The train loop's cue for a PRIORITY checkpoint."""
        if self.injector is None:
            return []
        return sorted(
            {e.slice_idx for e in self.injector.maintenance_notice(self._host_step)}
        )

    # ------------------------------------------------------------- step
    def step(self, states: List[Dict], batches: List[Any]) -> Tuple[List[Dict], Dict]:
        """One multislice step: grads on every live slice, host-mediated
        mean over the survivors, per-slice apply. Returns (states,
        metrics) with the loss averaged across contributing slices.
        Dead slices' states pass through untouched (stale by design —
        they are overwritten at readmit)."""
        if not self.elastic:
            results = [f(st["params"], b) for f, st, b in zip(self._grad_fns, states, batches)]
            losses = [r[0] for r in results]
            grads = self._dcn_mean([r[1] for r in results])
            new_states = [self._apply_fn(st, g) for st, g in zip(states, grads)]
            loss = float(np.mean([np.asarray(l) for l in losses]))
            return new_states, {"loss": loss, "step": int(np.asarray(new_states[0]["step"]))}
        return self._elastic_step(states, batches)

    def _elastic_step(self, states: List[Dict], batches: List[Any]) -> Tuple[List[Dict], Dict]:
        step_idx = self._host_step
        self._last_batches = batches

        # re-admit slices whose outage is over (injector-scheduled; a
        # real deployment calls readmit() when the slice re-registers)
        if self.injector is not None:
            for s in sorted(self.injector.revivable(step_idx)):
                if not self.alive[s]:
                    self.readmit(s, states)
                    # the shard arrived host-resident while the slice was
                    # dead — place it on the re-admitted mesh now
                    batches[s] = self._place_batch(s, batches[s])

        live = self._live_slices()
        gen_at_dispatch = {s: self._slice_gen[s] for s in live}

        def _work(s):
            if self.injector is not None:
                self.injector.check(s, step_idx)
            l, g = self._grad_fns[s](states[s]["params"], batches[s])
            # surface device/program failure inside the probe window
            jax.block_until_ready(l)
            return l, g

        futs = {s: self._pool.submit(_work, s) for s in live}
        results: Dict[int, Tuple[Any, Any]] = {}
        for s, f in futs.items():
            timeout = (
                self.probe_timeout_s
                if self._warm[s]
                else max(self.probe_timeout_s, self.compile_grace_s)
            )
            t0 = time.perf_counter()
            try:
                results[s] = f.result(timeout=timeout)
                self._warm[s] = True
            except SlicePreempted as e:
                self._mark_dead(s, e.kind, time.perf_counter() - t0)
            except FutureTimeoutError:
                # bounded-timeout probe tripped: the slice is hung. Its
                # thread may still deliver a result later — stamped with
                # the pre-death generation, so it can never be accepted.
                self._mark_dead(s, "hung", time.perf_counter() - t0)
            except Exception:
                self._mark_dead(s, "error", time.perf_counter() - t0)

        # generation-stamped acceptance: only contributions whose slice
        # is still alive AND whose stamp is unchanged since dispatch.
        # In THIS in-process harvest the filter is a defensive
        # invariant — a timed-out future's late result is simply never
        # read, so no stale path reaches here today — but the stamp is
        # the protocol a distributed harvest (late RPC replies from a
        # declared-dead slice) must check, and it guards refactors
        # where _mark_dead stops raising on total loss.
        accepted = [
            s for s in results
            if self.alive[s] and self._slice_gen[s] == gen_at_dispatch[s]
        ]
        if not accepted:
            # every contribution died this step: nothing to apply
            self.goodput.step_done(degraded=True)
            self._host_step += 1
            return states, {
                "loss": float("nan"), "step": int(np.asarray(states[self._live_slices()[0]]["step"])),
                "n_live": len(self._live_slices()), "generation": self.generation,
                "degraded": True, "applied": False,
            }

        grads = self._dcn_mean([results[s][1] for s in accepted], slice_ids=accepted)
        new_states = list(states)
        for j, s in enumerate(accepted):
            new_states[s] = self._apply_fn(states[s], grads[j])
        loss = float(np.mean([np.asarray(results[s][0]) for s in accepted]))
        degraded = len(accepted) < self.n_slices
        self.goodput.step_done(degraded=degraded)
        self._host_step += 1
        if self._host_step % 32 == 0:
            # live goodput on /api/training (queued to the background
            # flusher — never blocks the step)
            self.goodput.publish()
        metrics = {
            "loss": loss,
            "step": int(np.asarray(new_states[accepted[0]]["step"])),
            "n_live": len(self._live_slices()),
            "generation": self.generation,
            "degraded": degraded,
            "applied": True,
        }
        notice = self.maintenance_notice()
        if notice:
            metrics["maintenance_notice"] = notice
        return new_states, metrics

    def close(self) -> None:
        if self.elastic and self.goodput is not None:
            self.goodput.publish()
        if self._pool is not None:
            self._pool.shutdown(wait=False)


def setup_multislice_training(
    cfg,
    dcn_dp: int,
    strategy: str = "dp",
    devices=None,
    model=None,
    **step_kwargs,
):
    """Split the visible devices into `dcn_dp` islands, build a mesh per
    island with `strategy` laid out INSIDE the slice, and return the
    MultisliceTrainStep (JaxTrainer maps ScalingConfig.strategy
    "dcn_dp=2+<inner>" here; see train/step.py for the single-slice
    path this extends). Elastic knobs (`elastic=True`,
    `probe_timeout_s`, `injector`) pass through to MultisliceTrainStep."""
    from ray_tpu.train.step import default_mesh_for_strategy

    if devices is None:
        devices = jax.devices()
    islands = split_devices(devices, dcn_dp)
    spec = default_mesh_for_strategy(strategy, len(islands[0]))
    meshes = [build_mesh(spec, isl) for isl in islands]
    return MultisliceTrainStep(cfg, meshes, strategy=strategy, model=model, **step_kwargs)
