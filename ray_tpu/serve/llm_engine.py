"""Continuous-batching LLM engine: a paged K/V pool driven by macro-steps.

The reference's Serve LLM stack delegates the decode loop to vLLM
inside replicas (continuous batching + paged KV); there is no TPU
engine to wrap, so this is the green-field TPU-native equivalent
(SURVEY §7 step 10). There is one engine and one mode of it:

- LANES. A fixed number of slots, each an independent sequence at its
  own position. What a lane holds is the model's: the decode module the
  config object names (`cfg.decode_module`, one of the six
  models/*_decode.py; models/paged.py's docstring says what it offers)
  owns the cache pytree and the two halves of the macro-step, which that
  module's skeleton runs. For an attention-only model a lane is a block
  table into the K/V pool and a few scalars; where the pool holds ONE
  latent row a position in place of keys and values (a decode module
  with `LATENT_POOL`), the tables, the allocator and the planner are the
  same, and what copies or ships a K and a V pool (prefix reuse,
  speculation, migration, the cluster cache) is refused by name until it
  knows that pool. For a model with recurrent
  layers a lane ALSO owns a row of per-layer state (conv tail, SSM state)
  that admission overwrites, the decode step updates while the lane is
  live, and release abandons. Blocks alone cannot resume such a lane, so
  whatever needs a state snapshot (prefix reuse, speculation's rollback,
  migration and the cluster cache) is refused for it by name, with the
  reason, rather than switched off.
- THE POOL. K/V memory is one pool of fixed-size blocks: a host-side
  BlockAllocator (serve/_internal/kv_blocks.py) plans refcounted
  per-lane block tables that ride each dispatch as i32 program
  arguments, and a radix prefix cache (serve/_internal/prefix_cache.py)
  lets admissions that share a committed prompt prefix reuse its blocks
  and prefill only the suffix. Sampling (temperature/top-k/top-p,
  per-request seeds) and stop-token detection run on the device, inside
  the decode scan.
- COUNTERS ONLY. Scheduling never reads a token VALUE: admission,
  eviction and chunk sizing are decided from host-side counters (a
  request's length, the steps it still owes, the blocks it holds). For
  a greedy request without stop tokens the plan is exact. A stop token
  CAN end a sequence early, so there the plan is speculative: the
  device zeroes a stopped lane's `remaining` the moment it emits a
  stop, and the host repairs its plan when the resolved tokens reveal
  it, truncating delivery at the stop, freeing the lane and its blocks
  at the next plan boundary (`_repair`), and billing the discarded
  planned steps as `plan_repair_waste_pct`. Block reuse under such a
  plan is safe by construction: tables are PER-DISPATCH host plans, so
  a zombie lane (stopped or cancelled but still riding already-planned
  phases) only ever writes blocks it owned at dispatch time; every
  later dispatch points it at the null block, and a new owner's
  admission prefill (always a later dispatch, device programs
  serialize) overwrites before any read.
- PHASES. The host plans up to `macro_phases` phases of admissions and
  evictions ahead (`_plan`; `_plan_spec` for verify rounds of a draft
  model) and ships the WHOLE plan as one jitted dispatch
  (`_dispatch_macro`: the decode module's macro_step_slots_paged, a
  lax.scan over the plan whose phases run a fused admission prefill
  and a decode chunk). Prompts ride along as program arguments, so an
  admission is no dispatch of its own. One program is compiled per
  padded prompt width, a power of two (`_variant`), times greedy /
  sampled: its admission lanes are the engine's lanes rounded up to a
  power of two, and each admitting phase runs its admissions as the
  pieces of their count inside it (`models/paged.admit_phase`).
- ADAPTIVE CHUNKS. Each phase decodes exactly to the next scheduling
  event, min(chunk, least steps owed over the live lanes), so a freed
  lane is re-admitted at the very next phase and does not idle to a
  fixed chunk boundary; a phase's unused steps are skipped with
  lax.cond, so a shrunk phase costs only its real steps.
- A PLAN ENDS WHERE A LANE IS LEFT VACANT. The loop sees an arrival only
  between two dispatches, so a plan is as long as what the lanes hold
  allows. While every lane is live or somebody waits, nobody new could
  be let in anyway, and the plan runs to `macro_phases` phases. The
  first phase that opens with a lane free and nobody waiting for it
  (`vacant` of the lane account) is the plan's LAST, and it decodes at
  most the vacancy quantum (`_quantum`): the steps that take about
  `VACANT_PLAN_S` at the pace the engine measures for its own decode
  steps at resolve. The same compiled program runs it (the other
  phases' `steps` are 0 and they admit nobody), `short_plans` of
  `metrics()` counts such plans and the spans carry `short` and `q`.
  Whoever is let in stalls every resident for the length of an
  admission, at every quantum where arrivals keep coming; so the rule
  holds only once the engine has timed three admissions and while the
  last few are shorter than `VACANT_PLAN_S` themselves (prompts of a
  few hundred tokens). Only an engine that has not yet timed a single
  decode step closes its plans at `chunk` steps: such plans are what
  brings the first reading, and a deployment's warm-up is over them.
  Where one takes longer (prompts of a thousand tokens and more) the
  plan keeps its length, lets arrivals in together at its next
  dispatch, and a resident's decode steps run undisturbed.
- ONE BEHIND. Tokens are fetched one macro-step behind the dispatch
  frontier: while macro-step N executes, the host plans and dispatches
  N+1 from counters, then resolves N's tokens (the only blocking reads,
  `_resolve_inner`) overlapped with N+1's compute. A request is handed
  back when its last token has been resolved, not before (ROADMAP W2).
  The depth stays two under short plans too: with one dispatch queued
  behind the running one the device never waits for the host's plan,
  dispatch and resolve (tens of milliseconds an iteration), and an
  arrival is seen after the rest of the running dispatch and runs after
  the one queued behind it, about a quantum and a half while lanes
  stand vacant. A depth of one would see it half a quantum sooner and
  leave the device idle for a host iteration between any two dispatches.
- SPANS. Every stretch of a loop iteration runs under one of
  `observability.ENGINE_SPANS` (`engine.idle`, `engine.intake`,
  `engine.plan`, `engine.dispatch`, `engine.resolve`, and
  `engine.fetch` inside the last), written on the profiler's clock
  beside the device's events; `engine.dispatch` carries the plan's
  counts (`_dispatch_counts`), among them three accounts the plan
  keeps as it decides: of every lane-step (live, vacant, blocked on the
  pool, spent on an admission that decodes nothing), of every admitted
  request's wait (for a plan, then for a lane) and of what its dispatch
  runs before its own phase and admits while it is live;
  `engine.resolve` repeats them. What the host costs the device is read
  from those, by the benchmark: `engine.starved_idle_pct` (device idle
  time under any span but `engine.idle`) and `engine.deliver_lag_ms`
  (end of a dispatch's execution to the end of its resolve), per cell
  in PERF_LEDGER.jsonl and PERF.md section 5. `metrics()` keeps the
  counters: dispatches per token, lane occupancy, TTFT / TPOT
  percentiles, block utilisation.
- A REQUEST'S OWN ACCOUNT. Those spans and counts describe DISPATCHES;
  a request rides several (four or five under short plans, dozens where
  an answer is a thousand tokens). So each request keeps an account of
  its own (`_Account`), filled by the one walk `_dispatch_counts` makes
  of a plan and by two stamps a dispatch (`_Flight`: enqueued, fetched),
  all on `perf_counter`, and the engine writes it ONCE, where the
  request ends, as the span `engine.request`
  (`observability.REQUEST_SPAN`, inside the `engine.resolve` that
  delivered its last token) and as the fields of the lifeline's last
  event of the rid (`request_timeline`): five host stations that tile
  submit to finish exactly, the plan's counts over its whole life (lead,
  own admission, decode steps, others' admissions it sat through, what
  its last dispatch ran after its last token), how long its admitting
  dispatch stood behind the one in flight, and how many of its resolves
  the host came late for.

Static batching (the decode module's `generate`) remains the one-shot
path of `serve/llm.py` with `continuous=False`.
"""
from __future__ import annotations

import gc
import logging
import queue
import threading
import time
from collections import deque
from statistics import median_high
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.observability import ENGINE_SPANS, REQUEST_SPAN
from ray_tpu.observability import flight_recorder as _flightrec
from ray_tpu.observability import lifeline as _lifeline
from ray_tpu.util.metrics import metric_singletons as _metric_singletons

logger = logging.getLogger(__name__)

# flight-recorder event id resolved once: the per-dispatch ring write
# must be a constant-arg call (lint-pinned — no dict lookup, no
# allocation on the dispatch path)
_EV_DISPATCH = _flightrec.EV["dispatch"]

# the macro loop's spans in a `jax.profiler` trace (what each covers is
# said once, beside ENGINE_SPANS)
(_SPAN_IDLE, _SPAN_INTAKE, _SPAN_PLAN, _SPAN_DISPATCH, _SPAN_RESOLVE,
 _SPAN_FETCH) = ENGINE_SPANS

# latency histogram boundaries (seconds): wide enough for a cold
# compile inside the first request (TTFT can run seconds) and fine
# enough near the fast end for meaningful p50 interpolation
_TTFT_BOUNDS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                10.0, 30.0, 60.0)
_TPOT_BOUNDS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5)


def _engine_metrics_factory():
    """Process-wide serving metrics, tagged per engine — a singleton
    group because the metrics registry keeps every constructed Metric
    (two engines must not double-register the same name)."""
    from ray_tpu.util import metrics

    return dict(
        ttft=metrics.Histogram(
            "ray_tpu_llm_ttft_s", "time to first token",
            boundaries=_TTFT_BOUNDS, tag_keys=("engine",)),
        tpot=metrics.Histogram(
            "ray_tpu_llm_tpot_s", "time per output token",
            boundaries=_TPOT_BOUNDS, tag_keys=("engine",)),
        tokens=metrics.Counter(
            "ray_tpu_llm_tokens_out_total", "tokens delivered",
            tag_keys=("engine",)),
        dispatches=metrics.Counter(
            "ray_tpu_llm_dispatches_total", "device dispatches",
            tag_keys=("engine",)),
        dpt=metrics.Gauge(
            "ray_tpu_llm_dispatches_per_token",
            "dispatch amortization", tag_keys=("engine",)),
        occupancy=metrics.Gauge(
            "ray_tpu_llm_lane_occupancy_pct",
            "useful slot-steps / total slot-steps", tag_keys=("engine",)),
        migration=metrics.Histogram(
            "ray_tpu_llm_migration_s",
            "prefill->decode KV handoff latency",
            boundaries=_TTFT_BOUNDS, tag_keys=("engine",)),
    )


_engine_metrics = _metric_singletons(_engine_metrics_factory)


class _LatencyHist:
    """Engine-local latency histogram, mirrored into the shared
    Prometheus Histogram. The engine loop thread appends while metrics()
    reads — all mutation under one lock, so the percentile snapshot is
    consistent by construction (the PR 2 deque fix, structurally).

    Percentiles stay RECENT-weighted on a long-lived replica (the
    invariant the PR 2 deque carried): bucket counts rotate through two
    epochs of `epoch` observations each, and percentiles read the last
    epoch–2·epoch samples — so a latency regression moves p95 within
    ~epoch requests instead of needing to outvote the process's whole
    history. The shared Prometheus histogram stays cumulative (series
    math like rate() expects monotonic counters); resettable
    (reset_metrics between bench passes)."""

    def __init__(self, bounds, shared_hist, tags, epoch: int = 2048):
        import bisect

        self._bisect = bisect.bisect_left
        self.bounds = list(bounds)
        self._epoch = epoch
        self._counts = [0] * (len(self.bounds) + 1)   # current epoch
        self._prev = [0] * (len(self.bounds) + 1)     # previous epoch
        self._n = 0       # observations in the current epoch
        self._n_prev = 0
        self._sum = 0.0   # current-epoch sum (rotates with the counts)
        self._lock = threading.Lock()
        self._shared = shared_hist
        self._tags = tags

    def observe(self, v: float) -> None:
        with self._lock:
            if self._n >= self._epoch:
                self._prev, self._counts = (
                    self._counts, [0] * (len(self.bounds) + 1))
                self._n_prev, self._n = self._n, 0
                self._sum = 0.0
            self._counts[self._bisect(self.bounds, v)] += 1
            self._sum += v
            self._n += 1
        try:
            self._shared.observe(v, tags=self._tags)
        except Exception:
            pass

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._prev = [0] * (len(self.bounds) + 1)
            self._sum = 0.0
            self._n = 0
            self._n_prev = 0

    def percentiles_ms(self, qs=(0.50, 0.95, 0.99)) -> List[Optional[float]]:
        """Prometheus-style interpolation inside the target bucket over
        the rotating window (previous + current epoch); the +Inf bucket
        clamps to the last finite boundary."""
        with self._lock:
            counts = [p + c for p, c in zip(self._prev, self._counts)]
            n = self._n_prev + self._n
        if n == 0:
            return [None] * len(qs)
        out = []
        for q in qs:
            rank = q * n
            cum = 0
            val = self.bounds[-1]
            for i, c in enumerate(counts):
                prev_cum = cum
                cum += c
                if cum >= rank and c > 0:
                    lo = self.bounds[i - 1] if i > 0 else 0.0
                    hi = self.bounds[i] if i < len(self.bounds) else self.bounds[-1]
                    val = lo + (hi - lo) * ((rank - prev_cum) / c)
                    break
            out.append(round(val * 1e3, 3))
        return out


class _Flight:
    """One dispatch's stamps (`perf_counter`), shared by the requests that
    ride it: `t_enq` when `_dispatch_macro` had enqueued it, `t_fetched`
    when the fetch of its resolve had returned, `ahead_s` from `t_enq` to
    the return of the fetch of the dispatch that was in flight then (0.0
    where the device was idle; `behind` is the dispatch enqueued behind
    this one, until this one's fetch has told it), `late_before` the
    engine's count of late resolves before this one's."""
    __slots__ = ("seq", "t_enq", "t_fetched", "ahead_s", "late_before", "behind")

    def __init__(self, seq: int, late_before: int = 0):
        self.seq = seq
        self.t_enq: Optional[float] = None
        self.t_fetched: Optional[float] = None
        self.ahead_s = 0.0
        self.late_before = late_before
        self.behind: Optional["_Flight"] = None


class _Account:
    """A request's own account over every dispatch it rides: `first`, its
    admitting dispatch, and the plan's counts, added by `_dispatch_counts`
    in the walk it makes anyway (what each is, is said beside
    `observability.REQUEST_SPAN`; `_request_stats` makes the span's stats
    of it). Integers and one reference; made at submit."""
    __slots__ = ("first", "dispatches", "lead_steps", "lead_phases", "lead_rows",
                 "own_rows", "decode_steps", "stall_phases", "stall_rows",
                 "tail_steps", "tail_phases", "tail_rows")

    def __init__(self):
        self.first: Optional[_Flight] = None
        self.dispatches = self.lead_steps = self.lead_phases = self.lead_rows = 0
        self.own_rows = self.decode_steps = self.stall_phases = self.stall_rows = 0
        self.tail_steps = self.tail_phases = self.tail_rows = 0


# the counts of an `_Account`, in the order the span carries them
_ACCOUNT_COUNTS = _Account.__slots__[1:]


class _Request:
    __slots__ = ("prompt", "max_new_tokens", "tokens", "done", "error",
                 "exc", "on_done", "sampling", "finish_reason",
                 "_remaining", "_rounds_est", "_rounds_inflight",
                 "_t_submit", "_t_seen", "_t_admit", "_t_first", "_t_done",
                 "_trace_ctx", "_start", "_blocks", "_blocks_freed",
                 "_done_lock", "rid", "_rid_b", "_migrate", "export",
                 "_resume", "_qtok", "_acct")

    def __init__(self, prompt, max_new_tokens, on_done=None, sampling=None,
                 rid: Optional[str] = None):
        from ray_tpu.serve._internal.sampling import SamplingParams

        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling or SamplingParams()
        # caller-generated request id (redispatch bookkeeping + logs);
        # _rid_b is the pre-encoded flight-recorder form — encoded ONCE
        # here so no per-event path ever pays the str→bytes conversion
        self.rid = rid
        self._rid_b = _lifeline.rid_bytes(rid) if rid else b""
        # "length" | "stop" | "cancelled" | None (error/unfinished)
        self.finish_reason: Optional[str] = None
        self.tokens: List[int] = []
        self.done = threading.Event()
        # completion is a cross-thread event (engine loop delivers,
        # caller threads cancel): _finish's test-and-set runs under this
        self._done_lock = threading.Lock()
        self._start = 0            # reused-prefix tokens of the admission
        self._blocks: List[int] = []   # KV blocks owned
        self._blocks_freed = False
        # completion callback, fired (once) from the engine loop thread
        # right after done.set() — the serve direct-transport path
        # completes the caller's deferred reply here with one ring
        # write, instead of parking a replica thread per request on the
        # event (see _LLMServer.__call__)
        self.on_done = on_done
        self.error: Optional[str] = None
        # typed failure (serve/errors.py) — what generate()/the deferred
        # completion raise so the taxonomy survives the process boundary
        # (error stays the human-readable string form)
        self.exc: Optional[BaseException] = None
        self._remaining = 0      # host-side plan counter (decode steps owed)
        # KV-plane state: _migrate marks a prefill-pool request that
        # hands off after its first token; export holds the exporter's
        # {ref, ...} handoff metadata (keeps the ObjectRef alive until
        # the decode side's reply lands); _resume carries an inbound
        # migration's fetched payload until the import admits it
        self._migrate = False
        self.export: Optional[Dict[str, Any]] = None
        self._resume: Optional[Dict[str, Any]] = None
        self._qtok = 0           # queued-prefill-token accounting (idempotent)
        # speculative mode: acceptance is data-dependent, so the planner
        # schedules verify ROUNDS from an estimate instead of exact
        # steps — rounds still plannable / already dispatched-unresolved
        self._rounds_est = 0
        self._rounds_inflight = 0
        self._t_submit = time.perf_counter()
        # the wait account: the start of the first plan that found the
        # request waiting, and of the plan that admitted it (the same
        # stamp where that was one plan); a resumed migration has neither
        self._t_seen: Optional[float] = None
        self._t_admit: Optional[float] = None
        self._t_first: Optional[float] = None
        self._t_done: Optional[float] = None
        # its own account across every dispatch it rides (`_Account`)
        self._acct = _Account()
        # trace context captured on the SUBMITTING thread (the engine
        # loop runs in its own thread, where the contextvar is unset):
        # the dispatches this request rides parent under it, so a slow
        # serve request is followable proxy span → replica task → the
        # exact macro-steps that decoded it
        self._trace_ctx: Optional[Dict[str, str]] = None


def _suffix_len(req: "_Request") -> int:
    """Prompt tokens an admission prefills: those past its reused prefix."""
    return len(req.prompt) - req._start


# How long a plan decodes once a lane stands vacant and nobody waits (the
# vacancy quantum of `_plan`, in seconds of the engine's own measured
# decode steps). Long enough that the host is back with the next dispatch
# before the device runs dry (an iteration of `_loop_macro` costs the host
# 7-18 ms of plan and dispatch and 3-8 ms of resolve: ledger, PR 46,
# `engine.dispatch_lead_ms`, `engine.deliver_lag_ms`), and no longer, since
# an arrival waits a quantum and a half to run. Also what an admission may
# take for the rule to hold at all: one that stalls the residents for
# longer than the quantum costs them more than it saves whoever arrives.
VACANT_PLAN_S = 0.040

# the counts of `_dispatch_counts` that `engine.metrics()` sums as they are
_PLAN_SUMS = ("ctx_chunks", "past_window_lane_steps",
              "ctx_tokens", "prompt_pairs", "admit_rows", "admit_pieces", "admit_phases",
              "vacant_lane_steps", "blocked_lane_steps", "spent_lane_steps",
              "plan_wait_us", "lane_wait_us", "admitted_first_plan",
              "admit_lead_steps", "admit_lead_phases", "stall_lane_phases")


def _wait_us(req: "_Request") -> Tuple[int, int]:
    """(plan wait, lane wait) of an admitted request, whole microseconds:
    submit to the start of the first plan that found it waiting, and from
    there to the start of the plan that admitted it. The second is the
    whole wait less the first, so the two add up to (admitting plan's
    start - submit) exactly, and is 0 where one plan did both."""
    seen = round((req._t_seen - req._t_submit) * 1e6)
    return seen, round((req._t_admit - req._t_submit) * 1e6) - seen


def _request_stats(req: "_Request", reason: str, fetched: Optional["_Flight"] = None,
                   late: int = 0, spec: bool = False) -> Dict[str, Any]:
    """The stats of a request's `engine.request` span (all but `rid`), and
    the fields of its lifeline's `finish` event: ONE dict a request, made
    where it ends. `fetched` is the dispatch whose resolve delivers its
    last token (none where it ends outside a resolve), `late` the
    engine's count of late resolves so far.

    The five host stations are differences of ONE list of stamps, each
    rounded as (stamp - submit) in whole microseconds, as `_wait_us` does,
    so they add up to round((done - submit) * 1e6) exactly and the first two
    are `_wait_us`' own. A stamp the request never got (it was cancelled,
    shed, failed or migrated away before that station's end) takes the next
    one it has, `done` at the last: the station it ended in runs to `done`
    and the later ones read 0. So a request shed or cancelled in the queue
    lacks `plan_us`, `flight_us` and `deliver_us`; one cancelled in flight,
    by a thread that is not inside a resolve, `deliver_us`, and its
    `flight_us` runs to the cancel; one migrated away ends in the resolve of
    its admitting dispatch and has all five. A migration RESUMED here was
    never admitted by a plan (`_admit_resumes`): no `_t_seen`, no
    `_t_admit`, no admitting dispatch, so submit to its last fetch reads as
    `unseen_us`, `seq_first` is -1 and `ahead_us`, `late` and the lead are
    0. The counts are the PLAN's: where a stop token, a cancel or an error
    ends a request ahead of its plan they count what was planned for it, a
    dispatch enqueued after the one that finished it included (`dispatches`
    can pass `seq_last - seq_first + 1` there), and under a draft model
    (`spec` 1) `decode_steps` is verify rounds and every count an estimate."""
    acct, first = req._acct, req._acct.first
    done = req._t_done if req._t_done is not None else time.perf_counter()
    stamps = [done,
              fetched.t_fetched if fetched is not None else None,
              first.t_enq if first is not None else None,
              req._t_admit, req._t_seen]
    for i in range(1, 5):  # backwards: a missing stamp takes the next one
        if stamps[i] is None:
            stamps[i] = stamps[i - 1]
    t0 = req._t_submit
    # microseconds since submit at each stamp: the stations are their differences
    at_done, at_fetched, at_enq, at_admit, at_seen = (round((t - t0) * 1e6) for t in stamps)
    submit_us = round(t0 * 1e6)
    stats = {"reason": reason, "tokens": len(req.tokens),
             "submit_us": submit_us, "done_us": submit_us + at_done,
             "seq_first": first.seq if first is not None else -1,
             "seq_last": fetched.seq if fetched is not None else -1,
             "unseen_us": at_seen, "lane_wait_us": at_admit - at_seen,
             "plan_us": at_enq - at_admit, "flight_us": at_fetched - at_enq,
             "deliver_us": at_done - at_fetched}
    for key in _ACCOUNT_COUNTS:
        stats[key] = getattr(acct, key)
    stats["ahead_us"] = round(first.ahead_s * 1e6) if first is not None else 0
    stats["late"] = late - first.late_before if first is not None else 0
    stats["spec"] = int(spec)
    return stats


def _dispatch_counts(phases: List[Dict[str, Any]], recurrent: bool = False,
                     ctx_chunk: int = 0, window: int = 0,
                     variant: Tuple[int, int] = (0, 0),
                     n_slots: int = 0) -> Dict[str, int]:
    """What one macro dispatch carries, from the plan alone: the keyword
    arguments of its `engine.dispatch` span (host integers; nothing is
    read from the device). `_plan` leaves every request at its
    post-dispatch state, so a request of the plan that owes no more
    decode steps gets its last token in this dispatch (`finishing`), and
    `finish_wait_steps` sums, over those, the decode steps the dispatch
    still runs after that token exists: what a finished answer waits on
    the device before the host can see it. A speculative plan holds
    estimates and decrements nothing, so only requests that owe no decode
    step at admission count there. `state_lanes` is the state rows the
    dispatch's decode steps have to move: each live lane-step of a model
    whose lanes hold recurrent state (`recurrent`); no other model's
    dispatch carries the key. `ctx_chunks` is the trip counts of the paged
    decode attention's loop summed over the dispatch's decode steps: each
    step reads ceil(longest planned live context / `ctx_chunk`) chunks of
    `ctx_chunk` positions, a live lane's context at a step being its
    prompt, the tokens it has decoded and the one it feeds. Only an engine
    whose decode steps run that loop gives `ctx_chunk` (no draft model);
    the device's own count is smaller where a sampled stop ends a
    lane before its plan does. `past_window_lane_steps` is the live
    lane-steps whose context is longer than `window`, for a model with
    sliding-window layers (their rings are full there, and the window's
    mask cuts something off); no other model's dispatch carries the key.
    `ctx_tokens` is the positions the dispatch's decode steps attend, summed
    over steps and live lanes (a lane at position pos attends pos + 1), and
    `prompt_pairs` the (query, key) pairs of its admissions' causal
    attention, n (n + 1) / 2 for a prompt of n tokens (and n times its
    reused prefix): what the attention of each half has to do whatever
    does it, for every model. `admit_rows` is the token rows the device
    runs for the admissions, padding included: for each phase that admits
    (`admit_phases`) the pieces of its count (`models/paged.admit_pieces`,
    the function the device runs its admission bodies by: 3 admissions
    run 2 + 1 rows where each piece has tokens enough to be worth its
    pass over the weights, 4 where not), summed and times P, with (A, P) the compiled
    program's (`variant`), and `admit_pieces` the admission bodies run,
    so `admit_pieces / admit_phases` says how often a phase runs more
    than one; `prompt_tokens / admit_rows` is the share of the rows that
    is a prompt's, and A x P x `admit_phases` what the program would run
    with every phase at its full width.

    The wait account, summed over the dispatch's admissions (`_wait_us`):
    `plan_wait_us`, submit to the first plan that found the request
    waiting (plan granularity), `lane_wait_us`, from there to the plan
    that admits it (no lane or no block was free), and
    `admitted_first_plan`, how many one plan both found and admitted.
    What then runs on the device before an admission's own phase:
    `admit_lead_steps`, the decode steps of the phases before it, and
    `admit_lead_phases`, the admitting phases before it, both summed over
    the admissions. `stall_lane_phases` sums, over the admitting phases,
    the lanes that are live through one and were admitted before it (in
    this dispatch or an earlier one): what a request sits through of
    others' admissions.

    The lane account, for an engine of `n_slots` lanes (no other call
    carries the three keys): a phase's lanes are live (`lane_steps`),
    taken by an admission that owes no decode step in it (a one-token
    answer, a migration's prefill: `spent_lane_steps`), or empty, and the
    plan says why when it closes the phase: `vacant` lanes found nobody
    waiting, `blocked` ones a request the pool refused (a phase that says
    neither counts its empty lanes as vacant). Each times the phase's
    steps: `lane_steps + vacant_lane_steps + blocked_lane_steps +
    spent_lane_steps == n_slots * steps` for every dispatch.

    `short` is 1 where a vacant lane closed the plan (`_plan` marks that
    phase, its last, with the vacancy quantum it decoded by) and `q` that
    quantum in steps; both 0 in every other plan.

    The same walk adds to each request's own account (`req._acct`, an
    `_Account`; so it is made ONCE a plan): one more of its `dispatches`;
    at its admission `lead_steps`, `lead_phases`, `lead_rows` (what
    `admit_lead_steps` / `admit_lead_phases` sum over the dispatch, and
    the `admit_rows` of those phases) and `own_rows` (its phase's rows);
    its takes as `decode_steps`; `stall_phases` / `stall_rows` for every
    admitting phase it rides and was not admitted by; and, where this
    dispatch holds its last token, `tail_steps` (its part of
    `finish_wait_steps`), `tail_phases`, `tail_rows`. Two cuts of one
    plan: over the dispatches of requests that lived only inside them the
    requests' `lead_steps`, `lead_phases`, `stall_phases`, `decode_steps`
    and `tail_steps` sum to the dispatches' `admit_lead_steps`,
    `admit_lead_phases`, `stall_lane_phases`, `lane_steps` and
    `finish_wait_steps`."""
    from ray_tpu.models.paged import admit_pieces as pieces_of

    total = sum(ph["steps"] for ph in phases)
    done = 0  # decode steps of this dispatch run so far
    # finishing request -> (its account, `done`, `admit_phases` and
    # `admit_rows` when its last token exists)
    last: Dict[int, Tuple[_Account, int, int, int]] = {}
    rode = set()  # the requests this dispatch has been counted for
    admissions = prompt_tokens = prefix_tokens = lane_steps = 0
    plan_wait = lane_wait = first_plan = lead_steps = lead_phases = 0
    admit_phases = admit_pieces = admit_rows = stall = vacant = blocked = spent = 0
    for ph in phases:
        admissions += len(ph["admissions"])
        new = {id(req) for _, req in ph["admissions"]}
        own_rows = 0  # the token rows of this phase's admissions
        if new:
            pieces = pieces_of(len(ph["admissions"]), *variant)
            own_rows = sum(pieces) * variant[1]
        for _, req in ph["admissions"]:
            prompt_tokens += _suffix_len(req)
            prefix_tokens += req._start  # > 0: the admission's prefix loop runs
            seen_us, lane_us = _wait_us(req)
            plan_wait += seen_us
            lane_wait += lane_us
            first_plan += req._t_seen == req._t_admit
            lead_steps += done
            lead_phases += admit_phases
            acct = req._acct
            rode.add(id(req))
            acct.dispatches += 1
            acct.lead_steps, acct.lead_phases, acct.lead_rows = done, admit_phases, admit_rows
            acct.own_rows = own_rows
            if req._remaining == 0:  # the prefill's token, unless it decodes
                last[id(req)] = (acct, done, admit_phases + 1, admit_rows + own_rows)
        if new:
            admit_phases += 1
            admit_pieces += len(pieces)
            admit_rows += own_rows
        done += ph["steps"]
        riding = len(ph["takes"])
        decoding = 0  # admitted in this phase and decoding in it
        for _, req, take in ph["takes"]:
            lane_steps += take
            acct, key = req._acct, id(req)
            acct.decode_steps += take
            if key not in rode:
                rode.add(key)
                acct.dispatches += 1
            if key in new:
                decoding += 1
            elif new:  # live through an admission that is not its own
                acct.stall_phases += 1
                acct.stall_rows += own_rows
            if take and req._remaining == 0:
                last[key] = (acct, done, admit_phases, admit_rows)
        if new:
            stall += riding - decoding
        idle = len(new) - decoding  # admitted here, no decode step owed here
        empty = n_slots - riding - idle
        spent += idle * ph["steps"]
        blocked += ph.get("blocked", 0) * ph["steps"]
        vacant += ph.get("vacant", empty - ph.get("blocked", 0)) * ph["steps"]
    for acct, d, phases_then, rows_then in last.values():
        acct.tail_steps = total - d
        acct.tail_phases, acct.tail_rows = admit_phases - phases_then, admit_rows - rows_then
    q = phases[-1].get("short", 0) if phases else 0
    counts = {"phases": len(phases), "steps": total, "admissions": admissions,
              "prompt_tokens": prompt_tokens, "prefix_tokens": prefix_tokens,
              "lane_steps": lane_steps,
              "finishing": len(last),
              "finish_wait_steps": sum(total - d for _, d, _, _ in last.values()),
              "ctx_tokens": _ctx_tokens(phases), "prompt_pairs": _prompt_pairs(phases),
              "admit_rows": admit_rows, "admit_pieces": admit_pieces,
              "admit_phases": admit_phases, "plan_wait_us": plan_wait,
              "lane_wait_us": lane_wait, "admitted_first_plan": first_plan,
              "admit_lead_steps": lead_steps, "admit_lead_phases": lead_phases,
              "stall_lane_phases": stall,
              "short": int(q > 0), "q": q}
    if n_slots:
        counts.update(vacant_lane_steps=vacant, blocked_lane_steps=blocked,
                      spent_lane_steps=spent)
    if recurrent:
        counts["state_lanes"] = lane_steps
    if ctx_chunk:
        counts["ctx_chunks"] = _ctx_chunks(phases, ctx_chunk)
    if window:
        counts["past_window_lane_steps"] = _past_window_lane_steps(phases, window)
    return counts


def _past_window_lane_steps(phases: List[Dict[str, Any]], window: int) -> int:
    """`past_window_lane_steps` of `_dispatch_counts`, by `_ctx_chunks`'
    walk: a lane that owes r steps before a phase holds its prompt and
    max_new_tokens - r positions at the phase's first step, one more each
    step after."""
    owed: Dict[int, int] = {}
    past = 0
    for ph in reversed(phases):
        for _, req, take in ph["takes"]:
            before = owed[id(req)] = owed.get(id(req), req._remaining) + take
            first = len(req.prompt) + req.max_new_tokens - before
            # its contexts in this phase: first .. first + take - 1
            past += max(0, first + take - max(first, window + 1))
    return past


def _prompt_pairs(phases: List[Dict[str, Any]]) -> int:
    """`prompt_pairs` of `_dispatch_counts`."""
    new = [(_suffix_len(req), req._start) for ph in phases for _, req in ph["admissions"]]
    return sum(n * start + n * (n + 1) // 2 for n, start in new)


def _ctx_tokens(phases: List[Dict[str, Any]]) -> int:
    """`ctx_tokens` of `_dispatch_counts`, by `_ctx_chunks`' walk: a lane
    that owes r steps before a phase attends its prompt and max_new_tokens
    - r positions at the phase's first step, one more each step after."""
    owed: Dict[int, int] = {}
    tokens = 0
    for ph in reversed(phases):
        for _, req, take in ph["takes"]:
            before = owed[id(req)] = owed.get(id(req), req._remaining) + take
            first = len(req.prompt) + req.max_new_tokens - before
            tokens += take * first + take * (take - 1) // 2
    return tokens


def _ctx_chunks(phases: List[Dict[str, Any]], ctx_chunk: int) -> int:
    """`ctx_chunks` of `_dispatch_counts`. The plan leaves a request at its
    post-dispatch `_remaining`, so its steps owed before a phase are that
    plus what this phase and the later ones take, walking the phases
    backwards; a lane that owes r steps holds its prompt and
    max_new_tokens - 1 - r decoded tokens, and every live lane of a phase
    takes all of its steps."""
    owed: Dict[int, int] = {}  # request -> steps owed after the phase at hand
    chunks = 0
    for ph in reversed(phases):
        longest = 0  # live context at the phase's first step
        for _, req, take in ph["takes"]:
            before = owed[id(req)] = owed.get(id(req), req._remaining) + take
            longest = max(longest, len(req.prompt) + req.max_new_tokens - before)
        chunks += sum(-(-(longest + t) // ctx_chunk) for t in range(ph["steps"]))
    return chunks


def _finish(req: "_Request", error: Optional[str] = None,
            reason: Optional[str] = None,
            exc: Optional[BaseException] = None) -> bool:
    """Complete a request ATOMICALLY: exactly one caller wins (the
    engine loop delivering vs. a caller thread cancelling race here),
    the final error/finish_reason are written before `done` is visible,
    and on_done fires exactly once, outside the lock (callback failures
    are logged, never poison the engine loop). `exc` carries the typed
    failure (shed / deadline / replica-death) alongside the string form.
    Returns True for the winner, False if the request was already
    complete."""
    with req._done_lock:
        if req.done.is_set():
            return False
        if exc is not None:
            req.exc = exc
            if error is None:
                error = str(exc)
        if error is not None:
            req.error = error
        if reason is not None:
            req.finish_reason = reason
        cb = req.on_done
        req.on_done = None
        req.done.set()
    if cb is not None:
        try:
            cb(req)
        except Exception:
            logger.exception("llm request on_done callback failed")
    return True


def _refuse_what_reuses_kv_blocks(latent_pool: bool, **asked) -> None:
    """Every option that resumes, copies or ships a sequence by its K/V
    blocks is refused by name (never silently switched off) for a model
    those blocks do not describe, for one of two reasons. Lanes that hold
    rows of their own beside their blocks (recurrent state, a window
    layer's ring of its last positions): what those rows held at a block
    boundary is not kept, so the blocks cannot resume a sequence. A
    `latent_pool` (one pool of latent rows, no keys and no values): the
    blocks are a lane's whole state, but the programs that copy, gather,
    import and re-read blocks, and the speculative ones, are written for a
    K pool and a V pool."""
    if latent_pool:
        why = ("the model's cache is one pool of latent rows, and what reuses "
               "blocks is written for a K pool and a V pool: ")
        needs_kv = "the speculative programs read and write cache['k'] and cache['v']"
        reasons = {
            "prefix_cache": "the admission's prefix loop and the copy-on-write of a "
                            "shared block read K and V blocks (pass prefix_cache=False)",
            "draft_model": needs_kv,
            "num_speculative_tokens": needs_kv,
            "role": "the KV plane gathers and imports a K and a V array a "
                    "migration (disaggregated pools)",
            "cluster_cache": "a peer's prefix blocks arrive as a K and a V array",
        }
    else:
        why = ("the model's lanes hold recurrent state (or a window layer's "
               "ring) beside their blocks, and the state at a block boundary "
               "is not kept: ")
        no_rollback = ("rejected speculative tokens cannot be rolled back out "
                       "of a recurrence")
        reasons = {
            "prefix_cache": "a block-aligned prefix hit is useless without the "
                            "state at that boundary (pass prefix_cache=False)",
            "draft_model": no_rollback,
            "num_speculative_tokens": no_rollback,
            "role": "a migrated request's blocks cannot resume without its "
                    "lane's state (disaggregated pools)",
            "cluster_cache": "a peer's prefix blocks are useless without the "
                             "state at their boundary",
        }
    for name, reason in reasons.items():
        if asked.get(name):
            raise ValueError(f"{name}={asked[name]!r} is refused: " + why + reason)


class ContinuousBatchingEngine:
    def __init__(self, params, cfg, n_slots: int = 8, max_len: int = 0,
                 chunk: int = 8, macro_phases: int = 8, name: str = "default",
                 block_size: int = 16, n_blocks: int = 0,
                 prefix_cache: bool = True,
                 max_queue: Optional[int] = None, draft_model=None,
                 num_speculative_tokens: int = 0,
                 role: Optional[str] = None,
                 cluster_cache: Optional[bool] = None,
                 digest_prefix_len: int = 32):
        import jax

        # the model's decode module: cache pytree, macro-step halves
        D = cfg.decode_module
        # bytes of recurrent state a lane holds beside its K/V blocks
        self.state_bytes = int(D.state_bytes_per_lane(cfg))
        # one pool of latent rows where other models hold a K and a V pool
        latent_pool = bool(getattr(D, "LATENT_POOL", False))
        if self.state_bytes or latent_pool:
            _refuse_what_reuses_kv_blocks(
                latent_pool, prefix_cache=prefix_cache, draft_model=draft_model,
                num_speculative_tokens=num_speculative_tokens, role=role,
                cluster_cache=cluster_cache)

        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"engine role must be None, 'prefill' or 'decode', got "
                f"{role!r}")
        self.role = role
        if macro_phases < 1:
            raise ValueError(
                f"macro_phases must be >= 1, got {macro_phases}: every "
                "dispatch is a plan of at least one phase")
        if block_size & (block_size - 1) or block_size < 1:
            raise ValueError(f"block_size must be a power of two, got {block_size}")

        self._jax = jax
        # a span on the profiler's own clock, beside the device's events in
        # the same trace; about a microsecond while no session is open
        self._span = jax.profiler.TraceAnnotation
        self._D = D
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        # the macro-step's admission lanes: the lanes' power-of-two bucket
        self._admit_lanes = 1 << (n_slots - 1).bit_length()
        self.max_len = max_len or cfg.max_seq_len
        self.chunk = chunk
        self.macro_phases = macro_phases
        from ray_tpu.serve._internal.kv_blocks import BlockAllocator
        from ray_tpu.serve._internal.prefix_cache import RadixPrefixCache

        self.block_size = block_size
        # table width: blocks to cover max_len (per-slot ceiling)
        self._mb = -(-self.max_len // block_size)
        # P of the last dispatch, for a plan that admits nobody (`_variant`)
        self._last_P = self._bucket_paged(1)
        # default pool: the K/V budget of slots x max_len stripes (+1 for
        # the reserved null block), shared by however many lanes fit
        self.n_blocks = n_blocks or n_slots * self._mb + 1
        self._alloc = BlockAllocator(self.n_blocks, block_size)
        self._prefix = RadixPrefixCache(self._alloc) if prefix_cache else None
        self.cache = D.init_paged_cache(cfg, n_slots, self.n_blocks,
                                        block_size)
        # greedy variant prebound; _dispatch_macro rebinds it per plan (two
        # static variants: all-greedy traffic must not pay the per-step
        # sort/softmax/rng sampling pipeline)
        self._macro_paged_fn = D.jitted_macro_step_slots_paged(
            cfg, chunk, sampled=False)
        # draft-model speculative decoding: the spec macro program is a
        # THIRD static variant family beside the greedy/sampled pair. With
        # speculation off these attributes stay None and the engine never
        # traces a program containing a single draft parameter
        # (lint-enforced)
        self.n_spec = int(num_speculative_tokens)
        # positions a chunk of the paged decode attention covers; 0 where
        # no decode step runs that loop (speculative rounds)
        self._ctx_chunk = 0
        # the sliding window of a model that has window layers, else 0
        self._window = int(getattr(cfg, "sliding_window", 0) or 0)
        # what the model's macro-step counts on the device and hands back
        # beside its tokens (a (n,) int32 fifth return), by name: none for
        # a model whose decode module names none. They steer nothing: the
        # planner reads counters of its own only
        self._device_counters = tuple(getattr(D, "DEVICE_COUNTERS", ()))
        if draft_model is None:
            from ray_tpu.models.paged import decode_chunk_positions

            self._ctx_chunk = decode_chunk_positions(block_size, self._mb)
        self.draft_params = None
        self.draft_cfg = None
        self.draft_cache = None
        if draft_model is not None:
            if self.n_spec < 1:
                raise ValueError(
                    "draft_model requires num_speculative_tokens >= 1, "
                    f"got {self.n_spec}")
            from ray_tpu.serve._internal.speculative import resolve_draft_model

            self.draft_params, self.draft_cfg = resolve_draft_model(
                draft_model, params, cfg)
            if self.draft_params is params:
                # "self"-drafting: draft weights ARE the target weights,
                # so draft and verify writes are bit-identical and ONE
                # pool serves both models — draft_cache stays None (the
                # kernels' shared-pool mode): no mirror prefill at
                # admission, no second pool's memory, no hole tracking
                self.draft_cache = None
            else:
                # the draft pool mirrors the target's block geometry:
                # one host allocator plan addresses both pools
                self.draft_cache = D.init_spec_cache(
                    self.draft_cfg, n_slots, self.n_blocks, block_size)
            # acceptance EMA feeding the round planner: start optimistic
            # (full acceptance) so the first plans don't over-schedule —
            # resyncs against observed accepted lengths at resolution
            self._accept_ema = float(self.n_spec + 1)
        elif self.n_spec > 0:
            raise ValueError(
                "num_speculative_tokens > 0 requires a draft_model")
        if role is not None and self.draft_cache is not None:
            # a SEPARATE draft pool cannot follow a migration (only the
            # target pool's blocks ship) — the resumed request's draft
            # lane would verify against garbage. Shared-pool
            # self-drafting (draft_cache None) migrates fine.
            raise ValueError(
                "disaggregated pools require a shared-pool draft model "
                "(separate draft KV cannot migrate across replicas)")
        self._slots: List[Optional[_Request]] = [None] * n_slots
        import jax.numpy as jnp

        self._next_dev = jnp.zeros(n_slots, jnp.int32)  # device-side feed tokens
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._waiting: deque = deque()       # planner-side FIFO (loop thread only)
        self._t_plan = 0.0                   # start of the plan at hand (perf_counter)
        # the vacancy quantum's measurements (`_quantum`, `_time_dispatch`):
        # when the oldest dispatch in flight started on the device, where the
        # host knows it (it was shipped to an idle device, or the host was on
        # time for the resolve before it), and the last few readings of a
        # decode step's and of an admitting phase's time
        self._t_started: Optional[float] = None
        self._step_s: deque = deque(maxlen=5)
        self._admit_s: deque = deque(maxlen=5)
        self._pending: deque = deque()       # fetch frontier: tagged entries
        self._planned: Dict[int, Dict[str, int]] = {}  # seq -> plan counts, until resolved
        # the requests' accounts (`_Account`): seq -> the stamps of a dispatch
        # until its fetch has returned, the dispatch enqueued last, the one
        # whose resolve is delivering, and the resolves the host came late
        # for (never reset: a request's `late` is a difference of it)
        self._flights: Dict[int, _Flight] = {}
        self._last_flight: Optional[_Flight] = None
        self._resolving: Optional[_Flight] = None
        self._late = 0
        # KV-plane plumbing: inbound migrations (fetched payloads
        # awaiting a slot), cross-thread jobs the loop executes at plan
        # boundaries (allocator/trie mutation stays loop-thread-only),
        # and the queued-prefill-token gauge feeding the prefill pool's
        # autoscaling signal
        self._rqueue: "queue.Queue[_Request]" = queue.Queue()
        self._resuming: deque = deque()      # loop thread only
        self._jobs: "queue.Queue" = queue.Queue()
        self._qtok_lock = threading.Lock()
        self._queued_prefill_tokens = 0
        self._kv_inv = None
        if self._prefix is not None:
            from ray_tpu.serve._internal.kv_plane import (
                PrefixInventory, cluster_cache_enabled)

            self._cluster_cache = cluster_cache_enabled(cluster_cache)
            if self._cluster_cache:
                self._kv_inv = PrefixInventory(digest_prefix_len)
        else:
            self._cluster_cache = False
        self._dead: Optional[str] = None
        # admission bound: max requests WAITING (beyond the resident
        # slots) before submit() sheds with a typed 503-shaped error —
        # overload must become fast rejections, not a timeout pileup.
        # 0 = unbounded (the library default; serve deployments set it)
        import os as _os

        if max_queue is None:
            max_queue = int(_os.environ.get("RAY_TPU_SERVE_MAX_QUEUE", "0"))
        self.max_queue = max(0, int(max_queue))
        # EMA of completed-request service time (submit → done): the
        # admission ETA estimate. Written by the loop thread at
        # delivery, read by submit() — a torn float read is harmless
        self._ema_service_s = 0.0
        # serving metrics (monotonic counters + latency histograms).
        # _m_lock makes RELATED counters a consistent snapshot: the
        # migration/prefix-export sites bump several counters per event,
        # and metrics() copies the dict under the same lock so a
        # mid-burst scrape can't return torn totals (migrations_out
        # without its migrated_blocks_out). Single-counter bumps on the
        # loop thread stay lock-free — a lone counter can't tear.
        self.name = name
        self._m_lock = threading.Lock()
        # per-process crash ring: per-dispatch events land here with ONE
        # ring write (no allocation, no pickle, no RPC — lint-pinned)
        self._fr = _flightrec.get_recorder()
        self._m = {"dispatches": 0, "short_plans": 0,
                   # resolves the host came to after the result was ready
                   "late_resolves": 0,
                   "tokens_out": 0, "slot_steps": 0,
                   "useful_slot_steps": 0, "wasted_steps": 0,
                   "prefill_tokens": 0, "reused_prefix_tokens": 0,
                   "kv_blocks_peak_in_use": 0, "shed_queue_full": 0,
                   "shed_eta": 0, "deadline_expired": 0,
                   "spec_verify_rounds": 0, "draft_proposed_tokens": 0,
                   "draft_accepted_tokens": 0, "migrations_out": 0,
                   "migrations_in": 0, "migrated_blocks_out": 0,
                   "migrated_blocks_in": 0, "prefix_exports": 0,
                   "prefix_imports": 0, "requests_completed": 0,
                   # recurrent-state rows the decode steps had to move:
                   # live lane-steps of a model whose lanes hold state
                   "state_lane_steps": 0,
                   # chunks of context the paged decode attention's loop
                   # was planned to read, and what reading every step's
                   # whole table span would have been
                   "ctx_chunks": 0, "span_chunks": 0,
                   # positions the planned decode steps attend, and (query,
                   # key) pairs of the planned admissions' attention
                   "ctx_tokens": 0, "prompt_pairs": 0,
                   # token rows the device runs for the planned admissions,
                   # padding included (P x the pieces of a phase's count),
                   # the admission bodies run and the phases that admit
                   "admit_rows": 0, "admit_pieces": 0, "admit_phases": 0,
                   # the lane account: lane-steps left empty with nobody
                   # waiting, with a request the pool refused, and taken
                   # by an admission that owes no decode step; with
                   # useful_slot_steps they are slot_steps
                   "vacant_lane_steps": 0, "blocked_lane_steps": 0,
                   "spent_lane_steps": 0,
                   # the wait account, summed over admissions: submit to
                   # the first plan that saw the request, from there to
                   # the plan that admitted it, how many one plan did
                   # both; then decode steps and admitting phases of its
                   # dispatch before its own phase, and the admitting
                   # phases live lanes sat through
                   "plan_wait_us": 0, "lane_wait_us": 0,
                   "admitted_first_plan": 0, "admit_lead_steps": 0,
                   "admit_lead_phases": 0, "stall_lane_phases": 0,
                   # planned live lane-steps whose context passes the
                   # model's sliding window (0 for a model without one)
                   "past_window_lane_steps": 0,
                   **dict.fromkeys(self._device_counters, 0)}
        shared = _engine_metrics()
        self._tags = {"engine": name}
        self._ttft = _LatencyHist(_TTFT_BOUNDS, shared["ttft"], self._tags)
        self._tpot = _LatencyHist(_TPOT_BOUNDS, shared["tpot"], self._tags)
        self._mig = _LatencyHist(_TTFT_BOUNDS, shared["migration"], self._tags)
        # device-step telemetry for each dispatch: host dispatch slices
        # land on the unified trace's device rows, parented under the
        # trace contexts of the requests each dispatch serves
        from ray_tpu.observability import StepTelemetry, get as _get_tel

        self._tel = _get_tel(f"llm_dispatch:{name}") or StepTelemetry(
            f"llm_dispatch:{name}", kind="serve")
        self._jit_cache_sizes: Dict[int, int] = {}
        # a dispatch that traced and compiled a program leaves a large
        # young heap behind: it is collected the next time the loop idles
        self._collect_when_idle = False
        self._t_snapshot = 0.0
        self._pub_marker: Optional[tuple] = None
        self._wake = threading.Event()
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- public
    def eta_s(self) -> float:
        """Admission ETA estimate: how long a request submitted NOW is
        expected to wait+run, from the queue depth and the service-time
        EMA. 0.0 until the first completion (no data, no shedding)."""
        ema = self._ema_service_s
        if ema <= 0.0:
            return 0.0
        waiting = self._queue.qsize() + len(self._waiting)
        return (waiting / max(1, self.n_slots)) * ema + ema

    def _check_admission(self, sampling) -> None:
        """Deadline/overload admission control — the typed-503 gate.
        Raises; on the happy path costs two counter reads."""
        from ray_tpu.serve.errors import DeadlineExceededError, RequestShedError

        now = time.time()
        deadline = sampling.deadline
        if deadline is not None and deadline <= now:
            self._m["deadline_expired"] += 1
            raise DeadlineExceededError(
                f"deadline passed {now - deadline:.2f}s before admission"
            )
        if self.max_queue:
            waiting = self._queue.qsize() + len(self._waiting)
            if waiting >= self.max_queue:
                self._m["shed_queue_full"] += 1
                raise RequestShedError(
                    f"admission queue full ({waiting} waiting >= "
                    f"max_queue {self.max_queue})",
                    retry_after_s=max(0.1, round(self.eta_s(), 2)),
                )
        if deadline is not None:
            eta = self.eta_s()
            if eta > 0.0 and now + eta > deadline:
                self._m["shed_eta"] += 1
                raise RequestShedError(
                    f"queue ETA {eta:.2f}s overruns the request deadline "
                    f"({deadline - now:.2f}s away) — shedding instead of "
                    f"queueing a guaranteed miss",
                    retry_after_s=max(0.1, round(eta, 2)),
                )

    def submit(self, prompt: List[int], max_new_tokens: int,
               on_done=None, sampling=None, rid: Optional[str] = None) -> _Request:
        from ray_tpu.serve._internal.sampling import SamplingParams

        if self._dead is not None:
            raise RuntimeError(f"engine is dead: {self._dead}")
        if len(prompt) == 0:
            # length 0 is the macro plan's padding-row sentinel
            raise ValueError("prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt+generation ({len(prompt)}+{max_new_tokens}) exceeds "
                f"engine max_len {self.max_len}"
            )
        sampling = SamplingParams.from_request(sampling)
        if not sampling.greedy and sampling.seed is None:
            # seedless sampled requests draw fresh entropy: two users
            # omitting the seed must not share a token stream (an
            # explicit seed — including 0 — stays fully reproducible)
            import dataclasses as _dc
            import os as _os

            sampling = _dc.replace(
                sampling, seed=int.from_bytes(_os.urandom(4), "little"))
        # prefill-pool requests hand off after their first token, so
        # they reserve blocks for the PROMPT only (admission writes
        # prompt positions; the decode pool reserves the full span)
        will_migrate = self.role == "prefill" and max_new_tokens > 1
        span = len(prompt) if will_migrate else len(prompt) + max_new_tokens
        need = self._alloc.blocks_for_tokens(span)
        if need > self.n_blocks - 1:
            raise ValueError(
                f"request needs {need} KV blocks, pool only has "
                f"{self.n_blocks - 1}"
            )
        try:
            self._check_admission(sampling)
        except Exception as e:
            if rid:
                _lifeline.record(rid, "shed", engine=self.name,
                                 reason=type(e).__name__)
            raise
        req = _Request([int(t) for t in prompt], max_new_tokens,
                       on_done=on_done, sampling=sampling, rid=rid)
        req._migrate = will_migrate
        req._qtok = len(req.prompt)
        with self._qtok_lock:
            self._queued_prefill_tokens += req._qtok
        try:
            from ray_tpu.util import tracing

            req._trace_ctx = tracing.current_context()
        except Exception:
            pass
        if rid:
            _lifeline.record(rid, "submit", ctx=req._trace_ctx,
                             rid_b=req._rid_b, engine=self.name,
                             prompt_tokens=len(req.prompt),
                             max_new_tokens=max_new_tokens,
                             migrate=will_migrate,
                             a=float(len(req.prompt)))
        self._queue.put(req)
        if self._dead is not None:
            # lost the race with the loop dying: the dead loop will never
            # drain the queue, so fail the request here instead of letting
            # the caller eat a generic timeout
            msg = f"engine is dead: {self._dead}"
            _finish(req, error=msg)
            raise RuntimeError(msg)
        self._wake.set()
        return req

    def generate(self, prompt: List[int], max_new_tokens: int,
                 timeout: float = 120.0, sampling=None,
                 rid: Optional[str] = None) -> List[int]:
        req = self.submit(prompt, max_new_tokens, sampling=sampling, rid=rid)
        if not req.done.wait(timeout):
            # CANCEL, don't abandon: a timed-out request left live would
            # keep burning decode steps and holding KV blocks
            # forever — cancellation frees the slot and its blocks at
            # the engine's next plan boundary
            self.cancel(req, "cancelled: generation timed out")
            raise TimeoutError("generation timed out (request cancelled)")
        if req.error is not None:
            if req.exc is not None:
                # typed failure (shed / deadline / replica-death):
                # propagate the class, not a stringly RuntimeError — the
                # handle's redispatch policy and the proxy's HTTP
                # mapping both classify by isinstance
                raise req.exc
            raise RuntimeError(f"generation failed: {req.error}")
        return req.tokens

    def cancel(self, req: _Request, msg: str = "cancelled") -> None:
        """Cancel an in-flight request (idempotent, any thread). The
        request completes immediately with `error=msg`; the engine loop
        reclaims its slot and KV blocks at the next plan boundary
        (_repair). Device lanes it still rides in already-dispatched
        plans emit discarded tokens, billed as speculative waste. A
        cancel racing normal delivery loses cleanly: _finish's atomic
        test-and-set makes whoever gets there first the sole completer."""
        if _finish(req, error=msg, reason="cancelled"):
            stats = self._request_span(req, "cancelled")
            if req.rid:
                _lifeline.record(req.rid, "finish", ctx=req._trace_ctx,
                                 rid_b=req._rid_b, engine=self.name, **stats)
                _lifeline.finish(req.rid)
            self._wake.set()

    def shutdown(self):
        self._running = False
        self._wake.set()
        self._thread.join(timeout=10)
        if self._dead is None and not self._thread.is_alive():
            # final drain: the loop can exit between the _resolve that
            # completed a request and the _repair that frees its slot
            # and KV blocks (the ONLY freeing path in spec mode, which
            # never evicts at plan time) — run it here, single-threaded
            # now, so shutdown leaves allocator refs == radix-cache refs
            self._repair()

    def load(self) -> int:
        """Resident + queued request count — the autoscaling load
        signal a Replica publishes through the telemetry path. Counter
        reads only (the slot list and wait queue belong to the loop
        thread; a momentarily torn read just shifts one load sample)."""
        return (
            self._queue.qsize()
            + len(self._waiting)
            + self._rqueue.qsize()
            + len(self._resuming)
            + sum(1 for s in self._slots if s is not None)
        )

    # ------------------------------------------------------- KV plane
    def _dec_qtok(self, req: _Request) -> None:
        """Retire a request's queued-prefill-token contribution
        (idempotent — admission, shedding and death can race only in
        program order on the loop thread, but belt and braces)."""
        n, req._qtok = req._qtok, 0
        if n:
            with self._qtok_lock:
                self._queued_prefill_tokens -= n

    def pool_signals(self) -> Dict[str, Any]:
        """The per-pool autoscaling signals (ISSUE 18): queued prefill
        tokens for the prefill pool (work not yet admitted — slot-count
        load signals under-weigh long prompts), decode lane occupancy
        for the decode pool (resident + inbound migrations). Counter
        reads only; published by the Replica stat reporter."""
        with self._qtok_lock:
            qtok = self._queued_prefill_tokens
        resumes = self._rqueue.qsize() + len(self._resuming)
        return {
            "pool": self.role,
            "queued_prefill_tokens": max(0, qtok),
            "decode_lanes_busy":
                sum(1 for s in self._slots if s is not None) + resumes,
            "resume_queue": resumes,
        }

    def kv_inventory(self) -> List[str]:
        """Digest list of locally committed prompt prefixes — the
        replica's contribution to the cluster-wide cache inventory
        (JSON-safe, atomic snapshot)."""
        return self._kv_inv.published() if self._kv_inv is not None else []

    def has_local_prefix(self, digest) -> bool:
        return self._kv_inv is not None and digest in self._kv_inv

    def _register_prefix(self, prompt: List[int]) -> None:
        """Record a radix-committed prefix in the publishable inventory
        (loop thread, right after the trie insert)."""
        if self._kv_inv is None:
            return
        n_committed = (len(prompt) // self.block_size) * self.block_size
        self._kv_inv.register(prompt, n_committed)

    def call_on_loop(self, fn, timeout: float = 30.0):
        """Run `fn` on the engine loop thread (the only thread allowed
        to touch the allocator, the radix trie and the cache handle) and
        return its result. Blocks the CALLER, never the loop."""
        import concurrent.futures

        if self._dead is not None:
            raise RuntimeError(f"engine is dead: {self._dead}")
        fut: "concurrent.futures.Future" = concurrent.futures.Future()
        self._jobs.put((fn, fut))
        self._wake.set()
        return fut.result(timeout)

    def _drain_jobs(self) -> None:
        while True:
            try:
                fn, fut = self._jobs.get_nowait()
            except queue.Empty:
                return
            try:
                fut.set_result(fn())
            except Exception as e:  # noqa: BLE001 — job errors go to the caller
                fut.set_exception(e)

    def _refuse_block_transfer(self, what: str) -> None:
        """K/V blocks shipped between replicas resume nothing where a
        lane also holds rows of its own."""
        if self.state_bytes:
            raise ValueError(
                f"{what} is refused: the model's lanes hold recurrent "
                "state (or a window layer's ring) beside their blocks, "
                "and K/V blocks without the state at their boundary "
                "cannot resume or seed a sequence")

    def submit_resumed(self, prompt: List[int], first_token: int,
                       max_new_tokens: int, k, v, n_data_blocks: int,
                       on_done=None, sampling=None, rid: Optional[str] = None,
                       t_export: Optional[float] = None) -> _Request:
        """Admit a MIGRATED request: the prompt was prefilled (and its
        first token sampled) on a prefill-pool replica; `k`/`v` are its
        gathered KV block slices fetched from the object plane (padded
        to the exporter's bucket). The request joins the resume queue
        and the loop imports it at the next plan boundary — no admission
        control (it already paid admission at the prefill pool; shedding
        mid-migration would discard finished prefill work)."""
        from ray_tpu.serve._internal.sampling import SamplingParams

        self._refuse_block_transfer("submit_resumed")
        if self._dead is not None:
            raise RuntimeError(f"engine is dead: {self._dead}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt+generation ({len(prompt)}+{max_new_tokens}) exceeds "
                f"engine max_len {self.max_len}")
        need = self._alloc.blocks_for_tokens(len(prompt) + max_new_tokens)
        if need > self.n_blocks - 1:
            raise ValueError(
                f"resumed request needs {need} KV blocks, pool only has "
                f"{self.n_blocks - 1}")
        sampling = SamplingParams.from_request(sampling)
        req = _Request([int(t) for t in prompt], max_new_tokens,
                       on_done=on_done, sampling=sampling, rid=rid)
        req._resume = {"k": k, "v": v, "n_data": int(n_data_blocks),
                       "first": int(first_token), "t_export": t_export}
        try:
            from ray_tpu.util import tracing

            req._trace_ctx = tracing.current_context()
        except Exception:
            pass
        if rid:
            _lifeline.record(rid, "resume_submit", ctx=req._trace_ctx,
                             rid_b=req._rid_b, engine=self.name,
                             blocks=int(n_data_blocks),
                             a=float(n_data_blocks))
        self._rqueue.put(req)
        if self._dead is not None:
            msg = f"engine is dead: {self._dead}"
            _finish(req, error=msg)
            raise RuntimeError(msg)
        self._wake.set()
        return req

    def export_prefix(self, digest) -> Optional[Dict[str, Any]]:
        """Cluster prefix-cache export: look `digest` up in the local
        inventory, gather its committed blocks (dispatched on the loop
        thread, BEFORE any later mutation can recycle them — device
        programs serialize) and publish ONE object-plane put (this
        thread: serialization syncs on the gather, off the loop).
        Returns the handoff dict (tokens + hex ref + a live "_ref" the
        caller must hold until importers are done) or None on miss."""
        from ray_tpu.serve._internal import kv_plane

        self._refuse_block_transfer("export_prefix")

        def job():
            if self._kv_inv is None:
                return None
            tokens = self._kv_inv.tokens_for(digest)
            if tokens is None:
                return None
            blocks = self._prefix.match_blocks(tokens)
            if not blocks:
                return None
            import jax.numpy as jnp

            from ray_tpu.models import paged

            ids = kv_plane.pad_block_ids(blocks)
            k, v = paged.jitted_gather_kv_blocks()(
                self.cache, jnp.asarray(ids))
            return list(tokens[: len(blocks) * self.block_size]), k, v, \
                len(blocks)

        res = self.call_on_loop(job)
        if res is None:
            return None
        tokens, k, v, n = res
        import ray_tpu

        ref = ray_tpu.put({"k": k, "v": v, "n": n})
        with self._m_lock:
            # off-loop-thread increment: without the lock a concurrent
            # metrics() copy could tear this against the loop's counters
            self._m["prefix_exports"] += 1
        return {"tokens": tokens, "ref": ref.hex(), "n_data_blocks": n,
                "block_size": self.block_size, "_ref": ref}

    def import_prefix(self, tokens: List[int], k, v,
                      n_data_blocks: int) -> int:
        """Cluster prefix-cache import: scatter a peer's committed
        prefix blocks into the local pool and commit them to the radix
        trie, so later admissions here reuse a prefix prefilled on
        ANOTHER replica. Opportunistic — pool exhaustion drops the
        import silently (it's a cache fill, not a request). Returns
        blocks newly committed."""
        self._refuse_block_transfer("import_prefix")

        def job():
            if self._prefix is None:
                return 0
            have = self._prefix.match_blocks(tokens)
            if len(have) >= n_data_blocks:
                return 0  # already resident
            from ray_tpu.serve._internal import kv_plane
            from ray_tpu.serve._internal.kv_blocks import BlockPoolExhausted

            try:
                blocks = self._alloc.alloc(n_data_blocks)
            except BlockPoolExhausted:
                return 0
            import jax.numpy as jnp

            from ray_tpu.models import paged

            dst = kv_plane.pad_block_ids(blocks)
            self.cache = paged.jitted_scatter_kv_blocks()(
                self.cache, jnp.asarray(dst), k, v)
            committed = tokens[: n_data_blocks * self.block_size]
            added = self._prefix.insert(committed, blocks)
            # hand ownership to the cache: drop the alloc refs so the
            # trie's increfs are the only pins (duplicate blocks for
            # already-present nodes free right here — leak-audit clean)
            self._alloc.decref(blocks)
            self._register_prefix(committed)
            with self._m_lock:
                self._m["prefix_imports"] += 1
                self._m["migrated_blocks_in"] += added
            return added

        return self.call_on_loop(job)

    def metrics(self) -> Dict[str, Any]:
        """Serving metrics since construction (or reset_metrics()):
        dispatch counts, dispatches/token, lane occupancy %, TTFT/TPOT
        p50/p95/p99 from the latency histograms (bucket-interpolated;
        the histogram lock makes the snapshot safe against the engine
        loop's concurrent appends). Tokens count at DELIVERY, so read
        after requests complete for exact ratios. The copy happens under
        _m_lock so multi-counter updates (migration, prefix export) are
        all-or-nothing in the snapshot — a mid-burst scrape can't see
        migrations_out without its migrated_blocks_out."""
        with self._m_lock:
            m = dict(self._m)
        m["queue_depth"] = self.load()  # live gauge, not a counter
        m["state_bytes"] = self.state_bytes  # a constant: bytes a lane
        toks = max(1, m["tokens_out"])
        m["dispatches_per_token"] = round(m["dispatches"] / toks, 4)
        m["lane_occupancy_pct"] = round(
            100.0 * m["useful_slot_steps"] / max(1, m["slot_steps"]), 1
        )
        # plan-and-repair bill: % of PLANNED useful steps whose tokens
        # were discarded (early stop / cancellation revealed after the
        # speculative plan shipped); draft-model speculation has its
        # own, distinct rejection metric below
        m["plan_repair_waste_pct"] = round(
            100.0 * m["wasted_steps"] / max(1, m["useful_slot_steps"]), 2
        )
        # draft-model speculation ledger: % of proposed draft tokens the
        # target rejected, and the headline win — verified tokens per
        # verify round (= accepted drafts + the correction/bonus token;
        # 1.0 would mean speculation is buying nothing)
        proposed = m["draft_proposed_tokens"]
        m["draft_rejection_pct"] = round(
            100.0 * (proposed - m["draft_accepted_tokens"]) / max(1, proposed),
            2,
        )
        rounds = m["spec_verify_rounds"]
        m["accepted_tokens_per_dispatch"] = round(
            (m["draft_accepted_tokens"] + rounds) / rounds, 3
        ) if rounds else 0.0
        # admission-control ledger: total sheds + the ETA estimate the
        # next admission would be judged against
        m["shed_requests"] = m["shed_queue_full"] + m["shed_eta"]
        m["avg_service_ms"] = round(self._ema_service_s * 1e3, 1)
        m["admission_eta_ms"] = round(self.eta_s() * 1e3, 1)
        total = self.n_blocks - 1  # block 0 is the reserved null
        m["kv_blocks_total"] = total
        m["kv_blocks_in_use"] = self._alloc.used_blocks
        # peak utilization over the workload — the snapshot of record
        # (in_use drains to the cache-pinned floor between requests)
        m["kv_blocks_utilization_pct"] = round(
            100.0 * m["kv_blocks_peak_in_use"] / max(1, total), 1
        )
        if self._prefix is not None:
            m.update(self._prefix.stats())
        for key, hist in (("ttft", self._ttft), ("tpot", self._tpot),
                          ("migration", self._mig)):
            p50, p95, p99 = hist.percentiles_ms()
            m[f"{key}_ms_p50"] = p50
            m[f"{key}_ms_p95"] = p95
            m[f"{key}_ms_p99"] = p99
        if self.role is not None:
            # pool label: /api/serve groups each engine's token counters
            # (prefill_tokens / reused_prefix_tokens / tokens_out) and
            # migration ledger into per-pool views by this key
            m["pool"] = self.role
        try:
            g = _engine_metrics()
            g["dpt"].set(m["dispatches_per_token"], tags=self._tags)
            g["occupancy"].set(m["lane_occupancy_pct"], tags=self._tags)
        except Exception:
            pass
        return m

    def _request_span(self, req: _Request, reason: str,
                      fetched: Optional[_Flight] = None) -> Dict[str, Any]:
        """Write a request's `engine.request` span
        (`observability.REQUEST_SPAN`), once, where it ends: an annotation
        of no length on the calling thread, its stats the request's own
        account (`_request_stats`). `fetched` is the dispatch whose resolve
        is delivering, for a request that ends inside one. Returns the
        stats, which the caller hands to the lifeline's last event of the
        rid as they are."""
        stats = _request_stats(req, reason, fetched, self._late,
                               self.draft_params is not None)
        with self._span(REQUEST_SPAN, rid=req.rid or "", **stats):
            pass
        return stats

    def request_timeline(self, rid: str) -> List[Dict[str, Any]]:
        """One rid's process-local lifeline, time-sorted, with the
        macro-step dispatches the lane rode joined in at READ time: the
        dispatch hot path records nothing per request (one flight-ring
        write per dispatch, total), so the join scans this process's
        ring for dispatch records inside the request's [first, last]
        event window. Cluster-wide stitching (prefill→decode hop,
        redispatch attempts) happens a level up — the serve controller
        fans this out per replica and merges by rid. The rid's last event
        here (`finish`, `shed`, `migrate` or `error`) carries the request's
        own account, the stats of its `engine.request` span
        (`observability.REQUEST_SPAN`): where one slow request's time went,
        station by station, with no profiler."""
        evs = [dict(e) for e in _lifeline.events(rid)]
        ts = [e["t"] for e in evs]
        if ts:
            lo, hi = min(ts) - 1e-3, max(ts) + 1e-3
            try:
                for rec in _flightrec.read_tail(path=self._fr.path,
                                                n=self._fr.capacity):
                    if rec["kind"] == "dispatch" and lo <= rec["t"] <= hi:
                        evs.append({"t": rec["t"], "kind": "dispatch",
                                    "pid": rec["pid"],
                                    "engine": self.name,
                                    "step": rec["step"],
                                    "dispatch_ms": round(rec["a"], 3)})
            except Exception:
                pass
        evs.sort(key=lambda e: e["t"])
        return evs

    def reset_metrics(self) -> None:
        with self._m_lock:
            self._m = {k: 0 for k in self._m}
        self._ttft.reset()
        self._tpot.reset()
        self._mig.reset()
        self._tel.reset()
        if self._prefix is not None:
            for c in ("hits", "misses", "evictions", "hit_tokens",
                      "lookup_tokens"):
                setattr(self._prefix, c, 0)

    # ------------------------------------------------------------ engine
    # ---- macro-step scheduling ----------------------------------------
    def _free_request_blocks(self, req: _Request) -> None:
        """Return a request's KV blocks to the pool (idempotent — a
        request can be planned-evicted AND repaired in either order).
        Blocks the prefix cache committed stay pinned by its reference
        until cache eviction."""
        if req._blocks_freed:
            return
        req._blocks_freed = True
        self._alloc.decref(req._blocks)

    def _try_admit_paged(self, req: _Request) -> bool:
        """Reserve blocks + block table for one admission. Full
        reservation (prompt + max_new, minus the reused prefix) makes
        the plan deadlock-free by construction: an admitted request can
        always take every decode step it was promised. On exhaustion the
        radix cache evicts LRU committed prefixes; False means the
        caller must leave the request queued."""
        shared: List[int] = []
        matched = 0
        if self._prefix is not None:
            # record=False: a pool-exhausted admission retries every
            # plan tick and must not inflate the hit-rate counters —
            # record_lookup() fires once, on the admission that lands
            shared, matched = self._prefix.lookup(req.prompt, record=False)
        # migrating (prefill-pool) requests reserve prompt blocks only:
        # they ship their KV after the first token, so decode-span
        # blocks would just starve the prefill pool's admission rate
        span = len(req.prompt) if req._migrate else \
            len(req.prompt) + req.max_new_tokens
        need_total = self._alloc.blocks_for_tokens(span)
        need = need_total - len(shared)
        from ray_tpu.serve._internal.kv_blocks import BlockPoolExhausted

        try:
            private = self._alloc.alloc(need)
        except BlockPoolExhausted:
            if self._prefix is not None:
                self._prefix.evict(need - self._alloc.free_blocks)
            try:
                private = self._alloc.alloc(need)
            except BlockPoolExhausted:
                if shared:
                    self._alloc.decref(shared)
                return False
        req._start = matched
        req._blocks = shared + private
        req._blocks_freed = False
        if self._prefix is not None:
            self._prefix.record_lookup(len(req.prompt), len(shared))
        with self._m_lock:
            self._m["reused_prefix_tokens"] += matched
            self._m["prefill_tokens"] += len(req.prompt) - matched
            self._m["kv_blocks_peak_in_use"] = max(
                self._m["kv_blocks_peak_in_use"], self._alloc.used_blocks
            )
        if req.rid:
            _lifeline.record(req.rid, "admit", ctx=req._trace_ctx,
                             rid_b=req._rid_b, engine=self.name,
                             matched_prefix=matched,
                             blocks=len(req._blocks),
                             a=float(matched), b=float(len(req._blocks)))
        self._dec_qtok(req)
        if self._prefix is not None:
            # commit the full prompt blocks NOW: the prefill that fills
            # them rides the same (or an earlier) phase of the very
            # dispatch this plan compiles to, and phases execute in plan
            # order — so even a same-plan admission can share them
            self._prefix.insert(req.prompt, req._blocks)
            self._register_prefix(req.prompt)
        return True

    def _table_row(self, req: Optional[_Request]) -> "np.ndarray":
        row = np.zeros(self._mb, np.int32)  # null-block padded
        if req is not None:
            row[: len(req._blocks)] = req._blocks
        return row

    def _snapshot_phase(self) -> Dict[str, Any]:
        """Per-phase device plan arrays from current slot occupancy:
        block tables + sampling params. Freed slots stay all-null, so a
        zombie lane (stopped/cancelled request still riding the plan)
        can only write the null block from this phase on."""
        from ray_tpu.serve._internal.sampling import MAX_STOP_TOKENS

        B = self.n_slots
        tables = np.zeros((B, self._mb), np.int32)
        temps = np.zeros(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        top_ps = np.ones(B, np.float32)
        stops = np.full((B, MAX_STOP_TOKENS), -1, np.int32)
        for s, r in enumerate(self._slots):
            if r is None:
                continue
            tables[s] = self._table_row(r)
            sp = r.sampling
            temps[s] = sp.temperature
            top_ks[s] = sp.top_k
            top_ps[s] = sp.top_p
            stops[s] = sp.stop_row()
        return {"tables": tables, "temps": temps, "top_ks": top_ks,
                "top_ps": top_ps, "stops": stops}

    def _admit_resumes(self) -> None:
        """Import inbound migrations at the plan boundary: for each
        fetched payload in the resume queue, claim a free slot, reserve
        the FULL decode span, and land the KV with ONE fused scatter
        dispatch that also arms the slot (absolute position, remaining
        budget, recomputed rng). The slot then rides the next plan's
        phases as an ordinary live lane — the request continues
        mid-stream exactly where the prefill replica left it. Pool
        exhaustion leaves the head queued (FIFO, retried next tick)
        after a prefix-cache evict attempt."""
        while True:
            try:
                self._resuming.append(self._rqueue.get_nowait())
            except queue.Empty:
                break
        if not self._resuming:
            return
        import jax.numpy as jnp

        from ray_tpu.models import paged
        from ray_tpu.serve._internal import kv_plane
        from ray_tpu.serve._internal.kv_blocks import BlockPoolExhausted

        while self._resuming:
            req = self._resuming[0]
            if req.done.is_set():  # cancelled while queued
                self._resuming.popleft()
                continue
            slot = next(
                (i for i, r in enumerate(self._slots) if r is None), None)
            if slot is None:
                return
            need = self._alloc.blocks_for_tokens(
                len(req.prompt) + req.max_new_tokens)
            try:
                blocks = self._alloc.alloc(need)
            except BlockPoolExhausted:
                if self._prefix is not None:
                    self._prefix.evict(need - self._alloc.free_blocks)
                try:
                    blocks = self._alloc.alloc(need)
                except BlockPoolExhausted:
                    return
            self._resuming.popleft()
            payload, req._resume = req._resume, None
            req._blocks = blocks
            req._blocks_freed = False
            req._start = len(req.prompt)  # fully resident: no prefill owed
            n_data = payload["n_data"]
            dst = kv_plane.pad_block_ids(blocks[:n_data])
            if req.sampling.greedy:
                rng = np.zeros(2, np.uint32)
            else:
                # bit-exact recompute of the carried key the prefill
                # side's admission would have stored in its slot — rng
                # state never rides the wire
                rng = kv_plane.carried_rng_for_seed(req.sampling.seed or 0)
            self.cache = paged.jitted_import_kv_blocks()(
                self.cache, jnp.asarray(dst), payload["k"], payload["v"],
                jnp.int32(slot), jnp.int32(len(req.prompt)),
                jnp.int32(req.max_new_tokens - 1), jnp.asarray(rng))
            self._next_dev = self._next_dev.at[slot].set(
                jnp.int32(payload["first"]))
            req.tokens.append(payload["first"])
            req._t_first = time.perf_counter()  # TTFT was paid at prefill
            req._remaining = req.max_new_tokens - 1
            if self.draft_params is not None:
                req._rounds_est = self._rounds_for(req._remaining) \
                    if req._remaining > 0 else 0
                req._rounds_inflight = 0
            self._slots[slot] = req
            if self._prefix is not None:
                self._prefix.insert(req.prompt, req._blocks)
                self._register_prefix(req.prompt)
            with self._m_lock:
                self._m["migrations_in"] += 1
                self._m["migrated_blocks_in"] += n_data
                self._m["kv_blocks_peak_in_use"] = max(
                    self._m["kv_blocks_peak_in_use"],
                    self._alloc.used_blocks)
            if req.rid:
                _lifeline.record(req.rid, "kv_import", ctx=req._trace_ctx,
                                 rid_b=req._rid_b, engine=self.name,
                                 blocks=n_data, a=float(n_data))
            if payload.get("t_export") is not None:
                # end-to-end handoff latency (cross-process wall clock)
                self._mig.observe(max(0.0, time.time() - payload["t_export"]))
            if req._remaining <= 0:
                # max_new_tokens == 1: the migrated first token IS the
                # whole answer (prefill normally keeps these local, but
                # a redispatched resume can land here)
                self._slots[slot] = None
                self._free_request_blocks(req)
                if _finish(req, reason="length"):
                    self._m["requests_completed"] += 1
                    self._request_span(req, "length")

    def _migrate_out(self, req: _Request) -> None:
        """Export a prefill-pool request's KV at its first token: ONE
        fused gather + ONE object-plane put (the migration hot path's
        entire per-handoff cost — lint-pinned), then complete the
        request with reason "migrated"; the serving layer chains the
        decode-pool call from req.export. The put synchronizes on the
        gather before returning, so the blocks free immediately after;
        the ObjectRef stays alive on req.export until the decode side's
        reply lands. Export failure is a typed RETRYABLE failure — no
        output escaped (the first token rides the resume body, not the
        caller's reply)."""
        from ray_tpu.serve._internal import kv_plane

        t0 = time.perf_counter()
        try:
            n_data = self._alloc.blocks_for_tokens(len(req.prompt))
            ref, _w = kv_plane.export_kv_blocks(
                self.cache, req._blocks[:n_data], rid=req.rid)
        except Exception as e:  # noqa: BLE001 — device/object-plane errors
            from ray_tpu.serve.errors import ReplicaDiedError

            stats = self._request_span(req, "error", self._resolving)
            if req.rid:
                _lifeline.record(req.rid, "error", ctx=req._trace_ctx,
                                 rid_b=req._rid_b, engine=self.name,
                                 error=f"kv export failed: "
                                       f"{type(e).__name__}", **stats)
            self._free_request_blocks(req)
            _finish(req, exc=ReplicaDiedError(
                f"kv export failed: {type(e).__name__}: {e}", started=False))
            self._wake.set()
            return
        req.export = {
            "ref": ref, "ref_hex": ref.hex(), "n_data_blocks": n_data,
            "block_size": self.block_size, "t_export": time.time(),
        }
        with self._m_lock:
            self._m["migrations_out"] += 1
            self._m["migrated_blocks_out"] += n_data
        self._mig.observe(time.perf_counter() - t0)
        req._t_done = time.perf_counter()
        if req.rid:
            _lifeline.record(req.rid, "kv_export", ctx=req._trace_ctx,
                             rid_b=req._rid_b, engine=self.name,
                             blocks=n_data, a=float(n_data),
                             b=(time.perf_counter() - t0) * 1e3)
        if _finish(req, reason="migrated"):
            dur = req._t_done - req._t_submit
            ema = self._ema_service_s
            self._ema_service_s = dur if ema <= 0.0 else 0.8 * ema + 0.2 * dur
            stats = self._request_span(req, "migrated", self._resolving)
            if req.rid:
                _lifeline.record(req.rid, "migrate", ctx=req._trace_ctx,
                                 rid_b=req._rid_b, engine=self.name,
                                 blocks=n_data, **stats)
                # terminal on THIS engine (the request lives on at the
                # decode pool, in that process's store) — age the buffer
                _lifeline.finish(req.rid)
        self._free_request_blocks(req)
        self._wake.set()

    def _plan_start(self) -> None:
        """One clock read a plan: its start is the admission stamp of the
        requests it admits and the first-seen stamp of those the queue has
        brought since the last plan, the unstamped tail of `_waiting` (the
        wait account of `_dispatch_counts`)."""
        self._t_plan = now = time.perf_counter()
        for req in reversed(self._waiting):  # arrivals are at the tail
            if req._t_seen is not None:
                break
            req._t_seen = now

    def _admit_waiting(self) -> Tuple[List[Tuple[int, _Request]], Dict[str, int]]:
        """Open a phase: admit from the head of `_waiting` into the free
        lanes, FIFO, until lanes or blocks run out. Returns the admissions
        and the lane account of the lanes left empty: `vacant` where nobody
        was waiting, `blocked` where the head of the queue was refused by
        the pool (it stays queued, FIFO order kept)."""
        admissions = []
        free = [i for i, r in enumerate(self._slots) if r is None]
        while free and self._waiting:
            req = self._waiting[0]
            if not self._try_admit_paged(req):
                break
            self._waiting.popleft()
            slot = free.pop(0)
            req._t_admit = self._t_plan
            # migrating requests are prefill-only: zero decode steps
            # owed here, so the slot frees this very phase and the
            # device lane goes inactive right after its admission
            # prefill (rems row 0 in _dispatch_macro)
            req._remaining = 0 if req._migrate else req.max_new_tokens - 1
            if self.draft_params is not None:
                req._rounds_est = self._rounds_for(req._remaining) \
                    if req._remaining > 0 else 0
                req._rounds_inflight = 0
            self._slots[slot] = req
            admissions.append((slot, req))
        idle, refused = len(free), bool(self._waiting)
        return admissions, {"vacant": 0 if refused else idle,
                            "blocked": idle if refused else 0}

    def _plan(self) -> Optional[List[Dict[str, Any]]]:
        """Plan up to macro_phases phases of admissions + adaptive decode
        chunks purely from host counters. Greedy requests make this
        exact; sampled requests make it SPECULATIVE (a stop token can
        end them early — _deliver/_repair reconcile). Mutates engine
        bookkeeping to the post-macro-step state: slot assignments,
        per-request remaining counters, evictions, block
        allocations/frees.

        The plan's length follows what the lanes hold. While every lane
        is live or somebody waits (`vacant` 0: full, or `blocked` on the
        pool) an arrival could not be let in, so the phases run on to
        macro_phases. The first phase `_admit_waiting` closes with a lane
        vacant is the plan's last and decodes at most the vacancy quantum
        (`_quantum`, from the engine's own measured step time; the phase
        carries it as `short`): the loop is back at its intake that soon,
        and whoever arrived meanwhile is admitted by the plan after the
        one already queued behind this dispatch. Where the quantum is 0
        (admissions not yet timed, or that take longer than a quantum)
        no phase closes the plan: it is the parent's."""
        self._plan_start()
        if self.draft_params is not None:
            return self._plan_spec()
        self._admit_resumes()
        phases = []
        while len(phases) < self.macro_phases:
            admissions, empty = self._admit_waiting()
            live = [(s, r) for s, r in enumerate(self._slots)
                    if r is not None and r._remaining > 0]
            if not live and not admissions:
                break
            snapshot = self._snapshot_phase()
            # a lane nobody waits for closes the plan, a quantum on (none:
            # letting somebody in would stall the residents for longer)
            q = self._quantum() if empty["vacant"] else 0
            # adaptive chunk: decode exactly to the next scheduling event
            # (a slot finishing) so the freed lane re-admits immediately
            steps = min([q or self.chunk] + [r._remaining for _, r in live]) if live else 0
            # invariant: steps <= every live remaining, so each live slot
            # takes exactly `steps` real tokens this phase
            takes = []
            for s, r in live:
                r._remaining -= steps
                takes.append((s, r, steps))
            for s, r in enumerate(self._slots):
                if r is not None and r._remaining == 0:
                    self._slots[s] = None  # evict: freed for the next phase
                    if not r._migrate:
                        # a migrating request's blocks must survive to
                        # the export gather (fired from _deliver when
                        # its first token resolves) — _migrate_out and
                        # the _deliver stop/cancel paths free them
                        self._free_request_blocks(r)
            phases.append({"steps": steps, "admissions": admissions,
                           "takes": takes, **empty, **({"short": q} if q else {}), **snapshot})
            if q:
                break
        return phases or None

    def _quantum(self) -> int:
        """The vacancy quantum in decode steps: what takes about
        `VACANT_PLAN_S` at the median of the last few readings of a decode
        step's time (`_time_dispatch`), between 1 and `chunk`; `chunk`
        until there is a reading (short plans are what brings the first:
        a dispatch of decode steps alone behind another). From there 0, no
        short plan at all, until three admitting phases have been timed (a
        median of fewer is one reading's word: an engine that has not
        seen what its admissions cost plans as the parent did, so a
        closed loop's ramp, one lone client and then a burst, is the
        parent's) and while the median of the last few took longer than
        `VACANT_PLAN_S`: each arrival let in costs every resident that
        long, quantum after quantum."""
        if not self._step_s:
            return self.chunk
        if len(self._admit_s) < 3 or median_high(self._admit_s) > VACANT_PLAN_S:
            return 0
        return min(self.chunk, max(1, int(np.ceil(VACANT_PLAN_S / median_high(self._step_s)))))

    def _time_dispatch(self, planned: Dict[str, int], ran_s: float) -> None:
        """Read a decode step's or an admitting phase's time off a dispatch
        that ran for `ran_s`: one that admitted nobody spent it on its
        decode steps; one that admitted spent on each admitting phase what
        its decode steps, at the measured pace, leave over. `_resolve_next`
        reads only where the host knows both ends (so whatever the plans'
        length, and whether the gate of `_quantum` is open or shut, the
        next admissions are read and can move it); `_quantum` takes
        medians over what is left of the host's jitter."""
        steps, admitting = planned.get("steps", 0), planned.get("admit_phases", 0)
        if admitting and self._step_s:
            self._admit_s.append((ran_s - steps * median_high(self._step_s)) / admitting)
        elif steps and not admitting:
            self._step_s.append(ran_s / steps)

    def _rounds_for(self, tokens_owed: int) -> int:
        """Verify rounds expected to cover `tokens_owed` tokens, from
        the acceptance EMA (clamped to [1, n_spec + 1] tokens/round)."""
        e = min(max(self._accept_ema, 1.0), float(self.n_spec + 1))
        return max(1, int(np.ceil(tokens_owed / e)))

    def _plan_spec(self) -> Optional[List[Dict[str, Any]]]:
        """Speculative plan: phases of verify ROUNDS instead of decode
        steps. Acceptance is data-dependent, so per-request round counts
        are ESTIMATES from the acceptance EMA (resynced at resolution
        against observed accepted lengths) — and, critically, slots are
        NEVER evicted at plan time: an estimate saying a request is done
        is not the request being done, and freeing its blocks while a
        live device lane still writes them would hand corrupted blocks
        to the next admission. Eviction happens only in _repair(), after
        delivery confirms completion. A lane that finishes earlier than
        estimated rides its planned rounds emitting zero-count rows (the
        device zeroed its `remaining`); a lane that finishes later gets
        more rounds planned after the resync."""
        self._admit_resumes()
        phases = []
        while len(phases) < self.macro_phases:
            admissions, empty = self._admit_waiting()
            live = [(s, r) for s, r in enumerate(self._slots)
                    if r is not None]
            owing = [r._rounds_est for _, r in live if r._rounds_est > 0]
            if not owing and not admissions:
                break
            snapshot = self._snapshot_phase()
            steps = min([self.chunk] + owing) if owing else 0
            takes = []
            if steps > 0:
                # EVERY occupied slot rides the phase, not just the ones
                # the estimate says owe rounds: the device advances every
                # active lane each round regardless of the plan, so a
                # slot missing from `takes` would have its counts dropped
                # on the floor — lost tokens, then a device lane whose
                # `remaining` hits zero while the host still waits. Lanes
                # the estimate got right just emit zero-count rows.
                for s, r in live:
                    r._rounds_est = max(0, r._rounds_est - steps)
                    r._rounds_inflight += steps
                    takes.append((s, r, steps))
            phases.append({"steps": steps, "admissions": admissions,
                           "takes": takes, **empty, **snapshot})
        return phases or None

    def _bucket_paged(self, n: int) -> int:
        """Paged prompt bucket: power-of-two, at least one block, at
        most the table span — always a multiple of block_size (the
        suffix-prefill writes whole blocks)."""
        b = 16
        while b < n:
            b *= 2
        return min(max(b, self.block_size), self._mb * self.block_size)

    def _variant(self, phases: List[Dict[str, Any]]):
        """(A, P) of the compiled macro-step a plan runs. P, the padded
        prompt width, is the plan's longest suffix bucketed to a power of
        two, and alone names the program: A, its admission lanes, is
        always the engine's lanes rounded up to a power of two (no phase
        admits more), and each admitting phase runs at the width of its
        own admissions inside the program (`models/paged.admit_phase`).
        A plan that admits nobody runs the program of the last dispatch:
        it takes no admission branch, so any P serves it and none is
        compiled for it."""
        suffixes = [_suffix_len(r) for p in phases for _, r in p["admissions"]]
        P = self._bucket_paged(max(suffixes)) if suffixes else self._last_P
        return self._admit_lanes, P

    def _dispatch_macro(self, phases: List[Dict[str, Any]],
                        counts: Dict[str, int]) -> None:
        """Ship the plan as ONE jitted dispatch and append the result to
        the fetch frontier (resolved one macro-step behind). Admission
        rows carry only each prompt's SUFFIX beyond its reused prefix,
        and the per-phase block tables + sampling plan ride along as
        extra program arguments. `counts` is the plan's
        `_dispatch_counts`, kept for the dispatch's `engine.resolve`."""
        import jax.numpy as jnp

        from ray_tpu.serve._internal.sampling import MAX_STOP_TOKENS

        K = self.macro_phases
        A, P = self._variant(phases)
        self._last_P = P
        B, MB = self.n_slots, self._mb
        seq = self._m["dispatches"]
        flight = _Flight(seq, self._late)  # its admissions' accounts point at it
        steps = np.zeros(K, np.int32)
        has_admit = np.zeros(K, bool)
        prompts = np.zeros((K, A, P), np.int32)
        lengths = np.zeros((K, A), np.int32)
        slots = np.zeros((K, A), np.int32)
        rems = np.zeros((K, A), np.int32)
        starts = np.zeros((K, A), np.int32)
        seeds = np.zeros((K, A), np.uint32)
        tables = np.zeros((K, B, MB), np.int32)
        temps = np.zeros((K, B), np.float32)
        top_ks = np.zeros((K, B), np.int32)
        top_ps = np.ones((K, B), np.float32)
        stops = np.full((K, B, MAX_STOP_TOKENS), -1, np.int32)
        for k, ph in enumerate(phases):
            steps[k] = ph["steps"]
            tables[k] = ph["tables"]
            temps[k] = ph["temps"]
            top_ks[k] = ph["top_ks"]
            top_ps[k] = ph["top_ps"]
            stops[k] = ph["stops"]
            for a, (slot, req) in enumerate(ph["admissions"]):
                has_admit[k] = True
                req._acct.first = flight
                suffix = req.prompt[req._start:]
                prompts[k, a, : len(suffix)] = suffix
                lengths[k, a] = len(suffix)
                starts[k, a] = req._start
                # greedy rows never consume their key; submit()
                # materialized a real seed for every sampled row
                seeds[k, a] = np.uint32((req.sampling.seed or 0) & 0xFFFFFFFF)
                slots[k, a] = slot
                # migrating rows arm ZERO decode steps: the admission
                # prefill still samples their first token, then the lane
                # goes inactive (writes aim at the null block) — decode
                # happens on the importing replica
                rems[k, a] = 0 if req._migrate else req.max_new_tokens - 1
        riders = ([r for p in phases for _, r in p["admissions"]]
                  + [r for p in phases for _, r, _ in p["takes"]])
        # static variant selection: only pay the device sampling
        # pipeline when a sampled request actually rides the plan
        plan_sampled = any(not r.sampling.greedy for r in riders)
        t0 = time.perf_counter()
        try:
            plan_args = [jnp.asarray(x) for x in (
                steps, has_admit, prompts, lengths, starts, slots, rems,
                seeds, tables, temps, top_ks, top_ps, stops)]
            if self.draft_params is not None:
                # third static variant family: the speculative macro
                # program (drafts + batched verification per round)
                self._macro_paged_fn = self._D.jitted_macro_step_slots_spec(
                    self.cfg, self.draft_cfg, self.chunk, self.n_spec,
                    sampled=plan_sampled)
                (toks_dev, counts_dev, firsts_dev, self._next_dev,
                 self.cache, self.draft_cache) = self._macro_paged_fn(
                    self.params, self.draft_params, self.cache,
                    self.draft_cache, self._next_dev, *plan_args)
                entry = ("spec", (toks_dev, counts_dev), firsts_dev, phases,
                         seq)
            else:
                self._macro_paged_fn = self._D.jitted_macro_step_slots_paged(
                    self.cfg, self.chunk, sampled=plan_sampled)
                (toks_dev, firsts_dev, self._next_dev, self.cache,
                 *counted_dev) = self._macro_paged_fn(
                    self.params, self.cache, self._next_dev, *plan_args)
                entry = ("macro", toks_dev, firsts_dev, phases, seq,
                         *counted_dev)
        except Exception:
            # park the plan so _die can fail requests whose ONLY remaining
            # reference is this plan (admitted AND fully planned-out slots
            # are already evicted from the host bookkeeping)
            self._pending.append(("macro", None, None, phases, seq))
            raise
        self._record_dispatch(t0, time.perf_counter(), self._macro_paged_fn,
                              riders)
        self._m["dispatches"] += 1
        self._m["short_plans"] += counts.get("short", 0)
        for ph in phases:
            live = sum(t for _, _, t in ph["takes"])
            self._m["slot_steps"] += ph["steps"] * self.n_slots
            self._m["useful_slot_steps"] += live
            if self.state_bytes:
                self._m["state_lane_steps"] += live
        for key in _PLAN_SUMS:
            self._m[key] += counts.get(key, 0)
        self._planned[seq] = counts
        if self._ctx_chunk:
            self._m["span_chunks"] += sum(ph["steps"] for ph in phases) * -(
                -self._mb * self.block_size // self._ctx_chunk)
        # enqueued: the one stamp a dispatch, the end of `plan_us` and the
        # start of `flight_us` of the requests it admits
        flight.t_enq = now = time.perf_counter()
        if self._pending:  # it runs behind the dispatch in flight: `ahead_us`
            self._last_flight.behind = flight
        else:  # an idle device starts on it now
            self._t_started = now
        self._flights[seq] = self._last_flight = flight
        self._pending.append(entry)

    def _shed_expired(self) -> None:
        """Deadline shed at plan boundaries: a QUEUED request whose
        deadline already passed gets a typed failure now instead of
        burning decode steps on a result nobody can use. In-flight
        requests run to completion (their slots are already paid for —
        evicting mid-macro-step would cost a repair for no capacity
        gain). The finished entries leave the wait queue via _repair."""
        if not self._waiting:
            return
        now = time.time()
        shed = None
        for r in self._waiting:
            d = r.sampling.deadline
            if d is not None and d <= now and not r.done.is_set():
                shed = shed or []
                shed.append((r, now - d))
        if shed:
            from ray_tpu.serve.errors import DeadlineExceededError

            for r, late in shed:
                self._m["deadline_expired"] += 1
                stats = self._request_span(r, "shed")
                if r.rid:
                    stats["reason"] = "DeadlineExceededError"  # the event's own, as before
                    _lifeline.record(r.rid, "shed", ctx=r._trace_ctx,
                                     rid_b=r._rid_b, engine=self.name,
                                     a=late, **stats)
                    _lifeline.finish(r.rid)
                _finish(r, exc=DeadlineExceededError(
                    f"deadline passed {late:.2f}s into the queue"))

    def _repair(self) -> None:
        """Plan repair: reconcile host bookkeeping with requests that
        ended ahead of the speculative plan (device-side stop token,
        cancellation, timeout). Frees their slots and KV blocks so the
        very next _plan() can admit into them; drops finished stragglers
        from the wait queue. Runs on the engine loop thread at plan
        boundaries — the only place slot/block state is mutated."""
        for s, r in enumerate(self._slots):
            if r is not None and r.done.is_set():
                self._slots[s] = None
                self._free_request_blocks(r)
        if any(r.done.is_set() for r in self._waiting):
            for r in self._waiting:
                if r.done.is_set():
                    self._dec_qtok(r)
            self._waiting = deque(
                r for r in self._waiting if not r.done.is_set())

    def _collect_after_compiles(self) -> None:
        """Run the cyclic collector now, with nothing waiting and nothing
        in flight, if a program was traced and compiled since the last
        time. Tracing leaves hundreds of thousands of objects for the
        collector; left to its own counters it takes its full pass (50-70
        ms over this process's heap, every thread stopped: my chip runs,
        PR 29) whenever the next allocations cross its threshold, which is
        as traffic arrives. After an explicit pass the next full one needs
        a quarter more long-lived objects, so serving sees none."""
        if self._collect_when_idle and not self._wake.is_set():
            self._collect_when_idle = False
            gc.collect()

    def _resolve_next(self) -> None:
        """Resolve the oldest dispatch in flight, under its span, and time
        it for the vacancy quantum: it ran from `_t_started` (the end of
        the resolve before it or, shipped to an idle device, its own
        dispatch) to this resolve's end, if the host was on time for both
        (each still ran when the host came to fetch it: an interval that
        holds a compile, a collection or a device left idle by a late host
        is no reading). A resolve the host came late for (the result was
        ready before it asked) says so on its span, `late`, and is counted:
        `late_resolves` of `metrics()`, and `late` of every request that
        rides it (`_Flight.late_before`)."""
        entry = self._pending.popleft()
        planned = self._planned.pop(entry[4], {})
        late = int(entry[2] is not None and entry[2].is_ready())
        on_time = entry[2] is not None and not late
        self._flights[entry[4]].late_before = self._late
        self._late += late
        self._m["late_resolves"] += late
        # the span repeats its dispatch's plan counts: a trace that starts
        # after a dispatch still knows what its execution was planned to do
        with self._span(_SPAN_RESOLVE, seq=entry[4], late=late, **planned) as span:
            counted = self._resolve(entry)
            if counted:  # the dispatch's device counters, as the span's stats
                span.set_metadata(**counted)
        now = time.perf_counter()
        if on_time and self._t_started is not None:
            self._time_dispatch(planned, now - self._t_started)
        self._t_started = now if on_time and self._pending else None

    def _loop_macro(self) -> None:
        # every stretch of an iteration runs under one of ENGINE_SPANS, so
        # a device trace can say what this thread did while the device idled
        span = self._span
        while self._running:
            with span(_SPAN_INTAKE):
                self._drain_queue()
                self._drain_jobs()
                self._shed_expired()
                self._repair()
            if (not self._waiting
                    and not any(r is not None for r in self._slots)
                    and self._rqueue.empty() and not self._resuming):
                while self._pending:
                    self._resolve_next()
                with span(_SPAN_INTAKE):
                    self._repair()
                    self._maybe_publish(time.perf_counter())
                with span(_SPAN_IDLE):
                    self._collect_after_compiles()
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
                continue
            with span(_SPAN_PLAN):
                phases = self._plan()
                if phases:
                    A, P = self._variant(phases)
                    counts = _dispatch_counts(phases, bool(self.state_bytes),
                                              self._ctx_chunk, self._window, (A, P),
                                              self.n_slots)
            if phases:
                with span(_SPAN_DISPATCH, seq=self._m["dispatches"], A=A, P=P,
                          **counts):
                    self._dispatch_macro(phases, counts)
                # fetch one macro-step BEHIND: overlaps the one just
                # dispatched
                while len(self._pending) > 1:
                    self._resolve_next()
            elif self._pending:
                # nothing plannable until in-flight results land (spec
                # mode: every resident lane's round estimate is spent) —
                # resolve the frontier NOW so the acceptance resync can
                # unblock the next plan instead of spinning
                self._resolve_next()
            else:
                with span(_SPAN_IDLE):
                    self._wake.wait(timeout=0.01)
                    self._wake.clear()

    # ---- shared plumbing ----------------------------------------------
    def _record_dispatch(self, t0: float, t1: float, jit_fn, reqs) -> None:
        """Device-step telemetry for ONE dispatch: the host dispatch
        slice, compile-detected from the jit cache, parented under the
        trace ctx of the first traced request it serves (the rest ride
        as links). Counters only — never a device sync."""
        try:
            compiled = False
            cache_size = getattr(jit_fn, "_cache_size", None)
            if cache_size is not None:
                n = cache_size()
                key = id(jit_fn)
                seen = self._jit_cache_sizes.get(key, 0)
                compiled = n > seen
                self._jit_cache_sizes[key] = max(n, seen)
                self._collect_when_idle |= compiled
            ctxs, seen_spans = [], set()
            for r in reqs:
                c = r._trace_ctx
                if c is not None and c["span_id"] not in seen_spans:
                    seen_spans.add(c["span_id"])
                    ctxs.append(c)
            self._tel.record(
                t0, t1, compiled=compiled,
                ctx=ctxs[0] if ctxs else None,
                links=ctxs[1:] or None,
            )
            # per-dispatch flight-recorder record: ONE ring write (the
            # dispatch window in ms rides `a`) — no allocation, no
            # pickle, no RPC on this path (lint-pinned)
            self._fr.write(_EV_DISPATCH, step=self._m["dispatches"],
                           a=(t1 - t0) * 1e3)
            _engine_metrics()["dispatches"].inc(1, tags=self._tags)
            self._maybe_publish(t1)
        except Exception:
            pass

    def _maybe_publish(self, now: float) -> None:
        """Throttled /api/serve snapshot push (queued — the GCS RPC runs
        on the telemetry flusher thread, never the engine loop). Also
        called from the loop's idle branch: dispatch-time publishes
        snapshot counters BEFORE that macro's deliveries land, so
        without a final idle-time push a short burst would leave
        `requests_completed` (the SLO evaluator's good-count feed)
        permanently stale at its pre-finish value."""
        if now - self._t_snapshot < 2.0:
            return
        m = self._m
        marker = (m["dispatches"], m["requests_completed"],
                  m["shed_queue_full"] + m["shed_eta"]
                  + m["deadline_expired"])
        if marker == self._pub_marker:
            return  # idle and already published these exact counters
        self._t_snapshot = now
        self._pub_marker = marker
        try:
            from ray_tpu import observability

            observability.publish_snapshot(
                "serve", {f"engine:{self.name}": self.metrics()}
            )
        except Exception:
            pass

    def _drain_queue(self) -> None:
        while True:
            try:
                self._waiting.append(self._queue.get_nowait())
            except queue.Empty:
                return

    def _deliver(self, req: _Request, toks) -> None:
        if req.done.is_set():
            # the speculative plan outran this request (stop token,
            # cancel, timeout): these planned steps produced tokens
            # nobody wants — the plan-and-repair bill
            self._m["wasted_steps"] += len(toks)
            if req._migrate:
                # cancelled before its first token resolved: plan-time
                # eviction skipped this request's free expecting an
                # export that now never happens
                self._free_request_blocks(req)
            return
        stopped = False
        stop_set = req.sampling.stop
        if stop_set:
            for i, t in enumerate(toks):
                if t in stop_set:
                    # truncate AT the stop: the stop token itself is not
                    # delivered; tokens speculatively decoded beyond it
                    # are waste
                    self._m["wasted_steps"] += len(toks) - i - 1
                    toks = toks[:i]
                    stopped = True
                    break
        if req._t_first is None and (req.tokens or toks or stopped):
            req._t_first = time.perf_counter()
            self._ttft.observe(req._t_first - req._t_submit)
            if req.rid:
                # once per request, not per token — the per-token path
                # below stays counters-only (lint-pinned)
                _lifeline.record(req.rid, "first_token",
                                 ctx=req._trace_ctx, rid_b=req._rid_b,
                                 engine=self.name,
                                 ttft_ms=round(
                                     (req._t_first - req._t_submit) * 1e3,
                                     3),
                                 a=(req._t_first - req._t_submit) * 1e3)
        req.tokens.extend(toks)
        self._m["tokens_out"] += len(toks)
        try:
            _engine_metrics()["tokens"].inc(len(toks), tags=self._tags)
        except Exception:
            pass
        if stopped or len(req.tokens) >= req.max_new_tokens:
            req._t_done = time.perf_counter()
            if _finish(req, reason="stop" if stopped else "length"):
                if req._t_first is not None and len(req.tokens) > 1:
                    self._tpot.observe(
                        (req._t_done - req._t_first) / (len(req.tokens) - 1)
                    )
                # SLO availability numerator: requests DELIVERED here
                # (migrated finishes count on the decode side instead)
                self._m["requests_completed"] += 1
                # service-time EMA feeding the admission ETA estimate
                dur = req._t_done - req._t_submit
                ema = self._ema_service_s
                self._ema_service_s = dur if ema <= 0.0 else 0.8 * ema + 0.2 * dur
                # the request's own account, once: a span in the trace and
                # the fields of the lifeline's event (one dict a request)
                stats = self._request_span(req, req.finish_reason, self._resolving)
                if req.rid:
                    _lifeline.record(req.rid, "finish",
                                     ctx=req._trace_ctx, rid_b=req._rid_b,
                                     engine=self.name,
                                     a=float(len(req.tokens)), b=dur * 1e3,
                                     **stats)
                    _lifeline.finish(req.rid)
                self._wake.set()  # repair promptly: slot + blocks are free
            if req._migrate:
                # stopped AT its first token: finished here, no export —
                # reclaim the blocks plan-time eviction left pinned
                self._free_request_blocks(req)
        elif req._migrate:
            # first token resolved and the request is live: hand off to
            # the decode pool (gather + put + finish("migrated"))
            self._migrate_out(req)

    def _resolve(self, entry) -> None:
        """Fetch one macro-step's tokens — the only
        host sync, one dispatch behind the frontier — and deliver them
        to requests according to the plan. Dispatch is async, so a
        poisoned device program often surfaces HERE (at the blocking
        fetch), after the entry already left _pending — re-park it so
        _die can still reach its requests. Returns the dispatch's
        device counters by name (none for most models)."""
        try:
            return self._resolve_inner(entry)
        except Exception:
            self._pending.appendleft(entry)
            raise

    def _fetched(self, seq: int) -> None:
        """The fetch of dispatch `seq` has returned: the one stamp a
        resolve, the end of `flight_us` of the requests this resolve
        finishes (`_deliver` reads it off `_resolving`), and of `ahead_us`
        of those admitted by the dispatch that was enqueued behind it."""
        flight = self._resolving = self._flights.pop(seq)
        flight.t_fetched = now = time.perf_counter()
        behind, flight.behind = flight.behind, None
        if behind is not None:
            behind.ahead_s = now - behind.t_enq

    def _resolve_inner(self, entry) -> None:
        if entry[0] == "spec":
            _, toks_counts, firsts_dev, phases, seq = entry
            toks_dev, counts_dev = toks_counts
            with self._span(_SPAN_FETCH):
                toks = np.asarray(toks_dev)      # (K, chunk, B, n_spec + 1)
                counts = np.asarray(counts_dev)  # (K, chunk, B)
                firsts = np.asarray(firsts_dev)
            self._fetched(seq)
            for k, ph in enumerate(phases):
                for a, (_slot, req) in enumerate(ph["admissions"]):
                    self._deliver(req, [int(firsts[k, a])])
                for slot, req, take in ph["takes"]:
                    req._rounds_inflight = max(0, req._rounds_inflight - take)
                    for t in range(take):
                        c = int(counts[k, t, slot])
                        if c == 0:
                            # the device lane went inactive before this
                            # planned round — the spec-mode shape of a
                            # plan overrun
                            continue
                        self._m["spec_verify_rounds"] += 1
                        self._m["draft_proposed_tokens"] += self.n_spec
                        self._m["draft_accepted_tokens"] += c - 1
                        self._accept_ema = 0.9 * self._accept_ema + 0.1 * c
                        row = [int(x) for x in toks[k, t, slot, :c]]
                        if not req.done.is_set():
                            # a round can overshoot the request's token
                            # budget (it emits up to n_spec + 1 at once):
                            # cap delivery at what's owed and bill the
                            # excess as plan-repair waste
                            owed = req.max_new_tokens - len(req.tokens)
                            if c > owed:
                                self._m["wasted_steps"] += c - owed
                                row = row[:owed]
                        self._deliver(req, row)
                    if not req.done.is_set():
                        # resync the planner's round estimate to observed
                        # progress (the EMA moved, and the estimate this
                        # plan was built from is now stale)
                        owed = req.max_new_tokens - len(req.tokens)
                        est = self._rounds_for(owed) - req._rounds_inflight
                        if req._rounds_inflight <= 0:
                            est = max(1, est)
                        req._rounds_est = max(0, est)
            return
        _, toks_dev, firsts_dev, phases, seq, *counted_dev = entry
        with self._span(_SPAN_FETCH):
            toks = np.asarray(toks_dev)
            firsts = np.asarray(firsts_dev)
            counted = {name: int(v) for dev in counted_dev for name, v in
                       zip(self._device_counters, np.asarray(dev))}
        self._fetched(seq)
        for name, v in counted.items():
            self._m[name] += v
        for k, ph in enumerate(phases):
            for a, (_slot, req) in enumerate(ph["admissions"]):
                self._deliver(req, [int(firsts[k, a])])
            for slot, req, take in ph["takes"]:
                if take:
                    self._deliver(req, [int(t) for t in toks[k, :take, slot]])
        return counted

    def _die(self, msg: str) -> None:
        """Fail every in-flight and queued request with a diagnostic and
        mark the engine dead so submit() raises immediately — a poisoned
        device program must not surface as N generic timeouts.

        Failures are TYPED (ReplicaDiedError) with the redispatch-safety
        bit set from whether the request had already emitted tokens:
        token-less requests are safe to replay elsewhere (nothing
        escaped), partially-delivered ones must fail fast to the caller
        (a silent re-generation could diverge from output already
        observed). Every doomed request's KV blocks go back to the pool
        — engine death must leave allocator refs == radix-cache refs
        (the leak audit's invariant)."""
        from ray_tpu.serve.errors import ReplicaDiedError

        self._dead = msg
        doomed = set()
        for entry in self._pending:
            for ph in entry[3]:
                doomed.update(r for _, r in ph["admissions"])
                doomed.update(r for _, r, _ in ph["takes"])
        self._pending.clear()
        doomed.update(r for r in self._slots if r is not None)
        self._slots = [None] * self.n_slots
        doomed.update(self._waiting)
        self._waiting.clear()
        doomed.update(self._resuming)
        self._resuming.clear()
        while True:
            try:
                doomed.add(self._queue.get_nowait())
            except queue.Empty:
                break
        while True:
            try:
                doomed.add(self._rqueue.get_nowait())
            except queue.Empty:
                break
        while True:
            try:
                _fn, fut = self._jobs.get_nowait()
                fut.set_exception(RuntimeError(f"engine died: {msg}"))
            except queue.Empty:
                break
        for req in doomed:
            self._dec_qtok(req)
            self._free_request_blocks(req)
            # one that ended before the engine did has written its span
            stats = {} if req.done.is_set() else self._request_span(req, "error")
            if req.rid:
                _lifeline.record(req.rid, "error", ctx=req._trace_ctx,
                                 rid_b=req._rid_b, engine=self.name,
                                 error=f"engine died: {msg}"[:200], **stats)
                _lifeline.finish(req.rid)
            _finish(req, error=msg, exc=ReplicaDiedError(
                f"engine died: {msg}", started=len(req.tokens) > 0))

    def _loop(self) -> None:
        try:
            self._loop_macro()
            while self._pending:  # clean shutdown: drain the frontier
                self._resolve(self._pending.popleft())
        except Exception as e:  # noqa: BLE001 — anything device-side
            msg = f"{type(e).__name__}: {e}"
            logger.exception("continuous-batching engine loop died: %s", msg)
            self._die(msg)
