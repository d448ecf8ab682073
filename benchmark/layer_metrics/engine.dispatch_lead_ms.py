"""From the plan that admits a request to the device start of its own phase,
in ms: the station after `engine.lane_wait_ms`, without a gap (both use the
start of the admitting plan). Two parts. On the host's side of the trace's
clock, the median over the dispatches that admit somebody and are paired with
their execution of (execution start - START of the `engine.plan` span before
that `engine.dispatch` on the loop thread): the plan, the dispatch and the
macro-step still in flight. On the device, what the dispatch runs before an
admission's own phase, from the plan: (`admit_lead_steps` x a decode step +
`admit_lead_phases` x an admitting phase) / `admissions`.

This file also holds what the seven readers of the engine's accounts share
(`engine.plan_wait_ms`, `engine.lane_wait_ms`, `engine.admit_stall_ms`,
`engine.vacant_lane_pct`, `engine.blocked_lane_pct`, `engine.admit_real_pct`
load it by name): `stretch`, the plan counts of the executions that lie WHOLE
in the traced stretch, taken from their `engine.resolve` spans
(`sarvam_mla_spans.pair_resolves`: the engine dispatches a macro-step ahead,
so the first whole execution of a stretch was dispatched before the trace
began), with those executions' device time under `admit_prefill` and
`decode_chunk`; and `stations`, the account's figures in ms. A decode step is
`decode_chunk`'s time over `steps`, an admitting phase `admit_prefill`'s over
`admit_phases`, both of the counted executions alone. A program whose resolve
spans lack a count (the parent of PR 41) gives no sum of it, and the reader
that needs it returns None.

Printed beside the value, as a check of the whole account: the stations of a
request's life (plan wait, lane wait, lead, its own admitting phase, its
answer's decode steps, the stall under others' admissions, the finish wait,
`engine.deliver_lag_ms`, `serve_plane.overhead_ms`), their sum, the mean
client latency of the requests whose lifelines the run has, and the residual
in % of it. The answer is the mean over those same requests (tokens - 1 decode
steps); the stretch's own `lane_steps / finishing` is printed beside it."""
import bisect
import statistics

from benchmark import common, program_spans, sarvam_mla_spans

COUNTS = ("steps", "admissions", "finishing", "finish_wait_steps", "lane_steps", "prompt_tokens",
          "admit_rows", "admit_phases", "plan_wait_us", "lane_wait_us", "admitted_first_plan",
          "admit_lead_steps", "admit_lead_phases", "stall_lane_phases", "vacant_lane_steps",
          "blocked_lane_steps", "spent_lane_steps")
PLAN = "engine.plan"


def host_leads(spans, executions):
    """Seconds from the start of the `engine.plan` span before each
    `engine.dispatch` that admits to the start of that dispatch's execution."""
    leads, planned = [], {}
    at = None
    for name, start, _, stats in sorted(spans, key=lambda s: s[1]):
        if name == PLAN:
            at = start
        elif name == program_spans.DISPATCH and at is not None:
            planned[int(stats.get("seq", -1))] = at
            at = None
    pairs, _, _ = program_spans.pair_dispatches(
        [s for s in spans if s[0] == program_spans.DISPATCH], executions)
    for dsp, ex in pairs:
        seq = int(dsp[3].get("seq", -1))
        if seq in planned and int(dsp[3].get("admissions", 0)) > 0:
            leads.append(ex[0] - planned[seq])
    return leads


def stretch(trace):
    """Counts and device times of the executions whole in the window whose
    resolve span, with the plan's counts on it, the trace holds; None where
    there is none."""
    window, spans = trace["window"], trace["spans"]
    executions = sorted((s, d) for name, s, d in trace["modules"]
                        if program_spans.MACRO_STEP.match(name))
    if not window or not spans or not executions:
        return None
    lo, hi = window
    counted = [(st, ex) for st, ex in sarvam_mla_spans.pair_resolves(spans, executions)
               if "steps" in st and lo <= ex[0] and sum(ex) <= hi and ex != executions[-1]]
    if not counted:
        return None
    mine = sorted(ex for _, ex in counted)
    starts = [s for s, _ in mine]
    by_half = {program_spans.ADMIT: 0.0, program_spans.DECODE: 0.0, "": 0.0}
    for s, d, half in trace["ops"]:  # an operation goes to the execution that holds its middle
        i = bisect.bisect_right(starts, s + d / 2) - 1
        if i >= 0 and s + d / 2 <= sum(mine[i]):
            by_half[half] += d
    sums = {key: sum(int(st[key]) for st, _ in counted) for key in COUNTS
            if all(key in st for st, _ in counted)}
    return {"executions": len(counted), "sums": sums, "macro_s": sum(d for _, d in mine),
            "admit_s": by_half[program_spans.ADMIT], "decode_s": by_half[program_spans.DECODE],
            "host_lead_s": host_leads(spans, executions)}


def run_stretch(facts):
    """`stretch` of this run's trace, worked out once for all its readers."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "account_stretch" not in trace:
        trace["account_stretch"] = stretch(trace)
    return trace["account_stretch"]


def sums_with(facts, *keys):
    """(the stretch, its sums) where the resolve spans carry every one of `keys`."""
    acc = run_stretch(facts)
    if not acc or any(key not in acc["sums"] for key in keys):
        return None
    return acc, acc["sums"]


def stations(acc):
    """The account's figures in ms, each None where its base is 0."""
    s = acc["sums"]
    over = lambda a, b: a / b if b else None  # noqa: E731
    step_ms = over(1e3 * acc["decode_s"], s["steps"])
    phase_ms = over(1e3 * acc["admit_s"], s.get("admit_phases", 0))
    out = {"decode_step_ms": step_ms, "admit_phase_ms": phase_ms,
           "plan_wait_ms": over(1e-3 * s.get("plan_wait_us", 0), s["admissions"]),
           "lane_wait_ms": over(1e-3 * s.get("lane_wait_us", 0), s["admissions"]),
           "finish_wait_ms": over(s["finish_wait_steps"] * (step_ms or 0.0), s["finishing"]),
           "device_lead_ms": None, "admit_stall_ms": None}
    if "admit_lead_steps" in s and s["admissions"]:
        out["device_lead_ms"] = (s["admit_lead_steps"] * (step_ms or 0.0)
                                 + s["admit_lead_phases"] * (phase_ms or 0.0)) / s["admissions"]
    if "stall_lane_phases" in s and s["finishing"]:
        out["admit_stall_ms"] = s["stall_lane_phases"] * (phase_ms or 0.0) / s["finishing"]
    return out


def whole_account(ctx, st, lead_ms):
    """The stations of a request's life beside the mean client latency."""
    facts = ctx["facts"]
    timelines = facts.get("timelines") or {}
    mine = [r for r in facts.get("records") or [] if r["ok"] and timelines.get(r["rid"])]
    if not mine:
        return {}
    other = lambda name: (common.load_module("layer_metrics", name).read(ctx) or {}).get("value")  # noqa: E731
    answer_steps = statistics.mean(len(r["tokens"]) - 1 for r in mine)
    parts = {"plan_wait_ms": st["plan_wait_ms"], "lane_wait_ms": st["lane_wait_ms"],
             "dispatch_lead_ms": lead_ms, "own_admit_phase_ms": st["admit_phase_ms"],
             "decode_ms": answer_steps * st["decode_step_ms"] if st["decode_step_ms"] else None,
             "admit_stall_ms": st["admit_stall_ms"], "finish_wait_ms": st["finish_wait_ms"],
             "deliver_lag_ms": other("engine.deliver_lag_ms"),
             "serve_plane_overhead_ms": other("serve_plane.overhead_ms")}
    latency = 1e3 * statistics.mean(r["t_done"] - r["t_due"] for r in mine)
    total = sum(v for v in parts.values() if v is not None)
    return {"account": parts, "account_sum_ms": total, "mean_client_latency_ms": latency,
            "account_residual_pct": 100.0 * (latency - total) / latency,
            "account_requests": len(mine), "answer_decode_steps": answer_steps,
            "stations_without_a_reading": sorted(k for k, v in parts.items() if v is None)}


def read(ctx):
    got = sums_with(ctx["facts"], "admit_lead_steps", "admit_lead_phases", "admit_phases")
    if not got or not got[0]["host_lead_s"] or not got[1]["admissions"]:
        return None
    acc, s = got
    st = stations(acc)
    host_ms = 1e3 * statistics.median(acc["host_lead_s"])
    value = host_ms + st["device_lead_ms"]
    return {"value": value, "host_ms": host_ms, "device_ms": st["device_lead_ms"],
            "paired_admitting_dispatches": len(acc["host_lead_s"]),
            "admit_lead_steps": s["admit_lead_steps"], "admit_lead_phases": s["admit_lead_phases"],
            "admissions": s["admissions"], "admit_phases": s["admit_phases"],
            "decode_step_ms": st["decode_step_ms"], "admit_phase_ms": st["admit_phase_ms"],
            "executions": acc["executions"],
            "stretch_lane_steps_a_finishing": s["lane_steps"] / s["finishing"] if s["finishing"] else None,
            **whole_account(ctx, st, value)}
