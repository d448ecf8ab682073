"""One general traffic generator and one load generator, driven by a data file.

A traffic mix is a file under `benchmark/traffic/` naming a loop kind, a rate
or a client count, and length distributions by name. The distributions are the
functions in `DISTRIBUTIONS`; a new mix is a new file, never new code.

Every seed gets the SAME work: lengths and inter-arrival gaps are the
distribution's own quantiles (a stratified sample, not a random one), and the
file's `profile_seed` lays them out once, which fixes the window's bursts and
lulls, and which length arrives when, for every run. The run's seed draws the
token ids (and, in the drivers, the weights). On the chip the engine repeats a
schedule to a few parts in a thousand, while the same sizes in another order
moved the 90th percentile by 13 % (my chip runs, PR 26): an order drawn from
the seed would have been the widest term in every bound. So two seeds differ
as two runs of one seed do, and a run's request count and token totals are
fixed by the file and the window.

Timing is from the client's side and from when a request was DUE: in an open
loop the due moment is the schedule's, whether or not the generator was late
(the lateness is reported beside it); in a closed loop it is the moment the
client, having had its previous answer and thought for `think_s`, sends.
"""
from __future__ import annotations

import asyncio
import math
import statistics
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark.common import clock

# an open loop sleeps to within this of an arrival and spins the rest, so that
# a request is sent within some tens of microseconds of when it was due
SPIN_S = 0.002
# closed loops draw from a list made of stratified blocks of this many
# requests, so that any prefix of it is a near-balanced sample
BLOCK = 16


# ------------------------------------------------------------ distributions
def _norm_ppf(u: np.ndarray) -> np.ndarray:
    """Inverse normal CDF (Acklam's rational approximation, |err| < 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    u = np.asarray(u, float)
    out = np.empty_like(u)
    lo, hi = u < 0.02425, u > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(u[lo]))
    out[lo] = (((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    q = np.sqrt(-2 * np.log(1 - u[hi]))
    out[hi] = -(((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    q = u[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0]*r+a[1])*r+a[2])*r+a[3])*r+a[4])*r+a[5])*q / (((((b[0]*r+b[1])*r+b[2])*r+b[3])*r+b[4])*r+1)
    return out


def lognormal(u, p):
    """Quantiles of a lognormal with the given median and sigma (of the log)."""
    return p["median"] * np.exp(p["sigma"] * _norm_ppf(u))


def uniform(u, p):
    return p["min"] + (p["max"] - p["min"]) * u


def exponential(u, p):
    """Inter-arrival gaps of a Poisson process of unit rate."""
    return -np.log1p(-u)


def gamma_cv(u, p):
    """Bursty gaps of unit mean: a gamma with coefficient of variation `cv`
    (by Wilson-Hilferty, exact enough for a schedule)."""
    k = 1.0 / (p["cv"] ** 2)
    z = _norm_ppf(u)
    return np.maximum(k * (1 - 1 / (9 * k) + z / (3 * math.sqrt(k))) ** 3, 0.0) / k


DISTRIBUTIONS: Dict[str, Callable] = {
    "lognormal": lognormal, "uniform": uniform,
    "exponential": exponential, "gamma_cv": gamma_cv,
}


def stratified(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The distribution's n mid-quantiles, in increasing order."""
    u = (np.arange(n) + 0.5) / n
    return DISTRIBUTIONS[dist["dist"]](u, dist)


def lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    vals = np.rint(stratified(dist, n)).astype(int)
    return np.clip(vals, dist["min"], dist["max"])


# ----------------------------------------------------------------- the plan
def plan(traffic: Dict[str, Any], seed: int, seconds: float, vocab: int) -> Dict[str, Any]:
    """Everything the window will send, made before it starts.

    open loop:   `count` = rate x seconds requests with due offsets in
                 [0, seconds), gaps a shuffled stratified sample scaled so that
                 they sum to the window.
    closed loop: `clients` workers share one list, in stratified blocks, long
                 enough that no run exhausts it; `due` is None.
    """
    profile = np.random.default_rng([int(traffic.get("profile_seed", 0)), 0x70726F66])
    kind = traffic["kind"]
    if kind == "serve_open":
        rate = float(traffic["arrivals"]["rate_per_s"])
        n = max(1, int(math.floor(rate * seconds)))
        gaps = stratified({"dist": traffic["arrivals"]["process"], **traffic["arrivals"]}, n)
        gaps = gaps / gaps.sum() * seconds * (n / (n + 1))
        p_len, o_len = lengths(traffic["prompt_len"], n), lengths(traffic["output_len"], n)
        for values in (gaps, p_len, o_len):
            profile.shuffle(values)
        due = np.cumsum(gaps)
    elif kind == "serve_closed":
        n = int(traffic["max_requests"])
        due = None
        blocks_p, blocks_o = [], []
        for _ in range(-(-n // BLOCK)):
            p, o = lengths(traffic["prompt_len"], BLOCK), lengths(traffic["output_len"], BLOCK)
            profile.shuffle(p)
            profile.shuffle(o)
            blocks_p.append(p)
            blocks_o.append(o)
        p_len, o_len = np.concatenate(blocks_p)[:n], np.concatenate(blocks_o)[:n]
    else:
        raise ValueError(f"traffic kind {kind!r} is not one this generator knows")
    requests = []
    for i in range(n):
        ids = np.random.default_rng([int(seed), 1, i]).integers(0, vocab, int(p_len[i]))
        requests.append({"prompt": ids.tolist(), "max_new_tokens": int(o_len[i]),
                         "request_id": f"bench-{int(seed)}-{i}"})
    return {"kind": kind, "requests": requests,
            "due": None if due is None else due.tolist(),
            "clients": int(traffic.get("clients", 0)),
            "stagger_s": float(traffic.get("stagger_s", 0.0)),
            "think_s": float(traffic.get("think_s", 0.0)),
            "prompt_tokens": int(p_len.sum()), "output_tokens": int(o_len.sum())}


# ------------------------------------------------------------ load generator
async def _send(handle, req, rec, timeout_s: float) -> None:
    rec["t_sent"] = clock()
    try:
        result = await handle.remote(req).async_result(timeout_s)
        rec["tokens"] = list(result)
        rec["ok"] = len(rec["tokens"]) == req["max_new_tokens"]
        if not rec["ok"]:
            rec["error"] = f"asked {req['max_new_tokens']} tokens, got {len(rec['tokens'])}"
    except Exception as e:  # counted, never dropped: a failure misses every limit
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["t_done"] = clock()


async def _open_loop(handle, plan_, seconds, timeout_s, records):
    t0 = clock()
    tasks = []
    for i, (req, due) in enumerate(zip(plan_["requests"], plan_["due"])):
        delay = t0 + due - clock()
        if delay > SPIN_S:
            await asyncio.sleep(delay - SPIN_S)
        while clock() < t0 + due:  # the loop's timer is a millisecond coarse
            pass
        rec = {"i": i, "rid": req["request_id"], "t_due": t0 + due, "ok": False,
               "tokens": [], "error": None, "t_done": None}
        records.append(rec)
        tasks.append(asyncio.ensure_future(_send(handle, req, rec, timeout_s)))
    rest = t0 + seconds - clock()
    if rest > 0:
        await asyncio.sleep(rest)
    if tasks:  # drain: every arrival runs to an answer or a counted error
        await asyncio.wait(tasks, timeout=timeout_s)
    for t in tasks:
        t.cancel()
    return t0


async def _closed_loop(handle, plan_, seconds, timeout_s, records):
    t0 = clock()
    todo = iter(enumerate(plan_["requests"]))

    async def client(k: int):
        # clients start `stagger_s` apart, and each thinks for `think_s` before
        # its next request is due. With neither, a request races the engine's
        # planner: answers are delivered and the next dispatch is planned
        # microseconds later, while the client's next request needs a
        # millisecond or two to arrive, so it makes that plan or waits a whole
        # dispatch for the next. The race chose between trajectories 8-12 %
        # apart in tokens/s, each exact to the digit (my chip runs, PR 26).
        await asyncio.sleep(k * plan_["stagger_s"])
        while clock() < t0 + seconds:
            try:
                i, req = next(todo)
            except StopIteration:
                return
            rec = {"i": i, "rid": req["request_id"], "t_due": clock(), "ok": False,
                   "tokens": [], "error": None, "t_done": None}
            records.append(rec)
            await _send(handle, req, rec, timeout_s)
            await asyncio.sleep(plan_["think_s"])

    tasks = [asyncio.ensure_future(client(k)) for k in range(plan_["clients"])]
    await asyncio.wait(tasks, timeout=seconds + timeout_s)
    for t in tasks:
        t.cancel()
    return t0


def run_window(handle, plan_: Dict[str, Any], seconds: float,
               timeout_s: float = 120.0) -> Dict[str, Any]:
    """Offer the plan for `seconds`, wait for what is in flight, return every
    request's record. One thread, one event loop."""
    records: List[Dict[str, Any]] = []
    loop_fn = _open_loop if plan_["kind"] == "serve_open" else _closed_loop
    t0 = asyncio.run(loop_fn(handle, plan_, seconds, timeout_s, records))
    return {"t0": t0, "seconds": seconds, "records": records}


# ----------------------------------------------------- records to end-to-end
def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of all requests; None without any."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def summarize(window: Dict[str, Any]) -> Dict[str, Any]:
    """Latency over ALL requests due in the window (a failed one has no
    latency and makes the run's `failed` count, never a smaller denominator);
    tokens of requests completed inside the window over the window."""
    recs, t0, seconds = window["records"], window["t0"], window["seconds"]
    done = [r for r in recs if r["ok"]]
    lat_ms = [(r["t_done"] - r["t_due"]) * 1e3 for r in done]
    late_ms = [(r["t_sent"] - r["t_due"]) * 1e3 for r in recs if "t_sent" in r]
    in_window = [r for r in done if r["t_done"] <= t0 + seconds]
    return {
        "attempted": len(recs),
        "failed": len(recs) - len(done),
        "errors": sorted({r["error"] for r in recs if r["error"]})[:5],
        "latency_p50_ms": percentile(lat_ms, 0.50),
        "latency_p90_ms": percentile(lat_ms, 0.90),
        "latency_samples": len(lat_ms),
        "tok_s": sum(len(r["tokens"]) for r in in_window) / seconds,
        "completed_in_window": len(in_window),
        "generator_late_ms_p50": statistics.median(late_ms) if late_ms else None,
        "generator_late_ms_max": max(late_ms, default=None),
        "drain_s": max((r["t_done"] for r in done), default=t0 + seconds) - (t0 + seconds),
    }
