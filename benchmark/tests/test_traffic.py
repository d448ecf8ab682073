"""The traffic generator: the same seed gives the same schedule and lengths,
every seed the same work in another order, and times run from the due moment."""
import numpy as np
import pytest

from benchmark import common, traffic

CHAT = common.load_json(f"{common.BENCH_DIR}/traffic/chat-short.open.json")
DOCQA = common.load_json(f"{common.BENCH_DIR}/traffic/docqa.closed.json")


def _shape(plan):
    return (sorted(len(r["prompt"]) for r in plan["requests"]),
            sorted(r["max_new_tokens"] for r in plan["requests"]))


@pytest.mark.parametrize("tf", [CHAT, DOCQA], ids=["open", "closed"])
def test_same_seed_same_plan(tf):
    a, b = traffic.plan(tf, 2**31 + 17, 30.0, 32768), traffic.plan(tf, 2**31 + 17, 30.0, 32768)
    assert a["due"] == b["due"] and a["requests"] == b["requests"]


@pytest.mark.parametrize("tf", [CHAT, DOCQA], ids=["open", "closed"])
def test_every_seed_gets_the_same_work_with_other_tokens(tf):
    a, b = traffic.plan(tf, 1, 30.0, 32768), traffic.plan(tf, 2, 30.0, 32768)
    assert a["due"] == b["due"]
    assert [(len(r["prompt"]), r["max_new_tokens"]) for r in a["requests"]] == [
        (len(r["prompt"]), r["max_new_tokens"]) for r in b["requests"]]
    assert a["requests"][0]["prompt"][:8] != b["requests"][0]["prompt"][:8]
    assert (a["prompt_tokens"], a["output_tokens"]) == (b["prompt_tokens"], b["output_tokens"])
    other = traffic.plan({**tf, "profile_seed": 27}, 1, 30.0, 32768)
    assert _shape(other) == _shape(a)  # another layout of the same multiset
    assert [len(r["prompt"]) for r in other["requests"]] != [len(r["prompt"]) for r in a["requests"]]


def test_open_loop_schedule_is_fixed_by_file_and_window():
    a = traffic.plan(CHAT, 1, 30.0, 32768)
    n = int(CHAT["arrivals"]["rate_per_s"] * 30.0)
    assert len(a["due"]) == n
    assert 0 < a["due"][0] and a["due"][-1] < 30.0 and a["due"] == sorted(a["due"])
    gaps = np.diff([0.0] + a["due"])
    assert gaps.mean() == pytest.approx(1 / CHAT["arrivals"]["rate_per_s"], rel=0.02)
    assert 0.8 < gaps.std() / gaps.mean() < 1.1  # exponential gaps: a Poisson process


def test_lengths_follow_the_file():
    plan = traffic.plan(CHAT, 5, 60.0, 32768)
    p = [len(r["prompt"]) for r in plan["requests"]]
    o = [r["max_new_tokens"] for r in plan["requests"]]
    assert min(p) >= 129 and max(p) <= 512 and 230 <= np.median(p) <= 280
    assert min(o) >= 4 and max(o) <= 48 and 14 <= np.median(o) <= 18
    d = traffic.plan(DOCQA, 5, 60.0, 32768)
    assert all(513 <= len(r["prompt"]) <= 1024 and 4 <= r["max_new_tokens"] <= 16
               for r in d["requests"])
    assert d["clients"] == 8 and d["due"] is None


def test_latency_runs_from_the_due_moment_and_failures_stay_in_the_count():
    t0 = 1000.0
    recs = [
        # due at 1.0, sent late at 1.5, answered at 3.0: latency 2.0 s, not 1.5
        {"i": 0, "rid": "a", "t_due": t0 + 1.0, "t_sent": t0 + 1.5, "t_done": t0 + 3.0,
         "ok": True, "tokens": [1, 2, 3, 4], "error": None},
        # answered after the window closed: latency counts, tokens do not
        {"i": 1, "rid": "b", "t_due": t0 + 9.0, "t_sent": t0 + 9.0, "t_done": t0 + 12.0,
         "ok": True, "tokens": [1] * 10, "error": None},
        {"i": 2, "rid": "c", "t_due": t0 + 2.0, "t_sent": t0 + 2.0, "t_done": t0 + 2.5,
         "ok": False, "tokens": [], "error": "RuntimeError: boom"},
    ]
    s = traffic.summarize({"t0": t0, "seconds": 10.0, "records": recs})
    assert s["attempted"] == 3 and s["failed"] == 1 and s["latency_samples"] == 2
    assert s["latency_p50_ms"] == pytest.approx(2000.0) and s["latency_p90_ms"] == pytest.approx(3000.0)
    assert s["tok_s"] == pytest.approx(0.4)
    assert s["generator_late_ms_max"] == pytest.approx(500.0)


def test_distributions_are_their_own_quantiles():
    u = traffic.stratified({"dist": "exponential"}, 1000)
    assert abs(u.mean() - 1.0) < 0.01
    g = traffic.stratified({"dist": "gamma_cv", "cv": 2.0}, 4000)
    assert abs(g.mean() - 1.0) < 0.05 and 1.7 < g.std() / g.mean() < 2.3
    ln = traffic.stratified({"dist": "lognormal", "median": 256, "sigma": 0.45}, 1001)
    assert abs(np.median(ln) - 256) < 1.0
