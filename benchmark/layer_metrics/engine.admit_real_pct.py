"""Share of the admissions' token rows that is a prompt's: `prompt_tokens` /
`admit_rows` (A x P a phase that admits, padding included; on the spans since
PR 40), from the `engine.resolve` spans of the executions whole in the traced
stretch (`engine.dispatch_lead_ms` holds the shared reading). The rest is the
padding of the (A, P) buckets, which every layer of an admission but the
expert layer still pays for: the yardstick of an admission token budget
(ROADMAP S2(b))."""
from benchmark import common

account = common.load_module("layer_metrics", "engine.dispatch_lead_ms")


def read(ctx):
    got = account.sums_with(ctx["facts"], "prompt_tokens", "admit_rows")
    if not got or not got[1]["admit_rows"]:
        return None
    acc, s = got
    return {"value": 100.0 * s["prompt_tokens"] / s["admit_rows"],
            "prompt_tokens": s["prompt_tokens"], "admit_rows": s["admit_rows"],
            "admissions": s["admissions"], "executions": acc["executions"]}
