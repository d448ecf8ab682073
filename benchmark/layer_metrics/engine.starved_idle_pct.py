"""Share of the traced window in which no operation ran on the device and the
engine's loop thread was in a span other than `engine.idle`: the engine held
work, or was busy with it, and the device waited for the host. The rest of
`device.idle_pct.serve` is idle for want of traffic (under `engine.idle`),
which no change to the host path can remove, and what lies at the trace's two
edges, where a span still open when the profiler started or stopped was never
written. Printed beside it: the window's idle seconds under each span of the
loop, and two checks of the reading itself."""
from benchmark import program_spans


def read(ctx):
    view = program_spans.run_serve_view(ctx["facts"])
    if not view:
        return None
    reduced = ctx["facts"]["reduced"]
    idle = view["idle"]
    share = lambda seconds: 100.0 * seconds / idle["window_s"]  # noqa: E731
    starved = share(idle["starved_s"])
    device_idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    return {"value": starved, "idle_s_by_span": idle["by_span"], "idle_s": idle["idle_s"],
            "idle_s_between_spans": idle["uncovered_s"], "idle_s_at_trace_edges": idle["edges_s"],
            "device_idle_pct": device_idle, "rest_of_device_idle_pct": device_idle - starved,
            "idle_for_want_of_traffic_pct": share(idle["by_span"][program_spans.IDLE]),
            "check_starved_at_most_device_idle": starved <= device_idle + 1e-6,
            "check_spans_cover_idle_within_2pct":
                abs(idle["uncovered_s"]) <= 0.02 * idle["idle_s"]}
