"""Rehearsal 1 of the on-chip-measurement guide, kept as a test: both drivers
end to end on a CPU cluster at a tiny size. Control flow, counts and the
correctness comparison are checked; no timing is asserted or reported."""

import dataclasses
import time
import types

import pytest

from benchmark import common
from benchmark.drivers import serve, serve_afmoe, serve_hybrid


def _cell(config, traffic_name, traffic_file):
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/{config}.json")
    return {"name": "test", "chips": 1, "config": config, "traffic": traffic_name,
            "config_file": cfg, "traffic_file": traffic_file}


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=1)
    yield
    ray_tpu.shutdown()


OPEN = {"kind": "serve_open", "arrivals": {"process": "exponential", "rate_per_s": 4.0},
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.4, "min": 17, "max": 64},
        "output_len": {"dist": "uniform", "min": 2, "max": 8}}
CLOSED = {"kind": "serve_closed", "clients": 6, "max_requests": 64, "trace_seconds": 8.0,
          "prompt_len": {"dist": "uniform", "min": 33, "max": 64},
          "output_len": {"dist": "uniform", "min": 2, "max": 6}}


@pytest.mark.parametrize("traffic_name,traffic_file", [("open", OPEN), ("closed", CLOSED)])
def test_serve_driver_end_to_end(cluster, traffic_name, traffic_file):
    out = serve.measure(_cell("tiny.serve", traffic_name, traffic_file), seed=2**31 + 11,
                        seconds=3.0, trace=False, t_process_start=common.clock())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["facts"]["engine"]["tokens_out"] > 0
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric


def test_serve_variants_cover_the_traffic():
    from benchmark.drivers.serve import macro_variants

    chat = common.load_json(f"{common.BENCH_DIR}/traffic/chat-short.open.json")
    docqa = common.load_json(f"{common.BENCH_DIR}/traffic/docqa.closed.json")
    serve_cfg = {"n_slots": 4, "block_size": 16}
    assert macro_variants(chat, serve_cfg, 4096) == [
        [4, 512], [2, 512], [1, 512], [4, 256], [2, 256], [1, 256], [1, 16]]
    assert macro_variants(docqa, serve_cfg, 4096) == [[4, 1024], [2, 1024], [1, 1024], [1, 16]]


def test_serve_driver_traced_window_is_the_mark(cluster):
    """A traced serve run on the CPU: the replica's trace thread starts on the
    shared monotonic clock and the window it reports is the mark it left in
    the trace (no device plane here, so nothing is busy in it)."""
    out = serve.measure(_cell("tiny.serve", "closed", CLOSED), seed=2**31 + 12,
                        seconds=3.0, trace=True, t_process_start=common.clock())
    reduced = out["facts"]["reduced"]
    assert "error" not in reduced and reduced["window_marked"]
    assert 1.0 <= reduced["window_s"] < 1.5 and reduced["busy_s"] == 0.0  # a third of 3 s, not the file's 8
    assert "tokens_out" in reduced["counters"] and out["facts"]["timelines"]


class SlowTraceServer(serve.BenchLLMServer):
    """A replica whose trace takes 9 s longer to stop and reduce."""

    def _job_start(self, what, body):
        def slow():
            out = body()
            time.sleep(9.0 if what == "trace" else 0.0)
            return out

        super()._job_start(what, slow)


def test_a_trace_that_outlasts_the_stall_limit_is_fetched_through_pending_answers(
        cluster, monkeypatch, capsys):
    """What failed PR 34's check (and PR 33's first traced run): the replica
    needs longer to stop and reduce the trace than the transport lets a call
    stay without a reply. The limit is 6 s here for the program's 120; the
    replica answers `pending` every second, and the run ends with a result."""
    from ray_tpu.experimental import direct_transport

    monkeypatch.setattr(direct_transport, "_STALL_BREAK_S", 6.0)  # read by this process, the caller
    monkeypatch.setattr(serve, "REPLY_WITHIN_S", 1.0)             # handed to the replica with each poll
    out = serve.measure(_cell("tiny.serve", "closed", CLOSED), seed=2**31 + 13,
                        seconds=3.0, trace=True, t_process_start=common.clock(),
                        parts=dataclasses.replace(serve.LLAMA, server=SlowTraceServer))
    reduced = out["facts"]["reduced"]
    assert "error" not in reduced and reduced["window_marked"] and reduced["stop_reduce_s"] > 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    fetch = next(note for note in map(_json_or_none, capsys.readouterr().out.splitlines())
                 if note and note.get("phase") == "trace_fetch")
    assert fetch["polls"] >= 5 and fetch["waited_s"] > 6.0  # longer than the limit, and no call near it
    assert fetch["longest_silent_call_s"] < 3.0 and fetch["stop_reduce_s"] == reduced["stop_reduce_s"]


def _json_or_none(line):
    import json

    try:
        return json.loads(line)
    except ValueError:
        return None


class _NeverDone:
    """A job's thread that is still running; keeps the timeout it was joined with."""

    def join(self, timeout=None):
        self.joined_with = timeout

    def is_alive(self):
        return True


@pytest.mark.parametrize("method,job", [("bench_trace_result", "trace"),
                                        ("bench_reference_poll", "reference")])
@pytest.mark.parametrize("server", [serve.LLAMA.server, serve_hybrid.PARTS.server,
                                    serve_afmoe.PARTS.server], ids=lambda c: c.__name__)
def test_no_call_of_a_serve_driver_waits_for_the_replica_past_a_minute(server, method, job):
    """The rule by construction: whatever the caller asks for, the replica
    joins its job's thread for under 60 s and answers `pending`. All three
    drivers have the one `measure`, which fetches both through `poll`."""
    replica = object.__new__(server)  # no engine, no weights: the two methods need neither
    replica._bench_jobs = {job: {"thread": _NeverDone(), "result": None}}
    for asked in (None, 5.0, 600.0):
        answer = getattr(replica, method)(*(() if asked is None else (asked,)))
        assert answer == {"pending": True}
        assert replica._bench_jobs[job]["thread"].joined_with <= min(asked or 60.0, 45.0) < 60.0
    assert serve.REPLY_WITHIN_S < 60.0 < serve.CALL_TIMEOUT_S < 120.0
    for driver in (serve_hybrid, serve_afmoe):
        assert driver.measure.func is serve.measure and driver.bring_up.func is serve.bring_up
        assert driver.run.func is serve.run


def test_compile_counts_name_only_programs_the_engine_runs():
    """B7: `_macro_fn`, `_prefill_slots` and `_chunk_fn` are programs no engine
    runs; the benchmark no longer reads them, so the program may drop them (D2b)."""
    import glob
    import os

    for server in (serve.LLAMA.server, serve_hybrid.PARTS.server, serve_afmoe.PARTS.server):
        replica = object.__new__(server)
        replica.engine = types.SimpleNamespace(
            _macro_paged_fn=types.SimpleNamespace(_cache_size=lambda: 13))
        replica._bench_compile_events = ["a", "b"]
        assert replica.bench_compiles() == {"macro_paged": 13, "backend_compiles": 2}
    for path in glob.glob(os.path.join(common.BENCH_DIR, "**", "*.py"), recursive=True):
        if os.sep + "tests" + os.sep not in path:
            with open(path) as f:
                text = f.read()
            assert not any(name in text for name in ("_macro_fn", "_prefill_slots", "_chunk_fn")), path


def test_train_driver_end_to_end(cluster):
    from benchmark.drivers import train

    cell = _cell("tiny.train", "job", {"kind": "train_job", "seq_len": 64, "batch": 2})
    out = train.measure(cell, seed=2**31 + 5, seconds=1.0, trace=False,
                        t_process_start=common.clock(), platform="cpu")
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
