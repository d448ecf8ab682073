"""Rehearsal 1 of the on-chip-measurement guide, kept as a test: both drivers
end to end on a CPU cluster at a tiny size. Control flow, counts and the
correctness comparison are checked; no timing is asserted or reported."""

import pytest

from benchmark import common


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
CLOSED = {"kind": "serve_closed", "clients": 6, "max_requests": 64,
          "prompt_len": {"dist": "uniform", "min": 33, "max": 64},
          "output_len": {"dist": "uniform", "min": 2, "max": 6}}


@pytest.mark.parametrize("traffic_name,traffic_file", [("open", OPEN), ("closed", CLOSED)])
def test_serve_driver_end_to_end(cluster, traffic_name, traffic_file):
    from benchmark.drivers import serve

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
    from benchmark.drivers import serve

    out = serve.measure(_cell("tiny.serve", "closed", CLOSED), seed=2**31 + 12,
                        seconds=3.0, trace=True, t_process_start=common.clock())
    reduced = out["facts"]["reduced"]
    assert "error" not in reduced and reduced["window_marked"]
    assert 1.0 <= reduced["window_s"] < 1.5 and reduced["busy_s"] == 0.0
    assert "tokens_out" in reduced["counters"] and out["facts"]["timelines"]


def test_train_driver_end_to_end(cluster):
    from benchmark.drivers import train

    cell = _cell("tiny.train", "job", {"kind": "train_job", "seq_len": 64, "batch": 2})
    out = train.measure(cell, seed=2**31 + 5, seconds=1.0, trace=False,
                        t_process_start=common.clock(), platform="cpu")
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
