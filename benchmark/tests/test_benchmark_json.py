"""BENCHMARK.json keeps to its contract: allowed characters, files where it
says, and every `moves` an end-to-end metric of every cell the layer metric is
reported in."""
import os
import re

import pytest

from benchmark import common

BENCH = common.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_shape():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds", "configs", "workloads",
                                    "end_to_end", "per_layer"])
    assert BENCH["paths"] == ["benchmark"] and BENCH["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(common.REPO, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 4)
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(CELLS)


WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|head_dim|d_head|d_state"
                   r"|_expand|experts_per_tok)$")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations_and_their_files(entry):
    """Each configuration is held to ITS OWN `published` block (B1: this test
    held all of them to Mistral's widths, and was red from PR 29 on): what the
    file changed from its source is exactly `reduced`, with the source's value
    under `published`, and no width is among it."""
    c = entry
    used = {w["config"] for w in BENCH["workloads"]}
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["name"] in used and c["file"].startswith("benchmark/")
    assert [e["file"] for e in BENCH["configs"]].count(c["file"]) == 1
    cf = common.load_json(os.path.join(common.REPO, c["file"]))
    assert cf["name"] == c["name"] and cf["source"] == c["source"]
    assert sorted(cf["reduced"]) == sorted(c["reduced"]) and len(c["reduced"]) <= 16
    assert sorted(cf["published"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert NAME.match(key) and cf[key] != cf["published"][key]
        assert not WIDTH.search(key)
    assert os.path.isfile(os.path.join(common.BENCH_DIR, "drivers", cf["driver"] + ".py"))
    assert not re.search(r"llama|gemma|qwen|gpt-oss", c["name"] + c["source"], re.I)


def test_mistrals_published_widths_are_unchanged():
    for name in ("mistral-7b-v0.3.serve", "mistral-7b-v0.3.train"):
        cf = common.load_json(os.path.join(common.BENCH_DIR, "configs", name + ".json"))
        assert (cf["hidden_size"], cf["num_attention_heads"], cf["num_key_value_heads"],
                cf["head_dim"], cf["intermediate_size"], cf["vocab_size"]) == (
            4096, 32, 8, 128, 14336, 32768)
        assert cf["rope_theta"] == 1e6 and cf["rms_norm_eps"] == 1e-5 and cf["sliding_window"] is None


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_is_complete(cell):
    loaded = common.load_cell(cell)  # finds the configuration and the traffic file by name
    e2e = [m["name"] for m in BENCH["end_to_end"] if cell in _cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if cell in _cells_of(m)]
    assert layer and [m["name"] for m in layer] == [m["name"] for m in loaded["per_layer"]]
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"], cell)
        assert os.path.isfile(os.path.join(common.BENCH_DIR, "layer_metrics", m["name"] + ".py"))


def test_every_listed_cell_exists_and_layers_are_spelled_alike():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(_cells_of(m)) <= set(CELLS)
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers == {"entry", "serve plane", "engine", "device programs", "kernels", "device"}
