"""run.py never falls back to the CPU: without a chip it exits non-zero and
prints no result line, and so it does in a directory that holds only
BENCHMARK.json and the benchmark's own paths."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import common


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_WORKER_JAX_PLATFORMS"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "pretrain-4k", "--seed", "2147483999",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj and "metrics" in obj:
            out.append(obj)
    return out


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"}, {"JAX_PLATFORMS": ""}],
                         ids=["cpu-forced", "no-chip-found"])
def test_without_a_chip_run_fails_and_prints_no_result(env):
    p = _run(common.REPO, env)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert '"phase": "failed"' in p.stdout


def test_with_only_the_benchmarks_own_files_run_fails(tmp_path):
    shutil.copy(os.path.join(common.REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(common.REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "run", "*.pyc"))
    p = _run(str(tmp_path), {"JAX_PLATFORMS": "", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
