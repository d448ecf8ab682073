"""A `TPU` grant reaches the process that was granted it.

The raylet hands chip indices out with the task and takes them back when
the holder's process exits; the worker narrows its own environment to
them before the task body can load JAX. These tests schedule against a
fake 4-chip CPU node under the suite's platform pin (conftest), which
keeps winning over a grant — except the child-cluster test, which drops
the pin to see a granted worker refuse to compute on the CPU.
"""
import os
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def four_chip_node():
    ray_tpu.init(num_cpus=4, num_tpus=4, object_store_memory=128 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


def _chip_env():
    return {k: os.environ.get(k) for k in
            ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
             "TPU_CHIPS_PER_HOST_BOUNDS", "JAX_PLATFORMS")} | {"pid": os.getpid()}


@ray_tpu.remote(num_tpus=1)
class OneChip:
    def env(self):
        return _chip_env()


@ray_tpu.remote(num_tpus=2)
class TwoChips:
    def env(self):
        return _chip_env()


def test_granted_actors_see_disjoint_chips(four_chip_node):
    a, b, c = OneChip.remote(), OneChip.remote(), TwoChips.remote()
    ea, eb, ec = ray_tpu.get([a.env.remote(), b.env.remote(), c.env.remote()], timeout=60)
    chips = [e["TPU_VISIBLE_CHIPS"].split(",") for e in (ea, eb, ec)]
    assert [len(x) for x in chips] == [1, 1, 2]
    assert sorted(sum(chips, [])) == ["0", "1", "2", "3"]
    assert len({ea["pid"], eb["pid"], ec["pid"]}) == 3
    assert ea["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" and ec["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert ea["TPU_PROCESS_BOUNDS"] == "1,1,1" and ea["TPU_CHIPS_PER_HOST_BOUNDS"] is None
    # the suite's explicit pin wins over the grant's platform
    assert ea["JAX_PLATFORMS"] == "cpu"
    for h in (a, b, c):
        ray_tpu.kill(h)


def test_chips_return_when_the_holder_dies(four_chip_node):
    first = [OneChip.remote() for _ in range(4)]
    held = ray_tpu.get([h.env.remote() for h in first], timeout=60)
    assert sorted(e["TPU_VISIBLE_CHIPS"] for e in held) == ["0", "1", "2", "3"]
    victim = held[1]
    ray_tpu.kill(first[1])
    # all four chips were out: the next grant can only be the dead actor's
    # chip, in a new process
    again = ray_tpu.get(OneChip.remote().env.remote(), timeout=60)
    assert again["TPU_VISIBLE_CHIPS"] == victim["TPU_VISIBLE_CHIPS"]
    assert again["pid"] != victim["pid"]
    for h in first:
        ray_tpu.kill(h)


def test_granted_task_gets_an_unused_worker_and_does_not_return_it(four_chip_node):
    @ray_tpu.remote
    def plain():
        return _chip_env()

    @ray_tpu.remote(num_tpus=1)
    def granted():
        return _chip_env()

    plain_envs = ray_tpu.get([plain.remote() for _ in range(8)], timeout=60)
    assert all(e["TPU_VISIBLE_CHIPS"] is None for e in plain_envs)
    assert all(e["JAX_PLATFORMS"] == "cpu" for e in plain_envs)
    g1 = ray_tpu.get(granted.remote(), timeout=60)
    assert g1["TPU_VISIBLE_CHIPS"] is not None
    assert g1["pid"] not in {e["pid"] for e in plain_envs}
    # a process keeps its chips until it exits, so it takes no second task
    deadline = time.time() + 30
    while time.time() < deadline:
        later = ray_tpu.get([plain.remote() for _ in range(8)], timeout=60)
        assert g1["pid"] not in {e["pid"] for e in later}
        assert all(e["TPU_VISIBLE_CHIPS"] is None for e in later)
        try:
            os.kill(g1["pid"], 0)
        except ProcessLookupError:
            break
        time.sleep(0.2)
    else:
        pytest.fail("the granted task's worker outlived its task")
    g2 = ray_tpu.get(granted.remote(), timeout=60)
    assert g2["pid"] != g1["pid"]


def _run_script(body: str, env_drop=(), env_set=None, timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_set or {})
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(body)], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_granted_worker_without_the_pin_fails_typed_on_a_cpu_host():
    """No pin, no chip: the grant must not end in a CPU computation."""
    proc = _run_script(
        """
        import ray_tpu
        from ray_tpu.exceptions import TPUGrantError

        ray_tpu.init(num_cpus=2, num_tpus=1, object_store_memory=64 * 1024 * 1024)

        @ray_tpu.remote(num_tpus=1)
        def on_chip():
            import jax.numpy as jnp
            return float(jnp.ones(4).sum())

        try:
            print("COMPUTED", ray_tpu.get(on_chip.remote(), timeout=60))
        except TPUGrantError as e:
            print("TYPED", e)
        finally:
            ray_tpu.shutdown()
        """,
        env_drop=("RAY_TPU_WORKER_JAX_PLATFORMS",), env_set={"JAX_PLATFORMS": "cpu"},
    )
    assert "TYPED" in proc.stdout and "COMPUTED" not in proc.stdout, proc.stdout + proc.stderr


def test_chip_smoke_refuses_to_run_without_a_chip():
    t0 = time.time()
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert time.time() - t0 < 30
