"""Shared test fixtures.

Equivalent of the reference's python/ray/tests/conftest.py: the
`ray_start_regular` fixture boots a real local cluster (GCS + raylet +
workers as separate processes) per test module. JAX tests run on a
virtual 8-device CPU mesh (reference test strategy: SURVEY.md §4 —
multi-raylet-on-one-machine plus fake accelerator topology).
"""
import os

# Must be set before any jax backend is initialized: the suite runs on a
# virtual 8-device CPU mesh, and the explicit worker pin keeps a `TPU`
# grant on a fake-chip node from re-pointing a worker at the TPU backend.
# The CPU backend's code generation runs at level 2 of 3: tier-1 is bound by
# the CPU time of compiling hundreds of small programs once each (six xdist
# workers want thirteen of the machine's eight cores), nothing here measures
# the speed of CPU code, and the last level's passes cost a third of a
# model test's CPU seconds (PR 48, CHANGES.md). The programs compiled for a
# described TPU (tests/test_tpu_compile.py) do not pass through this backend.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8 --xla_backend_optimization_level=2"
).strip()
os.environ["RAY_TPU_WORKER_JAX_PLATFORMS"] = "cpu"

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy tests excluded from the tier-1 run (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection chaos tier — long injector schedules; run "
        "explicitly with -m chaos (chaos tests are also marked slow so they "
        "stay out of tier-1 timing)",
    )


def static_answers(generate, params, cfg, prompts, answers):
    """What a decode module's static `generate` gives each request of an
    engine test, greedy: a prompt alone, as many tokens as the longest answer
    asked of any prompt of its length, cut to the request's own (a greedy
    answer is a prefix of a longer one). `generate` compiles a program for
    every (prompt length, answer length): one a length this way, not one a
    request."""
    import numpy as np

    longest = {}
    for p, n in zip(prompts, answers):
        longest[len(p)] = max(longest.get(len(p), 0), n)
    return [generate(params, np.asarray([p]), cfg, longest[len(p)])[0, :n].tolist()
            for p, n in zip(prompts, answers)]


def latent_decode_steps_by_each_reader(Lanes, D, cfg, params, prompts, monkeypatch):
    """A latent decode module's decode steps (`Lanes` of the model's test
    file: an admission of `prompts`, then six steps) run three ways, and what
    each leaves: {way: (logits (6, lanes, V), the pool)}. "loop": as the CPU
    runs them. "kernel": `engages` patched true and the TPU interpret mode,
    which takes any shape. "tiles-refuse": a TPU by the backend test alone, on
    this pool of blocks of 4, which the tiles do not take; `attend` patched to
    fail. Also returns what `engages` saw in the kernel's way: (the query's
    shape, `v_full`, `v_cols`) a traced call."""
    import functools

    import jax
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import paged_decode_attention as PDA

    def run():
        halves = (functools.partial(D.admit_slots_paged, cfg=cfg, sampled=False),
                  functools.partial(D.decode_step_slots_paged, cfg=cfg, sampled=False))
        lanes = Lanes(cfg, params, n=len(prompts), halves=tuple(map(jax.jit, halves)))
        lanes.admit(list(enumerate(prompts)), bucket=32, new=8)
        return np.stack([lanes.step()[0] for _ in range(6)]), np.asarray(lanes.cache["latent"])

    ways, seen = {"loop": run()}, []
    with monkeypatch.context() as m:
        m.setattr(PDA, "_on_tpu", lambda: True)
        m.setattr(PDA, "attend", lambda *a, **k: pytest.fail("the kernel was called"))
        ways["tiles-refuse"] = run()
    with monkeypatch.context() as m, pltpu.force_tpu_interpret_mode():
        m.setattr(PDA, "engages", lambda q, k, v, v_cols=0: seen.append((q.shape, v, v_cols)) or True)
        ways["kernel"] = run()
    return ways, seen


@pytest.fixture(scope="module")
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_isolated():
    """Fresh cluster per test (slower; for lifecycle/failure tests)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()
