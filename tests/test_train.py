"""End-to-end JaxTrainer tests — the reference build-plan's 'one model
running' milestone (SURVEY.md §7 step 6): gang placement group, worker
actors, session.report with checkpoints, restore/resume, failure retry.

Models the reference's train tests (python/ray/train/tests/test_data_parallel_trainer.py).
"""
import os

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import CheckpointConfig, FailureConfig, JaxTrainer, RunConfig, ScalingConfig


def test_trainer_runs_and_reports(ray_start_regular, tmp_path):
    def loop(config):
        ctx = train.get_context()
        for step in range(3):
            train.report({"step": step, "rank": ctx.get_world_rank(), "loss": 1.0 / (step + 1)})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path), name="basic"),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert result.metrics["loss"] == pytest.approx(1 / 3)


def test_trainer_world_info(ray_start_regular, tmp_path):
    def loop(config):
        ctx = train.get_context()
        train.report({"world": ctx.get_world_size(), "rank": ctx.get_world_rank()})

    result = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=3),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    assert result.metrics["world"] == 3
    assert result.metrics["rank"] == 0


def test_trainer_checkpointing_and_restore(ray_start_regular, tmp_path):
    def loop(config):
        import jax.numpy as jnp

        from ray_tpu.air.checkpoint import Checkpoint
        from ray_tpu.train._internal.storage import load_jax_state, save_jax_state

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            state = load_jax_state(ckpt.path, {"w": jnp.zeros((4,)), "step": 0})
            start = int(state["step"]) + 1
        for step in range(start, 3):
            if ctx.get_world_rank() == 0:
                import tempfile

                d = tempfile.mkdtemp()
                save_jax_state(d, {"w": jnp.full((4,), float(step)), "step": step})
                train.report({"step": step}, checkpoint=Checkpoint(d))
            else:
                train.report({"step": step})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path), name="ckpt",
                             checkpoint_config=CheckpointConfig(num_to_keep=2)),
    )
    result = trainer.fit()
    assert result.checkpoint is not None
    # resume: starts from step 3 => no new steps, but restores state
    trainer2 = JaxTrainer.restore(
        os.path.join(str(tmp_path), "ckpt"),
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path), name="ckpt"),
    )
    result2 = trainer2.fit()
    assert result2.error is None


def test_trainer_surfaces_worker_failure(ray_start_regular, tmp_path):
    def loop(config):
        raise RuntimeError("train boom")

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path), failure_config=FailureConfig(max_failures=0)),
    )
    with pytest.raises(Exception, match="train boom"):
        trainer.fit()


def test_trainer_gang_infeasible_raises(ray_start_regular, tmp_path, monkeypatch):
    """No node will ever have 100 CPUs a worker: the reservation is waited for
    and given up. The product waits 120 s (`WorkerGroup.__init__`); the test
    hands `PlacementGroup.wait` 3 s of it, which the GCS waits out in full."""
    from ray_tpu.util.placement_group import PlacementGroup

    waited = []
    wait = PlacementGroup.wait

    def short_wait(self, timeout_seconds=60.0):
        waited.append(timeout_seconds)
        return wait(self, min(timeout_seconds, 3.0))

    monkeypatch.setattr(PlacementGroup, "wait", short_wait)
    trainer = JaxTrainer(
        lambda c: None,
        scaling_config=ScalingConfig(num_workers=2, resources_per_worker={"CPU": 100}),
        run_config=RunConfig(storage_path=str(tmp_path)),
    )
    with pytest.raises(RuntimeError, match="reserve"):
        trainer.fit()
    assert waited == [120]  # the product's own wait, asked for once


def test_trainer_jax_training_loop(ray_start_regular, tmp_path):
    """A real (tiny) jax model trained data-parallel style in the workers."""

    def loop(config):
        import jax
        import jax.numpy as jnp
        import optax

        ctx = train.get_context()
        key = jax.random.PRNGKey(ctx.get_world_rank())
        w = jnp.zeros((8,))
        x = jax.random.normal(key, (64, 8))
        y = x @ jnp.arange(8.0)
        tx = optax.sgd(0.1)
        opt = tx.init(w)

        @jax.jit
        def step(w, opt):
            def loss(w):
                return ((x @ w - y) ** 2).mean()

            l, g = jax.value_and_grad(loss)(w)
            u, opt = tx.update(g, opt)
            return optax.apply_updates(w, u), opt, l

        for i in range(50):
            w, opt, l = step(w, opt)
        train.report({"final_loss": float(l)})

    result = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path)),
    ).fit()
    assert result.metrics["final_loss"] < 1.0


def test_gbdt_trainer_classification(ray_start_regular):
    """Native distributed GBDT (reference: train/gbdt_trainer.py +
    xgboost_trainer.py — here a from-scratch histogram booster since
    xgboost isn't in the image): binary classification on a nonlinear
    target reaches high accuracy; per-round traffic is histograms, not
    rows."""
    import numpy as np

    import ray_tpu.data as rd
    from ray_tpu.train.gbdt_trainer import GBDTTrainer

    rng = np.random.default_rng(0)
    n = 4000
    x0 = rng.uniform(-2, 2, n)
    x1 = rng.uniform(-2, 2, n)
    # XOR-style quadrant labels: linearly inseparable, tree-friendly
    y = ((x0 * x1) > 0).astype(np.float64)
    ds = rd.from_items(
        [{"x0": float(a), "x1": float(b), "label": float(c)} for a, b, c in zip(x0, x1, y)],
        parallelism=4,
    )
    trainer = GBDTTrainer(
        datasets={"train": ds},
        label_column="label",
        params={"objective": "binary:logistic", "max_depth": 3, "eta": 0.4},
        num_boost_round=12,
    )
    result = trainer.fit()
    probe = np.stack([x0[:500], x1[:500]], 1)
    preds = result.model.predict(probe)
    acc = float(((preds > 0.5) == (y[:500] > 0.5)).mean())
    assert acc > 0.93, acc


def test_gbdt_trainer_regression(ray_start_regular):
    import numpy as np

    import ray_tpu.data as rd
    from ray_tpu.train.gbdt_trainer import GBDTTrainer

    rng = np.random.default_rng(1)
    x = rng.uniform(-3, 3, 3000)
    y = np.sin(x) * 2 + 0.05 * rng.normal(size=x.shape)
    ds = rd.from_items([{"x": float(a), "y": float(b)} for a, b in zip(x, y)], parallelism=3)
    trainer = GBDTTrainer(
        datasets={"train": ds}, label_column="y",
        params={"max_depth": 3, "eta": 0.3}, num_boost_round=25,
    )
    model = trainer.fit().model
    grid = np.linspace(-3, 3, 200)[:, None]
    mse = float(np.mean((model.predict(grid) - 2 * np.sin(grid[:, 0])) ** 2))
    assert mse < 0.1, mse
    # dict-batch prediction path
    p = model.predict({"x": np.asarray([0.5, -0.5])})
    assert abs(p[0] - 2 * np.sin(0.5)) < 0.5
