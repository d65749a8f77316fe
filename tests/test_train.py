"""TpuTrainer tests (reference analogue: python/ray/train/tests with mock
backends + the DataParallelTrainer lockstep/report/checkpoint/restart
semantics)."""

import os

import pytest

import ray_tpu
from ray_tpu.train.config import FailureConfig, RunConfig, ScalingConfig
from ray_tpu.train.session import Checkpoint
from ray_tpu.train.trainer import TpuTrainer
from ray_tpu.train import session as train_session


@pytest.fixture
def trainer_env(tmp_path, ray_tpu_local):
    yield tmp_path


def test_basic_fit_collects_metrics(trainer_env):
    def train_fn(config):
        import ray_tpu.train.session as s

        for step in range(3):
            s.report({"step": step, "loss": 1.0 / (step + 1)})

    result = TpuTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=2, cpus_per_worker=1),
        run_config=RunConfig(name="basic", storage_path=str(trainer_env)),
    ).fit()
    assert result.error is None
    assert len(result.metrics_history) == 3
    assert result.metrics["step"] == 2


def test_rank_and_world_size(trainer_env):
    def train_fn(config):
        import ray_tpu.train.session as s

        ctx = s.get_context()
        s.report({"rank": ctx.world_rank, "world": ctx.world_size})

    result = TpuTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=3, cpus_per_worker=1),
        run_config=RunConfig(name="ranks", storage_path=str(trainer_env)),
    ).fit()
    # rank-0 metrics are collected
    assert result.metrics == {"rank": 0, "world": 3}


def test_checkpoint_saved_and_returned(trainer_env):
    def train_fn(config):
        import tempfile

        import ray_tpu.train.session as s

        for step in range(2):
            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "state.txt"), "w") as f:
                    f.write(f"step={step}")
                s.report({"step": step}, checkpoint=Checkpoint.from_directory(d))

    result = TpuTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="ckpt", storage_path=str(trainer_env)),
    ).fit()
    assert result.checkpoint is not None
    content = open(os.path.join(result.checkpoint.path, "state.txt")).read()
    assert content == "step=1"


def test_failure_restart_resumes_from_checkpoint(trainer_env):
    def train_fn(config):
        import tempfile

        import ray_tpu.train.session as s

        start = 0
        ckpt = s.get_checkpoint()
        if ckpt is not None:
            start = int(open(os.path.join(ckpt.path, "step.txt")).read()) + 1
        for step in range(start, 4):
            with tempfile.TemporaryDirectory() as d:
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step))
                s.report({"step": step}, checkpoint=Checkpoint.from_directory(d))
            if step == 1 and ckpt is None:
                raise RuntimeError("simulated mid-training crash")

    result = TpuTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="restart", storage_path=str(trainer_env),
            failure_config=FailureConfig(max_failures=1),
        ),
    ).fit()
    assert result.error is None, result.error
    # resumed at step 2 after crash at step 1
    steps = [m["step"] for m in result.metrics_history]
    assert steps[-1] == 3
    assert 2 in steps


def test_failure_exhausted_returns_error(trainer_env):
    def train_fn(config):
        raise ValueError("always broken")

    result = TpuTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="fail", storage_path=str(trainer_env),
            failure_config=FailureConfig(max_failures=1),
        ),
    ).fit()
    assert result.error is not None
    assert "always broken" in str(result.error)


def test_train_tiny_llama_e2e(trainer_env):
    """End-to-end: the flagship model trained through TpuTrainer (CPU)."""

    def train_fn(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        import ray_tpu.train.session as s
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.train.step import default_optimizer, make_train_state_factory, make_train_step

        cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=None, attention_impl="reference")
        opt = default_optimizer(lr=1e-2, warmup_steps=1, total_steps=20)
        state = make_train_state_factory(cfg, opt)(jax.random.key(0))
        step_fn = make_train_step(cfg, opt, donate=False)
        tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32)), jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        for i in range(3):
            state, metrics = step_fn(state, tokens, targets)
            s.report({"step": i, "loss": float(metrics["loss"])})

    result = TpuTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=2),
        run_config=RunConfig(name="llama", storage_path=str(trainer_env)),
    ).fit()
    assert result.error is None
    losses = [m["loss"] for m in result.metrics_history]
    assert len(losses) == 3 and losses[-1] < losses[0]


def test_the_dense_family_through_the_seam_of_the_train_step():
    """``train/step.py`` names no family: it asks the module that holds the
    configuration's class for ``loss``, ``init_params`` and ``logical_axes``.
    The dense family steps through that seam; a module whose
    ``loss_and_counters`` wraps the dense loss steps loss for loss the same,
    with what it counted in the step's output; the state's logical axes are
    the family's."""
    import dataclasses
    import sys
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.parallel.sharding import axes_is_leaf
    from ray_tpu.train import step as ts

    assert not any(name in open(ts.__file__).read()
                   for name in ("llama", "mellum", "Llama"))
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, remat=None,
                                 attention_impl="reference")
    opt = ts.default_optimizer(lr=1e-2, warmup_steps=1, total_steps=20)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    def run(config):
        state = ts.make_train_state_factory(config, opt)(jax.random.key(0))
        step = ts.make_train_step(config, opt, donate=False)
        losses = []
        for _ in range(3):
            state, out = step(state, tokens, targets)
            losses.append(float(out["loss"]))
        return losses, out

    dense, out = run(cfg)
    assert set(out) == {"loss", "grad_norm", "step"} and dense[-1] < dense[0]

    # a family of its own module: the dense one's three, and a loss that counts
    counting = types.ModuleType("a_family_that_counts")
    counting.init_params, counting.logical_axes = llama.init_params, llama.logical_axes
    counting.loss_and_counters = lambda params, tokens, targets, config, **kw: (
        llama.loss(params, tokens, targets, config, **kw),
        {"tokens_seen": jnp.int32(tokens.size)})
    counting.Config = type("Config", (llama.LlamaConfig,),
                           {"__module__": counting.__name__})
    sys.modules[counting.__name__] = counting
    try:
        counted_cfg = counting.Config(**dataclasses.asdict(cfg))
        counted, out = run(counted_cfg)
        assert float(ts.make_eval_step(counted_cfg)(
            llama.init_params(cfg, jax.random.key(0)), tokens, targets)) > 0
    finally:
        del sys.modules[counting.__name__]
    assert counted == dense and int(out["tokens_seen"]) == 64

    state_axes = ts.state_logical_axes(cfg, opt)
    assert state_axes.params == llama.logical_axes(cfg)
    moments = jax.tree.leaves(state_axes.opt_state, is_leaf=axes_is_leaf)
    assert ("layers", "embed", "heads") in moments
