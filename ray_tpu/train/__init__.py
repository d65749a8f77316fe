from ray_tpu.train.config import CheckpointConfig, FailureConfig, RunConfig, ScalingConfig
from ray_tpu.train.session import Checkpoint, get_checkpoint, get_context, loop_stats, report, world_rank, world_size
from ray_tpu.train.step import TrainState, make_eval_step, make_train_state_factory, make_train_step, default_optimizer
from ray_tpu.train.trainer import Result, TpuTrainer

__all__ = [
    "Checkpoint",
    "CheckpointConfig",
    "FailureConfig",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "TpuTrainer",
    "TrainState",
    "default_optimizer",
    "get_checkpoint",
    "get_context",
    "loop_stats",
    "make_eval_step",
    "make_train_state_factory",
    "make_train_step",
    "report",
    "world_rank",
    "world_size",
]
