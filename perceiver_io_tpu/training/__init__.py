from perceiver_io_tpu.training.losses import (
    cross_entropy_with_ignore,
    classification_loss_and_accuracy,
)
from perceiver_io_tpu.training.optim import OptimizerConfig, make_optimizer
from perceiver_io_tpu.training.train_state import TrainState
from perceiver_io_tpu.training.steps import (
    make_ar_steps,
    make_lm_steps,
    make_mlm_steps,
    make_classifier_steps,
    make_flow_steps,
    make_multimodal_steps,
    freeze_subtrees,
    mlm_gather_capacity,
)
from perceiver_io_tpu.training.checkpoint import (
    CheckpointManager,
    load_hparams,
    restore_encoder_params,
    restore_params,
    restore_train_state,
)
from perceiver_io_tpu.training.metrics import MetricsLogger, next_version_dir, read_metrics
from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

__all__ = [
    "MetricsLogger",
    "next_version_dir",
    "read_metrics",
    "Trainer",
    "TrainerConfig",
    "CheckpointManager",
    "load_hparams",
    "restore_encoder_params",
    "restore_params",
    "restore_train_state",
    "cross_entropy_with_ignore",
    "classification_loss_and_accuracy",
    "OptimizerConfig",
    "make_optimizer",
    "TrainState",
    "make_ar_steps",
    "make_lm_steps",
    "make_mlm_steps",
    "mlm_gather_capacity",
    "make_classifier_steps",
    "make_flow_steps",
    "make_multimodal_steps",
    "freeze_subtrees",
]
