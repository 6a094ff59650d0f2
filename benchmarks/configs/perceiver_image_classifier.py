"""Builder of the ``perceiver_image_classifier`` family (see
``perceiver_mlm.py`` for the contract): the system under test as
``cli/train_imagenet.py`` builds it, minus the ImageFolder data module."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks import flops
from benchmarks.configs import schedule
from benchmarks.reference import perceiver as ref

MODEL_FLAGS = (
    "num_latents", "num_latent_channels", "num_encoder_layers",
    "num_self_attention_layers_per_block", "num_cross_attention_heads",
    "num_self_attention_heads", "dropout", "dtype", "attn_impl",
    "num_frequency_bands", "optimizer", "learning_rate", "weight_decay",
)


def _args(cfg: Dict[str, Any], batch_size: int, logdir: str):
    from perceiver_io_tpu.cli import train_imagenet

    argv = ["--batch_size", str(batch_size), "--image_size", str(cfg["image_shape"][0]),
            "--logdir", logdir, "--no_tensorboard", "--max_epochs", "1"]
    for flag in MODEL_FLAGS:
        argv += [f"--{flag}", str(cfg[flag])]
    argv += schedule.cli_flags(cfg)
    args = train_imagenet.build_parser().parse_args(argv)
    # train_imagenet.main: remat is the default at image_size >= 64
    args.remat = bool(cfg["remat"])
    return args


def build_model(cfg: Dict[str, Any], batch_size: int = 1, logdir: str = "logs"):
    from perceiver_io_tpu.cli import common

    args = _args(cfg, batch_size, logdir)
    model = common.build_image_classifier(
        args, tuple(cfg["image_shape"]), cfg["num_classes"],
        num_frequency_bands=args.num_frequency_bands)
    return model, args


def param_shapes(cfg: Dict[str, Any]):
    model, _ = build_model(cfg)
    image = jnp.zeros((1, *cfg["image_shape"]), jnp.float32)
    return jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)}, image)["params"])


def build_trainer(cfg: Dict[str, Any], mix: Dict[str, Any], params, rng,
                  example_batch, logdir: str):
    from perceiver_io_tpu.cli import common
    from perceiver_io_tpu.training import TrainState, make_classifier_steps
    from perceiver_io_tpu.training.trainer import Trainer

    model, args = build_model(cfg, mix["batch_size"], logdir)
    tx, schedule = common.optimizer_from_args(args)
    state = jax.jit(lambda p, k: TrainState.create(p, tx, k))(params, rng)
    train_step, eval_step = make_classifier_steps(model, schedule, input_kind="image")
    # see perceiver_mlm.build_trainer for compute_mfu
    config = dataclasses.replace(common.trainer_config(args), compute_mfu=False)
    return Trainer(
        train_step, lambda s, b, k: eval_step(s, b), state, config,
        example_batch={k: example_batch[k] for k in ("image", "label")},
        mesh=common.mesh_from_args(args), hparams=vars(args))


def train_flops_per_sample(cfg: Dict[str, Any], mix: Dict[str, Any], pool) -> float:
    h, w, ch = cfg["image_shape"]
    return flops.perceiver_io(
        input_positions=h * w,
        input_channels=ch + 2 * (2 * cfg["num_frequency_bands"] + 1),
        num_latents=cfg["num_latents"], num_channels=cfg["num_latent_channels"],
        num_encoder_layers=cfg["num_encoder_layers"],
        num_self_attention_layers_per_block=cfg["num_self_attention_layers_per_block"],
        output_queries=1, output_classes=cfg["num_classes"],
        input_needs_grad=False, training=True)


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {k: cfg[k] for k in (
        "num_encoder_layers", "num_self_attention_layers_per_block",
        "num_cross_attention_heads", "num_self_attention_heads",
        "num_frequency_bands")}


def reference_task(cfg: Dict[str, Any]) -> Dict[str, Any]:
    sz = sizes(cfg)

    def prepare(batch, rng, step: int):
        block = {"image": jnp.asarray(batch["image"]), "label": jnp.asarray(batch["label"])}
        return block, float(len(batch["label"]))

    return {
        "prepare": prepare,
        "ce_sum": lambda ar: (lambda params, block: ref.classifier_ce_sum(ar, params, block, sz)),
        "block_rows": 1,
        "learning_rate": schedule.learning_rate(cfg),
        "weight_decay": cfg["weight_decay"],
    }
