"""Builder of the ``perceiver_mlm`` family: everything that belongs to one
MLM configuration and nothing that belongs to a cell or a metric.

- ``build_trainer``: the system under test, built as ``cli/train_mlm.py``
  builds it (its parser, its preset, ``common.build_mlm``,
  ``make_mlm_steps``, ``Trainer``), minus the IMDB data module.
- ``train_flops_per_sample`` / ``serve_flops``: the operations count.
- ``reference_task``: the adapters between the plain reference and this
  configuration's batches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops
from benchmarks.configs import schedule
from benchmarks.reference import perceiver as ref

MODEL_FLAGS = (
    "num_latents", "num_latent_channels", "num_encoder_layers",
    "num_self_attention_layers_per_block", "num_cross_attention_heads",
    "num_self_attention_heads", "dropout", "dtype", "attn_impl",
    "vocab_size", "max_seq_len", "optimizer", "learning_rate", "weight_decay",
)


def _args(cfg: Dict[str, Any], batch_size: int, logdir: str):
    from perceiver_io_tpu.cli import train_mlm

    argv = ["--preset", cfg["preset"], "--batch_size", str(batch_size),
            "--logdir", logdir, "--no_tensorboard", "--max_epochs", "1"]
    for flag in MODEL_FLAGS:
        argv += [f"--{flag}", str(cfg[flag])]
    argv += schedule.cli_flags(cfg)
    return train_mlm.apply_preset(train_mlm.build_parser().parse_args(argv))


def build_model(cfg: Dict[str, Any], batch_size: int = 1, logdir: str = "logs"):
    from perceiver_io_tpu.cli import common

    args = _args(cfg, batch_size, logdir)
    return common.build_mlm(args, cfg["vocab_size"], cfg["max_seq_len"]), args


def param_shapes(cfg: Dict[str, Any]):
    model, _ = build_model(cfg)
    ids = jnp.zeros((1, cfg["max_seq_len"]), jnp.int32)
    pad = jnp.zeros((1, cfg["max_seq_len"]), bool)
    return jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0), "masking": jax.random.key(1)},
                           ids, pad)["params"])


def build_trainer(cfg: Dict[str, Any], mix: Dict[str, Any], params, rng,
                  example_batch, logdir: str):
    """The Trainer ``cli/train_mlm.build_trainer`` would build for these
    flags, on weights and an rng the benchmark made."""
    from perceiver_io_tpu.cli import common
    from perceiver_io_tpu.training import TrainState, make_mlm_steps, mlm_gather_capacity
    from perceiver_io_tpu.training.trainer import Trainer

    model, args = build_model(cfg, mix["batch_size"], logdir)
    tx, schedule = common.optimizer_from_args(args)
    state = jax.jit(lambda p, k: TrainState.create(p, tx, k))(params, rng)
    capacity = args.loss_gather_capacity
    if capacity < 0:
        capacity = mlm_gather_capacity(args.max_seq_len)
    mesh = common.mesh_from_args(args)
    fused = args.fused_head
    if fused == "auto":  # cli/train_mlm.build_trainer's rule
        fused = ("pallas" if jax.default_backend() == "tpu" and mesh.size == 1
                 and args.num_latent_channels <= 128 else "off")
    train_step, eval_step, _ = make_mlm_steps(
        model, schedule, loss_gather_capacity=capacity or None,
        fused_head={"pallas": "pallas", "xla": True, "off": False}[fused])
    # the in-loop MFU lowers the step a second time at the first log
    # boundary (step 50): a one-off that would land inside the window, and a
    # source the benchmark does not use (XLA's count, PERF.md)
    config = dataclasses.replace(common.trainer_config(args), compute_mfu=False)
    return Trainer(
        train_step, eval_step, state, config,
        example_batch={k: example_batch[k] for k in ("token_ids", "pad_mask")},
        mesh=mesh, hparams=vars(args), tokens_per_example=args.max_seq_len)


def _model_flops(cfg, *, input_positions, output_queries, training):
    return flops.perceiver_io(
        input_positions=input_positions, input_channels=cfg["num_latent_channels"],
        num_latents=cfg["num_latents"], num_channels=cfg["num_latent_channels"],
        num_encoder_layers=cfg["num_encoder_layers"],
        num_self_attention_layers_per_block=cfg["num_self_attention_layers_per_block"],
        output_queries=output_queries, output_classes=cfg["vocab_size"],
        input_needs_grad=True, training=training)


def train_flops_per_sample(cfg: Dict[str, Any], mix: Dict[str, Any], pool) -> float:
    """Inputs at the width the traffic states (padding included: one shape);
    decoded positions: the ``mask_p`` share of the REAL tokens, which is what
    the loss needs, not the program's gather capacity."""
    real = np.mean([(~b["pad_mask"]).sum(axis=1).mean() for b in pool])
    width = pool[0]["token_ids"].shape[1]
    return _model_flops(cfg, input_positions=width,
                        output_queries=cfg["mask_p"] * real, training=True)


def serve_flops(cfg: Dict[str, Any], tokens: int, masks: int) -> float:
    """Forward operations of one served request of ``tokens`` real tokens
    with ``masks`` decoded positions (unpadded)."""
    return _model_flops(cfg, input_positions=tokens, output_queries=masks,
                        training=False)


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {k: cfg[k] for k in (
        "num_encoder_layers", "num_self_attention_layers_per_block",
        "num_cross_attention_heads", "num_self_attention_heads", "vocab_size")}


def reference_task(cfg: Dict[str, Any]) -> Dict[str, Any]:
    sz = sizes(cfg)

    def prepare(batch, rng, step: int):
        key = ref.masking_key(rng, step)
        ids, labels = ref.mask_tokens(
            key, jnp.asarray(batch["token_ids"]), jnp.asarray(batch["pad_mask"]),
            cfg["vocab_size"], mask_p=cfg["mask_p"])
        count = float((labels != ref.IGNORE).sum())
        return {"token_ids": ids, "pad_mask": jnp.asarray(batch["pad_mask"]),
                "labels": labels}, count

    return {
        "prepare": prepare,
        "ce_sum": lambda ar: (lambda params, block: ref.mlm_ce_sum(ar, params, block, sz)),
        "block_rows": 16,
        "learning_rate": schedule.learning_rate(cfg),
        "weight_decay": cfg["weight_decay"],
    }


def build_server(cfg: Dict[str, Any], mix: Dict[str, Any], params, vocabulary):
    """The fill-mask server as ``cli/serve.py`` assembles it (``MLMServer``
    over a ``PerceiverMLM`` and a WordPiece tokenizer), on the benchmark's
    weights and a vocabulary built directly (no tokenizer training)."""
    from perceiver_io_tpu.data.tokenizer import WordPieceTokenizer
    from perceiver_io_tpu.inference.engine import MLMServer

    model, _ = build_model(cfg)
    tokenizer = WordPieceTokenizer(vocab={tok: i for i, tok in enumerate(vocabulary)})
    server_spec = mix["server"]
    return MLMServer(
        model, params, tokenizer, cfg["max_seq_len"],
        bucket_widths=server_spec["bucket_widths"],
        max_batch=server_spec["max_batch"],
        compute_dtype=server_spec["compute_dtype"])


def reference_logits_fn(cfg: Dict[str, Any]):
    """``arith -> f(params, ids, pad_mask) -> (B, L, vocab)`` logits of the
    plain reference on token ids as given."""
    sz = sizes(cfg)
    return lambda ar: (lambda params, ids, pad: ref.mlm_logits(ar, params, ids, pad, sz))
