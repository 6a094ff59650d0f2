"""Builder of the ``decoder_lm`` family: a token-level causal decoder with
latent attention, routed experts and a multi-token-prediction module
(``perceiver_io_tpu/models/decoder_lm.py``; the first family here that is not
a Perceiver).

- ``build_trainer``: the system under test, built as ``cli/train_lm.py``
  builds it (its parser, ``build_model``, ``make_lm_steps``, ``Trainer``),
  minus the IMDB data module.
- ``train_flops_per_sample``: from ``benchmarks/flops_decoder_lm.py``.
- ``reference_task``: the adapters between ``reference/decoder_lm.py`` and
  this family's batches.

What is the family's own (the README's notes for it):

- A configuration is the published ``config.json`` with the chip's share
  written over it: ``n_routed_experts`` is the number of experts HELD here,
  ``vocab_size`` the slice of the vocabulary, ``num_hidden_layers`` the depth
  kept; ``deployment`` states the published counts, over how many chips a layer
  is divided and which experts are held. The program is told the published
  router width (``--n_routed_experts``) and its share (``--experts_held``,
  ``--expert_offset``); the reference gets the same.
- Batches are full rows of ids drawn from the vocabulary slice: position i's
  targets are ``t_{i+1}`` and, in the MTP module, ``t_{i+2}``, so a row of T
  tokens has T-1 and T-2 of them and the two means have fixed counts.
- ``correct`` compares, per leaf, a held expert's stacked kernels
  ``(experts, in, out)`` as ONE leaf, and the router's selection bias as a leaf
  whose gradient is exactly 0 on both sides. Top-k selection is discrete: the
  program (bfloat16 activations) and the reference (float32) send a token to
  different experts where the 8th and 9th scores nearly tie; PERF.md gives how
  many assignments differ and what that does to the expert leaves' gaps.
- The reference takes ``block_rows`` = 1 row of 4,096 tokens at a time: four
  blocks a batch, so it holds 20 bytes a parameter plus one row's temporaries.
- The step's components (``benchmarks/components_decoder_lm.py``) and each
  Pallas kernel's operations and bytes (``benchmarks/flops_decoder_lm.py``)
  are files of this family.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks import flops_decoder_lm
from benchmarks.configs import schedule
from benchmarks.reference import common, decoder_lm as ref

# What this process's last ``build_trainer`` built (``cfg``, ``batch_size``,
# ``width``), for the family's metric readers: a reader gets the loop's
# result, which does not say what it ran.
BUILT = None

RUN_FLAGS = ("dtype", "attn_impl", "optimizer", "learning_rate", "weight_decay", "vocab_size")


def _args(cfg: Dict[str, Any], batch_size: int, max_seq_len: int, logdir: str):
    from perceiver_io_tpu.cli import train_lm

    share = cfg["deployment"]
    argv = ["--batch_size", str(batch_size), "--max_seq_len", str(max_seq_len),
            "--logdir", logdir, "--no_tensorboard", "--max_epochs", "1",
            "--experts_held", str(share["experts_held"]),
            "--expert_offset", str(share["expert_offset"])]
    sizes = {f.name for f in train_lm.MODEL_FIELDS} - {"experts_held", "expert_offset"}
    for flag in sorted(sizes | set(RUN_FLAGS)):
        if flag in cfg:
            argv += [f"--{flag}", str(cfg[flag])]
    # the router routes over the published experts; the file's key counts the held ones
    argv += ["--n_routed_experts", str(share["n_routed_experts_published"])]
    argv += schedule.cli_flags(cfg)
    return train_lm.build_parser().parse_args(argv)


def build_model(cfg: Dict[str, Any], batch_size: int = 1, max_seq_len: int = 8,
                logdir: str = "logs"):
    from perceiver_io_tpu.cli import train_lm

    args = _args(cfg, batch_size, max_seq_len, logdir)
    return train_lm.build_model(args, cfg["vocab_size"]), args


def param_shapes(cfg: Dict[str, Any]):
    model, _ = build_model(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    return jax.eval_shape(lambda: model.init({"params": jax.random.key(0)}, ids)["params"])


def build_trainer(cfg: Dict[str, Any], mix: Dict[str, Any], params, rng,
                  example_batch, logdir: str):
    """The Trainer ``cli/train_lm.main`` would build for these flags, on
    weights and an rng the benchmark made."""
    from perceiver_io_tpu.cli import common as cli_common
    from perceiver_io_tpu.training import TrainState, make_lm_steps
    from perceiver_io_tpu.training.trainer import Trainer

    global BUILT
    width = example_batch["token_ids"].shape[1]
    BUILT = {"cfg": cfg, "batch_size": mix["batch_size"], "width": width}
    model, args = build_model(cfg, mix["batch_size"], width, logdir)
    tx, lr_schedule = cli_common.optimizer_from_args(args)
    state = jax.jit(lambda p, k: TrainState.create(p, tx, k))(params, rng)
    train_step, eval_step, _ = make_lm_steps(model, lr_schedule)
    # the in-loop MFU lowers the step a second time at the first log boundary
    # (a one-off inside the window, from a source the benchmark does not use)
    config = dataclasses.replace(cli_common.trainer_config(args), compute_mfu=False)
    return Trainer(
        train_step, eval_step, state, config,
        example_batch={k: example_batch[k] for k in ("token_ids", "pad_mask")},
        mesh=cli_common.mesh_from_args(args), hparams=vars(args),
        tokens_per_example=width)


def train_flops_per_sample(cfg: Dict[str, Any], mix: Dict[str, Any], pool) -> float:
    return flops_decoder_lm.train_flops_per_sample(cfg, pool[0]["token_ids"].shape[1])


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/decoder_lm.py`` reads, under its own names."""
    share = cfg["deployment"]
    return {
        "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
        "top_k": cfg["num_experts_per_tok"], "scale": cfg["routed_scaling_factor"],
        "experts_held": share["experts_held"], "expert_offset": share["expert_offset"],
        "mtp_loss_factor": cfg["mtp_loss_factor"],
    }


def reference_task(cfg: Dict[str, Any]) -> Dict[str, Any]:
    sz = sizes(cfg)

    def prepare(batch, rng, step: int):
        ids = jnp.asarray(batch["token_ids"])
        # ``labels`` only says which targets count (the main term's: every
        # position but a row's last); the losses read the ids themselves
        labels = jnp.where(jnp.arange(ids.shape[1])[None, :] < ids.shape[1] - 1,
                           jnp.roll(ids, -1, axis=1), common.IGNORE)
        return {"token_ids": ids, "labels": labels}, float(ids.shape[0] * (ids.shape[1] - 1))

    return {
        "prepare": prepare,
        "ce_sum": lambda ar: (lambda params, block: ref.lm_ce_sum(ar, params, block, sz)),
        "block_rows": 1,
        "learning_rate": schedule.learning_rate(cfg),
        "weight_decay": cfg["weight_decay"],
    }
