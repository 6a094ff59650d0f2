"""Builder of the ``sdar_moe`` family: SDAR-30B-A3B-Chat as its
``config.json`` configures it (``model_type`` ``sdar_moe``: grouped-query
attention with per-head q/k norms and half-split rotary, and 128 SwiGLU
experts under a softmax router, in every layer; no shared expert; an untied
head), trained by BLOCK DIFFUSION: each row of L tokens runs as the 2L
positions ``[x_t ; x]`` under the block-diffusion mask, and the loss is the
weighted cross-entropy of the masked tokens of the noised half. The program is
the decoder skeleton the other decoder families run
(``perceiver_io_tpu/models/decoder_lm.py``), reached through the same CLI;
the configuration's ``objective`` and ``block_length`` choose
``training.make_block_diffusion_steps``.

- ``build_trainer``: the system under test, built as ``cli/train_lm.py``
  builds it (its parser, ``build_model``, ``make_block_diffusion_steps``,
  ``Trainer``), minus the IMDB data module.
- ``train_flops_per_sample``: from ``benchmarks/flops_sdar_moe.py``.
- ``reference_task``: the adapters between ``reference/sdar_moe.py`` and this
  family's batches.

What is the family's own:

- A configuration is the published ``config.json`` with the chip's share
  written over it: ``num_experts`` is the number of experts HELD here,
  ``vocab_size`` the slice of the vocabulary, ``num_hidden_layers`` the layers
  kept; ``deployment`` states the published counts. The program is told the
  published router width (``--num_experts``) and its share
  (``--experts_held``, ``--expert_offset``); the reference gets the same.
- Batches are full rows of ids drawn from the vocabulary slice but its last
  row, which is the mask id. The noise is drawn on the device from the
  step's ``masking`` stream of the train state's rng; ``prepare`` draws the
  same from the same rng (``reference/sdar_moe.masking_key``).
- Leaves are named as the program names them: a layer holds ``attn_norm``,
  ``attn`` (``q_proj``, ``k_proj``, ``v_proj``, ``out_proj``,
  ``q_layernorm`` / ``k_layernorm`` ``scale``: 1), ``ffn_norm`` and ``moe``
  (``router`` and ``experts_gate`` / ``experts_up`` / ``experts_down``
  stacked ``(experts, in, out)``, compared as ONE leaf each; no selection
  bias). Top-8 selection is discrete: the program (bfloat16 activations) and
  the reference (float32) send a token to different experts where the 8th and
  9th scores nearly tie.
- The reference takes ``block_rows`` = 1 row, which is the whole batch: 16
  bytes a parameter plus one row's temporaries at 16,384 positions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks import flops_sdar_moe
from benchmarks.configs import schedule
from benchmarks.reference import sdar_moe as ref

# What this process's last ``build_trainer`` built (``cfg``, ``batch_size``,
# ``width``), for the family's metric readers.
BUILT = None

# the keys the program is told as flags, and how the run is made
MODEL_FLAGS = ("model_type", "hidden_size", "moe_intermediate_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
               "norm_topk_prob", "rope_theta", "rms_norm_eps", "objective", "block_length",
               "mask_token_id")
RUN_FLAGS = ("dtype", "attn_impl", "optimizer", "learning_rate", "weight_decay", "vocab_size")


def _args(cfg: Dict[str, Any], batch_size: int, max_seq_len: int, logdir: str):
    from perceiver_io_tpu.cli import train_lm

    share = cfg["deployment"]
    argv = ["--batch_size", str(batch_size), "--max_seq_len", str(max_seq_len),
            "--logdir", logdir, "--no_tensorboard", "--max_epochs", "1",
            "--experts_held", str(share["experts_held"]),
            "--expert_offset", str(share["expert_offset"]),
            # the router routes over the published experts; the file's key counts the held ones
            "--num_experts", str(share["num_experts_published"])]
    for flag in MODEL_FLAGS + RUN_FLAGS:
        argv += [f"--{flag}", str(cfg[flag])]
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
    from perceiver_io_tpu.training import TrainState, make_block_diffusion_steps
    from perceiver_io_tpu.training.trainer import Trainer

    global BUILT
    width = example_batch["token_ids"].shape[1]
    BUILT = {"cfg": cfg, "batch_size": mix["batch_size"], "width": width}
    model, args = build_model(cfg, mix["batch_size"], width, logdir)
    tx, lr_schedule = cli_common.optimizer_from_args(args)
    state = jax.jit(lambda p, k: TrainState.create(p, tx, k))(params, rng)
    train_step, eval_step, _ = make_block_diffusion_steps(model, lr_schedule)
    # the in-loop MFU lowers the step a second time at the first log boundary
    # (a one-off inside the window, from a source the benchmark does not use)
    config = dataclasses.replace(cli_common.trainer_config(args), compute_mfu=False)
    return Trainer(
        train_step, eval_step, state, config,
        example_batch={k: example_batch[k] for k in ("token_ids", "pad_mask")},
        mesh=cli_common.mesh_from_args(args), hparams=vars(args),
        tokens_per_example=width)


def train_flops_per_sample(cfg: Dict[str, Any], mix: Dict[str, Any], pool) -> float:
    return flops_sdar_moe.train_flops_per_sample(cfg, pool[0]["token_ids"].shape[1])


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/sdar_moe.py`` reads, under its own names."""
    share = cfg["deployment"]
    return {
        "layers": cfg["num_hidden_layers"], "eps": cfg["rms_norm_eps"],
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"], "theta": cfg["rope_theta"],
        "block_length": cfg["block_length"],
        "top_k": cfg["num_experts_per_tok"], "scale": cfg["routed_scaling_factor"],
        "experts_held": share["experts_held"], "expert_offset": share["expert_offset"],
    }


def reference_task(cfg: Dict[str, Any]) -> Dict[str, Any]:
    sz = sizes(cfg)

    def prepare(batch, rng, step: int):
        ids = jnp.asarray(batch["token_ids"])
        noisy, weights = ref.noise(ref.masking_key(rng, step), ids, cfg["block_length"],
                                   cfg["mask_token_id"])
        return ({"noisy_ids": noisy, "token_ids": ids, "weights": weights},
                float(ids.shape[0] * ids.shape[1]))

    return {
        "prepare": prepare,
        "ce_sum": lambda ar: (lambda params, block: ref.bd_loss_sum(ar, params, block, sz)),
        "block_rows": 1,
        "learning_rate": schedule.learning_rate(cfg),
        "weight_decay": cfg["weight_decay"],
    }
