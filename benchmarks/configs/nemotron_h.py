"""Builder of the ``nemotron_h`` family: the Nemotron-H stack as
Nemotron-Labs-TwoTower-30B-A3B-Base's ``config.json`` configures it, a
token-level causal decoder of ONE sublayer a block by
``hybrid_override_pattern`` (``M`` a Mamba-2 state-space mixer, ``*``
grouped-query attention without positions, ``E`` routed squared-ReLU experts
beside a shared expert of its own width), an untied head. The program is the
decoder skeleton the ``decoder_lm`` and ``lfm2_moe`` families run
(``perceiver_io_tpu/models/decoder_lm.py``), reached through the same CLI. It
is the language model those keys define, trained by next-token cross-entropy:
the model card's second (denoiser) tower has no key in that file, and nothing
here stands in for it (the configuration's ``deployment.not_included``).

- ``build_trainer``: the system under test, built as ``cli/train_lm.py``
  builds it (its parser, ``build_model``, ``make_lm_steps``, ``Trainer``),
  minus the IMDB data module.
- ``train_flops_per_sample``: from ``benchmarks/flops_nemotron_h.py``.
- ``reference_task``: the adapters between ``reference/nemotron_h.py`` and
  this family's batches.

What is the family's own (its notes, as the README has them for the others):

- A configuration is the published ``config.json`` with the chip's share
  written over it: ``n_routed_experts`` is the number of experts HELD here,
  ``vocab_size`` the slice of the vocabulary, ``num_hidden_layers`` /
  ``hybrid_override_pattern`` the blocks kept; ``deployment`` states the
  published counts, over how many chips a layer is divided, which experts are
  held and what of the model's card is not included. The program is told the
  published router width (``--n_routed_experts``) and its share
  (``--experts_held``, ``--expert_offset``); the reference gets the same.
- Batches are full rows of ids drawn from the vocabulary slice: position i's
  target is ``t_{i+1}``, so a row of T tokens has T-1 of them and the mean has
  a fixed count.
- Leaves are named as the program names them, and so that
  ``benchmarks/weights.py`` draws them as meant: a block holds ``norm`` and ONE
  of ``mamba`` (``in_proj`` / ``out_proj`` ``kernel``: fan-in uniform;
  ``conv1d/kernel`` of (taps, channels): U(+-1/sqrt(taps)); ``conv1d/bias``:
  U(+-0.02), non-zero so that its gradient path is tested; ``norm/scale``: 1;
  ``A_log``, ``dt_bias``, ``D``: names the rules do not know, N(0, 0.02), the
  SHORT-memory regime of the scan (the configuration's
  ``assumed.scan_parameters``)), ``attn`` (``q_proj``, ``k_proj``, ``v_proj``,
  ``out_proj``) or ``moe`` (``router``, ``experts_up`` / ``experts_down``
  stacked ``(experts, in, out)`` and compared as ONE leaf each,
  ``shared_expert/up`` / ``down``, and the selection bias
  ``expert_bias/scale``: the last name is what gives every expert and every
  seed ONE value, and its gradient is exactly 0 on both sides). Top-6
  selection is discrete: the program (bfloat16 activations) and the reference
  (float32) send a token to different experts where the 6th and 7th scores
  nearly tie.
- The reference takes ``block_rows`` = 1 row of 8,192 tokens, which is the
  whole batch: it holds 16 bytes a parameter plus one row's temporaries. Its
  state-space recurrence walks the row token by token under a checkpoint
  every 128 tokens (64 states of 2 MB kept a layer, not 8,192), and its
  attention checkpoints each block of 512 queries.
- The step's components (``benchmarks/components_nemotron_h.py``), each Pallas
  kernel's operations and bytes and the scan's, counted as the recurrence
  needs them (``benchmarks/flops_nemotron_h.py``), are files of this family.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks import flops_nemotron_h
from benchmarks.configs import schedule
from benchmarks.reference import common, nemotron_h as ref

# What this process's last ``build_trainer`` built (``cfg``, ``batch_size``,
# ``width``), for the family's metric readers: a reader gets the loop's
# result, which does not say what it ran.
BUILT = None

# the published keys the program is told as flags, and how the run is made
MODEL_FLAGS = ("model_type", "hybrid_override_pattern", "hidden_size", "moe_intermediate_size",
               "moe_shared_expert_intermediate_size", "n_shared_experts", "mlp_hidden_act",
               "num_attention_heads", "num_key_value_heads", "head_dim", "mamba_num_heads",
               "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "use_conv_bias",
               "chunk_size", "time_step_min", "time_step_max", "time_step_floor",
               "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
               "layer_norm_epsilon")
RUN_FLAGS = ("dtype", "attn_impl", "optimizer", "learning_rate", "weight_decay", "vocab_size")


def _args(cfg: Dict[str, Any], batch_size: int, max_seq_len: int, logdir: str):
    from perceiver_io_tpu.cli import train_lm

    share = cfg["deployment"]
    argv = ["--batch_size", str(batch_size), "--max_seq_len", str(max_seq_len),
            "--logdir", logdir, "--no_tensorboard", "--max_epochs", "1",
            "--experts_held", str(share["experts_held"]),
            "--expert_offset", str(share["expert_offset"]),
            # the router routes over the published experts; the file's key counts the held ones
            "--n_routed_experts", str(share["n_routed_experts_published"])]
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
    return flops_nemotron_h.train_flops_per_sample(cfg, pool[0]["token_ids"].shape[1])


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/nemotron_h.py`` reads, under its own names."""
    share = cfg["deployment"]
    return {
        "layers": cfg["num_hidden_layers"], "eps": cfg["layer_norm_epsilon"],
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "mamba_heads": cfg["mamba_num_heads"], "mamba_head_dim": cfg["mamba_head_dim"],
        "groups": cfg["n_groups"], "state": cfg["ssm_state_size"],
        "top_k": cfg["num_experts_per_tok"], "scale": cfg["routed_scaling_factor"],
        "experts_held": share["experts_held"], "expert_offset": share["expert_offset"],
    }


def reference_task(cfg: Dict[str, Any]) -> Dict[str, Any]:
    sz = sizes(cfg)

    def prepare(batch, rng, step: int):
        ids = jnp.asarray(batch["token_ids"])
        # ``labels`` only says which targets count (every position but a
        # row's last); the loss reads the ids themselves
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
