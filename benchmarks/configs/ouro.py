"""Builder of the ``ouro`` family: Ouro-2.6B as its ``config.json`` configures
it (``model_type`` ``ouro``), trained as a LOOPED language model (the Ouro
LoopLM, arXiv:2510.25741). The program is the decoder skeleton the other
decoder families run (``perceiver_io_tpu/models/decoder_lm.py``), reached
through the same CLI, ``make_lm_steps`` and ``Trainer``.

The equations (R = ``total_ut_steps`` = 4, N the layers kept; N_i RMSNorms with
a learned scale and eps 1e-6; no projection has a bias; activations bfloat16,
parameters float32):

- block l, the sandwich norm:
  ``a = x + N2_l(Attn_l(N1_l(x)))``,
  ``y = a + N4_l(W_down(silu(N3_l(a) W_gate) * N3_l(a) W_up))``, SwiGLU width
  5,632; ``Attn`` multi-head, 16 query and 16 key/value heads of 128, q and k
  turned by half-split rotary over all 128 channels at theta 1e6 with the
  positions 0..T-1 at every loop step, no q/k norm, a causal softmax of
  ``q k^T / sqrt(128)`` over v, then ``W_o``.
- the loop: ``h^0 = E[ids]``, ``h^t = N_f(Block_N o ... o Block_1(h^{t-1}))``,
  t = 1..R, the same weights at every t; the final norm's output is the next
  step's input.
- the exits, per token: ``lambda_t = sigmoid(h^t w_g + b_g)``, ``p_t =
  lambda_t prod_{j<t}(1 - lambda_j)`` for t < R, ``p_R = prod_{j<R}(1 -
  lambda_j)``, ``CE_t`` the cross-entropy of ``h^t W_head`` (untied) against
  the next token.
- the loss: ``L = mean over tokens of [sum_t p_t CE_t + beta sum_t p_t log
  p_t]``, beta = 0.1: the expected loss over the exits less beta times the exit
  distribution's entropy.

The program runs the R passes as ONE ``nn.scan`` over the stack with the
parameters broadcast (scope ``ut_stack``), and the exits after it, one at a
time (scope ``ut_exits``).

- ``build_trainer``: the system under test, built as ``cli/train_lm.py``
  builds it (its parser, ``build_model``, ``make_lm_steps``, ``Trainer``),
  minus the IMDB data module.
- ``train_flops_per_sample``: from ``benchmarks/flops_ouro.py``.
- ``reference_task``: the adapters between ``reference/ouro.py`` and this
  family's batches.

What is the family's own:

- A configuration is the published ``config.json`` with the layers kept
  written over it (``num_hidden_layers``, ``layer_types``); ``deployment``
  states the published depth and the pipeline stage this chip is.
- Batches are full rows of ids drawn from the whole vocabulary: position i's
  target is ``t_{i+1}``, so a row of T tokens has T-1 of them and the mean has
  a fixed count.
- Leaves are named as the program names them: the stack's ONE weight set is
  ``loop`` (``layer_<i>`` with ``attn_norm``, ``attn`` (``q_proj``,
  ``k_proj``, ``v_proj``, ``out_proj``), ``attn_post_norm``, ``ffn_norm``,
  ``mlp`` (``gate``, ``up``, ``down``), ``ffn_post_norm``; and
  ``final_norm``), beside ``embed``, ``head`` and ``exit_gate`` (``kernel``
  (D, 1), ``bias`` (1,): ``weights.py`` draws both).
- The reference takes ``block_rows`` = 1 row, which is the whole batch: 16
  bytes a parameter plus one row's temporaries.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks import flops_ouro
from benchmarks.configs import schedule
from benchmarks.reference import common, ouro as ref

# What this process's last ``build_trainer`` built (``cfg``, ``batch_size``,
# ``width``), for the family's metric readers: a reader gets the loop's
# result, which does not say what it ran.
BUILT = None

# the published keys the program is told as flags, and how the run is made
MODEL_FLAGS = ("model_type", "hidden_size", "intermediate_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
               "rms_norm_eps", "tie_word_embeddings", "total_ut_steps", "exit_entropy_beta")
RUN_FLAGS = ("dtype", "attn_impl", "optimizer", "learning_rate", "weight_decay", "vocab_size")


def _args(cfg: Dict[str, Any], batch_size: int, max_seq_len: int, logdir: str):
    from perceiver_io_tpu.cli import train_lm

    argv = ["--batch_size", str(batch_size), "--max_seq_len", str(max_seq_len),
            "--logdir", logdir, "--no_tensorboard", "--max_epochs", "1",
            "--layer_types", ",".join(cfg["layer_types"])]
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
    return flops_ouro.train_flops_per_sample(cfg, pool[0]["token_ids"].shape[1])


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """What ``reference/ouro.py`` reads, under its own names."""
    return {
        "layers": cfg["num_hidden_layers"], "steps": cfg["total_ut_steps"],
        "heads": cfg["num_attention_heads"], "head_dim": cfg["head_dim"],
        "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
        "beta": cfg["exit_entropy_beta"],
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
        "ce_sum": lambda ar: (lambda params, block: ref.ut_loss_sum(ar, params, block, sz)),
        "block_rows": 1,
        "learning_rate": schedule.learning_rate(cfg),
        "weight_decay": cfg["weight_decay"],
    }
