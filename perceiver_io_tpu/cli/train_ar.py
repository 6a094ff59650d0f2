"""Perceiver-AR causal LM pretraining entry point (the generative task).

Trains :class:`~perceiver_io_tpu.models.perceiver.PerceiverARLM` —
next-token prediction over a causal latent window covering the last
``num_latents`` positions of each sequence — on the IMDB text pipeline (the
same tokenizer/collator the MLM task uses, so ``--synthetic`` long-doc mode
works fully offline). Checkpoints embed hparams and load back through
``inference.generate.load_ar_checkpoint`` for serving
(``serve.py --task generate`` / ``serving.replica --preset tiny_ar``).

Usage:

    python -m perceiver_io_tpu.cli.train_ar --synthetic --max_steps 200 \
        --default_root_dir /tmp/ar_run
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax
import numpy as np

from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.imdb import IMDBDataModule
from perceiver_io_tpu.training import TrainState, make_ar_steps
from perceiver_io_tpu.training.trainer import Trainer

# Width/compute defaults per --preset (the train_mlm pattern): 'reference' =
# CPU/GPU-scale widths, 'flagship_tpu' = the TPU-native flagship_ar widths.
PRESET_DEFAULTS = {
    "reference": {"num_latents": 64, "num_latent_channels": 64,
                  "attn_impl": "auto"},
    "flagship_tpu": {"num_latents": 256, "num_latent_channels": 512,
                     "attn_impl": "auto"},
}


def apply_preset(args: argparse.Namespace) -> argparse.Namespace:
    for key, value in PRESET_DEFAULTS[args.preset].items():
        if getattr(args, key) is None:
            setattr(args, key, value)
    return args


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    common.add_trainer_args(parser)
    common.add_mesh_args(parser)
    common.add_compute_args(parser)
    common.add_model_args(parser)
    common.add_optimizer_args(parser)
    common.add_imdb_args(parser)
    g = parser.add_argument_group("task (AR generation)")
    g.add_argument("--preset", choices=["reference", "flagship_tpu"],
                   default="reference",
                   help="model-width preset; explicit width flags override")
    g.add_argument("--sample_prefix_len", type=int, default=16,
                   help="per-validation-epoch sample generation: continue "
                        "this many tokens of the first validation row "
                        "(0 disables the hook)")
    g.add_argument("--sample_new_tokens", type=int, default=12)
    parser.set_defaults(experiment="ar", batch_size=64, num_latents=None,
                        num_latent_channels=None, attn_impl=None,
                        num_encoder_layers=3)
    return parser


def make_sample_hook(model, collator, prefix_len: int,
                     new_tokens: int, example_ids: np.ndarray):
    """Per-eval sample continuation (the AR analogue of train_mlm's
    predict_samples): greedy-continue a validation prefix and log the
    decoded text."""
    if prefix_len <= 0 or new_tokens <= 0:
        return None
    from perceiver_io_tpu.inference.generate import ARGenerator, SamplingConfig

    prefix = [int(t) for t in example_ids[:prefix_len] if int(t) != 0]
    if len(prefix) < 2:
        return None
    tokenizer = collator.tokenizer

    def hook(state, logger, step):
        gen = ARGenerator(model, state.params,
                          max_seq_len=collator.max_seq_len,
                          chunk=min(8, new_tokens), name="train-sample")
        tokens, _ = gen.generate(prefix, new_tokens, SamplingConfig())
        text = " ".join(tokenizer.id_to_token(int(t)) for t in tokens)
        logger.log_text("continuation", step,
                        f"prefix({len(prefix)} toks) → {text}")

    return hook


def main(argv: Optional[Sequence[str]] = None):
    args = apply_preset(common.parse_with_resume(build_parser(), argv))
    if common.maybe_spawn_hosts(args, argv):
        return None
    configure_compile_cache()
    common.maybe_initialize_distributed(args)
    common.validate_bucket_args(args)

    data = IMDBDataModule(
        root=args.root,
        max_seq_len=args.max_seq_len,
        vocab_size=args.vocab_size,
        batch_size=args.batch_size,
        synthetic=args.synthetic,
        synthetic_size=args.synthetic_size,
        seed=args.seed,
        shard_id=jax.process_index(),
        num_shards=jax.process_count(),
        download=not args.no_download,
        bucket_widths=args.bucket_widths,
        length_sort_window=args.length_sort_window,
        dispatch_group=args.steps_per_dispatch,
    )
    data.prepare_data()
    data.setup()
    vocab_size = data.tokenizer.get_vocab_size()

    model = common.build_ar(args, vocab_size, args.max_seq_len)
    example = next(iter(data.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(args.seed)},
        example["token_ids"][:1], example["pad_mask"][:1],
    )
    tx, schedule = common.optimizer_from_args(args)
    state = TrainState.create(variables["params"], tx,
                              jax.random.key(args.seed + 2))
    state, resume_dir = common.resume_state(args, state)

    mesh = common.mesh_from_args(args)
    train_step, eval_step, _ = make_ar_steps(model, schedule)

    trainer = Trainer(
        train_step,
        eval_step,
        state,
        common.trainer_config(args),
        example_batch={k: example[k] for k in ("token_ids", "pad_mask")},
        mesh=mesh,
        shard_seq=args.shard_seq,
        zero_opt=args.zero_opt,
        hparams=vars(args),
        run_dir=resume_dir,
        predict_hook=make_sample_hook(
            model, data.collator, args.sample_prefix_len,
            args.sample_new_tokens,
            np.asarray(example["token_ids"][0]),
        ),
        tokens_per_example=args.max_seq_len,
    )
    with trainer:
        state = common.run_fit(
            trainer, data.train_dataloader(), data.val_dataloader()
        )
    return trainer.run_dir


if __name__ == "__main__":
    main()
