"""IMDB sequence-classification entry point (reference ``train/train_seq_clf.py``).

Three init modes, mirroring ``train_seq_clf.py:18-28``:

- ``--mlm_checkpoint <run_dir/checkpoints>``: rebuild the encoder from the
  checkpoint's embedded hparams, graft its pretrained params subtree into a
  fresh classifier (the reference's checkpoint surgery as a pure pytree swap),
  optionally ``--freeze_encoder`` (no updates + encoder runs in eval mode —
  ``freeze()`` parity, reference ``train/utils.py:5-8``);
- ``--clf_checkpoint <run_dir/checkpoints>``: resume a classifier run;
- neither: train from scratch.

Both checkpoint flags also accept a reference PyTorch-Lightning ``.ckpt``
FILE (the artifacts the reference publishes, ``README.md:46-48``) — the torch
state_dict is converted on the fly (``perceiver_io_tpu/interop.py``), so the
reference's pretrained-weights workflow transfers unchanged. A ``.ckpt``
carries no compatible optimizer state, so ``--clf_checkpoint model.ckpt``
restores weights and starts a fresh optimizer (the reference's
``load_from_checkpoint`` does the same, ``train_seq_clf.py:26``).

Reference per-task defaults (``train_seq_clf.py:56-68``): batch 128,
weight_decay 1e-3, dropout 0.1.
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax

from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.imdb import IMDBDataModule
from perceiver_io_tpu.training import TrainState, make_classifier_steps
from perceiver_io_tpu.training.checkpoint import (
    load_hparams,
    restore_encoder_params,
    restore_train_state,
)
from perceiver_io_tpu.training.steps import freeze_subtrees
from perceiver_io_tpu.training.trainer import Trainer


def _is_torch_ckpt(path: str) -> bool:
    import os

    return os.path.isfile(path) and path.endswith(".ckpt")


def _check_tree(imported, like, source: str):
    """Imported params must exactly match the fresh model's tree — a mismatch
    means the .ckpt was trained with different shapes/hparams."""
    import jax

    imported_paths = {
        jax.tree_util.keystr(p): leaf.shape
        for p, leaf in jax.tree_util.tree_leaves_with_path(imported)
    }
    like_paths = {
        jax.tree_util.keystr(p): leaf.shape
        for p, leaf in jax.tree_util.tree_leaves_with_path(like)
    }
    if imported_paths != like_paths:
        missing = sorted(set(like_paths) - set(imported_paths))
        extra = sorted(set(imported_paths) - set(like_paths))
        mismatched = sorted(
            k for k in set(like_paths) & set(imported_paths)
            if like_paths[k] != imported_paths[k]
        )
        raise SystemExit(
            f"imported checkpoint {source} does not fit the model: "
            f"missing={missing[:4]} extra={extra[:4]} shape-mismatch={mismatched[:4]}"
        )
    return imported


def _warn_if_vocab_mismatch(tokenizer_path: str, ckpt: str) -> None:
    """A reference .ckpt's embedding rows are indexed by the reference's
    exact vocab. A locally-trained WordPiece of the same size passes every
    shape check while assigning different ids — warn loudly so the silent
    quality degradation is visible. (The reference's cached HF tokenizer
    JSON drops in at ``<root>/imdb-tokenizer-10003.json``.)"""
    import json
    import warnings

    try:
        with open(tokenizer_path, encoding="utf-8") as f:
            native = json.load(f).get("format", "").startswith("perceiver_io_tpu")
    except (OSError, ValueError):
        native = False
    if native:
        warnings.warn(
            f"importing {ckpt} while using a locally-trained tokenizer "
            f"({tokenizer_path}): token ids almost certainly differ from the "
            f"vocab the checkpoint was trained with, so pretrained embeddings "
            f"will be misaligned. Drop the reference's tokenizer JSON at that "
            f"path (tools/import_reference.py tokenizer) for exact ids.",
            stacklevel=2,
        )


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    common.add_trainer_args(parser)
    common.add_mesh_args(parser)
    common.add_compute_args(parser)
    common.add_model_args(parser)
    common.add_optimizer_args(parser)
    common.add_imdb_args(parser)
    g = parser.add_argument_group("task (sequence classification)")
    g.add_argument("--mlm_checkpoint", default=None,
                   help="checkpoints dir of a train_mlm run: transfer its encoder")
    g.add_argument("--clf_checkpoint", default=None,
                   help="checkpoints dir of a train_seq_clf run: resume")
    g.add_argument("--freeze_encoder", action="store_true")
    g.add_argument("--unsafe_load", action="store_true",
                   help="when a checkpoint flag points at a torch .ckpt that "
                        "the safe weights-only loader rejects, fall back to "
                        "the unrestricted pickle loader (executes code "
                        "embedded in the file — only for trusted artifacts)")
    # reference per-task defaults (train_seq_clf.py:56-68)
    parser.set_defaults(experiment="seq_clf", batch_size=128, weight_decay=1e-3,
                        dropout=0.1, num_latents=64, num_latent_channels=64,
                        num_encoder_layers=3)
    return parser


def main(argv: Optional[Sequence[str]] = None):
    args = common.parse_with_resume(build_parser(), argv)
    if common.maybe_spawn_hosts(args, argv):
        return None  # training ran in the spawned processes
    configure_compile_cache()
    common.maybe_initialize_distributed(args)
    if args.mlm_checkpoint and args.clf_checkpoint:
        raise SystemExit("--mlm_checkpoint and --clf_checkpoint are exclusive")
    if args.resume and (args.mlm_checkpoint or args.clf_checkpoint):
        # conflicting init modes: --resume continues one run in place, the
        # checkpoint flags start a NEW run from another run's weights
        raise SystemExit(
            "--resume is exclusive with --mlm_checkpoint/--clf_checkpoint"
        )

    # a restored encoder must be rebuilt with the shapes it was trained with
    source_ckpt = args.mlm_checkpoint or args.clf_checkpoint
    imported_params = None  # set when the source is a reference .ckpt file
    if source_ckpt and _is_torch_ckpt(source_ckpt):
        from perceiver_io_tpu.interop import import_lightning_checkpoint

        imported_params, source_hparams = import_lightning_checkpoint(
            source_ckpt, allow_unsafe_pickle=args.unsafe_load
        )
        common.override_model_args(args, source_hparams)
    elif source_ckpt:
        source_hparams = load_hparams(source_ckpt)
        common.override_model_args(args, source_hparams)
    if args.clf_checkpoint and imported_params is None:
        # resume also restores the training setup: the optimizer-state pytree
        # structure depends on these (load_from_checkpoint parity,
        # reference lightning.py:46 + train_seq_clf.py:26)
        hparams = load_hparams(args.clf_checkpoint)
        for key in ("optimizer", "weight_decay", "one_cycle_lr", "freeze_encoder"):
            if key in hparams:
                setattr(args, key, hparams[key])

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

    if imported_params is not None:
        _warn_if_vocab_mismatch(data.tokenizer_path, source_ckpt)

    model = common.build_text_classifier(args, vocab_size, args.max_seq_len)
    example = next(iter(data.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(args.seed)},
        example["token_ids"][:1], pad_mask=example["pad_mask"][:1],
    )
    params = variables["params"]

    if args.mlm_checkpoint:
        params = dict(params)
        if imported_params is not None:
            params["encoder"] = _check_tree(
                imported_params["encoder"], params["encoder"], args.mlm_checkpoint
            )
        else:
            params["encoder"] = restore_encoder_params(
                args.mlm_checkpoint, params["encoder"]
            )
    if args.clf_checkpoint and imported_params is not None:
        params = _check_tree(imported_params, params, args.clf_checkpoint)

    tx, schedule = common.optimizer_from_args(args)
    if args.freeze_encoder:
        tx = freeze_subtrees(tx, params, ["encoder"])
    state = TrainState.create(params, tx, jax.random.key(args.seed + 2))
    state, resume_dir = common.resume_state(args, state)

    if args.clf_checkpoint and imported_params is None:
        state = restore_train_state(args.clf_checkpoint, state)

    train_step, eval_step = make_classifier_steps(
        model, schedule, input_kind="text", frozen_encoder=args.freeze_encoder
    )
    mesh = common.mesh_from_args(args)

    trainer = Trainer(
        train_step,
        eval_step,
        state,
        common.trainer_config(args),
        example_batch={k: example[k] for k in ("token_ids", "pad_mask", "label")},
        mesh=mesh,
        shard_seq=args.shard_seq,
        zero_opt=args.zero_opt,
        hparams=vars(args),
        run_dir=resume_dir,
        tokens_per_example=args.max_seq_len,
    )
    with trainer:
        common.run_fit(trainer, data.train_dataloader(), data.val_dataloader())
    return trainer.run_dir


if __name__ == "__main__":
    main()
