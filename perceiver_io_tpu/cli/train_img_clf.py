"""MNIST image-classification entry point (reference ``train/train_img_clf.py``).

Reference per-task defaults (``train_img_clf.py:42-55``): 32 latents × 128
channels, 3 encoder layers × 3 self-attention layers per block, batch 128.
The model is built from the data module's ``dims``/``num_classes``
(``train_img_clf.py:15-17``).
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax

from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.mnist import MNISTDataModule
from perceiver_io_tpu.training import TrainState, make_classifier_steps
from perceiver_io_tpu.training.trainer import Trainer


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    common.add_trainer_args(parser)
    common.add_mesh_args(parser)
    common.add_compute_args(parser)
    common.add_model_args(parser)
    common.add_optimizer_args(parser)
    common.add_mnist_args(parser)
    g = parser.add_argument_group("task (image classification)")
    g.add_argument("--num_frequency_bands", type=int, default=32)
    # reference per-task defaults (train_img_clf.py:42-55)
    parser.set_defaults(experiment="img_clf", num_latents=32,
                        num_latent_channels=128, num_encoder_layers=3,
                        num_self_attention_layers_per_block=3)
    return parser


def main(argv: Optional[Sequence[str]] = None):
    args = common.parse_with_resume(build_parser(), argv)
    if common.maybe_spawn_hosts(args, argv):
        return None  # training ran in the spawned processes
    configure_compile_cache()
    common.maybe_initialize_distributed(args)

    data = MNISTDataModule(
        root=args.root,
        batch_size=args.batch_size,
        random_crop=args.random_crop,
        synthetic=args.synthetic,
        synthetic_size=args.synthetic_size,
        seed=args.seed,
        shard_id=jax.process_index(),
        num_shards=jax.process_count(),
        download=not args.no_download,
    )
    data.prepare_data()
    data.setup()

    model = common.build_image_classifier(
        args, data.dims, data.num_classes,
        num_frequency_bands=args.num_frequency_bands,
    )
    example = next(iter(data.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(args.seed)}, example["image"][:1]
    )
    tx, schedule = common.optimizer_from_args(args)
    state = TrainState.create(variables["params"], tx, jax.random.key(args.seed + 2))
    state, resume_dir = common.resume_state(args, state)

    train_step, eval_step = make_classifier_steps(model, schedule, input_kind="image")
    mesh = common.mesh_from_args(args)

    trainer = Trainer(
        train_step,
        eval_step,
        state,
        common.trainer_config(args),
        example_batch={k: example[k] for k in ("image", "label")},
        mesh=mesh,
        shard_seq=args.shard_seq,
        zero_opt=args.zero_opt,
        hparams=vars(args),
        run_dir=resume_dir,
    )
    with trainer:
        common.run_fit(trainer, data.train_dataloader(), data.val_dataloader())
    return trainer.run_dir


if __name__ == "__main__":
    main()
