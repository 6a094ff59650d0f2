"""ImageNet-1k image-classification entry point.

The Perceiver-paper configuration tracked in BASELINE.md that exceeds the
reference repo's scope (its image path stops at MNIST, reference
``train/train_img_clf.py``): 224×224 inputs (M = 50,176 pixel positions
cross-attended into the latent array), 512 latents × 1024 channels, 6 encoder
layers (layer 1 unique, 2..6 weight-shared) × 6 self-attention layers per
block, 64 Fourier bands. Rematerialization and bf16 are on by default — at
M = 50k the encoder KV streams dominate HBM, which is also where the Pallas
blockwise-KV kernel and the ``--sp`` sequence-parallel mesh axis pay off.

Data comes from a standard ImageFolder tree (``<root>/imagenet/{train,val}/
<class>/*.JPEG``); ``--synthetic`` runs on generated data (zero-egress box).
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax

from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.imagefolder import ImageFolderDataModule
from perceiver_io_tpu.training import TrainState, make_classifier_steps
from perceiver_io_tpu.training.trainer import Trainer


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    common.add_trainer_args(parser)
    common.add_mesh_args(parser)
    common.add_compute_args(parser)
    common.add_model_args(parser)
    common.add_optimizer_args(parser)
    g = parser.add_argument_group("data (ImageFolder)")
    g.add_argument("--root", default=".cache")
    g.add_argument("--dataset_name", default="imagenet",
                   help="subdirectory of --root holding the train/val tree")
    g.add_argument("--image_size", type=int, default=224)
    g.add_argument("--batch_size", type=int, default=64,
                   help="GLOBAL batch; the default is sized for a v5e-8 "
                        "(8/chip under dp). One v5e chip fits batch 8 at "
                        "224² (batch 64 OOMs its 16 GB HBM); batch scaling "
                        "is flat b8-b32 anyway — the step is compute-bound "
                        "(PERF.md)")
    g.add_argument("--num_workers", type=int, default=8,
                   help="JPEG-decode threads per host")
    g.add_argument("--synthetic", action="store_true")
    g.add_argument("--synthetic_size", type=int, default=4096)
    g.add_argument("--synthetic_classes", type=int, default=10)
    t = parser.add_argument_group("task (ImageNet classification)")
    t.add_argument("--num_frequency_bands", type=int, default=64)
    t.add_argument("--no_remat", action="store_true",
                   help="disable the remat-by-default applied at image_size ≥ 64 "
                        "(see --remat for what it keeps and what it recomputes)")
    # Perceiver-paper ImageNet defaults (BASELINE.md tracked config)
    parser.set_defaults(experiment="imagenet", num_latents=512,
                        num_latent_channels=1024, num_encoder_layers=6,
                        num_self_attention_layers_per_block=6,
                        num_cross_attention_heads=1,
                        num_self_attention_heads=8,
                        weight_decay=1e-1, optimizer="AdamW",
                        learning_rate=4e-3)
    return parser


def main(argv: Optional[Sequence[str]] = None):
    args = common.parse_with_resume(build_parser(), argv)
    if common.maybe_spawn_hosts(args, argv):
        return None  # training ran in the spawned processes
    configure_compile_cache()
    common.maybe_initialize_distributed(args)
    # remat is the sane default at M = image_size² (opt out via --no_remat)
    if args.image_size >= 64 and not args.no_remat:
        args.remat = True

    data = ImageFolderDataModule(
        root=args.root,
        name=args.dataset_name,
        image_size=args.image_size,
        batch_size=args.batch_size,
        synthetic=args.synthetic,
        synthetic_size=args.synthetic_size,
        synthetic_classes=args.synthetic_classes,
        num_workers=args.num_workers,
        seed=args.seed,
        shard_id=jax.process_index(),
        num_shards=jax.process_count(),
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
