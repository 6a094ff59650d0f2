"""Optical-flow training entry point (framework extension — the reference has
no flow task; this exercises BASELINE.md's Sintel config end-to-end: frame-pair
input adapter, dense per-pixel query decoder, end-point-error loss).

Usage:

    python train/train_flow.py --synthetic --experiment=flow \
        --image_height 64 --image_width 64 --max_epochs 10
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax

from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.flow import FlowDataModule
from perceiver_io_tpu.models.flow import build_optical_flow_model
from perceiver_io_tpu.training import TrainState, make_flow_steps
from perceiver_io_tpu.training.trainer import Trainer


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    common.add_trainer_args(parser)
    common.add_mesh_args(parser)
    common.add_compute_args(parser)
    common.add_model_args(parser)
    common.add_optimizer_args(parser)
    g = parser.add_argument_group("data (optical flow)")
    g.add_argument("--root", default=".cache")
    g.add_argument("--batch_size", type=int, default=8)
    g.add_argument("--image_height", type=int, default=368)
    g.add_argument("--image_width", type=int, default=496)
    g.add_argument("--image_channels", type=int, default=3)
    g.add_argument("--synthetic", action="store_true")
    g.add_argument("--synthetic_size", type=int, default=512)
    t = parser.add_argument_group("task (optical flow)")
    t.add_argument("--patch_size", type=int, default=3)
    t.add_argument("--num_frequency_bands", type=int, default=64)
    # flow-scale defaults (Perceiver IO paper config, scaled by CLI flags)
    parser.set_defaults(experiment="flow", num_latents=2048,
                        num_latent_channels=512, num_encoder_layers=1,
                        num_self_attention_layers_per_block=24,
                        num_cross_attention_heads=1,
                        num_self_attention_heads=8)
    return parser


def main(argv: Optional[Sequence[str]] = None):
    args = common.parse_with_resume(build_parser(), argv)
    if common.maybe_spawn_hosts(args, argv):
        return None  # training ran in the spawned processes
    configure_compile_cache()
    common.maybe_initialize_distributed(args)
    image_shape = (args.image_height, args.image_width, args.image_channels)

    data = FlowDataModule(
        root=args.root,
        image_shape=image_shape,
        batch_size=args.batch_size,
        synthetic=args.synthetic,
        synthetic_size=args.synthetic_size,
        seed=args.seed,
        shard_id=jax.process_index(),
        num_shards=jax.process_count(),
    )
    data.prepare_data()
    data.setup()

    model = build_optical_flow_model(
        image_shape=image_shape,
        latent_shape=(args.num_latents, args.num_latent_channels),
        num_layers=args.num_encoder_layers,
        num_self_attention_layers_per_block=args.num_self_attention_layers_per_block,
        num_cross_attention_heads=args.num_cross_attention_heads,
        num_self_attention_heads=args.num_self_attention_heads,
        patch_size=args.patch_size,
        num_frequency_bands=args.num_frequency_bands,
        dropout=args.dropout,
        dtype=common.DTYPES[args.dtype],
        attn_impl=args.attn_impl,
        remat=args.remat,
        reuse_kv=not getattr(args, "no_reuse_kv", False),
    )
    example = next(iter(data.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(args.seed)}, example["frames"][:1]
    )
    tx, schedule = common.optimizer_from_args(args)
    state = TrainState.create(variables["params"], tx, jax.random.key(args.seed + 2))
    state, resume_dir = common.resume_state(args, state)

    train_step, eval_step = make_flow_steps(model, schedule)
    mesh = common.mesh_from_args(args)

    trainer = Trainer(
        train_step,
        eval_step,
        state,
        common.trainer_config(args),
        example_batch={k: example[k] for k in ("frames", "flow")},
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
