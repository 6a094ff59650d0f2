"""Multimodal audio/video autoencoding entry point (framework extension — the
reference has no audio/video task; this exercises the Perceiver IO paper's
Kinetics-style config: fused video+audio token stream in, video+audio
reconstruction + classification out).

Usage:

    python train/train_multimodal.py --experiment=multimodal \
        --video_frames 8 --video_size 32 --audio_samples 2048 --max_epochs 10
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax

from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.av import AVDataModule
from perceiver_io_tpu.models.multimodal import build_multimodal_autoencoder
from perceiver_io_tpu.training import TrainState, make_multimodal_steps
from perceiver_io_tpu.training.trainer import Trainer


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    common.add_trainer_args(parser)
    common.add_mesh_args(parser)
    common.add_compute_args(parser)
    common.add_model_args(parser)
    common.add_optimizer_args(parser)
    g = parser.add_argument_group("data (audio/video)")
    g.add_argument("--root", default=".cache")
    g.add_argument("--batch_size", type=int, default=8)
    g.add_argument("--video_frames", type=int, default=16)
    g.add_argument("--video_size", type=int, default=224)
    g.add_argument("--video_channels", type=int, default=3)
    g.add_argument("--audio_samples", type=int, default=30720)
    g.add_argument("--audio_channels", type=int, default=1)
    g.add_argument("--num_classes", type=int, default=4)
    g.add_argument("--synthetic", action="store_true", default=True)
    g.add_argument("--real_data", dest="synthetic", action="store_false",
                   help="read <root>/av/<split>/<class>/<clip>.npz instead of "
                        "generating synthetic clips")
    g.add_argument("--synthetic_size", type=int, default=256)
    t = parser.add_argument_group("task (multimodal)")
    t.add_argument("--video_patch", type=int, nargs=3, default=(1, 4, 4),
                   metavar=("PT", "PH", "PW"))
    t.add_argument("--samples_per_patch", type=int, default=16)
    t.add_argument("--num_modality_channels", type=int, default=8)
    t.add_argument("--video_frequency_bands", type=int, default=32)
    t.add_argument("--audio_frequency_bands", type=int, default=64)
    t.add_argument("--video_patch_loss", action="store_true",
                   help="compute the video reconstruction loss in PATCH "
                        "space (patchify the target instead of un-patchifying "
                        "the prediction — same element set, exact up to fp "
                        "reassociation; skips the (B,T,H,W,C) transpose pair "
                        "in fwd+bwd). Params/checkpoints are unaffected")
    t.add_argument("--video_weight", type=float, default=1.0)
    t.add_argument("--audio_weight", type=float, default=1.0)
    t.add_argument("--label_weight", type=float, default=1.0)
    # paper-scale defaults, scaled down by CLI flags for smoke runs.
    # attn_impl 'xla' is the measured-best for the paper AV config (r4
    # roofline A/B: the area rule routes the 52k-query decoder cross to the
    # fused kernel, which loses 30.8 vs 27.7 ms end-to-end at b2 — the same
    # overlap dilution as PERF.md negative (11)); explicit --attn_impl wins
    parser.set_defaults(experiment="multimodal", num_latents=784,
                        num_latent_channels=512, num_encoder_layers=1,
                        num_self_attention_layers_per_block=8,
                        num_cross_attention_heads=1,
                        num_self_attention_heads=8,
                        attn_impl="xla")
    return parser


def main(argv: Optional[Sequence[str]] = None):
    args = common.parse_with_resume(build_parser(), argv)
    if common.maybe_spawn_hosts(args, argv):
        return None  # training ran in the spawned processes
    configure_compile_cache()
    common.maybe_initialize_distributed(args)
    video_shape = (
        args.video_frames, args.video_size, args.video_size, args.video_channels
    )

    data = AVDataModule(
        root=args.root,
        video_shape=video_shape,
        num_audio_samples=args.audio_samples,
        num_audio_channels=args.audio_channels,
        num_classes=args.num_classes,
        batch_size=args.batch_size,
        synthetic=args.synthetic,
        synthetic_size=args.synthetic_size,
        seed=args.seed,
        shard_id=jax.process_index(),
        num_shards=jax.process_count(),
    )
    data.prepare_data()
    data.setup()

    model = build_multimodal_autoencoder(
        video_shape=video_shape,
        num_audio_samples=args.audio_samples,
        samples_per_patch=args.samples_per_patch,
        num_audio_channels=args.audio_channels,
        num_classes=data.num_classes,
        latent_shape=(args.num_latents, args.num_latent_channels),
        video_patch_shape=tuple(args.video_patch),
        num_layers=args.num_encoder_layers,
        num_self_attention_layers_per_block=args.num_self_attention_layers_per_block,
        num_cross_attention_heads=args.num_cross_attention_heads,
        num_self_attention_heads=args.num_self_attention_heads,
        num_modality_channels=args.num_modality_channels,
        video_frequency_bands=args.video_frequency_bands,
        audio_frequency_bands=args.audio_frequency_bands,
        dropout=args.dropout,
        dtype=common.DTYPES[args.dtype],
        attn_impl=args.attn_impl,
        remat=args.remat,
        reuse_kv=not getattr(args, "no_reuse_kv", False),
        video_patch_loss=args.video_patch_loss,
    )
    example = next(iter(data.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(args.seed)},
        {"video": example["video"][:1], "audio": example["audio"][:1]},
    )
    tx, schedule = common.optimizer_from_args(args)
    state = TrainState.create(variables["params"], tx, jax.random.key(args.seed + 2))
    state, resume_dir = common.resume_state(args, state)

    train_step, eval_step = make_multimodal_steps(
        model, schedule,
        video_weight=args.video_weight,
        audio_weight=args.audio_weight,
        label_weight=args.label_weight,
    )
    mesh = common.mesh_from_args(args)

    trainer = Trainer(
        train_step,
        eval_step,
        state,
        common.trainer_config(args),
        example_batch={k: example[k] for k in ("video", "audio", "label")},
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
