"""MLM pretraining entry point (reference ``train/train_mlm.py``).

Reproduces the reference CLI surface and per-task defaults
(``train_mlm.py:93-106``: 64 latents × 64 channels, 3 encoder layers,
512-token sequences, batch 64) plus the per-validation-epoch masked-token
top-k sample predictions logged as text (``train_mlm.py:38-56``), on the
TPU-native stack: SPMD mesh instead of DDP, Orbax checkpoints, bf16 compute.

Usage (mirroring the reference README):

    python train/train_mlm.py --dataset=imdb --experiment=mlm \
        --one_cycle_lr --learning_rate=3e-3 --max_steps=50000
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax
import numpy as np

from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.imdb import IMDBDataModule
from perceiver_io_tpu.data.tokenizer import MASK_TOKEN
from perceiver_io_tpu.training import TrainState, make_mlm_steps, mlm_gather_capacity
from perceiver_io_tpu.training.trainer import Trainer

DEFAULT_PREDICT_SAMPLES = (
    "i have watched this [MASK] and it was awesome",
    "this movie was [MASK] from start to finish",
)


# Width/compute DEFAULTS per --preset, applied post-parse by apply_preset:
# the parser defaults the affected args to None (a sentinel), so explicit
# flags, resume's hparams-as-defaults layering, and the preset compose
# without any dependence on global sys.argv. attn_impl 'xla' under
# flagship_tpu is the measured-best at TPU widths (models/presets.py
# flagship_tpu_mlm).
PRESET_DEFAULTS = {
    "reference": {"num_latents": 64, "num_latent_channels": 64,
                  "attn_impl": "auto"},
    "flagship_tpu": {"num_latents": 256, "num_latent_channels": 512,
                     "attn_impl": "xla"},
}


def apply_preset(args: argparse.Namespace) -> argparse.Namespace:
    """Fill any still-None width/compute args from the chosen preset."""
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
    g = parser.add_argument_group("task (MLM)")
    g.add_argument("--preset", choices=["reference", "flagship_tpu"],
                   default="reference",
                   help="model-width preset: 'reference' = the GPU-sized "
                        "train_mlm defaults (64 latents x 64 channels, head "
                        "depth 16); 'flagship_tpu' = the same recipe at "
                        "TPU-native widths (256 latents x 512 channels, head "
                        "depth 128 — models/presets.py flagship_tpu_mlm). "
                        "Explicit --num_latents/--num_latent_channels still "
                        "override the preset")
    g.add_argument("--num_predictions", "--predict_k", type=int, default=5,
                   help="top-k predictions logged per [MASK] position "
                        "(--predict_k is the reference's spelling)")
    g.add_argument("--predict_samples", nargs="*", default=list(DEFAULT_PREDICT_SAMPLES))
    g.add_argument("--loss_gather_capacity", type=int, default=-1,
                   help="decode only the masked positions, up to this many per "
                        "row (gradient-equivalent, skips most vocab-projection "
                        "FLOPs). -1 = auto (2·mask_p·seq_len), 0 = full decode")
    g.add_argument("--fused_head", choices=["auto", "pallas", "off"],
                   default="auto",
                   help="fuse the vocab projection into the CE so the "
                        "(B, K, V) logits never materialize: 'pallas' = the "
                        "flash-CE kernel, 'off' = unfused. auto = pallas "
                        "only on a single-device TPU mesh at 128 latent "
                        "channels or fewer (off under ANY multi-chip "
                        "sharding — dp/sp/tp — and on other backends)")
    # reference per-task defaults (train_mlm.py:93-106); the preset-affected
    # args default to the None sentinel apply_preset resolves
    parser.set_defaults(experiment="mlm", batch_size=64, num_latents=None,
                        num_latent_channels=None, attn_impl=None,
                        num_encoder_layers=3)
    return parser


def encode_masked_samples(collator, samples: Sequence[str]):
    """Encode raw strings containing the ``[MASK]`` literal
    (see :func:`perceiver_io_tpu.inference.encode_masked_texts`)."""
    from perceiver_io_tpu.inference import encode_masked_texts

    return encode_masked_texts(collator.tokenizer, samples, collator.max_seq_len)


def make_predict_hook(predict_fn, collator, samples: Sequence[str], k: int):
    """Sample-prediction channel (reference ``train_mlm.py:14-35,44-56``):
    no-masking forward, top-k over the ``[MASK]`` positions, decoded text."""
    if not samples:
        return None
    tokenizer = collator.tokenizer
    mask_id = tokenizer.token_to_id(MASK_TOKEN)
    token_ids, pad_mask = encode_masked_samples(collator, samples)
    jit_predict = jax.jit(predict_fn)
    # The hook logs top-k at the FIRST mask position per sample (reference
    # semantics), and the sample token ids are fixed for the whole run — so
    # decode exactly those positions instead of all max_seq_len: at long
    # context the full (B, L, vocab) logits would be a GB-scale fetch per
    # evaluation. Rows without a mask decode position 0 and are skipped.
    has_mask = (token_ids == mask_id).any(axis=1)
    first_mask = np.where(
        has_mask, (token_ids == mask_id).argmax(axis=1), 0
    ).astype(np.int32)[:, None]

    def hook(state, logger, step):
        logits = np.asarray(jax.device_get(
            jit_predict(state.params, token_ids, pad_mask, first_mask)
        ))
        lines = []
        for row in range(len(samples)):
            if not has_mask[row]:
                continue
            # top-k over the first mask position, as the reference logs
            top = np.argsort(-logits[row, 0])[:k]
            filled = [
                samples[row].replace(MASK_TOKEN, f"**{tokenizer.id_to_token(int(t))}**", 1)
                for t in top
            ]
            lines.append(samples[row] + "\n\n" + "\n".join(f"- {s}" for s in filled))
        if lines:
            logger.log_text("predictions", step, "\n\n---\n\n".join(lines))

    return hook


def build_trainer(args: argparse.Namespace, mesh=None):
    """Data module, model, optimizer state, mesh and the :class:`Trainer`
    for parsed (preset-applied) ``args`` — everything :func:`main` does
    short of fitting. Returns ``(trainer, data)``; ``chip_smoke.py`` inspects
    the compiled step of exactly the trainer the CLI would run. ``mesh``
    replaces the one the mesh flags describe (the flags always span every
    device; a one-device run on a four-chip host needs a mesh of one)."""
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

    model = common.build_mlm(args, vocab_size, args.max_seq_len)
    example = next(iter(data.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(args.seed), "masking": jax.random.key(args.seed + 1)},
        example["token_ids"][:1], example["pad_mask"][:1],
    )
    tx, schedule = common.optimizer_from_args(args)
    state = TrainState.create(variables["params"], tx, jax.random.key(args.seed + 2))
    state, resume_dir = common.resume_state(args, state)

    capacity = args.loss_gather_capacity
    if capacity < 0:
        capacity = mlm_gather_capacity(args.max_seq_len)
    if mesh is None:
        mesh = common.mesh_from_args(args)
    fused = args.fused_head
    if fused == "auto":
        # the flash-CE kernel is a single-device op (ops/pallas_ce.py):
        # auto enables it only on a single-device TPU mesh — under ANY
        # multi-chip sharding GSPMD cannot partition the pallas_call (it
        # would all-gather the gathered-decode features on every chip),
        # so sharded meshes keep the unfused head whose collectives GSPMD
        # manages. Explicit 'pallas' overrides for dp/sp (correct, possibly
        # slower); tp is rejected below (vocab sharding conflicts). The
        # width gate (C <= 128) was set on an earlier device, not measured
        # on the v5e (ROADMAP S6).
        fused = ("pallas" if jax.default_backend() == "tpu"
                 and mesh.size == 1
                 and args.num_latent_channels <= 128 else "off")
    elif fused == "pallas" and mesh.shape["model"] > 1:
        raise SystemExit(
            "--fused_head pallas is a single-device head; with --tp > 1 the "
            "vocab projection shards over the model axis — use auto or off"
        )
    train_step, eval_step, predict_fn = make_mlm_steps(
        model, schedule, loss_gather_capacity=capacity or None,
        fused_head={"pallas": "pallas", "off": False}[fused],
    )

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
        predict_hook=make_predict_hook(
            predict_fn, data.collator, args.predict_samples, args.num_predictions
        ),
        tokens_per_example=args.max_seq_len,
    )
    return trainer, data


def main(argv: Optional[Sequence[str]] = None):
    args = apply_preset(common.parse_with_resume(build_parser(), argv))
    if common.maybe_spawn_hosts(args, argv):
        return None  # training ran in the spawned processes
    configure_compile_cache()
    common.maybe_initialize_distributed(args)
    # after distributed init: the multi-host guard reads jax.process_count()
    common.validate_bucket_args(args)
    trainer, data = build_trainer(args)
    with trainer:
        common.run_fit(
            trainer, data.train_dataloader(), data.val_dataloader()
        )
    return trainer.run_dir


if __name__ == "__main__":
    main()
