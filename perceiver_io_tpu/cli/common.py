"""Shared CLI plumbing: layered argparse groups + model/optimizer builders.

Mirrors the reference's composed-parser pattern (each layer contributes an
argument group: model ``lightning.py:26-40``, optimizer ``lightning.py:50-57``,
data ``imdb.py:103-112`` / ``mnist.py:53-61``, Trainer flags, per-task
``set_defaults`` — reference ``train_mlm.py:80-106``), with TPU-specific
groups the reference has no analogue for: mesh construction (dp/tp/sp — the
DDP-flags replacement) and compute (dtype / attention impl / remat).
"""

from __future__ import annotations

import time

_IMPORT_START_NS = time.monotonic_ns()  # first statement: the `import` span's start

import argparse
import os
from typing import Optional, Tuple

import jax.numpy as jnp

import perceiver_io_tpu as pit
from perceiver_io_tpu import obs
from perceiver_io_tpu.ops.masking import TextMasking
from perceiver_io_tpu.parallel.mesh import make_mesh
from perceiver_io_tpu.training.optim import OptimizerConfig, make_optimizer
from perceiver_io_tpu.training.trainer import TrainerConfig

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# -- argument groups ---------------------------------------------------------


def add_model_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("model")
    g.add_argument("--num_latents", type=int, default=64)
    g.add_argument("--num_latent_channels", type=int, default=64)
    g.add_argument("--num_encoder_layers", type=int, default=3)
    g.add_argument("--num_self_attention_layers_per_block", type=int, default=6)
    g.add_argument("--num_cross_attention_heads", type=int, default=4)
    g.add_argument("--num_self_attention_heads", type=int, default=4)
    g.add_argument("--dropout", type=float, default=0.0)


def add_optimizer_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("optimizer")
    g.add_argument("--optimizer",
                   choices=("Adam", "AdamW", "SGD", "RMSprop", "Adagrad",
                            "Adamax", "NAdam", "RAdam"),
                   default="Adam",
                   help="torch.optim name (the reference resolves any name "
                        "via getattr; these are mapped to optax with torch's "
                        "exact update semantics)")
    g.add_argument("--learning_rate", type=float, default=1e-3)
    g.add_argument("--weight_decay", type=float, default=0.0)
    g.add_argument("--momentum", type=float, default=0.0,
                   help="SGD momentum (torch trace semantics; ignored by "
                        "other optimizers)")
    g.add_argument("--one_cycle_lr", action="store_true")
    g.add_argument("--one_cycle_pct_start", type=float, default=0.1)
    g.add_argument("--grad_clip_norm", type=float, default=None,
                   help="clip gradients to this global norm before the update")
    g.add_argument("--accumulate_steps", type=int, default=1,
                   help="average gradients over N micro-batches per optimizer "
                        "update (effective batch = N * batch_size)")


def add_trainer_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("trainer")
    g.add_argument("--max_epochs", type=int, default=None)
    g.add_argument("--max_steps", type=int, default=None)
    g.add_argument("--log_every_n_steps", type=int, default=50)
    g.add_argument("--eval_every_n_steps", type=int, default=None,
                   help="validate every N steps (default: once per epoch)")
    g.add_argument("--logdir", default="logs")
    g.add_argument("--experiment", default="default")
    g.add_argument("--max_to_keep", type=int, default=1)
    g.add_argument("--no_tensorboard", action="store_true")
    g.add_argument("--profile_steps", type=int, default=0,
                   help="capture a profiler trace of N OPTIMIZER steps after "
                        "warmup (a K-step dispatch advances it by K; keep "
                        "the window under a few seconds of device time — "
                        "longer windows can overflow the xplane export, "
                        "which the trainer now warns about)")
    g.add_argument("--steps_per_dispatch", type=int, default=1,
                   help="lax.scan N optimizer steps per device dispatch — "
                        "amortizes per-call host latency when steps are very "
                        "fast or the host is busy")
    g.add_argument("--selfprofile_every_n_steps", type=int, default=0,
                   help="in-loop device-trace watchdog: every N optimizer "
                        "steps capture a short jax.profiler trace, analyze "
                        "it in-process (utils/xplane.py lower quartile), and "
                        "log device/host step time + MFU + compile count as "
                        "registry gauges and metrics.jsonl rows (PERF.md "
                        "§Observability). 0 disables")
    g.add_argument("--selfprofile_steps", type=int, default=4,
                   help="dispatches per watchdog capture window")
    g.add_argument("--debug_nans", action="store_true",
                   help="NaN localization (sanitizer): enable jax_debug_nans "
                        "so the first dispatch producing NaN/Inf re-runs "
                        "de-optimized and raises at the originating op. "
                        "Slow (per-dispatch host sync, no state donation) — "
                        "for post-mortems; halt_on_nonfinite already detects "
                        "divergence in production")
    g.add_argument("--resume", default=None, metavar="RUN_DIR",
                   help="continue a previous run in place: restore the newest "
                        "checkpoint (the preemption last/ slot if present), "
                        "override model args from its hparams, and keep "
                        "logging into the same run directory")
    g.add_argument("--skip_nonfinite_steps", action="store_true",
                   help="self-healing: check the loss after EVERY dispatch "
                        "and SKIP a non-finite step (keep the pre-step state) "
                        "instead of letting NaN poison the moments; after "
                        "--rollback_after_bad_steps consecutive bad steps, "
                        "roll back to the newest checkpoint. Costs one host "
                        "sync per dispatch and disables state donation "
                        "(PERF.md §Reliability)")
    g.add_argument("--rollback_after_bad_steps", type=int, default=3,
                   help="with --skip_nonfinite_steps: consecutive bad steps "
                        "before rolling back to the newest checkpoint "
                        "(0 = skip only, never roll back)")
    g.add_argument("--dispatch_error_retries", type=int, default=0,
                   help="self-healing: retry a train dispatch that fails "
                        "with a TRANSIENT error (connection drop, PJRT "
                        "UNAVAILABLE — never divergence or shape bugs) with "
                        "exponential backoff, up to N times per step. "
                        "Implies the per-dispatch host sync. 0 disables")
    g.add_argument("--fit_attempts", type=int, default=1,
                   help="self-healing: total fit attempts — on a transient "
                        "failure that escapes the per-step retries, "
                        "auto-resume from the newest checkpoint "
                        "(fit_with_recovery supervisor). 1 = no supervisor")
    g.add_argument("--step_timeout_s", type=float, default=None,
                   help="bounded-exit deadline on the train dispatch cycle: "
                        "if no step completion is observed within this many "
                        "seconds (a dead peer wedging a collective), dump "
                        "thread stacks and exit with the transient code 75 "
                        "so --spawn_attempts supervision restarts the world "
                        "(resilience/multihost.py). Default: off")
    g.add_argument("--peer_heartbeat_s", type=float, default=0.0,
                   help="multi-host peer-liveness heartbeat cadence over the "
                        "jax.distributed KV store; a peer that stops beating "
                        "for 5 intervals is declared dead and this host "
                        "exits transient (75) instead of hanging in its "
                        "next collective. 0 = off")
    g.add_argument("--publish_dir", default=None, metavar="DIR",
                   help="continuous deployment (perceiver_io_tpu.deploy): "
                        "atomically publish the current params here every "
                        "--publish_every_n_steps steps, with a manifest "
                        "(step, val metrics, content digest) — the feed "
                        "serve.py --watch_checkpoints admission-gates and "
                        "hot-swaps into live serving. Fail-soft: a failed "
                        "publish warns, training continues")
    g.add_argument("--publish_every_n_steps", type=int, default=0,
                   help="publication cadence in optimizer steps (required "
                        "with --publish_dir)")


def add_mesh_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("mesh (the DDP-flags replacement)")
    g.add_argument("--dp", type=int, default=None,
                   help="data-parallel size (default: n_devices / (tp*sp))")
    g.add_argument("--tp", type=int, default=1, help="tensor-parallel size")
    g.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel size (shards the input axis M)")
    g.add_argument("--dcn_dp", type=int, default=1,
                   help="outer data-parallel factor placed across slice/host "
                        "(DCN) boundaries; must divide dp. The inner data "
                        "factor and tp/sp stay on each slice's ICI")
    g.add_argument("--shard_seq", action="store_true",
                   help="shard batches over the seq mesh axis: token axis for "
                        "text, first spatial axis for image/frames (must be "
                        "divisible by sp)")
    # mutually exclusive: both share dest zero_opt, and letting argparse's
    # last-flag-wins silently downgrade '--zero3 --zero' to opt-state-only
    # sharding would be a surprise (--zero3 already implies --zero)
    zg = g.add_mutually_exclusive_group()
    zg.add_argument("--zero", dest="zero_opt", action="store_true",
                    help="ZeRO-style optimizer-state sharding over the data "
                         "axis (per-chip Adam mu/nu footprint / dp)")
    zg.add_argument("--zero3", dest="zero_opt", action="store_const",
                    const="params",
                    help="ZeRO-3/FSDP flavor: PARAMS shard over the data axis "
                         "too (all-gather-on-use + reduce-scatter inserted by "
                         "GSPMD); implies --zero (mutually exclusive with it)")
    g.add_argument("--spawn_hosts", type=int, default=None, metavar="N",
                   help="one-command multi-process launch (the reference's "
                        "'--accelerator=ddp --gpus=-1' UX): fork N copies of "
                        "this exact command with the coordinator flags set "
                        "(localhost coordinator, CPU backend per child — a "
                        "dev/simulation helper; real TPU pods auto-detect "
                        "via --multihost with one launch per host)")
    g.add_argument("--spawn_attempts", type=int, default=1, metavar="K",
                   help="restart-the-world supervision for --spawn_hosts: "
                        "on ANY child death the launcher kills the whole "
                        "world, re-resolves a fresh coordinator port, and "
                        "relaunches all N hosts with --resume from the "
                        "newest digest-verified checkpoint, up to K total "
                        "world launches (capped backoff between restarts; a "
                        "crash loop of consecutive fast failures detaches "
                        "early). 1 = today's fail-fast behavior")
    g.add_argument("--elastic", action="store_true",
                   help="elastic supervision for --spawn_hosts (r23): a "
                        "child death no longer restarts the world — the "
                        "supervisor waits for the survivors to resize "
                        "in-process (resilience.elastic) and resume, only "
                        "falling back to restart-the-world when the live "
                        "count drops below --elastic_quorum or the elastic "
                        "progress file stops advancing. Worlds that made "
                        "step progress reset the --spawn_attempts budget")
    g.add_argument("--elastic_quorum", type=int, default=1, metavar="Q",
                   help="minimum live hosts for in-process resize under "
                        "--elastic; below it the supervisor restarts the "
                        "world (r19 behavior)")
    g.add_argument("--multihost", action="store_true",
                   help="call jax.distributed.initialize() before touching "
                        "devices (TPU pods auto-detect the coordinator); "
                        "without it every host trains independently")
    g.add_argument("--coordinator_address", default=None,
                   help="host:port of process 0, for clusters JAX cannot "
                        "auto-detect (implies --multihost)")
    g.add_argument("--num_processes", type=int, default=None)
    g.add_argument("--process_id", type=int, default=None)


def add_compute_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("compute")
    g.add_argument("--dtype", choices=sorted(DTYPES), default="bfloat16")
    g.add_argument("--attn_impl",
                   choices=("auto", "xla", "pallas", "pallas_sp"),
                   default="auto",
                   help="attention inner-product impl; auto picks the fused "
                        "Pallas kernel for long KV streams, XLA otherwise "
                        "(and routes the encoder cross-attention through the "
                        "sequence-parallel kernel when --sp > 1 and "
                        "--shard_seq are active); pallas_sp forces the kernel "
                        "path with that sp routing")
    g.add_argument("--remat", action="store_true",
                   help="rematerialize encoder layers (HBM for FLOPs). "
                        "Selective where it pays: a cross-attention that "
                        "materializes its logits over a long input (XLA "
                        "path, >= 4096 positions) keeps logits, weighted sum "
                        "and K/V while their bytes over all layers fit 40%% "
                        "of the device's memory; otherwise, and for every "
                        "other tensor, whole layers are recomputed")
    g.add_argument("--no_reuse_kv", action="store_true",
                   help="recompute the shared layer_n cross-attention K/V "
                        "projections per recurrent application instead of "
                        "caching them (the cache is exact and measured "
                        "faster — PERF.md r5; this is the off switch for "
                        "A/Bs and minimal-live-memory runs under a --remat "
                        "that recomputes whole layers)")
    g.add_argument("--pad_vocab_multiple", type=int, default=None,
                   help="round the vocab/class projection width up to this "
                        "multiple (padded logits pinned to -1e30) so it "
                        "divides the model mesh axis and tensor-shards under "
                        "--tp; applies to MLM and classifier heads")
    g.add_argument("--seed", type=int, default=0)


def add_imdb_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("data (IMDB)")
    # accepted for drop-in compatibility with the reference recipes
    # (README.md:33-38); imdb is the only text dataset either repo ships
    g.add_argument("--dataset", choices=("imdb",), default="imdb")
    g.add_argument("--root", default=".cache")
    g.add_argument("--max_seq_len", type=int, default=512)
    g.add_argument("--vocab_size", type=int, default=10003)
    g.add_argument("--batch_size", type=int, default=64)
    g.add_argument("--synthetic", action="store_true",
                   help="deterministic generated corpus (no downloads)")
    g.add_argument("--synthetic_size", type=int, default=2048)
    g.add_argument("--no_download", action="store_true",
                   help="fail fast if data is absent instead of fetching it")
    g.add_argument("--bucket_widths", type=int, nargs="+", default=None,
                   help="pad each batch to the smallest of these sequence "
                        "widths that fits it (SPMD-safe bucketed padding — "
                        "the reference's pad-to-longest without dynamic "
                        "shapes; one cached compile per width). Combine with "
                        "--length_sort_window. Composes with "
                        "--steps_per_dispatch (same-width batches are "
                        "grouped into K-runs so stacked windows never mix "
                        "widths) and with multi-host runs (the loader "
                        "decides each global batch's width from shared "
                        "token lengths, so hosts always agree); under "
                        "--shard_seq every width must divide --sp")
    g.add_argument("--length_sort_window", type=int, default=8,
                   help="with --bucket_widths: sort examples by length within "
                        "windows of this many batches so batches are "
                        "length-homogeneous (batch order re-shuffled inside "
                        "the window; 0 = off)")


def validate_bucket_args(args) -> None:
    """Cross-flag constraints for bucketed-width batches."""
    widths = getattr(args, "bucket_widths", None)
    if not widths:
        return
    # Multi-host and steps_per_dispatch now COMPOSE with buckets (r4,
    # VERDICT r3 item 2): the loader decides each global batch's width from
    # the shared token-length table (host-consistent by construction) and
    # arranges same-width batches in K-runs so stacked dispatch windows
    # never mix widths (data/pipeline.py group_widths/group_size).
    if getattr(args, "shard_seq", False):
        sp = getattr(args, "sp", 1)
        bad = [w for w in widths if w % sp]
        if bad:
            raise SystemExit(
                f"--bucket_widths {bad} not divisible by --sp {sp} "
                f"(seq-sharded batches need width % sp == 0)"
            )


def add_mnist_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("data (MNIST)")
    # accepted for drop-in compatibility with the reference recipes
    # (README.md:77-79)
    g.add_argument("--dataset", choices=("mnist",), default="mnist")
    g.add_argument("--root", default=".cache")
    g.add_argument("--batch_size", type=int, default=128)
    g.add_argument("--random_crop", type=int, default=None)
    g.add_argument("--synthetic", action="store_true")
    g.add_argument("--synthetic_size", type=int, default=4096)
    g.add_argument("--no_download", action="store_true",
                   help="fail fast if data is absent instead of fetching it")


# -- builders ----------------------------------------------------------------


def trainer_config(args) -> TrainerConfig:
    return TrainerConfig(
        max_epochs=args.max_epochs,
        max_steps=args.max_steps,
        log_every_n_steps=args.log_every_n_steps,
        eval_every_n_steps=args.eval_every_n_steps,
        logdir=args.logdir,
        experiment=args.experiment,
        max_to_keep=args.max_to_keep,
        use_tensorboard=not args.no_tensorboard,
        profile_steps=args.profile_steps,
        steps_per_dispatch=getattr(args, "steps_per_dispatch", 1),
        debug_nans=getattr(args, "debug_nans", False),
        selfprofile_every_n_steps=getattr(
            args, "selfprofile_every_n_steps", 0),
        selfprofile_steps=getattr(args, "selfprofile_steps", 4),
        skip_nonfinite_steps=getattr(args, "skip_nonfinite_steps", False),
        rollback_after_bad_steps=getattr(args, "rollback_after_bad_steps", 3),
        dispatch_error_retries=getattr(args, "dispatch_error_retries", 0),
        fit_attempts=getattr(args, "fit_attempts", 1),
        step_timeout_s=getattr(args, "step_timeout_s", None),
        peer_heartbeat_s=getattr(args, "peer_heartbeat_s", 0.0),
        publish_dir=getattr(args, "publish_dir", None),
        publish_every_n_steps=getattr(args, "publish_every_n_steps", 0),
    )


def run_fit(trainer, train_loader, val_loader=None):
    """Drive ``trainer.fit`` — through the ``fit_with_recovery`` supervisor
    whenever the config asks for more than one attempt (``--fit_attempts``),
    so every train CLI gets the auto-resume story from one switch."""
    if trainer.config.fit_attempts > 1:
        return trainer.fit_with_recovery(train_loader, val_loader)
    return trainer.fit(train_loader, val_loader)


def optimizer_from_args(args):
    return make_optimizer(
        OptimizerConfig(
            optimizer=args.optimizer,
            learning_rate=args.learning_rate,
            weight_decay=args.weight_decay,
            one_cycle_lr=args.one_cycle_lr,
            one_cycle_pct_start=args.one_cycle_pct_start,
            max_steps=args.max_steps,
            momentum=getattr(args, "momentum", 0.0),
            grad_clip_norm=getattr(args, "grad_clip_norm", None),
            accumulate_steps=getattr(args, "accumulate_steps", 1),
        )
    )


def mesh_from_args(args):
    mesh = make_mesh(dp=args.dp, tp=args.tp, sp=args.sp,
                     dcn_dp=getattr(args, "dcn_dp", 1))
    dp = mesh.shape["data"]
    if args.batch_size % dp != 0:
        raise SystemExit(
            f"batch_size {args.batch_size} must be divisible by the data-"
            f"parallel mesh axis ({dp}); pass --batch_size or --dp/--tp/--sp"
        )
    return mesh


def build_text_encoder(args, vocab_size: int, max_seq_len: int) -> pit.PerceiverEncoder:
    """TextInputAdapter + encoder (reference ``lightning.py:108-116``; the
    embedding width equals the latent channel count, as in the reference's
    north-star config)."""
    dtype = DTYPES[args.dtype]
    return pit.PerceiverEncoder(
        input_adapter=pit.TextInputAdapter(
            vocab_size=vocab_size,
            max_seq_len=max_seq_len,
            num_channels=args.num_latent_channels,
            dtype=dtype,
        ),
        latent_shape=(args.num_latents, args.num_latent_channels),
        num_layers=args.num_encoder_layers,
        num_cross_attention_heads=args.num_cross_attention_heads,
        num_self_attention_heads=args.num_self_attention_heads,
        num_self_attention_layers_per_block=args.num_self_attention_layers_per_block,
        dropout=args.dropout,
        dtype=dtype,
        attn_impl=args.attn_impl,
        remat=args.remat,
        reuse_kv=not getattr(args, "no_reuse_kv", False),
    )


def build_mlm(args, vocab_size: int, max_seq_len: int) -> pit.PerceiverMLM:
    """MLM model (reference ``lightning.py:108-120``)."""
    dtype = DTYPES[args.dtype]
    return pit.PerceiverMLM(
        encoder=build_text_encoder(args, vocab_size, max_seq_len),
        decoder=pit.PerceiverDecoder(
            output_adapter=pit.TextOutputAdapter(
                vocab_size=vocab_size,
                max_seq_len=max_seq_len,
                num_output_channels=args.num_latent_channels,
                dtype=dtype,
                pad_classes_to=getattr(args, "pad_vocab_multiple", None),
            ),
            latent_shape=(args.num_latents, args.num_latent_channels),
            num_cross_attention_heads=args.num_cross_attention_heads,
            dropout=args.dropout,
            dtype=dtype,
            attn_impl=args.attn_impl,
        ),
        masking=TextMasking(
            vocab_size=vocab_size, unk_token_id=1, mask_token_id=2,
            num_special_tokens=3,
        ),
    )


def build_ar(args, vocab_size: int, max_seq_len: int):
    """Perceiver-AR causal LM (the generative task preset surface —
    mirrors :func:`build_mlm`'s width knobs over ``models.presets``)."""
    dtype = DTYPES[args.dtype]
    return pit.PerceiverARLM(
        input_adapter=pit.TextInputAdapter(
            vocab_size=vocab_size,
            max_seq_len=max_seq_len,
            num_channels=args.num_latent_channels,
            dtype=dtype,
        ),
        output_adapter=pit.TextOutputAdapter(
            vocab_size=vocab_size,
            max_seq_len=max_seq_len,
            num_output_channels=args.num_latent_channels,
            dtype=dtype,
            pad_classes_to=getattr(args, "pad_vocab_multiple", None),
        ),
        num_latents=args.num_latents,
        num_layers=args.num_encoder_layers,
        num_self_attention_layers_per_block=args.num_self_attention_layers_per_block,
        num_cross_attention_heads=args.num_cross_attention_heads,
        num_self_attention_heads=args.num_self_attention_heads,
        dropout=args.dropout,
        dtype=dtype,
        attn_impl=args.attn_impl,
    )


def build_text_classifier(args, vocab_size: int, max_seq_len: int,
                          num_classes: int = 2) -> pit.PerceiverIO:
    """Sequence classifier (reference ``lightning.py:186-200``)."""
    dtype = DTYPES[args.dtype]
    return pit.PerceiverIO(
        encoder=build_text_encoder(args, vocab_size, max_seq_len),
        decoder=pit.PerceiverDecoder(
            output_adapter=pit.ClassificationOutputAdapter(
                num_classes=num_classes,
                num_output_channels=args.num_latent_channels,
                dtype=dtype,
                pad_classes_to=getattr(args, "pad_vocab_multiple", None),
            ),
            latent_shape=(args.num_latents, args.num_latent_channels),
            num_cross_attention_heads=args.num_cross_attention_heads,
            dropout=args.dropout,
            dtype=dtype,
            attn_impl=args.attn_impl,
        ),
    )


def build_image_classifier(
    args, image_shape: Tuple[int, ...], num_classes: int,
    num_frequency_bands: int = 32,
) -> pit.PerceiverIO:
    """Image classifier (reference ``lightning.py:222-244``)."""
    dtype = DTYPES[args.dtype]
    return pit.PerceiverIO(
        encoder=pit.PerceiverEncoder(
            input_adapter=pit.ImageInputAdapter(
                image_shape=tuple(image_shape),
                num_frequency_bands=num_frequency_bands,
                dtype=dtype,
            ),
            latent_shape=(args.num_latents, args.num_latent_channels),
            num_layers=args.num_encoder_layers,
            num_cross_attention_heads=args.num_cross_attention_heads,
            num_self_attention_heads=args.num_self_attention_heads,
            num_self_attention_layers_per_block=args.num_self_attention_layers_per_block,
            dropout=args.dropout,
            dtype=dtype,
            attn_impl=args.attn_impl,
            remat=args.remat,
            reuse_kv=not getattr(args, "no_reuse_kv", False),
        ),
        decoder=pit.PerceiverDecoder(
            output_adapter=pit.ClassificationOutputAdapter(
                num_classes=num_classes,
                num_output_channels=args.num_latent_channels,
                dtype=dtype,
                pad_classes_to=getattr(args, "pad_vocab_multiple", None),
            ),
            latent_shape=(args.num_latents, args.num_latent_channels),
            num_cross_attention_heads=args.num_cross_attention_heads,
            dropout=args.dropout,
            dtype=dtype,
            attn_impl=args.attn_impl,
        ),
    )


MODEL_HPARAM_KEYS = (
    "num_latents", "num_latent_channels", "num_encoder_layers",
    "num_self_attention_layers_per_block", "num_cross_attention_heads",
    "num_self_attention_heads", "vocab_size", "max_seq_len",
)


def override_model_args(args, hparams: dict) -> None:
    """Overwrite shape-determining model args from a checkpoint's embedded
    hparams so a restored encoder fits (reference ``load_from_checkpoint``
    rebuilds the model from saved hyperparameters, ``lightning.py:46``)."""
    for key in MODEL_HPARAM_KEYS:
        if key in hparams:
            setattr(args, key, hparams[key])


def maybe_spawn_hosts(args, argv=None) -> bool:
    """Reference-style one-command multi-process launch (``--spawn_hosts N``).

    Lightning's ``--accelerator=ddp --gpus=-1`` spawns per-device processes
    from a single invocation (reference ``train_mlm.py:102-103``); the JAX
    equivalent normally needs one launch per process with coordinator flags
    (CLAUDE.md multi-host recipe). This dev helper closes the UX gap: it
    re-executes this command N times with
    ``--coordinator_address localhost:PORT --num_processes N --process_id R``
    appended and ``JAX_PLATFORMS=cpu`` in each child's env (a simulation
    harness — real TPU pods auto-detect the coordinator via ``--multihost``,
    one launch per host). Returns True when this process acted as the
    launcher (training ran in the children; the caller should return), False
    when training should proceed in-process. Child failure raises
    ``SystemExit`` with the first non-zero return code.

    The child command: for CLI invocations (``argv is None``) the children
    re-run ``sys.executable sys.argv[0]``. For PROGRAMMATIC calls —
    ``main(explicit_argv)`` from a library/REPL/pytest, where ``sys.argv[0]``
    is whatever binary happens to be running and must NOT be re-executed with
    training flags — the children run ``python -m <calling cli module>``
    instead (the module is read from the caller's frame).

    The coordinator port is picked bind-then-close, which leaves a TOCTOU
    window where another process can grab it before rank 0's
    ``jax.distributed`` service binds. A stolen port makes the children fail
    during init, well before training starts — so a launch whose first
    failure lands within ``_SPAWN_RETRY_WINDOW_S`` is retried (fresh port,
    same command) up to two more times before the failure is reported.

    Supervision (``--spawn_attempts K``, r19): the launch runs under a
    :class:`WorldSupervisor` — any child death kills the surviving world,
    the supervisor re-resolves a fresh coordinator port, and relaunches all
    N hosts with ``--resume`` pointing at the newest resumable run (the one
    whose restore will be digest-verified by ``restore_train_state``), with
    capped backoff between restarts and a crash-loop detach after
    consecutive fast failures. ``K=1`` (the default) keeps the historical
    fail-fast behavior.
    """
    import sys

    n = getattr(args, "spawn_hosts", None)
    if not n or n <= 1 or getattr(args, "process_id", None) is not None:
        return False
    base = list(sys.argv[1:] if argv is None else argv)
    child_argv, skip = [], False
    for a in base:
        if skip:
            skip = False
            continue
        if a in ("--spawn_hosts", "--spawn_attempts"):
            skip = True  # drop the launcher-only flag and its value
        elif a.startswith(("--spawn_hosts=", "--spawn_attempts=")):
            pass
        else:
            child_argv.append(a)
    if argv is None:
        target = [sys.executable, sys.argv[0]]
    else:
        caller_mod = sys._getframe(1).f_globals.get("__name__")
        if caller_mod and caller_mod != "__main__":
            target = [sys.executable, "-m", caller_mod]
        else:
            # a script's own main(argv) — its file path is still the command
            target = [sys.executable, sys.argv[0]]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if len(target) == 3:
        # `-m` children must resolve the package even when the parent
        # imported it from a path not on the default sys.path
        import perceiver_io_tpu

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(perceiver_io_tpu.__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")

    progress_probe = None
    if getattr(args, "elastic", False):
        from perceiver_io_tpu.resilience.elastic import (
            progress_path, read_progress)

        proot = getattr(args, "logdir", None) or "."
        progress_probe = lambda: read_progress(progress_path(proot))  # noqa: E731
    supervisor = WorldSupervisor(
        launch=lambda resume_dir: _launch_world(
            target, child_argv, env, n, resume_dir),
        n=n,
        attempts=getattr(args, "spawn_attempts", 1) or 1,
        find_resume=lambda: _newest_resumable_run(
            getattr(args, "logdir", None), getattr(args, "experiment", None)),
        elastic=getattr(args, "elastic", False),
        quorum=getattr(args, "elastic_quorum", 1) or 1,
        progress_probe=progress_probe,
    )
    supervisor.run()
    return True


def _pick_coordinator_port() -> int:
    import socket

    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch_world(target, child_argv, env, n, resume_dir=None):
    """Start all N ranks of one world on a fresh coordinator port. Returns
    ``(procs, logs)`` — rank 0 inherits stdout/stderr (it owns
    logging/checkpoints); the others write to temp files — NEVER undrained
    pipes, which fill the OS buffer once a child emits ~64KB and deadlock
    the whole cluster — replayed only on failure.

    ``resume_dir`` (world restarts) appends ``--resume`` AFTER the user's
    argv, so argparse's last-wins gives the supervisor's choice precedence
    over any ``--resume`` the original command carried.
    """
    import subprocess
    import sys
    import tempfile

    port = _pick_coordinator_port()
    extra = ["--resume", str(resume_dir)] if resume_dir else []
    procs, logs = [], []
    for rank in range(n):
        cmd = [*target, *child_argv, *extra,
               "--coordinator_address", f"localhost:{port}",
               "--num_processes", str(n), "--process_id", str(rank)]
        if rank == 0:
            out, log = None, None
        else:
            log = tempfile.NamedTemporaryFile(
                mode="w+", prefix=f"spawn_hosts_rank{rank}_", suffix=".log",
                delete=False,
            )
            out = log
        logs.append(log)
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=out,
            stderr=subprocess.STDOUT if rank else None, text=True,
        ))
    print(f"--spawn_hosts: launched {n} processes "
          f"(coordinator localhost:{port})"
          + (f", resuming {resume_dir}" if resume_dir else ""),
          file=sys.stderr)
    return procs, logs


def _newest_resumable_run(logdir, experiment):
    """The newest ``version_N`` run dir under ``logdir/experiment`` holding
    both embedded hparams and at least one committed checkpoint step (main
    slot or the preemption ``last/`` slot) — i.e. a dir ``--resume`` will
    accept and ``restore_train_state`` will digest-verify. None when the
    world died before its first checkpoint (restart fresh instead)."""
    import re

    if not logdir or not experiment:
        return None
    base = os.path.join(logdir, experiment)
    try:
        names = os.listdir(base)
    except OSError:
        return None
    versions = []
    for name in names:
        m = re.fullmatch(r"version_(\d+)", name)
        if m:
            versions.append((int(m.group(1)), name))
    for _, name in sorted(versions, reverse=True):
        run = os.path.join(base, name)
        ckpt = os.path.join(run, "checkpoints")
        if not os.path.isfile(os.path.join(ckpt, "hparams.json")):
            continue
        for slot in (ckpt, os.path.join(ckpt, "last")):
            try:
                entries = os.listdir(slot)
            except OSError:
                continue
            for entry in entries:
                if entry.isdigit() and os.path.exists(
                    os.path.join(slot, entry, "_CHECKPOINT_METADATA")
                ):
                    return run
    return None


class WorldSupervisor:
    """Elastic restart-the-world supervision over one ``--spawn_hosts`` job.

    One ``run()`` call owns the whole job lifetime: launch a world, watch
    every child, and on ANY child death kill the survivors and relaunch all
    N ranks from the newest resumable checkpoint — the process-level twin of
    the serving tier's ``ReplicaSupervisor`` (r12), except that multi-host
    training cannot restart one rank (its peers' collectives reference the
    dead one's program), so the restart unit is the WORLD.

    Injectable collaborators keep the policy tier-1-testable with fake
    children: ``launch(resume_dir) -> (procs, logs)`` where each proc
    exposes ``poll/terminate/kill/wait``; ``find_resume() -> run_dir|None``;
    ``sleep`` for the backoff. Three failure disciplines compose:

    - **port-race retry** (pre-existing): a fast failure with connect/bind
      evidence in a child log relaunches on a fresh port WITHOUT consuming
      a supervision attempt (bounded by ``_SPAWN_PORT_RETRIES`` per world);
    - **world restart**: up to ``attempts`` total world launches, capped
      exponential backoff between them, ``spawn_world_restarts_total``
      counting actuations;
    - **crash-loop detach**: ``_CRASHLOOP_LIMIT`` consecutive worlds dying
      within ``_CRASHLOOP_WINDOW_S`` of launch abandon the job early with
      the last exit code — a deterministic failure must not burn the whole
      attempt budget at backoff cadence.

    The chaos hook ``spawn.child_exit`` fires once per watch poll; an
    injected raise is treated as an observed child death (simulated-failure
    drills restart real worlds without killing real processes).
    """

    def __init__(self, launch, n, attempts=1, find_resume=None,
                 poll_s=0.2, backoff=None, sleep=None, reap_wait_s=10.0,
                 elastic=False, quorum=1, progress_probe=None,
                 elastic_grace_s=30.0):
        import time as _time

        import perceiver_io_tpu.obs as obs
        from perceiver_io_tpu.resilience import RetryPolicy

        self._launch = launch
        self.n = int(n)
        self.attempts = max(1, int(attempts))
        self._find_resume = find_resume or (lambda: None)
        self._poll_s = poll_s
        self._backoff = backoff or RetryPolicy(
            max_retries=self.attempts, base_s=1.0, multiplier=2.0, max_s=30.0)
        self._sleep = sleep or _time.sleep
        self._reap_wait_s = reap_wait_s
        # r23 elastic supervision: a child death is first offered to the
        # in-process resize path (resilience.elastic) — the supervisor only
        # restarts the world below the quorum floor or when the elastic
        # progress file stops advancing within the grace window.
        self.elastic = bool(elastic)
        self.quorum = max(1, int(quorum))
        self._progress_probe = progress_probe or (lambda: None)
        self._elastic_grace_s = elastic_grace_s
        self._m_restarts = obs.get_registry().counter(
            "spawn_world_restarts_total",
            "whole-world relaunches after a child death under "
            "--spawn_attempts supervision")
        self._m_absorbed = obs.get_registry().counter(
            "spawn_elastic_absorbed_total",
            "child deaths absorbed by an in-process elastic resize "
            "instead of a world restart (--elastic)")
        self.procs = []  # the CURRENT world, for the signal handlers

    # -- plumbing ------------------------------------------------------------

    def _reap(self) -> None:
        import subprocess

        live = [p for p in self.procs if p.poll() is None]
        for p in live:
            p.terminate()
        for p in live:
            try:
                p.wait(timeout=self._reap_wait_s)
            except subprocess.TimeoutExpired:
                p.kill()
                # wait out the SIGKILL too: the NEXT world must never
                # overlap a dying one (zombie reaping, port/file handles,
                # and CPU contention during its successor's compile)
                try:
                    p.wait(timeout=self._reap_wait_s)
                except subprocess.TimeoutExpired:
                    pass

    def _watch(self):
        """Poll until the world succeeds (-> None) or any child dies
        (-> (rank|None, rc)); rank None marks an injected simulated death."""
        import time as _time

        from perceiver_io_tpu.resilience import faults

        live = list(range(self.n))
        while live:
            try:
                # chaos hook: a raise simulates an observed child death
                faults.inject("spawn.child_exit")
            except Exception as e:
                import sys

                print(f"--spawn_hosts: injected child death "
                      f"({type(e).__name__})", file=sys.stderr)
                return None, 1
            for r in list(live):
                rc = self.procs[r].poll()
                if rc is not None:
                    live.remove(r)
                    if rc != 0:
                        if not self.elastic:
                            return r, rc
                        if len(live) < self.quorum:
                            import sys

                            print(f"--spawn_hosts: rank {r} died (rc={rc}) "
                                  f"and {len(live)} live < quorum "
                                  f"{self.quorum} — restarting the world",
                                  file=sys.stderr)
                            return r, rc
                        if not self._await_elastic_resume(r, rc):
                            return r, rc
            if live:
                _time.sleep(self._poll_s)
        return None

    # -- elastic absorption (r23) --------------------------------------------

    @staticmethod
    def _progress_key(progress):
        """Orderable identity of an elastic progress record (None = none)."""
        if not progress:
            return None
        return (progress.get("generation", -1), progress.get("step", -1),
                progress.get("wall", 0.0))

    def _await_elastic_resume(self, rank, rc) -> bool:
        """Give the survivors the grace window to resize in-process and
        advance the elastic progress file past its pre-death value. True =
        the death was absorbed (keep watching); False = restart the world."""
        import sys
        import time as _time

        import perceiver_io_tpu.obs as obs

        before = self._progress_key(self._progress_probe())
        print(f"--spawn_hosts --elastic: rank {rank} died (rc={rc}); "
              f"waiting up to {self._elastic_grace_s:.0f}s for the "
              "survivors to resize in-process", file=sys.stderr)
        deadline = _time.monotonic() + self._elastic_grace_s
        while _time.monotonic() < deadline:
            now = self._progress_key(self._progress_probe())
            if now is not None and now != before and (
                    before is None or now > before):
                self._m_absorbed.inc()
                obs.event("spawn_elastic_absorbed", rank=rank, rc=rc,
                          generation=now[0], step=now[1])
                print(f"--spawn_hosts --elastic: survivors resumed at "
                      f"generation {now[0]} step {now[1]} — death absorbed, "
                      "no world restart", file=sys.stderr)
                return True
            self._sleep(self._poll_s)
        print("--spawn_hosts --elastic: no elastic progress within the "
              "grace window — falling back to restart-the-world",
              file=sys.stderr)
        return False

    def _replay_log(self, logs, rank, label="") -> bool:
        """Dump a failed rank's captured output tail to stderr; returns
        whether there was a log to replay (rank 0 streams directly)."""
        import sys

        if rank is None or rank >= len(logs) or logs[rank] is None:
            return False
        logs[rank].flush()
        logs[rank].seek(0)
        print(f"--- rank {rank} output{label} ---\n"
              f"{logs[rank].read()[-4000:]}", file=sys.stderr)
        return True

    def _close_logs(self, logs, keep=None) -> None:
        """Close every log handle; delete all but ``keep``'s (kept for
        post-mortem) so repeated dev runs don't litter /tmp."""
        for rank, log in enumerate(logs):
            if log is None:
                continue
            log.close()
            if rank != keep:
                try:
                    os.unlink(log.name)
                except OSError:
                    pass

    # -- the supervision loop ------------------------------------------------

    def run(self) -> None:
        """Supervise to completion; raises SystemExit on final failure."""
        import signal

        # the launcher must never outlive-orphan its children:
        # SIGTERM/SIGINT (Ctrl-C, `timeout`, a scheduler preemption) reaps
        # the current world before exiting
        prev_handlers = {}

        def _on_signal(signum, frame):
            self._reap()
            raise SystemExit(128 + signum)

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(sig, _on_signal)
            except ValueError:
                pass  # non-main thread (programmatic use) — skip handlers
        try:
            self._run_supervised()
        finally:
            for sig, h in prev_handlers.items():
                signal.signal(sig, h)

    def _run_supervised(self) -> None:
        import sys
        import time as _time

        launches = 0          # FAILED worlds counted against the budget
        port_retries = 0      # per-world coordinator-port retries
        fast_failures = 0     # consecutive crash-loop candidates
        resume_dir = None
        while True:
            self.procs, logs = self._launch(resume_dir)
            started = _time.monotonic()
            progress_at_launch = self._progress_key(self._progress_probe())
            failed = self._watch()
            if failed is None:
                self._close_logs(logs)
                return
            rank, rc = failed
            self._reap()
            elapsed = _time.monotonic() - started
            # A world that demonstrably made step progress (elastic rejoins
            # reaching a clean boundary, or plain long productive training)
            # earns back the FULL attempt budget: this failure is
            # independent of the ones that consumed earlier attempts.
            progress_now = self._progress_key(self._progress_probe())
            if (progress_now is not None
                    and progress_now != progress_at_launch
                    and (progress_at_launch is None
                         or progress_now > progress_at_launch)
                    and (launches or fast_failures)):
                print(f"--spawn_hosts: world made step progress "
                      f"(generation {progress_now[0]} step {progress_now[1]})"
                      " — resetting the supervision attempt budget",
                      file=sys.stderr)
                launches = 0
                fast_failures = 0
            # Port-race retry ONLY with evidence of a coordinator bring-up
            # problem in some child's log — a deterministic fast failure
            # (bad flag, import error) must surface immediately, not be
            # retried with a misleading race diagnostic. Doesn't consume a
            # supervision attempt (hence counted before `launches` moves).
            if (elapsed < _SPAWN_RETRY_WINDOW_S
                    and port_retries < _SPAWN_PORT_RETRIES
                    and _logs_show_coordination_failure(logs)):
                port_retries += 1
                print(
                    f"--spawn_hosts: rank {rank} failed (rc={rc}) within "
                    f"{_SPAWN_RETRY_WINDOW_S:.0f}s with connect/bind "
                    "errors in the child logs — likely a coordinator-port "
                    "race; retrying with a fresh port",
                    file=sys.stderr,
                )
                # show the evidence on EVERY retry (ADVICE r5): if this is
                # actually a deterministic failure that happens to match a
                # connect/bind marker, the user sees the real error now
                self._replay_log(logs, rank, f" (retry {port_retries})")
                self._close_logs(logs)
                continue
            port_retries = 0
            launches += 1
            out_of_attempts = launches >= self.attempts
            crash_loop = False
            if elapsed < _CRASHLOOP_WINDOW_S:
                fast_failures += 1
                crash_loop = fast_failures >= _CRASHLOOP_LIMIT
            else:
                fast_failures = 0
            if out_of_attempts or crash_loop:
                replayed = self._replay_log(logs, rank)
                if replayed:
                    print(f"(full rank-{rank} log kept at "
                          f"{logs[rank].name})", file=sys.stderr)
                self._close_logs(logs, keep=rank)
                if crash_loop and not out_of_attempts:
                    print(
                        f"--spawn_hosts: {fast_failures} consecutive worlds "
                        f"died within {_CRASHLOOP_WINDOW_S:.0f}s of launch — "
                        f"crash loop, detaching with "
                        f"{self.attempts - launches} attempt(s) unused",
                        file=sys.stderr,
                    )
                raise SystemExit(rc)
            self._replay_log(logs, rank)
            self._close_logs(logs)
            self._m_restarts.inc()
            resume_dir = self._find_resume()
            pause = self._backoff.backoff_s(launches)
            print(
                f"--spawn_hosts: world attempt {launches}/{self.attempts} "
                f"failed ({'injected' if rank is None else f'rank {rank}'} "
                f"rc={rc}); restarting all {self.n} hosts in {pause:.1f}s"
                + (f" with --resume {resume_dir}" if resume_dir
                   else " fresh (no checkpoint yet)"),
                file=sys.stderr,
            )
            import perceiver_io_tpu.obs as obs

            obs.event("spawn_world_restart", attempt=launches, rc=rc,
                      rank=rank, resume_dir=resume_dir,
                      backoff_s=round(pause, 3))
            if pause > 0:
                self._sleep(pause)


# Children that die this quickly never started training — a candidate for
# the coordinator bring-up retry (e.g. the picked port got stolen), taken
# only when the child logs actually show coordination/bind errors.
_SPAWN_RETRY_WINDOW_S = 20.0
_SPAWN_PORT_RETRIES = 2

# Crash-loop detach (--spawn_attempts supervision): this many CONSECUTIVE
# worlds dying within the window of their launch abandon the job early — a
# deterministic failure (shape bug, poisoned checkpoint) must not burn the
# whole attempt budget at backoff cadence while looking like recovery.
_CRASHLOOP_WINDOW_S = 15.0
_CRASHLOOP_LIMIT = 3

# Signatures of a failed jax.distributed bring-up in a child's output —
# CONNECT/BIND-specific only (ADVICE r5): broad markers like
# 'jax.distributed.initialize' or bare 'unavailable:' also appear in
# deterministic init-failure tracebacks (bad --num_processes arithmetic,
# plugin errors), which must surface immediately rather than be retried
# twice under a misleading port-race diagnostic.
_COORDINATION_ERROR_MARKERS = (
    "address already in use",
    "failed to connect",
    "connection refused",
    "bind address",
)


def _logs_show_coordination_failure(logs) -> bool:
    """True when any child's captured output tail matches a distributed-
    bring-up failure signature (case-insensitive)."""
    for log in logs:
        if log is None:
            continue
        try:
            log.flush()
            log.seek(0, os.SEEK_END)
            size = log.tell()
            log.seek(max(0, size - 8000))
            tail = log.read().lower()
        except (OSError, ValueError):
            continue
        if any(m in tail for m in _COORDINATION_ERROR_MARKERS):
            return True
    return False


def maybe_initialize_distributed(args) -> None:
    """Multi-host bring-up, gated on ``--multihost``. MUST run before any
    device access (first use initializes the local-only backend)."""
    wants_distributed = (
        getattr(args, "multihost", False)
        or getattr(args, "coordinator_address", None) is not None
        or getattr(args, "num_processes", None) is not None
        or getattr(args, "process_id", None) is not None
    )
    if wants_distributed:
        from perceiver_io_tpu.parallel import initialize_distributed

        try:
            initialize_distributed(
                coordinator_address=getattr(args, "coordinator_address", None),
                num_processes=getattr(args, "num_processes", None),
                process_id=getattr(args, "process_id", None),
            )
        except (ValueError, RuntimeError) as e:
            raise SystemExit(
                f"--multihost: jax.distributed.initialize failed ({e}). On a "
                "TPU pod the coordinator is auto-detected; elsewhere pass "
                "--coordinator_address host:port --num_processes N "
                "--process_id I on every process, or drop the flag for "
                "single-host runs."
            ) from e
        import sys

        import jax

        print(
            f"[distributed] process {jax.process_index()}/"
            f"{jax.process_count()}, {jax.local_device_count()} local "
            f"device(s)", file=sys.stderr,
        )


def parse_with_resume(parser: argparse.ArgumentParser, argv):
    """Parse, and when ``--resume RUN_DIR`` is set, re-parse with the resumed
    run's embedded hparams installed as the parser's defaults.

    Every arg of the original run — model shapes, data shapes, optimizer
    structure (``accumulate_steps`` changes the opt_state pytree!) — comes
    back automatically, while flags given explicitly on THIS command line
    still win (so ``--resume RUN --max_steps 100000`` extends the schedule).
    ``--resume`` itself is never taken from hparams."""
    args = parser.parse_args(argv)
    if not getattr(args, "resume", None):
        return args
    from perceiver_io_tpu.training.checkpoint import load_hparams

    try:
        hparams = load_hparams(os.path.join(args.resume, "checkpoints"))
    except (FileNotFoundError, NotADirectoryError):
        raise SystemExit(_nothing_to_resume(args.resume)) from None
    known = vars(args)
    # environment/bring-up flags describe where THIS invocation runs, not the
    # training recipe — never inherit them from the original run (store_true
    # flags have no --no_* spelling to override with)
    env_flags = {"resume", "multihost", "coordinator_address", "num_processes",
                 "process_id", "dp", "tp", "sp", "shard_seq", "zero_opt",
                 # launcher topology/supervision describe THIS invocation
                 "spawn_hosts", "spawn_attempts", "elastic", "elastic_quorum",
                 # local paths: never inherit across hosts/invocations
                 "publish_dir", "publish_every_n_steps"}
    defaults = {
        k: v for k, v in hparams.items() if k in known and k not in env_flags
    }
    parser.set_defaults(**defaults)
    args = parser.parse_args(argv)
    args.resume = os.path.abspath(known["resume"])
    return args


def _nothing_to_resume(path: str) -> str:
    return (
        f"--resume {path}: no usable checkpoint under {path}/checkpoints — "
        f"the run was probably interrupted before its first checkpoint "
        f"(nothing to resume from; start fresh without --resume), or the "
        f"path is not a run directory (expected the version_N dir "
        f"containing checkpoints/)."
    )


def resume_state(args, state):
    """After building the fresh TrainState: restore the newest checkpoint of
    the ``--resume`` run (preferring the preemption ``last/`` slot). Returns
    ``(state, run_dir)`` — ``run_dir`` is the resumed directory (so logging
    and checkpoints continue in place) or None for a fresh run."""
    if not getattr(args, "resume", None):
        return state, None
    from perceiver_io_tpu.training.checkpoint import restore_train_state

    try:
        state = restore_train_state(
            os.path.join(args.resume, "checkpoints"), state, prefer_latest=True
        )
    except (FileNotFoundError, NotADirectoryError):
        # hparams.json is written at Trainer CONSTRUCTION, so a run killed
        # between construction and its first checkpoint save passes the
        # parse_with_resume guard but has no checkpoint steps to restore
        raise SystemExit(_nothing_to_resume(args.resume)) from None
    return state, args.resume


# last statement: this module's imports (the package, the Trainer's, orbax's own span inside)
obs.add_span("import", _IMPORT_START_NS, time.monotonic_ns(),
             module="perceiver_io_tpu.cli.common")
