"""Developer tool: reproduce PERF.md's end-to-end config table.

Times one full train step (fwd+bwd+optimizer, donated state, honest sync —
see PERF.md's measurement discipline) for each BASELINE.md-tracked config on
the current backend. Usage:

    python tools/e2e_configs_bench.py [config ...]   # default: all

Configs: mlm, seqclf, mnist, imagenet, imagenet8h, flow, multimodal.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.platform import probe_backend

import jax
import jax.numpy as jnp
import numpy as np

import perceiver_io_tpu as pit
from perceiver_io_tpu.training import (
    OptimizerConfig,
    TrainState,
    make_classifier_steps,
    make_flow_steps,
    make_mlm_steps,
    make_multimodal_steps,
    make_optimizer,
    mlm_gather_capacity,
)

STEPS = int(os.environ.get("PIT_BENCH_STEPS", "10"))
DTYPE = jnp.bfloat16
# Force one attention impl across every config (e.g. 'xla' so XLA cost
# analysis sees ALL the flops — Pallas custom-calls count zero there; see
# tools/hbm_roofline.py's MFU method). Default: each config's own choice.
ATTN_IMPL = os.environ.get("PIT_E2E_ATTN")
rng = np.random.default_rng(0)


def _image_classifier(image_shape, num_classes, latents, channels, blocks,
                      cross_heads, self_heads, bands):
    attn = ATTN_IMPL or "auto"
    return pit.PerceiverIO(
        encoder=pit.PerceiverEncoder(
            input_adapter=pit.ImageInputAdapter(
                image_shape=image_shape, num_frequency_bands=bands, dtype=DTYPE
            ),
            latent_shape=(latents, channels),
            num_layers=1,
            num_cross_attention_heads=cross_heads,
            num_self_attention_heads=self_heads,
            num_self_attention_layers_per_block=blocks,
            dtype=DTYPE,
            attn_impl=attn,
        ),
        decoder=pit.PerceiverDecoder(
            output_adapter=pit.ClassificationOutputAdapter(
                num_classes=num_classes, num_output_channels=channels, dtype=DTYPE
            ),
            latent_shape=(latents, channels),
            num_cross_attention_heads=cross_heads,
            dtype=DTYPE,
            attn_impl=attn,
        ),
    )


def _mlm_config(model_factory, batch_size: int, default_head: str,
                seq: int = 512):
    """Shared MLM bench recipe (synthetic batch, gather decode, PIT_E2E_HEAD
    override: 'pallas'|'none' — 'none' also feeds hbm_roofline's
    MFU-numerator build, where cost analysis must see the head's flops;
    PIT_E2E_DEC_ATTN overrides the DECODER attention impl separately —
    the gather-decode cross is a many-queries/few-keys shape that can
    prefer a different path than the encoder's long-KV stream)."""
    vocab, b = 10003, batch_size
    model = model_factory(dtype=DTYPE, attn_impl=ATTN_IMPL or "xla",
                          max_seq_len=seq,
                          decoder_attn_impl=os.environ.get("PIT_E2E_DEC_ATTN"))
    batch = {
        "token_ids": jnp.asarray(rng.integers(3, vocab, (b, seq)).astype(np.int32)),
        "pad_mask": jnp.zeros((b, seq), bool),
    }
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        batch["token_ids"], batch["pad_mask"],
    )
    head = os.environ.get("PIT_E2E_HEAD", default_head)
    fused_head = {"pallas": "pallas", "none": False}[head]
    train_step, _, _ = make_mlm_steps(
        model, loss_gather_capacity=mlm_gather_capacity(seq),
        fused_head=fused_head,
    )
    return variables, train_step, batch, b


def config_mlm():
    """Flagship IMDB MLM (512 seq, 256x64 latents, 3x6 layers, batch 64).
    Matches bench.py's defaults (attn_impl='xla', gather decode, fused
    flash-CE head on TPU)."""
    from perceiver_io_tpu.models.presets import flagship_mlm

    default_head = "pallas" if probe_backend().backend == "tpu" else "none"
    return _mlm_config(flagship_mlm, 64, default_head)


def config_mlm_tpu():
    """The MLM recipe at TPU-native widths (C=512, head depth 128 — the
    ``flagship_tpu_mlm`` preset; everything else identical to config_mlm).
    PIT_MLM_TPU_BATCH overrides the batch (default 64, the reference's —
    b128 measured WORSE: 130.0 ms = 34.0% MFU vs b64's 53.6%). The UNFUSED
    head is the default here (roofline A/B, r4: unfused 41.26 ms / 53.6%
    MFU vs flash-CE 42.08 / 52.6% — the K=512-deep head matmuls are
    MXU-efficient, so saving the logits traffic no longer pays, unlike the
    d=16 flagship where the kernel is +6.1%)."""
    from perceiver_io_tpu.models.presets import flagship_tpu_mlm

    b = int(os.environ.get("PIT_MLM_TPU_BATCH", "64"))
    seq = int(os.environ.get("PIT_MLM_TPU_SEQ", "512"))
    return _mlm_config(flagship_tpu_mlm, b, "none", seq=seq)


def config_seqclf():
    """IMDB sequence classification (the transfer target: same text encoder
    as MLM, classification decoder; reference train_seq_clf.py defaults —
    batch 128, 64x64 latents, 1 decoder cross-attention head,
    reference ``train_seq_clf.py:56-68``)."""
    vocab, seq, b = 10003, 512, 128
    attn = ATTN_IMPL or "xla"
    model = pit.PerceiverIO(
        encoder=pit.PerceiverEncoder(
            input_adapter=pit.TextInputAdapter(
                vocab_size=vocab, max_seq_len=seq, num_channels=64, dtype=DTYPE
            ),
            latent_shape=(64, 64),
            num_layers=3,
            num_self_attention_layers_per_block=6,
            dtype=DTYPE,
            attn_impl=attn,
        ),
        decoder=pit.PerceiverDecoder(
            output_adapter=pit.ClassificationOutputAdapter(
                num_classes=2, num_output_channels=64, dtype=DTYPE
            ),
            latent_shape=(64, 64),
            num_cross_attention_heads=1,
            dtype=DTYPE,
            attn_impl=attn,
        ),
    )
    batch = {
        "token_ids": jnp.asarray(rng.integers(3, vocab, (b, seq)).astype(np.int32)),
        "pad_mask": jnp.zeros((b, seq), bool),
        "label": jnp.asarray(rng.integers(0, 2, b).astype(np.int32)),
    }
    variables = model.init(
        {"params": jax.random.key(0)}, batch["token_ids"],
        pad_mask=batch["pad_mask"],
    )
    train_step, _ = make_classifier_steps(model, input_kind="text")
    return variables, train_step, batch, b


def config_mnist():
    """MNIST recipe (28x28, 32x128 latents, 3 self-attn, batch 128)."""
    b = 128
    model = _image_classifier((28, 28, 1), 10, 32, 128, 3, 4, 4, 32)
    batch = {
        "image": jnp.asarray(rng.normal(0, 1, (b, 28, 28, 1)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, b).astype(np.int32)),
    }
    variables = model.init({"params": jax.random.key(0)}, batch["image"][:1])
    train_step, _ = make_classifier_steps(model, input_kind="image")
    return variables, train_step, batch, b


def _imagenet(cross_heads):
    b = 8
    model = _image_classifier((224, 224, 3), 1000, 512, 1024, 6, cross_heads, 8, 64)
    batch = {
        "image": jnp.asarray(rng.normal(0, 1, (b, 224, 224, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 1000, b).astype(np.int32)),
    }
    variables = model.init({"params": jax.random.key(0)}, batch["image"][:1])
    train_step, _ = make_classifier_steps(model, input_kind="image")
    return variables, train_step, batch, b


def config_imagenet():
    """ImageNet-1k paper config (224^2, 512x1024 latents, 1-head cross)."""
    return _imagenet(1)


def config_imagenet8h():
    """ImageNet-1k, 8-head cross variant (the fused-kernel showcase)."""
    return _imagenet(8)


def config_flow():
    """Sintel optical flow (368x496, 2048x512 latents, dense 2D queries)."""
    from perceiver_io_tpu.models.flow import build_optical_flow_model

    b = int(os.environ.get("PIT_FLOW_BATCH", "1"))
    model = build_optical_flow_model(dtype=DTYPE, attn_impl=ATTN_IMPL or "auto")
    batch = {
        "frames": jnp.asarray(rng.normal(0, 1, (b, 2, 368, 496, 3)), jnp.float32),
        "flow": jnp.asarray(rng.normal(0, 1, (b, 368, 496, 2)), jnp.float32),
    }
    variables = model.init({"params": jax.random.key(0)}, batch["frames"][:1])
    train_step, _ = make_flow_steps(model)
    return variables, train_step, batch, b


def config_multimodal():
    """Kinetics-style AV autoencoding (16x224^2 video + audio, 784x512).

    Defaults are the r4 measured-best (roofline sweep, device trace):
    batch 8 (b2 79.2 → b4 86.4 → b8 88.8 ex/s; b16 regresses to 85.7),
    remat OFF (recompute cost > saved traffic at this depth: 28.5 vs
    30.8 ms at b2/auto), attn 'xla' (the area-rule kernel routing LOSES,
    30.8 ms vs xla's 27.7 at b2 — overlap dilution, PERF.md negative (11)).
    PIT_MM_BATCH / PIT_MM_REMAT=1 / PIT_MM_PATCH_LOSS=1 (patch-space video
    reconstruction loss — exact, skips the un-patchify transposes) override."""
    from perceiver_io_tpu.models.multimodal import build_multimodal_autoencoder

    b = int(os.environ.get("PIT_MM_BATCH", "8"))
    video_shape = (16, 224, 224, 3)
    model = build_multimodal_autoencoder(
        video_shape=video_shape, num_audio_samples=30720, dtype=DTYPE,
        remat=os.environ.get("PIT_MM_REMAT", "0") != "0",
        attn_impl=ATTN_IMPL or "xla",
        video_patch_loss=os.environ.get("PIT_MM_PATCH_LOSS", "0") != "0",
    )
    batch = {
        "video": jnp.asarray(rng.normal(0, 1, (b, *video_shape)), jnp.float32),
        "audio": jnp.asarray(rng.normal(0, 1, (b, 30720, 1)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 700, b).astype(np.int32)),
    }
    variables = model.init(
        {"params": jax.random.key(0)},
        {"video": batch["video"][:1], "audio": batch["audio"][:1]},
    )
    train_step, _ = make_multimodal_steps(model)
    return variables, train_step, batch, b


CONFIGS = {
    "mlm": config_mlm,
    "mlm_tpu": config_mlm_tpu,
    "seqclf": config_seqclf,
    "mnist": config_mnist,
    "imagenet": config_imagenet,
    "imagenet8h": config_imagenet8h,
    "flow": config_flow,
    "multimodal": config_multimodal,
}


def run(name: str) -> None:
    from perceiver_io_tpu.utils import profiling
    from perceiver_io_tpu.utils.benchmarking import time_train_step

    variables, train_step, batch, batch_size = CONFIGS[name]()
    tx, _ = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    # ONE jit wrapper: the cost analysis below compiles it (before the state
    # is donated), and the timing loop reuses the same executable
    jitted = jax.jit(train_step, donate_argnums=(0,))
    flops = (profiling.compiled_flops(jitted, state, batch)
             if profiling.device_peak_flops() is not None else None)
    seconds, _ = time_train_step(
        train_step, state, batch, STEPS, windows=3, jitted=jitted
    )

    mfu_str = ""
    if flops:
        u = profiling.mfu(flops, seconds)
        if u is not None:
            mfu_str = f"   MFU {100 * u:5.1f}%"
    print(f"{name:12s} {seconds * 1e3:9.2f} ms/step   "
          f"{batch_size / seconds:8.1f} ex/s{mfu_str}", file=sys.stderr)


def main():
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    names = sys.argv[1:] or list(CONFIGS)
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:
        raise SystemExit(f"unknown configs {unknown}; pick from {sorted(CONFIGS)}")
    print(f"device: {probe_backend().device_kind}, {STEPS} steps per config", file=sys.stderr)
    for name in names:
        run(name)


if __name__ == "__main__":
    main()
