"""Benchmark: MLM training-step throughput, printed as ONE JSON line.

Measures tokens/sec/chip for the reference train_mlm-equivalent hot loop
(IMDB config: 512-token sequences, 256 latents, 3 encoder layers × 6
self-attention layers per block, batch 64 — SURVEY.md §3.1 / BASELINE.md) on
one TPU chip. Without a TPU it exits non-zero with the reason on stderr and
prints no record: a CPU timing is not a result (run the CPU drives and the
tests for correctness; ``chip_smoke.py`` proves the program starts on the
chip).

Measurement: the value is the lower quartile of the per-step DEVICE
durations in a ``jax.profiler`` trace of a steady window
(``utils/benchmarking.time_train_step_device``). A host-clock
chained-window measurement (loss-scalar sync, 1-iter run subtracted, median
of 3 windows) is taken too and reported alongside as ``host_ms_per_step``;
it never stands in for the device number — a trace that cannot be captured
or parsed fails the run.

Env knobs: PIT_BENCH_STEPS / PIT_BENCH_BATCH override defaults;
PIT_BENCH_ATTN selects the attention impl ('xla' | 'pallas');
PIT_BENCH_GATHER sets the masked-decode capacity (-1 auto, 0 = reference-
shaped full decode); PIT_BENCH_HEAD selects the vocab head ('pallas' = the
fused flash-CE kernel, the default; 'none' = unfused). The compile cache
lives where ``aot.configure_compile_cache`` puts it
(``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.cache/jax``); compile time
never enters the measured window.
"""

from __future__ import annotations

import os
import sys

from perceiver_io_tpu.utils.jsonline import emit_json_line


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU chip; jax found {device.platform!r} "
            f"({device.device_kind}). No record is printed for another "
            "backend.")

    from perceiver_io_tpu.training import (
        OptimizerConfig,
        TrainState,
        make_mlm_steps,
        make_optimizer,
        mlm_gather_capacity,
    )

    vocab, seq_len = 10003, 512
    num_latents, channels = 256, 64
    batch_size = int(os.environ.get("PIT_BENCH_BATCH", "64"))
    steps = int(os.environ.get("PIT_BENCH_STEPS", "20"))
    compute_dtype = jnp.bfloat16
    attn_impl = os.environ.get("PIT_BENCH_ATTN", "xla")
    if attn_impl not in ("xla", "pallas"):
        raise SystemExit(
            f"PIT_BENCH_ATTN must be 'xla' or 'pallas', got {attn_impl!r}")
    gather = int(os.environ.get("PIT_BENCH_GATHER", "-1"))
    if gather < 0:
        gather = mlm_gather_capacity(seq_len)
    head = os.environ.get("PIT_BENCH_HEAD", "pallas")
    fused_head = {"pallas": "pallas", "none": False}.get(head)
    if fused_head is None:
        raise SystemExit(
            f"PIT_BENCH_HEAD must be 'pallas' or 'none', got {head!r}")

    from perceiver_io_tpu.models.presets import flagship_mlm

    model = flagship_mlm(
        vocab_size=vocab, max_seq_len=seq_len, num_latents=num_latents,
        num_channels=channels, dtype=compute_dtype, attn_impl=attn_impl,
    )

    rng = np.random.default_rng(0)
    batch = {
        "token_ids": jnp.asarray(
            rng.integers(3, vocab, (batch_size, seq_len)).astype(np.int32)
        ),
        "pad_mask": jnp.zeros((batch_size, seq_len), dtype=bool),
    }
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        batch["token_ids"], batch["pad_mask"],
    )
    tx, schedule = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    train_step, _, _ = make_mlm_steps(
        model, schedule, loss_gather_capacity=gather or None,
        fused_head=fused_head,
    )

    from perceiver_io_tpu.utils.benchmarking import (
        time_train_step,
        time_train_step_device,
    )

    jitted = jax.jit(train_step, donate_argnums=(0,))

    # the jitted step donates its state argument, so each measurement gets
    # its own copy
    fresh_state = lambda: jax.tree.map(jnp.copy, state)

    device_s, _, _ = time_train_step_device(
        train_step, fresh_state(), batch, steps, jitted=jitted
    )
    host_s, _ = time_train_step(
        train_step, fresh_state(), batch, steps, windows=3, jitted=jitted
    )

    # the jitted step runs on exactly one device (no sharding here), so
    # per-chip throughput is the total regardless of how many chips the
    # host exposes
    emit_json_line({
        "metric": "mlm_tokens_per_sec_per_chip",
        "value": round(batch_size * seq_len / device_s, 1),
        "unit": "tokens/s/chip",
        "method": "device_trace",
        "device_ms_per_step": round(device_s * 1e3, 3),
        "host_ms_per_step": round(host_s * 1e3, 3),
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
    })


if __name__ == "__main__":
    sys.exit(main())
