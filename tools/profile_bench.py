"""Profiling helper: MFU + per-impl timing for the flagship MLM step.

Not part of the library API — a developer tool. Computes compiled-graph FLOPs
via XLA cost analysis and reports model FLOPs utilisation against the chip's
peak, for each attention impl.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.platform import probe_backend

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.training import (
    OptimizerConfig,
    TrainState,
    make_mlm_steps,
    make_optimizer,
    mlm_gather_capacity,
)

def build(attn_impl: str):
    from perceiver_io_tpu.models.presets import flagship_mlm

    return flagship_mlm(dtype=jnp.bfloat16, attn_impl=attn_impl)


def run(attn_impl: str, batch_size=64, steps=20, gather=None):
    model = build(attn_impl)
    rng = np.random.default_rng(0)
    batch = {
        "token_ids": jnp.asarray(rng.integers(3, 10003, (batch_size, 512)).astype(np.int32)),
        "pad_mask": jnp.zeros((batch_size, 512), dtype=bool),
    }
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        batch["token_ids"], batch["pad_mask"],
    )
    tx, schedule = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    train_step, _, _ = make_mlm_steps(model, schedule, loss_gather_capacity=gather)
    step = jax.jit(train_step, donate_argnums=(0,))

    from perceiver_io_tpu.utils import profiling

    flops = profiling.compiled_flops(step, state, batch) or 0.0

    from perceiver_io_tpu.utils.benchmarking import time_train_step

    dt, _ = time_train_step(train_step, state, batch, steps, windows=3, jitted=step)

    toks = batch_size * 512 / dt
    u = profiling.mfu(flops, dt)
    mfu_str = f"  MFU {100 * u:.1f}%" if u is not None else ""
    tag = f"{attn_impl}+g{gather}" if gather else attn_impl
    print(f"{tag:12s} step {dt*1e3:7.2f} ms  {toks/1e6:6.2f} Mtok/s  "
          f"flops/step {flops/1e9:.1f} G{mfu_str}", file=sys.stderr)


if __name__ == "__main__":
    from perceiver_io_tpu.aot import configure_compile_cache
    from perceiver_io_tpu.utils import profiling

    configure_compile_cache()

    peak = profiling.device_peak_flops()
    peak_str = f", peak {peak/1e12:.0f} TF/s" if peak else " (no known peak: MFU off)"
    print(f"device: {probe_backend().device_kind}{peak_str}", file=sys.stderr)
    cap = mlm_gather_capacity(512)
    for impl in ("xla", "pallas"):
        run(impl)
        run(impl, gather=cap)
