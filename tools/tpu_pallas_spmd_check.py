"""Real-chip Pallas × SPMD check: run the fused kernel through a sharded
train step on an actual TPU mesh.

The CPU test suite proves the composition in interpreter mode
(``tests/test_sharding.py``); this tool proves the COMPILED kernel partitions
and executes under mesh shardings on hardware — a 1-device mesh with
``shard_seq=True`` (and dp/tp/sp factors when more chips are present),
``attn_impl='pallas'`` end to end, long-context shapes so the streaming
kernel path is the one exercised.

Usage: ``timeout 300 python tools/tpu_pallas_spmd_check.py [--seq 8192]``
Prints one summary line per configuration; non-zero exit on failure.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.platform import probe_backend

import numpy as np


def main() -> None:
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=8192)
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--steps", type=int, default=3)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.models.presets import flagship_mlm
    from perceiver_io_tpu.parallel import make_mesh, make_sharded_train_step
    from perceiver_io_tpu.training import (
        OptimizerConfig,
        TrainState,
        make_mlm_steps,
        make_optimizer,
        mlm_gather_capacity,
    )

    n = probe_backend().device_count
    print(f"backend={probe_backend().backend} devices={n}", file=sys.stderr)

    vocab, seq = 10003, args.seq
    model = flagship_mlm(
        vocab_size=vocab, max_seq_len=seq, num_latents=256, num_channels=64,
        dtype=jnp.bfloat16, attn_impl="pallas",
    )
    rng = np.random.default_rng(0)
    batch = {
        "token_ids": jnp.asarray(
            rng.integers(3, vocab, (args.batch, seq)).astype(np.int32)),
        "pad_mask": jnp.zeros((args.batch, seq), dtype=bool),
    }
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        batch["token_ids"], batch["pad_mask"],
    )
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    train_step, _, _ = make_mlm_steps(
        model, sched, loss_gather_capacity=mlm_gather_capacity(seq)
    )

    # every dp/tp/sp factorization the device count allows, always with the
    # seq axis present (shard_seq=True is the long-context claim under test)
    tp = 2 if n % 2 == 0 else 1
    sp = 2 if n % (tp * 2) == 0 else 1
    configs = [(n // (tp * sp), tp, sp)] if n > 1 else [(1, 1, 1)]
    for dp, tp, sp in configs:
        mesh = make_mesh(dp=dp, tp=tp, sp=sp)
        state = TrainState.create(variables["params"], tx, jax.random.key(2))
        step, sstate, bshard = make_sharded_train_step(
            train_step, mesh, state, batch, shard_seq=True
        )
        placed = jax.device_put(batch, bshard)
        loss = None
        for _ in range(args.steps):
            sstate, metrics = step(sstate, placed)
            loss = float(metrics["loss"])  # host fetch = the honest sync
        assert np.isfinite(loss), f"non-finite loss {loss}"
        print(
            f"OK mesh(data={dp}, model={tp}, seq={sp}) seq={seq} "
            f"attn=pallas loss={loss:.4f}", file=sys.stderr)


if __name__ == "__main__":
    main()
