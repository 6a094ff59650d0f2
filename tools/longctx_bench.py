"""Long-context MLM train-step bench: device-trace step time per (seq, batch).

Reproduces PERF.md's long-context family table (8k-131k tokens on one chip):
the flagship-MLM architecture at a longer ``max_seq_len``, bf16, auto
attention dispatch (→ the streaming fused kernel with auto-sized KV blocks at
these S), masked-position gather decode, and the flash-CE head. One line per
config:

    seq 32768 batch 4: 17.77 ms/step  7374577 tokens/s/chip

Usage: ``timeout 1800 python tools/longctx_bench.py [SEQ:BATCH ...]``
(default sweep = PERF.md's family table: 8192:8 32768:2 65536:1 131072:1;
the measured throughput PEAK is 32768:4). Timing discipline: the device
trace's lower-quartile step duration; off-TPU backends fall back to the
host-clock chained-window recipe and say so.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.platform import probe_backend

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_CONFIGS = ["8192:8", "32768:2", "65536:1", "131072:1"]


def main() -> None:
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    from perceiver_io_tpu.models.presets import flagship_mlm
    from perceiver_io_tpu.training import (
        OptimizerConfig,
        TrainState,
        make_mlm_steps,
        make_optimizer,
        mlm_gather_capacity,
    )
    from perceiver_io_tpu.utils.benchmarking import (
        time_train_step,
        time_train_step_device,
    )

    configs = sys.argv[1:] or DEFAULT_CONFIGS
    vocab = 10003
    rng = np.random.default_rng(0)
    on_tpu = probe_backend().backend == "tpu"
    for spec in configs:
        seq_len, batch = (int(x) for x in spec.split(":"))
        model = flagship_mlm(
            vocab_size=vocab, max_seq_len=seq_len, dtype=jnp.bfloat16
        )
        b = {
            "token_ids": jnp.asarray(
                rng.integers(3, vocab, (batch, seq_len)).astype(np.int32)
            ),
            "pad_mask": jnp.zeros((batch, seq_len), dtype=bool),
        }
        variables = model.init(
            {"params": jax.random.key(0), "masking": jax.random.key(1)},
            b["token_ids"], b["pad_mask"],
        )
        tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-3))
        state = TrainState.create(variables["params"], tx, jax.random.key(2))
        train_step, _, _ = make_mlm_steps(
            model, sched,
            loss_gather_capacity=mlm_gather_capacity(seq_len),
            # the flash-CE head is a TPU kernel; off-TPU interpret mode is
            # orders of magnitude slower than the unfused path
            fused_head="pallas" if on_tpu else False,
        )
        jitted = jax.jit(train_step, donate_argnums=(0,))
        if on_tpu:
            dev_s, _, _ = time_train_step_device(
                train_step, state, b, 12, jitted=jitted
            )
            method = "device_trace"
        else:
            dev_s, _ = time_train_step(
                train_step, state, b, 12, windows=3, jitted=jitted
            )
            method = "host_clock"
        print(
            f"seq {seq_len} batch {batch}: {dev_s * 1e3:7.3f} ms/step  "
            f"{batch * seq_len / dev_s:9.0f} tokens/s/chip  [{method}]", file=sys.stderr)


if __name__ == "__main__":
    main()
