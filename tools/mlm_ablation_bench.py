"""Developer tool: where does the flagship MLM step's time go?

Times config ablations of the train step with the honest sync discipline
(PERF.md): chain donated state, fetch the loss scalar, subtract a 1-iter run.
Each row removes one component, so deltas attribute time to components:

  full         the bench step (3 layers x 6 self-attn, gather decode)
  full-decode  all 512 positions decoded (reference-shaped CE)
  no-decode    loss on latent mean instead of decoder+CE
  no-self      blocks of 1 self-attention layer (delta = the 15 removed layers)
  one-layer    num_layers=1 (no shared-layer recurrence)
  fwd-only     no backward/optimizer (forward + loss only)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.platform import probe_backend

import jax
import jax.numpy as jnp
import numpy as np

import perceiver_io_tpu as pit
from perceiver_io_tpu.ops.masking import TextMasking
from perceiver_io_tpu.training import (
    OptimizerConfig,
    TrainState,
    make_mlm_steps,
    make_optimizer,
    mlm_gather_capacity,
)

VOCAB, SEQ, NLAT, C = 10003, 512, 256, 64
BATCH = int(os.environ.get("PIT_BENCH_BATCH", "64"))
STEPS = int(os.environ.get("PIT_BENCH_STEPS", "20"))


def build(num_layers=3, blocks=6, attn_impl="xla"):
    latent_shape = (NLAT, C)
    return pit.PerceiverMLM(
        encoder=pit.PerceiverEncoder(
            input_adapter=pit.TextInputAdapter(
                vocab_size=VOCAB, max_seq_len=SEQ, num_channels=C,
                dtype=jnp.bfloat16,
            ),
            latent_shape=latent_shape,
            num_layers=num_layers,
            num_self_attention_layers_per_block=blocks,
            dtype=jnp.bfloat16,
            attn_impl=attn_impl,
        ),
        decoder=pit.PerceiverDecoder(
            output_adapter=pit.TextOutputAdapter(
                vocab_size=VOCAB, max_seq_len=SEQ, num_output_channels=C,
                dtype=jnp.bfloat16,
            ),
            latent_shape=latent_shape,
            dtype=jnp.bfloat16,
            attn_impl=attn_impl,
        ),
        masking=TextMasking(vocab_size=VOCAB, unk_token_id=1, mask_token_id=2,
                            num_special_tokens=3),
    )


def batch():
    rng = np.random.default_rng(0)
    return {
        "token_ids": jnp.asarray(rng.integers(3, VOCAB, (BATCH, SEQ)).astype(np.int32)),
        "pad_mask": jnp.zeros((BATCH, SEQ), dtype=bool),
    }


def time_step(step, state, b) -> float:
    for _ in range(3):
        state, metrics = step(state, b)
    float(metrics["loss"])

    def timed(n):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = step(state, b)
        float(metrics["loss"])
        return time.perf_counter() - t0

    t_one = timed(1)
    return (timed(STEPS + 1) - t_one) / STEPS


def standard(model, gather=True):
    b = batch()
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        b["token_ids"], b["pad_mask"],
    )
    tx, schedule = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    cap = mlm_gather_capacity(SEQ) if gather else None
    train_step, _, _ = make_mlm_steps(model, schedule, loss_gather_capacity=cap)
    return jax.jit(train_step, donate_argnums=(0,)), state, b


def no_decode_variant():
    """Loss = mean(latent²) — everything except decoder+CE."""
    model = build()
    b = batch()
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        b["token_ids"], b["pad_mask"],
    )
    tx, _ = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))

    def loss_fn(params, bb, rngs):
        latent = model.encoder.apply(
            {"params": params["encoder"]}, bb["token_ids"], bb["pad_mask"],
            rngs=rngs, deterministic=False,
        )
        return jnp.mean(jnp.square(latent.astype(jnp.float32)))

    def train_step(state, bb):
        rngs = state.step_rngs("masking", "dropout")
        loss, grads = jax.value_and_grad(loss_fn)(state.params, bb, rngs)
        return state.apply_gradients(grads), {"loss": loss}

    return jax.jit(train_step, donate_argnums=(0,)), state, b


def fwd_only_variant():
    model = build()
    b = batch()
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        b["token_ids"], b["pad_mask"],
    )
    tx, _ = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    cap = mlm_gather_capacity(SEQ)

    def train_step(state, bb):
        rngs = state.step_rngs("masking", "dropout")
        logits, labels = model.apply(
            {"params": state.params}, bb["token_ids"], bb["pad_mask"],
            rngs=rngs, deterministic=False, loss_gather_capacity=cap,
        )
        from perceiver_io_tpu.training.losses import cross_entropy_with_ignore
        loss = cross_entropy_with_ignore(logits, labels)
        # thread params through the carry so nothing is dead code
        return state.replace(step=state.step + 1), {"loss": loss}

    return jax.jit(train_step, donate_argnums=(0,)), state, b


def main():
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    print(f"device: {probe_backend().device_kind}, batch {BATCH}, {STEPS} steps", file=sys.stderr)
    rows = [
        ("full (bench default)", standard(build())),
        ("full-decode (no gather)", standard(build(), gather=False)),
        ("no-decode (encoder only)", no_decode_variant()),
        ("no-self-attn (blocks=1)", standard(build(blocks=1))),
        ("one-layer (no recurrence)", standard(build(num_layers=1))),
        ("fwd-only (no bwd/opt)", fwd_only_variant()),
    ]
    for name, (step, state, b) in rows:
        ms = time_step(step, state, b) * 1e3
        toks = BATCH * SEQ / (ms / 1e3)
        print(f"{name:28s} {ms:8.2f} ms/step   {toks/1e6:6.2f}M tokens/s", file=sys.stderr)


if __name__ == "__main__":
    main()
