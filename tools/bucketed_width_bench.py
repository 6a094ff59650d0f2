"""Measure the bucketed-width text-batch win (VERDICT r2 item 8).

The reference pads each batch to its longest sequence (reference
``data/imdb.py:56-57`` ``enable_padding``), so short batches cost less than
512 tokens; this framework's static shapes pad everything to ``max_seq_len``.
The SPMD-safe middle ground is width buckets + length-sorted windows
(``Collator(bucket_widths=...)`` + ``DataLoader(sort_key=..., sort_window=``).

Method (device-clock): the win = Σ_w share(w) · step_time(w), with
- share(w): the fraction of an epoch's batches landing in each width bucket,
  counted by running the REAL data module (collator + window-sorted loader)
  over an IMDB-length-realistic corpus (log-normal word counts fit to the
  published IMDB profile: mean ≈ 230 words, median ≈ 175, ~20% truncated at
  512 wordpieces) — the real aclImdb tree is used instead when present;
- step_time(w): device-trace-measured train-step time compiled at each width
  (flagship MLM config, fused head), independent of host noise.

Prints per-bucket shares + device times and the bucketed-vs-static epoch
time ratio. Usage: ``timeout 900 python tools/bucketed_width_bench.py``.
"""

from __future__ import annotations

import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.platform import probe_backend

import numpy as np

BUCKETS = [256, 384]  # + the 512 cap appended by the Collator
SEQ_CAP = 512
BATCH = 64
VOCAB = 10003


def realistic_corpus(n: int, seed: int = 0):
    """Log-normal review lengths matching the published IMDB profile."""
    from perceiver_io_tpu.data.imdb import (
        _NEGATIVE_WORDS,
        _NEUTRAL_WORDS,
        _POSITIVE_WORDS,
    )

    rng = np.random.default_rng(seed)
    words = np.asarray(_POSITIVE_WORDS + _NEGATIVE_WORDS + _NEUTRAL_WORDS)
    lengths = np.clip(
        rng.lognormal(mean=np.log(175), sigma=0.72, size=n), 15, 2500
    ).astype(int)
    texts = [" ".join(rng.choice(words, size=k)) for k in lengths]
    labels = [int(rng.integers(0, 2)) for _ in range(n)]
    return texts, labels


def batch_width_shares(root: str) -> dict:
    """share(width) over one epoch of the bucketed module."""
    from perceiver_io_tpu.data import imdb as imdb_mod
    from perceiver_io_tpu.data.imdb import IMDBDataModule

    have_real = os.path.isdir(
        os.path.join(root, "IMDB", "aclImdb", "train")
    )
    dm = IMDBDataModule(
        root=root, max_seq_len=SEQ_CAP, vocab_size=VOCAB, batch_size=BATCH,
        synthetic=not have_real, synthetic_size=4096,
        bucket_widths=BUCKETS, length_sort_window=8,
    )
    if not have_real:
        # swap in the length-realistic generator (the stock synthetic corpus
        # is uniform 20-120 words — far shorter than IMDB)
        dm._train_texts = lambda: realistic_corpus(4096)  # type: ignore
    dm.prepare_data()
    dm.setup()
    counts: Counter = Counter()
    for b in dm.train_dataloader():
        counts[b["token_ids"].shape[1]] += 1
    total = sum(counts.values())
    return {w: c / total for w, c in sorted(counts.items())}


def device_step_ms(width: int) -> float:
    import jax
    import jax.numpy as jnp

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

    model = flagship_mlm(
        vocab_size=VOCAB, max_seq_len=SEQ_CAP, num_latents=256,
        num_channels=64, dtype=jnp.bfloat16, attn_impl="xla",
    )
    rng = np.random.default_rng(0)
    batch = {
        "token_ids": jnp.asarray(
            rng.integers(3, VOCAB, (BATCH, width)).astype(np.int32)),
        "pad_mask": jnp.zeros((BATCH, width), bool),
    }
    full = {
        "token_ids": jnp.asarray(
            rng.integers(3, VOCAB, (BATCH, SEQ_CAP)).astype(np.int32)),
        "pad_mask": jnp.zeros((BATCH, SEQ_CAP), bool),
    }
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        full["token_ids"], full["pad_mask"],
    )
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    head = "pallas" if probe_backend().backend == "tpu" else False
    train_step, _, _ = make_mlm_steps(
        model, sched, loss_gather_capacity=mlm_gather_capacity(SEQ_CAP),
        fused_head=head,
    )
    try:
        seconds, _, _ = time_train_step_device(train_step, state, batch, 15)
    except Exception:
        seconds, _ = time_train_step(train_step, state, batch, 10, windows=3)
    return seconds * 1e3


def eval_width_shares(root: str) -> dict:
    """share(width) over the EVAL split under the r5 eval width oracle
    (``val_dataloader``: sort_window=0, widths from the val token-length
    table — the reference's pad-to-longest eval behavior, SPMD-safe)."""
    from perceiver_io_tpu.data.imdb import IMDBDataModule

    have_real = os.path.isdir(os.path.join(root, "IMDB", "aclImdb", "train"))
    dm = IMDBDataModule(
        root=root, max_seq_len=SEQ_CAP, vocab_size=VOCAB, batch_size=BATCH,
        synthetic=not have_real, synthetic_size=4096,
        bucket_widths=BUCKETS, length_sort_window=8,
    )
    if not have_real:
        dm._train_texts = lambda: realistic_corpus(4096)  # type: ignore
        dm._valid_texts = lambda: realistic_corpus(4096, seed=3)  # type: ignore
    dm.prepare_data()
    dm.setup()
    counts: Counter = Counter()
    for b in dm.val_dataloader():
        counts[b["token_ids"].shape[1]] += 1
    total = sum(counts.values())
    return {w: c / total for w, c in sorted(counts.items())}


def device_eval_step_ms(width: int) -> float:
    """Device-trace eval (forward-only) step time at a width."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.models.presets import flagship_mlm
    from perceiver_io_tpu.training import (
        OptimizerConfig,
        TrainState,
        make_mlm_steps,
        make_optimizer,
        mlm_gather_capacity,
    )
    from perceiver_io_tpu.utils import xplane

    model = flagship_mlm(
        vocab_size=VOCAB, max_seq_len=SEQ_CAP, num_latents=256,
        num_channels=64, dtype=jnp.bfloat16, attn_impl="xla",
    )
    rng = np.random.default_rng(0)
    batch = {
        "token_ids": jnp.asarray(
            rng.integers(3, VOCAB, (BATCH, width)).astype(np.int32)),
        "pad_mask": jnp.zeros((BATCH, width), bool),
    }
    full = {
        "token_ids": jnp.asarray(
            rng.integers(3, VOCAB, (BATCH, SEQ_CAP)).astype(np.int32)),
        "pad_mask": jnp.zeros((BATCH, SEQ_CAP), bool),
    }
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        full["token_ids"], full["pad_mask"],
    )
    tx, _ = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    head = "pallas" if probe_backend().backend == "tpu" else False
    _, eval_step, _ = make_mlm_steps(
        model, loss_gather_capacity=mlm_gather_capacity(SEQ_CAP),
        fused_head=head,
    )
    jitted = jax.jit(eval_step)
    key = jax.random.key(9)
    float(jitted(state, batch, key)["loss"])  # compile
    td = tempfile.mkdtemp(prefix=f"evalw{width}_")
    with jax.profiler.trace(td):
        for i in range(12):
            with jax.profiler.StepTraceAnnotation("e", step_num=i):
                m = jitted(state, batch, key)
        float(m["loss"])
    sec, _ = xplane.device_step_seconds(td, skip_first=2)
    return sec * 1e3


def eval_main() -> None:
    shares = eval_width_shares(os.environ.get("PIT_ROOT", ".cache"))
    print("eval bucket shares (r5 width oracle, order preserved):",
          {w: f"{s:.1%}" for w, s in shares.items()}, file=sys.stderr)
    times = {w: device_eval_step_ms(w) for w in sorted(set(shares) | {SEQ_CAP})}
    for w, ms in times.items():
        print(f"  width {w}: {ms:.3f} ms/eval-step (device)", file=sys.stderr)
    bucketed = sum(shares[w] * times[w] for w in shares)
    static = times[SEQ_CAP]
    print(
        f"eval cost: bucketed {bucketed:.3f} ms/step avg vs static "
        f"{static:.3f} -> {static / bucketed:.3f}x "
        f"({(static / bucketed - 1) * 100:+.1f}% eval throughput)", file=sys.stderr)


def main() -> None:
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    if "--eval" in sys.argv:
        eval_main()
        return
    shares = batch_width_shares(os.environ.get("PIT_ROOT", ".cache"))
    print("bucket shares over one epoch:",
          {w: f"{s:.1%}" for w, s in shares.items()}, file=sys.stderr)

    times = {w: device_step_ms(w) for w in sorted(set(shares) | {SEQ_CAP})}
    for w, ms in times.items():
        print(f"  width {w}: {ms:.3f} ms/step (device)", file=sys.stderr)

    bucketed = sum(shares[w] * times[w] for w in shares)
    static = times[SEQ_CAP]
    print(
        f"epoch cost: bucketed {bucketed:.3f} ms/step avg vs static "
        f"{static:.3f} -> {static / bucketed:.3f}x "
        f"({(static / bucketed - 1) * 100:+.1f}% throughput)", file=sys.stderr)


if __name__ == "__main__":
    main()
