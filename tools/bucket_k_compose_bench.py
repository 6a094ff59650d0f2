"""A/B: bucketed widths x steps_per_dispatch compose.

Width buckets and ``steps_per_dispatch=K`` each cut trainer-loop time, but
they used to exclude each other. The trainer composes them (loader-
decided global widths + K-grouped same-width runs + the trainer's
flush-on-width-change stacker); this tool shows the wins STACK on hardware:

1. full-window fraction: over one epoch of the real bucketed module at
   ``group_size=K``, how many K-batch dispatch windows are full (the
   grouping's job — without it, width changes would flush nearly every
   window early and forfeit the dispatch amortization);
2. interleaved trainer A/B on the chip: ``Trainer.fit`` tokens/s with
   buckets x K=16 vs static-512 x K=16, run A/B/A/B in ONE process
   (same-process interleave), steady-state windows only (every shape
   compiled in a warmup epoch first).

Corpus: the same IMDB-length-realistic generator as
``bucketed_width_bench.py`` (log-normal fit to the published profile; the
real aclImdb tree is used instead when present).

Usage: ``timeout 1800 python tools/bucket_k_compose_bench.py``.
"""

from __future__ import annotations

import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.platform import probe_backend

import numpy as np

from bucketed_width_bench import BATCH, BUCKETS, SEQ_CAP, VOCAB, realistic_corpus

K = int(os.environ.get("PIT_COMPOSE_K", "16"))
STEPS = int(os.environ.get("PIT_COMPOSE_STEPS", "640"))


CORPUS = int(os.environ.get("PIT_COMPOSE_CORPUS", "16384"))


def make_module(root: str, buckets):
    from perceiver_io_tpu.data.imdb import IMDBDataModule

    have_real = os.path.isdir(os.path.join(root, "IMDB", "aclImdb", "train"))
    dm = IMDBDataModule(
        root=root, max_seq_len=SEQ_CAP, vocab_size=VOCAB, batch_size=BATCH,
        synthetic=not have_real, synthetic_size=CORPUS,
        bucket_widths=buckets, length_sort_window=8, dispatch_group=K,
    )
    if not have_real:
        dm._train_texts = lambda: realistic_corpus(CORPUS)  # type: ignore
        dm._valid_texts = lambda: realistic_corpus(256, seed=1)  # type: ignore
    dm.prepare_data()
    dm.setup()
    return dm


def window_stats(dm):
    """(full-window fraction, fraction of STEPS inside full windows) under
    the trainer's greedy flush-on-width-change stacker
    (Trainer._dispatch_batches)."""
    windows, run, prev = [], 0, None
    for b in dm.train_dataloader():
        w = b["token_ids"].shape[1]
        if run and (w != prev or run == K):
            windows.append(run)
            run = 0
        run += 1
        prev = w
    if run:
        windows.append(run)
    total = sum(windows) or 1
    return (
        sum(1 for w in windows if w == K) / max(len(windows), 1),
        sum(w for w in windows if w == K) / total,
    )


def trainer_rate(dm, label: str) -> float:
    """Median steady-state tokens/s over a fixed-step Trainer.fit run."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.models.presets import flagship_mlm
    from perceiver_io_tpu.training import (
        OptimizerConfig,
        TrainState,
        make_mlm_steps,
        make_optimizer,
        mlm_gather_capacity,
        read_metrics,
    )
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    model = flagship_mlm(
        vocab_size=dm.tokenizer.get_vocab_size(), max_seq_len=SEQ_CAP,
        dtype=jnp.bfloat16, attn_impl="xla",
    )
    example = next(iter(dm.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        example["token_ids"][:1], example["pad_mask"][:1],
    )
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    head = "pallas" if probe_backend().backend == "tpu" else False
    train_step, eval_step, _ = make_mlm_steps(
        model, sched, loss_gather_capacity=mlm_gather_capacity(SEQ_CAP),
        fused_head=head,
    )
    import tempfile

    logdir = tempfile.mkdtemp(prefix=f"compose_{label}_")
    cfg = TrainerConfig(
        max_steps=STEPS, log_every_n_steps=32, steps_per_dispatch=K,
        logdir=logdir, experiment=label, use_tensorboard=False,
        compute_mfu=False, async_checkpoint=False, max_to_keep=1,
    )
    trainer = Trainer(
        train_step, lambda s, b, k: eval_step(s, b, k), state, cfg,
        example_batch={k: example[k] for k in ("token_ids", "pad_mask")},
        tokens_per_example=SEQ_CAP,
    )
    with trainer:
        trainer.fit(dm.train_dataloader(), dm.val_dataloader())
    rows = read_metrics(trainer.run_dir)
    rates = [r["tokens_per_sec"] for r in rows if "tokens_per_sec" in r]
    # steady state: drop the first half (covers every per-shape compile)
    steady = rates[len(rates) // 2:] or rates
    return statistics.median(steady)


def _stacked_windows(dm):
    """The trainer's greedy flush-on-width-change stacking
    (Trainer._dispatch_batches), materialized: [(width, stacked_batch, k)].
    Collation and widths are exactly the composed loop's — only the dispatch
    site moves out here so each window can carry a StepTraceAnnotation."""
    windows, run, prev = [], [], None
    for b in dm.train_dataloader():
        w = b["token_ids"].shape[1]
        if run and (w != prev or len(run) == K):
            windows.append((prev, run))
            run = []
        run.append(b)
        prev = w
    if run:
        windows.append((prev, run))
    out = []
    for w, batches in windows:
        stacked = {
            key: np.stack([b[key] for b in batches])
            for key in ("token_ids", "pad_mask")
        }
        out.append((w, stacked, len(batches)))
    return out


def trace_ab(root: str) -> None:
    """Device-trace A/B of the composed bucketed K-loop vs static-512
    (VERDICT r4 item 4): per-dispatch device windows from the xplane Steps
    line, per-width LOWER-QUARTILE per-step durations over full windows,
    share-weighted by each width's true step share (partials included in the
    shares). Interleaved bucketed/static/bucketed/static in ONE process."""
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
    from perceiver_io_tpu.training.steps import make_scanned_step
    from perceiver_io_tpu.utils import xplane

    dm_b = make_module(root, BUCKETS)
    dm_s = make_module(root, None)

    model = flagship_mlm(
        vocab_size=dm_b.tokenizer.get_vocab_size(), max_seq_len=SEQ_CAP,
        dtype=jnp.bfloat16, attn_impl="xla",
    )
    example = next(iter(dm_b.val_dataloader()))
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        example["token_ids"][:1], example["pad_mask"][:1],
    )
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    head = "pallas" if probe_backend().backend == "tpu" else False
    train_step, _, _ = make_mlm_steps(
        model, sched, loss_gather_capacity=mlm_gather_capacity(SEQ_CAP),
        fused_head=head,
    )
    scanned = jax.jit(make_scanned_step(train_step), donate_argnums=(0,))

    def run_arm(windows, state, trace_dir):
        # warmup pass compiles every (width, k) program OUTSIDE the trace
        seen = set()
        for w, stacked, k in windows:
            if (w, k) not in seen:
                seen.add((w, k))
                state, _ = scanned(state, stacked)
        meta = []
        with jax.profiler.trace(trace_dir):
            for i, (w, stacked, k) in enumerate(windows):
                with jax.profiler.StepTraceAnnotation("win", step_num=i):
                    state, m = scanned(state, stacked)
                meta.append((w, k))
            float(m["loss"])  # sync inside the trace window
        spans = xplane.step_windows(xplane.load_tpu_plane(trace_dir))
        assert len(spans) == len(meta), (len(spans), len(meta))
        per_width: dict = {}
        shares: dict = {}
        for (w, k), (a, b) in zip(meta, spans):
            shares[w] = shares.get(w, 0) + k
            if k == K:  # LQ statistic over FULL windows only
                per_width.setdefault(w, []).append((b - a) / 1e12 / k)
        total = sum(shares.values())
        weighted = 0.0
        for w, share in shares.items():
            durs = sorted(per_width.get(w, []))
            if not durs:  # width with only partial windows — use all of them
                durs = sorted(
                    (b - a) / 1e12 / k
                    for (ww, k), (a, b) in zip(meta, spans) if ww == w
                )
            lq = durs[len(durs) // 4]
            weighted += lq * (share / total)
        return state, weighted, total, dict(
            (w, (s, sorted(per_width.get(w, [0]))[len(per_width.get(w, [0])) // 4]))
            for w, s in shares.items()
        )

    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    win_b = _stacked_windows(dm_b)
    win_s = _stacked_windows(dm_s)
    results = {"buckets": [], "static": []}
    for rep in range(2):
        for which, windows in (("buckets", win_b), ("static", win_s)):
            td = tempfile.mkdtemp(prefix=f"compose_trace_{which}{rep}_")
            state, weighted, steps, detail = run_arm(windows, state, td)
            results[which].append(weighted)
            wd = ", ".join(
                f"{w}: {s} steps @ {lq * 1e3:.2f} ms"
                for w, (s, lq) in sorted(detail.items())
            )
            print(f"  rep{rep} {which:8s}: share-weighted LQ "
                  f"{weighted * 1e3:.3f} ms/step over {steps} steps ({wd})",
                  flush=True, file=sys.stderr)
    b = statistics.median(results["buckets"])
    s = statistics.median(results["static"])
    print(
        f"device-trace composed A/B: bucketed {b * 1e3:.3f} vs static "
        f"{s * 1e3:.3f} ms/step -> {s / b:.3f}x ({(s / b - 1) * 100:+.1f}% "
        f"examples/s)", file=sys.stderr)


def main() -> None:
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    root = os.environ.get("PIT_ROOT", ".cache")
    dm_b = make_module(root, BUCKETS)
    frac, steps_frac = window_stats(dm_b)
    print(f"full {K}-batch windows with buckets {BUCKETS}+cap: {frac:.1%} "
          f"of windows, {steps_frac:.1%} of steps", file=sys.stderr)

    if "--trace-ab" in sys.argv:
        trace_ab(root)
        return

    dm_s = make_module(root, None)
    order = ["buckets", "static", "buckets", "static"]
    rates = {"buckets": [], "static": []}
    for which in order:
        dm = dm_b if which == "buckets" else dm_s
        r = trainer_rate(dm, which)
        rates[which].append(r)
        print(f"  {which:8s} K={K}: {r / 1e6:.3f}M tokens/s (trainer loop)", file=sys.stderr)
    b = statistics.median(rates["buckets"])
    s = statistics.median(rates["static"])
    print(
        f"composed win: bucketed {b / 1e6:.3f}M vs static {s / 1e6:.3f}M "
        f"tokens/s at K={K} -> {b / s:.3f}x ({(b / s - 1) * 100:+.1f}%)", file=sys.stderr)


if __name__ == "__main__":
    main()
