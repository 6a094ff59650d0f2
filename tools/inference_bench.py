"""Inference/serving-layer hardware bench (VERDICT r4 item 6).

The serving layer (``inference/predictor.py`` bucketed ``Predictor``,
``inference/mlm.py`` ``fill_masks`` gathered decode, ``inference/export.py``
StableHLO export) is a beyond-the-reference capability (the reference has no
serve/export path — SURVEY.md §3.4), so the bar is internal consistency:
every capability claim carries hardware numbers. This tool measures, on the
real chip:

1. ``fill_masks`` end-to-end latency at batch 1 / 8 / 64 — the HOST medians
   (what a caller of this process sees: tokenize, dispatch, the device
   round trip, top-k decode) AND the device-trace per-call compute time
   (lower-quartile per-step device window — the device's own clock).
2. Bucket-padding overhead on the gathered-decode forward (the realistic
   serving path — small outputs): a 5-text request padded to the 8-bucket vs
   a native 8-text request (same compiled program) vs a dedicated
   exact-shape jit at 5 (what bucketing trades away to keep steady-state
   serving recompile-free).
3. Exported-StableHLO vs live-jit dispatch on the same forward: steady-state
   per-call latency and device time, plus each path's time-to-first-result
   (the artifact's ahead-of-time selling point).

Sync discipline: device completion is forced by fetching a SCALAR slice of
every output leaf (a leaf nobody consumes could be dead code to the
compiler). ``fill_masks``/``Predictor`` already fetch their numpy results,
which is the same sync.

Prints a human table and ONE final JSON summary line on stdout (this is a
tools/ bench — bench.py's one-line stdout contract is untouched).

``--engine`` (VERDICT r5 weak #5/#6 closed): the SERVING-ENGINE bench — an
interleaved same-process A/B of the continuous micro-batcher
(``inference/engine.py``) against naive per-request ``Predictor`` dispatch on
a batch-1 request stream, plus request-latency percentiles per batch bucket
and (on TPU) per-micro-batch device-trace percentiles. Emits exactly ONE
JSON line on stdout (human progress goes to stderr) so the driver can track
an inference trajectory alongside ``bench.py``. ``--cpu`` pins the run to
the CPU backend via ``ensure_cpu_only()`` BEFORE jax initializes — tier-1
exercises the full path offline with ``--preset tiny``.

Usage::

    timeout 1800 python tools/inference_bench.py [--trace-dir DIR]
                                                 [--dtype float32|bfloat16]
    timeout 1800 python tools/inference_bench.py --engine [--cpu]
        [--preset auto|tiny|flagship] [--requests N] [--rounds R]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.jsonline import emit_json_line
from perceiver_io_tpu.utils.platform import probe_backend

# NOTE: jax is imported inside main() AFTER --cpu is handled —
# utils.platform.ensure_cpu_only must run before any backend initializes.
import numpy as np


def _consume(out) -> None:
    """Honest completion: a scalar slice of each output leaf is computed
    on-device (dependent on the full result) and fetched to the host."""
    import jax

    for leaf in jax.tree_util.tree_leaves(out):
        idx = (0,) * getattr(leaf, "ndim", 0)
        np.asarray(leaf[idx] if idx else leaf)


def _median_latency(fn, reps: int = 20, warmup: int = 3) -> float:
    """Median host wall-clock seconds per call. Serving latency: the host
    round trip is part of what a caller experiences — no subtraction; the
    device trace carries the compute time alongside."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _device_per_call(fn, trace_dir: str, calls: int = 12):
    """Lower-quartile device seconds per call, each call wrapped in a
    StepTraceAnnotation so the xplane Steps line carries per-call windows.
    Returns None off-TPU or when the trace has no device plane — the host
    medians still stand on their own."""
    import jax

    from perceiver_io_tpu.utils import xplane

    fn()  # compiled before tracing
    try:
        with jax.profiler.trace(trace_dir):
            for i in range(calls):
                with jax.profiler.StepTraceAnnotation("serve", step_num=i):
                    fn()
        sec, _ = xplane.device_step_seconds(trace_dir, skip_first=2)
        return sec
    except Exception as e:
        print(f"  (device trace unavailable: {type(e).__name__}: "
              f"{str(e)[:80]})", file=sys.stderr)
        return None


def _ms(sec) -> str:
    return f"{sec * 1e3:.3f}" if sec is not None else "—"


def _build_predictor(dtype_name: str):
    """Flagship-shaped MLM + a real first-party tokenizer over a synthetic
    Zipf corpus (zero-egress environment: no downloads)."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.data.tokenizer import (
        create_tokenizer,
        train_tokenizer,
    )
    from perceiver_io_tpu.inference.mlm import MLMPredictor
    from perceiver_io_tpu.models.presets import flagship_mlm

    rng = np.random.default_rng(0)
    # enough word TYPES that the trainer actually reaches the full 10003
    # vocab (the head cost scales with vocab — keep it representative)
    words = [f"w{i}" for i in range(16000)]
    probs = 1.0 / np.arange(1, len(words) + 1)
    probs /= probs.sum()
    corpus = [
        " ".join(rng.choice(words, size=150, p=probs)) for _ in range(1200)
    ]
    tokenizer = create_tokenizer()
    train_tokenizer(tokenizer, corpus, vocab_size=10003)
    vocab = tokenizer.get_vocab_size()

    max_seq_len = 512
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    model = flagship_mlm(
        vocab_size=vocab, max_seq_len=max_seq_len, dtype=dtype,
        attn_impl="auto",
    )
    ids = np.zeros((1, max_seq_len), np.int32)
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        ids, ids == 0,
    )
    predictor = MLMPredictor(
        model, variables["params"], tokenizer, max_seq_len, max_batch=64
    )
    texts = [
        f"the {tokenizer.id_to_token(10 + i)} movie was [MASK] and the plot "
        "felt [MASK] overall" for i in range(64)
    ]
    return predictor, texts, model, variables["params"], vocab, max_seq_len


def _build_engine_model(tiny: bool, dtype_name: str):
    """Model + tokenizer for the engine A/B: flagship-shaped on TPU, a
    scaled-down twin for the CPU (tier-1) run — same code path, minutes not
    hours."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.data.tokenizer import create_tokenizer, train_tokenizer
    from perceiver_io_tpu.models.presets import flagship_mlm, tiny_mlm

    rng = np.random.default_rng(0)
    n_words, vocab_target, doc_words, docs = (
        (800, 503, 40, 200) if tiny else (16000, 10003, 150, 1200)
    )
    words = [f"w{i}" for i in range(n_words)]
    probs = 1.0 / np.arange(1, len(words) + 1)
    probs /= probs.sum()
    corpus = [
        " ".join(rng.choice(words, size=doc_words, p=probs))
        for _ in range(docs)
    ]
    tokenizer = create_tokenizer()
    train_tokenizer(tokenizer, corpus, vocab_size=vocab_target)
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    build = tiny_mlm if tiny else flagship_mlm
    max_seq_len = 64 if tiny else 512
    model = build(
        vocab_size=tokenizer.get_vocab_size(), max_seq_len=max_seq_len,
        dtype=dtype, attn_impl="auto",
    )
    ids = np.zeros((1, max_seq_len), np.int32)
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        ids, ids == 0,
    )
    return model, variables["params"], tokenizer, max_seq_len


def _percentiles(values) -> dict:
    v = sorted(values)
    pick = lambda q: v[min(len(v) - 1, int(q * len(v)))]
    return {"p50_ms": round(pick(0.50) * 1e3, 3),
            "p95_ms": round(pick(0.95) * 1e3, 3)}


def _engine_mode(args) -> None:
    """Interleaved engine-vs-naive A/B on a batch-1 request stream.

    Both arms run the identical gathered serving forward; the engine's only
    edge is what it claims — coalescing the stream into bucketed
    micro-batches with pipelined dispatch. Same process, alternating rounds
    (drift of the shared host cancels between the arms)."""
    import jax

    from perceiver_io_tpu.inference import Predictor, ServingEngine
    from perceiver_io_tpu.inference.mlm import encode_masked_texts

    log = lambda *a: print(*a, file=sys.stderr)
    backend = probe_backend().backend
    tiny = args.preset == "tiny" or (args.preset == "auto" and backend != "tpu")
    log(f"backend: {backend}; preset {'tiny' if tiny else 'flagship'}; "
        f"dtype {args.dtype}; {args.requests} requests x {args.rounds} rounds")
    model, params, tokenizer, max_seq_len = _build_engine_model(
        tiny, args.dtype
    )

    # batch-1 request stream: every text carries two [MASK] slots (the
    # fill-mask serving shape), identical signature so the A/B isolates
    # batching — width bucketing has its own tests/bench
    texts = [
        f"the {tokenizer.id_to_token(10 + (i % 40))} movie was [MASK] and "
        f"felt [MASK] overall" for i in range(args.requests)
    ]
    ids, pad = encode_masked_texts(tokenizer, texts, max_seq_len)
    positions = np.zeros((len(texts), 2), np.int32)
    mask_id = tokenizer.token_to_id("[MASK]")
    for i in range(len(texts)):
        positions[i] = np.nonzero(ids[i] == mask_id)[0][:2]
    requests = [
        (ids[i: i + 1], pad[i: i + 1], positions[i: i + 1])
        for i in range(len(texts))
    ]

    def gathered_apply(p, token_ids, pad_mask, pos):
        logits, _ = model.apply(
            {"params": p}, token_ids, pad_mask, masking=False,
            deterministic=True, positions=pos,
        )
        return logits

    naive = Predictor(gathered_apply, params, max_batch=args.max_batch)
    engine = ServingEngine(
        gathered_apply, params, max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms, name="engine_bench",
        compute_dtype="bfloat16" if args.dtype == "bfloat16" else None,
    )
    # both arms compile everything they will use before any timing
    engine.warmup(*requests[0])
    naive(*requests[0])
    log(f"warmed {engine.num_programs} engine bucket programs")

    def naive_round() -> float:
        t0 = time.perf_counter()
        for r in requests:
            naive(*r)
        return time.perf_counter() - t0

    def engine_round() -> float:
        t0 = time.perf_counter()
        futures = [engine.submit(*r) for r in requests]
        for f in futures:
            f.result()
        return time.perf_counter() - t0

    naive_round()  # one unmeasured round each: steady-state caches
    engine_round()
    naive_s, engine_s = [], []
    for r in range(args.rounds):  # interleaved: A, B, A, B ...
        naive_s.append(naive_round())
        engine_s.append(engine_round())
        log(f"round {r}: naive {naive_s[-1]:.3f}s engine {engine_s[-1]:.3f}s")
    n_med, e_med = statistics.median(naive_s), statistics.median(engine_s)

    n = args.requests
    stats = engine.stats()  # locked deep-copied snapshot
    results = {
        "mode": "engine", "backend": backend, "dtype": args.dtype,
        "preset": "tiny" if tiny else "flagship",
        "requests": n, "rounds": args.rounds,
        "max_batch": args.max_batch, "seq_len": max_seq_len,
        "naive_requests_per_s": round(n / n_med, 2),
        "engine_requests_per_s": round(n / e_med, 2),
        "engine_tokens_per_s": round(n * max_seq_len / e_med, 1),
        "speedup": round(n_med / e_med, 3),
        "batches": stats["batches"],
        "mean_rows_per_batch": round(
            stats["rows"] / max(stats["batches"], 1), 2),
    }
    for bucket, lats in sorted(stats["latency_s_by_bucket"].items()):
        for k, v in _percentiles(lats).items():
            results[f"bucket{bucket}_{k}"] = v

    # device-trace per-micro-batch percentiles (TPU): the device-clock
    # latency statistic — each engine dispatch is a StepTraceAnnotation step
    if backend == "tpu":
        try:
            from perceiver_io_tpu.utils import xplane

            trace_dir = args.trace_dir or tempfile.mkdtemp(
                prefix="engine_bench_")
            with jax.profiler.trace(trace_dir):
                engine_round()
            windows = xplane.step_windows(xplane.load_tpu_plane(trace_dir))
            durations = [(b - a) / 1e12 for a, b in windows]
            if durations:
                for k, v in _percentiles(durations).items():
                    results[f"device_batch_{k}"] = v
        except Exception as e:
            log(f"(device trace unavailable: {type(e).__name__}: "
                f"{str(e)[:80]})")

    engine.close()
    emit_json_line(results)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--trace-dir", default=None,
                        help="keep traces here instead of a temp dir")
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"],
                        help="serving dtype (float32 = the from_checkpoint "
                             "golden-parity default)")
    parser.add_argument("--engine", action="store_true",
                        help="serving-engine A/B mode: ONE JSON line on "
                             "stdout, progress on stderr")
    parser.add_argument("--cpu", action="store_true",
                        help="pin to the CPU backend (ensure_cpu_only before "
                             "jax initializes) — the offline/tier-1 mode")
    parser.add_argument("--preset", choices=["auto", "tiny", "flagship"],
                        default="auto",
                        help="engine-mode model size: auto = flagship on "
                             "TPU, tiny elsewhere")
    parser.add_argument("--requests", type=int, default=64,
                        help="engine mode: batch-1 requests per round")
    parser.add_argument("--rounds", type=int, default=4,
                        help="engine mode: interleaved A/B rounds")
    parser.add_argument("--max_batch", type=int, default=32,
                        help="engine mode: micro-batch cap")
    parser.add_argument("--max_delay_ms", type=float, default=0.0,
                        help="engine mode: batch-formation hold")
    args = parser.parse_args()

    if args.cpu:
        from perceiver_io_tpu.utils.platform import ensure_cpu_only

        ensure_cpu_only()
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    import jax

    if args.engine:
        _engine_mode(args)
        return

    backend = probe_backend().backend
    print(f"backend: {backend}; dtype {args.dtype}", file=sys.stderr)
    predictor, texts, model, params, vocab, max_seq_len = _build_predictor(
        args.dtype
    )
    results: dict = {"backend": backend, "dtype": args.dtype, "vocab": vocab}
    trace_root = args.trace_dir or tempfile.mkdtemp(prefix="inference_bench_")

    # 1) fill_masks latency/throughput ------------------------------------
    print("\nfill_masks (2 [MASK] per text, k=5):", file=sys.stderr)
    print(f"{'batch':>6} {'host ms/call':>13} {'device ms/call':>15} "
          f"{'texts/s (host)':>15}", file=sys.stderr)
    for n in (1, 8, 64):
        batch = texts[:n]
        host = _median_latency(lambda: predictor.fill_masks(batch, k=5))
        dev = _device_per_call(
            lambda: predictor.fill_masks(batch, k=5),
            os.path.join(trace_root, f"fill{n}"),
        )
        print(f"{n:>6} {host * 1e3:>13.2f} {_ms(dev):>15} "
              f"{n / host:>15.1f}", file=sys.stderr)
        results[f"fill_masks_b{n}_host_ms"] = round(host * 1e3, 3)
        if dev is not None:
            results[f"fill_masks_b{n}_device_ms"] = round(dev * 1e3, 4)

    # 2) bucket-padding overhead (gathered forward: small outputs) --------
    from perceiver_io_tpu.inference.mlm import encode_masked_texts

    ids5, pad5 = encode_masked_texts(
        predictor.tokenizer, texts[:5], max_seq_len)
    ids8, pad8 = encode_masked_texts(
        predictor.tokenizer, texts[:8], max_seq_len)
    pos5 = np.tile(np.arange(8, dtype=np.int32), (5, 1))
    pos8 = np.tile(np.arange(8, dtype=np.int32), (8, 1))

    gathered = predictor._gathered  # the Predictor fill_masks dispatches

    def exact_apply(p, token_ids, pad_mask, positions):
        return model.apply(
            {"params": p}, token_ids, pad_mask, masking=False,
            deterministic=True, positions=positions,
        )

    exact5 = jax.jit(exact_apply)

    host_b5 = _median_latency(lambda: gathered(ids5, pad5, pos5))
    host_b8 = _median_latency(lambda: gathered(ids8, pad8, pos8))
    host_exact5 = _median_latency(
        lambda: _consume(exact5(params, ids5, pad5, pos5)))
    dev_b5 = _device_per_call(
        lambda: gathered(ids5, pad5, pos5),
        os.path.join(trace_root, "bucket5"))
    dev_exact5 = _device_per_call(
        lambda: _consume(exact5(params, ids5, pad5, pos5)),
        os.path.join(trace_root, "exact5"))
    print("\nbucket padding (5 texts -> 8-bucket, gathered decode):", file=sys.stderr)
    print(f"  bucketed@5   host {host_b5 * 1e3:7.2f} ms   device "
          f"{_ms(dev_b5)} ms", file=sys.stderr)
    print(f"  native@8     host {host_b8 * 1e3:7.2f} ms", file=sys.stderr)
    print(f"  exact-jit@5  host {host_exact5 * 1e3:7.2f} ms   device "
          f"{_ms(dev_exact5)} ms", file=sys.stderr)
    results.update(
        bucket5_host_ms=round(host_b5 * 1e3, 3),
        native8_host_ms=round(host_b8 * 1e3, 3),
        exact5_host_ms=round(host_exact5 * 1e3, 3),
    )
    if dev_b5 is not None:
        results["bucket5_device_ms"] = round(dev_b5 * 1e3, 4)
    if dev_exact5 is not None:
        results["exact5_device_ms"] = round(dev_exact5 * 1e3, 4)

    # 3) exported StableHLO vs live jit (gathered forward, b8) ------------
    from perceiver_io_tpu.inference.export import export_fn, load_exported

    art = os.path.join(trace_root, "mlm.stablehlo")
    # ONE definition of the gathered serving forward for export/live/exact —
    # positions must stay an ARGUMENT of the exported callable (it varies per
    # request; export_forward's *inputs splat would collide with the model's
    # positional `masking`), and params are baked via partial for the
    # self-contained-artifact semantics
    import functools

    gathered_fn = functools.partial(exact_apply, params)

    t0 = time.perf_counter()
    export_fn(gathered_fn, (ids8, pad8, pos8), path=art)
    export_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    exported_call = load_exported(art)
    _consume(exported_call(ids8, pad8, pos8))
    exported_first_s = time.perf_counter() - t0

    live = jax.jit(gathered_fn)
    t0 = time.perf_counter()
    _consume(live(ids8, pad8, pos8))
    live_first_s = time.perf_counter() - t0

    host_exported = _median_latency(
        lambda: _consume(exported_call(ids8, pad8, pos8)))
    host_live = _median_latency(lambda: _consume(live(ids8, pad8, pos8)))
    dev_exported = _device_per_call(
        lambda: _consume(exported_call(ids8, pad8, pos8)),
        os.path.join(trace_root, "exported"))
    dev_live = _device_per_call(
        lambda: _consume(live(ids8, pad8, pos8)),
        os.path.join(trace_root, "livejit"))
    size_mb = os.path.getsize(art) / 1e6
    print(f"\nStableHLO export (b8 gathered forward, artifact "
          f"{size_mb:.1f} MB, export took {export_s:.1f} s):", file=sys.stderr)
    print(f"  exported  first-result {exported_first_s:6.1f} s   steady "
          f"host {host_exported * 1e3:7.2f} ms   device "
          f"{_ms(dev_exported)} ms", file=sys.stderr)
    print(f"  live jit  first-result {live_first_s:6.1f} s   steady "
          f"host {host_live * 1e3:7.2f} ms   device {_ms(dev_live)} ms", file=sys.stderr)
    results.update(
        export_artifact_mb=round(size_mb, 2),
        export_s=round(export_s, 2),
        exported_first_result_s=round(exported_first_s, 2),
        live_first_result_s=round(live_first_s, 2),
        exported_steady_host_ms=round(host_exported * 1e3, 3),
        live_steady_host_ms=round(host_live * 1e3, 3),
    )
    if dev_exported is not None:
        results["exported_device_ms"] = round(dev_exported * 1e3, 4)
    if dev_live is not None:
        results["live_device_ms"] = round(dev_live * 1e3, 4)

    print(file=sys.stderr)
    emit_json_line(results)


if __name__ == "__main__":
    main()
