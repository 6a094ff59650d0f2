#!/usr/bin/env python
"""Same-process interleaved A/B: continuous-batching arena decode vs r18
per-session chains, at concurrency, with admit/retire churn mid-sweep.

The claim under test (PERF.md §Continuous batching): Perceiver-AR decode is
weight-stream-bound, so packing every active stream's step into ONE batched
dispatch amortizes the per-dispatch cost (weights on TPU, dispatch/launch
overhead on CPU) across the batch — aggregate tokens/s should scale with
concurrency instead of flat-lining. Both arms serve the IDENTICAL stream
schedule (same prefixes, budgets, sampling, stagger); the position-folded
sampling keys make the token streams bit-identical across arms, which the
record asserts (``tokens_match``) — this is a PERF A/B with a built-in
correctness pin, not two unrelated runs.

Measurement discipline (PERF.md): the two arms run INTERLEAVED in one
process (B, A, A, B per pair — order-alternated against drift), never
cross-session; the verdict is the per-pair speedup median. Streams launch
on a bounded worker pool sized BELOW the stream count, so later streams are
admitted as earlier ones retire — membership churns mid-sweep (continuous
batching, not a fixed cohort).

Emits exactly ONE JSON line on stdout; progress rides stderr.
``--dry`` declares the record keys without touching any backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.jsonline import emit_json_line  # noqa: E402

RECORD_KEYS = (
    "metric", "dry", "backend", "streams", "concurrency", "chunk", "slots",
    "pairs", "mean_new", "max_new_cap", "prefix_lens", "temperature",
    "top_k", "quantize",
    "batched_tokens_per_s", "sequential_tokens_per_s",
    "speedup", "speedup_median", "tokens_match",
    "admitted", "retired", "slot_occupancy_mean", "steps_per_dispatch_mean",
    "per_pair",
)


def _log(msg: str) -> None:
    print(f"decode_batching_bench: {msg}", file=sys.stderr, flush=True)


def _schedule(args, vocab: int, max_seq_len: int):
    """The deterministic stream schedule both arms replay: (prefix,
    max_new, stagger_s) per stream. Budgets vary (short and long mixed) so
    retirements free slots while later arrivals are still queued."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    plens = [int(p) for p in args.prefix_lens.split(",")]
    sched = []
    for i in range(args.streams):
        plen = int(rng.choice(plens))
        prefix = [int(t) for t in rng.integers(3, vocab, plen)]
        max_new = int(min(1 + rng.geometric(1.0 / args.mean_new),
                          args.max_new_cap,
                          max_seq_len - plen - 1))
        stagger = float(i % 4) * args.stagger_s
        sched.append((prefix, max_new, stagger))
    return sched


def _run_arm(gen, sched, sampling, concurrency: int):
    """Replay the schedule against one engine on a FIXED worker pool of
    ``concurrency`` threads pulling from an arrival queue; returns
    (wall_s, tokens_total, streams_tokens). The pool bound < len(sched)
    forces mid-sweep admit/retire in the batched arm, and reusing workers
    keeps per-stream thread-spawn cost out of both arms' walls."""
    import queue as _queue

    results = [None] * len(sched)
    errors = []
    work: "_queue.SimpleQueue" = _queue.SimpleQueue()

    def worker():
        while True:
            item = work.get()
            if item is None:
                return
            i, prefix, max_new = item
            try:
                toks, _ = gen.generate(prefix, max_new, sampling)
                results[i] = toks
            except Exception as e:  # pragma: no cover - in the record
                errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, name=f"ab-worker-{w}",
                                daemon=True) for w in range(concurrency)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for i, (prefix, max_new, stagger) in enumerate(sched):
        target = t0 + stagger
        now = time.monotonic()
        if now < target:
            time.sleep(target - now)
        work.put((i, list(prefix), max_new))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} streams failed: {errors[0]}")
    return wall, sum(len(r) for r in results), results


def run(args) -> int:
    if args.dry:
        emit_json_line({
            "metric": "decode_batching_ab", "dry": True, "backend": None,
            "record_keys": list(RECORD_KEYS),
        })
        return 0
    from perceiver_io_tpu.utils.platform import ensure_cpu_only, probe_backend

    if args.cpu:
        ensure_cpu_only()
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    import jax
    import numpy as np

    from perceiver_io_tpu.models.presets import tiny_ar
    from perceiver_io_tpu.inference.batching import ContinuousBatcher
    from perceiver_io_tpu.inference.generate import (
        ARGenerator,
        SamplingConfig,
    )

    model = tiny_ar()
    max_seq_len = 64
    ids0 = np.zeros((1, max_seq_len), np.int32)
    params = model.init(
        {"params": jax.random.key(0)}, ids0, ids0 == 0)["params"]
    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=args.top_k, seed=args.seed)

    quantize = None if args.quantize == "none" else args.quantize
    seq = ARGenerator(model, params, max_seq_len=max_seq_len,
                      chunk=args.chunk, quantize=quantize, name="ab_seq")
    # max_slots pinned to slots: arena growth is the right policy on TPU
    # (a marginal slot rides the same weight stream) but on CPU every slot
    # costs linear compute, so the A/B holds capacity fixed and lets the
    # admission queue keep the arena full instead.
    bat = ContinuousBatcher(model, params, max_seq_len=max_seq_len,
                            chunk=args.chunk, slots=args.slots,
                            max_slots=args.slots, quantize=quantize,
                            name="ab_bat")
    sched = _schedule(args, vocab=int(model.input_adapter.vocab_size),
                      max_seq_len=max_seq_len)
    _log(f"{len(sched)} streams, concurrency {args.concurrency}, "
         f"chunk {args.chunk}, slots {args.slots}, {args.pairs} pairs")
    # warm both arms on the schedule itself (compiles + first-touch), then
    # measure — an unwarmed arm's compile wall would swamp the A/B
    _run_arm(seq, sched, sampling, args.concurrency)
    _run_arm(bat, sched, sampling, args.concurrency)

    per_pair = []
    tokens_match = True
    for p in range(args.pairs):
        # order-alternated (B,A then A,B) so drift cancels per pair
        order = (("bat", "seq") if p % 2 == 0 else ("seq", "bat"))
        walls = {}
        toks = {}
        for arm in order:
            gen = bat if arm == "bat" else seq
            wall, total, results = _run_arm(gen, sched, sampling,
                                            args.concurrency)
            walls[arm] = wall
            toks[arm] = (total, results)
        tokens_match = tokens_match and toks["bat"][1] == toks["seq"][1]
        pair = {
            "batched_tokens_per_s": round(toks["bat"][0] / walls["bat"], 2),
            "sequential_tokens_per_s": round(
                toks["seq"][0] / walls["seq"], 2),
            "order": "->".join(order),
        }
        pair["speedup"] = round(pair["batched_tokens_per_s"]
                                / pair["sequential_tokens_per_s"], 3)
        per_pair.append(pair)
        _log(f"pair {p}: batched {pair['batched_tokens_per_s']} tok/s, "
             f"sequential {pair['sequential_tokens_per_s']} tok/s "
             f"({pair['speedup']}x), match={tokens_match}")
    stats = bat.stats()
    speedups = sorted(p["speedup"] for p in per_pair)
    record = {
        "metric": "decode_batching_ab", "dry": False,
        "backend": probe_backend().backend,
        "streams": len(sched), "concurrency": args.concurrency,
        "chunk": args.chunk, "slots": args.slots, "pairs": args.pairs,
        "mean_new": args.mean_new, "max_new_cap": args.max_new_cap,
        "prefix_lens": args.prefix_lens,
        "temperature": args.temperature, "top_k": args.top_k,
        "quantize": args.quantize,
        "batched_tokens_per_s": per_pair[-1]["batched_tokens_per_s"],
        "sequential_tokens_per_s": per_pair[-1]["sequential_tokens_per_s"],
        "speedup": per_pair[-1]["speedup"],
        "speedup_median": speedups[len(speedups) // 2],
        "tokens_match": tokens_match,
        "admitted": stats["admitted"], "retired": stats["retired"],
        "slot_occupancy_mean": stats["slot_occupancy_mean"],
        "steps_per_dispatch_mean": stats["steps_per_dispatch_mean"],
        "per_pair": per_pair,
    }
    bat.close()
    emit_json_line(record)
    return 0


def main() -> None:
    p = argparse.ArgumentParser(
        description="interleaved A/B: continuous-batching arena decode vs "
                    "per-session chains (tiny preset)")
    p.add_argument("--cpu", action="store_true",
                   help="pin the CPU backend before jax initializes")
    p.add_argument("--dry", action="store_true",
                   help="emit the record schema without touching a backend")
    p.add_argument("--streams", type=int, default=128,
                   help="streams per arm replay (> concurrency: membership "
                        "churns mid-sweep)")
    p.add_argument("--concurrency", type=int, default=40,
                   help="stream worker pool bound (= concurrent sessions); "
                        "kept above slots so the admission queue holds the "
                        "arena at full occupancy")
    p.add_argument("--chunk", type=int, default=4)
    p.add_argument("--slots", type=int, default=16,
                   help="arena slots per prefill width (batched arm)")
    p.add_argument("--pairs", type=int, default=3,
                   help="order-alternated A/B pairs (median speedup wins)")
    p.add_argument("--mean_new", type=int, default=24,
                   help="mean geometric continuation budget (pre-cap)")
    p.add_argument("--max_new_cap", type=int, default=12,
                   help="max_tokens-style budget cap; with the default "
                        "prefix band this keeps every stream inside its "
                        "prefill episode (no width crossing)")
    p.add_argument("--prefix_lens", default="2,3,4",
                   help="prompt lengths; the defaults land every stream in "
                        "the width-16 episode band so the arena packs "
                        "instead of scattering across widths")
    p.add_argument("--stagger_s", type=float, default=0.002,
                   help="arrival stagger between launch cohorts")
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top_k", type=int, default=16)
    p.add_argument("--quantize", choices=("none", "int8", "int4"),
                   default="none",
                   help="weight-only quantization for BOTH arms (the A/B "
                        "stays apples-to-apples; sequential==batched token "
                        "identity must hold per mode — tests/test_batching)")
    p.add_argument("--seed", type=int, default=0)
    raise SystemExit(run(p.parse_args()))


if __name__ == "__main__":
    main()
