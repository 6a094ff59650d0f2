"""Cold-start bench: same-process cold-vs-warm warmup A/B over the AOT cache.

Measures what the persistent executable cache (``perceiver_io_tpu.aot``,
PERF.md §Cold start) actually buys at process start:

1. **cold**: a fresh ``ServingEngine`` warms its full bucket-program family
   against an EMPTY cache directory — every program traces, lowers, and
   compiles (and is persisted);
2. **warm**: a second engine (same model/config/signatures, new instance —
   a fresh closure, so jax's in-process jit cache cannot help it) warms the
   same family against the now-populated cache — every program deserializes.
   The ``jax_compilations_total`` delta over this phase is reported
   (``compiles_warm``; the zero-recompile claim) alongside the wall-clock
   ratio (``speedup``);
3. **first-result latency under background warmup**: a third engine starts a
   priority-ordered background warmup and immediately receives one request —
   ``first_result_s`` is how long that first caller waited, against
   ``bg_warmup_s`` for the whole family (the serve-before-warm claim).

Both arms run in ONE process, interleaved with nothing — compile wall time
is host-side work (trace + lower + backend compile). This bench never times
steady-state dispatch. It measures the executable tier ALONE, so it turns
jax's persistent compilation cache off for its process (with that cache on,
executable stores are refused — ``aot/cache.py``).

Emits exactly ONE JSON line on stdout (progress on stderr). ``--cpu`` pins
the CPU backend (the offline contract mode).

Usage::

    timeout 900 python tools/coldstart_bench.py --cpu [--cache_dir DIR]
        [--max_batch N] [--widths W ...]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.jsonline import emit_json_line
from perceiver_io_tpu.utils.platform import probe_backend

import numpy as np


def _log(*a) -> None:
    print(*a, file=sys.stderr)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cpu", action="store_true",
                        help="pin to the CPU backend (ensure_cpu_only before "
                             "jax initializes) — the offline/tier-1 mode")
    parser.add_argument("--cache_dir", default=None,
                        help="executable-cache directory (default: "
                             "<checkout>/.cache/coldstart_aot, emptied "
                             "before and removed after the run; pass one "
                             "to inspect entries or A/B across invocations)")
    parser.add_argument("--max_batch", type=int, default=16,
                        help="micro-batch cap → power-of-two bucket family")
    parser.add_argument("--widths", type=int, nargs="+", default=[32, 64],
                        help="sequence widths (one program family per width)")
    args = parser.parse_args()

    if args.cpu:
        from perceiver_io_tpu.utils.platform import ensure_cpu_only

        ensure_cpu_only()
    import jax

    # the executable tier alone (module docstring)
    jax.config.update("jax_enable_compilation_cache", False)

    from perceiver_io_tpu.inference import ServingEngine
    from perceiver_io_tpu.models.presets import tiny_mlm
    from perceiver_io_tpu.obs import install_compile_counter

    backend = probe_backend().backend
    widths = sorted({int(w) for w in args.widths})
    _log(f"backend: {backend}; widths {widths}; max_batch {args.max_batch}")

    model = tiny_mlm(max_seq_len=widths[-1])
    ids0 = np.zeros((1, widths[-1]), np.int32)
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        ids0, ids0 == 0,
    )
    params = variables["params"]

    def gathered_apply(p, token_ids, pad_mask, pos):
        logits, _ = model.apply(
            {"params": p}, token_ids, pad_mask, masking=False,
            deterministic=True, positions=pos,
        )
        return logits

    def examples(width: int):
        return (np.zeros((1, width), np.int32),
                np.zeros((1, width), bool),
                np.zeros((1, 2), np.int32))

    counter = install_compile_counter()
    ephemeral = args.cache_dir is None
    cache_dir = args.cache_dir or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".cache", "coldstart_aot")
    if ephemeral:  # the cold arm needs an empty cache
        shutil.rmtree(cache_dir, ignore_errors=True)

    def warm_family(name: str):
        """Fresh engine, full-family blocking warmup; returns
        (wall_s, compiles, programs)."""
        engine = ServingEngine(
            gathered_apply, params, max_batch=args.max_batch,
            compile_cache=cache_dir, name=name,
        )
        c0 = counter.value
        t0 = time.perf_counter()
        for width in widths:
            engine.warmup(*examples(width))
        wall = time.perf_counter() - t0
        programs = engine.num_programs
        engine.close()
        return wall, counter.value - c0, programs

    try:
        cold_s, compiles_cold, programs = warm_family("coldstart_cold")
        _log(f"cold: {programs} programs in {cold_s:.3f}s "
             f"({compiles_cold:.0f} compiles)")
        warm_s, compiles_warm, _ = warm_family("coldstart_warm")
        _log(f"warm: {warm_s:.3f}s ({compiles_warm:.0f} compiles)")

        # serve-before-warm: background warmup + an immediate request
        engine = ServingEngine(
            gathered_apply, params, max_batch=args.max_batch,
            compile_cache=cache_dir, name="coldstart_bg",
        )
        handle = engine.warmup(*examples(widths[0]), background=True)
        t0 = time.perf_counter()
        fut = engine.submit(*examples(widths[0]))
        fut.result(timeout=600)
        first_result_s = time.perf_counter() - t0
        handle.wait(timeout=600)
        bg_warmup_s = time.perf_counter() - t0
        engine.close()
        _log(f"background: first result {first_result_s:.3f}s, family warm "
             f"{bg_warmup_s:.3f}s")
    finally:
        if ephemeral:
            shutil.rmtree(cache_dir, ignore_errors=True)

    emit_json_line({
        "metric": "coldstart_warmup_speedup",
        "value": round(cold_s / warm_s, 2) if warm_s > 0 else None,
        "unit": "x (cold/warm wall)",
        "backend": backend,
        "widths": widths,
        "max_batch": args.max_batch,
        "programs": programs,
        "cold_warmup_s": round(cold_s, 3),
        "warm_warmup_s": round(warm_s, 3),
        "speedup": round(cold_s / warm_s, 2) if warm_s > 0 else None,
        "compiles_cold": int(compiles_cold),
        "compiles_warm": int(compiles_warm),
        "bg_first_result_s": round(first_result_s, 3),
        "bg_family_warm_s": round(bg_warmup_s, 3),
    })


if __name__ == "__main__":
    main()
