"""Weight-only quantized serving A/B: bf16 vs int8w vs grouped-int4w
through the micro-batching engine, with parity vs the f32 oracle,
bytes-streamed accounting, and a fused-kernel-vs-XLA micro A/B.

The measured roofline (PERF.md, `tools/hbm_roofline.py`) shows the serving
forward bound by HBM param/elementwise streams, and every engine dispatch
re-streams the full weight set — so weight bytes are the lever. This tool
measures what `perceiver_io_tpu.quant` actually buys, per the PERF.md
discipline:

1. **Throughput A/B**: same process, interleaved rounds (bf16, int8w,
   bf16, int8w, ... — drift of the shared host cancels) of the same
   batch-1 gathered fill-mask request stream through two ``ServingEngine``s
   that differ ONLY in weight storage (both compute in bf16; int8w
   dequantizes inside the compiled program).
2. **Parity**: both arms' logits against the f32 oracle (the golden-parity
   forward on the identical inputs), reported as max |err| / max |oracle|
   — the bound documented in PERF.md §Quantization and pinned by
   ``tests/test_quant.py`` on the same tiny preset.
3. **Bytes-streamed**: the roofline PREDICTION (param-tree bytes per
   dispatch: int8 values + f32 scales vs the bf16 cast — every dispatch
   streams the weights once) and, on TPU, the ACHIEVED per-dispatch HBM
   bytes from the device trace's per-op ``memory_access_breakdown`` summed
   inside the engine's StepTraceAnnotation windows (the same analysis
   `tools/hbm_roofline.py` runs) — prediction vs measurement in one record.

4. **Kernel A/B** (r24): the fused dequant-matmul Pallas kernel
   (``ops/pallas_matmul``) vs the XLA dequant-then-matmul lowering on the
   SAME int8 vocab-head-shaped operands, same-process interleaved rounds.
   On CPU the kernel runs in interpret mode — expected much slower (a
   documented negative result, PERF.md §Quantization); the decision-grade
   number is the TPU run (§r10 queue).

Prints ONE JSON line on stdout (logs on stderr) — the driver-trackable
contract shared with ``tools/inference_bench.py --engine``. ``--cpu`` pins
the CPU backend before jax initializes (the tier-1 offline mode, tiny
preset); TPU runs additionally carry the ``device_*``/``achieved_*`` keys.
``--dry`` emits the record's key contract without touching any device.

Usage::

    timeout 1800 python tools/quant_bench.py [--cpu] [--dry]
        [--preset auto|tiny|flagship] [--requests N] [--rounds R]
        [--max_batch M] [--trace-dir DIR]
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

# jax is imported inside main() AFTER --cpu is handled (ensure_cpu_only must
# run before any backend initializes)
import numpy as np


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _build(tiny: bool):
    """Tiny/flagship MLM at f32 (the oracle dtype) + a synthetic batch-1
    gathered fill-mask request stream. No tokenizer: quant parity and the
    byte stream are properties of the forward, and synthetic token ids keep
    the tier-1 mode in minutes."""
    import jax

    from perceiver_io_tpu.models.presets import flagship_mlm, tiny_mlm

    build = tiny_mlm if tiny else flagship_mlm
    model = build()  # f32: scales quantize from the full-precision tree
    # read the shapes back off the preset (ONE definition — presets.py)
    max_seq_len = model.encoder.input_adapter.max_seq_len
    vocab = model.encoder.input_adapter.vocab_size

    ids = np.zeros((1, max_seq_len), np.int32)
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        ids, ids == 1,
    )
    return model, variables["params"], max_seq_len, vocab


def _requests(n: int, max_seq_len: int, vocab: int):
    rng = np.random.default_rng(0)
    ids = rng.integers(3, vocab, (n, max_seq_len)).astype(np.int32)
    pad = np.zeros((n, max_seq_len), bool)
    positions = np.stack(
        [rng.choice(max_seq_len, 2, replace=False) for _ in range(n)]
    ).astype(np.int32)
    return [
        (ids[i: i + 1], pad[i: i + 1], positions[i: i + 1]) for i in range(n)
    ]


def _rel_to_peak_err(got: np.ndarray, ref: np.ndarray) -> float:
    scale = float(np.max(np.abs(ref))) or 1.0
    return float(np.max(np.abs(got - ref))) / scale


def _trace_hbm_per_dispatch(round_fn, trace_dir: str):
    """TPU only: per-engine-dispatch HBM bytes + lower-quartile device
    seconds, from one traced round (each engine dispatch is a
    StepTraceAnnotation step — the hbm_roofline analysis, reused)."""
    import jax

    from perceiver_io_tpu.utils.xplane import load_tpu_plane, step_windows
    from tools.hbm_roofline import HBM_SPACE, parse_memory_breakdown

    with jax.profiler.trace(trace_dir):
        round_fn()
    tpu = load_tpu_plane(trace_dir)
    names = {k: v.name for k, v in tpu.stat_metadata.items()}
    hbm_by_meta = {}
    for mid, em in tpu.event_metadata.items():
        st = {names.get(s.metadata_id): s for s in em.stats}
        if "memory_access_breakdown" not in st:
            continue
        brk = parse_memory_breakdown(st["memory_access_breakdown"].bytes_value)
        hbm_by_meta[mid] = sum(b for _, sp, b in brk if sp == HBM_SPACE)
    windows = step_windows(tpu)
    if not windows:
        return None, None, 0
    ops_line = [l for l in tpu.lines if l.name == "XLA Ops"][0]
    tot_hbm = 0
    for e in ops_line.events:
        if any(a <= e.offset_ps < b for a, b in windows):
            tot_hbm += hbm_by_meta.get(e.metadata_id, 0)
    durs = sorted(b - a for a, b in windows)
    lq_s = durs[len(durs) // 4] / 1e12
    return tot_hbm / len(windows), lq_s, len(windows)


# the record's key contract, declared for --dry (bench_compare and the
# driver read this shape; TPU runs add the achieved/device keys)
RECORD_KEYS = (
    "mode", "backend", "preset", "requests", "rounds", "max_batch",
    "seq_len",
    "bf16_requests_per_s", "int8w_requests_per_s", "int4w_requests_per_s",
    "speedup_int8w_vs_bf16", "speedup_int4w_vs_bf16",
    "parity_bf16_rel_err", "parity_int8w_rel_err", "parity_int4w_rel_err",
    "param_bytes_f32", "param_bytes_bfloat16", "param_bytes_int8w",
    "param_bytes_int4w", "quantized_leaves",
    "predicted_weight_stream_ratio", "predicted_weight_stream_ratio_int4w",
    "qmm_shape", "qmm_xla_ms", "qmm_pallas_ms", "qmm_kernel_rel_err",
    "speedup_qmm_pallas_vs_xla",
)
TPU_ONLY_KEYS = (
    "achieved_hbm_bytes_per_dispatch_bf16",
    "achieved_hbm_bytes_per_dispatch_int8w",
    "achieved_hbm_bytes_per_dispatch_int4w",
    "device_dispatch_lq_ms_bf16", "device_dispatch_lq_ms_int8w",
    "device_dispatch_lq_ms_int4w",
    "achieved_hbm_ratio_int8w_vs_bf16",
)


def _qmm_kernel_ab(tiny: bool, rounds: int):
    """Same-process interleaved fused-Pallas-vs-XLA dequant-matmul A/B at
    the vocab-head shape (the biggest serving weight stream). Both impls
    consume the SAME int8 operands, so ``qmm_kernel_rel_err`` is purely
    kernel-vs-XLA. Off-TPU the kernel runs in interpret mode — the timing
    is a correctness exercise, not a perf claim (PERF.md discipline)."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.ops.pallas_matmul import quantized_matmul
    from perceiver_io_tpu.quant.int8 import QKernel, quantize_array

    m, k, n = (64, 32, 384) if tiny else (512, 64, 10112)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.bfloat16)
    q, scale = quantize_array(rng.normal(0, 0.02, (k, n)).astype(np.float32))
    qk = QKernel(jnp.asarray(q, jnp.int8), jnp.asarray(scale), "bfloat16")

    impls = {
        "pallas": jax.jit(lambda x, w: quantized_matmul(x, w, impl="pallas")),
        "xla": jax.jit(lambda x, w: quantized_matmul(x, w, impl="xla")),
    }
    outs = {name: np.asarray(fn(x, qk), np.float32)
            for name, fn in impls.items()}  # warm + parity in one pass
    rel_err = _rel_to_peak_err(outs["pallas"], outs["xla"])
    times = {name: [] for name in impls}
    for _ in range(max(rounds, 2)):  # interleaved: pallas, xla, pallas, ...
        for name, fn in impls.items():
            t0 = time.perf_counter()
            fn(x, qk).block_until_ready()
            times[name].append(time.perf_counter() - t0)
    med = {k_: statistics.median(v) for k_, v in times.items()}
    return {
        "qmm_shape": f"{m}x{k}x{n}",
        "qmm_xla_ms": round(med["xla"] * 1e3, 4),
        "qmm_pallas_ms": round(med["pallas"] * 1e3, 4),
        "qmm_kernel_rel_err": round(rel_err, 6),
        "speedup_qmm_pallas_vs_xla": round(med["xla"] / med["pallas"], 4),
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cpu", action="store_true",
                        help="pin to the CPU backend (ensure_cpu_only before "
                             "jax initializes) — the offline/tier-1 mode")
    parser.add_argument("--preset", choices=["auto", "tiny", "flagship"],
                        default="auto",
                        help="model size: auto = flagship on TPU, tiny "
                             "elsewhere (models/presets.py tiny_mlm)")
    parser.add_argument("--requests", type=int, default=64,
                        help="batch-1 requests per round")
    parser.add_argument("--rounds", type=int, default=4,
                        help="interleaved A/B rounds")
    parser.add_argument("--max_batch", type=int, default=32,
                        help="engine micro-batch cap")
    parser.add_argument("--trace-dir", default=None,
                        help="keep TPU traces here instead of a temp dir")
    parser.add_argument("--dry", action="store_true",
                        help="emit the record's key contract as one JSON "
                             "line without touching any device (stdout-"
                             "contract CI mode, like kernel_smoke --dry)")
    args = parser.parse_args()

    if args.dry:
        emit_json_line({
            "mode": "quant", "dry": True,
            "keys": list(RECORD_KEYS),
            "tpu_only_keys": list(TPU_ONLY_KEYS),
        })
        return

    if args.cpu:
        from perceiver_io_tpu.utils.platform import ensure_cpu_only

        ensure_cpu_only()
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    import jax

    from perceiver_io_tpu import quant
    from perceiver_io_tpu.inference import ServingEngine

    backend = probe_backend().backend
    tiny = args.preset == "tiny" or (args.preset == "auto" and backend != "tpu")
    _log(f"backend: {backend}; preset {'tiny' if tiny else 'flagship'}; "
         f"{args.requests} requests x {args.rounds} rounds")

    model, params, max_seq_len, vocab = _build(tiny)
    requests = _requests(args.requests, max_seq_len, vocab)

    def gathered_apply(p, token_ids, pad_mask, pos):
        logits, _ = model.apply(
            {"params": p}, token_ids, pad_mask, masking=False,
            deterministic=True, positions=pos,
        )
        return logits

    # f32 oracle over the whole stream in one shot (golden-parity path)
    stacked = tuple(
        np.concatenate([r[i] for r in requests], axis=0) for i in range(3)
    )
    oracle = np.asarray(
        jax.jit(gathered_apply)(params, *stacked), np.float32
    )

    bytes_acct = quant.bytes_summary(params, compute_dtype="bfloat16")
    int4_acct = quant.bytes_summary(
        params, qparams=quant.quantize_tree(
            params, compute_dtype="bfloat16", bits=4),
        compute_dtype="bfloat16",
    )
    bytes_acct["param_bytes_int4w"] = int4_acct["param_bytes_int4w"]
    bytes_acct["predicted_weight_stream_ratio_int4w"] = (
        int4_acct["predicted_weight_stream_ratio"])
    _log(f"param bytes: f32 {bytes_acct['param_bytes_f32']:,} / bf16 "
         f"{bytes_acct['param_bytes_bfloat16']:,} / int8w "
         f"{bytes_acct['param_bytes_int8w']:,} / int4w "
         f"{bytes_acct['param_bytes_int4w']:,} "
         f"(predicted weight-stream ratios "
         f"{bytes_acct['predicted_weight_stream_ratio']} / "
         f"{bytes_acct['predicted_weight_stream_ratio_int4w']})")

    engines = {
        "bf16": ServingEngine(
            gathered_apply, params, max_batch=args.max_batch,
            compute_dtype="bfloat16", name="quant_bench_bf16",
        ),
        "int8w": ServingEngine(
            gathered_apply, params, max_batch=args.max_batch,
            compute_dtype="int8w", name="quant_bench_int8w",
        ),
        "int4w": ServingEngine(
            gathered_apply, params, max_batch=args.max_batch,
            compute_dtype="int4w", name="quant_bench_int4w",
        ),
    }
    try:
        for name, eng in engines.items():
            eng.warmup(*requests[0])
            _log(f"{name}: warmed {eng.num_programs} bucket programs")

        # parity vs the f32 oracle, identical inputs through the engine path
        parity = {}
        for name, eng in engines.items():
            futs = [eng.submit(*r) for r in requests]
            got = np.concatenate(
                [np.asarray(f.result(timeout=600), np.float32) for f in futs],
                axis=0,
            )
            parity[name] = _rel_to_peak_err(got, oracle)
            _log(f"{name}: rel-to-peak parity err vs f32 oracle "
                 f"{parity[name]:.4g}")

        def engine_round(eng) -> float:
            t0 = time.perf_counter()
            futs = [eng.submit(*r) for r in requests]
            for f in futs:
                f.result(timeout=600)
            return time.perf_counter() - t0

        for eng in engines.values():  # unmeasured steady-state round each
            engine_round(eng)
        times = {name: [] for name in engines}
        for r in range(args.rounds):  # interleaved: A, B, C, A, B, C, ...
            for name, eng in engines.items():
                times[name].append(engine_round(eng))
            _log("round %d: %s" % (r, " ".join(
                f"{name} {times[name][-1]:.3f}s" for name in engines)))
        med = {k: statistics.median(v) for k, v in times.items()}

        # the fused-kernel-vs-XLA micro A/B (interleaved, same operands)
        qmm = _qmm_kernel_ab(tiny, args.rounds)
        _log(f"qmm {qmm['qmm_shape']}: pallas {qmm['qmm_pallas_ms']} ms vs "
             f"xla {qmm['qmm_xla_ms']} ms (speedup "
             f"{qmm['speedup_qmm_pallas_vs_xla']}x, rel err "
             f"{qmm['qmm_kernel_rel_err']})")

        n = args.requests
        results = {
            "mode": "quant", "backend": backend,
            "preset": "tiny" if tiny else "flagship",
            "requests": n, "rounds": args.rounds,
            "max_batch": args.max_batch, "seq_len": max_seq_len,
            "bf16_requests_per_s": round(n / med["bf16"], 2),
            "int8w_requests_per_s": round(n / med["int8w"], 2),
            "int4w_requests_per_s": round(n / med["int4w"], 2),
            "speedup_int8w_vs_bf16": round(med["bf16"] / med["int8w"], 3),
            "speedup_int4w_vs_bf16": round(med["bf16"] / med["int4w"], 3),
            "parity_bf16_rel_err": round(parity["bf16"], 6),
            "parity_int8w_rel_err": round(parity["int8w"], 6),
            "parity_int4w_rel_err": round(parity["int4w"], 6),
            **bytes_acct,
            **qmm,
        }

        # achieved bytes-streamed (TPU): trace one round per arm, sum HBM
        # bytes inside the dispatch step windows — prediction vs measurement
        if backend == "tpu":
            trace_root = args.trace_dir or tempfile.mkdtemp(prefix="quant_bench_")
            for name, eng in engines.items():
                try:
                    hbm, lq_s, steps = _trace_hbm_per_dispatch(
                        lambda e=eng: engine_round(e),
                        os.path.join(trace_root, name),
                    )
                    if hbm is not None:
                        results[f"achieved_hbm_bytes_per_dispatch_{name}"] = (
                            int(hbm))
                        results[f"device_dispatch_lq_ms_{name}"] = round(
                            lq_s * 1e3, 4)
                        _log(f"{name}: {steps} traced dispatches, "
                             f"{hbm / 1e6:.2f} MB HBM/dispatch, "
                             f"lq {lq_s * 1e3:.3f} ms")
                except Exception as e:
                    _log(f"({name} device trace unavailable: "
                         f"{type(e).__name__}: {str(e)[:120]})")
            a, b = (results.get("achieved_hbm_bytes_per_dispatch_int8w"),
                    results.get("achieved_hbm_bytes_per_dispatch_bf16"))
            if a and b:
                results["achieved_hbm_ratio_int8w_vs_bf16"] = round(a / b, 4)
    finally:
        for eng in engines.values():
            eng.close()

    emit_json_line(results)


if __name__ == "__main__":
    main()
