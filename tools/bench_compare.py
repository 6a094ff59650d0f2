"""Noise-floor-aware bench record comparison: the regression sentinel.

The bench trajectory (BENCH_*.json, load_bench/deploy_bench records,
PERF.md's measured curves) has been compared by EYE against the measurement
discipline's noise floors — this tool machine-checks it. Given a baseline
record and one or more candidates, every comparable numeric metric gets a
verdict: ``improved`` / ``regressed`` / ``within_noise``.

The floors are TAKEN FROM PERF.md's recorded null-control measurements,
never re-derived at compare time (re-deriving would launder today's noise
into tomorrow's threshold):

- **device-trace** statistics (``bench.py``'s headline
  ``mlm_tokens_per_sec_per_chip`` with ``method=device_trace``, and
  ``device_ms_per_step``): ±0.04% — the lower-quartile device-trace step
  time reproduces to that across sessions (PERF.md §Measurement, r3).
- **same-process paired-interleave** percentages (``overhead_pct`` from
  ``--trace_ab``-family A/Bs): ±1.5 absolute points — the r15 null control
  (both arms identical) measured a ±1.5% floor on this host.
- **host-clock / cross-session** numbers (``host_ms_per_step``, CPU
  requests/s, latency percentiles, calibrated capacities): a shared host
  CPU swings widely BETWEEN sessions (how widely on today's machines is
  not measured — PERF.md), so a
  cross-record comparison gets a 100% floor — only a >2x change clears it.
  This is deliberately brutal: cross-session host numbers cannot resolve
  finer, and the honest verdict for a 30% "win" measured across sessions
  is ``within_noise``. Same-process interleaves are the tool for finer
  claims; this sentinel's job is the trajectory, not the A/B.

Record formats accepted: a bare one-line JSON record (what every tool
emits), or the driver's ``BENCH_rNN.json`` wrapper (the ``parsed`` field is
used). Nested records flatten to dot paths (``capacity.knee_rps``,
``trace.overhead_pct``); list elements index (``sweep.0.p99_ms``). By
default only keys a floor class recognizes are compared (counts and config
echoes are not measurements); ``--keys`` selects explicitly, ``--all``
compares every shared numeric key (unrecognized keys get the host floor).

Usage::

    python tools/bench_compare.py BASELINE.json CAND.json [MORE.json ...]
        [--keys value,device_ms_per_step] [--all] [--fail_on_regress]

Emits exactly ONE JSON line on stdout; per-metric detail rides stderr.
Exit 0 always, unless ``--fail_on_regress`` and any candidate regressed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.jsonline import emit_json_line, log

# -- noise floors: PERF.md's recorded null-control numbers --------------------
# (pattern over the flattened dot-path key; first match wins; floor is a
# FRACTION of the baseline unless mode == "abs" — absolute difference in the
# metric's own unit, for metrics that are already percentages)

DEVICE_FLOOR = 0.0004   # PERF.md §Measurement (r3): device-trace lower
# quartile reproduces ±0.04% across sessions
PAIRED_FLOOR_PTS = 1.5  # PERF.md §Tracing (r15): null-control paired
# interleave measured a ±1.5% floor on this host
HOST_FLOOR = 1.0        # host clocks on a shared CPU: cross-record host
# numbers are held to a 2x change until their spread is measured (PERF.md)

FLOOR_CLASSES: List[Tuple[str, str, float, str, str]] = [
    # (key regex, mode frac|abs, floor, direction higher|lower, source)
    (r"(^|\.)device_ms_per_step$", "frac", DEVICE_FLOOR, "lower",
     "PERF.md §Measurement r3: device-trace lower-quartile ±0.04%"),
    (r"(^|\.)overhead_pct$", "abs", PAIRED_FLOOR_PTS, "lower",
     "PERF.md §Tracing r15: paired-interleave null control ±1.5%"),
    (r"(^|\.)blip_ratio$", "frac", HOST_FLOOR, "lower",
     "PERF.md §Deployment: host-clock blip attribution, cross-session"),
    (r"(^|\.)host_ms_per_step$", "frac", HOST_FLOOR, "lower",
     "host clock on a shared CPU: cross-session spread unmeasured"),
    (r"(^|\.)(mfu|mxu)([_%]|$)", "frac", DEVICE_FLOOR, "higher",
     "PERF.md §Roofline: derived from the device trace"),
    (r"(_|\.|^)(knee_rps|capacity_rps|slo_sustainable_rps|calibrated_rps"
     r"|achieved_rps|offered_rps)$", "frac", HOST_FLOOR, "higher",
     "PERF.md §SLO: CPU open-loop rates are host-clock, cross-session"),
    (r"(_|\.|^)p\d+_ms$|(^|\.)calibrated_latency_ms$|service_floor_ms$"
     r"|p99_floor_ms$|_p99_ms$|_steady_ms$|_swap_ms$", "frac", HOST_FLOOR,
     "lower", "PERF.md: latency percentiles are host-clock, cross-session"),
    (r"(^|\.)shed_rate$", "abs", 0.01, "lower",
     "PERF.md §SLO: shed fractions jitter ~1e-2 point-to-point on CPU"),
    # decode_batching_bench (r20): the speedup is a SAME-PROCESS paired
    # ratio, so host drift cancels — the floor is the observed per-pair
    # spread (pairs 1.973/2.114/2.266 around the 2.114 median, ±7%),
    # doubled. The arm throughputs themselves are host-clock.
    (r"(^|\.)speedup(_median)?$", "frac", 0.15, "higher",
     "PERF.md §Continuous batching r20: per-pair speedup spread ±7% "
     "around the 2.114x median; 2x that as the floor"),
    (r"(^|\.)(batched|sequential)_tokens_per_s$|(^|\.)tokens_per_s$",
     "frac", HOST_FLOOR, "higher",
     "CLAUDE.md: CPU tokens/s is host-clock, cross-session (±2x swing)"),
    # load_bench transport A/B (r22): the speedups are SAME-PROCESS paired
    # (throughput: order-alternated wave pairs) or same-log derived (rpc
    # span p50 ratio) — host drift cancels, so the floor is per-pair
    # spread, the r20 paired-speedup treatment. The arm rates themselves
    # are host-clock.
    (r"(^|\.)(rpc_p50_speedup|throughput_speedup)$", "frac", 0.15, "higher",
     "PERF.md §Transport r22: same-process http-vs-transport paired "
     "ratio; per-pair spread floor (the r20 paired-speedup class)"),
    (r"(^|\.)(http_rps|transport_rps)$", "frac", HOST_FLOOR, "higher",
     "CLAUDE.md: CPU requests/s is host-clock, cross-session (±2x swing)"),
    (r"(^|\.)(slot_occupancy|steps_per_dispatch)(_mean)?$"
     r"|(^|\.)ar_decode_slot_occupancy$", "frac", 0.10, "higher",
     "PERF.md §Continuous batching r20: occupancy/steps-per-dispatch are "
     "schedule-determined aggregates; ~10% run-to-run on CPU"),
    # multihost_drill (r19 restart / r23 elastic): recovery walls are
    # host-clock CPU-sim walls; the elastic-vs-restart `speedup` is a
    # same-process paired ratio and matches the r20 speedup class above.
    (r"(^|\.)(kill_to_\w+_s|total_wall_s|resize_wall_s|grow_wall_s"
     r"|join_wall_s|restart_baseline_s)$", "frac", HOST_FLOOR, "lower",
     "PERF.md §Elastic training r23: recovery walls are host-clock, "
     "cross-session (±2x swing)"),
    (r"(^|\.)steps_lost$", "abs", 0.0, "lower",
     "PERF.md §Elastic training r23: zero-loss accounting is "
     "deterministic — ANY lost step is a regression"),
    # quant_bench (r24): parity errors are seed/model-deterministic
    # (identical quantized values every run) — only the compiler's lowering
    # can wiggle the last ulps, so a 10% floor is already generous; any
    # bigger jump means the kernel or the quantizer changed behavior.
    (r"(^|\.)(parity_\w+_rel_err|qmm_kernel_rel_err)$", "frac", 0.10,
     "lower", "PERF.md §Quantization r24: parity vs the f32 oracle is "
     "deterministic per seed/preset; 10% floor covers compiler ulps"),
    # byte accounting is pure arithmetic over the param tree: ANY drift is
    # a storage-format change, not noise
    (r"(^|\.)(param_bytes_\w+|predicted_weight_stream_ratio(_int4w)?)$",
     "abs", 0.0, "lower",
     "PERF.md §Quantization r24: predicted weight-stream bytes are "
     "deterministic accounting — any change is a format change"),
    # the engine-arm and kernel A/B speedups are SAME-PROCESS interleaved
    # ratios (drift cancels): the r20 paired-speedup treatment
    (r"(^|\.)(speedup_int[48]w_vs_bf16|speedup_qmm_pallas_vs_xla)$",
     "frac", 0.15, "higher",
     "PERF.md §Quantization r24: same-process interleaved A/B ratio; "
     "per-round spread floor (the r20 paired-speedup class)"),
    (r"(^|\.)(bf16|int8w|int4w)_requests_per_s$", "frac", HOST_FLOOR,
     "higher",
     "CLAUDE.md: CPU requests/s is host-clock, cross-session (±2x swing)"),
    (r"(^|\.)qmm_(pallas|xla)_ms$", "frac", HOST_FLOOR, "lower",
     "CLAUDE.md: kernel micro-A/B arm times are host-clock; only the "
     "paired speedup_qmm ratio resolves finer"),
    (r"(^|\.)device_dispatch_lq_ms_\w+$", "frac", DEVICE_FLOOR, "lower",
     "PERF.md §Measurement r3: device-trace lower-quartile ±0.04%"),
    (r"(^|\.)achieved_hbm_(bytes_per_dispatch_\w+|ratio_\w+)$", "frac",
     0.05, "lower",
     "PERF.md §Quantization r24: traced HBM bytes/dispatch vary with "
     "batching composition ~5% run-to-run"),
]

# bench.py's headline: 'value' is device-trace only when the record says so
_HEADLINE = "mlm_tokens_per_sec_per_chip"


def classify(key: str, record: Dict[str, Any]
             ) -> Optional[Tuple[str, float, str, str]]:
    """``(mode, floor, direction, source)`` for a flattened key, or None
    when the key is not a recognized measurement."""
    leaf = key.rsplit(".", 1)[-1]
    if leaf == "value" and record.get("metric") == _HEADLINE:
        if record.get("method") == "device_trace":
            return ("frac", DEVICE_FLOOR, "higher",
                    "PERF.md §Measurement r3: device-trace headline ±0.04%")
        return ("frac", HOST_FLOOR, "higher",
                "host-clock headline: cross-session spread unmeasured")
    for pattern, mode, floor, direction, source in FLOOR_CLASSES:
        if re.search(pattern, key):
            return (mode, floor, direction, source)
    return None


def flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    """Numeric scalars by dot path (bools excluded — they are states, not
    measurements; list elements index numerically)."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(flatten(v, f"{prefix}.{k}" if prefix else str(k)))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}.{i}" if prefix else str(i)))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = float(obj)
    return out


def load_record(path: str) -> Dict[str, Any]:
    """One bench record: a bare JSON object/line, or the driver's
    BENCH_rNN.json wrapper (its ``parsed`` field is the record)."""
    with open(path) as f:
        text = f.read().strip()
    try:
        body = json.loads(text)
    except json.JSONDecodeError:
        # a JSONL file: take the last parseable line (tools emit one, but
        # a concatenated log should still compare by its newest record)
        body = None
        for line in reversed(text.splitlines()):
            try:
                body = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
        if body is None:
            raise ValueError(f"{path}: no JSON record found")
    if isinstance(body, dict) and isinstance(body.get("parsed"), dict):
        body = body["parsed"]
    if not isinstance(body, dict):
        raise ValueError(f"{path}: record is not a JSON object")
    return body


def compare(base: Dict[str, Any], cand: Dict[str, Any],
            keys: Optional[List[str]] = None,
            include_all: bool = False) -> List[Dict[str, Any]]:
    """Per-metric verdicts for one candidate against the baseline."""
    fb, fc = flatten(base), flatten(cand)
    shared = sorted(set(fb) & set(fc))
    out: List[Dict[str, Any]] = []
    for key in shared:
        if keys is not None and key not in keys:
            continue
        cls = classify(key, base)
        if cls is None:
            if not (include_all or keys is not None):
                continue
            cls = ("frac", HOST_FLOOR, None,
                   "unclassified metric — host-conservative 100% floor")
        mode, floor, direction, source = cls
        b, c = fb[key], fc[key]
        delta = c - b
        if mode == "abs":
            over = abs(delta) > floor
            floor_desc = f"±{floor:g} abs"
            delta_frac = None if b == 0 else delta / abs(b)
        else:
            delta_frac = None if b == 0 else delta / abs(b)
            over = (abs(delta) > 0 if b == 0
                    else abs(delta_frac) > floor)
            floor_desc = f"±{100 * floor:g}%"
        if not over:
            verdict = "within_noise"
        elif direction is None:
            verdict = "changed"
        else:
            better = delta > 0 if direction == "higher" else delta < 0
            verdict = "improved" if better else "regressed"
        out.append({
            "key": key, "base": b, "cand": c,
            "delta_pct": (None if delta_frac is None
                          else round(100 * delta_frac, 4)),
            "floor": floor_desc, "direction": direction,
            "verdict": verdict, "floor_source": source,
        })
    return out


def summarize(comparisons: List[Dict[str, Any]]) -> Dict[str, Any]:
    counts = {"improved": 0, "regressed": 0, "within_noise": 0, "changed": 0}
    for c in comparisons:
        counts[c["verdict"]] += 1
    if not comparisons:
        # schema drift / a --dry record / the wrong file: "nothing was
        # checked" must never read as "nothing regressed"
        verdict = "no_comparable_metrics"
    elif counts["regressed"]:
        verdict = "regressed"
    elif counts["improved"]:
        verdict = "improved"
    elif counts["changed"]:
        verdict = "changed"
    else:
        verdict = "within_noise"
    return {**counts, "verdict": verdict}


def main() -> int:
    parser = argparse.ArgumentParser(
        description="noise-floor-aware bench record comparison")
    parser.add_argument("records", nargs="+", metavar="RECORD.json",
                        help="baseline first, then candidate(s)")
    parser.add_argument("--keys", default=None,
                        help="comma-separated flattened keys to compare "
                             "(default: every shared key a floor class "
                             "recognizes)")
    parser.add_argument("--all", action="store_true",
                        help="compare every shared numeric key; "
                             "unrecognized keys get the conservative "
                             "host-class 100%% floor")
    parser.add_argument("--fail_on_regress", action="store_true",
                        help="exit nonzero when any candidate regressed")
    args = parser.parse_args()
    if len(args.records) < 2:
        parser.error("need a baseline and at least one candidate record")

    keys = ([k.strip() for k in args.keys.split(",") if k.strip()]
            if args.keys else None)
    base = load_record(args.records[0])
    candidates = []
    any_regressed = False
    for path in args.records[1:]:
        cand = load_record(path)
        comparisons = compare(base, cand, keys=keys, include_all=args.all)
        summary = summarize(comparisons)
        any_regressed = any_regressed or summary["verdict"] == "regressed"
        if not comparisons:
            log(f"compare: {path}: NO comparable metrics vs the baseline "
                "(schema drift or a non-measurement record?) — nothing "
                "was checked")
        for c in comparisons:
            log(f"compare: {c['key']}: {c['base']:g} -> {c['cand']:g} "
                + (f"({c['delta_pct']:+.3f}%) " if c["delta_pct"] is not None
                   else "")
                + f"[{c['verdict']}; floor {c['floor']} — "
                + f"{c['floor_source']}]")
        candidates.append({
            "record": path,
            "summary": summary,
            "comparisons": comparisons,
        })

    compared = sum(len(c["comparisons"]) for c in candidates)
    verdict = ("regressed" if any_regressed else
               summarize([x for c in candidates
                          for x in c["comparisons"]])["verdict"])
    # under --fail_on_regress an unchecked CANDIDATE fails, not just an
    # all-empty run: a gate that skipped one record must not pass because
    # a sibling record compared fine
    any_unchecked = any(not c["comparisons"] for c in candidates)
    failed = args.fail_on_regress and (any_regressed or any_unchecked)
    emit_json_line({
        "tool": "bench_compare",
        "baseline": args.records[0],
        "candidates": candidates,
        "compared": compared,
        "verdict": verdict,
        "ok": not failed,
    })
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
