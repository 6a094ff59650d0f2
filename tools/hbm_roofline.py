"""Hardware-trace roofline for any BASELINE config: device-measured step
time, achieved HBM bandwidth, and TRACE-MEASURED MFU.

Captures a ``jax.profiler`` trace of one full train step on the real TPU,
parses the xplane (via ``perceiver_io_tpu.utils.xplane`` — the tensorboard-
plugin converter is incompatible with this TF build), and reports:

- device-measured step time (from the trace's Steps line — the device's
  own clock, not the host's),
- achieved HBM bytes/s vs the device's own advertised peak, plus on-chip
  (VMEM) bytes/s,
- **trace-measured MFU**: model FLOPs ÷ (device step time × peak). The
  numerator comes from XLA cost analysis of the SAME config compiled with
  ``attn_impl='xla'`` (identical math, no custom calls) — because cost
  analysis counts ZERO flops for Pallas custom-calls, summing per-op trace
  flops would under-report exactly the configs whose hot ops run in the
  kernels (the PERF.md caveat this tool closes; VERDICT r2 item 4). The
  denominator is hardware-measured, so Pallas time is fully counted.
- a per-component table (duration, HBM/VMEM bandwidth, TF/s) so the binding
  resource of each phase is visible. (Per-op TF/s shows 0 for Pallas
  custom-calls — cost-analysis metadata, trust the aggregate MFU.)

Byte counts come from XLA's per-op cost analysis embedded in the trace
(``memory_access_breakdown``); durations are hardware-measured. This is the
same bytes-modeled/time-measured definition the TensorBoard profiler's
"memory BW utilization" uses. Memory-space code 1 is HBM, 3 is on-chip
(verified empirically: space-3 aggregate bandwidth exceeds the HBM peak
severalfold, and known-HBM-resident tensors — the vocab embedding table,
optimizer state — report space 1).

Usage::

    timeout 900 python tools/hbm_roofline.py [--config mlm|imagenet|imagenet8h|flow|mnist|multimodal]
                                             [--steps 10] [--components 12]
                                             [--trace-dir DIR]  # re-analyze
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_SPACE, ONCHIP_SPACE = 1, 3


def _varint(buf: bytes, i: int):
    r = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def parse_memory_breakdown(buf: bytes):
    """Decode the repeated {operation_type, memory_space, bytes} submessages
    of the ``memory_access_breakdown`` stat."""
    out = []
    i = 0
    while i < len(buf):
        tag, i = _varint(buf, i)
        if tag != 0x0A:
            break
        ln, i = _varint(buf, i)
        sub = buf[i : i + ln]
        i += ln
        j = 0
        op = space = nbytes = 0
        while j < len(sub):
            t, j = _varint(sub, j)
            v, j = _varint(sub, j)
            if t == 0x08:
                op = v
            elif t == 0x10:
                space = v
            elif t == 0x18:
                nbytes = v
        out.append((op, space, nbytes))
    return out


def _build(config: str):
    """(state, jitted_step, batch, batch_size) for a named e2e config."""
    import jax

    from e2e_configs_bench import CONFIGS
    from perceiver_io_tpu.training import (
        OptimizerConfig,
        TrainState,
        make_optimizer,
    )

    variables, train_step, batch, batch_size = CONFIGS[config]()
    tx, _ = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    return state, jax.jit(train_step, donate_argnums=(0,)), batch, batch_size


def model_flops_per_step(config: str) -> float | None:
    """Cost-analysis FLOPs of the config compiled with attn_impl='xla'.

    Runs in a SUBPROCESS because the attention impl is baked in at model
    construction via the PIT_E2E_ATTN env, which this process has already
    read."""
    import json
    import subprocess

    tools_dir = os.path.dirname(os.path.abspath(__file__))
    code = (
        "import os, sys, json\n"
        f"sys.path.insert(0, {tools_dir!r})\n"
        f"sys.path.insert(0, {os.path.dirname(tools_dir)!r})\n"
        "os.environ['PIT_E2E_ATTN'] = 'xla'\n"
        "os.environ['PIT_E2E_HEAD'] = 'none'\n"  # count the head's flops too\n
        "import jax\n"
        "from e2e_configs_bench import CONFIGS\n"
        "from perceiver_io_tpu.training import (OptimizerConfig, TrainState,\n"
        "                                       make_optimizer)\n"
        "from perceiver_io_tpu.utils import profiling\n"
        f"variables, train_step, batch, _ = CONFIGS[{config!r}]()\n"
        "tx, _ = make_optimizer(OptimizerConfig(learning_rate=1e-3))\n"
        "state = TrainState.create(variables['params'], tx, jax.random.key(2))\n"
        "jitted = jax.jit(train_step, donate_argnums=(0,))\n"
        "flops = profiling.compiled_flops(jitted, state, batch)\n"
        "print(json.dumps({'flops': flops}))\n"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=560, check=True,
        )
        return json.loads(out.stdout.strip().splitlines()[-1])["flops"]
    except Exception as e:
        print(f"(flops subprocess failed: {e}; MFU omitted)", file=sys.stderr)
        return None


def capture_trace(trace_dir: str, config: str, steps: int) -> int:
    """Run + trace the config's train step; returns the batch size."""
    state, jitted, batch, batch_size = _build(config)

    import jax

    state, m = jitted(state, batch)  # compile + warm
    float(m["loss"])
    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        state, m = jitted(state, batch)
    float(m["loss"])
    jax.profiler.stop_trace()
    return batch_size


def analyze(trace_dir: str, n_components: int, batch_size: int | None,
            flops_per_step: float | None) -> dict:
    from perceiver_io_tpu.utils.xplane import load_tpu_plane, step_windows

    tpu = load_tpu_plane(trace_dir)
    names = {k: v.name for k, v in tpu.stat_metadata.items()}

    peaks = {}
    for s in tpu.stats:
        peaks[names.get(s.metadata_id)] = s.double_value
    peak_hbm = peaks.get("peak_hbm_bw_gigabytes_per_second")
    peak_tf = peaks.get("peak_teraflops_per_second")
    if not (peak_hbm and peak_tf):
        # the trace names no peak: the one table, by this host's device kind
        # (an unknown kind raises — a share of an assumed peak is no share)
        import jax

        from perceiver_io_tpu.utils.profiling import device_peaks

        table_flops, table_hbm = device_peaks(jax.devices()[0].device_kind)
        peak_hbm = peak_hbm or table_hbm / 1e9
        peak_tf = peak_tf or table_flops / 1e12

    windows = step_windows(tpu)
    windows = windows[2:] if len(windows) > 4 else windows  # steady state
    n_steps = len(windows)
    step_s = sum(b - a for a, b in windows) / 1e12 / n_steps
    # robust capability estimate on a time-shared chip (see
    # utils.xplane.device_step_seconds): lower quartile of per-step durations
    durs = sorted(b - a for a, b in windows)
    step_s_lq = durs[len(durs) // 4] / 1e12

    meta = {}
    for mid, em in tpu.event_metadata.items():
        st = {names.get(s.metadata_id): s for s in em.stats}
        if "memory_access_breakdown" not in st:
            continue
        brk = parse_memory_breakdown(st["memory_access_breakdown"].bytes_value)
        hbm = sum(b for _, sp, b in brk if sp == HBM_SPACE)
        onchip = sum(b for _, sp, b in brk if sp == ONCHIP_SPACE)
        flops = st["flops"].int64_value if "flops" in st else 0
        src = st["tf_op"].str_value if "tf_op" in st else ""
        key = (
            src.split("jvp(")[-1].split(":")[0][:64]
            if src else em.name.split(" = ")[0][:40]
        )
        meta[mid] = (hbm, onchip, flops, key)

    ops_line = [l for l in tpu.lines if l.name == "XLA Ops"][0]
    tot_hbm = tot_onchip = tot_flops = 0
    comp = defaultdict(lambda: [0, 0, 0, 0])
    for e in ops_line.events:
        if not any(a <= e.offset_ps < b for a, b in windows):
            continue
        m = meta.get(e.metadata_id)
        if m is None:
            continue
        hbm, onchip, flops, key = m
        tot_hbm += hbm
        tot_onchip += onchip
        tot_flops += flops
        row = comp[key]
        row[0] += e.duration_ps
        row[1] += hbm
        row[2] += onchip
        row[3] += flops

    result = {
        "step_ms": step_s * 1e3,
        "step_ms_lower_quartile": step_s_lq * 1e3,
        "hbm_gb_per_step": tot_hbm / n_steps / 1e9,
        "hbm_gb_s": tot_hbm / n_steps / step_s / 1e9,
        "hbm_peak_gb_s": peak_hbm,
        "hbm_util": tot_hbm / n_steps / step_s / 1e9 / peak_hbm,
        "onchip_gb_s": tot_onchip / n_steps / step_s / 1e9,
        "trace_op_tf_s": tot_flops / n_steps / step_s / 1e12,
    }
    if batch_size:
        result["examples_per_sec"] = batch_size / step_s
    if flops_per_step:
        result["model_tf_per_step"] = flops_per_step / 1e12
        result["mfu"] = flops_per_step / step_s / 1e12 / peak_tf
        result["mfu_lower_quartile_step"] = (
            flops_per_step / step_s_lq / 1e12 / peak_tf
        )

    print(
        f"device step: {result['step_ms']:.3f} ms mean / "
        f"{result['step_ms_lower_quartile']:.3f} ms lower-quartile"
        + (f" ({result['examples_per_sec']:.1f} ex/s)" if batch_size else ""), file=sys.stderr)
    print(
        f"HBM: {result['hbm_gb_per_step']:.2f} GB/step -> "
        f"{result['hbm_gb_s']:.0f} GB/s = {result['hbm_util']*100:.1f}% of "
        f"{peak_hbm:.0f} GB/s peak; on-chip {result['onchip_gb_s']:.0f} GB/s", file=sys.stderr)
    if "mfu" in result:
        print(
            f"MFU (trace-measured): {result['mfu']*100:.1f}% mean / "
            f"{result['mfu_lower_quartile_step']*100:.1f}% lower-quartile "
            f"({result['model_tf_per_step']:.2f} TF/step vs {peak_tf:.0f} "
            f"TF/s peak)", file=sys.stderr)
    print(
        f"(per-op trace flops sum: {result['trace_op_tf_s']:.1f} TF/s — "
        f"undercounts Pallas custom-calls)", file=sys.stderr)
    print(f"\n{'ms':>7} {'HBM GB/s':>8} {'chip GB/s':>9} {'TF/s':>6}  component", file=sys.stderr)
    rows = sorted(comp.items(), key=lambda kv: -kv[1][0])[:n_components]
    for key, (d, h, o, f) in rows:
        sec = d / 1e12 / n_steps
        if sec <= 0:
            continue
        print(
            f"{sec*1e3:7.3f} {h/n_steps/sec/1e9:8.0f} "
            f"{o/n_steps/sec/1e9:9.0f} {f/n_steps/sec/1e12:6.2f}  {key[:66]}", file=sys.stderr)
    return result


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default=None,
                        help="e2e config name (see tools/e2e_configs_bench.py); "
                             "default mlm when capturing. With --trace-dir it "
                             "must be passed explicitly for MFU — the trace "
                             "doesn't record which config produced it, and a "
                             "mismatched numerator would report a confidently "
                             "wrong MFU")
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--components", type=int, default=12)
    parser.add_argument("--batch-size", type=int, default=None,
                        help="with --trace-dir: batch size for ex/s")
    parser.add_argument("--no-mfu", action="store_true",
                        help="skip the flops subprocess (faster)")
    parser.add_argument("--flops", type=float, default=None,
                        help="MFU numerator in FLOPs/step, bypassing the "
                             "cost-analysis subprocess — for re-runs where "
                             "the numerator is already known (it is shape-"
                             "stable per config), or when the subprocess's "
                             "compile window is squeezed by a busy chip "
                             "(the multimodal numerator compile alone can "
                             "exceed it)")
    parser.add_argument("--trace-dir", default=None,
                        help="analyze an existing trace instead of capturing")
    args = parser.parse_args()

    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

    config = args.config
    if config is None:
        if args.trace_dir is not None:
            if args.flops is None:
                print("(--trace-dir without --config: MFU omitted — pass "
                      "the config that produced the trace, or --flops)", file=sys.stderr)
        else:
            config = "mlm"

    flops = args.flops
    if flops is not None:
        print(f"(MFU numerator: {flops / 1e12:.2f} TF/step, caller-supplied)", file=sys.stderr)
    elif config is not None and not args.no_mfu:
        flops = model_flops_per_step(config)
        if flops:
            print(f"(MFU numerator: {config} config, "
                  f"{flops / 1e12:.2f} TF/step from XLA cost analysis)", file=sys.stderr)
    trace_dir = args.trace_dir
    batch_size = args.batch_size
    if trace_dir is None:
        trace_dir = tempfile.mkdtemp(prefix=f"hbm_roofline_{config}_")
        print(f"capturing {args.steps}-step {config} trace to {trace_dir} ...", file=sys.stderr)
        batch_size = capture_trace(trace_dir, config, args.steps)
    analyze(trace_dir, args.components, batch_size, flops)


if __name__ == "__main__":
    main()
