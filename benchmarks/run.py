"""One run of one benchmark cell:

    python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one last line of JSON on standard output. The cell,
its configuration, its traffic mix and its metrics are found by name in
``BENCHMARK.json`` and under ``benchmarks/`` (README.md there). Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no line.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's record."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class Probes:
    """What a loop reads from the process: its age, and the program's count
    of XLA compilations (``jax_compilations_total``) so far."""

    def __init__(self, clock, compiles):
        self.clock, self.compiles = clock, compiles


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def find_cell(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: Dict[str, Any], cell: Dict[str, Any], group: str):
    """The metrics of ``group`` that this cell reports: those that list it
    under ``workloads``, and those that list nothing (every cell)."""
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def read_metric(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    """Run ``benchmarks/metrics/<name>.py``'s ``read(ctx)``: a number, or
    None where the reader finds nothing to read (the metric is then left out
    of the line)."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmarks.metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(ctx)
    return None if value is None else float(value)


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark needs {chips} TPU chip(s); JAX reports "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        raise SystemExit(2)
    return devices


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    cfg = load_config(cell["config"])

    from benchmarks import traffic
    mix = traffic.load_mix(cell["traffic"])

    devices = require_chips(cell["chips"])
    from perceiver_io_tpu.aot import configure_compile_cache
    from perceiver_io_tpu.obs.watchdog import install_compile_counter
    configure_compile_cache()
    compiles = install_compile_counter()

    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    loop = importlib.import_module(f"benchmarks.loops.{mix['loop']}")
    probes = Probes(clock=process_age_s, compiles=lambda: compiles.value)
    result = loop.run(cell, cfg, mix, builder, args.seed, args.seconds,
                      bool(args.trace), probes)
    print_result(bench, cell, devices, result, bool(args.trace))


def print_result(bench, cell, devices, result, traced: bool) -> None:
    from benchmarks import peaks

    ctx = dict(result)
    ctx["peak_flops"] = peaks.peak_flops(devices[0].device_kind) * cell["chips"]
    group = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, cell, group):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": cell["chips"], "memory_peak_bytes": result["memory_peak_bytes"],
    }
    line: Dict[str, Any] = {
        "correct": result["verdict"]["correct"],
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics": metrics, "device": device,
    }
    summary = result.get("summary")
    if traced:
        if summary is None:
            print("traced run saw no operation on the device", file=sys.stderr)
            raise SystemExit(3)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
        }
    line["workload"] = cell["name"]
    line["details"] = result.get("details", {})
    line["compared"] = result["verdict"]["compared"]
    for name, c in result["verdict"]["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
