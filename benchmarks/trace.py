"""Reduction from a profiler trace to what the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but JAX
(``jax.profiler.ProfileData``). The reduction itself (:func:`summarize`) works
on plain tuples, so ``benchmarks/tests`` checks it on a small recorded trace.

Vocabulary: a device plane (``/device:TPU:0``) has a line of XLA operations
(one event per operation that ran), a line of XLA modules (one event per
program execution: the jitted step) and host planes have one line per thread
with the ``TraceAnnotation`` spans the benchmark wrote. All times are
nanoseconds on the trace's one clock.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
HOST_SPAN_PREFIX = "bench."
OP_NAME_CHARS = 160  # the trace names an operation by its whole HLO line


def union_ns(intervals: Sequence[Tuple[int, int]]) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_start, cur_end = 0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def gaps_ns(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The ``(start, end)`` holes between merged intervals."""
    holes, cur_end = [], None
    for start, end in sorted(intervals):
        if cur_end is not None and start > cur_end:
            holes.append((cur_end, start))
        cur_end = end if cur_end is None else max(cur_end, end)
    return holes


@dataclass
class DeviceTrace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Summary:
    """What the metric readers get from a trace."""

    window_s: float                      # first step start .. last op end, mean over chips
    busy_s: float                        # union of device operations in it, mean over chips
    step_name: Optional[str]             # the program with the most summed device time
    step_durations_ms: List[float]       # device duration of each execution of it
    step_gaps_ms: List[float]            # idle gap between consecutive executions
    device_ops: List[Tuple[str, float]]  # top operations by summed seconds
    idle_gaps: List[Tuple[str, float]]   # longest idle gaps by what the host was doing


def summarize(devices: Sequence[DeviceTrace], host_spans: Sequence[Event],
              top: int = 10) -> Optional[Summary]:
    """Reduce per-device events and the benchmark's host spans. Returns None
    when no operation ran on any device."""
    devices = [d for d in devices if d.ops]
    if not devices:
        return None
    windows, busies = [], []
    for d in devices:
        starts = [s for _, s, _ in d.modules] or [s for _, s, _ in d.ops]
        lo = min(starts)
        hi = max(s + dur for _, s, dur in d.ops)
        inside = [(max(s, lo), s + dur) for _, s, dur in d.ops if s + dur > lo]
        windows.append(hi - lo)
        busies.append(union_ns(inside))
    first = devices[0]
    by_module: Dict[str, int] = defaultdict(int)
    for name, _, dur in first.modules:
        by_module[name] += dur
    step_name = max(by_module, key=by_module.get) if by_module else None
    steps = sorted((s, dur) for name, s, dur in first.modules if name == step_name)
    step_durations = [dur / 1e6 for _, dur in steps]
    step_gaps = [max(0, steps[i + 1][0] - (steps[i][0] + steps[i][1])) / 1e6
                 for i in range(len(steps) - 1)]
    by_op: Dict[str, int] = defaultdict(int)
    for name, _, dur in first.ops:
        by_op[name[:OP_NAME_CHARS]] += dur
    device_ops = sorted(((n, t / 1e9) for n, t in by_op.items()),
                        key=lambda x: -x[1])[:top]
    lo = min([s for _, s, _ in first.modules] or [s for _, s, _ in first.ops])
    holes = gaps_ns([(s, s + dur) for _, s, dur in first.ops if s + dur > lo])
    by_host: Dict[str, int] = defaultdict(int)
    for start, end in holes:
        by_host[_host_activity(host_spans, start, end)] += end - start
    idle = sorted(((n, t / 1e9) for n, t in by_host.items()), key=lambda x: -x[1])[:top]
    n = len(devices)
    return Summary(
        window_s=sum(windows) / n / 1e9, busy_s=sum(busies) / n / 1e9,
        step_name=step_name, step_durations_ms=step_durations,
        step_gaps_ms=step_gaps, device_ops=device_ops, idle_gaps=idle)


def _host_activity(host_spans: Sequence[Event], start: int, end: int) -> str:
    """What the host was doing in ``[start, end)``: the innermost (shortest)
    of the benchmark's host spans that covers at least half of it, else the
    one that covers most of it."""
    half = (end - start) / 2.0
    inner, inner_len = None, None
    best, best_cover = "host:other", 0
    for name, s, dur in host_spans:
        cover = min(end, s + dur) - max(start, s)
        if cover <= 0:
            continue
        if cover >= half and (inner is None or dur < inner_len):
            inner, inner_len = name, dur
        if cover > best_cover:
            best, best_cover = name, cover
    return inner or best


def load(trace_dir: str) -> Tuple[List[DeviceTrace], List[Event]]:
    """Device events and the benchmark's host spans of the newest capture
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths or os.path.getsize(paths[-1]) == 0:
        raise RuntimeError(f"no usable xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: List[DeviceTrace] = []
    host_spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace()
            for line in plane.lines:
                if line.name in OPS_LINES:
                    dev.ops = _events(line)
                elif line.name in MODULE_LINES:
                    dev.modules = _events(line)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(e for e in _events(line)
                                  if e[0].startswith(HOST_SPAN_PREFIX))
    return devices, host_spans


def _events(line) -> List[Event]:
    return [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]


def describe(trace_dir: str) -> List[str]:
    """Plane and line names with event counts, for a look by hand."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            sample = events[0].name if events else ""
            out.append(f"{plane.name} | {line.name} | {len(events)} | {sample[:60]}")
    return out
