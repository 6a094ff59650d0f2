"""Readers' arithmetic over the program's own spans (``obs.tracing.spans``).

The program keeps every finished span in memory: a dict with ``name``,
``id``, ``parent``, ``thread``, ``start_ns`` / ``end_ns`` on its monotonic
clock, ``ok`` and the span's fields. ``read(ctx)`` of a metric runs in the
process that ran the loop, after it, so the readers under ``metrics/`` take
the spans from the process and not from the profiler's capture. Names read
here: ``train.fit`` (one per call of ``Trainer.fit``), ``train.step`` (one per
iteration of its dispatch loop, child of its fit, with ``loader_ns`` and
``dispatch_ns``), ``trainer.init``, and the compile path's ``jax.trace``,
``jax.lower``, ``jax.backend_compile``.

- ``program_spans()``: the process's spans, oldest first; empty from a
  program that keeps none (every reader then returns None).
- ``window_fit(spans)``: the last ``train.fit`` span: the window is the last
  call of ``Trainer.fit`` (the reference that runs after it uses no Trainer).
- ``before(spans, t_ns)``: the spans that had ended by ``t_ns``.
- ``named(spans, *names)``: the spans of these names.
- ``union_s(spans)``: seconds covered by the spans' intervals. Trace events
  nest (an inner ``jit`` traced inside the step's trace fires its own), so a
  sum would count that time twice.
- ``window_steps(spans)``: the ``train.step`` records under the window's fit.
- ``self_ns(record)``: an iteration's length less its two named parts: the
  Trainer's own Python between the calls (on the one iteration in a log
  interval that logs, its syncs too: a median does not see them).
- ``ran_ahead(records)``: the iterations in which the host ran ahead of the
  device, shorter than half the mean iteration (the mean is the pace: the
  device's step where the device sets it). The runtime holds a dispatch back
  for one device step once its queue is full (32 programs on the v5e), so the
  other iterations' ``dispatch_ns`` reads the device and not the host. Where
  no iteration is that short, nothing tells held back from slow, and all are
  returned: the host sets the pace, or the window met no sync that drained
  the queue, and then ``dispatch_ns`` reads the runtime's back-pressure.
- ``median_ms(records, field)``: median of a nanosecond field (or of a
  function of the record), in milliseconds. A median per iteration, not a
  sum over the window: in a traced run the benchmark's loader starts and
  stops the profiler inside the Trainer's ``next()``, and that one wait of
  seconds would poison a sum.
"""

from __future__ import annotations

import statistics
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from benchmarks.trace import union_ns

Span = Dict[str, Any]
STEP_PARTS = ("loader_ns", "dispatch_ns")


def program_spans() -> List[Span]:
    from perceiver_io_tpu.obs import tracing

    read = getattr(tracing, "spans", None)
    if read is None:
        return []
    spans = read()
    if spans.dropped:
        print(f"program spans dropped by full buffers: {spans.dropped}", file=sys.stderr)
    return spans


def window_fit(spans: Sequence[Span]) -> Optional[Span]:
    fits = named(spans, "train.fit")
    return max(fits, key=lambda s: s["start_ns"]) if fits else None


def before(spans: Sequence[Span], t_ns: int) -> List[Span]:
    return [s for s in spans if s["end_ns"] <= t_ns]


def named(spans: Sequence[Span], *names: str) -> List[Span]:
    return [s for s in spans if s["name"] in names]


def union_s(spans: Sequence[Span]) -> float:
    return union_ns([(s["start_ns"], s["end_ns"]) for s in spans]) / 1e9


def window_steps(spans: Sequence[Span]) -> List[Span]:
    fit = window_fit(spans)
    if fit is None:
        return []
    return [s for s in named(spans, "train.step") if s["parent"] == fit["id"]]


def self_ns(record: Span) -> int:
    return record["end_ns"] - record["start_ns"] - sum(record[p] for p in STEP_PARTS)


def ran_ahead(records: Sequence[Span]) -> List[Span]:
    if not records:
        return []
    lengths = [r["end_ns"] - r["start_ns"] for r in records]
    half_mean = sum(lengths) / len(lengths) / 2
    ahead = [r for r, length in zip(records, lengths) if length < half_mean]
    return ahead or list(records)


def median_ms(records: Sequence[Span],
              field: Union[str, Callable[[Span], float]]) -> Optional[float]:
    if not records:
        return None
    get = field if callable(field) else (lambda r: r[field])
    return statistics.median(get(r) for r in records) / 1e6


def setup_union_s(*names: str) -> Optional[float]:
    """Seconds covered by the spans of ``names`` that had ended when the
    window's ``train.fit`` began; None where the process holds no fit."""
    spans = program_spans()
    fit = window_fit(spans)
    if fit is None:
        return None
    return union_s(named(before(spans, fit["start_ns"]), *names))


def window_median_ms(field: Union[str, Callable[[Span], float]],
                     only: Callable[[Sequence[Span]], Sequence[Span]] = list) -> Optional[float]:
    """Median over the window's ``train.step`` records (those that ``only``
    keeps); None where the process holds no fit."""
    return median_ms(only(window_steps(program_spans())), field)
