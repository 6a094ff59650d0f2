"""Process-wide, thread-safe metrics registry: counters, gauges, and bounded
histograms with on-demand percentiles.

The single source of truth for runtime telemetry: the serving engine, the
Trainer/``MetricsLogger``, and the self-profiling watchdog all publish here,
and every exporter (``/metrics`` Prometheus text, ``/statz`` JSON, the
``metrics.jsonl`` stream) reads the same instruments. Instruments are keyed by
``(name, labels)`` — asking twice returns the same object, so producers in
different modules aggregate naturally.

Deliberately importable before jax initializes any backend (no jax import at
module scope): the CLI entry points parse flags and set up observability
before the first device touch, and ``ensure_cpu_only`` must stay effective.
Multi-host awareness lives at the export edge: every process records locally
(cheap, lock-per-instrument), but ``is_export_process()`` gates the HTTP
sidecar / text exposition to process 0.
"""

from __future__ import annotations

import json
import math
import re
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

_NAME_OK = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_ESCAPES = {"\\": "\\\\", '"': '\\"', "\n": "\\n"}


def sanitize_metric_name(name: str) -> str:
    """Coerce an arbitrary scalar key (``val_loss``, ``bucket64.p95``) into a
    valid Prometheus metric name."""
    name = re.sub(r"[^a-zA-Z0-9_:]", "_", str(name))
    if not name or not _NAME_OK.match(name):
        name = "_" + name
    return name


def _escape_label(value: str) -> str:
    return "".join(_LABEL_ESCAPES.get(c, c) for c in str(value))


def _label_suffix(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels)
    return "{" + inner + "}"


class _Instrument:
    kind = "untyped"

    def __init__(self, name: str, help: str,
                 labels: Tuple[Tuple[str, str], ...]):
        self.name = name
        self.help = help
        self.labels = labels
        self._lock = threading.Lock()

    @property
    def label_dict(self) -> Dict[str, str]:
        return dict(self.labels)


class Counter(_Instrument):
    """Monotonically increasing count."""

    kind = "counter"

    def __init__(self, name, help, labels):
        super().__init__(name, help, labels)
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease (inc {n})")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge(_Instrument):
    """Last-written value (may go up or down)."""

    kind = "gauge"

    def __init__(self, name, help, labels):
        super().__init__(name, help, labels)
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram(_Instrument):
    """Bounded observation window with exact count/sum, on-demand
    percentiles over the window, and optional *exemplars*.

    An engine serves indefinitely — unbounded per-observation lists would grow
    without limit; a 4096-observation window is plenty for p50/p95/p99
    reporting while keeping memory flat. ``count``/``sum`` stay exact over the
    instrument's whole lifetime (they feed Prometheus summary semantics).

    Exemplars (OpenMetrics-style, carried on ``snapshot()``/``/statz``
    rather than the 0.0.4 text exposition, which predates them): an
    ``observe(v, exemplar=trace_id)`` attaches a concrete trace id to the
    observation, and the histogram keeps a small ring of RECENT exemplars
    plus one sticky slot for the SLOWEST exemplar'd observation ever — so
    "p99 is high" links directly to an assembled trace even after the slow
    request scrolls out of the recency ring.
    """

    kind = "histogram"

    def __init__(self, name, help, labels, window: int = 4096):
        super().__init__(name, help, labels)
        self._window: deque = deque(maxlen=window)
        self._count = 0
        self._sum = 0.0
        self._exemplars: deque = deque(maxlen=8)
        self._slowest_exemplar: Optional[Dict[str, Any]] = None

    def observe(self, v: float, exemplar: Optional[str] = None) -> None:
        v = float(v)
        with self._lock:
            self._window.append(v)
            self._count += 1
            self._sum += v
            if exemplar is not None:
                entry = {"value": v, "trace": str(exemplar)}
                self._exemplars.append(entry)
                if (self._slowest_exemplar is None
                        or v >= self._slowest_exemplar["value"]):
                    self._slowest_exemplar = entry

    def exemplars(self) -> List[Dict[str, Any]]:
        """(value, trace) exemplars, slowest first: the sticky slowest-ever
        slot plus the recency ring (deduped) — the p99→trace link
        ``tools/trace_assemble.py`` resolves."""
        with self._lock:
            ex = list(self._exemplars)
            slowest = self._slowest_exemplar
        if slowest is not None and slowest not in ex:
            ex.append(slowest)
        return sorted(ex, key=lambda e: -e["value"])

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def values(self) -> List[float]:
        """Copy of the current observation window."""
        with self._lock:
            return list(self._window)

    def percentiles(self, qs=(0.5, 0.95, 0.99)) -> Dict[float, float]:
        """Window percentiles; empty dict when nothing was observed."""
        with self._lock:
            v = sorted(self._window)
        if not v:
            return {}
        return {q: v[min(len(v) - 1, int(q * len(v)))] for q in qs}


class MetricsRegistry:
    """Thread-safe instrument factory + exporter.

    ``counter``/``gauge``/``histogram`` return THE instrument for
    ``(name, labels)`` — creating on first ask, reusing afterwards. Asking for
    an existing name with a different instrument type raises (one name, one
    TYPE line in the exposition).
    """

    # pitlint PIT-LOCK: the instrument table and collector list are hit from
    # every producer thread and every exporter scrape — only under _lock
    _guarded_by = {
        "_instruments": "_lock",
        "_kinds": "_lock",
        "_collectors": "_lock",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                                _Instrument] = {}
        self._kinds: Dict[str, str] = {}
        self._collectors: List = []

    def register_collector(self, fn) -> None:
        """Add a zero-arg callable invoked at every export (``snapshot`` /
        ``prometheus_text``) BEFORE instruments are read — the pull-model
        hook for sampled values (process RSS, thread count) that would be
        stale if only written on some producer's cadence. Collectors must be
        cheap and must not raise; a raising collector is dropped from
        subsequent exports (telemetry never breaks the scrape)."""
        with self._lock:
            self._collectors.append(fn)

    def _run_collectors(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        dead = []
        for fn in collectors:
            try:
                fn()
            except Exception:
                dead.append(fn)
        if dead:
            with self._lock:
                self._collectors = [
                    c for c in self._collectors if c not in dead
                ]

    def _get(self, cls, name: str, help: str,
             labels: Optional[Dict[str, str]], **kwargs):
        name = sanitize_metric_name(name)
        key = (name, tuple(sorted((str(k), str(v))
                                  for k, v in (labels or {}).items())))
        with self._lock:
            inst = self._instruments.get(key)
            if inst is None:
                kind = self._kinds.get(name)
                if kind is not None and kind != cls.kind:
                    raise TypeError(
                        f"metric {name!r} already registered as {kind}, "
                        f"cannot re-register as {cls.kind}"
                    )
                inst = cls(name, help, key[1], **kwargs)
                self._instruments[key] = inst
                self._kinds[name] = cls.kind
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r}{dict(key[1])} is a "
                    f"{inst.kind}, not a {cls.kind}"
                )
        return inst

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._get(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None) -> Gauge:
        return self._get(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, str]] = None,
                  window: int = 4096) -> Histogram:
        return self._get(Histogram, name, help, labels, window=window)

    def remove(self, name: str,
               labels: Optional[Dict[str, str]] = None) -> bool:
        """Drop ONE ``(name, labels)`` instrument from the exposition;
        returns whether it existed. For bounded-lifecycle label sets only —
        a retired replica's per-replica gauges must leave ``/metrics``
        instead of exporting its last values forever. The name's KIND stays
        reserved (a later re-registration of the same name as a different
        type still raises), and any live reference a producer still holds
        keeps working — it just no longer exports."""
        name = sanitize_metric_name(name)
        key = (name, tuple(sorted((str(k), str(v))
                                  for k, v in (labels or {}).items())))
        with self._lock:
            return self._instruments.pop(key, None) is not None

    def _sorted_instruments(self) -> List[_Instrument]:
        with self._lock:
            return [self._instruments[k] for k in sorted(self._instruments)]

    def instruments_by_key(self) -> Dict[str, _Instrument]:
        """Every instrument keyed by its ``name{labels}`` exposition key —
        how the time-series/alerting layer resolves a series key back to
        the live instrument (e.g. to read a histogram's exemplars)."""
        return {
            inst.name + _label_suffix(inst.labels): inst
            for inst in self._sorted_instruments()
        }

    # -- exporters -----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able view of every instrument (the ``/statz`` body)."""
        self._run_collectors()
        out: Dict[str, Any] = {"counters": {}, "gauges": {}, "histograms": {}}
        for inst in self._sorted_instruments():
            key = inst.name + _label_suffix(inst.labels)
            if isinstance(inst, Counter):
                out["counters"][key] = inst.value
            elif isinstance(inst, Gauge):
                out["gauges"][key] = inst.value
            elif isinstance(inst, Histogram):
                pcts = inst.percentiles()
                entry = {
                    "count": inst.count,
                    "sum": inst.sum,
                    **{f"p{int(q * 100)}": v for q, v in pcts.items()},
                }
                ex = inst.exemplars()
                if ex:
                    entry["exemplars"] = ex
                out["histograms"][key] = entry
        return out

    def prometheus_text(self) -> str:
        """Prometheus text exposition (version 0.0.4). Histograms export as
        summaries — window quantiles plus exact _sum/_count."""
        self._run_collectors()
        lines: List[str] = []
        seen_header = set()
        for inst in self._sorted_instruments():
            kind = "summary" if isinstance(inst, Histogram) else inst.kind
            if inst.name not in seen_header:
                seen_header.add(inst.name)
                if inst.help:
                    lines.append(f"# HELP {inst.name} {inst.help}")
                lines.append(f"# TYPE {inst.name} {kind}")
            suffix = _label_suffix(inst.labels)
            if isinstance(inst, Histogram):
                for q, v in inst.percentiles().items():
                    q_labels = inst.labels + (("quantile", f"{q:g}"),)
                    lines.append(
                        f"{inst.name}{_label_suffix(q_labels)} {_fmt(v)}"
                    )
                lines.append(f"{inst.name}_sum{suffix} {_fmt(inst.sum)}")
                lines.append(f"{inst.name}_count{suffix} {inst.count}")
            else:
                lines.append(f"{inst.name}{suffix} {_fmt(inst.value)}")
        return "\n".join(lines) + "\n"

    def statz_json(self) -> str:
        return json.dumps(self.snapshot())


def _fmt(v: float) -> str:
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


_DEFAULT = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry (what every layer publishes to when
    not handed an explicit one)."""
    return _DEFAULT


def is_export_process() -> bool:
    """True when this process should export (process 0, or jax not yet
    initialized / single-process).

    Must NEVER force backend initialization: the first device touch CLAIMS
    the chip for this process and fixes the platform, and the export path
    (the HTTP sidecar) may start before the entry point has chosen its
    backend. So jax is only consulted when a backend is ALREADY up; otherwise
    this process is assumed to be the exporter (true for every
    single-process flow, and multi-host jobs initialize jax.distributed
    long before anyone exports)."""
    try:
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return True
        from jax._src import xla_bridge as xb

        if not getattr(xb, "_backends", None):
            return True  # no backend initialized yet — don't trigger one
        return jax.process_index() == 0
    except Exception:
        return True
