"""Unified runtime telemetry for the framework.

One subsystem, four pieces, every layer wired through it:

- :mod:`registry` — the process-wide thread-safe metrics registry (counters,
  gauges, bounded histograms with p50/p95/p99); the single source of truth
  the serving engine, the Trainer/``MetricsLogger``, and the watchdog all
  publish to.
- :mod:`tracing` — timed spans and discrete events. ``span`` is always on:
  every finished span (start, end, parent, thread, fields) is kept in memory,
  bounded per span name, read back with ``spans()``, and mirrored while open
  as a profiler annotation ``pio.<name>`` once jax is imported; ``add_span``
  enters one measured elsewhere (the compile path's ``jax.trace`` /
  ``jax.lower`` / ``jax.backend_compile``, the Trainer's ``train.step``, a
  module's own ``import``).
  ``event`` and the JSONL sink (compiles, warmups, stalls; every record
  dual-stamped wall + monotonic and pid-labeled) stay opt-in.
- :mod:`reqtrace` — distributed request tracing: ``TraceContext``
  propagation across router → RPC → replica → engine, span records, and
  cross-process trace assembly with clock alignment and tail sampling.
- :mod:`health` — dispatch heartbeats with stall detection + diagnostic
  thread-stack dumps, aggregated by ``healthz()``.
- :mod:`watchdog` — the in-loop self-profiler: periodic short device traces
  analyzed in-process (``utils/xplane.py`` lower-quartile discipline) into
  live device-step-time / MFU / recompile gauges.
- :mod:`http` — the localhost sidecar serving ``/metrics`` (Prometheus text),
  ``/healthz``, and ``/statz``.
- :mod:`slo` — declarative serving objectives: per-request accounting into
  error-budget burn-rate gauges (wired into ``healthz()``), and the capacity
  model fitted from an offered-load sweep (``tools/load_bench.py``).
- :mod:`process` — process self-metrics (RSS, uptime, threads, GC) refreshed
  at scrape time via the registry's collector hook.
- :mod:`fleet` — multi-replica aggregation: the fleet-aware ``healthz()``
  source (one replica's open breaker degrades that replica's label, never
  the router's status code while other replicas serve) and the per-replica
  labeled gauges the router publishes from its scrape loop.
- :mod:`timeseries` — the historical half: a bounded ring-buffer
  ``SeriesStore`` with windowed ``last``/``rate``/``delta`` queries, a
  cadenced ``Sampler`` over every registry instrument (rotating-JSONL
  persistence, served live as ``/seriesz``), and per-replica fleet
  ingestion from the router's scrape loop.
- :mod:`alerts` — declarative alerting over the series store:
  ``AlertRule`` (threshold / rate-of-change / absence over a window, with
  ``for_s`` hold-down and hysteresis), evaluated into EventLog
  firing/resolved events (exemplar trace-linked), ``alert_state{rule=}``
  gauges, and a ``healthz()`` source — a firing page-class alert degrades
  ``/healthz`` like a stall, a breaker, or SLO burn.

Importing this package never initializes a jax backend — entry points stay
free to pick their platform (``ensure_cpu_only``) first.
"""

from perceiver_io_tpu.obs.health import (
    Heartbeat,
    healthz,
    register_health_source,
    thread_stacks,
    unregister_health_source,
)
from perceiver_io_tpu.obs.fleet import FleetHealth, ReplicaGauges
from perceiver_io_tpu.obs.http import ObsServer
from perceiver_io_tpu.obs.process import install_process_metrics
from perceiver_io_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    is_export_process,
    sanitize_metric_name,
)
from perceiver_io_tpu.obs.reqtrace import (
    SPAN_NAMES,
    TraceBuffer,
    TraceContext,
    assemble_traces,
    maybe_trace,
    new_span_id,
    record_span,
    tail_sample,
)
from perceiver_io_tpu.obs.alerts import (
    AlertEngine,
    AlertRule,
    load_rules as load_alert_rules,
)
from perceiver_io_tpu.obs.slo import SLO, SLOTracker, fit_capacity
from perceiver_io_tpu.obs.timeseries import (
    Sampler,
    SeriesStore,
    get_series_store,
    install_series_store,
    series_key,
)
from perceiver_io_tpu.obs.tracing import (
    EventLog,
    add_span,
    configure_event_log,
    event,
    get_event_log,
    span,
    spans,
)
from perceiver_io_tpu.obs.watchdog import SelfProfiler, install_compile_counter

__all__ = [
    "AlertEngine",
    "AlertRule",
    "Counter",
    "EventLog",
    "FleetHealth",
    "Gauge",
    "Heartbeat",
    "Histogram",
    "MetricsRegistry",
    "ObsServer",
    "ReplicaGauges",
    "SLO",
    "SLOTracker",
    "SPAN_NAMES",
    "Sampler",
    "SelfProfiler",
    "SeriesStore",
    "TraceBuffer",
    "TraceContext",
    "add_span",
    "assemble_traces",
    "configure_event_log",
    "event",
    "fit_capacity",
    "get_event_log",
    "get_registry",
    "get_series_store",
    "healthz",
    "install_series_store",
    "load_alert_rules",
    "series_key",
    "install_compile_counter",
    "install_process_metrics",
    "is_export_process",
    "maybe_trace",
    "new_span_id",
    "record_span",
    "register_health_source",
    "sanitize_metric_name",
    "span",
    "spans",
    "tail_sample",
    "thread_stacks",
    "unregister_health_source",
]
