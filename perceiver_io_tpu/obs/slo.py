"""Declarative serving SLOs: objectives, error-budget burn rate, and the
measured capacity model.

Three pieces, composing the r7 registry and r9 health aggregation into
SLO-grade evidence:

- :class:`SLO` — a declarative objective: a latency target (a request
  answered within ``latency_target_s`` is *good*) and an availability target
  (the fraction of requests that must be good). The error budget is
  ``1 - availability_target``.
- :class:`SLOTracker` — per-request accounting against an SLO over a bounded
  window: ``slo_good_fraction`` and ``slo_error_budget_burn_rate`` gauges
  (burn rate = observed bad fraction / error budget — 1.0 means spending the
  budget exactly as fast as it accrues, >1 means burning it down), breach
  counters by reason, and a ``healthz()`` source that degrades the process
  when the burn rate crosses ``burn_alert`` (the same aggregation path as a
  stalled heartbeat or an open breaker, so ``/healthz`` 503s on a burning
  SLO too).
- :func:`fit_capacity` — the capacity model over an offered-load sweep
  (``tools/load_bench.py``): the service-time floor from the light-load
  points, the knee where p99 departs that floor (or shedding begins, or
  achieved throughput stops tracking offered), the achieved-throughput
  plateau as the capacity estimate, and the max offered rate that still
  meets a given SLO.

Pure host-side python over the registry — importable before jax initializes
a backend, provable on CPU.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

from perceiver_io_tpu.obs import health as _health
from perceiver_io_tpu.obs.registry import MetricsRegistry, get_registry

__all__ = ["SLO", "SLOTracker", "fit_capacity"]


@dataclasses.dataclass(frozen=True)
class SLO:
    """One serving objective.

    ``latency_target_s``: a request is *good* when it completes successfully
    within this many seconds (shed/failed requests are always bad).
    ``availability_target``: the fraction of requests that must be good —
    the error budget is its complement. ``burn_alert``: burn rate above
    which the tracker reports unhealthy (None disables the health wire).
    ``min_samples``: the health wire stays quiet below this many recorded
    requests — one bad first request must not 503 a fresh process.

    ``ttft_target_s`` / ``itl_target_s`` (optional) are the STREAM-shaped
    objectives (r21): a decode stream is good against each set target when
    its time-to-first-token / mean inter-token latency lands inside it.
    Request latency is the wrong signal for a token stream — a stream can
    meet a whole-request deadline while every token arrives in stalls —
    so each stream signal gets its own window and burn rate
    (``slo_stream_burn_rate{signal=}``), sharing this SLO's availability
    target, burn alert, and min-samples guard.
    """

    latency_target_s: float
    availability_target: float = 0.999
    name: str = "serving"
    burn_alert: Optional[float] = 2.0
    min_samples: int = 20
    ttft_target_s: Optional[float] = None
    itl_target_s: Optional[float] = None

    def __post_init__(self):
        if self.latency_target_s <= 0:
            raise ValueError(
                f"latency_target_s must be positive, got {self.latency_target_s}"
            )
        if not 0.0 < self.availability_target < 1.0:
            raise ValueError(
                "availability_target must lie in (0, 1) — a 1.0 target has "
                f"zero error budget, got {self.availability_target}"
            )
        for field in ("ttft_target_s", "itl_target_s"):
            v = getattr(self, field)
            if v is not None and v <= 0:
                raise ValueError(f"{field} must be positive, got {v}")

    @property
    def stream_signals(self) -> Dict[str, float]:
        """The configured stream objectives: ``{signal: target_s}`` over
        ``ttft``/``itl`` (empty when this SLO is request-only)."""
        out = {}
        if self.ttft_target_s is not None:
            out["ttft"] = self.ttft_target_s
        if self.itl_target_s is not None:
            out["itl"] = self.itl_target_s
        return out

    @property
    def error_budget(self) -> float:
        return 1.0 - self.availability_target


class SLOTracker:
    """Per-request accounting against one :class:`SLO` over a bounded window.

    ``record(latency_s=..., ok=...)`` classifies each request: good when it
    completed (``ok=True``) within the latency target; bad otherwise, with
    the breach reason counted (``latency`` vs ``error`` — shed requests ride
    the error reason). The window is bounded (an engine serves indefinitely)
    and all derived numbers — good fraction, burn rate — are over that
    window, which is what a burn-rate alert wants: recent behavior, not the
    lifetime average.

    Thread-safe; registers as a ``healthz()`` source when the SLO carries a
    ``burn_alert`` (``close()`` unregisters).
    """

    def __init__(self, slo: SLO, registry: Optional[MetricsRegistry] = None,
                 labels: Optional[Dict[str, str]] = None, window: int = 4096):
        self.slo = slo
        reg = registry if registry is not None else get_registry()
        self._lock = threading.Lock()
        self._window: deque = deque(maxlen=window)  # True = good
        self._good_in_window = 0
        base = {"slo": slo.name, **(labels or {})}
        self._m_target = reg.gauge(
            "slo_latency_target_seconds",
            "latency bound under which a served request counts good", base)
        self._m_avail = reg.gauge(
            "slo_availability_target",
            "fraction of requests that must be good", base)
        self._m_target.set(slo.latency_target_s)
        self._m_avail.set(slo.availability_target)
        self._m_requests = reg.counter(
            "slo_requests_total", "requests classified against the SLO", base)
        self._m_breaches = {
            reason: reg.counter(
                "slo_breaches_total", "bad requests by breach reason",
                {**base, "reason": reason})
            for reason in ("latency", "error")
        }
        self._m_good = reg.gauge(
            "slo_good_fraction", "good requests / all, over the window", base)
        self._m_burn = reg.gauge(
            "slo_error_budget_burn_rate",
            "bad fraction / error budget over the window (1.0 = spending "
            "the budget exactly as it accrues; >1 = burning it down)", base)
        # -- the stream signals (r21): one window + burn gauge per
        # configured target, same availability budget/alert as requests
        self._stream_windows: Dict[str, deque] = {}
        self._stream_good: Dict[str, int] = {}
        self._m_stream_burn: Dict[str, Any] = {}
        self._m_stream_breaches: Dict[str, Any] = {}
        if slo.stream_signals:
            self._m_ttft_target = reg.gauge(
                "slo_ttft_target_seconds",
                "TTFT bound under which a decode stream counts good", base)
            self._m_itl_target = reg.gauge(
                "slo_itl_target_seconds",
                "mean inter-token-latency bound under which a decode "
                "stream counts good", base)
            if slo.ttft_target_s is not None:
                self._m_ttft_target.set(slo.ttft_target_s)
            if slo.itl_target_s is not None:
                self._m_itl_target.set(slo.itl_target_s)
        for signal in slo.stream_signals:
            self._stream_windows[signal] = deque(maxlen=window)
            self._stream_good[signal] = 0
            sig_labels = {**base, "signal": signal}
            self._m_stream_burn[signal] = reg.gauge(
                "slo_stream_burn_rate",
                "bad stream fraction / error budget over the window, per "
                "token-latency signal (ttft|itl)", sig_labels)
            self._m_stream_breaches[signal] = reg.counter(
                "slo_stream_breaches_total",
                "decode streams missing a token-latency target, by signal",
                sig_labels)
        self._name = ":".join(["slo", slo.name]
                              + [v for _, v in sorted((labels or {}).items())])
        self._registered = slo.burn_alert is not None
        if self._registered:
            _health.register_health_source(self)

    def record(self, latency_s: Optional[float] = None, ok: bool = True) -> None:
        """Classify one finished (or shed/failed) request."""
        good = bool(ok) and (
            latency_s is None or latency_s <= self.slo.latency_target_s
        )
        with self._lock:
            if len(self._window) == self._window.maxlen and self._window[0]:
                self._good_in_window -= 1
            self._window.append(good)
            if good:
                self._good_in_window += 1
            n, g = len(self._window), self._good_in_window
        self._m_requests.inc()
        if not good:
            self._m_breaches["latency" if ok else "error"].inc()
        frac = g / n
        self._m_good.set(frac)
        self._m_burn.set((1.0 - frac) / self.slo.error_budget)

    def record_stream(self, ttft_s: Optional[float] = None,
                      itl_s: Optional[float] = None,
                      ok: bool = True) -> None:
        """Classify one finished decode stream against the configured
        stream signals: ``ttft_s`` (enqueue -> first token) and ``itl_s``
        (mean inter-token latency) each against their own target. A stream
        that died (``ok=False``) is bad on every configured signal — a
        killed stream never met its token deadline. No-op on a
        request-only SLO."""
        for signal, target in self.slo.stream_signals.items():
            v = ttft_s if signal == "ttft" else itl_s
            if ok and v is None:
                continue  # signal unmeasured this stream (e.g. 0 tokens)
            good = bool(ok) and v is not None and v <= target
            with self._lock:
                w = self._stream_windows[signal]
                if len(w) == w.maxlen and w[0]:
                    self._stream_good[signal] -= 1
                w.append(good)
                if good:
                    self._stream_good[signal] += 1
                n, g = len(w), self._stream_good[signal]
            if not good:
                self._m_stream_breaches[signal].inc()
            self._m_stream_burn[signal].set(
                (1.0 - g / n) / self.slo.error_budget)

    def good_fraction(self) -> float:
        with self._lock:
            return (self._good_in_window / len(self._window)
                    if self._window else 1.0)

    def burn_rate(self) -> float:
        return (1.0 - self.good_fraction()) / self.slo.error_budget

    def stream_burn_rate(self, signal: Optional[str] = None) -> float:
        """The windowed stream burn rate — one signal, or the max across
        the configured ones (the scrape's single per-replica number).
        0.0 on a request-only SLO or an empty window."""
        signals = ([signal] if signal is not None
                   else list(self._stream_windows))
        worst = 0.0
        with self._lock:
            for s in signals:
                w = self._stream_windows.get(s)
                if not w:
                    continue
                frac = self._stream_good[s] / len(w)
                worst = max(worst, (1.0 - frac) / self.slo.error_budget)
        return worst

    def stream_sample_count(self, signal: str) -> int:
        with self._lock:
            w = self._stream_windows.get(signal)
            return len(w) if w is not None else 0

    def sample_count(self) -> int:
        with self._lock:
            return len(self._window)

    # -- healthz() source ----------------------------------------------------

    def health_status(self):
        burn = self.burn_rate()
        n = self.sample_count()
        alert = self.slo.burn_alert
        ok = (alert is None or n < self.slo.min_samples or burn <= alert)
        detail = {
            "burn_rate": round(burn, 4),
            "good_fraction": round(self.good_fraction(), 4),
            "samples": n,
            "burn_alert": alert,
        }
        # a burning stream signal degrades like a burning request signal
        # (same alert threshold, same per-signal min-samples guard)
        for signal in self._stream_windows:
            s_burn = self.stream_burn_rate(signal)
            s_n = self.stream_sample_count(signal)
            detail[f"stream_{signal}_burn_rate"] = round(s_burn, 4)
            detail[f"stream_{signal}_samples"] = s_n
            if (alert is not None and s_n >= self.slo.min_samples
                    and s_burn > alert):
                ok = False
        return self._name, ok, detail

    def close(self) -> None:
        if self._registered:
            _health.unregister_health_source(self)
            self._registered = False


def fit_capacity(
    points: Sequence[Dict[str, Any]],
    slo: Optional[SLO] = None,
    p99_departure_factor: float = 3.0,
    sustain_fraction: float = 0.9,
    shed_tolerance: float = 1e-3,
) -> Dict[str, Any]:
    """Fit the capacity model from an offered-load sweep.

    ``points``: one dict per offered rate, carrying ``offered_rps``,
    ``achieved_rps``, ``p50_s``, ``p99_s``, ``shed_rate`` (as
    ``tools/load_bench.py`` measures them). Returns:

    - ``service_floor_s`` / ``p99_floor_s``: the light-load latency floor
      (min p50 / min p99 across the sweep) — the service time itself;
    - ``knee_rps``: the highest offered rate the system still *sustains*
      (achieved ≥ ``sustain_fraction`` × offered, shedding within
      ``shed_tolerance`` — an exact-zero bar would let one transient blip
      in a thousand-request point collapse the knee to 0 — and p99 within
      ``p99_departure_factor`` × the p99 floor) — where p99 departs the
      service-time floor;
    - ``capacity_rps``: the achieved-throughput plateau (max achieved across
      the sweep) — what the system actually serves under overload;
    - ``slo_sustainable_rps``: the highest offered rate meeting ``slo``
      (p99 within the latency target, shed rate within the error budget),
      present only when an SLO is given.

    0.0 knee/sustainable values mean no point qualified (the sweep started
    past saturation).
    """
    pts = sorted(points, key=lambda p: float(p["offered_rps"]))
    if not pts:
        raise ValueError("fit_capacity needs at least one sweep point")
    p50s = [float(p["p50_s"]) for p in pts]
    p99s = [float(p["p99_s"]) for p in pts]
    floor_p50 = min(p50s)
    floor_p99 = min(p99s)

    def sustains(p) -> bool:
        return (
            float(p["achieved_rps"])
            >= sustain_fraction * float(p["offered_rps"])
            and float(p["shed_rate"]) <= shed_tolerance
            and float(p["p99_s"]) <= p99_departure_factor * floor_p99
        )

    knee = 0.0
    for p in pts:
        if sustains(p):
            knee = float(p["offered_rps"])
        else:
            break  # the knee is where sustained operation ENDS
    out: Dict[str, Any] = {
        "service_floor_s": floor_p50,
        "p99_floor_s": floor_p99,
        "knee_rps": knee,
        "capacity_rps": max(float(p["achieved_rps"]) for p in pts),
        "points": len(pts),
    }
    if slo is not None:
        ok_rates = [
            float(p["offered_rps"]) for p in pts
            if float(p["p99_s"]) <= slo.latency_target_s
            and float(p["shed_rate"]) <= slo.error_budget
        ]
        out["slo_sustainable_rps"] = max(ok_rates) if ok_rates else 0.0
        out["slo"] = {
            "name": slo.name,
            "latency_target_s": slo.latency_target_s,
            "availability_target": slo.availability_target,
        }
    return out
