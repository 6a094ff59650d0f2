"""Circuit breaker for a dispatch loop: fail fast while the device is down.

When the device wedges or PJRT starts throwing, every queued request is dead
weight: it occupies queue slots, burns dispatch attempts, and holds its
caller in a blocking ``result()``. The breaker turns *repeated* failure into
an admission-control signal:

- **closed** (healthy): requests flow; each dispatch outcome is recorded.
  ``failure_threshold`` consecutive failures — or an explicit :meth:`trip`
  from the heartbeat's stall monitor — open it.
- **open**: admission fast-fails (:class:`BreakerOpen`) for ``cooldown_s``.
  No queue growth, no doomed dispatches, callers learn immediately.
- **half-open**: after the cooldown, the next :meth:`allow` lets traffic
  probe the device. One recorded success closes the breaker; a failure (or a
  stall trip) re-opens it with a fresh cooldown.

State is exported to the metrics registry (``breaker_state`` gauge: 0 closed,
1 half-open, 2 open; ``breaker_transitions_total`` counter per target state)
and to ``healthz()`` — an open breaker makes ``/healthz`` 503 via the obs
health-source registration, so orchestrators see the outage the same way they
see a heartbeat stall.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.resilience.retry import RejectedError

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

_STATE_CODES = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}


class BreakerOpen(RejectedError):
    """Admission refused: the circuit breaker is open (device presumed down)."""


class CircuitBreaker:
    """Thread-safe closed → open → half-open breaker around one dispatch loop.

    ``failure_threshold`` consecutive ``record_failure`` calls open it;
    ``trip()`` opens it immediately (the heartbeat-stall path); ``cooldown_s``
    after opening, one probe round is admitted and its outcome decides.

    ``clock`` is injectable for deterministic tests.
    """

    def __init__(
        self,
        name: str = "device",
        failure_threshold: int = 5,
        cooldown_s: float = 5.0,
        registry: Optional[obs.MetricsRegistry] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if failure_threshold < 1:
            raise ValueError(
                f"failure_threshold must be >= 1, got {failure_threshold}"
            )
        if cooldown_s <= 0:
            raise ValueError(f"cooldown_s must be > 0, got {cooldown_s}")
        self.name = name
        self.failure_threshold = failure_threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self._lock = threading.Lock()
        self._state = CLOSED
        self._consecutive_failures = 0
        self._opened_at = 0.0
        self._last_reason = ""

        reg = registry if registry is not None else obs.get_registry()
        labels = {"breaker": name}
        self._m_state = reg.gauge(
            "breaker_state", "0 closed, 1 half-open, 2 open", labels)
        self._m_transitions = {
            s: reg.counter(
                "breaker_transitions_total", "state transitions by target",
                {**labels, "to": s})
            for s in (CLOSED, OPEN, HALF_OPEN)
        }
        self._m_state.set(0)
        obs.register_health_source(self)

    # -- state machine -------------------------------------------------------

    def _transition(self, state: str, reason: str = "") -> None:
        # callers hold self._lock
        if state == self._state:
            return
        self._state = state
        self._last_reason = reason
        if state == OPEN:
            self._opened_at = self._clock()
        if state != CLOSED:
            # entering OPEN always starts a fresh failure count; HALF_OPEN
            # keeps it so a failed probe reopens on the first failure
            self._consecutive_failures = (
                0 if state == OPEN else self._consecutive_failures
            )
        self._m_state.set(_STATE_CODES[state])
        self._m_transitions[state].inc()
        obs.event("breaker_transition", breaker=self.name, to=state,
                  reason=reason)

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def allow(self) -> bool:
        """Admission check: True when requests may enter. An open breaker
        whose cooldown elapsed flips to half-open and admits the probe."""
        with self._lock:
            if self._state == OPEN:
                if self._clock() - self._opened_at >= self.cooldown_s:
                    self._transition(HALF_OPEN, "cooldown elapsed")
                    return True
                return False
            return True

    def check(self) -> None:
        """Raise :class:`BreakerOpen` unless :meth:`allow` admits."""
        if not self.allow():
            raise BreakerOpen(
                f"circuit breaker {self.name!r} is open "
                f"({self._last_reason or 'consecutive dispatch failures'}); "
                f"retry after {self.cooldown_s:g}s cooldown"
            )

    def record_success(self) -> None:
        with self._lock:
            self._consecutive_failures = 0
            if self._state == HALF_OPEN:
                self._transition(CLOSED, "probe succeeded")

    def record_failure(self, error: Optional[BaseException] = None) -> None:
        reason = f"{type(error).__name__}: {error}" if error else "failure"
        with self._lock:
            self._consecutive_failures += 1
            if self._state == HALF_OPEN:
                self._transition(OPEN, f"probe failed ({reason})")
            elif (self._state == CLOSED
                  and self._consecutive_failures >= self.failure_threshold):
                self._transition(
                    OPEN,
                    f"{self._consecutive_failures} consecutive failures "
                    f"(last: {reason})",
                )

    def trip(self, reason: str = "tripped") -> None:
        """Open immediately regardless of counts — the heartbeat-stall hook
        (a wedged dispatch never *fails*, it just never completes). The
        stall monitor re-trips on every poll while the stall persists, so an
        already-open breaker EXTENDS its cooldown window here: a wedge
        outlasting ``cooldown_s`` must not park the breaker half-open,
        admitting traffic behind a worker still stuck in the device call."""
        with self._lock:
            if self._state == OPEN:
                self._opened_at = self._clock()
                self._last_reason = reason
            else:
                self._transition(OPEN, reason)

    # -- obs integration -----------------------------------------------------

    def health_status(self) -> Tuple[str, bool, Dict[str, Any]]:
        """The obs health-source contract: ``(name, ok, detail)``. Open =
        unhealthy; half-open is probing and counts as healthy (traffic is
        admitted again)."""
        with self._lock:
            state = self._state
            detail = {
                "state": state,
                "consecutive_failures": self._consecutive_failures,
                "reason": self._last_reason,
            }
            if state == OPEN:
                detail["open_for_s"] = round(self._clock() - self._opened_at, 3)
        return f"breaker:{self.name}", state != OPEN, detail

    def close(self) -> None:
        """Deregister from ``healthz()`` (engines call this on shutdown)."""
        obs.unregister_health_source(self)
