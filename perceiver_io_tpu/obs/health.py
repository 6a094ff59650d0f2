"""Dispatch heartbeats and process health.

A device call can wedge: it simply never returns (a hung collective, a dead
peer, a driver fault), and a serving loop built on blocking futures hangs
silently.
A ``Heartbeat`` turns that failure mode into a *signal*: the dispatch loop
arms it when work goes in flight and beats it on every completion; if no beat
arrives within the deadline, the heartbeat reports stalled — ``/healthz``
flips to 503 — and (once per stall episode) dumps a diagnostic snapshot:
every thread's stack, plus whatever queue/stats context the owner's
``diagnostics`` callback supplies.

Heartbeats self-register in a process-wide set so ``healthz()`` can aggregate
without wiring; ``close()`` (or garbage collection) removes them.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

from perceiver_io_tpu.obs import tracing

_HEARTBEATS: "weakref.WeakSet[Heartbeat]" = weakref.WeakSet()
_HEARTBEATS_LOCK = threading.Lock()

# Non-heartbeat health contributors (circuit breakers, future sources):
# anything exposing health_status() -> (name, ok, detail). Registered by the
# resilience layer; obs stays free of upward imports.
_SOURCES: "weakref.WeakSet" = weakref.WeakSet()
_SOURCES_LOCK = threading.Lock()


def register_health_source(source) -> None:
    """Add a ``health_status() -> (name, ok, detail)`` contributor to
    ``healthz()`` aggregation (weakly referenced; GC removes it)."""
    with _SOURCES_LOCK:
        _SOURCES.add(source)


def unregister_health_source(source) -> None:
    with _SOURCES_LOCK:
        _SOURCES.discard(source)


def thread_stacks() -> Dict[str, str]:
    """Formatted stack per live thread, keyed by thread name (the core of the
    stall diagnostic: where is everyone stuck?)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    return {
        names.get(ident, f"thread-{ident}"):
            "".join(traceback.format_stack(frame))
        for ident, frame in sys._current_frames().items()
    }


class Heartbeat:
    """Deadline-monitored liveness signal for one dispatch loop.

    - ``arm()`` when work goes in flight (starts the deadline clock);
    - ``beat()`` on every completion (resets it);
    - ``disarm()`` when nothing is in flight (an idle loop is healthy).

    ``deadline_s=None`` disables monitoring (the heartbeat always reports
    healthy and no monitor thread runs). With a deadline, a daemon monitor
    thread watches for a stall and emits the diagnostic dump — detection
    itself (``stalled()``/``healthy()``) is computed on demand, so a health
    probe never depends on the monitor's cadence.

    ``on_stall`` (optional) is invoked once per stall episode from the
    monitor thread, right before the diagnostic dump — the actuation hook
    (e.g. tripping a circuit breaker open: a wedged dispatch never *fails*,
    so only the stall monitor can observe it).
    """

    def __init__(
        self,
        name: str,
        deadline_s: Optional[float] = None,
        diagnostics: Optional[Callable[[], Dict[str, Any]]] = None,
        on_stall: Optional[Callable[[], None]] = None,
    ):
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {deadline_s}")
        self.name = name
        self.deadline_s = deadline_s
        self._diagnostics = diagnostics
        self._on_stall = on_stall
        self._lock = threading.Lock()
        self._armed = False
        self._last = time.monotonic()
        self._dumped = False
        self._closed = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        with _HEARTBEATS_LOCK:
            _HEARTBEATS.add(self)
        if deadline_s is not None:
            self._monitor = threading.Thread(
                target=self._watch, name=f"{name}-heartbeat", daemon=True
            )
            self._monitor.start()

    # -- the loop's side -----------------------------------------------------

    def arm(self) -> None:
        with self._lock:
            if not self._armed:
                self._armed = True
                self._last = time.monotonic()

    def beat(self) -> None:
        with self._lock:
            self._last = time.monotonic()
            self._dumped = False

    def disarm(self) -> None:
        with self._lock:
            self._armed = False

    # -- the probe's side ----------------------------------------------------

    def stalled(self) -> bool:
        with self._lock:
            return (
                self._armed
                and self.deadline_s is not None
                and time.monotonic() - self._last > self.deadline_s
            )

    def healthy(self) -> bool:
        return not self.stalled()

    def seconds_since_beat(self) -> float:
        with self._lock:
            return time.monotonic() - self._last

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        self._closed.set()
        self.disarm()
        with _HEARTBEATS_LOCK:
            _HEARTBEATS.discard(self)

    # -- stall monitor -------------------------------------------------------

    def _watch(self) -> None:
        poll = max(0.05, min(self.deadline_s / 4.0, 1.0))
        while not self._closed.wait(poll):
            if not self.stalled():
                continue
            if self._on_stall is not None:
                # EVERY poll while stalled, not once per episode: the hook
                # must keep re-asserting for as long as the stall persists
                # (a tripped breaker's cooldown can elapse mid-stall — the
                # re-trip is what keeps it from parking half-open and
                # admitting traffic into a still-wedged dispatch loop)
                try:
                    self._on_stall()
                except Exception as e:  # actuation must not kill the monitor
                    print(f"[obs] heartbeat {self.name!r} on_stall hook "
                          f"failed: {type(e).__name__}: {e}", file=sys.stderr)
            with self._lock:
                if self._dumped:
                    continue
                self._dumped = True
            self._dump()

    def _dump(self) -> None:
        age = self.seconds_since_beat()
        diag: Dict[str, Any] = {}
        if self._diagnostics is not None:
            try:
                diag = self._diagnostics()
            except Exception as e:  # a broken callback must not kill the dump
                diag = {"diagnostics_error": f"{type(e).__name__}: {e}"}
        stacks = thread_stacks()
        print(
            f"[obs] heartbeat {self.name!r} STALLED: no dispatch completion "
            f"for {age:.1f}s (deadline {self.deadline_s}s) — diagnostic "
            f"snapshot follows",
            file=sys.stderr,
        )
        for key, val in diag.items():
            print(f"[obs]   {key}: {val}", file=sys.stderr)
        for tname, stack in stacks.items():
            print(f"[obs]   -- thread {tname} --\n{stack}",
                  file=sys.stderr, end="")
        sys.stderr.flush()
        tracing.event(
            "heartbeat_stall", heartbeat=self.name,
            seconds_since_beat=round(age, 3), deadline_s=self.deadline_s,
            diagnostics=diag, threads=sorted(stacks),
        )


def healthz() -> Tuple[bool, Dict[str, Any]]:
    """Aggregate health over every live heartbeat and registered health
    source (circuit breakers): ``(ok, detail)``.

    A process with no heartbeats or sources is healthy (nothing claims to be
    dispatching); any stalled heartbeat or unhealthy source (an OPEN breaker)
    makes it unhealthy.
    """
    with _HEARTBEATS_LOCK:
        beats = list(_HEARTBEATS)
    detail: Dict[str, Any] = {}
    ok = True
    for hb in sorted(beats, key=lambda h: h.name):
        stalled = hb.stalled()
        detail[hb.name] = {
            "stalled": stalled,
            "seconds_since_beat": round(hb.seconds_since_beat(), 3),
            "deadline_s": hb.deadline_s,
        }
        ok = ok and not stalled
    with _SOURCES_LOCK:
        sources = list(_SOURCES)
    source_detail: Dict[str, Any] = {}
    for src in sources:
        try:
            name, src_ok, src_info = src.health_status()
        except Exception as e:  # a broken source must not break the probe
            name, src_ok, src_info = (
                f"{type(src).__name__}", False,
                {"error": f"{type(e).__name__}: {e}"},
            )
        source_detail[name] = src_info
        ok = ok and src_ok
    body: Dict[str, Any] = {
        "status": "ok" if ok else "degraded", "heartbeats": detail,
    }
    if source_detail:
        body["sources"] = dict(sorted(source_detail.items()))
    return ok, body
