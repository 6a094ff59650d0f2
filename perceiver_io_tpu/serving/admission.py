"""Admission control at the router's front door: priority classes,
per-client token-bucket quotas, and weighted-fair queueing.

The r12 router treats every request identically — one global queue limit,
FIFO through the dispatch pool. At fleet scale that is exactly wrong: one
bursting client fills the shared queue and every OTHER client's p99 inherits
the backlog. This module gives the router the three standard isolation
primitives, composed so an over-quota client degrades *its own* service
class while the rest of the fleet's tail stays flat:

- :class:`PriorityClass` — a named class with a scheduling ``weight`` and a
  bounded queue share. Requests name their class (``Router.submit(...,
  priority="gold")``) or inherit the controller's default.
- **per-client token buckets** — each distinct ``client`` id draws from its
  own bucket (``rate_per_s`` sustained, ``burst`` ceiling). An empty bucket
  sheds the request *at admission* with a honestly classified
  :class:`~perceiver_io_tpu.resilience.RejectedError` (``reason="quota"``):
  the failover policy treats it exactly like an engine-side rejection, and
  the shed burns the CLIENT'S class SLO, nobody else's.
- **weighted-fair queueing** — admitted requests enter per-class FIFO queues
  tagged with start-time-fair virtual finish times; the dispatch pool pops
  the globally smallest tag. Under contention each backlogged class receives
  service proportional to its weight — a flooded bronze queue cannot starve
  gold — while an idle system degenerates to plain FIFO (tags only matter
  when there is a backlog to order).

Shedding is bounded per CLASS, not globally: each class owns
``queue_limit`` slots (its share of the controller's total, weight-
proportional unless set explicitly), so a class that outruns its share
sheds with ``reason="class_queue_full"`` while the other classes' slots
stay free. Every admission outcome is counted
(``admission_requests_total`` / ``admission_shed_total{reason=}``), queue
state is live (``admission_queue_depth``, ``admission_wait_seconds``), and
each class gets its own :class:`~perceiver_io_tpu.obs.slo.SLOTracker` so
``slo_error_budget_burn_rate{class=...}`` shows exactly whose budget a
noisy neighbor burned (its own).

The ``router.admit`` fault site fires inside :meth:`AdmissionController.
admit` before any token or queue slot is consumed — a chaos drill can
raise/hang the admission edge without corrupting accounting.

Pure host-side python (stdlib + obs + resilience); importable before jax
initializes, like the rest of ``serving``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.obs.slo import SLO, SLOTracker
from perceiver_io_tpu.resilience import RejectedError, faults

__all__ = [
    "AdmissionController",
    "AdmissionTicket",
    "PriorityClass",
    "TokenBucket",
    "parse_priority_classes",
]

FAULT_SITE = "router.admit"

# distinct per-client token buckets kept live; past the cap the least-
# recently-seen bucket is evicted (a returning client restarts with a full
# burst — bounded memory beats perfect accounting for abandoned client ids)
_MAX_CLIENT_BUCKETS = 4096


@dataclasses.dataclass(frozen=True)
class PriorityClass:
    """One service class: scheduling ``weight`` (relative share of dispatch
    under contention) and an optional explicit per-class ``queue_limit``
    (None = a weight-proportional share of the controller's total)."""

    name: str
    weight: float = 1.0
    queue_limit: Optional[int] = None

    def __post_init__(self):
        if not self.name:
            raise ValueError("priority class needs a name")
        if self.weight <= 0:
            raise ValueError(
                f"class {self.name!r}: weight must be positive, "
                f"got {self.weight}")
        if self.queue_limit is not None and self.queue_limit < 1:
            raise ValueError(
                f"class {self.name!r}: queue_limit must be >= 1")


def parse_priority_classes(text: str) -> List[PriorityClass]:
    """``"gold:8,silver:4,bronze:1"`` → priority classes (the CLI grammar;
    a bare name gets weight 1)."""
    classes = []
    for clause in filter(None, (c.strip() for c in text.split(","))):
        name, _, weight = clause.partition(":")
        classes.append(PriorityClass(
            name=name.strip(), weight=float(weight) if weight else 1.0))
    if not classes:
        raise ValueError(f"no priority classes in {text!r}")
    names = [c.name for c in classes]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate priority class names in {names}")
    return classes


class TokenBucket:
    """The standard leaky-bucket quota: ``rate_per_s`` sustained refill up
    to a ``burst`` ceiling. Monotonic-clock; callers serialize access (the
    controller holds its lock)."""

    __slots__ = ("rate_per_s", "burst", "tokens", "_t_last")

    def __init__(self, rate_per_s: float, burst: float,
                 now: Optional[float] = None):
        if rate_per_s <= 0:
            raise ValueError(f"rate_per_s must be positive, got {rate_per_s}")
        if burst < 1:
            raise ValueError(f"burst must be >= 1, got {burst}")
        self.rate_per_s = float(rate_per_s)
        self.burst = float(burst)
        self.tokens = float(burst)  # a fresh client starts with a full burst
        self._t_last = time.monotonic() if now is None else now

    def try_take(self, now: Optional[float] = None, n: float = 1.0) -> bool:
        now = time.monotonic() if now is None else now
        if now > self._t_last:
            self.tokens = min(self.burst,
                              self.tokens + (now - self._t_last)
                              * self.rate_per_s)
            self._t_last = now
        if self.tokens >= n:
            self.tokens -= n
            return True
        return False


class AdmissionTicket:
    """One admitted request's accounting handle: its class, client id, and
    admission stamp (the WFQ wait histogram's anchor)."""

    __slots__ = ("cls", "client", "t_admit")

    def __init__(self, cls: str, client: Optional[str], t_admit: float):
        self.cls = cls
        self.client = client
        self.t_admit = t_admit


class AdmissionController:
    """Priority classes + per-client quotas + WFQ over one router.

    ``admit()`` is the gate (sheds raise :class:`RejectedError` with a
    ``reason`` attribute); ``enqueue()``/``pop()`` are the WFQ the router's
    dispatch pool drives; ``on_result()`` closes each request's accounting
    (per-class SLO classification).

    ``quota`` (rate, burst) applies PER DISTINCT ``client`` id — each
    client draws from its own bucket — and ``client_quotas`` overrides the
    default for named clients (a paying tenant's bigger bucket; with no
    default ``quota``, ONLY the named clients are limited). Requests with
    no client id bypass quotas (the operator's own traffic); classes and
    WFQ still apply. ``client_classes`` maps a client id to its class when
    the caller does not name one explicitly.
    """

    # pitlint PIT-LOCK: queues, depths, buckets, and the virtual clock are
    # hit from every submitter and every dispatch-pool worker — only under
    # _lock
    _guarded_by = {
        "_queues": "_lock",
        "_depth": "_lock",
        "_buckets": "_lock",
        "_finish": "_lock",
        "_vtime": "_lock",
        "_m_shed": "_lock",
    }

    def __init__(
        self,
        classes: Optional[Sequence[PriorityClass]] = None,
        default_class: Optional[str] = None,
        quota: Optional[Tuple[float, float]] = None,
        client_quotas: Optional[Dict[str, Tuple[float, float]]] = None,
        client_classes: Optional[Dict[str, str]] = None,
        queue_limit: int = 256,
        slo: Optional[SLO] = None,
        name: str = "router",
        registry: Optional[obs.MetricsRegistry] = None,
    ):
        classes = list(classes) if classes else [PriorityClass("default")]
        names = [c.name for c in classes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate priority class names in {names}")
        if queue_limit < len(classes):
            raise ValueError(
                f"queue_limit {queue_limit} below one slot per class "
                f"({len(classes)} classes)")
        self.name = name
        self.classes: Dict[str, PriorityClass] = {c.name: c for c in classes}
        self.default_class = default_class or classes[0].name
        if self.default_class not in self.classes:
            raise ValueError(
                f"default class {self.default_class!r} not among {names}")
        self._client_classes = dict(client_classes or {})
        unknown = set(self._client_classes.values()) - set(self.classes)
        if unknown:
            raise ValueError(
                f"client_classes map to unknown classes {sorted(unknown)}")
        if quota is not None:
            TokenBucket(*quota)  # validate rate/burst eagerly
        self.quota = quota
        self.client_quotas = dict(client_quotas or {})
        for spec in self.client_quotas.values():
            TokenBucket(*spec)
        # weight-proportional queue shares (explicit per-class limits win);
        # every class gets at least one slot
        total_w = sum(c.weight for c in classes)
        self._limits = {
            c.name: (c.queue_limit if c.queue_limit is not None
                     else max(1, int(queue_limit * c.weight / total_w)))
            for c in classes
        }
        self._lock = threading.Lock()
        self._queues: Dict[str, deque] = {n: deque() for n in self.classes}
        self._depth: Dict[str, int] = {n: 0 for n in self.classes}
        self._buckets: "OrderedDict[str, TokenBucket]" = OrderedDict()
        self._finish: Dict[str, float] = {n: 0.0 for n in self.classes}
        self._vtime = 0.0
        reg = registry if registry is not None else obs.get_registry()
        self.registry = reg
        self._m_admitted = {
            n: reg.counter(
                "admission_requests_total",
                "requests admitted through the class gate",
                {"router": name, "class": n})
            for n in self.classes
        }
        self._m_shed: Dict[Tuple[str, str], Any] = {}
        self._m_depth = {
            n: reg.gauge(
                "admission_queue_depth",
                "requests waiting in this class's WFQ queue",
                {"router": name, "class": n})
            for n in self.classes
        }
        self._m_wait = {
            n: reg.histogram(
                "admission_wait_seconds",
                "admission → WFQ dispatch pick-up",
                {"router": name, "class": n})
            for n in self.classes
        }
        # per-class SLO accounting: the noisy-neighbor verdict is that the
        # abuser's class burns ITS budget while the victim's stays whole.
        # burn_alert=None — per-class burn must not 503 the router's
        # /healthz (the router-level SLO owns the health wire)
        self._trackers: Dict[str, SLOTracker] = {}
        if slo is not None:
            for n in self.classes:
                self._trackers[n] = SLOTracker(
                    dataclasses.replace(slo, burn_alert=None),
                    registry=reg, labels={"router": name, "class": n})

    # -- the gate ------------------------------------------------------------

    def resolve_class(self, client: Optional[str],
                      priority: Optional[str]) -> str:
        if priority is not None:
            if priority not in self.classes:
                raise ValueError(
                    f"unknown priority class {priority!r}; one of "
                    f"{sorted(self.classes)}")
            return priority
        if client is not None and client in self._client_classes:
            return self._client_classes[client]
        return self.default_class

    def _bucket_locked(self, client: str, now: float) -> Optional[TokenBucket]:
        b = self._buckets.get(client)
        if b is None:
            spec = self.client_quotas.get(client, self.quota)
            if spec is None:
                return None  # no default and not named: unlimited
            b = TokenBucket(*spec, now=now)
            self._buckets[client] = b
            while len(self._buckets) > _MAX_CLIENT_BUCKETS:
                self._buckets.popitem(last=False)
        else:
            self._buckets.move_to_end(client)
        return b

    def _shed_counter_locked(self, cls: str, reason: str):
        key = (cls, reason)
        counter = self._m_shed.get(key)
        if counter is None:
            counter = self._m_shed[key] = self.registry.counter(
                "admission_shed_total",
                "requests refused at the admission gate, by reason",
                {"router": self.name, "class": cls, "reason": reason})
        return counter

    def _shed(self, cls: str, reason: str, message: str) -> RejectedError:
        self._shed_counter_locked(cls, reason).inc()
        tracker = self._trackers.get(cls)
        if tracker is not None:
            tracker.record(ok=False)  # the shed burns THIS class's budget
        err = RejectedError(message)
        err.reason = reason
        return err

    def admit(self, client: Optional[str] = None,
              priority: Optional[str] = None,
              now: Optional[float] = None) -> AdmissionTicket:
        """Gate one request; returns its ticket or raises
        :class:`RejectedError` (``.reason`` in ``quota`` /
        ``class_queue_full``). The fault site fires FIRST — an injected
        admission failure consumes no token and no queue slot."""
        faults.inject(FAULT_SITE)
        cls = self.resolve_class(client, priority)
        now = time.monotonic() if now is None else now
        with self._lock:
            if client is not None:
                bucket = self._bucket_locked(client, now)
                if bucket is not None and not bucket.try_take(now):
                    raise self._shed(
                        cls, "quota",
                        f"client {client!r} over quota "
                        f"({bucket.rate_per_s:g} req/s sustained, burst "
                        f"{bucket.burst:g}) — request shed in class "
                        f"{cls!r}")
            if self._depth[cls] >= self._limits[cls]:
                raise self._shed(
                    cls, "class_queue_full",
                    f"class {cls!r} queue full "
                    f"({self._depth[cls]}/{self._limits[cls]}) — request "
                    f"shed")
            self._depth[cls] += 1
            depth = self._depth[cls]
        self._m_admitted[cls].inc()
        self._m_depth[cls].set(depth)
        return AdmissionTicket(cls, client, now)

    # -- the weighted-fair queue ---------------------------------------------

    def enqueue(self, ticket: AdmissionTicket, *payload: Any) -> None:
        """Append an admitted request to its class queue, tagged with its
        start-time-fair virtual finish time. ``payload`` rides along
        opaquely (the router stores its future + dispatch thunk)."""
        w = self.classes[ticket.cls].weight
        with self._lock:
            tag = max(self._vtime, self._finish[ticket.cls]) + 1.0 / w
            self._finish[ticket.cls] = tag
            self._queues[ticket.cls].append((tag, ticket, payload))

    def pop(self) -> Optional[Tuple[AdmissionTicket, Tuple[Any, ...]]]:
        """Dequeue the globally next request by WFQ order (smallest virtual
        finish tag across the class heads); None when nothing waits."""
        with self._lock:
            best = None
            for cls, q in self._queues.items():
                if q and (best is None or q[0][0] < best[0]):
                    best = (q[0][0], cls)
            if best is None:
                return None
            tag, cls = best
            _, ticket, payload = self._queues[cls].popleft()
            self._vtime = tag
            self._depth[cls] -= 1
            depth = self._depth[cls]
        self._m_depth[cls].set(depth)
        self._m_wait[cls].observe(time.monotonic() - ticket.t_admit)
        return ticket, payload

    def drain_queue(self) -> List[Tuple[AdmissionTicket, Tuple[Any, ...]]]:
        """Pop EVERYTHING still queued (router shutdown: the caller fails
        each request's future explicitly instead of leaving it hanging);
        each drained request counts as a ``closed`` shed."""
        out = []
        while True:
            item = self.pop()
            if item is None:
                return out
            with self._lock:
                counter = self._shed_counter_locked(item[0].cls, "closed")
            counter.inc()
            out.append(item)

    # -- accounting ----------------------------------------------------------

    def on_result(self, ticket: AdmissionTicket, latency_s: float,
                  ok: bool) -> None:
        """Close one admitted request's books (the router calls this when
        the routed dispatch delivers or fails)."""
        tracker = self._trackers.get(ticket.cls)
        if tracker is not None:
            tracker.record(latency_s=latency_s, ok=ok)

    def queued(self) -> int:
        with self._lock:
            return sum(self._depth.values())

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            depth = dict(self._depth)
            shed_counters = dict(self._m_shed)
        shed: Dict[str, int] = {}
        for (cls, reason), counter in shed_counters.items():
            shed[f"{cls}:{reason}"] = int(counter.value)
        out: Dict[str, Any] = {
            "classes": {
                n: {
                    "weight": c.weight,
                    "queue_limit": self._limits[n],
                    "depth": depth[n],
                    "admitted": int(self._m_admitted[n].value),
                }
                for n, c in self.classes.items()
            },
            "default_class": self.default_class,
            "quota": (None if self.quota is None
                      else {"rate_per_s": self.quota[0],
                            "burst": self.quota[1]}),
            "client_quotas": {
                c: {"rate_per_s": r, "burst": b}
                for c, (r, b) in sorted(self.client_quotas.items())
            },
            "shed": shed,
        }
        if self._trackers:
            out["slo_burn"] = {
                n: round(t.burn_rate(), 4)
                for n, t in self._trackers.items()
            }
        return out

    def close(self) -> None:
        for tracker in self._trackers.values():
            tracker.close()
