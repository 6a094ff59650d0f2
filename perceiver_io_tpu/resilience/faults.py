"""Deterministic, test-seedable fault injection for the runtime paths.

The environment this framework targets exhibits real failure modes — a
device call that hangs indefinitely, transient PJRT errors, throughput
collapses, silent NaN outputs.
None of them can be provoked on demand from a CPU test box, so the recovery
machinery (``retry``/``breaker``, the engine's shed/retry paths, the trainer's
bad-step guard) would otherwise ship untested. This module is the substrate
for the chaos suite: instrumented sites in the dispatch paths call
:func:`inject` / :func:`corrupt`, which are no-ops until a
:class:`FaultInjector` is installed — then they raise, hang, sleep, or
NaN-corrupt exactly where the real failures would.

Faults are **deterministic**: each spec names the 1-based call indices at
which it fires (``at=(2, 5)``), or an every-N cadence, so a chaos drill
replays identically. A ``hang`` spec blocks on a ``threading.Event`` the test
holds (the wedged-dispatch simulation — release it to "un-wedge" the device).

Instrumented sites (grep for ``faults.inject`` / ``faults.corrupt``):

- ``engine.dispatch`` — inside :meth:`ServingEngine._execute`, before the
  jitted call (raise/hang here = the dispatch itself failing/wedging);
- ``engine.complete`` — before the worker's ``device_get`` (a completion-side
  failure);
- ``trainer.dispatch`` — before the trainer's train-step dispatch;
- ``trainer.metrics`` — ``corrupt`` hook over the train-step metrics (NaN
  loss injection: the signature of a poisoned step);
- ``deploy.publish`` / ``deploy.gate`` / ``deploy.swap`` — the train→serve
  deployment loop (``perceiver_io_tpu.deploy``): checkpoint publication
  (``fire`` hook: raise kinds AND nan corruption of the published tree),
  the serving-side admission gate, and the fleet hot-swap;
- ``trainer.collective`` — ``fire`` hook over the host-local batch right
  before every train dispatch (multi-host chaos: per-host NaN corruption,
  wedged-host hangs, per-step throttling);
- ``multihost.heartbeat`` — the peer-liveness publisher
  (``resilience/multihost.py``);
- ``spawn.child_exit`` — the restart-the-world supervisor's child watch
  loop (``cli/common.py``);
- ``transport.send`` / ``transport.recv`` — the replica RPC data plane
  (``serving/transport.py`` and the HTTP client): before a frame is
  written / after one is accepted, so transport chaos drills (mid-call
  connection death, torn exchanges) run without killing real processes;
- ``multihost.resize`` — the elastic world-resize edge
  (``resilience/elastic.py``): fired at the start of every shrink/grow
  attempt, so drills can kill a survivor mid-resize or throttle a
  straggler;
- ``multihost.buddy_send`` — ``fire`` hook over the host-local state
  snapshot before it is framed to the buddy host (NaN corruption here is
  the corrupted-mirror drill the digest check must catch at restore);
- ``multihost.join`` — the spare/hot-join path (a spare dying mid-join,
  or joining while a shrink is in flight).

The registered sites live in :data:`SITES`; :func:`parse_spec` validates
every clause against them (and the kind set), so a typo'd drill fails
loudly at install instead of silently injecting nothing.

Env gating for whole-process chaos runs (no code changes)::

    PIT_FAULTS="engine.dispatch:transient@2,5;trainer.metrics:nan@3" python ...

is parsed by :func:`install_from_env`, called lazily on the first ``inject``.
Production default: ``PIT_FAULTS`` unset, no injector installed, every hook
is a None-check.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

ENV_VAR = "PIT_FAULTS"

_KINDS = ("transient", "fatal", "hang", "slow", "nan")

# The registered instrumentation sites. parse_spec VALIDATES against this
# set: a typo'd PIT_FAULTS drill must fail loudly at install, not silently
# inject nothing while the operator believes chaos is running. Sites in
# _SUFFIXED also accept a ".<qualifier>" suffix (the per-engine drill
# targets, e.g. ``engine.dispatch.replica0-infer``).
SITES = (
    "engine.dispatch",
    "engine.complete",
    "trainer.dispatch",
    "trainer.metrics",
    # the train->serve deployment loop (perceiver_io_tpu.deploy): publish
    # (raise = a publish dying mid-write; nan = a poisoned tree whose digest
    # still verifies), admission gate, and the fleet swap itself
    "deploy.publish",
    "deploy.gate",
    "deploy.swap",
    # the serving control loop (perceiver_io_tpu.serving): the autoscaler's
    # actuation edge (raise = a spawn/retire failing — the backoff drill:
    # PIT_FAULTS="autoscale.scale:transient@1" fails the first spawn) and
    # the router's admission gate (raise/hang inside admit, before any
    # queue slot or token is consumed)
    "autoscale.scale",
    "router.admit",
    # the generative decode path (perceiver_io_tpu.inference.generate): the
    # prefix encode and the chunked decode dispatch — the mid-stream chaos
    # drills target a replica's step path without code changes
    "generation.prefill",
    "generation.step",
    # the continuous-batching arena (perceiver_io_tpu.inference.batching):
    # ONE batched decode dispatch covers every active stream, so a fault
    # here is the blast-radius drill — all in-flight streams on the replica
    # observe the same failure and must reroute content-losslessly
    "generation.batch_dispatch",
    # multi-host training fault tolerance (r19): the collective train-step
    # edge (fire hook over the HOST-LOCAL batch before dispatch — nan =
    # one host's shard corrupted, whose NaN then rides the global loss
    # reduction to every peer; hang = a wedged host inside the collective;
    # slow = per-step throttle for drill timing), the peer-liveness
    # publisher (resilience/multihost.py — transient = a KV-store write
    # failing; hang = this host stops beating, so PEERS mark it down), and
    # the world supervisor's child watch loop (cli/common.py — a raise is
    # treated as an observed child death, driving restart drills without
    # killing real processes)
    "trainer.collective",
    "multihost.heartbeat",
    "spawn.child_exit",
    # the replica transport data plane (serving/transport.py + the HTTP
    # client): "send" fires just before a request/response frame hits the
    # wire (client request writes AND replica response writes share the
    # site), "recv" just after a frame is accepted — the chaos drills for
    # mid-RPC connection death and torn-exchange failover without killing
    # real processes
    "transport.send",
    "transport.recv",
    # elastic multi-host training (resilience/elastic.py): the resize
    # negotiation edge (inject at the start of every shrink/grow attempt —
    # fatal/kill here = a survivor dying MID-RESIZE, so the remaining peers
    # must re-verdict and resize AGAIN; slow = a straggler survivor), the
    # buddy in-memory-checkpoint send (fire hook over the host-local
    # snapshot before it is framed — nan = a corrupted mirror the
    # tree-digest check must reject at restore), and the spare/hot-join
    # edge (inject inside the join path — a spare failing, or joining while
    # a shrink is in flight)
    "multihost.resize",
    "multihost.buddy_send",
    "multihost.join",
)
_SUFFIXED = ("engine.dispatch", "engine.complete")


def validate_site(site: str) -> str:
    """Return ``site`` if registered (exactly, or a registered per-engine
    prefix); raise ValueError naming the valid options otherwise."""
    if site in SITES or any(site.startswith(s + ".") and len(site) > len(s) + 1
                            for s in _SUFFIXED):
        return site
    raise ValueError(
        f"unknown fault site {site!r}; one of {SITES} "
        f"(or {', '.join(s + '.<engine-name>' for s in _SUFFIXED)})"
    )


class InjectedTransientError(RuntimeError):
    """An injected fault standing in for a transient runtime error (the
    classifier in :mod:`perceiver_io_tpu.resilience.retry` maps it to
    ``'transient'``, like a PJRT UNAVAILABLE)."""


class InjectedFatalError(RuntimeError):
    """An injected fault the classification must treat as fatal (no retry)."""


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One fault at one site.

    ``at``: 1-based call indices of the site at which the fault fires;
    ``every``: alternatively fire on every Nth call (``at`` wins when set).
    ``kind``: ``transient`` / ``fatal`` raise; ``hang`` blocks until
    ``release`` is set (or ``delay_s`` elapses, when given); ``slow`` sleeps
    ``delay_s``; ``nan`` fires only through :func:`corrupt` and NaN-fills
    every floating leaf of the payload.
    """

    site: str
    kind: str
    at: Tuple[int, ...] = ()
    every: int = 0
    delay_s: float = 0.0
    release: Optional[threading.Event] = None

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; one of {_KINDS}")
        if not self.at and self.every <= 0:
            raise ValueError("FaultSpec needs at=(indices...) or every=N")

    def fires(self, call_index: int) -> bool:
        if self.at:
            return call_index in self.at
        return call_index % self.every == 0


class FaultInjector:
    """Holds the fault plan plus per-site call counters (thread-safe: sites
    are hit from engine workers, submitter threads, and the trainer loop)."""

    def __init__(self, specs: Iterable[FaultSpec] = ()):
        self._specs = list(specs)
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self.fired: Dict[str, int] = {}  # site -> faults actually fired

    def add(self, spec: FaultSpec) -> "FaultInjector":
        self._specs.append(spec)
        return self

    def calls(self, site: str) -> int:
        with self._lock:
            return self._counts.get(site, 0)

    def _tick(self, site: str, kinds: Tuple[str, ...]):
        """Count one call of ``site`` and return the specs that fire on it."""
        with self._lock:
            n = self._counts.get(site, 0) + 1
            self._counts[site] = n
            due = [
                s for s in self._specs
                if s.site == site and s.kind in kinds and s.fires(n)
            ]
            if due:
                self.fired[site] = self.fired.get(site, 0) + len(due)
        return due

    def inject(self, site: str) -> None:
        for spec in self._tick(site, ("transient", "fatal", "hang", "slow")):
            self._execute(spec, site)

    def corrupt(self, site: str, payload):
        """NaN-fill the floating leaves of ``payload`` when a ``nan`` spec
        fires on this call of ``site``; otherwise return it unchanged."""
        if not self._tick(site, ("nan",)):
            return payload
        return _poison_tree(payload)

    def fire(self, site: str, payload):
        """Combined hook for sites that support BOTH raise-type faults and
        payload corruption (``deploy.publish``): ONE tick of ``site`` per
        call, every spec kind considered, so a drill's 1-based call indices
        count real calls — not the two internal ticks a separate
        inject+corrupt pair would burn. Returns the (possibly corrupted)
        payload, or raises/sleeps/hangs per the due raise-kind specs."""
        due = self._tick(site, _KINDS)
        for spec in due:
            if spec.kind != "nan":
                self._execute(spec, site)
        if any(spec.kind == "nan" for spec in due):
            payload = _poison_tree(payload)
        return payload

    def _execute(self, spec: FaultSpec, site: str) -> None:
        """Run one due raise-kind spec (shared by inject and fire, so the
        hang/slow/raise semantics cannot drift between the two hooks)."""
        if spec.kind == "slow":
            _interruptible_sleep(spec.delay_s)
        elif spec.kind == "hang":
            # the wedged dispatch: block until the test un-wedges it (or a
            # bounded delay, so a forgotten release can't hang a suite)
            if spec.release is not None:
                spec.release.wait(spec.delay_s or None)
            else:
                _interruptible_sleep(spec.delay_s or 3600.0)
        elif spec.kind == "transient":
            raise InjectedTransientError(
                f"injected transient fault at {site!r} "
                f"(call {self.calls(site)})"
            )
        else:
            raise InjectedFatalError(
                f"injected fatal fault at {site!r} (call {self.calls(site)})"
            )


def _poison_tree(payload):
    import jax

    def poison(x):
        a = np.asarray(x)
        if np.issubdtype(a.dtype, np.floating):
            return np.full_like(a, np.nan)
        return x

    return jax.tree.map(poison, payload)


def _interruptible_sleep(seconds: float) -> None:
    # Event.wait rather than time.sleep: a daemon thread stuck in a plain
    # sleep delays interpreter shutdown on some platforms
    threading.Event().wait(seconds)


# -- process-global install point --------------------------------------------

_ACTIVE: Optional[FaultInjector] = None
_ENV_CHECKED = False


def install(injector: Optional[FaultInjector]) -> Optional[FaultInjector]:
    """Install (or with None, remove) the process-global injector; returns the
    previous one so tests can restore it."""
    global _ACTIVE, _ENV_CHECKED
    previous, _ACTIVE = _ACTIVE, injector
    _ENV_CHECKED = True  # an explicit install wins over the env var
    return previous


def get() -> Optional[FaultInjector]:
    return _ACTIVE


def parse_spec(text: str) -> FaultInjector:
    """Parse the ``PIT_FAULTS`` grammar:
    ``site:kind@1,4;site2:kind2@every:3[@delay:0.5]``.

    Each ``;``-separated clause is ``site:kind@WHEN`` where WHEN is a
    comma-list of 1-based call indices or ``every:N``; an optional trailing
    ``@delay:SECONDS`` sets the hang/slow duration.
    """
    inj = FaultInjector()
    for clause in filter(None, (c.strip() for c in text.split(";"))):
        try:
            site, rest = clause.split(":", 1)
            # validate EAGERLY against the registered site and kind sets: a
            # typo'd drill must fail at install with the valid options named,
            # not silently inject nothing (the kind check lives in FaultSpec;
            # both surface through the clause-naming ValueError below)
            validate_site(site)
            kind, _, when = rest.partition("@")
            delay = 0.0
            if "@delay:" in when:
                when, _, d = when.partition("@delay:")
                delay = float(d)
            if when.startswith("every:"):
                inj.add(FaultSpec(site=site, kind=kind,
                                  every=int(when[len("every:"):]),
                                  delay_s=delay))
            else:
                inj.add(FaultSpec(
                    site=site, kind=kind, delay_s=delay,
                    at=tuple(int(i) for i in when.split(",") if i),
                ))
        except (ValueError, TypeError) as e:
            raise ValueError(
                f"bad {ENV_VAR} clause {clause!r} "
                f"(expected site:kind@1,4 or site:kind@every:N): {e}"
            ) from e
    return inj


def install_from_env() -> None:
    """Install an injector from ``PIT_FAULTS`` once per process (no-op when
    unset or an injector was installed explicitly)."""
    global _ENV_CHECKED, _ACTIVE
    if _ENV_CHECKED:
        return
    _ENV_CHECKED = True
    text = os.environ.get(ENV_VAR)
    if text:
        _ACTIVE = parse_spec(text)


# -- the site-side hooks (near-zero cost when inactive) ----------------------


def inject(site: str) -> None:
    """Instrumentation hook: raise/hang/sleep if a fault is due at ``site``."""
    if not _ENV_CHECKED:
        install_from_env()
    if _ACTIVE is not None:
        _ACTIVE.inject(site)


def corrupt(site: str, payload):
    """Instrumentation hook: NaN-corrupt ``payload`` if a fault is due."""
    if not _ENV_CHECKED:
        install_from_env()
    if _ACTIVE is not None:
        return _ACTIVE.corrupt(site, payload)
    return payload


def fire(site: str, payload):
    """Combined raise+corrupt hook (one site tick per call — see
    :meth:`FaultInjector.fire`); returns the possibly-corrupted payload."""
    if not _ENV_CHECKED:
        install_from_env()
    if _ACTIVE is not None:
        return _ACTIVE.fire(site, payload)
    return payload
