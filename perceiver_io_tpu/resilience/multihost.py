"""Bounded-exit failure detection for multi-host training.

A dead or wedged peer turns every subsequent collective into a trap: the
survivors' next train dispatch simply never completes, and a pod burns its
allocation doing nothing until a human notices (SURVEY.md §5; the r12
serving fabric already solves this for replicas — this is the training-side
twin). Nothing can *unblock* a host stuck inside a collective, so the only
sane contract is **bounded exit**: detect the dead peer within a configured
window, dump diagnostics, and leave with a *transient* exit code so the
restart-the-world supervisor (``cli/common.py maybe_spawn_hosts``) relaunches
the whole job from the newest checkpoint.

Two detectors, complementary by construction:

- :class:`PeerLivenessMonitor` — a host-side heartbeat over the
  ``jax.distributed`` coordinator KV store (the one cross-host channel that
  does NOT ride device collectives, so it keeps working while the main
  thread is stuck in one). Every host publishes a beat counter; every host
  watches every peer's counter through an :class:`~perceiver_io_tpu.obs
  .health.Heartbeat` (deadline-monitored, healthz-aggregated, stall-dumping
  — the serving loops' liveness primitive, reused verbatim). A peer whose
  counter stops advancing for ``deadline_s`` is declared down once:
  ``multihost_peer_down_total`` increments and ``on_peer_down`` fires —
  by default :func:`abort_transient`.
- :class:`StepDeadline` — a per-step deadline on the training loop itself
  (arm before the dispatch, beat at the completion the host observes): the
  wedged-collective detector for failure modes the KV channel cannot see
  (a peer that still heartbeats but whose device wedged).

Exit discipline: :func:`abort_transient` leaves with ``EXIT_TRANSIENT``
(75, ``EX_TEMPFAIL``) via ``os._exit`` — a daemon thread cannot raise into
a main thread that is blocked in a collective, and a ``sys.exit`` there
would be swallowed. The supervisor treats any child death as
restart-the-world; the dedicated code makes the *reason* legible in logs
and drills. The KV error classification rides ``resilience.retry.classify_error``:
transient KV hiccups are tolerated (counted, retried next beat), but a
persistently failing KV store means the coordinator itself is gone — a
peer-down event in its own right.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from perceiver_io_tpu.resilience import faults
from perceiver_io_tpu.resilience.retry import is_transient

# EX_TEMPFAIL: the bounded-exit code — "transient failure, retry the world".
# The supervisor restarts on ANY nonzero child exit; this code exists so a
# bounded-exit abort is distinguishable from a crash in logs and drills.
EXIT_TRANSIENT = 75

_KV_PREFIX = "pit_hb"


def abort_transient(reason: str, exit_code: int = EXIT_TRANSIENT) -> None:
    """Leave the process NOW with a transient exit code.

    ``os._exit`` on purpose: this runs on a monitor thread while the main
    thread is (by hypothesis) stuck inside a dead collective — no exception
    can reach it, no atexit hook involving jax/device state can be trusted
    to return. Checkpoints are the recovery source, not a graceful unwind.
    """
    print(f"[multihost] bounded exit ({exit_code}): {reason}",
          file=sys.stderr)
    sys.stderr.flush()
    os._exit(exit_code)


class InMemoryKV:
    """Dict-backed stand-in for the coordinator KV store (tests, and
    single-process dry runs of the monitor). Thread-safe like the real one."""

    _guarded_by = {"_data": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._data: Dict[str, str] = {}

    def key_value_set(self, key: str, value: str,
                      allow_overwrite: bool = False) -> None:
        with self._lock:
            if not allow_overwrite and key in self._data:
                raise ValueError(f"key {key!r} already set")
            self._data[key] = value

    def key_value_dir_get(self, key: str) -> List[Tuple[str, str]]:
        with self._lock:
            return [(k, v) for k, v in sorted(self._data.items())
                    if k.startswith(key)]


def distributed_kv_client():
    """The live ``jax.distributed`` coordinator KV client, or None when no
    distributed runtime is up (single-process runs)."""
    from jax._src import distributed

    return distributed.global_state.client


class PeerLivenessMonitor:
    """Cross-host liveness over the coordinator KV store.

    Each host runs one monitor: a daemon thread publishes this host's beat
    counter every ``interval_s`` and scans every peer's counter. Peer
    liveness state is held by one :class:`obs.health.Heartbeat` per peer
    (``deadline_s`` stale → stalled), so ``healthz()`` aggregates peer
    health for free and a stall produces the standard diagnostic dump. The
    first stall of a peer fires ``on_peer_down(peer_id)`` exactly once and
    bumps ``multihost_peer_down_total``.

    ``kv`` defaults to the live ``jax.distributed`` client; tests pass an
    :class:`InMemoryKV` shared between two monitors. Constructing without
    any KV store raises — a monitor that silently watches nothing is worse
    than none.
    """

    _guarded_by = {"_down": "_lock", "_last_seen": "_lock",
                   "_kv_failures": "_lock"}

    def __init__(
        self,
        process_id: Optional[int] = None,
        num_processes: Optional[int] = None,
        kv=None,
        interval_s: float = 1.0,
        deadline_s: Optional[float] = None,
        on_peer_down: Optional[Callable[[int], None]] = None,
        kv_failure_limit: int = 5,
        namespace: str = _KV_PREFIX,
    ):
        import jax

        import perceiver_io_tpu.obs as obs

        if kv is None:
            kv = distributed_kv_client()
        if kv is None:
            raise ValueError(
                "PeerLivenessMonitor needs a KV store: initialize "
                "jax.distributed first, or pass kv= explicitly"
            )
        self._kv = kv
        self._pid = (jax.process_index() if process_id is None
                     else int(process_id))
        self._n = (jax.process_count() if num_processes is None
                   else int(num_processes))
        if interval_s <= 0:
            raise ValueError(f"interval_s must be positive, got {interval_s}")
        self._interval_s = float(interval_s)
        self._deadline_s = float(deadline_s if deadline_s is not None
                                 else 5.0 * interval_s)
        self._on_peer_down = on_peer_down or (lambda peer: abort_transient(
            f"peer {peer} unresponsive for >{self._deadline_s:.1f}s "
            f"(no KV heartbeat advance) — presumed dead; exiting before the "
            f"next collective wedges"))
        self._kv_failure_limit = int(kv_failure_limit)
        self._namespace = namespace
        self._counter = 0
        self._lock = threading.Lock()
        self._down: set = set()
        self._last_seen: Dict[int, str] = {}
        self._kv_failures = 0
        self._m_peer_down = obs.get_registry().counter(
            "multihost_peer_down_total",
            "peers declared dead by the KV liveness monitor")
        from perceiver_io_tpu.obs.health import Heartbeat

        # one deadline-monitored heartbeat per PEER; its stall hook fires
        # every monitor poll while stale, so _peer_down de-dupes under _lock
        self._peer_beats = {
            peer: Heartbeat(
                f"multihost_peer{peer}", deadline_s=self._deadline_s,
                on_stall=(lambda p=peer: self._peer_down(p)),
            )
            for peer in range(self._n) if peer != self._pid
        }
        self._closed = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "PeerLivenessMonitor":
        for hb in self._peer_beats.values():
            hb.arm()
        self._thread = threading.Thread(
            target=self._run, name=f"peer-liveness-p{self._pid}", daemon=True)
        self._thread.start()
        return self

    def close(self) -> None:
        self._closed.set()
        for hb in self._peer_beats.values():
            hb.close()
        if self._thread is not None:
            self._thread.join(timeout=2 * self._interval_s + 1.0)

    def __enter__(self) -> "PeerLivenessMonitor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- elastic generation changes ------------------------------------------

    def set_peers(self, peer_ids) -> None:
        """Watch exactly ``peer_ids`` from now on (elastic world resize).

        A shrink/grow changes WHO counts as a peer without restarting the
        monitor: removed peers' heartbeats close and their down-verdicts
        clear (a rank that left the world on purpose — or whose death was
        already acted on — must not keep reading as a live failure), new
        peers get fresh armed heartbeats, and surviving peers keep their
        beat state uninterrupted. ``peer_ids`` may include this host's own
        id; it is ignored.
        """
        from perceiver_io_tpu.obs.health import Heartbeat

        wanted = {int(p) for p in peer_ids} - {self._pid}
        started = self._thread is not None
        stale = set(self._peer_beats) - wanted
        for peer in stale:
            self._peer_beats.pop(peer).close()
        with self._lock:
            self._down -= stale
            for peer in stale:
                self._last_seen.pop(peer, None)
        for peer in sorted(wanted - set(self._peer_beats)):
            hb = Heartbeat(
                f"multihost_peer{peer}", deadline_s=self._deadline_s,
                on_stall=(lambda p=peer: self._peer_down(p)),
            )
            self._peer_beats[peer] = hb
            if started:
                hb.arm()
        self._n = len(wanted) + 1

    # -- introspection (tests / healthz detail) ------------------------------

    def peers_down(self) -> Tuple[int, ...]:
        with self._lock:
            return tuple(sorted(self._down))

    def kv_failures(self) -> int:
        with self._lock:
            return self._kv_failures

    # -- the monitor thread --------------------------------------------------

    def _run(self) -> None:
        while not self._closed.wait(self._interval_s):
            self._beat_once()

    def _beat_once(self) -> None:
        """One publish + scan round (exposed for deterministic tests)."""
        import perceiver_io_tpu.obs as obs

        try:
            # chaos hook: hang = this host stops beating (peers mark it
            # down); transient = a KV write failing (tolerated, counted)
            faults.inject("multihost.heartbeat")
            self._counter += 1
            self._kv.key_value_set(
                f"{self._namespace}/{self._pid}", str(self._counter),
                allow_overwrite=True)
            entries = dict(self._kv.key_value_dir_get(self._namespace))
        except Exception as e:
            with self._lock:
                self._kv_failures += 1
                failures = self._kv_failures
            obs.event("multihost_kv_error", error=type(e).__name__,
                      transient=is_transient(e), consecutive=failures)
            if failures >= self._kv_failure_limit:
                # the KV store IS the coordinator: persistently unreachable
                # means rank 0's service is gone — a peer-down of its own
                self._peer_down(-1)
            return
        with self._lock:
            self._kv_failures = 0
        # snapshot: set_peers (elastic resize, main thread) mutates the dict
        for peer, hb in list(self._peer_beats.items()):
            value = entries.get(f"{self._namespace}/{peer}")
            with self._lock:
                advanced = (value is not None
                            and value != self._last_seen.get(peer))
                if advanced:
                    self._last_seen[peer] = value
            if advanced:
                hb.beat()

    def _peer_down(self, peer: int) -> None:
        with self._lock:
            if peer in self._down:
                return
            self._down.add(peer)
        self._m_peer_down.inc()
        import perceiver_io_tpu.obs as obs

        obs.event("multihost_peer_down", peer=peer,
                  deadline_s=self._deadline_s)
        self._on_peer_down(peer)


class StepDeadline:
    """Bounded-exit deadline on the training loop's dispatch cycle.

    ``arm()`` before the dispatch, ``beat()`` at the completion the host
    observes, ``disarm()`` around long legitimate pauses (eval, checkpoint
    save). If no beat lands within ``deadline_s`` the underlying
    :class:`obs.health.Heartbeat` stalls — diagnostics dump (every thread's
    stack: *where* is the collective stuck?) and ``on_expire`` fires once,
    by default :func:`abort_transient`. This is the guarantee the chaos
    drill pins: a surviving host never blocks longer than the configured
    window inside a dead collective.
    """

    _guarded_by = {"_expired": "_lock"}

    def __init__(self, name: str, deadline_s: float,
                 on_expire: Optional[Callable[[], None]] = None):
        from perceiver_io_tpu.obs.health import Heartbeat

        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self._on_expire = on_expire or (lambda: abort_transient(
            f"step deadline {deadline_s:.1f}s expired — dispatch presumed "
            f"wedged in a dead collective"))
        self._lock = threading.Lock()
        self._expired = False
        self._hb = Heartbeat(name, deadline_s=self.deadline_s,
                             on_stall=self._expire_once)
        self._armed_at: Optional[float] = None

    def arm(self) -> None:
        self._armed_at = time.monotonic()
        self._hb.arm()

    def beat(self) -> None:
        self._hb.beat()

    def disarm(self) -> None:
        self._hb.disarm()

    def close(self) -> None:
        self._hb.close()

    def _expire_once(self) -> None:
        with self._lock:
            if self._expired:
                return
            self._expired = True
        self._on_expire()
