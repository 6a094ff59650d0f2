"""Runtime sanitizers: recompiles, silent host transfers, lock ordering.

The static rules in this package catch what an AST can see; these catch what
only a running process can. All three are cheap enough to arm inside tier-1
tests (the lock recorder wraps ``threading.Lock`` creation only inside its
context; the other two are a counter read and a jax config scope).

- :func:`no_recompile` — a steady-state serving block must do ZERO XLA
  compiles (the bucket programs + AOT cache exist to guarantee it; a
  climbing ``jax_compilations_total`` during serving is the recompile bug).
- :func:`no_implicit_transfers` — ``jax.transfer_guard`` armed around engine
  dispatch: a silent device→host transfer (an un-fetched tracer leaking into
  numpy) is a host sync per occurrence in production and
  raises here instead.
- :func:`record_lock_order` — wraps locks created inside the context,
  records the acquisition graph (every held lock → newly acquired lock,
  nodes keyed by creation site so all instances of e.g.
  ``ServingEngine._stats_lock`` collapse to one node, lockdep-style), and
  fails on cycles: two code paths taking the same two locks in opposite
  orders is a deadlock waiting for the right interleaving.
"""

from __future__ import annotations

import contextlib
import threading
import traceback
from typing import Dict, Iterator, List, Optional, Set, Tuple


class RecompileDetected(AssertionError):
    """Steady-state code compiled when it must not have."""


class LockOrderViolation(AssertionError):
    """The recorded lock-acquisition graph contains a cycle."""


@contextlib.contextmanager
def no_recompile(registry=None) -> Iterator[None]:
    """Assert ZERO ``jax_compilations_total`` delta across the block.

    Rides the process-wide ``jax.monitoring`` backend-compile listener
    (:func:`~perceiver_io_tpu.obs.watchdog.install_compile_counter`), which
    fires once per real XLA compilation and never for cache hits — so an AOT
    disk deserialize stays silent and a genuine recompile trips this.

    The counter is PROCESS-WIDE: wrap only blocks whose whole process should
    be compile-quiet. An engine still background-warming (``warmup(...,
    background=True)``) legitimately compiles on its warmup thread — wait
    for the warm pool (``engine_ready``) before arming this.
    """
    from perceiver_io_tpu.obs.watchdog import install_compile_counter

    counter = install_compile_counter(registry)
    before = counter.value
    yield
    delta = counter.value - before
    if delta:
        raise RecompileDetected(
            f"no_recompile(): {delta:g} XLA compilation(s) inside a "
            f"steady-state block (jax_compilations_total "
            f"{before:g} -> {counter.value:g})"
        )


@contextlib.contextmanager
def no_implicit_transfers(direction: str = "device_to_host",
                          guard: str = "disallow") -> Iterator[None]:
    """Arm jax's transfer guard PROCESS-WIDE for the block.

    Default scope is the DEVICE→HOST direction: that is the silent transfer
    that stalls the dispatch pipeline on every occurrence (a stray
    ``np.asarray(device_array)`` or ``float(tracer_output)`` deep in a
    completion path). Explicit movement (``jax.device_get``) stays legal —
    the engine's result fetches are deliberate. Host→device stays free by
    default because feeding numpy micro-batches straight into the jitted
    dispatch IS the engine's staging path on CPU; pass
    ``direction="all"`` to arm every direction.

    Deliberately NOT ``jax.transfer_guard(...)`` the context manager: that
    config scope is THREAD-LOCAL, and the transfers this sanitizer exists
    to catch happen on the engine's worker thread, not the test thread
    arming it. The global ``jax.config.update`` default IS visible to
    threads outside any thread-local scope (verified empirically on this
    jax build), which makes the guard bite where the dispatch actually
    runs. Consequence: do not run concurrent jax work that must stay
    guard-free while armed.
    """
    import jax

    flags = {
        "all": "jax_transfer_guard",
        "device_to_host": "jax_transfer_guard_device_to_host",
        "host_to_device": "jax_transfer_guard_host_to_device",
    }
    if direction not in flags:
        raise ValueError(
            f"no_implicit_transfers: unknown direction {direction!r} "
            f"(one of {sorted(flags)}) — a typo here would silently arm "
            f"the wrong guard")
    flag = flags[direction]
    previous = getattr(jax.config, flag)  # None when never set (= allow)
    jax.config.update(flag, guard)
    try:
        yield
    finally:
        jax.config.update(flag, previous)


# -- lock-order recording -----------------------------------------------------

_FRAMEWORK_FILES = ("threading.py", "queue.py", "sanitizers.py")


def _creation_site() -> str:
    """First stack frame outside threading/queue/this module — the lock's
    declaration site, the node key that collapses per-instance locks."""
    for frame in reversed(traceback.extract_stack()):
        if not frame.filename.endswith(_FRAMEWORK_FILES):
            return f"{frame.filename.rsplit('/', 1)[-1]}:{frame.lineno}"
    return "<unknown>"


class _RecordingLock:
    """Duck-typed ``threading.Lock`` stand-in that reports acquisitions.

    Supports the full surface ``Condition``/``Event``/``queue.Queue`` use
    (``acquire(blocking, timeout)``, ``release``, ``locked``, context
    manager), so a recorder context can transparently wrap every lock the
    engine/router stack creates.
    """

    __slots__ = ("_lock", "_recorder", "site")

    def __init__(self, lock, recorder: "LockOrderRecorder", site: str):
        self._lock = lock
        self._recorder = recorder
        self.site = site

    def acquire(self, blocking: bool = True, timeout: float = -1):
        got = self._lock.acquire(blocking, timeout)
        if got:
            self._recorder._note_acquire(self.site)
        return got

    def release(self) -> None:
        self._lock.release()
        self._recorder._note_release(self.site)

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class LockOrderRecorder:
    """Builds the lock-acquisition graph as wrapped locks are taken.

    Edge ``A -> B``: some thread acquired ``B`` while holding ``A``. A cycle
    in this graph means two orderings coexist — the deadlock precondition.
    ``check()`` raises :class:`LockOrderViolation` naming the cycle.
    """

    def __init__(self):
        self._graph_lock = threading.Lock()
        self._edges: Dict[str, Set[str]] = {}
        self._acquisitions = 0
        self._local = threading.local()

    def _held(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _note_acquire(self, site: str) -> None:
        held = self._held()
        if held:
            with self._graph_lock:
                for h in held:
                    if h != site:
                        self._edges.setdefault(h, set()).add(site)
        with self._graph_lock:
            self._acquisitions += 1
        held.append(site)

    def _note_release(self, site: str) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] == site:
                del held[i]
                break

    def wrap(self, lock, site: Optional[str] = None) -> _RecordingLock:
        return _RecordingLock(lock, self, site or _creation_site())

    @property
    def edges(self) -> Dict[str, Set[str]]:
        with self._graph_lock:
            return {k: set(v) for k, v in self._edges.items()}

    @property
    def acquisitions(self) -> int:
        with self._graph_lock:
            return self._acquisitions

    def find_cycle(self) -> Optional[List[str]]:
        edges = self.edges
        WHITE, GRAY, BLACK = 0, 1, 2
        color: Dict[str, int] = {}
        path: List[str] = []

        def dfs(node: str) -> Optional[List[str]]:
            color[node] = GRAY
            path.append(node)
            for nxt in sorted(edges.get(node, ())):
                c = color.get(nxt, WHITE)
                if c == GRAY:
                    return path[path.index(nxt):] + [nxt]
                if c == WHITE:
                    cycle = dfs(nxt)
                    if cycle:
                        return cycle
            path.pop()
            color[node] = BLACK
            return None

        for node in sorted(edges):
            if color.get(node, WHITE) == WHITE:
                cycle = dfs(node)
                if cycle:
                    return cycle
        return None

    def check(self) -> None:
        cycle = self.find_cycle()
        if cycle:
            raise LockOrderViolation(
                "lock-order cycle (deadlock precondition): "
                + " -> ".join(cycle)
                + " — two code paths acquire these locks in opposite orders"
            )


@contextlib.contextmanager
def record_lock_order() -> Iterator[LockOrderRecorder]:
    """Record the acquisition order of every lock CREATED inside the block
    (``threading.Lock`` is patched for the duration — existing locks are
    untouched), then fail on cycles at exit.

    Construct the system under test inside the context so its locks are
    wrapped; drive it; the exit check raises :class:`LockOrderViolation` on
    any inconsistent ordering observed — even ones that didn't deadlock this
    run. The check is skipped when the body itself raised (the original
    error wins).
    """
    recorder = LockOrderRecorder()
    real_lock = threading.Lock

    def recording_lock():
        return recorder.wrap(real_lock(), _creation_site())

    threading.Lock = recording_lock
    try:
        yield recorder
    finally:
        threading.Lock = real_lock
    recorder.check()
