"""Replica process supervision: spawn, babysit, restart-with-backoff,
runtime grow/shrink.

The supervisor owns the PROCESS half of the fleet story (the router owns the
TRAFFIC half): it spawns N replica processes (``serving.replica`` CLI),
watches them, and restarts any that die — with capped exponential backoff
(:class:`~perceiver_io_tpu.resilience.RetryPolicy`), on the same port (so
the router's client handle stays valid across a restart), never more than
``max_restarts`` times per replica (a crash-looping replica is detached, not
hammered). The fleet is ELASTIC at runtime: ``add_replica()`` grows it (the
autoscaler's scale-up edge — the newcomer JOINs through the router's
readiness gate) and ``retire()`` shrinks it gracefully (drain RPC → SIGTERM
→ SIGKILL only as a last resort; the port releases with the process and the
babysitter can never restart a retirement).

A restarted replica REJOINS only after its warm pool is live: the router's
scrape loop sees it as JOINING (``ready=False``) until every engine's
``engine_ready`` gauge flips — the restart is invisible to traffic beyond
the failover blip, which is the whole point.

Child-process hygiene reuses the r4 ``--spawn_hosts`` wiring lessons
(``cli/common.py``): children write to LOG FILES, never undrained pipes (a
chatty child deadlocks a pipe at ~64KB); the CPU backend is pinned via the
child's env (the only fleet there is, see :class:`ReplicaSupervisor`); SIGTERM gives a child its graceful drain (the replica CLI's
signal handler) before SIGKILL.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.resilience import RetryPolicy
from perceiver_io_tpu.serving.replica import HttpReplicaClient
from perceiver_io_tpu.serving.transport import make_client


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def default_replica_argv(name: str, port: int,
                         extra: Sequence[str] = (),
                         transport: str = "http") -> List[str]:
    """The standard child command: ``python -m
    perceiver_io_tpu.serving.replica --port P --name NAME [extra...]``.
    A non-default ``transport`` rides along so the spawned replica serves
    the matching data plane (its endpoints are keyed by the port)."""
    argv = [sys.executable, "-m", "perceiver_io_tpu.serving.replica",
            "--port", str(port), "--name", name]
    if transport != "http":
        argv += ["--transport", transport]
    return argv + list(extra)


class _Replica:
    def __init__(self, name: str, port: int):
        self.name = name
        self.port = port
        self.proc: Optional[subprocess.Popen] = None
        self.log = None
        self.restarts = 0
        self.restart_at: Optional[float] = None  # backoff gate
        self.failed = False  # crash-looped past max_restarts


class ReplicaSupervisor:
    """Spawn and babysit ``count`` replica processes.

    ``argv_builder(name, port) -> argv`` builds each child's full command
    (default: the ``serving.replica`` CLI via :func:`default_replica_argv`
    with ``extra_args``). ``cpu=True`` pins ``JAX_PLATFORMS=cpu`` in the
    children (the offline fleet). ``cpu=False`` is REFUSED: every child
    would inherit this process's environment and claim every chip of the
    host, and a chip belongs to one process. Pinning each child to its own
    chip through its environment has not been shown to work on a multi-chip
    host yet (ROADMAP.md); until it has, a TPU host runs its replicas in one
    process (``LocalReplica``), one per device.
    """

    # pitlint PIT-LOCK: fleet membership is mutated by add_replica/retire
    # (the autoscaler's actuation thread) while the babysitter thread
    # iterates it — touched only under _lock
    _guarded_by = {
        "_replicas": "_lock",
        "_clients": "_lock",
        "_m_restarts": "_lock",
    }

    def __init__(
        self,
        count: int = 3,
        extra_args: Sequence[str] = (),
        argv_builder: Optional[Callable[[str, int], List[str]]] = None,
        base_name: str = "r",
        cpu: bool = True,
        restart_policy: Optional[RetryPolicy] = None,
        max_restarts: int = 5,
        poll_s: float = 0.2,
        log_dir: Optional[str] = None,
        registry: Optional[obs.MetricsRegistry] = None,
        transport: str = "http",
    ):
        if count < 1:
            raise ValueError(f"count must be >= 1, got {count}")
        if not cpu:
            raise NotImplementedError(
                "ReplicaSupervisor(cpu=False): replica processes are not "
                "pinned to chips yet, so each of the "
                f"{count} children would claim every chip of the host and "
                "all but one would fail or hang. Pass cpu=True (the CPU "
                "fleet) or run in-process replicas on the TPU host "
                "(ROADMAP.md: per-chip pinning).")
        self.count = count
        self.transport = transport
        self._argv_builder = argv_builder or (
            lambda name, port: default_replica_argv(
                name, port, extra=extra_args, transport=transport)
        )
        self._policy = restart_policy or RetryPolicy(
            max_retries=max_restarts, base_s=0.25, max_s=5.0)
        self.max_restarts = max_restarts
        self._poll_s = poll_s
        self._log_dir = log_dir
        self._base_name = base_name
        self._next_index = count
        self._lock = threading.Lock()
        self._replicas: Dict[str, _Replica] = {
            f"{base_name}{i}": _Replica(f"{base_name}{i}", _free_port())
            for i in range(count)
        }
        self._clients: Dict[str, HttpReplicaClient] = {
            name: make_client(transport, name, rep.port)
            for name, rep in self._replicas.items()
        }
        self._registry = (registry if registry is not None
                          else obs.get_registry())
        self._m_restarts = {
            name: self._restart_counter(name) for name in self._replicas
        }
        self._stopping = threading.Event()
        self._monitor: Optional[threading.Thread] = None

    def _restart_counter(self, name: str):
        return self._registry.counter(
            "fleet_replica_restarts_total",
            "unexpected replica exits the supervisor restarted",
            {"replica": name})

    # -- lifecycle -----------------------------------------------------------

    def _env(self) -> Dict[str, str]:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # children must resolve the package even when the parent imported it
        # from a path not on the default sys.path (cli/common.py pattern)
        import perceiver_io_tpu

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(perceiver_io_tpu.__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        return env

    def _spawn(self, rep: _Replica) -> None:
        if rep.log is None:
            if self._log_dir is not None:
                os.makedirs(self._log_dir, exist_ok=True)
                rep.log = open(
                    os.path.join(self._log_dir, f"{rep.name}.log"), "a")
            else:
                rep.log = tempfile.NamedTemporaryFile(
                    mode="w+", prefix=f"replica_{rep.name}_", suffix=".log",
                    delete=False)
        argv = self._argv_builder(rep.name, rep.port)
        # log FILES, never undrained pipes (cli/common.py: a child that
        # emits ~64KB into a pipe nobody reads deadlocks)
        rep.proc = subprocess.Popen(
            argv, env=self._env(), stdout=rep.log,
            stderr=subprocess.STDOUT, text=True,
        )
        obs.event("replica_spawned", replica=rep.name, port=rep.port,
                  pid=rep.proc.pid, restarts=rep.restarts)

    def start(self) -> List[HttpReplicaClient]:
        """Spawn the fleet and start the babysitter; returns the clients
        (hand them to a :class:`Router`). Does NOT wait for readiness —
        ``wait_ready()`` does, or let the router's JOINING state gate."""
        with self._lock:
            reps = list(self._replicas.values())
            clients = list(self._clients.values())
        for rep in reps:
            if rep.proc is None:  # add_replica may already have spawned it
                self._spawn(rep)
        self._monitor = threading.Thread(
            target=self._watch, name="replica-supervisor", daemon=True)
        self._monitor.start()
        return clients

    def add_replica(self, name: Optional[str] = None) -> HttpReplicaClient:
        """Grow the fleet by one replica at runtime (the autoscaler's
        scale-up edge): allocate a fresh port, spawn the child, and return
        its client — hand it to ``Router.add_replica``. Does NOT wait for
        readiness: the router scrapes the newcomer as JOINING until its
        warm pool is live, so traffic never sees a cold replica."""
        with self._lock:
            if name is None:
                name = f"{self._base_name}{self._next_index}"
                self._next_index += 1
            if name in self._replicas:
                raise ValueError(f"replica {name!r} already exists")
            rep = _Replica(name, _free_port())
            client = make_client(self.transport, name, rep.port)
            self._replicas[name] = rep
            self._clients[name] = client
            self._m_restarts[name] = self._restart_counter(name)
        self._spawn(rep)
        return client

    def retire(self, name: str, drain_timeout_s: float = 30.0,
               term_timeout_s: float = 10.0) -> bool:
        """Shrink the fleet by one replica: graceful drain (the replica
        finishes every accepted request) → SIGTERM (its signal handler
        exits 0) → SIGKILL only past ``term_timeout_s``. The replica leaves
        the supervised set FIRST, so the babysitter can never restart a
        retirement, and its port is released with the process. Returns
        whether the replica reported fully drained.

        Callers draining through a router (``Router.drain_replica(...,
        detach=True)``) should retire AFTER the router detach — the router
        stops placing work, this call reaps the process."""
        with self._lock:
            rep = self._replicas.pop(name, None)
            client = self._clients.pop(name, None)
            self._m_restarts.pop(name, None)
        if rep is None:
            raise KeyError(f"unknown replica {name!r}")
        rep.failed = True  # a babysitter holding a stale snapshot skips it
        drained = False
        if rep.proc is not None and rep.proc.poll() is None:
            try:
                drained = bool(client.drain(drain_timeout_s))
            except Exception:
                pass  # an unresponsive replica still gets the SIGTERM drain
            rep.proc.terminate()
            try:
                rep.proc.wait(timeout=term_timeout_s)
            except subprocess.TimeoutExpired:
                rep.proc.kill()
                rep.proc.wait(timeout=5)
        if rep.log is not None:
            rep.log.close()
            rep.log = None
        # the retired replica's restart counter leaves /metrics with it
        # (autoscale churn mints monotonically-new names — without this the
        # exposition grows one dead counter per retirement, forever)
        self._registry.remove("fleet_replica_restarts_total",
                              {"replica": name})
        obs.event("replica_retired", replica=name, port=rep.port,
                  drained=drained)
        return drained

    def clients(self) -> List[HttpReplicaClient]:
        with self._lock:
            return list(self._clients.values())

    def client(self, name: str) -> HttpReplicaClient:
        with self._lock:
            return self._clients[name]

    def ports(self) -> Dict[str, int]:
        """``{name: http_port}`` for the current fleet — the key every
        transport endpoint derives from (``uds_path_for``/``shm_slab_name``),
        so callers can build a SECOND client set over the same replicas
        (load_bench's transport A/B runs http and uds/shmem arms against
        one live fleet)."""
        with self._lock:
            return {name: rep.port for name, rep in self._replicas.items()}

    def wait_ready(self, timeout_s: float = 180.0,
                   names: Optional[Sequence[str]] = None) -> None:
        """Block until every (named) replica scrapes ready — the AOT warm
        pool is live and traffic can flow without a compile wall."""
        deadline = time.monotonic() + timeout_s
        with self._lock:
            clients = dict(self._clients)
        waiting = list(names if names is not None else clients)
        while waiting:
            waiting = [
                n for n in waiting
                if not clients[n].scrape(timeout_s=2.0).get("ready")
            ]
            if not waiting:
                return
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"replicas not ready within {timeout_s:g}s: {waiting}"
                )
            time.sleep(self._poll_s)

    # -- the babysitter ------------------------------------------------------

    def _watch(self) -> None:
        while not self._stopping.wait(self._poll_s):
            with self._lock:
                reps = list(self._replicas.values())
                counters = dict(self._m_restarts)
            for rep in reps:
                if rep.proc is None or rep.failed:
                    continue
                rc = rep.proc.poll()
                if rc is None:
                    continue
                now = time.monotonic()
                if rep.restart_at is None:
                    rep.restarts += 1
                    counter = counters.get(rep.name)
                    if counter is not None:
                        counter.inc()
                    if rep.restarts > self.max_restarts:
                        rep.failed = True
                        obs.event("replica_crash_looped", replica=rep.name,
                                  rc=rc, restarts=rep.restarts)
                        print(
                            f"[supervisor] replica {rep.name!r} crash-looped "
                            f"({rep.restarts} restarts) — detaching",
                            file=sys.stderr,
                        )
                        continue
                    pause = self._policy.backoff_s(rep.restarts)
                    rep.restart_at = now + pause
                    obs.event("replica_exited", replica=rep.name, rc=rc,
                              restart_in_s=round(pause, 3),
                              restarts=rep.restarts)
                if now >= rep.restart_at:
                    rep.restart_at = None
                    self._spawn(rep)

    def note_stable(self, name: str) -> None:
        """Reset a replica's restart budget after proven stability (callers
        decide what 'stable' means — e.g. N minutes serving)."""
        with self._lock:
            self._replicas[name].restarts = 0

    # -- chaos / teardown ----------------------------------------------------

    def kill(self, name: str, sig: int = signal.SIGKILL) -> int:
        """Send ``sig`` to a replica (the chaos drill's ``kill -9``); returns
        the pid. The babysitter restarts it with backoff."""
        with self._lock:
            rep = self._replicas[name]
        if rep.proc is None or rep.proc.poll() is not None:
            raise RuntimeError(f"replica {name!r} is not running")
        pid = rep.proc.pid
        os.kill(pid, sig)
        obs.event("replica_killed", replica=name, pid=pid, sig=int(sig))
        return pid

    def pid(self, name: str) -> Optional[int]:
        with self._lock:
            rep = self._replicas[name]
        return rep.proc.pid if rep.proc is not None else None

    def restarts(self, name: str) -> int:
        with self._lock:
            return self._replicas[name].restarts

    def stop(self, timeout_s: float = 20.0) -> None:
        """Graceful fleet shutdown: quit RPC → SIGTERM (drain) → SIGKILL."""
        self._stopping.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5)
        with self._lock:
            replicas = dict(self._replicas)
            clients = dict(self._clients)
        for name, rep in replicas.items():
            if rep.proc is None or rep.proc.poll() is not None:
                continue
            clients[name].quit()
        deadline = time.monotonic() + timeout_s
        for rep in replicas.values():
            if rep.proc is None:
                continue
            left = max(0.1, deadline - time.monotonic())
            try:
                rep.proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                rep.proc.terminate()
                try:
                    rep.proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    rep.proc.kill()
                    rep.proc.wait(timeout=5)
        for rep in replicas.values():
            if rep.log is not None:
                rep.log.close()

    def log_path(self, name: str) -> Optional[str]:
        with self._lock:
            rep = self._replicas[name]
        return rep.log.name if rep.log is not None else None

    def __enter__(self) -> "ReplicaSupervisor":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
