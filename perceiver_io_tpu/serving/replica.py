"""Replica-side RPC shim: one serving process behind the router tier.

A *replica* is one process-wide set of serving engines (the fused MLM path
plus the encode/decode latent-cache split — ``mlm_apply_fns``) exposed over a
localhost HTTP surface the router consumes. The wire protocol is deliberately
boring — stdlib HTTP, ``np.savez`` bodies — because the interesting contracts
are semantic, not syntactic:

- **arrays in, arrays out** (``POST /rpc/infer|encode|decode``): request body
  is an npz of positional input arrays; a 200 response body is an npz of the
  output pytree's leaves. Anything else is a JSON error that MIRRORS the
  replica-side exception class across the process boundary (rejected /
  breaker_open / deadline / affinity_lost / engine+transient-bool), so the
  router's failover policy classifies a remote failure exactly as it would a
  local one.
- **latent-cache sessions live ON the replica** (``/rpc/encode?session=S``
  stores the latents; ``/rpc/decode?session=S`` reads them): the whole point
  of affinity routing is that the encoded state never re-crosses the wire.
  A replica that died (or restarted) answers a decode for a session it never
  saw with ``affinity_lost`` — the router drops the pin and the caller
  re-encodes (spill-on-death).
- **admin verbs are the rollout surface**: ``/admin/drain`` stops admission
  and returns once accepted work finished (``ServingEngine.drain``),
  ``/admin/resume`` re-opens, ``/admin/update_params`` hot-swaps the served
  tree from a params *spec* (checkpoint path / deploy publication dir
  (digest-verified on load) / reinit seed / scale factor / ``rollback`` to
  the previous tree — kept in memory exactly for the router's
  auto-rollback), ``/admin/quit`` exits cleanly.
- **readiness is explicit** (``GET /statz`` → ``replica.ready``): true only
  once every engine's warm pool is live (the ``engine_ready`` gauges), which
  is what gates a (re)started replica's join — a replica mid-warmup is
  scraped as JOINING and receives no traffic.

``python -m perceiver_io_tpu.serving.replica --port P --preset tiny --cpu``
runs a synthetic-init replica (tests, ``tools/load_bench.py --replicas``);
``--checkpoint/--tokenizer`` serves a real train run (``cli/serve.py
--replicas`` spawns exactly this). SIGTERM/SIGINT drain gracefully and exit
0. ``PIT_FAULTS`` (env) applies inside the replica process, so chaos drills
target one replica's dispatch path (``engine.dispatch.<engine-name>``)
without code changes.

:class:`LocalReplica` is the in-process twin of the HTTP client — the same
call/scrape/drain/update surface over engines in THIS process (tier-1 tests,
single-host load sweeps) with a ``kill()`` that simulates the dead-replica
transport signature (connection errors, sessions lost).
"""

from __future__ import annotations

import argparse
import io
import json
import socket
import sys
import threading
import time
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.resilience import (
    AffinityLost,
    BreakerOpen,
    DeadlineExceeded,
    RejectedError,
    classify_error,
    faults,
)

_MAX_SESSIONS = 1024  # FIFO-evicted; a session is one encode's latents


class RemoteEngineError(RuntimeError):
    """A replica-side engine error mirrored across the RPC boundary; carries
    the remote classification as the ``transient`` attribute the classification
    honors (``classify_error``), so failover decisions survive the hop."""

    def __init__(self, message: str, transient: bool):
        super().__init__(message)
        self.transient = transient


# -- wire format -------------------------------------------------------------


def pack_arrays(arrays: Sequence[np.ndarray]) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **{f"arr{i}": np.asarray(a) for i, a in enumerate(arrays)})
    return buf.getvalue()


def unpack_arrays(data: bytes) -> List[np.ndarray]:
    with np.load(io.BytesIO(data)) as z:
        return [z[f"arr{i}"] for i in range(len(z.files))]


def _error_body(kind: str, message: str, transient: bool = False) -> bytes:
    return json.dumps(
        {"error": kind, "message": message, "transient": transient}
    ).encode()


_ERROR_KINDS = {
    BreakerOpen: "breaker_open",
    RejectedError: "rejected",
    DeadlineExceeded: "deadline",
    AffinityLost: "affinity_lost",
}


# -- streamed-frame wire format (the generate RPC) ---------------------------
#
# A generate response is a SEQUENCE of length-prefixed frames — 4-byte
# big-endian length + a JSON payload — written incrementally (chunked
# transfer encoding on the HTTP twin), so the router/caller observes tokens
# as they decode instead of waiting out the stream. Token-chunk frames carry
# per-step phase timestamps (`chunk_ms`, `pos`, `steps`); the terminal frame
# is either the `done` summary or an `error` frame mirroring the replica
# exception (the streaming counterpart of `_wire_error` — by the time a
# mid-stream error occurs, the 200 status line is long gone).


def pack_frame(payload: Dict[str, Any]) -> bytes:
    body = json.dumps(payload).encode()
    return len(body).to_bytes(4, "big") + body


def read_frames(read: Callable[[int], bytes]):
    """Yield JSON frames from a ``read(n)`` byte source until EOF. ``read``
    may return short; EOF mid-frame raises ConnectionError (the dead-replica
    signature the failover policy re-routes)."""

    def read_exact(n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            part = read(n - len(buf))
            if not part:
                if buf:
                    raise ConnectionError(
                        "generate stream truncated mid-frame")
                return None
            buf += part
        return buf

    while True:
        header = read_exact(4)
        if header is None:
            return
        body = read_exact(int.from_bytes(header, "big"))
        if body is None:
            raise ConnectionError("generate stream truncated at frame body")
        yield json.loads(body.decode())


def _wire_error(exc: BaseException) -> bytes:
    for cls, kind in _ERROR_KINDS.items():
        if isinstance(exc, cls):
            return _error_body(kind, str(exc))
    return _error_body(
        "engine", f"{type(exc).__name__}: {exc}",
        transient=classify_error(exc) == "transient",
    )


def raise_wire_error(body: bytes, replica: str) -> None:
    """Client side: re-raise the replica's mirrored exception."""
    try:
        err = json.loads(body.decode())
    except (ValueError, UnicodeDecodeError):
        raise RemoteEngineError(
            f"replica {replica!r}: unparseable error body", transient=False)
    kind, msg = err.get("error", "engine"), err.get("message", "")
    prefix = f"replica {replica!r}: "
    if kind == "breaker_open":
        raise BreakerOpen(prefix + msg)
    if kind == "rejected":
        raise RejectedError(prefix + msg)
    if kind == "deadline":
        raise DeadlineExceeded(prefix + msg)
    if kind == "affinity_lost":
        raise AffinityLost(prefix + msg)
    raise RemoteEngineError(prefix + msg, transient=bool(err.get("transient")))


# -- the replica application -------------------------------------------------


class ReplicaApp:
    """One replica's serving state: engines keyed by RPC verb, the latent
    session store, and the params spec machinery (update / in-memory
    rollback) the rolling rollout drives.

    ``params_factory(spec) -> raw param tree`` realizes ``checkpoint`` /
    ``reinit`` specs (the process entry point knows how to build its model);
    ``scale`` and ``rollback`` are handled here. The previous raw tree is
    kept in memory so a rollback is an instant re-install, never a reload.
    """

    def __init__(
        self,
        engines: Dict[str, Any],
        params,
        params_factory: Optional[Callable[[Dict[str, Any]], Any]] = None,
        name: str = "replica",
        registry: Optional[obs.MetricsRegistry] = None,
        assume_ready: bool = False,
        drain_timeout_s: float = 60.0,
        generator=None,
        stream_slo: Optional[obs.SLO] = None,
    ):
        if not engines:
            raise ValueError("ReplicaApp needs at least one engine")
        self.name = name
        self.engines = dict(engines)
        self.drain_timeout_s = drain_timeout_s
        self._params = params
        self._prev_params = None
        self._params_factory = params_factory
        self._update_lock = threading.Lock()
        self._assume_ready = assume_ready
        self._sessions: "OrderedDict[str, Any]" = OrderedDict()
        self._sessions_lock = threading.Lock()
        self.quit_event = threading.Event()
        reg = registry if registry is not None else obs.get_registry()
        # the generative workload (task=generate): an ARGenerator serving
        # streamed continuations with replica-resident session caches —
        # pinned by the router exactly like the latent-cache sessions
        self.generator = generator
        self._gen_store = None
        # stream-shaped SLO (TTFT/ITL targets): classified from the
        # caller-visible frame clock in generate(), scraped as stream_burn
        self.stream_slo_tracker = None
        if (generator is not None and stream_slo is not None
                and stream_slo.stream_signals):
            self.stream_slo_tracker = obs.SLOTracker(
                stream_slo, registry=reg, labels={"replica": name})
        self._gen_lock = threading.Lock()
        self._gen_active = 0        # streams in flight (under _gen_lock)
        self._gen_requests = 0      # streams served (under _gen_lock)
        self._gen_draining = threading.Event()
        if generator is not None:
            from perceiver_io_tpu.inference.generate import (
                GenerateSessionStore,
            )

            # a continuous-batching generator owns arena slots behind its
            # resident sessions — store evictions must free them (epoch-
            # checked in the engine, so a stale handle is a no-op)
            self._gen_store = GenerateSessionStore(
                registry=reg, name=name,
                on_evict=getattr(generator, "release_session", None))
            self._m_gen_requests = reg.counter(
                "replica_generate_requests_total",
                "streamed generate RPCs served",
                {"replica": name, "task": "generate"})
            self._m_gen_tokens = reg.counter(
                "replica_generate_tokens_total",
                "tokens streamed to callers",
                {"replica": name, "task": "generate"})
            self._m_gen_active = reg.gauge(
                "replica_generate_active",
                "generate streams in flight",
                {"replica": name, "task": "generate"})
        self._m_version = reg.gauge(
            "replica_params_version",
            "monotonic count of installed param trees (0 = the boot tree)",
            {"replica": name})
        self._m_sessions = reg.gauge(
            "replica_sessions", "latent-cache sessions resident",
            {"replica": name})

    # -- traffic -------------------------------------------------------------

    def call(self, kind: str, arrays: List[np.ndarray],
             session: Optional[str] = None,
             timeout_s: Optional[float] = None,
             trace: Optional[obs.TraceContext] = None,
             meta: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
        """Serve one RPC verb. ``trace`` (the caller's propagated context)
        attaches a ``replica_serve`` span and flows into the engine;
        ``meta``, when a dict is passed, is filled with the engine future's
        per-part ``phases`` — the attribution that previously died at the
        engine boundary now crosses the RPC (the HTTP shim rides it back as
        the ``X-Phases`` response header; ``LocalReplica`` fills it
        directly — parity pinned by the fabric tests)."""
        if trace is None:  # untraced: no span bookkeeping at all
            return self._call_inner(kind, arrays, session, timeout_s,
                                    None, meta)
        t0 = time.monotonic()
        serve_ctx = trace.child()
        try:
            out = self._call_inner(kind, arrays, session, timeout_s,
                                   serve_ctx, meta)
        except BaseException as e:
            obs.record_span("replica_serve", serve_ctx, t0,
                            time.monotonic() - t0, replica=self.name,
                            kind=kind, ok=False, error=type(e).__name__)
            raise
        obs.record_span("replica_serve", serve_ctx, t0,
                        time.monotonic() - t0, replica=self.name, kind=kind,
                        ok=True)
        return out

    def _call_inner(self, kind: str, arrays: List[np.ndarray],
                    session: Optional[str],
                    timeout_s: Optional[float],
                    trace: Optional[obs.TraceContext],
                    meta: Optional[Dict[str, Any]]) -> List[np.ndarray]:
        import jax

        engine = self.engines.get(kind)
        if engine is None:
            raise ValueError(
                f"unknown rpc kind {kind!r}; one of {sorted(self.engines)}"
            )
        if kind == "decode" and session is not None:
            with self._sessions_lock:
                latents = self._sessions.get(session)
            if latents is None:
                raise AffinityLost(
                    f"session {session!r} not resident on replica "
                    f"{self.name!r} (encoded elsewhere, or lost to a restart)"
                )
            arrays = [latents, *arrays]
        fut = engine.submit(*arrays, trace=trace)
        out = fut.result(timeout=timeout_s)
        if meta is not None:
            meta["phases"] = fut.phases
        if kind == "encode" and session is not None:
            with self._sessions_lock:
                self._sessions[session] = out
                while len(self._sessions) > _MAX_SESSIONS:
                    self._sessions.popitem(last=False)
                self._m_sessions.set(len(self._sessions))
            # the latents stay HERE (that is the point of affinity); the
            # caller gets the batch/latent geometry as its ack
            return [np.asarray(np.asarray(out).shape, np.int64)]
        return [np.asarray(leaf) for leaf in jax.tree.leaves(out)]

    # -- the generative workload (task=generate) -----------------------------

    def generate(self, prefix: Sequence[int],
                 session: Optional[str] = None,
                 max_new: int = 16,
                 temperature: float = 0.0,
                 top_k: int = 0,
                 seed: int = 0,
                 on_frame: Optional[Callable[[Dict[str, Any]], None]] = None,
                 trace: Optional[obs.TraceContext] = None) -> Dict[str, Any]:
        """Serve one streamed continuation of ``prefix`` (the FULL accepted
        sequence — prompt plus any previously streamed tokens the caller
        holds). When ``session`` names a resident cache whose sequence is
        exactly ``prefix``, decoding continues incrementally; anything else
        (first call, evicted, replica restarted, spilled pin) re-encodes
        from the prefix — which, with the position-folded sampling keys,
        reproduces the identical stream. Frames go to ``on_frame``: token
        chunks with per-step phase timestamps, then a final ``done``
        summary. Returns the summary."""
        if self.generator is None:
            raise ValueError(
                f"replica {self.name!r} serves no generate task")
        if self._gen_draining.is_set():
            raise RejectedError(
                f"replica {self.name!r} is draining — not admitting new "
                "generate streams")
        from perceiver_io_tpu.inference.generate import SamplingConfig

        prefix = [int(t) for t in np.asarray(prefix).reshape(-1)]
        sampling = SamplingConfig(temperature=temperature, top_k=top_k,
                                  seed=seed).normalized()
        with self._gen_lock:
            self._gen_active += 1
            self._m_gen_active.set(self._gen_active)
        t0 = time.monotonic()
        serve_ctx = trace.child() if trace is not None else None
        resident = self._gen_store.match(session, prefix)
        chunks = 0
        # the caller-visible frame clock: TTFT/ITL as this stream's consumer
        # experienced them (the ground truth the engine histograms reconcile
        # against, and the sample the stream SLO classifies)
        t_first: Optional[float] = None
        t_prev = t0
        itl_sum, itl_n = 0.0, 0

        def chunk_cb(tokens: List[int], info: Dict[str, Any]) -> None:
            nonlocal chunks, t_first, t_prev, itl_sum, itl_n
            now = time.monotonic()
            if t_first is None:
                t_first = now
            elif tokens:
                itl_sum += now - t_prev
                itl_n += len(tokens)
            t_prev = now
            chunks += 1
            self._m_gen_tokens.inc(len(tokens))
            if serve_ctx is not None:
                # one span per chunked decode dispatch: multi-step tail
                # attribution — which chunk of which stream burned the time
                dur = info["chunk_ms"] / 1e3
                obs.record_span(
                    "generate_step", serve_ctx.child(),
                    time.monotonic() - dur, dur, replica=self.name,
                    pos=info["pos"], steps=info["steps"])
            if on_frame is not None:
                on_frame({"tokens": tokens, **info})

        try:
            tokens, ses = self.generator.generate(
                prefix, max_new, sampling, on_chunk=chunk_cb,
                session=resident, trace=serve_ctx)
        except BaseException as e:
            if self.stream_slo_tracker is not None:
                # a died stream is bad on every configured stream signal
                self.stream_slo_tracker.record_stream(
                    ttft_s=(None if t_first is None else t_first - t0),
                    itl_s=(itl_sum / itl_n if itl_n else None), ok=False)
            if serve_ctx is not None:
                obs.record_span(
                    "replica_generate", serve_ctx, t0,
                    time.monotonic() - t0, replica=self.name, ok=False,
                    error=type(e).__name__)
            raise
        finally:
            with self._gen_lock:
                self._gen_active -= 1
                self._gen_requests += 1
                self._m_gen_active.set(self._gen_active)
        if ses is not None and len(ses.seq) < self.generator.max_seq_len:
            self._gen_store.put(session, ses)
        else:
            # the continuation exhausted the absolute position budget (or
            # the engine kept no resident state): retire the pin for real —
            # reason-labeled, so drills assert on metrics, not logs
            self._gen_store.remove(session, "finished")
        self._m_gen_requests.inc()
        if self.stream_slo_tracker is not None:
            self.stream_slo_tracker.record_stream(
                ttft_s=(None if t_first is None else t_first - t0),
                itl_s=(itl_sum / itl_n if itl_n else None), ok=True)
        summary = {
            "done": True,
            "tokens_total": len(tokens),
            "chunks": chunks,
            "resumed": resident is not None,
            "ms": round((time.monotonic() - t0) * 1e3, 3),
        }
        if serve_ctx is not None:
            obs.record_span(
                "replica_generate", serve_ctx, t0, time.monotonic() - t0,
                replica=self.name, ok=True, tokens=len(tokens),
                resumed=resident is not None)
        if on_frame is not None:
            on_frame(summary)
        return summary

    # -- rollout surface -----------------------------------------------------

    def update_params(self, spec: Dict[str, Any]) -> int:
        """Hot-swap from a params spec; returns the new version. The engines
        keep their compiled programs (same treedef/avals ⇒ no recompile; the
        AOT warm pool carries over), so a swap is params-preparation time,
        not a compile family."""
        kind = spec.get("kind")
        with self._update_lock:
            if kind == "rollback":
                if self._prev_params is None:
                    raise ValueError("nothing to roll back to")
                tree = self._prev_params
            elif kind == "scale":
                factor = float(spec["factor"])
                tree = _scale_tree(self._params, factor)
            elif kind in ("reinit", "checkpoint", "publication"):
                if self._params_factory is None:
                    raise ValueError(
                        f"this replica cannot realize {kind!r} specs "
                        "(no params factory)"
                    )
                tree = self._params_factory(spec)
            else:
                raise ValueError(
                    f"unknown params spec kind {kind!r}; one of "
                    "rollback|scale|reinit|checkpoint|publication"
                )
            for engine in self.engines.values():
                engine.update_params(tree)
            # the swap RPC answers only once every worker INSTALLED the
            # staged tree (bounded: a worker wedged in a dispatch must not
            # hang the admin surface) — the rollout's bake then watches the
            # new tree from its first poll, never a half-swapped replica
            deadline = time.monotonic() + 10.0
            while (any(e.params_pending for e in self.engines.values())
                   and time.monotonic() < deadline):
                time.sleep(0.002)
            self._prev_params, self._params = self._params, tree
            self._m_version.inc()
            version = int(self._m_version.value)
        obs.event("replica_params_update", replica=self.name, kind=kind,
                  version=version)
        return version

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        timeout_s = self.drain_timeout_s if timeout_s is None else timeout_s
        from perceiver_io_tpu.inference.engine import drain_engines

        # close every door first (drain_engines discipline): generate
        # streams stop admitting before the engines drain, then accepted
        # streams finish within the shared deadline
        self._gen_draining.set()
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        ok = drain_engines(self.engines.values(), timeout_s)
        while True:
            with self._gen_lock:
                active = self._gen_active
            if active == 0:
                return ok
            if deadline is not None and time.monotonic() >= deadline:
                obs.event("replica_generate_drain_timeout",
                          replica=self.name, active=active)
                return False
            time.sleep(0.01)

    def resume(self) -> None:
        self._gen_draining.clear()
        for engine in self.engines.values():
            engine.resume_admission()

    # -- introspection -------------------------------------------------------

    @property
    def ready(self) -> bool:
        return self._assume_ready or all(
            e.ready for e in self.engines.values()
        )

    def status(self) -> Dict[str, Any]:
        """The scrape body the router's load/health view is built from."""
        engines = {}
        queue_depth = inflight = 0
        breaker_open = False
        slo_burn = 0.0
        for key, e in self.engines.items():
            backlog = e.backlog
            queue_depth += backlog
            inflight += e.inflight
            b_open = e.breaker is not None and e.breaker.state == "open"
            breaker_open = breaker_open or b_open
            burn = (e.slo_tracker.burn_rate()
                    if e.slo_tracker is not None
                    and e.slo_tracker.sample_count()
                    >= e.slo_tracker.slo.min_samples else 0.0)
            slo_burn = max(slo_burn, burn)
            engines[key] = {
                "ready": e.ready, "draining": e.draining,
                "backlog": backlog, "breaker_open": b_open,
                "slo_burn": round(burn, 4),
            }
        stream_burn = 0.0
        tr = self.stream_slo_tracker
        if tr is not None:
            for signal in tr.slo.stream_signals:
                # same min_samples quiet period as the request burn: one
                # slow first stream must not degrade a fresh replica
                if tr.stream_sample_count(signal) >= tr.slo.min_samples:
                    stream_burn = max(stream_burn,
                                      tr.stream_burn_rate(signal))
        with self._sessions_lock:
            sessions = len(self._sessions)
        with self._gen_lock:
            gen_active, gen_requests = self._gen_active, self._gen_requests
        return {
            "name": self.name,
            "ready": self.ready,
            # generate streams count as requests (the autoscaler's offered-
            # rate signal must see the second traffic class) and as load
            # (queue_depth steers least-loaded placement)
            "requests_total": gen_requests + sum(
                e.requests_served for e in self.engines.values()),
            "draining": (self._gen_draining.is_set()
                         or any(e.draining for e in self.engines.values())),
            "queue_depth": queue_depth + gen_active,
            "inflight": inflight + gen_active,
            "breaker_open": breaker_open,
            "slo_burn": round(slo_burn, 4),
            "stream_burn": round(stream_burn, 4),
            "params_version": int(self._m_version.value),
            "sessions": sessions,
            "generate_sessions": (len(self._gen_store)
                                  if self._gen_store is not None else 0),
            "generate_active": gen_active,
            # continuous-batching engines expose their dispatch aggregates
            # (slot occupancy, steps/dispatch) — absent for per-session ones
            "decode_batching": (self.generator.stats()
                                if hasattr(self.generator, "stats")
                                else None),
            "engines": engines,
        }

    def close(self) -> None:
        for engine in self.engines.values():
            engine.close()
        closer = getattr(self.generator, "close", None)
        if closer is not None:
            closer()
        if self.stream_slo_tracker is not None:
            self.stream_slo_tracker.close()


def _scale_tree(tree, factor: float):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: x * factor
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
        tree,
    )


# -- the HTTP surface --------------------------------------------------------


class _TrackedHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that can SEVER live keep-alive connections.

    ``server_close`` only closes the listener; with pooled persistent
    router connections (r22), handler threads keep serving on their open
    sockets after shutdown — a "closed" replica would keep answering. The
    dead-replica contract (ConnectionError, the failover classification's
    reroute class) requires close to cut every live connection, matching
    the uds server's close semantics."""

    daemon_threads = True

    # pitlint PIT-LOCK: accepted sockets are added by the accept loop and
    # discarded by handler threads — touched only under _live_lock
    _guarded_by = {"_live": "_live_lock"}

    def __init__(self, *args, **kwargs):
        self._live: set = set()
        self._live_lock = threading.Lock()
        super().__init__(*args, **kwargs)

    def get_request(self):
        sock, addr = super().get_request()
        with self._live_lock:
            self._live.add(sock)
        return sock, addr

    def shutdown_request(self, request):
        with self._live_lock:
            self._live.discard(request)
        super().shutdown_request(request)

    def close_all_connections(self) -> None:
        with self._live_lock:
            live, self._live = list(self._live), set()
        for sock in live:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass


class ReplicaServer:
    """Loopback HTTP server over one :class:`ReplicaApp` (the replica-side
    half of the RPC shim; ``HttpReplicaClient`` is the router-side half)."""

    def __init__(self, app: ReplicaApp, host: str = "127.0.0.1",
                 port: int = 0,
                 registry: Optional[obs.MetricsRegistry] = None):
        self.app = app
        self._host = host
        self._port = port
        self._registry = registry if registry is not None else obs.get_registry()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> Optional[int]:
        return self._httpd.server_address[1] if self._httpd else None

    @property
    def url(self) -> Optional[str]:
        return f"http://{self._host}:{self.port}" if self._httpd else None

    def start(self) -> str:
        if self._httpd is not None:
            return self.url
        app, registry = self.app, self._registry

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"  # keep-alive: the client pools
            # persistent connections, and 1.1 gives Content-Length framed
            # bodies on both sides
            disable_nagle_algorithm = True  # small response frames must not
            # sit behind the peer's delayed ACK (the ~40 ms stall mode)

            def log_message(self, *args) -> None:
                pass  # RPC traffic must not spam the replica's stderr

            def _reply(self, code: int, body: bytes,
                       ctype: str = "application/json",
                       extra_headers: Optional[Dict[str, str]] = None,
                       ) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _query(self) -> Dict[str, str]:
                if "?" not in self.path:
                    return {}
                out = {}
                for pair in self.path.split("?", 1)[1].split("&"):
                    k, _, v = pair.partition("=")
                    out[k] = v
                return out

            def _body(self) -> bytes:
                n = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(n) if n else b""

            def do_GET(self) -> None:
                path = self.path.split("?", 1)[0]
                if path == "/healthz":
                    ok, detail = obs.healthz()
                    self._reply(200 if ok else 503,
                                json.dumps(detail).encode() + b"\n")
                elif path == "/statz":
                    ok, detail = obs.healthz()
                    body = {"replica": app.status(), "health": detail,
                            **registry.snapshot()}
                    self._reply(200, json.dumps(body).encode() + b"\n")
                else:
                    self._reply(404, _error_body("not_found", path))

            def _stream_generate(self, q: Dict[str, str]) -> None:
                """The generate RPC: body = npz([prefix ids]); response =
                length-prefixed JSON frames under chunked transfer encoding
                (the streaming twin of the arrays-in/arrays-out verbs)."""
                trace = obs.TraceContext.from_headers(self.headers)
                arrays = unpack_arrays(self._body())
                prefix = arrays[0].reshape(-1)
                started = False

                def send_chunk(data: bytes) -> None:
                    self.wfile.write(f"{len(data):X}\r\n".encode()
                                     + data + b"\r\n")
                    self.wfile.flush()

                def on_frame(frame: Dict[str, Any]) -> None:
                    nonlocal started
                    if not started:
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/octet-stream")
                        self.send_header("Transfer-Encoding", "chunked")
                        self.end_headers()
                        started = True
                    send_chunk(pack_frame(frame))

                try:
                    app.generate(
                        prefix,
                        session=q.get("session"),
                        max_new=int(q.get("max_new", 16)),
                        temperature=float(q.get("temperature", 0.0)),
                        top_k=int(q.get("top_k", 0)),
                        seed=int(q.get("seed", 0)),
                        on_frame=on_frame,
                        trace=trace,
                    )
                except BaseException as e:
                    if not started:
                        self._reply(503, _wire_error(e))
                        return
                    # mid-stream failure: the status line is gone — mirror
                    # the exception as a terminal error frame instead
                    err = json.loads(_wire_error(e).decode())
                    send_chunk(pack_frame(err))
                if not started:
                    self._reply(200, b"")  # degenerate: nothing streamed
                    return
                self.wfile.write(b"0\r\n\r\n")  # terminal chunk
                self.wfile.flush()

            def do_POST(self) -> None:
                path = self.path.split("?", 1)[0]
                q = self._query()
                try:
                    if path == "/rpc/generate":
                        self._stream_generate(q)
                    elif path.startswith("/rpc/"):
                        kind = path[len("/rpc/"):]
                        timeout_s = (float(q["timeout_s"])
                                     if "timeout_s" in q else None)
                        # the propagated trace context rides the request
                        # headers; the engine's per-part phase attribution
                        # rides BACK as a response header (the npz body
                        # stays pure arrays)
                        trace = obs.TraceContext.from_headers(self.headers)
                        meta: Dict[str, Any] = {}
                        out = app.call(kind, unpack_arrays(self._body()),
                                       session=q.get("session"),
                                       timeout_s=timeout_s, trace=trace,
                                       meta=meta)
                        extra = {}
                        if meta.get("phases"):
                            # headers must stay under http.client's 64 KB
                            # line limit: a many-part request (hundreds of
                            # engine parts) would otherwise fail an
                            # ALREADY-SERVED rpc at the router's response
                            # parse — cap the attribution, never the result
                            body_json = json.dumps(meta["phases"][:64])
                            if len(body_json) <= 32768:
                                extra["X-Phases"] = body_json
                        self._reply(200, pack_arrays(out),
                                    "application/octet-stream",
                                    extra_headers=extra)
                    elif path == "/admin/drain":
                        timeout_s = (float(q["timeout_s"])
                                     if "timeout_s" in q else None)
                        drained = app.drain(timeout_s)
                        self._reply(200, json.dumps(
                            {"drained": drained}).encode())
                    elif path == "/admin/resume":
                        app.resume()
                        self._reply(200, b"{}")
                    elif path == "/admin/update_params":
                        spec = json.loads(self._body().decode() or "{}")
                        version = app.update_params(spec)
                        self._reply(200, json.dumps(
                            {"params_version": version}).encode())
                    elif path == "/admin/quit":
                        self._reply(200, b"{}")
                        app.quit_event.set()
                    else:
                        self._reply(404, _error_body("not_found", path))
                except BaseException as e:  # mirrored, never a stack trace
                    self._reply(503, _wire_error(e))

        self._httpd = _TrackedHTTPServer((self._host, self._port), Handler)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"{self.app.name}-rpc", daemon=True,
        )
        self._thread.start()
        return self.url

    def close(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            # sever live keep-alive connections too: pooled router clients
            # must see the dead-replica ConnectionError, not stale service
            self._httpd.close_all_connections()
            self._httpd = None
            if self._thread is not None:
                self._thread.join(timeout=5)
                self._thread = None


# -- the router-side clients -------------------------------------------------


class HttpReplicaClient:
    """Router-side handle to one replica process. Transport failures (dead
    replica, mid-request ``kill -9``) surface as ``ConnectionError`` with the
    classification's transient markers — the failover policy re-routes them.

    Requests ride POOLED persistent HTTP/1.1 connections with TCP_NODELAY
    set on both sides: the previous one-urllib-connection-per-call pattern
    wrote headers and body as separate segments, and Nagle holding the
    second segment behind the peer's delayed ACK put a ~40 ms mode on
    small-frame round-trips (the documented trap from the abandoned
    transport prototype — ROADMAP item 1). A request that fails on a pooled
    connection is NOT transparently resent (the replica may have executed
    it); it surfaces as ConnectionError and the failover policy decides."""

    # pitlint PIT-LOCK: idle pooled connections are checked out/in by every
    # router worker thread concurrently — touched only under _pool_lock
    _guarded_by = {"_pool": "_pool_lock"}

    def __init__(self, name: str, base_url: str, timeout_s: float = 120.0,
                 pool_size: int = 4):
        self.name = name
        self.base_url = base_url.rstrip("/")
        self.timeout_s = timeout_s
        hostport = self.base_url.split("://", 1)[-1]
        host, _, port = hostport.partition(":")
        self._host, self._port = host, int(port or 80)
        self._pool_size = max(1, int(pool_size))
        self._pool_lock = threading.Lock()
        self._pool: List[Any] = []  # idle http.client.HTTPConnection

    def _checkout(self, timeout_s: float):
        import http.client

        with self._pool_lock:
            conn = self._pool.pop() if self._pool else None
        if conn is None:
            conn = http.client.HTTPConnection(
                self._host, self._port, timeout=timeout_s)
        else:
            conn.timeout = timeout_s
        if conn.sock is not None:
            conn.sock.settimeout(timeout_s)
        return conn

    def _checkin(self, conn) -> None:
        with self._pool_lock:
            if len(self._pool) < self._pool_size:
                self._pool.append(conn)
                return
        conn.close()

    def close(self) -> None:
        with self._pool_lock:
            pool, self._pool = list(self._pool), []
        for conn in pool:
            conn.close()

    def _request(self, method: str, path: str, body: Optional[bytes] = None,
                 timeout_s: Optional[float] = None,
                 headers: Optional[Dict[str, str]] = None,
                 meta: Optional[Dict[str, Any]] = None) -> bytes:
        import http.client

        conn = self._checkout(
            timeout_s if timeout_s is not None else self.timeout_s)
        try:
            if conn.sock is None:
                conn.connect()
                # no-delay on the client side too: the request's header and
                # body writes must not wait out the replica's delayed ACK
                conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            faults.inject("transport.send")
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/octet-stream",
                                  **(headers or {})})
            resp = conn.getresponse()
            data = resp.read()
            faults.inject("transport.recv")
            status = resp.status
            if meta is not None and status < 400:
                phases = resp.getheader("X-Phases")
                if phases:
                    try:
                        meta["phases"] = json.loads(phases)
                    except ValueError:
                        pass  # a torn header degrades attribution only
            reusable = not resp.will_close
        except (http.client.HTTPException, ConnectionError, OSError) as e:
            conn.close()
            raise ConnectionError(
                f"replica {self.name!r}: connection closed / failed to "
                f"connect ({type(e).__name__}: {e})"
            ) from e
        if reusable:
            self._checkin(conn)
        else:
            conn.close()
        if status >= 400:
            # classification bodies ride error statuses (the body was fully read,
            # so the connection above stayed reusable)
            raise_wire_error(data, self.name)
        return data

    def call(self, kind: str, arrays: Sequence[np.ndarray],
             session: Optional[str] = None,
             timeout_s: Optional[float] = None,
             trace: Optional[obs.TraceContext] = None,
             meta: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
        """One RPC verb. ``trace`` propagates the caller's span context to
        the replica as headers; ``meta`` (a dict, filled in place) receives
        the replica engine's per-part ``phases`` from the response header —
        the router surfaces them on its futures."""
        q = []
        if session is not None:
            q.append(f"session={session}")
        if timeout_s is not None:
            q.append(f"timeout_s={timeout_s:g}")
        path = f"/rpc/{kind}" + ("?" + "&".join(q) if q else "")
        out = self._request("POST", path, pack_arrays(arrays),
                            timeout_s=timeout_s,
                            headers=(trace.to_headers()
                                     if trace is not None else None),
                            meta=meta)
        return unpack_arrays(out)

    def generate_stream(self, prefix: Sequence[int],
                        session: Optional[str] = None,
                        max_new: int = 16,
                        temperature: float = 0.0,
                        top_k: int = 0,
                        seed: int = 0,
                        on_frame: Optional[Callable[[Dict[str, Any]], None]]
                        = None,
                        timeout_s: Optional[float] = None,
                        trace: Optional[obs.TraceContext] = None
                        ) -> Dict[str, Any]:
        """The streamed generate RPC: frames (token chunks with per-step
        phase stamps, then the ``done`` summary) are delivered to
        ``on_frame`` AS THEY ARRIVE; returns the summary. A mid-stream
        error frame re-raises the replica's mirrored exception; a cut
        connection raises ConnectionError — the caller (router) decides
        what already-received tokens mean (they are accepted: re-encode
        from the extended prefix)."""
        import urllib.error
        import urllib.request

        q = [f"max_new={int(max_new)}", f"temperature={float(temperature):g}",
             f"top_k={int(top_k)}", f"seed={int(seed)}"]
        if session is not None:
            q.append(f"session={session}")
        req = urllib.request.Request(
            self.base_url + "/rpc/generate?" + "&".join(q),
            data=pack_arrays([np.asarray(prefix, np.int64)]),
            method="POST",
            headers={"Content-Type": "application/octet-stream",
                     **(trace.to_headers() if trace is not None else {})},
        )
        summary: Optional[Dict[str, Any]] = None
        try:
            with urllib.request.urlopen(
                req, timeout=timeout_s if timeout_s is not None
                else self.timeout_s
            ) as resp:
                for frame in read_frames(resp.read):
                    if "error" in frame:
                        raise_wire_error(
                            json.dumps(frame).encode(), self.name)
                    if frame.get("done"):
                        summary = frame
                    if on_frame is not None:
                        on_frame(frame)
        except urllib.error.HTTPError as e:
            raise_wire_error(e.read(), self.name)
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            if isinstance(e, ConnectionError) and "truncated" in str(e):
                raise
            reason = getattr(e, "reason", e)
            raise ConnectionError(
                f"replica {self.name!r}: connection closed / failed to "
                f"connect ({type(reason).__name__}: {reason})"
            ) from e
        if summary is None:
            raise ConnectionError(
                f"replica {self.name!r}: generate stream ended without a "
                "done frame")
        return summary

    def scrape(self, timeout_s: float = 5.0) -> Dict[str, Any]:
        """The replica's ``/statz`` ``replica`` block, plus ``up``. Never
        raises: an unreachable replica scrapes as ``{"up": False}``."""
        try:
            body = self._request("GET", "/statz", timeout_s=timeout_s)
            status = json.loads(body.decode()).get("replica", {})
            status["up"] = True
            return status
        except Exception as e:
            return {"up": False, "error": f"{type(e).__name__}: {e}"}

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        q = f"?timeout_s={timeout_s:g}" if timeout_s is not None else ""
        body = self._request(
            "POST", "/admin/drain" + q,
            timeout_s=(timeout_s + 10.0) if timeout_s is not None else None,
        )
        return bool(json.loads(body.decode()).get("drained"))

    def resume(self) -> None:
        self._request("POST", "/admin/resume")

    def update_params(self, spec: Dict[str, Any],
                      timeout_s: Optional[float] = None) -> int:
        body = self._request("POST", "/admin/update_params",
                             json.dumps(spec).encode(), timeout_s=timeout_s)
        return int(json.loads(body.decode())["params_version"])

    def quit(self) -> None:
        try:
            self._request("POST", "/admin/quit", timeout_s=5.0)
        except Exception:
            pass  # already gone is fine


class LocalReplica:
    """In-process twin of :class:`HttpReplicaClient` over a
    :class:`ReplicaApp` — the tier-1/test/local-bench transport.

    ``kill()`` simulates ``kill -9``: every subsequent (and in-flight) call
    raises the dead-replica ``ConnectionError`` signature, the session store
    is wiped (the latents died with the 'process'), and scrapes report
    ``up=False`` — until ``revive()`` (the supervisor-restart analogue, which
    also resets admission and reports not-ready until re-warmed)."""

    def __init__(self, app: ReplicaApp):
        self.app = app
        self.name = app.name
        self._dead = threading.Event()

    def _check_dead(self) -> None:
        if self._dead.is_set():
            raise ConnectionError(
                f"replica {self.name!r}: connection closed (replica killed)"
            )

    def call(self, kind: str, arrays: Sequence[np.ndarray],
             session: Optional[str] = None,
             timeout_s: Optional[float] = None,
             trace: Optional[obs.TraceContext] = None,
             meta: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
        self._check_dead()
        # same trace/meta surface as HttpReplicaClient (parity pinned by
        # the fabric tests): the context flows into the app, the engine's
        # phase attribution flows back through meta
        out = self.app.call(kind, list(arrays), session=session,
                            timeout_s=timeout_s, trace=trace, meta=meta)
        # a kill LANDING mid-request: the work may have run, but the
        # response never reached the router (at-most-once delivery is about
        # responses, not executions)
        self._check_dead()
        return out

    def generate_stream(self, prefix: Sequence[int],
                        session: Optional[str] = None,
                        max_new: int = 16,
                        temperature: float = 0.0,
                        top_k: int = 0,
                        seed: int = 0,
                        on_frame: Optional[Callable[[Dict[str, Any]], None]]
                        = None,
                        timeout_s: Optional[float] = None,
                        trace: Optional[obs.TraceContext] = None
                        ) -> Dict[str, Any]:
        """In-process twin of the streamed generate RPC, with the kill
        semantics of a cut connection: a ``kill()`` landing mid-stream
        suppresses every later frame and raises the dead-replica
        ConnectionError — frames already delivered were accepted (exactly
        the at-most-once boundary the HTTP twin has)."""
        self._check_dead()

        def gated(frame: Dict[str, Any]) -> None:
            self._check_dead()  # the wire died: nothing further arrives
            if on_frame is not None:
                on_frame(frame)

        summary = self.app.generate(
            prefix, session=session, max_new=max_new,
            temperature=temperature, top_k=top_k, seed=seed,
            on_frame=gated, trace=trace)
        self._check_dead()
        return summary

    def scrape(self, timeout_s: float = 5.0) -> Dict[str, Any]:
        if self._dead.is_set():
            return {"up": False, "error": "replica killed"}
        status = self.app.status()
        status["up"] = True
        return status

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        self._check_dead()
        return self.app.drain(timeout_s)

    def resume(self) -> None:
        self._check_dead()
        self.app.resume()

    def update_params(self, spec: Dict[str, Any],
                      timeout_s: Optional[float] = None) -> int:
        self._check_dead()
        return self.app.update_params(spec)

    def quit(self) -> None:
        self.app.quit_event.set()

    def kill(self) -> None:
        self._dead.set()
        with self.app._sessions_lock:
            self.app._sessions.clear()
        if self.app._gen_store is not None:
            # the generation caches died with the 'process'
            self.app._gen_store.clear()

    def revive(self) -> None:
        self.app.resume()
        self._dead.clear()


# -- the replica process entry point -----------------------------------------


def _load_publication_spec(spec: Dict[str, Any]):
    """Realize a ``{"kind": "publication", "path": DIR}`` params spec: the
    deploy-loop rollout surface (``perceiver_io_tpu.deploy``). The load
    VERIFIES the manifest's content digest on the replica — even with the
    router-side admission gate already passed, a tree corrupted between
    gate and install raises here instead of serving."""
    from perceiver_io_tpu.deploy import load_publication

    tree, _ = load_publication(spec["path"], verify_digest=True)
    return tree


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="one serving replica behind the router tier "
                    "(perceiver_io_tpu.serving)")
    parser.add_argument("--port", type=int, default=0,
                        help="RPC port (0 = ephemeral; announced on stderr)")
    parser.add_argument("--name", default="replica")
    parser.add_argument("--cpu", action="store_true",
                        help="pin the CPU backend before jax initializes")
    parser.add_argument("--transport", choices=("http", "uds", "shmem"),
                        default="http",
                        help="data plane for the call() RPC: 'uds' adds a "
                             "pipelined unix-socket frame server, 'shmem' "
                             "adds the shared-memory slot slab on top; the "
                             "HTTP surface stays up either way (admin verbs "
                             "+ the streamed generate RPC ride it)")
    parser.add_argument("--shm_slots", type=int, default=16,
                        help="shmem transport: slots in the replica's slab")
    parser.add_argument("--shm_slot_mb", type=float, default=4.0,
                        help="shmem transport: slot size; payloads past it "
                             "fall back to inline uds frames")
    src = parser.add_argument_group("model source")
    src.add_argument("--task", choices=("mlm", "generate"), default="mlm",
                     help="workload class: 'mlm' = the fill-mask engines "
                          "(infer/encode/decode); 'generate' = the "
                          "Perceiver-AR causal LM with the streamed "
                          "generate RPC + session cache")
    src.add_argument("--preset", choices=("tiny", "flagship"), default=None,
                     help="synthetic-init preset (tests/benches; no "
                          "checkpoint needed; task picks the mlm or ar "
                          "variant)")
    src.add_argument("--seed", type=int, default=0,
                     help="preset mode: param init seed")
    src.add_argument("--checkpoint", default=None,
                     help="serve a train_mlm (or, with --task generate, "
                          "train_ar) checkpoint dir instead")
    src.add_argument("--tokenizer", default=None,
                     help="tokenizer json (checkpoint mode)")
    src.add_argument("--step", type=int, default=None)
    src.add_argument("--generate_chunk", type=int, default=8,
                     help="generate task: decode steps per chunked "
                          "dispatch (= streaming granularity)")
    src.add_argument("--decode_batching", action="store_true",
                     help="generate task: continuous batching — pool "
                          "session caches into a slotted arena and pack "
                          "every active stream's steps into ONE batched "
                          "dispatch (token streams identical either way)")
    src.add_argument("--decode_slots", type=int, default=8,
                     help="decode batching: initial arena slots per "
                          "prefill width (power-of-two-bucketed; doubles "
                          "under pressure up to 8x)")
    eng = parser.add_argument_group("engine (mirrors cli/serve.py)")
    eng.add_argument("--max_batch", type=int, default=8)
    eng.add_argument("--max_delay_ms", type=float, default=0.0)
    eng.add_argument("--dtype", choices=("float32", "bfloat16"),
                     default="float32")
    eng.add_argument("--quantize", choices=("none", "int8", "int4"),
                     default="none")
    eng.add_argument("--group_size", type=int, default=None,
                     help="int4 quantization group size along the reduction "
                          "dim (default 128)")
    eng.add_argument("--compile_cache", default=None)
    eng.add_argument("--no_warmup", action="store_true")
    eng.add_argument("--queue_limit", type=int, default=None)
    eng.add_argument("--request_deadline_s", type=float, default=None)
    eng.add_argument("--dispatch_retries", type=int, default=2)
    eng.add_argument("--breaker_failures", type=int, default=0)
    eng.add_argument("--breaker_cooldown_s", type=float, default=5.0)
    eng.add_argument("--heartbeat_deadline_s", type=float, default=None)
    eng.add_argument("--slo_p99_ms", type=float, default=None)
    eng.add_argument("--slo_availability", type=float, default=0.999)
    eng.add_argument("--slo_ttft_ms", type=float, default=None,
                     help="generate task: time-to-first-token target — "
                          "streams over it burn the stream SLO "
                          "(stream_burn in the scrape)")
    eng.add_argument("--slo_itl_ms", type=float, default=None,
                     help="generate task: mean inter-token-latency target "
                          "per stream (same burn wire as --slo_ttft_ms)")
    eng.add_argument("--trace_sample", type=float, default=0.0,
                     help="head-sampling rate for engine-MINTED traces, "
                          "i.e. requests arriving without a propagated "
                          "router context. Default 0: behind a router the "
                          "sampling decision belongs to the router (an "
                          "unsampled request arrives context-less, and a "
                          "replica re-minting for it would double-sample); "
                          "raise only for standalone replica use")
    parser.add_argument("--drain_timeout_s", type=float, default=60.0,
                        help="graceful-exit bound: SIGTERM/SIGINT stop "
                             "admission and wait this long for accepted "
                             "work before exiting")
    parser.add_argument("--events_jsonl", default=None,
                        help="append THIS replica's runtime events and "
                             "request-trace spans as JSON lines here (each "
                             "fleet process writes its own log; "
                             "tools/trace_assemble.py merges them into "
                             "per-request trace trees)")
    parser.add_argument("--events_max_mb", type=float, default=64.0,
                        help="rotate the events file past this size "
                             "(3 numbered segments kept); 0 disables "
                             "rotation. serve.py --replicas forwards its "
                             "--events_max_mb here")
    return parser


def _build_app(args):
    """Returns ``(app, max_seq_len)`` for the warmup example."""
    import jax

    from perceiver_io_tpu.inference.engine import ServingEngine, mlm_apply_fns

    if args.task == "generate":
        return _build_generate_app(args)
    if args.checkpoint:
        if not args.tokenizer:
            raise SystemExit("--checkpoint mode needs --tokenizer")
        from perceiver_io_tpu.data.tokenizer import load_tokenizer
        from perceiver_io_tpu.inference import load_mlm_checkpoint

        tokenizer = load_tokenizer(args.tokenizer)
        model, params, max_seq_len = load_mlm_checkpoint(
            args.checkpoint, tokenizer, step=args.step,
            dtype="bfloat16" if args.dtype == "bfloat16" else None,
        )

        def params_factory(spec):
            if spec.get("kind") == "publication":
                return _load_publication_spec(spec)
            if spec.get("kind") != "checkpoint":
                raise ValueError(f"checkpoint replica got spec {spec!r}")
            _, new_params, _ = load_mlm_checkpoint(
                spec.get("path", args.checkpoint), tokenizer,
                step=spec.get("step"),
                dtype="bfloat16" if args.dtype == "bfloat16" else None,
            )
            return new_params
    else:
        from perceiver_io_tpu.models.presets import flagship_mlm, tiny_mlm

        tiny = (args.preset or "tiny") == "tiny"
        build = tiny_mlm if tiny else flagship_mlm
        vocab = 503 if tiny else 10003
        max_seq_len = 64 if tiny else 512
        model = build(vocab_size=vocab, max_seq_len=max_seq_len)
        ids0 = np.zeros((1, max_seq_len), np.int32)

        def init_params(seed: int):
            return model.init(
                {"params": jax.random.key(seed),
                 "masking": jax.random.key(seed + 1)},
                ids0, ids0 == 0,
            )["params"]

        params = init_params(args.seed)

        def params_factory(spec):
            if spec.get("kind") == "publication":
                return _load_publication_spec(spec)
            if spec.get("kind") != "reinit":
                raise ValueError(f"preset replica got spec {spec!r}")
            return init_params(int(spec.get("seed", 0)))

    slo = None
    if args.slo_p99_ms is not None:
        slo = obs.SLO(latency_target_s=args.slo_p99_ms / 1e3,
                      availability_target=args.slo_availability,
                      name=args.name, burn_alert=None)
    common = dict(
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        compute_dtype="bfloat16" if args.dtype == "bfloat16" else None,
        quantize=None if args.quantize == "none" else args.quantize,
        group_size=args.group_size,
        queue_limit=args.queue_limit,
        request_deadline_s=args.request_deadline_s,
        dispatch_retries=args.dispatch_retries,
        breaker_failures=args.breaker_failures,
        breaker_cooldown_s=args.breaker_cooldown_s,
        heartbeat_deadline_s=args.heartbeat_deadline_s,
        compile_cache=args.compile_cache,
        slo=slo,
        trace_sample=args.trace_sample,
    )
    fns = mlm_apply_fns(model)
    engines = {
        kind: ServingEngine(fn, params, name=f"{args.name}-{kind}", **common)
        for kind, fn in fns.items()
    }
    app = ReplicaApp(
        engines, params, params_factory=params_factory, name=args.name,
        assume_ready=args.no_warmup, drain_timeout_s=args.drain_timeout_s,
    )
    return app, max_seq_len


def _build_generate_app(args):
    """The generate-task replica: a Perceiver-AR model behind the streamed
    RPC (plus a dense-forward ``infer`` engine — scoring/perplexity calls
    ride the ordinary arrays verb)."""
    import jax

    from perceiver_io_tpu.inference.engine import ServingEngine
    from perceiver_io_tpu.inference.generate import ARGenerator

    compute_dtype = "bfloat16" if args.dtype == "bfloat16" else None
    if args.checkpoint:
        if not args.tokenizer:
            raise SystemExit("--checkpoint mode needs --tokenizer")
        from perceiver_io_tpu.data.tokenizer import load_tokenizer
        from perceiver_io_tpu.inference.generate import load_ar_checkpoint

        tokenizer = load_tokenizer(args.tokenizer)
        model, params, max_seq_len = load_ar_checkpoint(
            args.checkpoint, tokenizer, step=args.step,
            dtype="bfloat16" if args.dtype == "bfloat16" else None,
        )

        def params_factory(spec):
            if spec.get("kind") == "publication":
                return _load_publication_spec(spec)
            if spec.get("kind") != "checkpoint":
                raise ValueError(f"checkpoint replica got spec {spec!r}")
            _, new_params, _ = load_ar_checkpoint(
                spec.get("path", args.checkpoint), tokenizer,
                step=spec.get("step"),
                dtype="bfloat16" if args.dtype == "bfloat16" else None,
            )
            return new_params
    else:
        from perceiver_io_tpu.models.presets import flagship_ar, tiny_ar

        tiny = (args.preset or "tiny") == "tiny"
        build = tiny_ar if tiny else flagship_ar
        max_seq_len = 64 if tiny else 512
        model = build()
        ids0 = np.zeros((1, max_seq_len), np.int32)

        def init_params(seed: int):
            import jax as _jax

            return model.init(
                {"params": _jax.random.key(seed)}, ids0, ids0 == 0,
            )["params"]

        params = init_params(args.seed)

        def params_factory(spec):
            if spec.get("kind") == "publication":
                return _load_publication_spec(spec)
            if spec.get("kind") != "reinit":
                raise ValueError(f"preset replica got spec {spec!r}")
            return init_params(int(spec.get("seed", 0)))

    if getattr(args, "decode_batching", False):
        from perceiver_io_tpu.inference.batching import ContinuousBatcher

        generator = ContinuousBatcher(
            model, params, max_seq_len=max_seq_len,
            chunk=args.generate_chunk, slots=args.decode_slots,
            max_slots=args.decode_slots * 8,
            compute_dtype=compute_dtype, name=f"{args.name}-gen",
            compile_cache=args.compile_cache,
            heartbeat_deadline_s=args.heartbeat_deadline_s,
        )
    else:
        generator = ARGenerator(
            model, params, max_seq_len=max_seq_len,
            chunk=args.generate_chunk,
            compute_dtype=compute_dtype, name=f"{args.name}-gen",
        )

    def infer_apply(p, token_ids, pad_mask):
        return model.apply({"params": p}, token_ids, pad_mask)

    slo = None
    if args.slo_p99_ms is not None:
        slo = obs.SLO(latency_target_s=args.slo_p99_ms / 1e3,
                      availability_target=args.slo_availability,
                      name=args.name, burn_alert=None)
    stream_slo = None
    if args.slo_ttft_ms is not None or args.slo_itl_ms is not None:
        stream_slo = obs.SLO(
            latency_target_s=(args.slo_p99_ms / 1e3
                              if args.slo_p99_ms is not None else 1.0),
            availability_target=args.slo_availability,
            name=f"{args.name}-stream", burn_alert=None,
            ttft_target_s=(args.slo_ttft_ms / 1e3
                           if args.slo_ttft_ms is not None else None),
            itl_target_s=(args.slo_itl_ms / 1e3
                          if args.slo_itl_ms is not None else None))
    engines = {
        "infer": ServingEngine(
            infer_apply, params, name=f"{args.name}-infer",
            max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
            compute_dtype=compute_dtype,
            queue_limit=args.queue_limit,
            request_deadline_s=args.request_deadline_s,
            dispatch_retries=args.dispatch_retries,
            breaker_failures=args.breaker_failures,
            breaker_cooldown_s=args.breaker_cooldown_s,
            heartbeat_deadline_s=args.heartbeat_deadline_s,
            compile_cache=args.compile_cache,
            slo=slo,
            trace_sample=args.trace_sample,
        ),
    }
    app = ReplicaApp(
        engines, params, params_factory=params_factory, name=args.name,
        assume_ready=args.no_warmup, drain_timeout_s=args.drain_timeout_s,
        generator=generator, stream_slo=stream_slo,
    )
    return app, max_seq_len


def _warm(app: ReplicaApp, args, max_seq_len: int) -> None:
    if args.task == "generate":
        # prefill-width family + the chunked decode program, then the dense
        # scoring engine's buckets — off the serving path
        def warm_generate():
            try:
                app.generator.warmup()
                ids = np.zeros((1, max_seq_len), np.int32)
                pad = np.zeros((1, max_seq_len), bool)
                app.engines["infer"].warmup(ids, pad)
            except Exception as e:
                print(f"replica: generate warmup failed "
                      f"({type(e).__name__}: {e})", file=sys.stderr)

        threading.Thread(target=warm_generate, name="replica-warm-generate",
                         daemon=True).start()
        return
    ids = np.zeros((1, max_seq_len), np.int32)
    pad = np.zeros((1, max_seq_len), bool)
    positions = np.zeros((1, 2), np.int32)
    app.engines["infer"].warmup(ids, pad, positions, background=True)
    app.engines["encode"].warmup(ids, pad, background=True)

    def warm_decode():
        # the decoder's warmup example needs one latent row
        try:
            latents = app.engines["encode"].predict(ids, pad)
            app.engines["decode"].warmup(latents, positions, background=True)
        except Exception as e:
            print(f"replica: decoder warmup failed ({type(e).__name__}: {e})",
                  file=sys.stderr)

    threading.Thread(target=warm_decode, name="replica-warm-decode",
                     daemon=True).start()


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    if args.cpu:
        from perceiver_io_tpu.utils.platform import ensure_cpu_only

        ensure_cpu_only()
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    if args.events_jsonl:
        obs.configure_event_log(
            args.events_jsonl,
            max_bytes=(int(args.events_max_mb * 1024 * 1024)
                       if args.events_max_mb > 0 else None))

    app, max_seq_len = _build_app(args)
    server = ReplicaServer(app, port=args.port)
    url = server.start()
    extra_server = None
    if args.transport != "http":
        from perceiver_io_tpu.serving.transport import serve_transport

        extra_server = serve_transport(
            app, args.transport, server.port, slots=args.shm_slots,
            slot_bytes=int(args.shm_slot_mb * 1024 * 1024))
    print(f"replica {args.name!r}: listening on {url}"
          + (f" (+{args.transport} {extra_server.path})"
             if extra_server is not None else ""),
          file=sys.stderr, flush=True)
    if not args.no_warmup:
        _warm(app, args, max_seq_len)

    import signal

    def _on_signal(signum, frame):
        # graceful drain: stop admitting, finish accepted work, exit 0 —
        # the same contract cli/serve.py honors (a supervisor rotation must
        # not drop the queue)
        print(f"replica {args.name!r}: signal {signum} — draining",
              file=sys.stderr, flush=True)
        flight = getattr(app.generator, "flight", None)
        if flight is not None:
            # last words: the scheduler's recent decision ring goes to the
            # event log BEFORE the drain, so a post-mortem on a killed
            # replica sees why its final rounds idled
            flight.dump(f"signal_{signum}")
        app.quit_event.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _on_signal)
        except ValueError:
            pass  # not the main thread (programmatic use)

    try:
        app.quit_event.wait()
    finally:
        app.drain(args.drain_timeout_s)
        if extra_server is not None:
            extra_server.close()
        server.close()
        app.close()
        obs.configure_event_log(None)
    print(f"replica {args.name!r}: drained and exiting", file=sys.stderr,
          flush=True)


if __name__ == "__main__":
    main()
