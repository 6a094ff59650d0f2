"""Pluggable replica transports: HTTP twin, pipelined UDS frames, shmem ring.

The router→replica RPC moves full input/output arrays per call. The portable
path (``HttpReplicaClient`` / ``ReplicaServer``) serializes them as npz over
localhost HTTP — correct everywhere, but at real traffic the router tier pays
a per-request serialize+copy+syscall tax that starves the decode batcher
(ROADMAP item 1). This module puts that hop behind a transport choice:

- ``http`` — the existing portable twin (default; nothing changes).
- ``uds`` — a unix-domain-socket framed protocol: length-prefixed frames
  (one ``sendall`` per frame — unix sockets have no Nagle/delayed-ACK, so
  small frames never hit the 40 ms stall the abandoned prototype died on),
  pooled PERSISTENT connections, and PIPELINED requests: multiple in flight
  per connection, responses matched to requests by id, replica health
  piggybacked on every response frame. Arrays ride a raw dtype/shape/bytes
  codec (:func:`pack_raw_arrays`) — no npz/zlib framing on the hot path.
- ``shmem`` — the uds control channel plus a ``multiprocessing.shared_memory``
  slab per replica: fixed-size slots hold request/response array payloads,
  written once by the producer and read IN PLACE by the consumer
  (``np.frombuffer`` views on the replica side — the arrays cross the
  process boundary without a copy); the socket carries only slot indices and
  metadata. Slot ownership is an explicit client-side state machine
  (:class:`SlotRing`): FREE→WRITING→READY→READING→FREE, every transition
  validated under a lock the PIT-LOCK rule audits. A slot whose response
  never arrived while the connection stayed alive is quarantined (LOST, never
  reused) — the replica may still write into it later; reusing it would hand
  a future request a torn payload. Oversized payloads fall back to inline
  uds frames, so slot geometry bounds memory, not request size.

Contract parity — all three transports speak the SAME fabric contract as the
HTTP twin (pinned by the parametrized suite in ``tests/test_transport.py``):

- the error classification crosses the wire (``raise_wire_error`` bodies:
  breaker_open/rejected/deadline/affinity_lost/engine+transient);
- trace headers propagate (``TraceContext.to_headers`` rides the request
  frame; the replica's ``replica_serve`` span parents to the router's);
- the engine's per-part ``phases`` ride back on the response frame;
- session pins, drain/resume, update_params behave identically (admin verbs
  and the streamed generate RPC ride the replica's always-on HTTP twin —
  the transport choice selects the ``call()`` DATA PLANE only);
- at-most-once on timeout: a client-side deadline with the connection still
  ALIVE raises :class:`~perceiver_io_tpu.resilience.DeadlineExceeded`
  (failover FAILs it — the request may have executed; re-placing it would
  be at-least-once). Only a DEAD connection (reset/EOF — the replica cannot
  have a response in flight) surfaces as ``ConnectionError``, the
  dead-replica signature the failover policy re-routes.

Endpoints are keyed by the replica's HTTP port (host-unique): the uds socket
at :func:`uds_path_for`, the slab at :func:`shm_slab_name` — a supervisor
restart on the same port recreates both, and clients reconnect/re-attach
lazily, so router handles stay valid across restarts exactly like HTTP.
"""

from __future__ import annotations

import io
import itertools
import json
import os
import socket
import struct
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.resilience import DeadlineExceeded, faults
from perceiver_io_tpu.serving.replica import (
    HttpReplicaClient,
    ReplicaApp,
    _wire_error,
    raise_wire_error,
)

TRANSPORTS = ("http", "uds", "shmem")

# sanity bounds on inbound frames: a desynced/garbage stream must fail the
# connection, not allocate gigabytes from a corrupt length prefix
_MAX_HEADER = 1 << 20
_MAX_PAYLOAD = 1 << 31


def uds_path_for(port: int, root: Optional[str] = None) -> str:
    """The replica's unix-socket path, keyed by its (host-unique) HTTP port
    so a restart on the same port lands on the same endpoint."""
    return os.path.join(root or tempfile.gettempdir(), f"pit-uds-{port}.sock")


def shm_slab_name(port: int) -> str:
    """The replica's shared-memory slab name (same port keying)."""
    return f"pit_shm_{port}"


# -- raw array codec ----------------------------------------------------------
#
# npz (pack_arrays) re-buffers every array through zipfile machinery; the
# framed transports carry dtype/shape/bytes directly so the replica side can
# reconstruct zero-copy views (np.frombuffer) on the shmem slab. Layout:
#   u32 count, then per array:
#     u8 len(dtype.str) | dtype.str ascii | u8 ndim | u64*ndim shape |
#     u64 nbytes | raw C-order bytes


def _as_wire_arrays(arrays: Sequence[np.ndarray]) -> List[np.ndarray]:
    out = []
    for a in arrays:
        a = np.asarray(a)
        if not a.flags["C_CONTIGUOUS"]:
            # NOTE: guarded — np.ascontiguousarray would also promote 0-d
            # arrays to 1-d, tearing shape parity with the npz twin
            a = np.ascontiguousarray(a)
        out.append(a)
    return out


def raw_arrays_nbytes(arrays: Sequence[np.ndarray]) -> int:
    total = 4
    for a in arrays:
        total += 1 + len(a.dtype.str) + 1 + 8 * a.ndim + 8 + a.nbytes
    return total


def write_raw_arrays(buf: memoryview, arrays: Sequence[np.ndarray]) -> int:
    """Encode ``arrays`` (already C-contiguous) into ``buf`` at offset 0;
    returns bytes written. Raises ValueError if ``buf`` is too small."""
    if raw_arrays_nbytes(arrays) > len(buf):
        raise ValueError("payload exceeds buffer")
    struct.pack_into(">I", buf, 0, len(arrays))
    off = 4
    for a in arrays:
        d = a.dtype.str.encode("ascii")
        struct.pack_into(f">B{len(d)}sB", buf, off, len(d), d, a.ndim)
        off += 1 + len(d) + 1
        for dim in a.shape:
            struct.pack_into(">Q", buf, off, dim)
            off += 8
        struct.pack_into(">Q", buf, off, a.nbytes)
        off += 8
        buf[off:off + a.nbytes] = a.reshape(-1).view(np.uint8).data
        off += a.nbytes
    return off


def pack_raw_arrays(arrays: Sequence[np.ndarray]) -> bytes:
    arrays = _as_wire_arrays(arrays)
    out = bytearray(raw_arrays_nbytes(arrays))
    write_raw_arrays(memoryview(out), arrays)
    return bytes(out)


def read_raw_arrays(buf, copy: bool = True) -> List[np.ndarray]:
    """Decode arrays from ``buf`` (bytes or memoryview). ``copy=False``
    returns views INTO the buffer (the shmem zero-copy read — valid only
    while the caller holds the slot); ``copy=True`` returns owned, writable
    arrays (anything handed to callers)."""
    mv = memoryview(buf)
    (count,) = struct.unpack_from(">I", mv, 0)
    off = 4
    out: List[np.ndarray] = []
    for _ in range(count):
        (dlen,) = struct.unpack_from(">B", mv, off)
        off += 1
        dtype = np.dtype(bytes(mv[off:off + dlen]).decode("ascii"))
        off += dlen
        (ndim,) = struct.unpack_from(">B", mv, off)
        off += 1
        shape = struct.unpack_from(f">{ndim}Q", mv, off) if ndim else ()
        off += 8 * ndim
        (nbytes,) = struct.unpack_from(">Q", mv, off)
        off += 8
        arr = np.frombuffer(mv[off:off + nbytes], dtype=dtype).reshape(shape)
        out.append(arr.copy() if copy else arr)
        off += nbytes
    return out


# -- framed uds protocol ------------------------------------------------------
#
# frame := u32 header_len | header json | payload (header["plen"] bytes),
# written with ONE sendall per frame. Request headers: {id, op, kind,
# session, timeout_s, trace, plen[, slot, slen]}; response headers: {id, ok,
# phases, h, plen[, slot, slen]} or {id, ok: false, error: {...}, h}. "h" is
# the piggybacked health sample ({ready, draining, queue_depth}) every
# response carries — a router gets a fresh liveness read with every reply,
# between scrapes.


def _send_frame(sock: socket.socket, header: Dict[str, Any],
                payload: bytes = b"") -> None:
    header = dict(header, plen=len(payload))
    body = json.dumps(header).encode()
    sock.sendall(struct.pack(">I", len(body)) + body + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        part = sock.recv(n - len(buf))
        if not part:
            raise ConnectionError("transport stream closed mid-frame")
        buf += part
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> Tuple[Dict[str, Any], bytes]:
    (hlen,) = struct.unpack(">I", _recv_exact(sock, 4))
    if hlen > _MAX_HEADER:
        raise ConnectionError(f"transport frame header too large ({hlen})")
    header = json.loads(_recv_exact(sock, hlen).decode())
    plen = int(header.get("plen", 0))
    if plen < 0 or plen > _MAX_PAYLOAD:
        raise ConnectionError(f"transport frame payload too large ({plen})")
    payload = _recv_exact(sock, plen) if plen else b""
    return header, payload


# public aliases for OTHER planes built on the same frame format — the
# elastic buddy-mirror channel (resilience/elastic.py) ships host-side
# checkpoint shards over these frames so there is exactly one length-
# prefixed wire protocol in the tree (same desync-fails-the-connection
# bounds as the replica data plane)
send_frame = _send_frame
recv_frame = _recv_frame


# -- the shared-memory slot ring ----------------------------------------------

FREE = "free"
WRITING = "writing"
READY = "ready"
READING = "reading"
LOST = "lost"

_FORWARD = {  # the legal forward transitions of one request's lifecycle
    (FREE, WRITING), (WRITING, READY), (READY, READING),
}


class SlotRing:
    """Client-side slot ownership over one replica's shared-memory slab.

    The slab itself is dumb bytes; correctness lives in this state machine.
    Each slot is FREE until a request claims it (WRITING), publishes it to
    the replica (READY — the control frame carrying the slot index provides
    the happens-before edge), and consumes the in-place response (READING)
    before releasing. Transitions outside ``_FORWARD`` raise — an
    out-of-order touch is a protocol bug, not a recoverable condition.
    ``quarantine`` parks a slot as LOST when its response never arrived on a
    LIVE connection: the replica may still write into it, so handing it to a
    new request would tear that request's payload. LOST slots are reclaimed
    only by :meth:`invalidate` (the slab handle is being dropped).
    """

    # pitlint PIT-LOCK: slot states are touched by every router worker
    # thread concurrently — all transitions happen under _lock
    _guarded_by = {"_states": "_lock", "_free": "_lock"}

    def __init__(self, shm, slots: int, slot_bytes: int):
        self._shm = shm
        self.slots = int(slots)
        self.slot_bytes = int(slot_bytes)
        self._lock = threading.Lock()
        self._states = [FREE] * self.slots
        self._free = list(range(self.slots))

    def acquire(self, timeout_s: float = 5.0) -> int:
        """FREE→WRITING; blocks briefly under slot pressure, then raises
        RejectedError-shaped pressure as a plain TimeoutError (callers fall
        back to the inline path)."""
        deadline = time.monotonic() + timeout_s
        while True:
            with self._lock:
                if self._free:
                    idx = self._free.pop()
                    self._states[idx] = WRITING
                    return idx
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"no free shmem slot within {timeout_s:g}s "
                    f"({self.counts()})")
            time.sleep(0.001)

    def _transition(self, idx: int, new: str) -> None:
        with self._lock:
            old = self._states[idx]
            if (old, new) not in _FORWARD:
                raise RuntimeError(
                    f"illegal slot transition {old}->{new} (slot {idx})")
            self._states[idx] = new

    def mark_ready(self, idx: int) -> None:
        self._transition(idx, READY)

    def mark_reading(self, idx: int) -> None:
        self._transition(idx, READING)

    def release(self, idx: int) -> None:
        """Return a held slot to FREE (idempotent; LOST stays LOST — see
        :meth:`quarantine`)."""
        with self._lock:
            if self._states[idx] in (FREE, LOST):
                return
            self._states[idx] = FREE
            self._free.append(idx)

    def quarantine(self, idx: int) -> None:
        """Park a slot whose response never arrived while the connection
        stayed alive — the replica may still write into it."""
        with self._lock:
            if self._states[idx] in (FREE, LOST):
                return
            self._states[idx] = LOST

    def counts(self) -> Dict[str, int]:
        with self._lock:
            out: Dict[str, int] = {}
            for s in self._states:
                out[s] = out.get(s, 0) + 1
            return out

    def view(self, idx: int) -> memoryview:
        off = _SLAB_HEADER + idx * self.slot_bytes
        return memoryview(self._shm.buf)[off:off + self.slot_bytes]

    def invalidate(self) -> None:
        """Drop the slab handle (replica died: its restart creates a FRESH
        segment under the same name, so this mapping can never see it)."""
        with self._lock:
            self._states = [FREE] * self.slots
            self._free = list(range(self.slots))
        try:
            self._shm.close()
        except (OSError, BufferError):
            pass


# slab names CREATED by this process (the replica side). attach_slab skips
# its tracker workaround for these: in-process fabrics (tests) would
# otherwise double-unregister one tracker entry
_OWNED_SLABS: set = set()

# the slab self-describes its geometry in a fixed header, so clients
# DISCOVER slots/slot_bytes instead of assuming them (a client guessing a
# larger slot size than the replica allocated would write past slot bounds)
_SLAB_MAGIC = b"PITSLAB1"
_SLAB_HEADER = 64  # magic(8) + u32 slots + u64 slot_bytes, padded


def create_slab(port: int, slots: int, slot_bytes: int):
    """Replica side: create (re-create over a stale predecessor) the slab,
    geometry stamped into its header."""
    from multiprocessing import shared_memory

    name = shm_slab_name(port)
    size = _SLAB_HEADER + slots * slot_bytes
    try:
        shm = shared_memory.SharedMemory(name=name, create=True, size=size)
    except FileExistsError:
        stale = shared_memory.SharedMemory(name=name)
        stale.close()
        stale.unlink()
        shm = shared_memory.SharedMemory(name=name, create=True, size=size)
    struct.pack_into(f">{len(_SLAB_MAGIC)}sIQ", shm.buf, 0,
                     _SLAB_MAGIC, slots, slot_bytes)
    _OWNED_SLABS.add(name)
    return shm


def attach_slab(port: int):
    """Client side: attach the replica's slab; returns ``(shm, slots,
    slot_bytes)`` read from the header. Python 3.10's resource tracker
    registers ATTACHMENTS for destruction at process exit — the router
    would unlink a live replica's slab when it exits — so the attachment is
    explicitly unregistered (the replica owns the lifetime)."""
    from multiprocessing import resource_tracker, shared_memory

    name = shm_slab_name(port)
    shm = shared_memory.SharedMemory(name=name)
    if name not in _OWNED_SLABS:
        try:
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:
            pass  # tracker layout differs across versions; leak-warn only
    magic, slots, slot_bytes = struct.unpack_from(
        f">{len(_SLAB_MAGIC)}sIQ", shm.buf, 0)
    if magic != _SLAB_MAGIC:
        shm.close()
        raise ConnectionError(
            f"slab {name!r} has no geometry header (torn or foreign)")
    return shm, int(slots), int(slot_bytes)


# -- the replica-side uds server ----------------------------------------------


class UdsReplicaServer:
    """The replica half of the uds/shmem data plane: a unix-socket listener
    over one :class:`ReplicaApp`, serving pipelined framed requests.

    One dedicated BLOCKING accept thread (never a poll timer — the abandoned
    prototype's 5 s stalls came from tying wakeups to accept timing), one
    reader thread per connection, a shared worker pool per server so slow
    calls never head-of-line-block the frame reader, and a per-connection
    write lock so concurrent responses interleave at frame granularity.
    Payloads arriving by slot index are read as zero-copy views on the slab;
    the response is written back into the SAME slot (the client holds it out
    of FREE for the whole exchange) when it fits, inline otherwise.
    """

    # pitlint PIT-LOCK: the live-connection set is mutated by the accept
    # thread and swept by close() — touched only under _lock
    _guarded_by = {"_conns": "_lock"}

    def __init__(self, app: ReplicaApp, path: str,
                 slab=None, slot_bytes: int = 0, workers: int = 8):
        self.app = app
        self.path = path
        self._slab = slab
        self._slot_bytes = int(slot_bytes)
        self._listener: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix=f"{app.name}-uds")
        self._lock = threading.Lock()
        self._conns: List[socket.socket] = []
        self._closing = threading.Event()
        self._health_lock = threading.Lock()
        self._health_cache: Tuple[float, Dict[str, Any]] = (-1.0, {})

    def start(self) -> str:
        if self._listener is not None:
            return self.path
        try:
            os.unlink(self.path)  # a stale endpoint from a killed
        except FileNotFoundError:  # predecessor on this port
            pass
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(self.path)
        listener.listen(64)
        self._listener = listener
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name=f"{self.app.name}-uds-accept",
            daemon=True)
        self._accept_thread.start()
        return self.path

    def _accept_loop(self) -> None:
        while not self._closing.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return  # listener closed
            with self._lock:
                self._conns.append(conn)
            threading.Thread(
                target=self._conn_loop, args=(conn,),
                name=f"{self.app.name}-uds-conn", daemon=True).start()

    def _conn_loop(self, conn: socket.socket) -> None:
        send_lock = threading.Lock()
        try:
            while True:
                header, payload = _recv_frame(conn)
                if header.get("op") == "ping":
                    with send_lock:
                        _send_frame(conn, {"id": header.get("id"),
                                           "ok": True, "h": self._health()})
                    continue
                self._pool.submit(
                    self._serve_one, conn, send_lock, header, payload)
        except (ConnectionError, OSError, ValueError):
            pass  # client went away / stream desynced: drop the connection
        finally:
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _slot_view(self, slot: int) -> memoryview:
        off = _SLAB_HEADER + slot * self._slot_bytes
        return memoryview(self._slab.buf)[off:off + self._slot_bytes]

    def _health(self) -> Dict[str, Any]:
        """The piggyback sample — cached briefly (it walks the engines)."""
        now = time.monotonic()
        with self._health_lock:
            stamp, cached = self._health_cache
            if now - stamp < 0.1:
                return cached
        app = self.app
        sample = {
            "ready": app.ready,
            "draining": any(e.draining for e in app.engines.values()),
            "queue_depth": sum(e.backlog for e in app.engines.values()),
        }
        with self._health_lock:
            self._health_cache = (now, sample)
        return sample

    def _serve_one(self, conn: socket.socket, send_lock: threading.Lock,
                   header: Dict[str, Any], payload: bytes) -> None:
        rid = header.get("id")
        slot = int(header.get("slot", -1))
        try:
            faults.inject("transport.recv")
            if slot >= 0:
                view = self._slot_view(slot)
                arrays = read_raw_arrays(
                    view[:int(header["slen"])], copy=False)
            else:
                arrays = read_raw_arrays(payload, copy=True)
            trace = obs.TraceContext.from_headers(header.get("trace") or {})
            meta: Dict[str, Any] = {}
            out = _as_wire_arrays(self.app.call(
                header["kind"], arrays,
                session=header.get("session"),
                timeout_s=header.get("timeout_s"),
                trace=trace, meta=meta))
            resp: Dict[str, Any] = {"id": rid, "ok": True,
                                    "h": self._health()}
            if meta.get("phases"):
                resp["phases"] = meta["phases"][:64]  # parity with X-Phases
            body = b""
            if slot >= 0 and raw_arrays_nbytes(out) <= self._slot_bytes:
                resp["slot"] = slot
                resp["slen"] = write_raw_arrays(self._slot_view(slot), out)
            else:
                resp["slot"] = -1  # response outgrew the slot: inline
                body = pack_raw_arrays(out)
            with send_lock:
                faults.inject("transport.send")
                _send_frame(conn, resp, body)
        except BaseException as e:  # mirrored, never a stack trace
            err = json.loads(_wire_error(e).decode())
            try:
                with send_lock:
                    _send_frame(conn, {"id": rid, "ok": False, "error": err,
                                       "h": self._health()})
            except OSError:
                pass  # client already gone

    def close(self) -> None:
        self._closing.set()
        if self._listener is not None:
            try:
                # close() alone does not wake a thread blocked in accept();
                # shutdown() does — without it every close eats the full
                # accept-thread join timeout
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._listener.close()
            except OSError:
                pass
            self._listener = None
        with self._lock:
            conns, self._conns = list(self._conns), []
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass
        self._pool.shutdown(wait=False)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
            self._accept_thread = None
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass


# -- the router-side clients --------------------------------------------------


class _Pending:
    __slots__ = ("event", "header", "payload", "error")

    def __init__(self):
        self.event = threading.Event()
        self.header: Optional[Dict[str, Any]] = None
        self.payload: bytes = b""
        self.error: Optional[BaseException] = None


class _UdsConn:
    """One persistent pipelined connection: a send lock serializes frame
    writes, a reader thread matches response ids to pending waiters, and a
    connection death fails EVERY pending request with the dead-replica
    ConnectionError signature (the failover policy re-routes those — the
    replica is gone, no response can be in flight)."""

    # pitlint PIT-LOCK: the pending map is touched by every caller thread
    # and the reader thread — only under _lock
    _guarded_by = {"_pending": "_lock"}

    def __init__(self, path: str, name: str):
        self._name = name
        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._sock.connect(path)
        self._send_lock = threading.Lock()
        self._lock = threading.Lock()
        self._pending: Dict[int, _Pending] = {}
        self.dead = False
        self._reader = threading.Thread(
            target=self._read_loop, name=f"{name}-uds-reader", daemon=True)
        self._reader.start()

    def _read_loop(self) -> None:
        try:
            while True:
                header, payload = _recv_frame(self._sock)
                with self._lock:
                    p = self._pending.pop(int(header.get("id", -1)), None)
                if p is not None:  # orphans (timed-out ids) are dropped
                    p.header, p.payload = header, payload
                    p.event.set()
        except (ConnectionError, OSError, ValueError) as e:
            self._fail_all(e)

    def _fail_all(self, cause: BaseException) -> None:
        self.dead = True
        try:
            self._sock.close()
        except OSError:
            pass
        with self._lock:
            pending, self._pending = list(self._pending.values()), {}
        err = ConnectionError(
            f"replica {self._name!r}: connection closed / failed to "
            f"connect ({type(cause).__name__}: {cause})")
        err.__cause__ = cause
        for p in pending:
            p.error = err
            p.event.set()

    def send(self, rid: int, header: Dict[str, Any],
             payload: bytes) -> _Pending:
        p = _Pending()
        with self._lock:
            self._pending[rid] = p
        try:
            with self._send_lock:
                faults.inject("transport.send")
                _send_frame(self._sock, header, payload)
        except (ConnectionError, OSError) as e:
            self._fail_all(e)
        return p

    def forget(self, rid: int) -> None:
        with self._lock:
            self._pending.pop(rid, None)

    def close(self) -> None:
        self._fail_all(ConnectionError("client closed"))


class UdsReplicaClient:
    """Router-side handle speaking the framed uds data plane for ``call()``;
    admin verbs (scrape/drain/resume/update_params/quit) and the streamed
    generate RPC ride the replica's always-on HTTP twin. ``health`` holds
    the latest piggybacked liveness sample (stamped with the receive time)."""

    transport = "uds"

    # pitlint PIT-LOCK: the connection pool is rebuilt by any caller thread
    # on reconnect — touched only under _lock
    _guarded_by = {"_conns": "_lock"}

    def __init__(self, name: str, base_url: str, timeout_s: float = 120.0,
                 pool_size: int = 2, path: Optional[str] = None):
        self.name = name
        self.timeout_s = timeout_s
        self._http = HttpReplicaClient(name, base_url, timeout_s=timeout_s)
        port = int(base_url.rstrip("/").rsplit(":", 1)[1])
        self.port = port
        self.path = path or uds_path_for(port)
        self._pool_size = max(1, int(pool_size))
        self._lock = threading.Lock()
        self._conns: List[_UdsConn] = []
        self._rr = itertools.count()
        self._ids = itertools.count(1)
        self.health: Optional[Dict[str, Any]] = None
        self.health_stamp: float = -1.0

    # -- connection pool -----------------------------------------------------

    def _conn(self) -> _UdsConn:
        turn = next(self._rr)
        with self._lock:
            self._conns = [c for c in self._conns if not c.dead]
            if len(self._conns) >= self._pool_size:
                return self._conns[turn % len(self._conns)]
        try:
            conn = _UdsConn(self.path, self.name)
        except (ConnectionError, OSError, FileNotFoundError) as e:
            raise ConnectionError(
                f"replica {self.name!r}: connection closed / failed to "
                f"connect ({type(e).__name__}: {e})") from e
        with self._lock:
            self._conns.append(conn)
        return conn

    # -- the data plane ------------------------------------------------------

    def _roundtrip(self, header: Dict[str, Any], payload: bytes,
                   timeout_s: Optional[float],
                   ) -> Tuple[Dict[str, Any], bytes]:
        """Send one request frame and wait for its id-matched response.

        At-most-once on timeout: if the wait expires with the connection
        still alive, the request MAY have executed (or still be executing) —
        this raises DeadlineExceeded, which the failover policy FAILs,
        never re-routes. A dead connection raises ConnectionError instead
        (no response can be in flight) and the router re-places the work.
        """
        conn = self._conn()
        rid = next(self._ids)
        header = dict(header, id=rid)
        p = conn.send(rid, header, payload)
        # the replica enforces timeout_s server-side (DeadlineExceeded comes
        # back as a classification frame); the client-side wait is a safety net
        # set BEYOND it so the server's verdict always wins the race
        wait_s = (timeout_s if timeout_s is not None else self.timeout_s)
        if not p.event.wait(timeout=wait_s + 5.0):
            conn.forget(rid)
            raise DeadlineExceeded(
                f"replica {self.name!r}: no response within {wait_s:g}s "
                f"(connection alive — not re-routed: the request may have "
                f"executed)")
        if p.error is not None:
            raise p.error
        faults.inject("transport.recv")
        header = p.header or {}
        h = header.get("h")
        if h is not None:
            self.health, self.health_stamp = h, time.monotonic()
        return header, p.payload

    def _finish_call(self, resp: Dict[str, Any], payload,
                     meta: Optional[Dict[str, Any]]) -> List[np.ndarray]:
        if not resp.get("ok"):
            raise_wire_error(
                json.dumps(resp.get("error", {})).encode(), self.name)
        if meta is not None and resp.get("phases"):
            meta["phases"] = resp["phases"]
        return read_raw_arrays(payload, copy=True)

    # reads straight off a slot view; _finish_call's copy=True is what makes
    # this safe (the arrays own their bytes before the caller frees the slot)
    _finish_call_view = _finish_call

    def call(self, kind: str, arrays: Sequence[np.ndarray],
             session: Optional[str] = None,
             timeout_s: Optional[float] = None,
             trace: Optional[obs.TraceContext] = None,
             meta: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
        header = {
            "op": "call", "kind": kind, "session": session,
            "timeout_s": timeout_s,
            "trace": trace.to_headers() if trace is not None else None,
        }
        resp, payload = self._roundtrip(
            header, pack_raw_arrays(arrays), timeout_s)
        return self._finish_call(resp, payload, meta)

    # -- admin plane: the HTTP twin ------------------------------------------

    def generate_stream(self, *args, **kwargs):
        return self._http.generate_stream(*args, **kwargs)

    def scrape(self, timeout_s: float = 5.0) -> Dict[str, Any]:
        return self._http.scrape(timeout_s=timeout_s)

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        return self._http.drain(timeout_s)

    def resume(self) -> None:
        self._http.resume()

    def update_params(self, spec: Dict[str, Any],
                      timeout_s: Optional[float] = None) -> int:
        return self._http.update_params(spec, timeout_s)

    def quit(self) -> None:
        self._http.quit()

    def close(self) -> None:
        with self._lock:
            conns, self._conns = list(self._conns), []
        for c in conns:
            c.close()


class ShmemReplicaClient(UdsReplicaClient):
    """The shmem data plane: request arrays are written ONCE into a slot of
    the replica's slab (state machine in :class:`SlotRing`), the uds control
    frame carries only the slot index + metadata, and the replica reads the
    payload in place and writes the response back into the same slot.
    Payloads that outgrow a slot (or slot exhaustion) fall back to inline
    uds frames — geometry bounds memory, never request size."""

    transport = "shmem"

    # pitlint PIT-LOCK: the lazily-attached ring handle is swapped on
    # replica death/reattach by any caller thread — only under _ring_lock
    _guarded_by = {"_ring": "_ring_lock"}

    def __init__(self, name: str, base_url: str, timeout_s: float = 120.0,
                 pool_size: int = 2, path: Optional[str] = None):
        super().__init__(name, base_url, timeout_s=timeout_s,
                         pool_size=pool_size, path=path)
        self._ring_lock = threading.Lock()
        self._ring: Optional[SlotRing] = None

    def ring(self) -> Optional[SlotRing]:
        """The attached slot ring (lazily attached; geometry is read from
        the slab's header — never assumed). None while the replica's slab
        does not exist yet."""
        with self._ring_lock:
            if self._ring is not None:
                return self._ring
        try:
            shm, slots, slot_bytes = attach_slab(self.port)
        except (FileNotFoundError, ConnectionError):
            return None
        ring = SlotRing(shm, slots, slot_bytes)
        with self._ring_lock:
            if self._ring is None:
                self._ring = ring
            return self._ring

    def _drop_ring(self) -> None:
        """The replica died: its restart creates a FRESH segment under the
        same name — this mapping can never see it, so drop and re-attach."""
        with self._ring_lock:
            ring, self._ring = self._ring, None
        if ring is not None:
            ring.invalidate()

    def call(self, kind: str, arrays: Sequence[np.ndarray],
             session: Optional[str] = None,
             timeout_s: Optional[float] = None,
             trace: Optional[obs.TraceContext] = None,
             meta: Optional[Dict[str, Any]] = None) -> List[np.ndarray]:
        arrays = _as_wire_arrays(arrays)
        ring = self.ring()
        if ring is None or raw_arrays_nbytes(arrays) > ring.slot_bytes:
            return super().call(kind, arrays, session=session,
                                timeout_s=timeout_s, trace=trace, meta=meta)
        try:
            idx = ring.acquire()
        except TimeoutError:  # slot pressure: inline fallback, never block
            return super().call(kind, arrays, session=session,
                                timeout_s=timeout_s, trace=trace, meta=meta)
        try:
            slen = write_raw_arrays(ring.view(idx), arrays)
            ring.mark_ready(idx)
            header = {
                "op": "call", "kind": kind, "session": session,
                "timeout_s": timeout_s,
                "trace": trace.to_headers() if trace is not None else None,
                "slot": idx, "slen": slen,
            }
            try:
                resp, payload = self._roundtrip(header, b"", timeout_s)
            except DeadlineExceeded:
                # no response on a LIVE connection: the replica may still
                # write into the slot — quarantine it, never reuse it
                ring.quarantine(idx)
                raise
            except ConnectionError:
                self._drop_ring()  # a restarted replica makes a fresh slab
                raise
            ring.mark_reading(idx)
            if resp.get("ok") and int(resp.get("slot", -1)) == idx:
                # copy=True owns the arrays BEFORE release frees the slot
                return self._finish_call_view(
                    resp, ring.view(idx)[:int(resp["slen"])], meta)
            return self._finish_call(resp, payload, meta)
        finally:
            ring.release(idx)

    def close(self) -> None:
        super().close()
        self._drop_ring()


# -- factory ------------------------------------------------------------------


def make_client(transport: str, name: str, port: int,
                host: str = "127.0.0.1", timeout_s: float = 120.0,
                **kwargs):
    """Build the router-side client for one replica on ``transport``."""
    base_url = f"http://{host}:{port}"
    if transport == "http":
        return HttpReplicaClient(name, base_url, timeout_s=timeout_s)
    if transport == "uds":
        return UdsReplicaClient(name, base_url, timeout_s=timeout_s,
                                **kwargs)
    if transport == "shmem":
        return ShmemReplicaClient(name, base_url, timeout_s=timeout_s,
                                  **kwargs)
    raise ValueError(
        f"unknown transport {transport!r}; one of {TRANSPORTS}")


def serve_transport(app: ReplicaApp, transport: str, port: int,
                    slots: int = 16, slot_bytes: int = 4 << 20,
                    ) -> Optional[UdsReplicaServer]:
    """Replica side: start the extra data-plane server for ``transport``
    next to the always-on HTTP twin (None for ``http``). The caller owns
    ``close()``; the slab (shmem) is created here and unlinked on close."""
    if transport == "http":
        return None
    if transport not in TRANSPORTS:
        raise ValueError(
            f"unknown transport {transport!r}; one of {TRANSPORTS}")
    slab = None
    if transport == "shmem":
        slab = create_slab(port, slots, slot_bytes)
    server = UdsReplicaServer(app, uds_path_for(port), slab=slab,
                              slot_bytes=slot_bytes)
    server.start()
    if slab is not None:
        base_close = server.close

        def close_with_slab():
            base_close()
            try:
                slab.unlink()
            except (OSError, FileNotFoundError):
                pass
            try:
                # in-flight np.frombuffer views may still pin the mapping
                # (BufferError); the segment is already unlinked and the OS
                # frees it when the last mapping drops
                slab.close()
            except (OSError, BufferError):
                pass

        server.close = close_with_slab  # type: ignore[method-assign]
    return server
