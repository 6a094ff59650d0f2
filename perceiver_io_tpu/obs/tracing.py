"""Spans and events: timed spans kept in memory, and a JSONL sink for both.

The narrative channel next to the registry's numeric one. Two kinds of
record:

- ``span(name, **fields)`` times a stretch of the program. Every finished
  span is kept IN MEMORY, always: ``name``, ``id``, ``parent`` (the innermost
  span open on the same thread when it started), ``thread``, ``start_ns`` /
  ``end_ns`` on ``time.monotonic_ns()``, ``ok`` and the fields. The buffers
  are bounded PER SPAN NAME (``SPAN_DEPTH`` records each, oldest dropped
  first, drops counted per name), so a week of ``train.step`` records can
  never evict the dozen set-up spans. ``spans()`` returns a snapshot;
  ``add_span`` enters a span whose ends were measured elsewhere (the
  ``jax.monitoring`` listener in ``obs.watchdog``, the Trainer's loop, and a
  module's own import: the package and ``cli.common`` take the clock in their
  first statement and enter ``import`` with ``module`` in their last; a
  third-party import that costs a quarter second or more on the chip host is
  ``with span("import", module=...)`` where the program first performs it,
  so a process's timeline starts at the package's first statement; PERF.md
  section 3 lists them). While
  open, a span is also a ``jax.profiler.TraceAnnotation("pio." + name)`` if
  ``jax`` is already imported in the process (this module never imports it):
  under an active profiler the program's spans lie in the capture's host
  plane on the trace's own clock, and cost nothing extra when none runs.
  About 3 µs a span on the sandbox's CPU (PERF.md, PR 29).
- ``event(name, **fields)`` is one discrete happening (a bucket program
  compiled, a warmup finished, a heartbeat stalled): a no-op until a JSONL
  sink is configured.

The JSONL sink is the opt-in it always was: only entry points configure one,
and then every ``event`` and every ``span`` (as one record carrying
``dur_s``, ``ok``, ``error``) appends one JSON object per line to it.

Every record carries DUAL clock stamps plus the writer's pid: ``t``
(wall-clock epoch seconds — external log correlation and cross-process
alignment anchoring) and ``mono`` (the process's monotonic clock — the only
clock durations may be computed from, PIT-CLOCK). The pair is what lets
``obs.reqtrace.assemble_traces`` anchor one process's monotonic span stamps
against another's: per process, the median ``t − mono`` offset maps
monotonic onto the shared wall timeline. Multi-host: configure the sink on
process 0 only (the helpers never check — the caller owns that policy,
mirroring ``MetricsLogger``).

Writes are ASYNCHRONOUS (r15): ``write()`` stamps the clocks and enqueues;
a writer thread serializes, rotates, and flushes off the caller's path —
per-request span emission costs the producer ~2 µs instead of a ~25 µs
serialize+write+flush (the measured difference between tracing overhead
above and below the 2% acceptance bar at CPU serving rates). The bounded
queue DROPS (counted, reported once) rather than blocks when the writer
falls behind — telemetry must never stall the loop it observes. ``close()``
(and ``configure_event_log(None)``) drains the queue before closing, so the
every-record-visible-after-close contract the tests and the serve CLI's
drain path rely on still holds.

Bounded by construction: the sink rotates at ``max_bytes`` (keeping
``backups`` numbered segments, newest first: ``events.jsonl.1`` is the most
recent full segment) so a week of serving — or an open-loop load sweep
emitting one span per request — can never grow the log unboundedly. Pass
``max_bytes=None`` to disable rotation (the pre-r11 behavior).
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["EventLog", "SPAN_DEPTH", "add_span", "configure_event_log",
           "event", "get_event_log", "span", "spans"]

# rotation defaults: ~64 MB live segment + 3 rotated = a ~256 MB hard ceiling
# per process, weeks of serving events at typical rates
DEFAULT_MAX_BYTES = 64 * 1024 * 1024
DEFAULT_BACKUPS = 3

# producer-side bound: at the measured ~25 µs/record drain rate this absorbs
# multi-second bursts; past it, records drop (counted) rather than block
DEFAULT_QUEUE_DEPTH = 8192


class EventLog:
    """Append-only JSONL event sink with size-capped rotation and an
    asynchronous writer thread (producers enqueue; serialization, rotation,
    and flushing happen off the hot path)."""

    def __init__(self, path: str, max_bytes: Optional[int] = DEFAULT_MAX_BYTES,
                 backups: int = DEFAULT_BACKUPS,
                 queue_depth: int = DEFAULT_QUEUE_DEPTH,
                 registry=None):
        if max_bytes is not None and max_bytes < 1:
            raise ValueError(f"max_bytes must be positive, got {max_bytes}")
        if backups < 0:
            raise ValueError(f"backups must be >= 0, got {backups}")
        self.path = path
        self.max_bytes = max_bytes
        self.backups = backups
        self._pid = os.getpid()  # per-record process label (trace assembly
        # merges logs from many processes; pid keys the clock alignment)
        self._lock = threading.Lock()
        self._f = open(path, "a")
        self._size = self._f.tell()  # append mode: tell() is the file size
        self._closed = False
        self._write_error_reported = False
        self._drop_reported = False
        self.dropped = 0  # records the full buffer refused (never blocks)
        self._depth = max(1, int(queue_depth))
        # a plain deque, NOT queue.Queue: append is GIL-atomic and lock-free
        # and — decisively — does not notify a condition variable per
        # record. Waking the writer thread per span put a context-switch +
        # GIL hand-off on every completion; polling amortizes it to zero
        # (measured: the difference between ~10% and <2% tracing overhead)
        self._buf: deque = deque()
        self._writing = False  # a popped batch is in flight to disk
        # record loss must be VISIBLE, not just counted on the object:
        # eventlog_dropped_total / eventlog_queue_depth ride /metrics (and
        # therefore the time-series + alerting layer) via a registry
        # collector, refreshed at every scrape. The collector holds only a
        # weakref and raises once the log is gone, which drops it from
        # subsequent exports (the registry's documented removal path).
        # ``registry`` lets an owner on a private registry (a Sampler's
        # series log) keep its drop signal sampleable by that owner.
        if registry is None:
            from perceiver_io_tpu.obs.registry import get_registry

            registry = get_registry()
        reg = registry
        labels = {"log": os.path.basename(path)}
        self._m_dropped = reg.counter(
            "eventlog_dropped_total",
            "records the bounded writer queue (or a write failure) refused",
            labels)
        self._m_queue = reg.gauge(
            "eventlog_queue_depth",
            "records buffered for the async writer", labels)
        self._dropped_synced = 0
        ref = weakref.ref(self)

        def _sync_collector():
            log = ref()
            if log is None or log._closed:
                raise LookupError("event log gone — drop this collector")
            log._sync_metrics()

        reg.register_collector(_sync_collector)
        self._stop = threading.Event()
        self._writer = threading.Thread(
            target=self._drain_loop, name="event-log-writer", daemon=True)
        self._writer.start()

    def _sync_metrics(self) -> None:
        """Publish drop/queue state into the registry instruments (counter
        semantics: only the delta since the last sync increments, so many
        EventLog lifetimes sharing one instrument aggregate correctly)."""
        d = self.dropped
        if d > self._dropped_synced:
            self._m_dropped.inc(d - self._dropped_synced)
            self._dropped_synced = d
        self._m_queue.set(len(self._buf))

    def write(self, record: Dict[str, Any]) -> None:
        """Buffer one record (~2 µs, no lock, no thread wakeup). Clock
        stamps are captured HERE — the record's times are submission times,
        however far behind the writer runs. A full buffer drops the record
        (counted, reported once): telemetry must never stall the loop it
        observes."""
        if self._closed:
            return
        if len(self._buf) >= self._depth:  # racy read: the bound is soft
            self.dropped += 1
            if not self._drop_reported:
                self._drop_reported = True
                import sys

                print(f"[obs] event log buffer full — dropping records "
                      f"(writer behind on {self.path!r}; drops are counted "
                      f"on EventLog.dropped)", file=sys.stderr)
            return
        # dual stamps: wall for correlation/alignment anchoring, monotonic
        # for durations (PIT-CLOCK — never subtract wall clocks)
        self._buf.append(
            {"t": time.time(), "mono": time.monotonic(),
             "pid": self._pid, **record})

    def _drain_loop(self) -> None:
        """Writer thread: poll → drain the buffer in batches → ONE write +
        flush per batch (a flush-per-record writer measurably steals
        serving throughput through the GIL). Exits once stopped AND
        drained, so ``close()`` sees every record accepted before the stop
        on disk."""
        while True:
            if not self._buf:
                if self._stop.wait(0.02):
                    if not self._buf:
                        return
                continue
            # flagged BEFORE popping: flush() must not observe an empty
            # deque while a popped batch is still unwritten
            self._writing = True
            batch = []
            while len(batch) < 512:
                try:
                    batch.append(self._buf.popleft())
                except IndexError:
                    break
            try:
                self._write_batch(batch)
            except Exception as e:  # the writer thread is immortal: any
                # surprise drops the batch (counted), never the sink
                self.dropped += len(batch)
                if not self._write_error_reported:
                    self._write_error_reported = True
                    import sys

                    print(f"[obs] event log writer error "
                          f"({type(e).__name__}: {e}) — batch dropped",
                          file=sys.stderr)
            finally:
                self._writing = False

    def _write_batch(self, records) -> None:
        """Serialize and land a batch: rotation is checked per record (the
        size cap stays exact), but the flush is per batch."""
        with self._lock:
            for record in records:
                self._write_one_locked(record, flush=False)
            if self._f is not None:
                try:
                    self._f.flush()
                except OSError:
                    pass  # the per-record handler already reported

    def _write_line(self, record: Dict[str, Any]) -> None:
        with self._lock:
            self._write_one_locked(record, flush=True)

    def _write_one_locked(self, record: Dict[str, Any],
                          flush: bool) -> None:
        try:
            line = json.dumps(record, default=str) + "\n"
        except (TypeError, ValueError) as e:
            # default=str does not cover every shape (non-scalar dict
            # keys, circular refs); one bad record must DROP, not kill
            # the writer thread and silently end all event logging
            self.dropped += 1
            if not self._write_error_reported:
                self._write_error_reported = True
                import sys

                print(f"[obs] event log record not serializable ({e}) — "
                      f"dropped (counted on EventLog.dropped)",
                      file=sys.stderr)
            return
        if self._f is None:
            if self._closed:
                return
            # a FAILED rotation left the log fileless (not closed):
            # retry the reopen so a transient disk condition degrades
            # the log only while it lasts, symmetric with plain write
            # failures which also self-recover
            try:
                self._f = open(self.path, "a")
                self._size = self._f.tell()
            except OSError:
                return
        try:
            if (self.max_bytes is not None
                    and self._size + len(line) > self.max_bytes
                    and self._size > 0):
                self._rotate_locked()
            self._f.write(line)
            if flush:
                self._f.flush()
            self._size += len(line)
        except OSError as e:
                # telemetry must never crash the loop it observes (events
                # are emitted from the engine worker / trainer hot paths);
                # a full disk degrades the log, reported once
                if not self._write_error_reported:
                    self._write_error_reported = True
                    import sys

                    print(f"[obs] event log write failed ({e}) — further "
                          f"events to {self.path!r} may be dropped",
                          file=sys.stderr)

    def flush(self, timeout_s: float = 10.0) -> bool:
        """Block until every record buffered so far is on disk (bounded).
        Returns False if the writer did not catch up in time."""
        deadline = time.monotonic() + timeout_s
        while self._buf or self._writing:
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.002)
        return True

    def _rotate_locked(self) -> None:
        """Shift ``path.(N-1)`` → ``path.N`` … ``path`` → ``path.1`` and
        reopen a fresh live segment. With ``backups == 0`` the live segment
        is simply truncated (still bounded)."""
        self._f.close()
        self._f = None  # a failure below leaves the log closed, not torn
        if self.backups > 0:
            oldest = f"{self.path}.{self.backups}"
            if os.path.exists(oldest):
                os.remove(oldest)
            for i in range(self.backups - 1, 0, -1):
                src = f"{self.path}.{i}"
                if os.path.exists(src):
                    os.replace(src, f"{self.path}.{i + 1}")
            os.replace(self.path, f"{self.path}.1")
        else:
            os.remove(self.path)
        self._f = open(self.path, "a")
        self._size = 0

    def close(self) -> None:
        """Stop accepting records, DRAIN the queue to disk, close the file —
        the flush half of the serve CLI's drain contract."""
        self._closed = True  # write() refuses new records from here on
        self._stop.set()
        self._writer.join(timeout=10.0)
        # a writer wedged past the join bound is abandoned (daemon); any
        # records it left behind are drained synchronously so close() keeps
        # its everything-accepted-is-on-disk promise
        while True:
            try:
                self._write_line(self._buf.popleft())
            except IndexError:
                break
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None
        # the collector stops reporting for a closed log — push the final
        # drop tally and zero the queue gauge while we still can
        self._sync_metrics()
        self._m_queue.set(0)


_LOG: Optional[EventLog] = None
_LOG_LOCK = threading.Lock()


def configure_event_log(path: Optional[str],
                        max_bytes: Optional[int] = DEFAULT_MAX_BYTES,
                        backups: int = DEFAULT_BACKUPS) -> Optional[EventLog]:
    """Install (or, with None, remove) the process-wide event sink."""
    global _LOG
    with _LOG_LOCK:
        if _LOG is not None:
            _LOG.close()
        _LOG = EventLog(path, max_bytes=max_bytes, backups=backups) \
            if path else None
        return _LOG


def get_event_log() -> Optional[EventLog]:
    return _LOG


def event(name: str, **fields: Any) -> None:
    """Record one discrete event (no-op until a sink is configured)."""
    log = _LOG
    if log is not None:
        log.write({"event": name, **fields})


# records kept per span name; a constant, not an option: what a reader needs
# of one name (the set-up spans, the last window's steps) fits many times over
SPAN_DEPTH = 4096


class SpanSnapshot(list):
    """What ``spans()`` returns: the records, oldest first, and ``dropped``,
    the number of records each name's full buffer has let go so far."""

    dropped: Dict[str, int]


class SpanRecorder:
    """The process's finished spans, in one bounded buffer per span name."""

    # pitlint PIT-LOCK: spans finish on any thread (the fit loop, jax's
    # compile path, engine workers) and readers snapshot from another
    _guarded_by = {"_buffers": "_lock", "_dropped": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._buffers: Dict[str, deque] = {}
        self._dropped: Dict[str, int] = {}
        self._ids = itertools.count(1)  # next() on it is atomic under the GIL
        self._local = threading.local()  # .open: ids of this thread's open spans

    def _open_ids(self) -> List[int]:
        try:
            return self._local.open
        except AttributeError:
            self._local.open = []
            return self._local.open

    def open(self) -> int:
        """A new span begins on this thread: its id, now the innermost open."""
        span_id = next(self._ids)
        self._open_ids().append(span_id)
        return span_id

    def close(self, span_id: int) -> None:
        try:
            self._open_ids().remove(span_id)
        except ValueError:
            pass  # closed on another thread than the one that opened it

    def add(self, name: str, start_ns: int, end_ns: int, ok: bool,
            fields: Dict[str, Any], span_id: Optional[int] = None) -> None:
        """Keep one finished span. Its parent is the innermost span open on
        this thread now: spans close innermost first, so for a span that has
        just closed that is the one that was open when it started."""
        open_ids = self._open_ids()
        record = {  # a new dict; its own keys last, so no field shadows them
            **fields,
            "name": name,
            "id": next(self._ids) if span_id is None else span_id,
            "parent": open_ids[-1] if open_ids else None,
            "thread": threading.get_ident(),
            "start_ns": start_ns,
            "end_ns": end_ns,
            "ok": ok,
        }
        with self._lock:
            buf = self._buffers.get(name)
            if buf is None:
                buf = self._buffers[name] = deque(maxlen=SPAN_DEPTH)
            elif len(buf) == SPAN_DEPTH:
                self._dropped[name] = self._dropped.get(name, 0) + 1
            buf.append(record)

    def snapshot(self, name: Optional[str] = None) -> SpanSnapshot:
        with self._lock:
            if name is None:
                records = [r for buf in self._buffers.values() for r in buf]
            else:
                records = list(self._buffers.get(name, ()))
            dropped = dict(self._dropped)
        out = SpanSnapshot(sorted(records, key=lambda r: (r["start_ns"], r["id"])))
        out.dropped = dropped
        return out


_RECORDER = SpanRecorder()
_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is imported


def _trace_annotation():
    """The profiler's annotation class if ``jax`` is already imported in the
    process, else None. Never imports jax: entry points pick their platform
    before the first import, and obs must not pre-empt that."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _TRACE_ANNOTATION = getattr(profiler, "TraceAnnotation", None)
    return _TRACE_ANNOTATION


class span(contextlib.ContextDecorator):
    """Time a stretch of the program as one span: ``with span("warmup",
    engine="e1"):`` or ``@span("trainer.init")`` on a function (each call is
    then its own span). The finished span is kept in memory (module
    docstring), mirrored while open as the profiler annotation
    ``pio.<name>``, and, with a JSONL sink configured, written as one event
    carrying ``dur_s`` (and ``ok=False`` plus the error type when the body
    raises)."""

    def __init__(self, name: str, **fields: Any):
        self.name = name
        self.fields = fields

    def _recreate_cm(self):  # decorator use: a fresh span per call
        return span(self.name, **self.fields)

    def __enter__(self) -> None:
        self._id = _RECORDER.open()
        cls = _trace_annotation()
        self._annotation = None if cls is None else cls("pio." + self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._start_ns = time.monotonic_ns()

    def __exit__(self, exc_type, exc, tb) -> None:
        end_ns = time.monotonic_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        _RECORDER.close(self._id)
        ok = exc_type is None
        if _LOG is not None:
            dur_s = round((end_ns - self._start_ns) / 1e9, 6)
            if ok:
                event(self.name, dur_s=dur_s, ok=True, **self.fields)
            else:
                event(self.name, dur_s=dur_s, ok=False,
                      error=exc_type.__name__, **self.fields)
        _RECORDER.add(self.name, self._start_ns, end_ns, ok, self.fields,
                      self._id)


def add_span(name: str, start_ns: int, end_ns: int, **fields: Any) -> None:
    """Enter a finished span whose ends (``time.monotonic_ns()``) were taken
    elsewhere: one append, kept in memory only (never written to the JSONL
    sink, no profiler annotation). Its parent is the innermost span open on
    the calling thread."""
    _RECORDER.add(name, start_ns, end_ns, True, fields)


def spans(name: Optional[str] = None) -> SpanSnapshot:
    """A snapshot of the finished spans kept in memory (of one name, or of
    all), oldest first; ``.dropped`` counts, per name, the records a full
    buffer has let go."""
    return _RECORDER.snapshot(name)
