"""High-throughput serving engine: continuous micro-batching over the
bucketed XLA programs, plus the latent-cache (encode-once / decode-many) path.

``Predictor`` (``inference/predictor.py``) made single requests
compile-stable; this module makes a *stream* of requests fast. The three
ideas, all reusing machinery the training stack already proved out:

1. **Continuous micro-batching** (``ServingEngine``): callers ``submit()``
   requests into a queue; a worker thread coalesces whatever is pending into
   one micro-batch, pads it to the next power-of-two bucket (the
   ``Predictor`` shapes — one XLA program per bucket, ``warmup()`` compiles
   them all ahead of time so steady state never compiles), and dispatches.
   Up to ``max_inflight`` dispatches stay in flight, so host work — queue
   drain, padding, result slicing — overlaps device compute exactly the way
   ``steps_per_dispatch`` overlaps the training loop. While the device chews
   on batch *i*, arrivals accumulate and become batch *i+1*: under load the
   engine serves large batches at device throughput; idle, a lone request
   dispatches immediately (``max_delay_ms`` optionally holds the first
   request back to let a batch form).

2. **Latent-cache decode** (``MLMServer.encode`` / ``decode``): Perceiver
   IO's fixed latent array is the model's entire summary of the input — the
   architecture's analogue of a KV cache. The split ``encode()``/``decode()``
   methods on the model core (``models/perceiver.py``) let multi-query
   workloads (fill-mask at several positions, multi-task decode heads) pay
   the O(L) encoder cross-attention once and decode arbitrarily many query
   sets against the cached latents.

3. **Width bucketing for variable-length text** (``MLMServer``): requests
   tokenize to their natural length and pad to the smallest serving width
   bucket (``resolve_bucket_width`` — the same rule as the training
   collator's ``bucket_widths``), so short requests never pay max_seq_len
   compute. Same-width requests batch together; each (width, batch-bucket,
   query-bucket) triple is one compiled program, all warmable ahead of time.

bf16 serving: pass ``compute_dtype='bfloat16'`` to an engine built over a
bf16-``dtype`` model — floating params/inputs are cast ONCE at engine
construction / dispatch (halving param HBM traffic per batch). Never set it
on the f32 golden-parity path: bf16 rounds. On TPU the padded input buffers
are donated to XLA (``donate_argnums``) — each dispatch's staging buffer is
handed to the device while the host fills the next one (ping-pong staging);
off-TPU donation is skipped (unimplemented there, and XLA would warn).

int8w serving: ``quantize='int8'`` (or the ``compute_dtype='int8w'``
shorthand — bf16 compute over int8-stored weights) quantizes the matmul
kernels ONCE at engine construction (``perceiver_io_tpu.quant``: per-channel
symmetric int8, f32 scales, key paths identical to the f32 tree) and
dequantizes inside the jitted dispatch, so each micro-batch streams int8
weight bytes from HBM — the measured roofline's binding term. Same bucket
programs, same AOT ``warmup()``; checkpoints stay f32 on disk.
``update_params()`` hot-swaps (re-quantizing under the same mode) without
recompiling: preparation runs on the caller thread and the worker installs
the finished tree atomically between micro-batches, so requests that arrive
mid-(re)quantization queue against the old params rather than racing a
half-built tree.

Self-healing (``perceiver_io_tpu.resilience``): the engine assumes the
device can misbehave: hang, throw transient errors, or fail for good —

- **request deadlines** (``request_deadline_s`` / ``submit(deadline_s=)``):
  enforced at admission (an already-expired deadline is refused) and again
  at batch assembly, where expired parts are shed with
  :class:`~perceiver_io_tpu.resilience.DeadlineExceeded` instead of burning
  a dispatch on work whose caller's ``result(timeout=)`` already gave up;
- **bounded queue** (``queue_limit``): admission fast-fails with
  :class:`~perceiver_io_tpu.resilience.RejectedError` once that many parts
  are backlogged — explicit load shedding instead of unbounded queue growth;
- **transient re-dispatch** (``dispatch_retries``): a dispatch or completion
  failure the classification classifies transient re-queues the micro-batch with
  exponential backoff instead of failing every rider's future;
- **circuit breaker** (``breaker_failures`` > 0): consecutive dispatch
  failures — or a heartbeat stall, via the monitor's ``on_stall`` hook —
  open it; submissions then fast-fail
  (:class:`~perceiver_io_tpu.resilience.BreakerOpen`) until a cooldown
  half-open probe succeeds. State rides the obs registry and ``/healthz``.

Shed/retry/breaker counts export as ``serving_shed_total{reason=...}`` /
``serving_dispatch_retries_total`` / ``breaker_*``.

SLO observability (``perceiver_io_tpu.obs.slo``, ``tools/load_bench.py``):
every request part carries phase timestamps through its whole lifecycle —
submit → queue → batch assembly → dispatch → device compute → completion —
exported per phase as ``serving_phase_seconds{phase=...}`` histograms, as
JSONL spans when an event log is configured (untraced traffic:
``request_phases`` per part, sampled by ``span_every``; traced requests —
``submit(trace=)`` or an engine-minted root under ``trace_sample`` — ride
the compact spooled ``request_phases_batch`` record, assembled into
distributed trace trees by ``obs.reqtrace``/``tools/trace_assemble.py``),
and on the caller's future (``fut.phases``). The phases are consecutive
timestamp diffs, so their sum reconciles with the end-to-end
``serving_latency_seconds`` by construction (``serving_phase_sum_ratio`` is
the live self-check; the test suite pins the p50 reconciliation within 5%,
cross-process since r15). Tail latency therefore ATTRIBUTES: "p99 is high"
becomes "p99 is high because admission wait, not device time". Passing ``slo=obs.SLO(...)``
additionally classifies every completion/shed against a declarative
objective — error-budget burn-rate gauges ride ``/statz`` and ``healthz()``,
and ``tools/load_bench.py`` fits the measured capacity model
(requests/s/chip at the SLO) from an open-loop offered-load sweep.

Zero-recompile cold start (``perceiver_io_tpu.aot``): ``compile_cache=DIR``
persists every compiled bucket program to disk
(``jax.experimental.serialize_executable``), keyed by a content fingerprint
(apply-fn source/model identity, jax+PJRT platform/topology, abstract
shapes/dtypes, donation/quantize/dtype config). A warm restart deserializes
each program instead of tracing+lowering+compiling it — ``warmup()`` then
performs ZERO XLA compiles (pinned by test via ``jax_compilations_total``).
Corrupt entries and fingerprint drift fall back to a normal compile; a cache
problem never refuses traffic. ``warmup(background=True)`` turns the
blocking compile-everything call into a cache-first, priority-ordered
(smallest bucket first) BACKGROUND warmup: the engine serves traffic as soon
as the first needed bucket is ready — a request for a not-yet-warm program
either rides the warmup thread's in-flight build (cache mode dedups via a
per-program claim) or compiles on demand — and the remaining family keeps
warming off the hot path. Warmth is observable: per-engine ``engine_ready``
gauge (0 = warming, 1 = last requested family fully warm, surfaced on
``/statz``) and ``serving_warmup_seconds``.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.aot import (
    callable_sources,
    environment_fingerprint,
    fingerprint as aot_fingerprint,
    resolve_cache,
)
from perceiver_io_tpu.inference.predictor import bucket_size
from perceiver_io_tpu.resilience import (
    BreakerOpen,
    CircuitBreaker,
    DeadlineExceeded,
    RejectedError,
    RetryPolicy,
    faults,
    is_transient,
)

_IDLE_POLL_S = 0.05  # worker wake-up cadence while idle (checks shutdown)
_TRACE_SPOOL_ROWS = 64  # traced span rows per flushed JSONL record (the
# spool also flushes at the first idle moment and on worker exit, so span
# visibility lags only while the engine is saturated — when offline
# assembly is the consumer anyway)

# per-request lifecycle phases, in order; consecutive timestamp diffs, so the
# sum reconciles with the end-to-end latency by construction (the self-check
# rides serving_phase_sum_ratio and the test suite):
#   admission — submit() entry → part enqueued (validation, chunking, bounds)
#   queue     — enqueued → sealed into a micro-batch by the worker
#   assembly  — sealed → padded/cast columns built (host batch formation)
#   dispatch  — columns → the program call returned (host dispatch; a cold
#               program pays its compile/deserialize here)
#   device    — dispatch returned → outputs fetched to host (device compute
#               plus any wait behind earlier in-flight dispatches)
#   complete  — fetched → this part's future delivered (slicing, fan-out)
PHASES = ("admission", "queue", "assembly", "dispatch", "device", "complete")


def resolve_params_mode(
    compute_dtype: Optional[str], quantize: Optional[str]
) -> Tuple[Optional[str], Optional[str]]:
    """Normalize the (compute_dtype, quantize) pair — ONE definition of the
    ``'int8w'``/``'int4w'`` shorthands (bf16 compute over int-stored
    weights) and the mode validation, shared by ``ServingEngine``,
    ``MLMServer``, and the decode engines so they can never drift."""
    # validate BEFORE the shorthand rewrite: compute_dtype='int8w' must not
    # silently swallow a typo'd quantize= argument
    if quantize not in (None, "int8", "int4"):
        raise ValueError(
            f"unknown quantize mode {quantize!r}; expected None, 'int8', "
            "or 'int4'"
        )
    if compute_dtype == "int8w":
        compute_dtype, quantize = "bfloat16", "int8"
    elif compute_dtype == "int4w":
        compute_dtype, quantize = "bfloat16", "int4"
    return compute_dtype, quantize


def prepare_param_tree(params, compute_dtype, quantize: Optional[str],
                       group_size: Optional[int] = None):
    """Load-time param preparation under a serving mode (no device_put):
    cast floating leaves to ``compute_dtype`` (bf16 path), or quantize the
    matmul kernels to int8/int4 with the remaining floats cast (int8w/int4w
    paths — scales computed from the caller's tree, so hand in f32 for full
    scale precision; int4 defaults to grouped scales, ``group_size``
    overrides). A tree that is already ``QuantizedParams`` is trusted as
    prepared."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.quant import is_quantized, quantize_tree

    if is_quantized(params):
        return params  # prepared upstream (e.g. once for MLMServer's 3 engines)
    if quantize in ("int8", "int4"):
        return quantize_tree(
            params,
            compute_dtype=str(jnp.dtype(compute_dtype or jnp.float32)),
            bits=8 if quantize == "int8" else 4,
            group_size=group_size,
        )
    if compute_dtype is not None:
        dt = jnp.dtype(compute_dtype)
        cast = lambda x: (
            x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x
        )
        return jax.tree.map(cast, params)
    return params


class EngineClosed(RuntimeError):
    """submit() after close()."""


class WarmupHandle:
    """Tracks one (possibly background) warmup run.

    ``wait()`` blocks until the warmup finishes and returns its result (the
    warmed bucket list for an engine, the warmed program count for an
    ``MLMServer``), re-raising any warmup error. ``cancel()`` asks the
    warming thread(s) to stop at the next bucket boundary (an in-flight
    compile cannot be interrupted); ``close()`` cancels automatically.
    """

    def __init__(self):
        self._done_event = threading.Event()
        self._cancel_event = threading.Event()
        self._error: Optional[BaseException] = None
        self._threads: List[threading.Thread] = []
        self.result: Any = None

    def done(self) -> bool:
        return self._done_event.is_set()

    def cancelled(self) -> bool:
        return self._cancel_event.is_set()

    def cancel(self) -> None:
        self._cancel_event.set()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for the warming thread(s) to actually exit (bounded).

        ``cancel()`` only asks; a thread mid-compile finishes that build
        first. Owners call this from ``close()`` so no warmup thread keeps
        driving the jax runtime concurrently with whatever the process does
        next — a leftover compile racing later work is a real crash, not a
        hygiene nit. A wedged build past ``timeout`` is abandoned (daemon)."""
        for t in self._threads:
            t.join(timeout)

    def wait(self, timeout: Optional[float] = None):
        if not self._done_event.wait(timeout):
            raise TimeoutError("warmup not finished within timeout")
        if self._error is not None:
            raise self._error
        return self.result

    def _finish(self, result) -> None:
        self.result = result
        self._done_event.set()

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self._done_event.set()


class _Future:
    """Result handle for one submitted request.

    Oversized requests are split into ``num_parts`` sub-dispatches; the
    future assembles them (axis-0 concat per leaf) when the last completes.
    ``transform`` (optional) maps the assembled result in the caller's
    ``result()`` — post-processing (top-k decode, detokenization) stays off
    the engine worker thread.
    """

    def __init__(self, num_parts: int = 1,
                 transform: Optional[Callable[[Any], Any]] = None,
                 trace: Optional[obs.TraceContext] = None):
        self._event = threading.Event()
        self._parts: List[Any] = [None] * num_parts
        self._remaining = num_parts
        self._lock = threading.Lock()
        self._error: Optional[BaseException] = None
        self._transform = transform
        self._assembled = None
        self._has_result = False
        self._phases: List[Dict[str, float]] = []
        self.trace = trace  # distributed-trace context (None = untraced)

    def _note_phases(self, phases: Dict[str, float]) -> None:
        with self._lock:
            self._phases.append(phases)

    @property
    def phases(self) -> List[Dict[str, float]]:
        """Per-part phase timings (seconds, :data:`PHASES` keys) recorded at
        completion — one dict per dispatched part, the caller-side view the
        load harness consumes without scraping the registry."""
        with self._lock:
            return [dict(p) for p in self._phases]

    def _deliver(self, index: int, result) -> None:
        with self._lock:
            self._parts[index] = result
            self._remaining -= 1
            if self._remaining == 0:
                self._event.set()

    def _fail(self, error: BaseException) -> None:
        with self._lock:
            if self._error is None:
                self._error = error
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("request not served within timeout")
        if self._error is not None:
            raise self._error
        with self._lock:
            if not self._has_result:
                if len(self._parts) == 1:
                    out = self._parts[0]
                else:
                    import jax

                    out = jax.tree.map(
                        lambda *xs: np.concatenate(xs, axis=0), *self._parts
                    )
                if self._transform is not None:
                    out = self._transform(out)
                self._assembled, self._has_result = out, True
                self._parts = []  # free the per-part copies
        return self._assembled


class _Part:
    """One queue unit: ≤ max_batch rows of one request.

    ``deadline`` (monotonic, or None) is checked at batch assembly — expired
    parts are shed, never dispatched. ``retries`` counts transient
    re-dispatch cycles this part has ridden (worker-thread-only writes).

    Phase timestamps (monotonic): ``t_entry`` (submit() entry),
    ``t_submit`` (enqueued), then worker-written ``t_sealed`` / ``t_built`` /
    ``t_sent`` — a retried part overwrites them on its final dispatch, so the
    queue phase absorbs the retry wait and the sum still partitions
    [t_entry, delivery].
    """

    __slots__ = ("inputs", "n", "key", "future", "index", "t_submit",
                 "deadline", "retries", "t_entry", "t_sealed", "t_built",
                 "t_sent")

    def __init__(self, inputs: List[np.ndarray], key, future: _Future,
                 index: int, deadline: Optional[float] = None,
                 t_entry: Optional[float] = None):
        self.inputs = inputs
        self.n = inputs[0].shape[0]
        self.key = key
        self.future = future
        self.index = index
        self.t_submit = time.monotonic()
        self.t_entry = self.t_submit if t_entry is None else t_entry
        self.deadline = deadline
        self.retries = 0
        self.t_sealed = self.t_built = self.t_sent = self.t_submit


class ServingEngine:
    """Continuous micro-batching over ``apply_fn(params, *inputs)``.

    - requests with identical non-leading shapes/dtypes (the program *key* —
      e.g. one sequence-width bucket) coalesce into micro-batches, padded to
      the next power-of-two ≤ ``max_batch`` (padding repeats row 0; sliced
      off per request), oldest key first;
    - requests larger than ``max_batch`` are chunked and reassembled;
    - ``max_inflight`` dispatches are kept outstanding — assembling batch
      *i+1* overlaps the device computing batch *i*;
    - ``warmup(*example)`` compiles every batch bucket for an input
      signature ahead of time, so steady-state serving never compiles;
    - ``compute_dtype`` casts floating params (once) and inputs (per batch)
      — the bf16 serving path; leave None on the f32 parity path;
    - on TPU, input buffers are donated to XLA (ping-pong staging).

    Telemetry: every engine publishes ``serving_*`` instruments (labeled
    ``engine=<name>``) to the metrics registry — request/row/batch/padding
    counters, queue-depth and in-flight gauges, admission→dispatch wait and
    per-bucket latency histograms, compile events. ``heartbeat_deadline_s``
    arms a dispatch heartbeat: if no dispatch completes within the deadline
    while work is in flight (the wedged-dispatch signature), ``/healthz`` flips
    unhealthy and a diagnostic snapshot (thread stacks + queue state) is
    dumped instead of the loop hanging silently. ``selfprofile_every`` > 0
    turns on the in-loop device-trace watchdog every that-many micro-batches.
    ``stats()`` remains as a locked, deep-copied per-instance snapshot (the
    registry is the cross-engine aggregate).

    ``apply_fn`` must treat examples independently along the leading axis
    (true of every model here) and be deterministic (dropout off).
    """

    # pitlint PIT-LOCK (analysis/rules_locks.py): these attributes are shared
    # between the submit/caller threads and the worker — every touch outside
    # __init__ must sit inside `with self.<lock>` (lock-free fast paths carry
    # an inline pragma with their reasoning)
    _guarded_by = {
        "_stats": "_stats_lock",
        "_dispatch_seq": "_stats_lock",
        "_backlog": "_stats_lock",
        "_assembling": "_stats_lock",
        "_pending_params": "_params_lock",
        "_params_version": "_params_lock",
        "_params_staged": "_params_lock",
        "_aot_programs": "_aot_lock",
        "_aot_claims": "_aot_lock",
    }

    def __init__(
        self,
        apply_fn: Callable[..., Any],
        params,
        max_batch: int = 64,
        max_delay_ms: float = 0.0,
        max_inflight: int = 2,
        compute_dtype: Optional[str] = None,
        quantize: Optional[str] = None,
        group_size: Optional[int] = None,
        donate_inputs: Optional[bool] = None,
        name: str = "serve",
        registry: Optional[obs.MetricsRegistry] = None,
        heartbeat_deadline_s: Optional[float] = None,
        selfprofile_every: int = 0,
        request_deadline_s: Optional[float] = None,
        queue_limit: Optional[int] = None,
        dispatch_retries: int = 2,
        retry_policy: Optional[RetryPolicy] = None,
        breaker_failures: int = 0,
        breaker_cooldown_s: float = 5.0,
        compile_cache=None,
        cache_salt: str = "",
        slo: Optional[obs.SLO] = None,
        slo_window: int = 4096,
        span_every: int = 1,
        trace_sample: float = 1.0,
    ):
        import jax
        import jax.numpy as jnp

        from perceiver_io_tpu.quant import is_quantized, kernel_operands

        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        if request_deadline_s is not None and request_deadline_s <= 0:
            raise ValueError(
                f"request_deadline_s must be positive, got {request_deadline_s}"
            )
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        self.max_batch = max_batch
        self.max_delay = max_delay_ms / 1e3
        self.max_inflight = max_inflight
        self.name = name
        self.request_deadline_s = request_deadline_s
        self.queue_limit = queue_limit
        self._retry_policy = (
            retry_policy if retry_policy is not None
            else RetryPolicy(max_retries=max(0, int(dispatch_retries)))
        )
        compute_dtype, quantize = resolve_params_mode(compute_dtype, quantize)
        if is_quantized(params):
            # a pre-quantized tree (MLMServer shares ONE across its engines)
            # implies the mode; its baked compute dtype is validated in
            # _prepare_params — which also guards update_params, so a later
            # hot-swap cannot slip in a mismatched tree either
            quantize = params.mode
            group_size = params.group_size
        if quantize == "int4" and group_size is None:
            # pin the effective group size at construction so the mode
            # guard in _prepare_params can demand exact equality — a
            # hot-swap with a different grouping changes the treedef and
            # would recompile every warmed bucket program
            from perceiver_io_tpu.quant import DEFAULT_GROUP_SIZE

            group_size = DEFAULT_GROUP_SIZE
        self.quantize = quantize
        self.group_size = group_size
        self._compute_dtype = (
            None if compute_dtype is None else jnp.dtype(compute_dtype)
        )
        if donate_inputs is None:
            # donation is a TPU/GPU runtime feature; on CPU XLA ignores it
            # with a warning per program
            donate_inputs = jax.default_backend() == "tpu"
        self.donate_inputs = donate_inputs

        self._params_lock = threading.Lock()
        self._pending_params = None
        # update_params ordering (both under the lock): _params_version hands
        # out call-order tickets, _params_staged records the newest ticket
        # whose PREPARED tree actually staged — a failing preparation never
        # consumes its ticket, so it cannot cancel a concurrent valid update
        self._params_version = 0
        self._params_staged = 0
        self.params = self._prepare_params(params)

        self._apply_fn = apply_fn

        def call(p, inputs):
            if is_quantized(p):
                # traced inside the jit: quantized kernels travel as QKernel
                # operands to the linear_apply sites, where the fused
                # dequant-matmul (TPU) or the XLA-fused dequant (elsewhere)
                # streams the int8/int4 bytes (ops/pallas_matmul.py)
                p = kernel_operands(p)
            return apply_fn(p, *inputs)

        self._call = call
        self._jitted = jax.jit(
            call, donate_argnums=(1,) if donate_inputs else ()
        )

        self._queue: "queue.Queue[_Part]" = queue.Queue()
        # program-key → deque of pending parts; dict order = arrival order of
        # the oldest pending part per key (FIFO across keys)
        self._pending: Dict[Any, deque] = {}
        self._programs: set = set()  # (key, bucket) pairs ever dispatched

        # per-instance stats live behind ONE lock (they are written from the
        # submit/caller threads AND the worker); stats() deep-copies under it
        self._stats_lock = threading.Lock()
        self._stats: Dict[str, Any] = {
            "requests": 0, "rows": 0, "batches": 0, "padded_rows": 0,
            "latency_s_by_bucket": {},
            # per-phase latency windows, written at completion under this
            # same lock so stats() snapshots latency AND its attribution in
            # one consistent read (external pollers never see torn phases)
            "phase_s": {},
        }
        self._dispatch_seq = 0  # StepTraceAnnotation ids (under _stats_lock)
        self._inflight_count = 0  # worker-written, racily read by diagnostics

        self.registry = registry if registry is not None else obs.get_registry()
        labels = {"engine": name}
        reg = self.registry
        self._m_requests = reg.counter(
            "serving_requests_total", "requests submitted", labels)
        self._m_rows = reg.counter(
            "serving_rows_total", "request rows served", labels)
        self._m_batches = reg.counter(
            "serving_batches_total", "micro-batches dispatched", labels)
        self._m_padded = reg.counter(
            "serving_padded_rows_total",
            "padded filler rows (bucket waste)", labels)
        self._m_compiles = reg.counter(
            "serving_compile_events_total",
            "new (signature, batch-bucket) programs entered (one XLA compile "
            "unless warmed — or a zero-compile disk deserialize when the AOT "
            "cache hits; aot_cache_hits_total tells the two apart)", labels)
        self._m_queue = reg.gauge(
            "serving_queue_depth", "parts awaiting batch formation", labels)
        self._m_inflight = reg.gauge(
            "serving_inflight_dispatches", "dispatches in flight", labels)
        self._m_programs = reg.gauge(
            "serving_programs", "distinct compiled programs", labels)
        self._m_occupancy = reg.histogram(
            "serving_batch_occupancy",
            "real rows / bucket rows per micro-batch (1.0 = no padding)",
            labels)
        self._m_wait = reg.histogram(
            "serving_admission_wait_seconds",
            "submit → dispatch wait per request part", labels)
        self._latency_hists: Dict[int, obs.Histogram] = {}
        # per-request phase attribution: "p99 is high" becomes "p99 is high
        # because admission wait, not device time" — one histogram per
        # lifecycle phase, observed at completion from the part's timestamps
        self._m_phase = {
            phase: reg.histogram(
                "serving_phase_seconds",
                "per-request-part time in each lifecycle phase "
                "(admission|queue|assembly|dispatch|device|complete; the "
                "phase sum reconciles with serving_latency_seconds)",
                {**labels, "phase": phase})
            for phase in PHASES
        }
        self._m_phase_ratio = reg.gauge(
            "serving_phase_sum_ratio",
            "phase-sum / end-to-end latency of the last completed part "
            "(the tracing self-check: ~1.0 when the phases partition the "
            "request lifetime)", labels)
        shed_help = "requests/parts shed instead of served, by reason"
        self._m_shed = {
            reason: reg.counter("serving_shed_total", shed_help,
                                {**labels, "reason": reason})
            for reason in ("queue_full", "breaker_open", "deadline", "draining")
        }
        self._m_retries = reg.counter(
            "serving_dispatch_retries_total",
            "transient micro-batch re-dispatch cycles", labels)
        self._backlog = 0  # parts admitted but not yet dispatched/shed
                           # (written under _stats_lock)
        self._assembling = 0  # parts the worker has popped from the backlog
                              # but not yet dispatched/shed/failed — closes
                              # the drain() window between the backlog
                              # decrement and the in-flight increment
                              # (written under _stats_lock)

        # zero-recompile cold start (perceiver_io_tpu.aot): when a cache is
        # attached, every bucket program dispatches through an AOT-compiled
        # executable — loaded from disk on a fingerprint hit, compiled (and
        # persisted) otherwise. _aot_claims dedups concurrent builds of the
        # same program (background warmup racing the worker's on-demand path).
        self._cache = resolve_cache(compile_cache, registry=reg)
        self._cache_salt = cache_salt
        self._aot_lock = threading.Lock()
        self._aot_programs: Dict[Any, Any] = {}
        self._aot_claims: Dict[Any, threading.Event] = {}
        self._fp_base = None  # lazy: needs the backend up
        # every live warmup's handle (one per warmup() call — e.g. one per
        # signature): close() must cancel+join ALL of them, not just the
        # newest, or an earlier signature's thread outlives the engine
        self._warmup_handles: List[WarmupHandle] = []
        self._m_ready = reg.gauge(
            "engine_ready",
            "1 once the last requested warmup family is fully "
            "compiled/loaded; 0 while cold or warming", labels)
        self._m_warmup_s = reg.gauge(
            "serving_warmup_seconds",
            "wall seconds the last warmup took (cache hits make this "
            "near-zero)", labels)

        self.breaker: Optional[CircuitBreaker] = None
        if breaker_failures > 0:
            self.breaker = CircuitBreaker(
                name=name, failure_threshold=breaker_failures,
                cooldown_s=breaker_cooldown_s, registry=reg,
            )

        # declarative objective: every completion/shed classifies against it,
        # burn-rate gauges ride the registry and healthz() (obs/slo.py)
        self.slo_tracker: Optional[obs.SLOTracker] = None
        if slo is not None:
            # slo_window bounds the classification window (burn rate =
            # recent behavior): a smaller window makes the burn gauge — and
            # any alert rule over it — track episode boundaries faster
            self.slo_tracker = obs.SLOTracker(slo, registry=reg,
                                              labels=labels,
                                              window=slo_window)

        # untraced JSONL request_phases spans sample every Nth part (the
        # registry histograms keep the full-rate view regardless); TRACED
        # parts instead spool compact rows that flush as ONE record per
        # _TRACE_SPOOL_ROWS completions (or at the first idle moment /
        # worker exit), so full tracing amortizes its serialization the
        # way the dispatch amortizes everything else
        self._span_every = max(1, int(span_every))
        self._trace_spool: List[list] = []  # worker-thread-only
        self._span_seq = 0  # worker-thread-only
        # distributed tracing: requests arriving WITHOUT a propagated
        # context (single-process serving) mint their own root at this
        # head-sampling rate once an event log is configured; propagated
        # contexts (the replica shim) carry the router's decision instead
        self._trace_sample = float(trace_sample)

        self.heartbeat = obs.Heartbeat(
            f"{name}-dispatch", deadline_s=heartbeat_deadline_s,
            diagnostics=self._diagnostics,
            # a wedged dispatch never FAILS — only the stall monitor can see
            # it; tripping the breaker makes submission fast-fail while the
            # worker is stuck inside the hung device call
            on_stall=(
                (lambda: self.breaker.trip("heartbeat stall (wedged dispatch)"))
                if self.breaker is not None else None
            ),
        )
        self._profiler: Optional[obs.SelfProfiler] = None
        if selfprofile_every > 0:
            self._profiler = obs.SelfProfiler(
                every_n=selfprofile_every, prefix=name, registry=reg
            )

        self._crash: Optional[BaseException] = None
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"{name}-engine", daemon=True
        )
        self._thread.start()

    # -- params preparation / hot swap ---------------------------------------

    def _prepare_params(self, params):
        """:func:`prepare_param_tree` under this engine's mode + device_put.

        Guards BOTH construction and ``update_params``: a pre-quantized tree
        must match this engine's mode exactly — a mismatched baked compute
        dtype (or a quantized tree handed to a non-quantized engine) would
        silently serve a different precision than advertised AND change the
        params treedef, recompiling every warmed bucket program mid-serving.
        """
        import jax
        import jax.numpy as jnp

        from perceiver_io_tpu.quant import is_quantized

        if is_quantized(params):
            want = str(jnp.dtype(self._compute_dtype or jnp.float32))
            if (self.quantize != params.mode
                    or params.compute_dtype != want
                    or self.group_size != params.group_size):
                raise ValueError(
                    f"pre-quantized params (mode={params.mode!r}, "
                    f"compute_dtype={params.compute_dtype!r}, group_size="
                    f"{params.group_size}) do not match this engine's mode "
                    f"(quantize={self.quantize!r}, compute_dtype={want!r}, "
                    f"group_size={self.group_size}) — re-quantize under the "
                    "engine's mode or pass the raw f32 tree"
                )
        return jax.device_put(
            prepare_param_tree(params, self._compute_dtype, self.quantize,
                               self.group_size)
        )

    def update_params(self, params) -> None:
        """Hot-swap the served parameters without recompiling.

        Preparation (the same cast/quantize as construction — hand in the
        raw f32 tree, not a pre-cast copy) runs on the CALLER thread; the
        finished tree is installed atomically by the worker between
        micro-batches. Requests arriving while a (re)quantization is in
        progress therefore queue normally and are served with whichever
        complete tree is installed at their dispatch — never a torn one.
        In-flight dispatches finish on the old params. As long as the new
        tree matches the old structure/shapes/dtypes (same checkpoint
        family), the warmed bucket programs are reused without recompiling.

        Concurrent calls resolve in CALL order, not prepare-completion
        order: each call takes a version ticket up front and only stages its
        tree if no NEWER call has already staged — a slow (re)quantization
        of an older tree can never overwrite a newer one, and a call whose
        preparation RAISES (e.g. a mismatched pre-quantized tree) never
        consumes its ticket, so it cannot cancel a concurrent valid update.
        """
        if self._stop.is_set():
            raise self._closed_error("update_params()")
        with self._params_lock:
            self._params_version += 1
            version = self._params_version
        prepared = self._prepare_params(params)  # may raise: nothing consumed
        with self._params_lock:
            if version < self._params_staged:
                return  # a newer update_params call already staged its tree
            self._params_staged = version
            self._pending_params = prepared
        obs.event("engine_params_update_staged", engine=self.name)

    def _install_pending_params(self) -> None:
        """Worker-only: adopt a staged param tree between micro-batches."""
        # lock-free fast path on the per-batch hot loop: a stale None read
        # just defers the install one micro-batch; the adopt re-reads locked
        if self._pending_params is None:  # pitlint: ignore[PIT-LOCK] racy-None fast path, install re-reads under the lock
            return
        with self._params_lock:
            pending, self._pending_params = self._pending_params, None
        self.params = pending
        obs.event("engine_params_update", engine=self.name)

    # -- submission ----------------------------------------------------------

    def _closed_error(self, verb: str = "submit()") -> EngineClosed:
        """EngineClosed naming WHY the engine is closed; a worker crash is
        chained as ``__cause__`` so post-crash callers see the root error,
        not just 'closed'."""
        if self._crash is not None:
            err = EngineClosed(
                f"{verb} on a crashed engine (worker died: "
                f"{type(self._crash).__name__}: {self._crash})"
            )
            err.__cause__ = self._crash
            return err
        return EngineClosed(f"{verb} on a closed engine")

    def _slo_bad(self, n: int = 1) -> None:
        """Shed/failed work counts against the SLO's error budget. The unit
        is the PART (what completions record); admission-time refusals that
        happen before the request is chunked (breaker open, pre-expired
        deadline) record one sample — their part count does not exist yet."""
        if self.slo_tracker is not None:
            for _ in range(n):
                self.slo_tracker.record(ok=False)

    def submit(self, *inputs, transform: Optional[Callable] = None,
               deadline_s: Optional[float] = None,
               trace: Optional[obs.TraceContext] = None) -> _Future:
        """Enqueue one request (arrays sharing a leading batch axis); returns
        a future whose ``result()`` is the output pytree sliced to this
        request's rows (numpy, on host).

        ``deadline_s`` (default: the engine's ``request_deadline_s``) bounds
        how long the request may wait for a dispatch: an expired request is
        shed with :class:`DeadlineExceeded` at admission or batch assembly
        instead of occupying the queue as dead work. Admission can also
        fast-fail with :class:`RejectedError` (queue full) or
        :class:`BreakerOpen` (device presumed down).

        ``trace`` joins this request to a distributed trace (the replica
        shim propagates the router's context here); with none given and an
        event log configured, a fresh root is minted (head sampling via the
        engine's ``trace_sample``) — single-process serving traces too.
        Traced parts always emit their engine span, riding the compact
        per-micro-batch ``request_phases_batch`` record (``span_every``
        sampling applies only to untraced traffic: a tail-sampled trace
        with a missing engine hop would assemble as a hole).
        """
        t_entry = time.monotonic()
        if trace is None:
            trace = obs.maybe_trace(self._trace_sample)
        if self._stop.is_set():
            raise self._closed_error()
        if self._draining.is_set():
            # graceful drain: already-admitted work keeps flowing, NEW work
            # is refused with the shed-fast semantics of a full queue. The
            # refusal is deliberately NOT an SLO breach: the tier above (the
            # serving router, a supervisor restart) re-routes it — the
            # request is displaced, not lost.
            self._m_shed["draining"].inc()
            raise RejectedError(
                f"engine {self.name!r} is draining — not admitting new work"
            )
        if self.breaker is not None and not self.breaker.allow():
            self._m_shed["breaker_open"].inc()
            self._slo_bad()
            raise BreakerOpen(
                f"engine {self.name!r}: circuit breaker open "
                f"(device presumed down; cooldown {self.breaker.cooldown_s:g}s)"
            )
        if deadline_s is None:
            deadline_s = self.request_deadline_s
        if deadline_s is not None and deadline_s <= 0:
            self._m_shed["deadline"].inc()
            self._slo_bad()
            raise DeadlineExceeded(
                f"request deadline {deadline_s}s already expired at admission"
            )
        arrays = [np.asarray(x) for x in inputs]
        if not arrays:
            raise ValueError("submit() needs at least one input array")
        n = arrays[0].shape[0]
        if any(a.shape[0] != n for a in arrays):
            raise ValueError("all inputs must share the leading batch axis")
        if n == 0:
            fut = _Future(1, transform, trace=trace)
            fut._deliver(0, self._empty_result(arrays))
            return fut
        starts = list(range(0, n, self.max_batch))
        # backlog is tracked unconditionally (diagnostics read it); the
        # bound is only ENFORCED when queue_limit is set
        with self._stats_lock:
            if (self.queue_limit is not None
                    and self._backlog + len(starts) > self.queue_limit):
                backlog = self._backlog
                admitted = False
            else:
                self._backlog += len(starts)
                admitted = True
        if not admitted:
            self._m_shed["queue_full"].inc()
            # per PART, the same unit completions record at — a shed 4-part
            # request must weigh as much in the burn rate as a served one
            self._slo_bad(len(starts))
            raise RejectedError(
                f"engine {self.name!r}: queue full ({backlog} parts "
                f"backlogged, limit {self.queue_limit}) — request shed"
            )
        fut = _Future(len(starts), transform, trace=trace)
        deadline = (
            None if deadline_s is None else time.monotonic() + deadline_s
        )
        with self._stats_lock:
            self._stats["requests"] += 1
        self._m_requests.inc()
        for index, start in enumerate(starts):
            chunk = [a[start: start + self.max_batch] for a in arrays]
            self._queue.put(
                _Part(chunk, self._key(chunk), fut, index, deadline=deadline,
                      t_entry=t_entry)
            )
        self._m_queue.set(self._queue.qsize())
        if self._stop.is_set() and not self._thread.is_alive():
            # raced a shutdown/worker-crash: the drain already ran, so these
            # parts would sit unread forever — fail the future ourselves
            fut._fail(self._closed_error("request queued"))
        return fut

    def predict(self, *inputs, timeout: Optional[float] = None):
        """Synchronous submit + result."""
        return self.submit(*inputs).result(timeout=timeout)

    def _key(self, arrays: Sequence[np.ndarray]):
        return tuple((a.shape[1:], str(a.dtype)) for a in arrays)

    def _empty_result(self, arrays: Sequence[np.ndarray]):
        """n=0 request: pytree of empty arrays via eval_shape (no device)."""
        import jax

        ones = tuple(
            self._cast(np.zeros((1, *a.shape[1:]), a.dtype)) for a in arrays
        )
        shapes = jax.eval_shape(self._call, self.params, ones)
        return jax.tree.map(
            lambda s: np.zeros((0, *s.shape[1:]), s.dtype), shapes
        )

    # -- warmup --------------------------------------------------------------

    def warmup(self, *example_inputs,
               buckets: Optional[Sequence[int]] = None,
               background: bool = False):
        """Ready every batch bucket for this input signature (row 0 of
        ``example_inputs``, tiled) ahead of traffic — from the AOT cache when
        one is attached (deserialize, zero compiles), compiling otherwise.
        One call per distinct signature — e.g. per serving width bucket —
        and steady state never compiles.

        Blocking (default): returns the warmed bucket list, raising on
        error — the historical contract. ``background=True`` returns a
        :class:`WarmupHandle` immediately and warms on a daemon thread in
        PRIORITY order (smallest bucket first, so a lone request is
        servable as soon as bucket 1 lands); traffic may be submitted right
        away — a request whose program is mid-build rides the warmup
        thread's build (cache mode) or compiles on demand.
        """
        arrays = [np.asarray(x) for x in example_inputs]
        if any(a.shape[0] < 1 for a in arrays):
            raise ValueError("warmup needs at least one example row")
        if buckets is None:
            buckets, b = [], 1
            while b < self.max_batch:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_batch)
        # ascending = priority order: the small buckets unblock first traffic
        buckets = sorted({bucket_size(int(b), self.max_batch) for b in buckets})
        handle = WarmupHandle()
        # prune finished handles so a long-lived engine's list stays flat
        self._warmup_handles = [
            h for h in self._warmup_handles if not h.done()
        ] + [handle]
        self._m_ready.set(0.0)
        if background:
            thread = threading.Thread(
                target=self._warm_buckets, args=(arrays, buckets, handle),
                name=f"{self.name}-warmup", daemon=True,
            )
            handle._threads.append(thread)
            thread.start()
            return handle
        self._warm_buckets(arrays, buckets, handle)
        return handle.wait()

    def _warm_buckets(self, arrays: List[np.ndarray], buckets: List[int],
                      handle: WarmupHandle) -> None:
        """Warm ``buckets`` for one signature, smallest first; finishes (or
        fails) ``handle`` and publishes readiness + duration gauges."""
        import jax

        t0 = time.monotonic()
        key = self._key([a[:1] for a in arrays])
        warmed: List[int] = []
        try:
            for b in buckets:
                if self._crash is not None:
                    # a crashed engine must FAIL the warmup, not report a
                    # truncated bucket list as success (blocking callers
                    # treat the return as 'warm')
                    raise self._closed_error("warmup()")
                if handle.cancelled():
                    break
                cols = tuple(
                    self._cast(np.ascontiguousarray(
                        np.broadcast_to(a[:1], (b, *a.shape[1:]))
                    ))
                    for a in arrays
                )
                out = self._execute(cols, b, key)
                jax.block_until_ready(out)
                warmed.append(b)
        except BaseException as e:
            self._m_warmup_s.set(time.monotonic() - t0)
            obs.event("serving_warmup_failed", engine=self.name,
                      error=type(e).__name__, warmed=warmed)
            handle._fail(e)
            return
        elapsed = time.monotonic() - t0
        self._m_warmup_s.set(elapsed)
        if warmed == buckets:
            self._m_ready.set(1.0)
        obs.event("serving_warmup", engine=self.name, buckets=warmed,
                  seconds=round(elapsed, 3),
                  cached=self._cache is not None)
        handle._finish(warmed)

    # -- worker --------------------------------------------------------------

    def _run(self) -> None:
        inflight: deque = deque()  # ((device_out, bucket), parts)

        def _sync_inflight() -> None:
            # watchdog window close: the trace must not stop while dispatches
            # are still executing — truncated trailing step windows would
            # bias the lower-quartile device number low
            import jax

            for (out, _bucket), _parts in list(inflight):
                jax.block_until_ready(out)

        def _note_inflight() -> None:
            self._inflight_count = len(inflight)
            self._m_inflight.set(len(inflight))
            if inflight:
                self.heartbeat.arm()
            else:
                self.heartbeat.disarm()

        try:
            while True:
                self._install_pending_params()
                parts = None
                if len(inflight) < self.max_inflight:
                    # while dispatches are in flight this poll is
                    # non-blocking: the device working IS the micro-batching
                    # window
                    parts = self._next_batch(0.0 if inflight else _IDLE_POLL_S)
                if parts is not None:
                    with self._stats_lock:
                        self._backlog -= len(parts)
                        self._assembling += len(parts)
                    try:
                        # assembly-side deadline enforcement: a part whose
                        # caller already gave up must not burn a dispatch
                        live = self._shed_expired(parts)
                        if live:
                            # armed BEFORE the dispatch call: a wedged device
                            # can hang the dispatch itself, not just the
                            # completion
                            self.heartbeat.arm()
                            try:
                                inflight.append((self._dispatch(live), live))
                            except BaseException as e:  # bad batch
                                self._batch_failed(live, e, where="dispatch")
                            _note_inflight()
                    finally:
                        # only AFTER the parts are accounted elsewhere
                        # (in-flight, shed, failed, or re-queued) — a
                        # concurrent drain() poll never sees a false-empty
                        # window mid-assembly
                        with self._stats_lock:
                            self._assembling -= len(parts)
                    if live and self._profiler is not None:
                        self._profiler.tick(sync=_sync_inflight)
                    continue
                if inflight:
                    self._complete(*inflight.popleft())
                    self.heartbeat.beat()
                    _note_inflight()
                    continue
                # idle (nothing in flight, nothing sealed): any spooled
                # traced span rows land now rather than waiting out the
                # next saturated stretch — and before worker exit below
                self._flush_trace_spool()
                if (self._stop.is_set() and self._queue.empty()
                        and not self._pending):
                    return
        except BaseException as e:
            # the worker must never die with futures outstanding — a caller
            # blocked in result() with no timeout would hang forever. Fail
            # everything queued/pending/in flight, record the cause (so
            # submit() raises EngineClosed chained from it), stop accepting.
            self._crash = e
            self._stop.set()
            self.heartbeat.disarm()
            try:
                # completed work's spans are valid telemetry even when the
                # worker dies — land them (best effort) before failing out
                self._flush_trace_spool()
            except Exception:
                pass
            obs.event("engine_worker_crash", engine=self.name,
                      error=type(e).__name__)
            for _, parts in inflight:
                for p in parts:
                    p.future._fail(e)
            for dq in self._pending.values():
                for p in dq:
                    p.future._fail(e)
            self._pending.clear()
            while True:
                try:
                    self._queue.get_nowait().future._fail(e)
                except queue.Empty:
                    break
            with self._stats_lock:
                self._backlog = 0
                self._assembling = 0
            raise

    def _flush_trace_spool(self) -> None:
        """Worker-only: land the spooled traced span rows as one
        ``request_phases_batch`` record — ``parts`` is the ";"-joined
        packed rows (the assembler expands each back into an engine span
        + six phase children)."""
        if self._trace_spool:
            rows, self._trace_spool = self._trace_spool, []
            obs.event("request_phases_batch", engine=self.name,
                      parts=";".join(rows))

    def _shed_expired(self, parts: List[_Part]) -> List[_Part]:
        """Worker-only: drop parts whose deadline passed; their futures fail
        with :class:`DeadlineExceeded` (a terminal result — the caller's
        ``result(timeout=)`` has almost certainly given up already, and the
        part must not occupy a dispatch)."""
        now = time.monotonic()
        alive = []
        for p in parts:
            if p.deadline is not None and now >= p.deadline:
                self._m_shed["deadline"].inc()
                self._slo_bad()
                obs.event("engine_request_shed", engine=self.name,
                          reason="deadline",
                          waited_s=round(now - p.t_submit, 4))
                p.future._fail(DeadlineExceeded(
                    f"request deadline expired before dispatch "
                    f"(waited {now - p.t_submit:.3f}s in engine "
                    f"{self.name!r})"
                ))
            else:
                alive.append(p)
        return alive

    def _batch_failed(self, parts: List[_Part], error: BaseException,
                      where: str) -> None:
        """Worker-only: a micro-batch dispatch (or its completion fetch)
        raised. Transient errors re-queue the parts — with backoff, at the
        front of their key's line — up to the retry budget, so one flaky
        dispatch no longer fails every rider's future; fatal errors (and
        exhausted budgets) fail the futures and feed the breaker."""
        if self.breaker is not None:
            self.breaker.record_failure(error)
        policy = self._retry_policy
        retries = parts[0].retries
        if (retries < policy.max_retries and is_transient(error)
                and not self._stop.is_set()):
            for p in parts:
                p.retries += 1
            self._m_retries.inc()
            with self._stats_lock:
                self._backlog += len(parts)  # back into the admission count
            pause = policy.backoff_s(retries + 1)
            obs.event("engine_dispatch_retry", engine=self.name, where=where,
                      error=type(error).__name__, retry=retries + 1,
                      backoff_s=round(pause, 4))
            if pause > 0:
                self._stop.wait(pause)
            # front of the key's deque: retried work keeps its place in line
            self._pending.setdefault(parts[0].key, deque()).extendleft(
                reversed(parts)
            )
            return
        obs.event("engine_batch_failed", engine=self.name, where=where,
                  error=type(error).__name__, retries=retries)
        self._slo_bad(len(parts))
        for p in parts:
            p.future._fail(error)

    def _absorb(self, part: _Part) -> None:
        self._pending.setdefault(part.key, deque()).append(part)

    def _rows_pending(self, key) -> int:
        return sum(p.n for p in self._pending.get(key, ()))

    def _next_batch(self, timeout: float) -> Optional[List[_Part]]:
        """Collect the next micro-batch: drain the queue into per-key pending
        lists, wait up to ``max_delay`` for the oldest key to fill (skipped
        when 0 — pure continuous batching), then seal whole parts of the
        oldest key up to ``max_batch`` rows."""
        if not self._pending:
            try:
                self._absorb(self._queue.get(timeout=timeout))
            except queue.Empty:
                return None
        deadline = time.monotonic() + self.max_delay
        while True:
            try:
                while True:  # non-blocking drain of everything queued now
                    self._absorb(self._queue.get_nowait())
            except queue.Empty:
                pass
            key = next(iter(self._pending))
            if self._rows_pending(key) >= self.max_batch:
                break
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                self._absorb(self._queue.get(timeout=remaining))
            except queue.Empty:
                break
        key = next(iter(self._pending))
        dq = self._pending[key]
        parts, total = [], 0
        while dq and total + dq[0].n <= self.max_batch:
            part = dq.popleft()
            parts.append(part)
            total += part.n
        if not dq:
            del self._pending[key]
        else:
            # round-robin across keys: a key with work left over goes to the
            # BACK of the dict order, so sustained load on one program key
            # cannot starve requests queued under another
            self._pending[key] = self._pending.pop(key)
        return parts

    def _cast(self, a: np.ndarray):
        if self._compute_dtype is not None and np.issubdtype(
            a.dtype, np.floating
        ):
            return a.astype(self._compute_dtype)
        return a

    def _execute(self, cols: Tuple[np.ndarray, ...], bucket: int, key):
        import jax

        program = (key, bucket)
        with self._stats_lock:  # warmup (caller thread) races the worker
            is_new = program not in self._programs
            if is_new:
                self._programs.add(program)
            self._dispatch_seq += 1
            step_num = self._dispatch_seq
        if is_new:
            self._m_compiles.inc()
            self._m_programs.set(len(self._programs))
            obs.event("serving_compile", engine=self.name, bucket=bucket,
                      programs=len(self._programs))
        fn = (
            self._jitted if self._cache is None
            else self._aot_program(program, cols)
        )
        with jax.profiler.StepTraceAnnotation(
            self.name, step_num=step_num
        ):
            try:
                return fn(self.params, cols)
            except ValueError as e:
                # an update_params() that changed the param PLACEMENT (not
                # the avals — those are validated) invalidates an AOT
                # executable lowered for the old shardings: rebuild against
                # the current placement (new fingerprint → correct entry)
                if (self._cache is None
                        or "Compiled object called with input" not in str(e)):
                    raise
                with self._aot_lock:
                    self._aot_programs.pop(program, None)
                return self._aot_program(program, cols)(self.params, cols)

    # -- AOT program cache (perceiver_io_tpu.aot) ----------------------------

    def _aot_program(self, program, cols: Tuple[np.ndarray, ...]):
        """The compiled executable for ``program`` — from memory, the disk
        cache, or a fresh compile (which is then persisted). Concurrent
        requests for the same program (background warmup vs the worker's
        on-demand path, or two warmup threads) build it ONCE: the first
        caller claims the build, the rest wait on its event."""
        while True:
            with self._aot_lock:
                compiled = self._aot_programs.get(program)
                if compiled is not None:
                    return compiled
                claim = self._aot_claims.get(program)
                if claim is None:
                    claim = threading.Event()
                    self._aot_claims[program] = claim
                    break  # this thread owns the build
            claim.wait()  # owner finished (or failed) — re-check / re-claim
        try:
            compiled = self._build_aot_program(cols)
            with self._aot_lock:
                self._aot_programs[program] = compiled
            return compiled
        finally:
            # on failure the claim is simply released: the error propagates
            # to this caller, and waiters re-claim (retrying the build)
            with self._aot_lock:
                self._aot_claims.pop(program, None)
            claim.set()

    def _build_aot_program(self, cols: Tuple[np.ndarray, ...]):
        import jax

        def sds(x):
            # committed params (e.g. NamedSharding from a mesh-restored
            # checkpoint) must compile AND fingerprint with their placement:
            # a Compiled object rejects inputs whose sharding differs from
            # what it was lowered for
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)
            )

        avals = jax.tree.map(sds, (self.params, tuple(cols)))
        fp = aot_fingerprint(self._fingerprint_base(), avals=avals,
                             extra=self._fp_sources)
        compiled = self._cache.load(fp)
        if compiled is None:
            compiled = self._jitted.lower(*avals).compile()
            self._cache.store(fp, compiled)
        return compiled

    def _fingerprint_base(self) -> Dict[str, Any]:
        """Static (per-engine) half of every program fingerprint; computed
        once, after the backend is up."""
        if self._fp_base is None:
            base = dict(environment_fingerprint())
            base.update(
                donate=self.donate_inputs,
                quantize=str(self.quantize),
                group_size=str(self.group_size),
                compute_dtype=str(self._compute_dtype),
                salt=self._cache_salt,
            )
            # apply_fn identity: source text + closure reprs (model
            # hyperparameters ride the flax module repr)
            self._fp_sources = tuple(callable_sources(self._apply_fn))
            self._fp_base = base
        return self._fp_base

    def _dispatch(self, parts: List[_Part]):
        t_sealed = time.monotonic()  # the micro-batch is decided: queue ends
        for p in parts:
            p.t_sealed = t_sealed
        faults.inject("engine.dispatch")  # chaos hook: no-op unless installed
        # per-engine site: multi-replica chaos drills target ONE replica's
        # dispatch path (`engine.dispatch.<name>`) without perturbing the
        # generic site's call counts
        faults.inject(f"engine.dispatch.{self.name}")
        n = sum(p.n for p in parts)
        bucket = bucket_size(n, self.max_batch)
        num_inputs = len(parts[0].inputs)
        cols = []
        for i in range(num_inputs):
            col = (
                parts[0].inputs[i] if len(parts) == 1
                else np.concatenate([p.inputs[i] for p in parts], axis=0)
            )
            if bucket > n:  # padding repeats row 0; sliced off at completion
                col = np.concatenate(
                    [col, np.broadcast_to(col[:1], (bucket - n, *col.shape[1:]))],
                    axis=0,
                )
            cols.append(self._cast(np.ascontiguousarray(col)))
        now = time.monotonic()
        for p in parts:
            self._m_wait.observe(now - p.t_submit)
            p.t_built = now
        out = self._execute(tuple(cols), bucket, parts[0].key)
        t_sent = time.monotonic()
        for p in parts:
            p.t_sent = t_sent
        with self._stats_lock:
            self._stats["batches"] += 1
            self._stats["rows"] += n
            self._stats["padded_rows"] += bucket - n
        self._m_batches.inc()
        self._m_rows.inc(n)
        self._m_padded.inc(bucket - n)
        self._m_occupancy.observe(n / bucket)
        self._m_queue.set(self._queue.qsize())
        return out, bucket

    def _latency_hist(self, bucket: int) -> obs.Histogram:
        hist = self._latency_hists.get(bucket)
        if hist is None:
            hist = self.registry.histogram(
                "serving_latency_seconds",
                "submit → result latency by batch bucket",
                {"engine": self.name, "bucket": str(bucket)},
            )
            self._latency_hists[bucket] = hist
        return hist

    def _complete(self, out_bucket, parts: List[_Part]) -> None:
        import jax

        out, bucket = out_bucket
        try:
            faults.inject("engine.complete")  # chaos hook
            faults.inject(f"engine.complete.{self.name}")  # per-engine site
            host = jax.tree.map(np.asarray, jax.device_get(out))
        except BaseException as e:
            self._batch_failed(parts, e, where="complete")
            return
        if self.breaker is not None:
            self.breaker.record_success()
        t_fetched = time.monotonic()  # device phase ends: outputs on host
        hist = self._latency_hist(bucket)
        emit_spans = obs.get_event_log() is not None
        latencies, phase_rows = [], []
        offset = 0
        for p in parts:
            now = time.monotonic()
            # consecutive diffs over the part's timestamps: the phases
            # PARTITION [t_entry, now], so their sum reconciles with the
            # end-to-end latency by construction (self-check below; the sum
            # exceeds e2e by exactly the admission phase, since the latency
            # metric's clock starts at enqueue)
            phases = {
                "admission": p.t_submit - p.t_entry,
                "queue": p.t_sealed - p.t_submit,
                "assembly": p.t_built - p.t_sealed,
                "dispatch": p.t_sent - p.t_built,
                "device": t_fetched - p.t_sent,
                "complete": now - t_fetched,
            }
            e2e = now - p.t_submit
            self._span_seq += 1
            trace = p.future.trace
            traced = trace is not None and trace.sampled
            # an exemplar per 4 observations is plenty of linkage (the
            # ring keeps 8) and keeps the attach off most completions;
            # the SAME trace id lands on the latency histogram and every
            # phase histogram, so a phase-level alert ("p99 queue time is
            # burning") links to the identical assembled trace
            exemplar = (trace.trace_id
                        if traced and self._span_seq & 3 == 0 else None)
            for k, v in phases.items():
                self._m_phase[k].observe(v, exemplar=exemplar)
            if e2e > 0:
                self._m_phase_ratio.set(sum(phases.values()) / e2e)
            # record BEFORE delivering: result() waking the caller is the
            # publication point — a caller reading fut.phases right after
            # result() must find this part's record already there
            p.future._note_phases(phases)
            if self.slo_tracker is not None:
                self.slo_tracker.record(latency_s=e2e, ok=True)
            if emit_spans and traced:
                # each part is one engine span: fresh id under the
                # propagated context, so the assembler hangs the six
                # phases (synthesized children) off the right hop. The
                # row is a PACKED string (comma-separated, integer
                # microseconds, PHASES order — phases is built in that
                # order): the flushed record then carries one long string
                # the writer's json.dumps only escape-scans, instead of
                # ~12 values x 64 rows it would format element-wise. This
                # plus the spool is what keeps full tracing inside the
                # <=2% overhead bar (PERF.md §Tracing)
                ph_a, ph_q, ph_as, ph_d, ph_dev, ph_c = phases.values()
                self._trace_spool.append(
                    f"{trace.trace_id},{obs.new_span_id()},"
                    f"{trace.span_id},{int(p.t_entry * 1e6)},{p.n},"
                    f"{int(ph_a * 1e6)},{int(ph_q * 1e6)},"
                    f"{int(ph_as * 1e6)},{int(ph_d * 1e6)},"
                    f"{int(ph_dev * 1e6)},{int(ph_c * 1e6)},{bucket}"
                )
            elif emit_spans and self._span_seq % self._span_every == 0:
                obs.event("request_phases", engine=self.name, bucket=bucket,
                          rows=p.n, total_s=round(e2e, 6),
                          **{k: round(v, 6) for k, v in phases.items()})
            latencies.append(e2e)
            hist.observe(e2e, exemplar=exemplar)
            phase_rows.append(phases)
            o = offset
            p.future._deliver(
                p.index, jax.tree.map(lambda a: a[o: o + p.n], host)
            )
            offset += p.n
        if len(self._trace_spool) >= _TRACE_SPOOL_ROWS:
            self._flush_trace_spool()
        with self._stats_lock:
            # bounded: an engine serves indefinitely — unbounded per-request
            # float lists would grow without limit; the window is plenty for
            # p50/p95 reporting. Phase rows land under the SAME lock (and in
            # the same order) as the latencies they attribute, so stats()
            # reads a consistent latency+attribution pair.
            lat = self._stats["latency_s_by_bucket"].setdefault(
                bucket, deque(maxlen=4096)
            )
            lat.extend(latencies)
            ph = self._stats["phase_s"]
            for row in phase_rows:
                for k, v in row.items():
                    ph.setdefault(k, deque(maxlen=4096)).append(v)

    # -- replica-facing surface (perceiver_io_tpu.serving) -------------------
    #
    # The router tier consumes exactly this contract from every replica:
    # submit()/predict() for traffic, update_params() for rolling rollout,
    # `ready` for join gating, drain()/resume_admission() for graceful
    # rotation, stats()/the registry gauges for load-aware dispatch.

    @property
    def ready(self) -> bool:
        """True once the last requested warmup family is fully warm (the
        ``engine_ready`` gauge) — what a router's join gate polls before
        admitting a (re)started replica."""
        return self._m_ready.value >= 1.0

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    @property
    def backlog(self) -> int:
        """Parts admitted but not yet dispatched/shed — the queue-depth term
        of a router's least-loaded score."""
        with self._stats_lock:
            return self._backlog

    @property
    def inflight(self) -> int:
        """Micro-batches currently dispatched (racy read, diagnostics-grade)."""
        return self._inflight_count

    @property
    def params_pending(self) -> bool:
        """True while a staged ``update_params`` tree awaits the worker's
        between-batches install (the replica shim's swap RPC answers only
        once this clears, so a rollout's bake window never watches a
        replica that is still serving the OLD tree)."""
        with self._params_lock:
            return self._pending_params is not None

    @property
    def requests_served(self) -> int:
        """Requests admitted over this engine's lifetime (the rollout bake's
        did-traffic-actually-flow check)."""
        with self._stats_lock:
            return self._stats["requests"]

    def drain(self, timeout: Optional[float] = None,
              poll_s: float = 0.01) -> bool:
        """Graceful drain: stop admitting, finish everything already accepted.

        New ``submit()`` calls fail fast with :class:`RejectedError`
        immediately; queued parts and in-flight micro-batches complete
        normally (accepted work is never dropped). Returns True once nothing
        admitted remains un-served, False if ``timeout`` elapsed first (work
        is still in flight — the engine stays draining either way). The
        engine itself stays alive: ``resume_admission()`` re-opens it (the
        rolling-rollout path drains, swaps params, resumes), ``close()``
        detaches it.
        """
        self._draining.set()
        obs.event("engine_drain_begin", engine=self.name)
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._stats_lock:
                backlog = self._backlog + self._assembling
            if (backlog == 0 and self._inflight_count == 0
                    and self._queue.empty()):
                obs.event("engine_drained", engine=self.name)
                return True
            if self._stop.is_set():
                # a closing/crashed engine cannot finish the work; the
                # worker's own shutdown/crash path fails the futures
                return False
            if deadline is not None and time.monotonic() >= deadline:
                obs.event("engine_drain_timeout", engine=self.name,
                          backlog=backlog, inflight=self._inflight_count)
                return False
            time.sleep(poll_s)

    def stop_admission(self) -> None:
        """Close admission without waiting (``drain()`` = this + the wait).
        Multi-engine callers close EVERY door first so a composite request
        can never slip in behind an already-drained sibling — see
        :func:`drain_engines`."""
        self._draining.set()

    def resume_admission(self) -> None:
        """Re-open a drained engine for traffic (the rollout undrain)."""
        self._draining.clear()
        obs.event("engine_drain_end", engine=self.name)

    # -- introspection / lifecycle -------------------------------------------

    @property
    def num_programs(self) -> int:
        """Distinct (signature, batch-bucket) programs dispatched or warmed."""
        return len(self._programs)

    def stats(self) -> Dict[str, Any]:
        """Locked, deep-copied snapshot of this instance's counters.

        The compatibility surface over the registry instruments (which
        aggregate across engines sharing a name): mutating the returned dict
        or its latency lists never touches live state, and the read is
        consistent (taken under the same lock every writer holds).
        """
        with self._stats_lock:
            snap: Dict[str, Any] = {
                k: v for k, v in self._stats.items()
                if k not in ("latency_s_by_bucket", "phase_s")
            }
            snap["latency_s_by_bucket"] = {
                b: list(d)
                for b, d in self._stats["latency_s_by_bucket"].items()
            }
            # same locked deep-copy as the latencies: external pollers (the
            # future router tier) never read torn phase attribution
            snap["phase_s"] = {
                k: list(d) for k, d in self._stats["phase_s"].items()
            }
        return snap

    def _diagnostics(self) -> Dict[str, Any]:
        """Heartbeat-stall snapshot: queue/in-flight state + last-known
        counters (runs on the monitor thread — reads are racy by design;
        a wedged worker cannot be asked to cooperate)."""
        snap = self.stats()
        snap.pop("latency_s_by_bucket", None)
        snap.pop("phase_s", None)
        with self._stats_lock:
            backlog = self._backlog
        return {
            "queue_parts": self._queue.qsize(),
            "pending_keys": len(self._pending),
            "inflight": self._inflight_count,
            "backlog_parts": backlog,
            "breaker": (self.breaker.state if self.breaker is not None
                        else "absent"),
            "programs": len(self._programs),
            "warming": any(not h.done() for h in self._warmup_handles),
            "stats": snap,
        }

    def close(self, timeout: Optional[float] = None) -> None:
        """Stop accepting requests, drain everything queued, join the worker."""
        # EVERY background warmup stops at its next bucket boundary, and we
        # WAIT for the threads to exit (bounded): a leftover warmup compile
        # racing whatever the process runs next corrupts the jax runtime.
        # A build wedged past the bound is abandoned (daemon thread) rather
        # than hanging close().
        for h in self._warmup_handles:
            h.cancel()
        for h in self._warmup_handles:
            h.join(timeout if timeout is not None else 60.0)
        self._stop.set()
        self._thread.join(timeout)
        self.heartbeat.close()
        if self.breaker is not None:
            self.breaker.close()
        if self.slo_tracker is not None:
            self.slo_tracker.close()
        if self._profiler is not None:
            self._profiler.close()
        # a submit() racing close() can slip a part in after the worker
        # exits — fail it rather than leave its future hanging
        while True:
            try:
                self._queue.get_nowait().future._fail(
                    EngineClosed("engine closed before this request ran")
                )
            except queue.Empty:
                break

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def drain_engines(engines, timeout: Optional[float] = None) -> bool:
    """Drain several engines as ONE unit: close every door first (a
    composite request — e.g. an MLM fill that rides encoder AND decoder —
    can never slip in behind an already-drained sibling), then wait on each
    under one shared deadline. Returns True only when every engine drained
    in time. The callers: :meth:`MLMServer.drain` and the replica shim's
    ``ReplicaApp.drain``."""
    engines = list(engines)
    for eng in engines:
        eng.stop_admission()
    deadline = None if timeout is None else time.monotonic() + timeout
    ok = True
    for eng in engines:
        left = (None if deadline is None
                else max(0.0, deadline - time.monotonic()))
        ok = eng.drain(timeout=left) and ok
    return ok


def mlm_apply_fns(model) -> Dict[str, Callable]:
    """The three serving program families over one ``PerceiverMLM`` — the
    fused single-pass path plus the encode/decode latent-cache split — as
    plain ``apply_fn(params, *arrays)`` callables, keyed by the RPC verb the
    replica shim serves them under (``infer``/``encode``/``decode``).

    ONE definition shared by :class:`MLMServer` (in-process serving) and
    ``perceiver_io_tpu.serving.replica`` (a replica process hosting the same
    engines behind the router tier), so the two surfaces can never drift."""

    def fused_apply(p, token_ids, pad_mask, positions):
        logits, _ = model.apply(
            {"params": p}, token_ids, pad_mask, masking=False,
            deterministic=True, positions=positions,
        )
        return logits

    def encode_apply(p, token_ids, pad_mask):
        return model.apply(
            {"params": p}, token_ids, pad_mask, deterministic=True,
            method="encode",
        )

    def decode_apply(p, latents, positions):
        return model.apply(
            {"params": p}, latents, deterministic=True,
            positions=positions, method="decode",
        )

    return {"infer": fused_apply, "encode": encode_apply,
            "decode": decode_apply}


class CachedLatents:
    """Result of :meth:`MLMServer.encode`: the latent arrays plus the
    request-side bookkeeping needed to decode against them later."""

    __slots__ = ("latents", "token_ids", "mask_positions")

    def __init__(self, latents: np.ndarray, token_ids: List[np.ndarray],
                 mask_positions: List[np.ndarray]):
        self.latents = latents          # (B, N, C) — width-independent
        self.token_ids = token_ids      # per row, at its serving width
        self.mask_positions = mask_positions  # per row, [MASK] indices

    def __len__(self) -> int:
        return self.latents.shape[0]


class MLMServer:
    """Text serving frontend over a ``PerceiverMLM``: tokenize → width-bucket
    → micro-batching engine; fill-mask via the gathered decode, plus the
    encode-once/decode-many latent cache.

    ``bucket_widths``: serving sequence-width buckets (the training
    collator's rule, ``resolve_bucket_width``); None = always ``max_seq_len``.
    Each (width, batch-bucket, K-bucket) is one program — ``warmup()``
    compiles them all so steady state never compiles.

    ``quantize='int8'`` (or ``compute_dtype='int8w'``): weight-only int8
    serving — the checkpoint's f32 params are quantized ONCE here and the
    single ``QuantizedParams`` copy is shared by all three engines, exactly
    like the bf16 path shares its one cast copy.
    """

    def __init__(
        self,
        model,
        params,
        tokenizer,
        max_seq_len: int,
        bucket_widths: Optional[Sequence[int]] = None,
        max_batch: int = 64,
        max_delay_ms: float = 0.0,
        max_inflight: int = 2,
        compute_dtype: Optional[str] = None,
        quantize: Optional[str] = None,
        group_size: Optional[int] = None,
        registry: Optional[obs.MetricsRegistry] = None,
        heartbeat_deadline_s: Optional[float] = None,
        selfprofile_every: int = 0,
        request_deadline_s: Optional[float] = None,
        queue_limit: Optional[int] = None,
        dispatch_retries: int = 2,
        breaker_failures: int = 0,
        breaker_cooldown_s: float = 5.0,
        compile_cache=None,
        slo: Optional[obs.SLO] = None,
        span_every: int = 1,
        trace_sample: float = 1.0,
    ):
        import jax

        from perceiver_io_tpu.data.tokenizer import MASK_TOKEN

        self.model = model
        self.tokenizer = tokenizer
        self.max_seq_len = max_seq_len
        self.mask_id = tokenizer.token_to_id(MASK_TOKEN)
        if bucket_widths:
            widths = sorted({int(w) for w in bucket_widths})
            if widths[0] <= 0 or widths[-1] > max_seq_len:
                raise ValueError(
                    f"bucket_widths must lie in [1, max_seq_len={max_seq_len}],"
                    f" got {widths}"
                )
            if widths[-1] != max_seq_len:
                widths.append(max_seq_len)
            self.widths: List[int] = widths
        else:
            self.widths = [max_seq_len]

        # ONE device-resident (optionally bf16-cast or int8-quantized) param
        # copy shared by all three programs — the engines receive committed
        # arrays and their device_put is a no-op (same mode resolution and
        # preparation as the engines themselves: resolve_params_mode /
        # prepare_param_tree, so server and engine can never drift)
        compute_dtype, quantize = resolve_params_mode(compute_dtype, quantize)
        self._compute_dtype, self._quantize = compute_dtype, quantize
        self._group_size = group_size
        self._update_lock = threading.Lock()
        self._warmup_handles: List[WarmupHandle] = []
        params = jax.device_put(
            prepare_param_tree(params, compute_dtype, quantize, group_size)
        )

        apply_fns = mlm_apply_fns(model)

        common = dict(
            max_batch=max_batch, max_delay_ms=max_delay_ms,
            max_inflight=max_inflight, compute_dtype=compute_dtype,
            registry=registry, heartbeat_deadline_s=heartbeat_deadline_s,
            selfprofile_every=selfprofile_every,
            # resilience knobs: per-engine breakers (labeled by engine name)
            # over the shared device, shared deadline/shed/retry policy
            request_deadline_s=request_deadline_s, queue_limit=queue_limit,
            dispatch_retries=dispatch_retries,
            breaker_failures=breaker_failures,
            breaker_cooldown_s=breaker_cooldown_s,
            # one SLO spec, one tracker per engine (labeled by engine name):
            # the fused path's burn rate and the latent-cache halves' stay
            # separately attributable on /statz and healthz()
            slo=slo,
            span_every=span_every,
            trace_sample=trace_sample,
            # ONE ExecutableCache (resolved here so a fail-soft warning
            # prints once, not three times) shared by all three program
            # families; their fingerprints differ by apply-fn source/avals
            compile_cache=resolve_cache(compile_cache, registry=registry),
        )
        # fused single-pass path (one-shot requests) + the split pair
        # (latent-cache workloads); each engine owns one program family
        self.engine = ServingEngine(
            apply_fns["infer"], params, name="mlm", **common
        )
        self.encoder = ServingEngine(
            apply_fns["encode"], params, name="mlm_enc", **common
        )
        self.decoder = ServingEngine(
            apply_fns["decode"], params, name="mlm_dec", **common
        )

        # latent-cache accounting: a "hit" is a fill-mask answered from
        # cached latents (no encoder work), a "miss" is the fused path
        reg = registry if registry is not None else obs.get_registry()
        self._m_fused = reg.counter(
            "mlm_fill_mask_requests_total", "fill-mask requests by path",
            {"path": "fused"})
        self._m_cached = reg.counter(
            "mlm_fill_mask_requests_total", "fill-mask requests by path",
            {"path": "cached"})
        self._m_encoded = reg.counter(
            "mlm_cache_encodes_total", "texts encoded into the latent cache")
        self._m_hit_rate = reg.gauge(
            "mlm_latent_cache_hit_rate",
            "cached fill-masks / all fill-masks (encode-once pay-off)")

    def _note_fill(self, cached: bool) -> None:
        (self._m_cached if cached else self._m_fused).inc()
        total = self._m_cached.value + self._m_fused.value
        if total:
            self._m_hit_rate.set(self._m_cached.value / total)

    # -- request preparation -------------------------------------------------

    def _prepare(self, text: str) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Tokenize one text (ONCE, at natural length) and pad to its serving
        width bucket; returns ``(token_ids (1, W), pad_mask (1, W),
        mask_positions)``."""
        from perceiver_io_tpu.data.pipeline import resolve_bucket_width
        from perceiver_io_tpu.inference.mlm import (
            masked_token_ids,
            pad_token_rows,
        )

        row = masked_token_ids(self.tokenizer, text)
        width = resolve_bucket_width(len(row), self.widths)
        ids, pad = pad_token_rows([row], width, self._pad_id())
        return ids, pad, np.nonzero(ids[0] == self.mask_id)[0]

    def _pad_id(self) -> int:
        from perceiver_io_tpu.data.tokenizer import PAD_TOKEN

        return self.tokenizer.token_to_id(PAD_TOKEN)

    def _positions_row(self, mask_pos: np.ndarray, width: int) -> np.ndarray:
        """(1, K-bucket) positions row; filler slots repeat position 0 (their
        logits are never read). K buckets are powers of two so same-K
        requests share a program."""
        kb = bucket_size(max(len(mask_pos), 1), width)
        row = np.zeros((1, kb), np.int32)
        row[0, : len(mask_pos)] = mask_pos
        return row

    def _topk_transform(self, n_masks: int, k: int):
        def transform(logits: np.ndarray) -> List[List[str]]:
            out = []
            for slot in range(n_masks):
                top = np.argsort(-np.asarray(logits[0, slot], np.float32))[:k]
                out.append([self.tokenizer.id_to_token(int(t)) for t in top])
            return out

        return transform

    # -- one-shot fill-mask (fused path) -------------------------------------

    def submit(self, text: str, k: int = 5) -> _Future:
        """Enqueue one fill-mask request; ``result()`` is the per-``[MASK]``
        top-k token lists (``MLMPredictor.fill_masks`` row semantics)."""
        self._note_fill(cached=False)
        ids, pad, mask_pos = self._prepare(text)
        if len(mask_pos) == 0:  # nothing to decode: complete without device
            fut = _Future(1, None)
            fut._deliver(0, [])
            return fut
        positions = self._positions_row(mask_pos, ids.shape[1])
        return self.engine.submit(
            ids, pad, positions,
            transform=self._topk_transform(len(mask_pos), k),
        )

    def fill_masks(self, texts: Sequence[str], k: int = 5) -> List[List[List[str]]]:
        """Batch-synchronous fill-mask: submit everything, then collect —
        the engine micro-batches the whole set."""
        futures = [self.submit(t, k) for t in texts]
        return [f.result() for f in futures]

    # -- latent cache: encode once, decode many ------------------------------

    def encode(self, texts: Sequence[str]) -> CachedLatents:
        """Run the encoder half once per text (width-bucketed, micro-batched)
        and cache the latents; the O(L) work never repeats across decodes."""
        prepared = [self._prepare(t) for t in texts]
        self._m_encoded.inc(len(prepared))
        futures = [
            self.encoder.submit(ids, pad) for ids, pad, _ in prepared
        ]
        latents = np.concatenate([f.result() for f in futures], axis=0)
        return CachedLatents(
            latents,
            [ids[0] for ids, _, _ in prepared],
            [pos for _, _, pos in prepared],
        )

    def decode(self, cached: CachedLatents, positions: np.ndarray) -> np.ndarray:
        """Decode explicit (B, K) query ``positions`` against cached latents:
        (B, K, vocab) logits. B must match ``len(cached)``."""
        positions = np.asarray(positions, np.int32)
        if positions.shape[0] != len(cached):
            raise ValueError(
                f"positions rows {positions.shape[0]} != cached batch "
                f"{len(cached)}"
            )
        return self.decoder.predict(cached.latents, positions)

    def fill_masks_cached(self, cached: CachedLatents,
                          k: int = 5) -> List[List[List[str]]]:
        """Fill-mask from cached latents only — the decode-many half of the
        cache: each row decodes its own ``[MASK]`` positions (K-bucketed), no
        encoder work at all."""
        futures = []
        for row in range(len(cached)):
            self._note_fill(cached=True)
            mask_pos = cached.mask_positions[row]
            if len(mask_pos) == 0:
                fut = _Future(1, None)
                fut._deliver(0, [])
                futures.append(fut)
                continue
            positions = self._positions_row(mask_pos, self.max_seq_len)
            futures.append(self.decoder.submit(
                cached.latents[row: row + 1], positions,
                transform=self._topk_transform(len(mask_pos), k),
            ))
        return [f.result() for f in futures]

    # -- lifecycle -----------------------------------------------------------

    def update_params(self, params) -> None:
        """Hot-swap the served model across ALL THREE engines from ONE
        prepared tree (cast/quantized once under the server's mode — not
        three times), staged on each engine in the same call so the
        cross-engine mismatch window is one worker-loop iteration, not three
        independent re-preparations.

        Caveat for latent-cache users: ``CachedLatents`` obtained before the
        swap were encoded by the OLD weights — decoding them against the new
        decoder mixes models. Re-``encode()`` after an update.

        Concurrent server-level updates are serialized (one lock around
        prepare + the three stagings): without it, two racing calls could
        interleave their per-engine stagings and permanently install
        DIFFERENT versions on the fused/encoder/decoder paths.
        """
        import jax

        with self._update_lock:
            prepared = jax.device_put(
                prepare_param_tree(params, self._compute_dtype,
                                   self._quantize, self._group_size)
            )
            for eng in (self.engine, self.encoder, self.decoder):
                eng.update_params(prepared)

    def warmup(self, batch_buckets: Optional[Sequence[int]] = None,
               query_buckets: Sequence[int] = (1, 2, 4),
               background: bool = False):
        """Ready the serving programs ahead of traffic: every width bucket ×
        batch bucket (× K bucket for the decode paths), cache-first when a
        ``compile_cache`` is attached. The three program families (fused /
        encoder / decoder) warm CONCURRENTLY on their own threads, each in
        priority order (smallest width and bucket first).

        Blocking (default): returns the number of programs warmed — after
        this, steady-state serving never compiles (the compile-count test
        pins it). ``background=True`` returns a :class:`WarmupHandle`
        immediately; requests may be submitted right away and are answered
        as soon as their program is ready (not-yet-warm programs build on
        demand, deduped against the warmup threads in cache mode).
        """
        handle = WarmupHandle()
        self._warmup_handles = [
            h for h in self._warmup_handles if not h.done()
        ] + [handle]
        counts = [0, 0, 0]
        errors: List[BaseException] = []

        def example(width: int):
            # pad NOTHING in the warmup example: a fully-padded row would
            # feed the cross-attention an all-masked KV stream (NaN softmax)
            return (np.zeros((1, width), np.int32),
                    np.zeros((1, width), bool))

        def warm_fused():
            for width in self.widths:
                ids, pad = example(width)
                for kb in sorted({bucket_size(int(q), width)
                                  for q in query_buckets}):
                    if handle.cancelled():
                        return
                    positions = np.zeros((1, kb), np.int32)
                    counts[0] += len(self.engine.warmup(
                        ids, pad, positions, buckets=batch_buckets
                    ))

        def warm_encoder():
            for width in self.widths:
                if handle.cancelled():
                    return
                counts[1] += len(self.encoder.warmup(
                    *example(width), buckets=batch_buckets
                ))

        def warm_decoder():
            # needs one latent row; the encoder dispatch dedups against
            # warm_encoder's in-flight build of the same program
            latent_row = self.encoder.predict(*example(self.widths[0]))
            for kb in sorted({bucket_size(int(q), self.max_seq_len)
                              for q in query_buckets}):
                if handle.cancelled():
                    return
                positions = np.zeros((1, kb), np.int32)
                counts[2] += len(self.decoder.warmup(
                    latent_row, positions, buckets=batch_buckets
                ))

        def guarded(fn):
            def run():
                try:
                    fn()
                except BaseException as e:
                    errors.append(e)
                    # fail FAST: stop the sibling families at their next
                    # boundary instead of paying their full compile wall
                    # before the caller sees the first error
                    handle.cancel()
            return run

        def supervise():
            t0 = time.monotonic()
            threads = [
                threading.Thread(target=guarded(fn), name=f"mlm-warm-{i}",
                                 daemon=True)
                for i, fn in enumerate(
                    (warm_fused, warm_encoder, warm_decoder))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            obs.event("mlm_server_warmup", programs=sum(counts),
                      seconds=round(time.monotonic() - t0, 3),
                      cancelled=handle.cancelled(), errors=len(errors))
            if errors:
                handle._fail(errors[0])
            else:
                handle._finish(sum(counts))

        if background:
            supervisor = threading.Thread(
                target=supervise, name="mlm-warmup", daemon=True
            )
            handle._threads.append(supervisor)
            supervisor.start()
            return handle
        supervise()
        return handle.wait()

    @property
    def ready(self) -> bool:
        """All three program families fully warm (router join gate)."""
        return all(e.ready for e in (self.engine, self.encoder, self.decoder))

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain across all three engines: stop admitting, finish
        everything accepted (see :meth:`ServingEngine.drain` and
        :func:`drain_engines` for the close-every-door-first ordering)."""
        return drain_engines((self.engine, self.encoder, self.decoder),
                             timeout)

    def resume_admission(self) -> None:
        for eng in (self.engine, self.encoder, self.decoder):
            eng.resume_admission()

    def stats(self) -> Dict[str, Any]:
        """Locked, deep-copied snapshot across the three engines (the
        compatibility shim over the registry instruments)."""
        return {
            "fused": self.engine.stats(),
            "encode": self.encoder.stats(),
            "decode": self.decoder.stats(),
            "programs": (self.engine.num_programs
                         + self.encoder.num_programs
                         + self.decoder.num_programs),
        }

    def close(self) -> None:
        # ask every warm run's threads to stop, then WAIT for the
        # supervisors (which join them) — no warmup compile may outlive
        # the server (see ServingEngine.close)
        for h in self._warmup_handles:
            h.cancel()
        for h in self._warmup_handles:
            h.join(60.0)
        self.engine.close()
        self.encoder.close()
        self.decoder.close()

    def __enter__(self) -> "MLMServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
