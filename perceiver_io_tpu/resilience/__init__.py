"""Self-healing primitives for the runtime paths (SURVEY.md §5, actuation).

r7 built the *detection* half of the reliability story — heartbeats, stall
diagnostics, ``/healthz``. This package is the *actuation* half, plus the
chaos substrate that proves it works without real hardware failures:

- :mod:`faults` — deterministic, test-seedable fault injection (transient
  errors, wedged-dispatch hangs, host slowdowns, NaN corruption) behind
  no-op-by-default hooks at the dispatch sites; env-gated via ``PIT_FAULTS``.
- :mod:`retry` — the error classification (transient vs fatal, with the measured
  scoped-VMEM-OOM carve-out) and capped exponential backoff with jitter.
- :mod:`breaker` — a circuit breaker (closed → open on consecutive failures
  or heartbeat stalls → half-open probe), exported to the metrics registry
  and ``healthz()``.
- :mod:`failover` — the router-side placement policy: which replica errors
  displace a request to ANOTHER replica (rejections and dead-replica socket
  errors re-route, deadline expiry and lost session affinity never do), and
  how many placements one request may burn.
- :mod:`multihost` — bounded-exit failure detection for multi-host
  training: the KV-store peer-liveness monitor and the per-step deadline,
  both exiting with :data:`~perceiver_io_tpu.resilience.multihost
  .EXIT_TRANSIENT` so restart-the-world supervision relaunches the job.
- :mod:`elastic` — the in-process alternative to restart-the-world:
  shrink/grow the world on a peer-death verdict without relaunching
  survivors, with peer-redundant in-memory checkpoints (buddy mirrors)
  and hot-spare join; degrades to :mod:`multihost` bounded exit below
  the quorum floor.

Consumers: ``inference/engine.py`` (deadline shedding, bounded-queue
admission, transient re-dispatch, breaker-gated submission),
``training/trainer.py`` (bad-step skip/rollback, dispatch retry,
``fit_with_recovery``), ``data/download.py`` (transient-HTTP backoff).

Importing this package never initializes a jax backend.
"""

from perceiver_io_tpu.resilience.breaker import BreakerOpen, CircuitBreaker
from perceiver_io_tpu.resilience.elastic import (
    BuddyMirror,
    BuddyStore,
    ElasticConfig,
    ElasticRuntime,
)
from perceiver_io_tpu.resilience.failover import AffinityLost, FailoverPolicy
from perceiver_io_tpu.resilience.faults import (
    FaultInjector,
    FaultSpec,
    InjectedFatalError,
    InjectedTransientError,
)
from perceiver_io_tpu.resilience.multihost import (
    EXIT_TRANSIENT,
    InMemoryKV,
    PeerLivenessMonitor,
    StepDeadline,
    abort_transient,
)
from perceiver_io_tpu.resilience.retry import (
    DeadlineExceeded,
    RejectedError,
    RetryPolicy,
    call_with_retry,
    classify_error,
    is_transient,
)

__all__ = [
    "AffinityLost",
    "BreakerOpen",
    "BuddyMirror",
    "BuddyStore",
    "CircuitBreaker",
    "DeadlineExceeded",
    "ElasticConfig",
    "ElasticRuntime",
    "EXIT_TRANSIENT",
    "FailoverPolicy",
    "FaultInjector",
    "FaultSpec",
    "InMemoryKV",
    "InjectedFatalError",
    "InjectedTransientError",
    "PeerLivenessMonitor",
    "RejectedError",
    "RetryPolicy",
    "StepDeadline",
    "abort_transient",
    "call_with_retry",
    "classify_error",
    "is_transient",
]
