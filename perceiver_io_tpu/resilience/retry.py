"""Error classification + exponential backoff with jitter.

The classification answers ONE question for every exception escaping a device
dispatch (or an HTTP fetch): *is retrying sane?* It is deliberately
conservative and string-based — jaxlib surfaces every PJRT failure as
``XlaRuntimeError`` with an absl status prefix, and importing jaxlib types
here would force jax into processes (the download path, the obs sidecar)
that must stay backend-free.

Classification rules, in order:

- injected faults carry their class (``InjectedTransientError`` /
  ``InjectedFatalError``) — the chaos suite's ground truth;
- connection-ish OS errors (reset/aborted/broken pipe/timeout) are transient
  — a dropped connection's signature;
- ``XlaRuntimeError``-family messages are transient only under status
  prefixes that name infrastructure (UNAVAILABLE, ABORTED, CANCELLED,
  DEADLINE_EXCEEDED, UNKNOWN, INTERNAL) — **RESOURCE_EXHAUSTED is fatal**:
  on this stack those are real scoped-VMEM OOMs with measured boundaries
  (PERF.md r3), and retrying one blind re-runs a deterministic failure;
- everything else (tracing/type/shape errors, ``FloatingPointError`` from the
  NaN guards) is fatal.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Callable, Optional

from perceiver_io_tpu.resilience.faults import (
    InjectedFatalError,
    InjectedTransientError,
)

TRANSIENT = "transient"
FATAL = "fatal"


class RejectedError(RuntimeError):
    """A request refused at admission (bounded-queue load shedding or an open
    circuit breaker) — shed fast instead of queueing toward a timeout."""


class DeadlineExceeded(TimeoutError):
    """A request shed because its deadline expired before (or at) dispatch —
    the work would have been dead on arrival."""


# absl status prefixes as they appear in XlaRuntimeError messages.
# RESOURCE_EXHAUSTED deliberately absent: real scoped-VMEM OOMs (PERF.md r3).
_TRANSIENT_STATUS_PREFIXES = (
    "UNAVAILABLE", "ABORTED", "CANCELLED", "DEADLINE_EXCEEDED", "UNKNOWN",
    "INTERNAL",
)
# connection-level failure text (dropped connections surface these inside URLError /
# XlaRuntimeError messages as well as bare OSErrors)
_TRANSIENT_MESSAGE_MARKERS = (
    "connection reset", "connection aborted", "broken pipe", "socket closed",
    "failed to connect", "connection closed", "transient",
)
_RUNTIME_ERROR_TYPES = ("XlaRuntimeError", "PjRtError", "JaxRuntimeError")
# deterministic failures that can surface under infra-looking status
# prefixes: the compile-time scoped-VMEM OOMs of an oversized kernel block
_FATAL_MESSAGE_MARKERS = ("scoped vmem", "scoped allocation", "out of memory")


def classify_error(exc: BaseException) -> str:
    """``'transient'`` (retry is sane) or ``'fatal'`` (it is not)."""
    if isinstance(exc, InjectedTransientError):
        return TRANSIENT
    if isinstance(exc, InjectedFatalError):
        return FATAL
    # self-declared class: an error that crossed a process boundary (the
    # replica RPC shim mirrors the REMOTE side's classification as a bool
    # `transient` attribute) keeps its original verdict — re-deriving it
    # from the mirrored message text would misread, e.g., a fatal shape
    # error whose repr happens to contain 'connection'
    declared = getattr(exc, "transient", None)
    if isinstance(declared, bool):
        return TRANSIENT if declared else FATAL
    if isinstance(exc, (ConnectionResetError, ConnectionAbortedError,
                        BrokenPipeError, TimeoutError)):
        return TRANSIENT
    msg = str(exc)
    lowered = msg.lower()
    mro_names = {c.__name__ for c in type(exc).__mro__}
    if mro_names.intersection(_RUNTIME_ERROR_TYPES):
        if any(m in lowered for m in _FATAL_MESSAGE_MARKERS):
            # deterministic compiler failures can ride infra-looking status
            # prefixes — never retry these
            return FATAL
        head = msg.lstrip().split(":", 1)[0].strip()
        if head in _TRANSIENT_STATUS_PREFIXES:
            return TRANSIENT
        if any(m in lowered for m in _TRANSIENT_MESSAGE_MARKERS):
            return TRANSIENT
        return FATAL
    if isinstance(exc, OSError) and any(
        m in lowered for m in _TRANSIENT_MESSAGE_MARKERS
    ):
        return TRANSIENT
    return FATAL


def is_transient(exc: BaseException) -> bool:
    return classify_error(exc) == TRANSIENT


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Capped exponential backoff with deterministic-when-seeded jitter.

    ``max_retries`` counts RE-tries: 0 means one attempt, no retry. Backoff
    for retry *i* (1-based) is ``min(base_s * multiplier**(i-1), max_s)``
    scaled by a jitter factor in ``[1 - jitter, 1 + jitter]``.
    """

    max_retries: int = 3
    base_s: float = 0.05
    multiplier: float = 2.0
    max_s: float = 2.0
    jitter: float = 0.25

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if not 0.0 <= self.jitter < 1.0:
            raise ValueError(f"jitter must be in [0, 1), got {self.jitter}")

    def backoff_s(self, retry: int, rng: Optional[random.Random] = None) -> float:
        """Sleep before 1-based retry ``retry``; pass a seeded ``rng`` for a
        reproducible schedule (the chaos tests do)."""
        if retry < 1:
            return 0.0
        base = min(self.base_s * self.multiplier ** (retry - 1), self.max_s)
        if self.jitter == 0.0:
            return base
        r = rng if rng is not None else random
        return base * (1.0 + self.jitter * (2.0 * r.random() - 1.0))


def call_with_retry(
    fn: Callable,
    policy: RetryPolicy = RetryPolicy(),
    classify: Callable[[BaseException], str] = classify_error,
    on_retry: Optional[Callable[[int, BaseException, float], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
    rng: Optional[random.Random] = None,
):
    """Call ``fn()``; on a TRANSIENT exception back off and retry up to
    ``policy.max_retries`` times. Fatal errors and exhausted budgets re-raise
    the original exception. ``on_retry(retry_index, error, backoff_s)`` is the
    observability hook (counters, event log)."""
    retry = 0
    while True:
        try:
            return fn()
        except Exception as e:
            if retry >= policy.max_retries or classify(e) != TRANSIENT:
                raise
            retry += 1
            pause = policy.backoff_s(retry, rng=rng)
            if on_retry is not None:
                on_retry(retry, e, pause)
            if pause > 0:
                sleep(pause)
