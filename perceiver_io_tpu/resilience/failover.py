"""Router-side failover policy: which replica errors displace a request to
another replica, and how many placements one request may burn.

The engine-side classification (:mod:`perceiver_io_tpu.resilience.retry`) answers
"is retrying *this dispatch* sane?"; this module answers the router's
question one level up: "is retrying *on a different replica* sane?" The two
differ in exactly three places:

- **admission refusals re-route**: a ``RejectedError`` (bounded queue full,
  breaker open, replica draining) is FATAL engine-side — retrying the same
  engine re-asks a full queue — but it is precisely the signal that another
  replica should take the work. Load-aware failover IS re-routing rejections.
- **deadline expiry never re-routes**: a ``DeadlineExceeded`` request is dead
  on every replica; placing it again burns capacity on work whose caller
  already gave up. (It must be carved out explicitly — it subclasses
  ``TimeoutError``, which the transient classifier would happily retry.)
- **a dead replica is transient-class**: ``kill -9`` surfaces router-side as
  connection reset/refused/EOF on the RPC socket — the dropped-connection signature
  the classification already classifies transient — so in-flight requests on a
  killed replica re-route instead of failing their callers. The request was
  ACCEPTED by the router; acceptance is the router's delivery promise.

At-most-once delivery: the router re-routes only requests for which NO
response was received. A replica may have executed work whose response died
with it — inference is idempotent, so re-execution is safe — but a completed
(delivered) request is never dispatched again.
"""

from __future__ import annotations

import dataclasses

from perceiver_io_tpu.resilience.retry import (
    DeadlineExceeded,
    RejectedError,
    RetryPolicy,
    is_transient,
)

REROUTE = "reroute"
FAIL = "fail"


class AffinityLost(RuntimeError):
    """The replica holding this session's cached state (latents) is gone —
    the request CANNOT be transparently re-routed because the state it
    referenced died with the replica. The caller re-establishes the session
    (re-encode) on whichever replica the router pins next; the router drops
    the dead pin so the re-encode lands on a live replica (spill-on-death)."""


@dataclasses.dataclass(frozen=True)
class FailoverPolicy:
    """How a router re-places failed requests.

    ``max_attempts`` counts total placements (1 = never fail over).
    ``reroute_rejections``: treat admission refusals (queue full / breaker
    open / draining) as displacement signals — on by default, the
    load-shedding-becomes-load-balancing behavior. ``backoff`` paces the
    attempts (default: immediate — a dead replica is already detected, and
    the next placement goes elsewhere; pacing matters only when the whole
    fleet is refusing).
    """

    max_attempts: int = 3
    reroute_rejections: bool = True
    backoff: RetryPolicy = dataclasses.field(
        default_factory=lambda: RetryPolicy(max_retries=0, base_s=0.0,
                                            jitter=0.0)
    )

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError(
                f"max_attempts must be >= 1, got {self.max_attempts}"
            )

    def classify(self, error: BaseException) -> str:
        """``'reroute'`` (place on another replica) or ``'fail'`` (the
        caller sees this error)."""
        if isinstance(error, (DeadlineExceeded, AffinityLost)):
            # dead-on-arrival everywhere / state died with the replica —
            # both checked BEFORE the transient classes they subclass or
            # resemble would claim them
            return FAIL
        if isinstance(error, RejectedError):
            return REROUTE if self.reroute_rejections else FAIL
        return REROUTE if is_transient(error) else FAIL

    def should_reroute(self, error: BaseException, attempt: int) -> bool:
        """``attempt`` is 1-based (the placement that just failed)."""
        return attempt < self.max_attempts and self.classify(error) == REROUTE
