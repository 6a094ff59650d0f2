"""Chaos drills (CPU, fault-injected): the resilience subsystem end to end.

Every recovery path a misbehaving device will need is provoked here
deterministically via ``resilience.faults``: transient dispatch errors are
retried with backoff, wedged dispatches trip the breaker (via the heartbeat
stall monitor) and flip ``/healthz``, expired/over-quota requests are shed
with terminal results (no future ever hangs), and the trainer survives
injected NaN steps (skip → rollback) and transient device errors (retry →
``fit_with_recovery`` restart) — with the retry/shed/breaker/bad-step
counters asserted against the obs registry.
"""

import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.inference import ServingEngine
from perceiver_io_tpu.resilience import (
    BreakerOpen,
    CircuitBreaker,
    DeadlineExceeded,
    FaultInjector,
    FaultSpec,
    InjectedFatalError,
    InjectedTransientError,
    RejectedError,
    RetryPolicy,
    call_with_retry,
    classify_error,
    faults,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with no injector installed."""
    prev = faults.install(None)
    yield
    faults.install(prev)


class XlaRuntimeError(RuntimeError):
    """Stand-in with jaxlib's type NAME — the classification matches by name, so
    the tests need no jaxlib import."""


# -- classification ----------------------------------------------------------------


def test_error_classification():
    t, f = "transient", "fatal"
    assert classify_error(XlaRuntimeError("UNAVAILABLE: socket closed")) == t
    assert classify_error(XlaRuntimeError("ABORTED: coordination lost")) == t
    assert classify_error(XlaRuntimeError("DEADLINE_EXCEEDED: rpc")) == t
    assert classify_error(XlaRuntimeError("INTERNAL: stream failed")) == t
    assert classify_error(XlaRuntimeError("INVALID_ARGUMENT: bad shape")) == f
    # real scoped-VMEM OOMs (PERF.md r3) must NEVER be retried, even under
    # an infra-looking prefix
    assert classify_error(XlaRuntimeError(
        "INTERNAL: Scoped allocation with size 18.0M exceeded scoped vmem "
        "limit of 16.0M")) == f
    assert classify_error(XlaRuntimeError("RESOURCE_EXHAUSTED: hbm oom")) == f
    assert classify_error(ConnectionResetError("peer reset")) == t
    assert classify_error(TimeoutError("read timed out")) == t
    assert classify_error(InjectedTransientError("chaos")) == t
    assert classify_error(InjectedFatalError("chaos")) == f
    assert classify_error(ValueError("tracing failed")) == f
    assert classify_error(FloatingPointError("non-finite loss")) == f


def test_retry_policy_backoff_caps_and_is_seedable():
    p = RetryPolicy(max_retries=5, base_s=0.1, multiplier=2.0, max_s=0.5,
                    jitter=0.0)
    assert [p.backoff_s(i) for i in (1, 2, 3, 4, 5)] == [0.1, 0.2, 0.4, 0.5, 0.5]
    assert p.backoff_s(0) == 0.0
    j = RetryPolicy(base_s=0.1, jitter=0.5)
    a = j.backoff_s(1, rng=random.Random(7))
    b = j.backoff_s(1, rng=random.Random(7))
    assert a == b, "seeded jitter must be deterministic"
    assert 0.05 <= a <= 0.15
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.5)


def test_call_with_retry_semantics():
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise InjectedTransientError("flap")
        return "done"

    out = call_with_retry(
        flaky, RetryPolicy(max_retries=3, base_s=0.01, jitter=0.0),
        sleep=sleeps.append,
    )
    assert out == "done" and calls["n"] == 3
    assert sleeps == [0.01, 0.02]

    # fatal: one attempt, the error propagates untouched
    calls["n"] = 0

    def fatal():
        calls["n"] += 1
        raise InjectedFatalError("stop")

    with pytest.raises(InjectedFatalError):
        call_with_retry(fatal, RetryPolicy(max_retries=5, base_s=0.0))
    assert calls["n"] == 1

    # exhausted budget re-raises the transient error
    def always():
        raise InjectedTransientError("down")

    with pytest.raises(InjectedTransientError):
        call_with_retry(always, RetryPolicy(max_retries=2, base_s=0.0),
                        sleep=lambda s: None)


# -- fault injector ----------------------------------------------------------


def test_fault_injector_is_deterministic():
    inj = FaultInjector([
        # abstract site names: this test pins the injector's counting
        # mechanics, not the registry (which only parse_spec enforces)
        FaultSpec(site="s", kind="transient", at=(2, 4)),  # pitlint: ignore[PIT-FAULT] abstract mechanics fixture
        FaultSpec(site="e", kind="fatal", every=3),  # pitlint: ignore[PIT-FAULT] abstract mechanics fixture
    ])
    fired = []
    for i in range(1, 6):
        try:
            inj.inject("s")
            fired.append(False)
        except InjectedTransientError:
            fired.append(True)
    assert fired == [False, True, False, True, False]
    assert inj.calls("s") == 5
    for i in range(1, 7):
        if i % 3 == 0:
            with pytest.raises(InjectedFatalError):
                inj.inject("e")
        else:
            inj.inject("e")

    # nan corruption poisons floating leaves only, at the named call
    inj2 = FaultInjector([FaultSpec(site="m", kind="nan", at=(2,))])  # pitlint: ignore[PIT-FAULT] abstract mechanics fixture
    clean = {"loss": jnp.float32(1.5), "count": np.int32(3)}
    assert inj2.corrupt("m", clean) is clean
    poisoned = inj2.corrupt("m", clean)
    assert np.isnan(poisoned["loss"]) and poisoned["count"] == 3


def test_fault_env_spec_parses():
    inj = faults.parse_spec(
        "engine.dispatch:transient@2,5;trainer.metrics:nan@every:3;"
        "engine.complete:slow@1@delay:0.25"
    )
    with pytest.raises(InjectedTransientError):
        for _ in range(2):
            inj.inject("engine.dispatch")
    with pytest.raises(ValueError, match="bad PIT_FAULTS clause"):
        faults.parse_spec("nonsense")


# -- circuit breaker ---------------------------------------------------------


def test_breaker_state_machine_and_telemetry():
    now = [0.0]
    reg = obs.MetricsRegistry()
    b = CircuitBreaker("bt", failure_threshold=2, cooldown_s=10.0,
                       registry=reg, clock=lambda: now[0])
    try:
        assert b.state == "closed" and b.allow()
        b.record_failure(RuntimeError("one"))
        assert b.state == "closed"  # below threshold
        b.record_success()
        b.record_failure(RuntimeError("one"))
        b.record_failure(RuntimeError("two"))  # consecutive pair → open
        assert b.state == "open" and not b.allow()
        with pytest.raises(BreakerOpen):
            b.check()
        now[0] = 10.0  # cooldown elapsed → half-open probe admitted
        assert b.allow() and b.state == "half_open"
        b.record_failure(RuntimeError("probe died"))  # probe fails → reopen
        assert b.state == "open"
        now[0] = 20.0
        assert b.allow() and b.state == "half_open"
        b.record_success()
        assert b.state == "closed"
        gauge = reg.gauge("breaker_state", labels={"breaker": "bt"})
        assert gauge.value == 0
        opens = reg.counter("breaker_transitions_total",
                            labels={"breaker": "bt", "to": "open"})
        assert opens.value == 2

        # a trip() while already OPEN extends the cooldown window — the
        # stall monitor re-asserts every poll during a persistent wedge, and
        # the breaker must not drift half-open while the stall continues
        now[0] = 100.0
        b.trip("stall")
        now[0] = 109.0
        b.trip("stall persists")
        now[0] = 112.0  # 12s after the first trip, 3s after the re-trip
        assert not b.allow() and b.state == "open"
        now[0] = 119.5  # cooldown (10s) elapsed since the LAST re-trip
        assert b.allow() and b.state == "half_open"
        b.record_success()
        assert b.state == "closed"

        # healthz reflects an open breaker (the /healthz body)
        b.trip("drill")
        ok, detail = obs.healthz()
        assert not ok and detail["sources"]["breaker:bt"]["state"] == "open"
    finally:
        b.close()
    ok, detail = obs.healthz()
    assert "breaker:bt" not in detail.get("sources", {})


# -- engine chaos ------------------------------------------------------------


def _mul_engine(**kw):
    def apply_fn(p, x):
        return x * p

    kw.setdefault("max_batch", 4)
    return ServingEngine(apply_fn, jnp.float32(2.0), **kw)


def test_engine_transient_dispatch_retried_no_request_fails():
    """One flaky dispatch no longer fails its whole micro-batch: the batch
    re-dispatches with backoff and every future still resolves."""
    reg = obs.MetricsRegistry()
    faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch", kind="transient", at=(2, 3)),
    ]))
    with _mul_engine(name="rt", registry=reg,
                     retry_policy=RetryPolicy(max_retries=3, base_s=0.01,
                                              jitter=0.0),
                     breaker_failures=10) as eng:
        futs = [eng.submit(np.full((1, 2), float(i), np.float32))
                for i in range(6)]
        for i, fut in enumerate(futs):
            np.testing.assert_allclose(fut.result(timeout=60), 2.0 * i)
        assert reg.counter("serving_dispatch_retries_total",
                           labels={"engine": "rt"}).value >= 1
        assert eng.breaker.state == "closed"  # recovered failures don't trip


def test_engine_complete_side_transient_redispatches():
    """A completion-side failure (device_get) re-dispatches the batch too —
    the request still resolves with the right answer."""
    reg = obs.MetricsRegistry()
    faults.install(FaultInjector([
        FaultSpec(site="engine.complete", kind="transient", at=(1,)),
    ]))
    with _mul_engine(name="ct", registry=reg,
                     retry_policy=RetryPolicy(max_retries=2, base_s=0.01,
                                              jitter=0.0)) as eng:
        out = eng.predict(np.full((2, 3), 4.0, np.float32), timeout=60)
        np.testing.assert_allclose(out, 8.0)
        assert reg.counter("serving_dispatch_retries_total",
                           labels={"engine": "ct"}).value == 1


def test_engine_retry_budget_exhausted_fails_with_original_error():
    faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch", kind="transient", every=1),
    ]))
    with _mul_engine(name="ex",
                     retry_policy=RetryPolicy(max_retries=1, base_s=0.01,
                                              jitter=0.0)) as eng:
        with pytest.raises(InjectedTransientError):
            eng.submit(np.ones((1, 2), np.float32)).result(timeout=60)


def test_engine_fatal_dispatch_error_never_retried():
    reg = obs.MetricsRegistry()
    faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch", kind="fatal", at=(1,)),
    ]))
    with _mul_engine(name="ft", registry=reg, dispatch_retries=5) as eng:
        with pytest.raises(InjectedFatalError):
            eng.submit(np.ones((1, 2), np.float32)).result(timeout=60)
        assert reg.counter("serving_dispatch_retries_total",
                           labels={"engine": "ft"}).value == 0
        # the engine survives and keeps serving
        np.testing.assert_allclose(
            eng.predict(np.ones((1, 2), np.float32), timeout=60), 2.0)


def test_engine_deadline_shed_at_admission_and_assembly():
    reg = obs.MetricsRegistry()
    release = threading.Event()
    faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch", kind="hang", at=(1,),
                  release=release, delay_s=30.0),
    ]))
    try:
        with _mul_engine(name="dl", registry=reg) as eng:
            # admission: an already-expired deadline is refused outright
            with pytest.raises(DeadlineExceeded):
                eng.submit(np.ones((1, 2), np.float32), deadline_s=0.0)

            f1 = eng.submit(np.ones((1, 2), np.float32))
            time.sleep(0.1)  # let the worker wedge inside dispatch #1
            f2 = eng.submit(np.full((1, 2), 5.0, np.float32), deadline_s=0.05)
            time.sleep(0.15)  # f2's deadline expires while the dispatch is stuck
            release.set()
            np.testing.assert_allclose(f1.result(timeout=60), 2.0)
            # shed AT ASSEMBLY with a terminal result — not a silent hang and
            # not a burned dispatch
            with pytest.raises(DeadlineExceeded):
                f2.result(timeout=60)
            shed = reg.counter("serving_shed_total",
                               labels={"engine": "dl", "reason": "deadline"})
            assert shed.value == 2  # one admission + one assembly shed
    finally:
        release.set()


def test_engine_queue_limit_sheds_with_fast_fail():
    reg = obs.MetricsRegistry()
    release = threading.Event()
    faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch", kind="hang", at=(1,),
                  release=release, delay_s=30.0),
    ]))
    try:
        with _mul_engine(name="ql", registry=reg, queue_limit=2) as eng:
            first = eng.submit(np.ones((1, 2), np.float32))
            time.sleep(0.1)  # worker wedged in dispatch #1 (backlog drained)
            admitted = [eng.submit(np.ones((1, 2), np.float32))
                        for _ in range(2)]
            with pytest.raises(RejectedError):
                eng.submit(np.ones((1, 2), np.float32))
            assert reg.counter(
                "serving_shed_total",
                labels={"engine": "ql", "reason": "queue_full"}).value == 1
            release.set()
            for fut in [first, *admitted]:
                np.testing.assert_allclose(fut.result(timeout=60), 2.0)
    finally:
        release.set()


def test_wedged_dispatch_trips_breaker_and_healthz_503():
    """THE acceptance drill, detection half: a wedged dispatch (hang fault)
    stalls the heartbeat → the monitor trips the breaker → the obs registry
    shows state 2 and the HTTP /healthz endpoint returns 503 naming it."""
    import json
    import urllib.error
    import urllib.request

    reg = obs.MetricsRegistry()
    release = threading.Event()
    faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch", kind="hang", at=(1,),
                  release=release, delay_s=60.0),
    ]))
    try:
        with obs.ObsServer(registry=reg) as server, _mul_engine(
            name="wedge", registry=reg,
            heartbeat_deadline_s=0.15,
            breaker_failures=3, breaker_cooldown_s=0.2,
        ) as eng:
            f1 = eng.submit(np.ones((1, 2), np.float32))
            deadline = time.monotonic() + 20
            while eng.breaker.state != "open" and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.breaker.state == "open", "stall monitor must trip it"
            assert reg.gauge("breaker_state",
                             labels={"breaker": "wedge"}).value == 2

            ok, detail = obs.healthz()
            assert not ok
            assert detail["sources"]["breaker:wedge"]["state"] == "open"
            try:
                with urllib.request.urlopen(f"{server.url}/healthz"):
                    code, body = 200, {}
            except urllib.error.HTTPError as e:
                code, body = e.code, json.loads(e.read().decode())
            assert code == 503
            assert body["sources"]["breaker:wedge"]["state"] == "open"

            release.set()  # un-wedge: the hung future still resolves
            np.testing.assert_allclose(f1.result(timeout=60), 2.0)
    finally:
        release.set()


def test_wedged_dispatch_breaker_full_cycle():
    """Same drill without the HTTP assertion plumbing: fast-fail while open,
    zero hung futures, half-open probe recovery."""
    reg = obs.MetricsRegistry()
    release = threading.Event()
    faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch", kind="hang", at=(1,),
                  release=release, delay_s=60.0),
    ]))
    try:
        with _mul_engine(
            name="wedge2", registry=reg, heartbeat_deadline_s=0.15,
            breaker_failures=3, breaker_cooldown_s=0.2,
        ) as eng:
            f1 = eng.submit(np.ones((1, 2), np.float32))
            deadline = time.monotonic() + 20
            while eng.breaker.state != "open" and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.breaker.state == "open"
            # fast-fail while open: no queue growth behind a dead device
            with pytest.raises(BreakerOpen):
                eng.submit(np.ones((1, 2), np.float32))
            assert reg.counter(
                "serving_shed_total",
                labels={"engine": "wedge2", "reason": "breaker_open"},
            ).value >= 1

            # cooldown elapses while STILL wedged: one submit may slip into
            # the half-open window, but the stall monitor re-trips every
            # poll — the breaker must not PARK half-open admitting unbounded
            # traffic behind the hung worker
            time.sleep(3 * 0.2)
            probe = None
            try:
                probe = eng.submit(np.ones((1, 2), np.float32))
            except BreakerOpen:
                pass
            deadline = time.monotonic() + 5
            while eng.breaker.state != "open" and time.monotonic() < deadline:
                time.sleep(0.02)
            assert eng.breaker.state == "open"

            release.set()  # un-wedge the device
            # the wedged request was never lost: terminal result, right answer
            np.testing.assert_allclose(f1.result(timeout=60), 2.0)

            # after the cooldown the half-open probe flows and closes it
            out = None
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                try:
                    out = eng.submit(
                        np.full((1, 2), 3.0, np.float32)).result(timeout=60)
                    break
                except BreakerOpen:
                    time.sleep(0.05)
            np.testing.assert_allclose(out, 6.0)
            assert eng.breaker.state == "closed"
            if probe is not None:  # the half-open slip still resolved
                np.testing.assert_allclose(probe.result(timeout=60), 2.0)
        ok, _ = obs.healthz()
        assert ok, "breaker deregisters on engine close"
    finally:
        release.set()


# -- trainer chaos -----------------------------------------------------------


def _toy_trainer(tmp_path, *, max_steps=6, **cfg_overrides):
    """A tiny deterministic quadratic-fit trainer (no Perceiver — the drills
    exercise the LOOP, not the model)."""
    import optax

    from perceiver_io_tpu.training import Trainer, TrainerConfig, TrainState

    def train_step(state, batch):
        def loss_fn(params):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads), {"loss": loss}

    params = {"w": jnp.zeros((3, 1))}
    state = TrainState.create(params, optax.sgd(0.1), jax.random.key(0))
    cfg = TrainerConfig(
        max_steps=max_steps, log_every_n_steps=100,
        logdir=str(tmp_path / "logs"), experiment="chaos",
        use_tensorboard=False, compute_mfu=False, **cfg_overrides,
    )
    return Trainer(train_step, None, state, cfg,
                   example_batch=_toy_batches()[0])


def _toy_batches(n=8, seed=0):
    rng = np.random.default_rng(seed)
    w_true = np.asarray([[1.0], [-2.0], [0.5]], np.float32)
    batches = []
    for _ in range(n):
        x = rng.normal(0, 1, (4, 3)).astype(np.float32)
        batches.append({"x": x, "y": x @ w_true})
    return batches


def _counter(name):
    return obs.get_registry().counter(name)


def test_trainer_skips_injected_nan_step(tmp_path):
    """An injected NaN step is skipped (pre-step state kept) and the run
    finishes with a finite loss on par with the fault-free run."""
    batches = _toy_batches()

    clean = _toy_trainer(tmp_path / "clean", skip_nonfinite_steps=True)
    with clean:
        clean_state = clean.fit(batches)
    clean_loss = float(jax.device_get(
        jnp.mean((batches[0]["x"] @ clean_state.params["w"]
                  - batches[0]["y"]) ** 2)))

    bad0 = _counter("trainer_bad_steps_total").value
    faults.install(FaultInjector([
        FaultSpec(site="trainer.metrics", kind="nan", at=(3,)),
    ]))
    trainer = _toy_trainer(tmp_path / "faulted", skip_nonfinite_steps=True,
                           rollback_after_bad_steps=0)
    with trainer:
        state = trainer.fit(batches)
    assert int(jax.device_get(state.step)) == 6  # skipped step not counted
    assert _counter("trainer_bad_steps_total").value == bad0 + 1
    faulted_loss = float(jax.device_get(
        jnp.mean((batches[0]["x"] @ state.params["w"]
                  - batches[0]["y"]) ** 2)))
    assert np.isfinite(faulted_loss)
    # loss parity with the fault-free run: both converged well below the
    # w=0 starting loss (~5.0 on this toy); skipping one batch of eight must
    # not change the outcome's order of magnitude, let alone poison it
    assert faulted_loss < 1.0
    assert faulted_loss < 5.0 * max(clean_loss, 0.05)


def test_trainer_rolls_back_after_consecutive_bad_steps(tmp_path):
    batches = _toy_batches()
    bad0 = _counter("trainer_bad_steps_total").value
    rb0 = _counter("trainer_rollbacks_total").value
    faults.install(FaultInjector([
        FaultSpec(site="trainer.metrics", kind="nan", at=(3, 4, 5)),
    ]))
    trainer = _toy_trainer(tmp_path, skip_nonfinite_steps=True,
                           rollback_after_bad_steps=3)
    with trainer:
        state = trainer.fit(batches)
    assert int(jax.device_get(state.step)) == 6  # finished despite the streak
    assert _counter("trainer_bad_steps_total").value == bad0 + 3
    assert _counter("trainer_rollbacks_total").value == rb0 + 1


def test_trainer_transient_dispatch_retry_exact_parity(tmp_path):
    """A transiently-failing dispatch retries the SAME batch — the recovered
    trajectory is bit-identical to the fault-free one."""
    batches = _toy_batches()
    clean = _toy_trainer(tmp_path / "clean", dispatch_error_retries=2)
    with clean:
        clean_state = clean.fit(batches)

    r0 = _counter("trainer_dispatch_retries_total").value
    faults.install(FaultInjector([
        FaultSpec(site="trainer.dispatch", kind="transient", at=(4,)),
    ]))
    trainer = _toy_trainer(tmp_path / "faulted", dispatch_error_retries=2)
    with trainer:
        state = trainer.fit(batches)
    assert _counter("trainer_dispatch_retries_total").value == r0 + 1
    assert int(jax.device_get(state.step)) == 6
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(state.params["w"])),
        np.asarray(jax.device_get(clean_state.params["w"])),
    )


def test_trainer_fatal_dispatch_error_raises(tmp_path):
    faults.install(FaultInjector([
        FaultSpec(site="trainer.dispatch", kind="fatal", at=(2,)),
    ]))
    trainer = _toy_trainer(tmp_path, dispatch_error_retries=5)
    with trainer:
        with pytest.raises(InjectedFatalError):
            trainer.fit(_toy_batches())


def test_fit_with_recovery_auto_resumes_transient_crash(tmp_path):
    """A transient failure that escapes the per-step retries kills the fit
    attempt; the supervisor restores the newest checkpoint and finishes."""
    batches = _toy_batches()
    rs0 = _counter("trainer_fit_restarts_total").value
    faults.install(FaultInjector([
        FaultSpec(site="trainer.dispatch", kind="transient", at=(4,)),
    ]))
    trainer = _toy_trainer(tmp_path, skip_nonfinite_steps=True,
                           fit_attempts=3)  # retries=0: the error escapes
    with trainer:
        state = trainer.fit_with_recovery(batches)
    assert int(jax.device_get(state.step)) == 6
    assert _counter("trainer_fit_restarts_total").value == rs0 + 1

    # fatal errors are NOT restarted
    faults.install(FaultInjector([
        FaultSpec(site="trainer.dispatch", kind="fatal", at=(2,)),
    ]))
    trainer2 = _toy_trainer(tmp_path / "fatal", skip_nonfinite_steps=True,
                            fit_attempts=3)
    with trainer2:
        with pytest.raises(InjectedFatalError):
            trainer2.fit_with_recovery(batches)
    assert _counter("trainer_fit_restarts_total").value == rs0 + 1


def test_recovery_mode_disables_donation(tmp_path):
    """The kept pre-step state (and a transient retry's replayed arguments)
    must stay alive: recovery mode must not donate the train state — same
    rule as debug_nans. CPU ignores donation, so assert the trainer's own
    donation decision, which is what the TPU path compiles with."""
    with _toy_trainer(tmp_path / "a", skip_nonfinite_steps=True) as t1:
        assert not t1.donates_state
    with _toy_trainer(tmp_path / "b", dispatch_error_retries=1) as t2:
        assert not t2.donates_state
    with _toy_trainer(tmp_path / "c") as t3:
        assert t3.donates_state
