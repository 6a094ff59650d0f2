"""Multi-replica serving fabric: least-loaded routing, failover with zero
lost accepted requests, latent-cache affinity spill-on-death, graceful
drain, rolling rollout with auto-rollback, and fleet-aware health.

Tier-1 coverage runs IN-PROCESS over trivial jitted engines behind
``LocalReplica`` shims (seconds, not minutes); the real-process drills —
``kill -9`` under open-loop load_bench traffic, supervisor restart+rejoin,
the serve CLI fleet mode — are ``slow``-marked, each naming the tier-1 test
that retains its logic coverage.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.inference import ServingEngine
from perceiver_io_tpu.resilience import (
    AffinityLost,
    BreakerOpen,
    DeadlineExceeded,
    FailoverPolicy,
    FaultInjector,
    FaultSpec,
    RejectedError,
    faults,
)
from perceiver_io_tpu.serving import (
    HttpReplicaClient,
    LocalReplica,
    ReplicaApp,
    ReplicaServer,
    Router,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _router(replicas, **kw):
    """A Router over a FRESH registry: router counters are keyed by name in
    the process-global registry, and absolute-value asserts must not see
    other tests' traffic."""
    kw.setdefault("scrape_interval_s", 0.02)
    kw.setdefault("registry", obs.MetricsRegistry())
    return Router(replicas, **kw)


def _make_replica(name, scale=2.0, slo=None, **engine_kw):
    """One in-process replica over trivial jitted apply fns (no flax model:
    the fabric's logic is model-agnostic and tier-1 time is precious)."""

    def infer(p, x):
        return x * p

    def encode(p, x):
        return x + p

    def decode(p, latents, positions):
        return latents * positions

    engines = {
        kind: ServingEngine(fn, np.float32(scale), max_batch=4,
                            name=f"{name}-{kind}", slo=slo, **engine_kw)
        for kind, fn in (("infer", infer), ("encode", encode),
                         ("decode", decode))
    }

    def params_factory(spec):
        return np.float32(spec.get("seed", 0) + 1.0)

    app = ReplicaApp(engines, np.float32(scale),
                     params_factory=params_factory, name=name,
                     assume_ready=True)
    return LocalReplica(app)


@pytest.fixture
def x():
    return np.ones((2, 3), np.float32)


def _close(router, *replicas):
    router.close()
    for r in replicas:
        r.app.close()


# -- failover policy (pure) ---------------------------------------------------


def test_failover_policy_classification():
    """Rejections and dead-replica transport errors re-route; deadline
    expiry and lost affinity never do (DeadlineExceeded subclasses
    TimeoutError, which the transient classifier would otherwise retry)."""
    p = FailoverPolicy(max_attempts=3)
    assert p.classify(RejectedError("queue full")) == "reroute"
    assert p.classify(BreakerOpen("open")) == "reroute"
    assert p.classify(ConnectionError("connection closed")) == "reroute"
    assert p.classify(DeadlineExceeded("expired")) == "fail"
    assert p.classify(AffinityLost("gone")) == "fail"
    assert p.classify(ValueError("shape mismatch")) == "fail"
    # attempt budget: 1-based attempt index, max_attempts total placements
    err = ConnectionError("connection closed")
    assert p.should_reroute(err, 1) and p.should_reroute(err, 2)
    assert not p.should_reroute(err, 3)
    assert not FailoverPolicy(
        max_attempts=2, reroute_rejections=False
    ).should_reroute(RejectedError("full"), 1)
    # the mirrored-error contract: a self-declared bool wins over message text
    from perceiver_io_tpu.serving import RemoteEngineError

    assert p.classify(RemoteEngineError("UNAVAILABLE: x", transient=True)) \
        == "reroute"
    assert p.classify(
        RemoteEngineError("connection reset", transient=False)) == "fail"


# -- routing ------------------------------------------------------------------


def test_router_least_loaded_routing_skewed(x):
    """A replica with an artificially slow dispatch path accumulates queue
    depth; the router's load score must steer traffic to the fast one.

    Runs under the lock-order sanitizer (analysis/): this traffic crosses
    the engine worker / submitter / router dispatch-pool / scrape-thread
    lock soup, and the recorded acquisition graph must stay cycle-free —
    an inconsistent ordering is a deadlock waiting for the interleaving
    even when this run never blocks."""
    from perceiver_io_tpu.analysis import record_lock_order

    with record_lock_order() as lock_rec:
        slow = _make_replica("slowrep")
        fast = _make_replica("fastrep")
        prev = faults.install(FaultInjector([
            FaultSpec(site="engine.dispatch.slowrep-infer", kind="slow",
                      every=1, delay_s=0.05),
        ]))
        try:
            router = _router([slow, fast])
            futs = []
            for _ in range(24):
                futs.append(router.submit(x))
                time.sleep(0.005)  # let queue depth become observable
            for f in futs:
                f.result(30)
            served_fast = fast.app.engines["infer"].requests_served
            served_slow = slow.app.engines["infer"].requests_served
            assert served_fast + served_slow == 24
            assert served_fast > served_slow, (served_fast, served_slow)
            _close(router, slow, fast)
        finally:
            faults.install(prev)
    assert lock_rec.acquisitions > 0  # the recorder really saw the traffic


def test_router_failover_zero_lost_accepted(x):
    """Kill one of three replicas with traffic in flight: every accepted
    request must still be answered (re-routed via the transient classification),
    none duplicated, none lost — the tier-1 twin of the kill -9 drill."""
    reps = [_make_replica(f"fo{i}") for i in range(3)]
    router = _router(reps)
    futs = [router.submit(x) for _ in range(10)]
    reps[0].kill()
    futs += [router.submit(x) for _ in range(30)]
    results = [f.result(30) for f in futs]  # raises if any was lost
    assert len(results) == 40
    assert all(np.allclose(r, 2.0) for r in results)
    stats = router.stats()
    assert stats["failed"] == 0
    assert stats["completed"] == 40
    # each future delivered exactly once, by exactly one replica
    assert all(f.replica in {"fo1", "fo2"} or f.attempts == 1 for f in futs)
    time.sleep(0.05)  # scrape loop observes the corpse
    assert router.statuses()["fo0"]["state"] == "down"
    _close(router, *reps)


def test_router_all_replicas_down_sheds(x):
    reps = [_make_replica(f"dead{i}") for i in range(2)]
    router = _router(reps)
    for r in reps:
        r.kill()
    router.refresh()
    fut = router.submit(x)
    with pytest.raises(RejectedError, match="no replica available"):
        fut.result(10)
    _close(router, *reps)


# -- latent-cache affinity ----------------------------------------------------


def test_router_affinity_spill_on_death(x):
    """Sessions pin to the replica holding their latents; a dead pin
    surfaces as AffinityLost (never a silent wrong-latents decode), and
    re-encoding re-pins on a live replica."""
    reps = [_make_replica(f"aff{i}") for i in range(2)]
    router = _router(reps)
    router.refresh()
    ack = router.encode(x, session="s", timeout=30)
    assert list(ack) == [2, 3]  # latents stay ON the replica; shape ack only
    first = router.pinned("s")
    assert first in ("aff0", "aff1")
    pos = np.ones((2, 3), np.float32)
    decoded = router.decode(pos, session="s", timeout=30)
    assert decoded.shape == (2, 3)
    # decode always follows the pin, even under load skew
    for _ in range(4):
        router.decode(pos, session="s", timeout=30)
    assert router.pinned("s") == first

    dict(zip(("aff0", "aff1"), reps))[first].kill()
    router.refresh()
    with pytest.raises(AffinityLost):
        router.decode(pos, session="s", timeout=30)
    assert router.pinned("s") is None  # the pin spilled
    assert router.stats()["affinity_spills"] >= 1
    router.encode(x, session="s", timeout=30)  # re-encode re-pins...
    assert router.pinned("s") != first  # ...on the surviving replica
    router.decode(pos, session="s", timeout=30)
    _close(router, *reps)


# -- graceful drain -----------------------------------------------------------


def test_router_drain_completes_inflight_then_refuses(x):
    """Drain: accepted work finishes (a slow in-flight dispatch included),
    new work is refused at the drained replica, and with the whole fleet
    drained the router sheds; resume restores service."""
    rep = _make_replica("dr0")
    router = _router([rep])
    prev = faults.install(FaultInjector([
        FaultSpec(site="engine.dispatch.dr0-infer", kind="slow",
                  at=(1,), delay_s=0.2),
    ]))
    try:
        futs = [router.submit(x) for _ in range(6)]
        time.sleep(0.02)  # the slow first dispatch is now in flight
        assert router.drain_replica("dr0", timeout_s=30)
        for f in futs:  # everything accepted before the drain completed
            assert np.allclose(f.result(30), 2.0)
        assert router.statuses()["dr0"]["state"] == "draining"
        fut = router.submit(x)
        with pytest.raises(RejectedError):
            fut.result(10)
        router.resume_replica("dr0")
        router.refresh()
        assert np.allclose(router.predict(x, timeout=30), 2.0)
    finally:
        faults.install(prev)
    _close(router, rep)


def test_engine_drain_is_reentrant_and_observable(x):
    """The engine-level drain surface the replica shim and serve.py share."""
    eng = ServingEngine(lambda p, a: a * p, np.float32(3.0), max_batch=4,
                        name="drain-unit")
    assert np.allclose(eng.predict(x), 3.0)
    assert eng.drain(timeout=10)
    assert eng.draining
    with pytest.raises(RejectedError, match="draining"):
        eng.submit(x)
    assert eng.drain(timeout=10)  # idempotent
    eng.resume_admission()
    assert not eng.draining
    assert np.allclose(eng.predict(x), 3.0)
    shed = eng.registry.counter(
        "serving_shed_total", labels={"engine": "drain-unit",
                                      "reason": "draining"})
    assert shed.value == 1
    eng.close()


# -- rolling rollout ----------------------------------------------------------


def test_rolling_update_swaps_fleet_and_rolls_params(x):
    reps = [_make_replica(f"ru{i}", scale=2.0) for i in range(2)]
    router = _router(reps)
    router.refresh()
    report = router.rolling_update({"kind": "scale", "factor": 2.0},
                                   bake_s=0.1, poll_s=0.02)
    assert report["updated"] == ["ru0", "ru1"]
    assert not report["rolled_back"]
    # both replicas now serve the scaled tree (params 4.0)
    for _ in range(4):
        assert np.allclose(router.predict(x, timeout=30), 4.0)
    _close(router, *reps)


def test_rolling_swap_auto_rollback_on_injected_slo_burn(x):
    """The acceptance rollback drill, tier-1: swap replica ru0, inject
    post-swap dispatch faults (PIT_FAULTS machinery targeting ONLY ru0's
    per-engine site) under live traffic — its SLO burn crosses the
    threshold during the bake, the rollout rolls the WHOLE fleet back, and
    no router-accepted request is lost (failures re-route)."""
    slo = obs.SLO(latency_target_s=5.0, availability_target=0.9,
                  name="fabric", burn_alert=None, min_samples=5)
    reps = [_make_replica(f"rb{i}", slo=slo, dispatch_retries=0)
            for i in range(2)]
    router = _router(reps)
    router.refresh()
    x1 = np.ones((1, 3), np.float32)

    stop = threading.Event()
    lost = []

    def traffic():
        while not stop.is_set():
            try:
                fut = router.submit(x1)
                fut.result(30)
            except Exception as e:
                lost.append(e)
            time.sleep(0.002)

    injector = FaultInjector([FaultSpec(
        site="engine.dispatch.rb0-infer", kind="transient", every=1)])
    swapped = threading.Event()

    def arm_faults_after_swap():
        # the regression is strictly POST-swap: wait for ru0's version bump
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if reps[0].scrape().get("params_version", 0) >= 1:
                faults.install(injector)
                swapped.set()
                return
            time.sleep(0.005)

    prev = faults.install(None)
    t = threading.Thread(target=traffic, daemon=True)
    watcher = threading.Thread(target=arm_faults_after_swap, daemon=True)
    t.start()
    watcher.start()
    try:
        report = router.rolling_update(
            {"kind": "scale", "factor": 2.0}, bake_s=1.5,
            burn_threshold=2.0, poll_s=0.02, min_bake_requests=5,
        )
        assert swapped.is_set(), "faults never armed — the drill did not run"
        assert report["rolled_back"], report
        assert report["regressed"] == "rb0"
        assert "SLO burn" in report["reason"]
    finally:
        stop.set()
        t.join(timeout=10)
        faults.install(prev)
    # the fleet rolled back: serving the ORIGINAL tree again
    router.refresh()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:  # rb0 sheds its last faulted calls
        try:
            out = router.predict(x1, timeout=30)
            break
        except Exception:
            time.sleep(0.02)
    assert np.allclose(out, 2.0), "rollback must restore the previous params"
    assert not lost, f"accepted requests lost during rollout: {lost[:3]}"
    _close(router, *reps)


# -- fleet-aware health (the healthz fix) -------------------------------------


def test_fleet_health_degrades_label_not_router(x):
    """One replica's open breaker (or burning SLO) must degrade THAT
    replica's label in the fleet detail — never flip the router process's
    healthz() to unhealthy while other replicas serve. Only a fleet below
    min_serving goes unhealthy."""
    reps = [_make_replica(f"fh{i}", breaker_failures=1) for i in range(2)]
    router = _router(reps)
    # adopt the per-engine breakers under the fleet: without adoption they
    # would 503 the router's global healthz the moment one opens
    for rep in reps:
        router.fleet_health.adopt_source(
            rep.name,
            rep.app.engines["infer"].breaker,
        )
    router.refresh()
    ok, detail = obs.healthz()
    assert ok

    reps[0].app.engines["infer"].breaker.trip("test outage")
    router.refresh()
    ok, detail = obs.healthz()
    assert ok, f"one degraded replica must not 503 the router: {detail}"
    fleet = detail["sources"][f"fleet:{router.name}"]
    assert fleet["status"] == "degraded"
    assert fleet["replicas"]["fh0"]["state"] == "degraded"
    assert fleet["replicas"]["fh1"]["state"] == "serving"
    # traffic still flows around the degraded replica
    assert np.allclose(router.predict(x, timeout=30), 2.0)

    reps[1].kill()
    router.refresh()
    ok, detail = obs.healthz()
    assert not ok, "a fleet with nothing serving IS down"
    _close(router, *reps)


# -- scrape staleness + the fleet time-series (ISSUE 12) ----------------------


def test_router_scrape_staleness_degrades_placement(x):
    """A stale-but-up replica's frozen gauges must stop steering
    least-loaded dispatch: past ``stale_after_intervals`` the slot is
    DEGRADED for placement (routed around while any fresh replica serves),
    ``fleet_scrape_age_s{replica=}`` rides the registry, and the next
    completed scrape reinstates it."""
    reps = [_make_replica(f"st{i}") for i in range(2)]
    reg = obs.MetricsRegistry()
    # a long interval parks the background loop; refresh() drives scrapes
    router = Router(reps, registry=reg, scrape_interval_s=60.0,
                    stale_after_intervals=0.05)  # stale past the 0.5s floor
    try:
        router.refresh()
        labels = {"fleet": router.name, "replica": "st0"}
        age_key = obs.series_key("fleet_scrape_age_s", labels)
        assert reg.snapshot()["gauges"][age_key] < 0.5
        assert reg.gauge(
            "fleet_replica_requests_total",
            labels={"fleet": router.name, "replica": "st0"}).value >= 0
        assert all(s["state"] == "serving"
                   for s in router.statuses().values())
        # st0's view goes stale (the observation aged, not the replica)
        with router._lock:
            router._slots["st0"].last_scrape_mono -= 10.0
        st = router.statuses()
        assert st["st0"]["state"] == "degraded"
        assert st["st0"]["scrape_age_s"] > 0.5
        assert st["st1"]["state"] == "serving"
        # the exported gauge reports the LIVE age (computed at export by
        # the registry collector): a wedged scrape loop — which is exactly
        # when refresh() stops running — cannot freeze it near zero
        assert reg.snapshot()["gauges"][age_key] > 0.5
        # placement routes around the stale slot while a fresh one serves
        for _ in range(4):
            fut = router.submit(x)
            fut.result(timeout=30)
            assert fut.replica == "st1"
        # a completed scrape is a fresh observation: reinstated
        router.refresh()
        assert router.statuses()["st0"]["state"] == "serving"
    finally:
        _close(router, *reps)


def test_router_feeds_fleet_series_store(x):
    """The scrape loop feeds per-replica series into one fleet store
    (labels ``replica=``): a scraped LocalReplica leaves a queryable
    up/queue-depth/requests history instead of a point read."""
    reps = [_make_replica(f"ts{i}") for i in range(2)]
    router = _router(reps)
    try:
        router.refresh()
        assert np.allclose(router.predict(x, timeout=30), 2.0)
        router.refresh()
        router.refresh()
        labels = {"fleet": router.name, "replica": "ts0"}
        up = obs.series_key("fleet_replica_up", labels)
        pts = router.series.points(up)
        assert len(pts) >= 3 and all(v == 1.0 for _, v in pts)
        # the replica's lifetime request counter ingests counter-kind:
        # windowed delta answers "how much did this replica serve lately"
        served = 0.0
        for r in ("ts0", "ts1"):
            key = obs.series_key("fleet_replica_requests_total",
                                 {"fleet": router.name, "replica": r})
            assert router.series.kind(key) == "counter"
            served += router.series.delta(key, window_s=3600.0) or 0.0
        assert served >= 1.0
        # a killed replica's outage is visible IN the history (up drops
        # to 0), not a gap in it
        reps[0].kill()
        router.refresh()
        assert router.series.last(up) == 0.0
    finally:
        _close(router, *reps)


def test_bake_judges_burn_history_not_point_reads(x):
    """A burn spike the bake's own polls never catch (landed in the fleet
    series between polls — e.g. by the background scrape loop) must still
    roll the swap back: the bake judges the windowed MAX since the swap,
    not whatever the latest poll happened to read."""
    rep = _make_replica("bk0")
    router = _router([rep])
    try:
        router.refresh()
        assert router._bake(router._slots["bk0"], bake_s=0.1,
                            burn_threshold=2.0, poll_s=0.02,
                            min_requests=0) is None  # clean bake
        # a spike stamped inside the upcoming bake window, invisible to
        # every direct scrape (the replica's own gauge reads 0 throughout)
        router.series.record(
            obs.series_key("fleet_replica_slo_burn",
                           {"fleet": router.name, "replica": "bk0"}),
            9.0, "gauge", mono=time.monotonic() + 0.03)
        reason = router._bake(router._slots["bk0"], bake_s=0.3,
                              burn_threshold=2.0, poll_s=0.02,
                              min_requests=0)
        assert reason is not None and "SLO burn" in reason
    finally:
        _close(router, rep)


# -- the RPC shim over real HTTP (in-process server) --------------------------


def test_replica_http_rpc_roundtrip(x):
    """The wire protocol end to end against a live in-process ReplicaServer:
    arrays round-trip, sessions stay resident, admin verbs work, and error
    classes survive the hop (the mirrored-exception contract)."""
    rep = _make_replica("httprep", queue_limit=64)
    server = ReplicaServer(rep.app)
    url = server.start()
    client = HttpReplicaClient("httprep", url, timeout_s=30)
    try:
        out = client.call("infer", [x])
        assert np.allclose(out[0], 2.0)
        ack = client.call("encode", [x], session="s1")
        assert list(ack[0]) == [2, 3]
        dec = client.call("decode", [np.ones((2, 3), np.float32)],
                          session="s1")
        assert dec[0].shape == (2, 3)
        with pytest.raises(AffinityLost):
            client.call("decode", [np.ones((2, 3), np.float32)],
                        session="never-encoded")
        status = client.scrape()
        assert status["up"] and status["ready"]
        assert status["sessions"] == 1
        assert client.update_params({"kind": "scale", "factor": 0.5}) == 1
        assert np.allclose(client.call("infer", [x])[0], 1.0)
        assert client.update_params({"kind": "rollback"}) == 2
        assert np.allclose(client.call("infer", [x])[0], 2.0)
        assert client.drain(timeout_s=10)
        with pytest.raises(RejectedError, match="draining"):
            client.call("infer", [x])
        client.resume()
        assert np.allclose(client.call("infer", [x])[0], 2.0)
    finally:
        server.close()
        rep.app.close()
    # the dead-server signature is the failover classification's transient class
    with pytest.raises(ConnectionError):
        client.call("infer", [x])


def test_serve_drain_handler_contract():
    """First SIGTERM raises _DrainRequested (stops admission, even out of a
    blocked read); later signals are absorbed so finish-in-flight cannot be
    aborted. restore() reinstates the host's handlers."""
    from perceiver_io_tpu.cli.serve import (
        _DrainRequested,
        _install_drain_handlers,
    )

    state, restore = _install_drain_handlers()
    try:
        with pytest.raises(_DrainRequested):
            os.kill(os.getpid(), signal.SIGTERM)
        assert state["draining"]
        os.kill(os.getpid(), signal.SIGTERM)  # absorbed, no raise
    finally:
        restore()


def test_load_bench_dry_fleet_schema():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "load_bench.py"),
         "--dry"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1
    record = json.loads(lines[0])
    assert record["fleet"] is None
    assert record["fleet_keys"] == [
        "replicas", "mode", "transport", "killed", "kill_at_frac",
        "kill_point", "reroutes", "affinity_spills", "lost_accepted",
        "restarts"]
    # r15: the tracing-overhead A/B block is declared in the schema
    assert record["trace"] is None
    assert record["trace_keys"] == [
        "ab_waves", "untraced_rps", "traced_rps", "overhead_pct",
        "spans_recorded", "generate_ab"]
    # r22: the transport A/B block (--trace_ab --transport uds|shmem)
    assert record["transport"] is None
    assert record["transport_keys"] == [
        "transport", "ab_waves", "wave_size", "http_rps", "transport_rps",
        "throughput_speedup", "http_rpc_p50_ms", "http_rpc_p99_ms",
        "rpc_p50_ms", "rpc_p99_ms", "rpc_p50_speedup", "spans_http",
        "spans_transport"]


# -- distributed request tracing (r15) ----------------------------------------


def test_rpc_phase_attribution_and_trace_cross_the_wire(x, tmp_path):
    """Satellite pin: the replica returns the engine future's ``phases``
    through the RPC (response header) and the router-side clients surface
    them — HTTP and LocalReplica in parity — while the propagated
    TraceContext parents the replica's spans under the caller's."""
    from perceiver_io_tpu.inference.engine import PHASES

    events = tmp_path / "ev.jsonl"
    obs.configure_event_log(str(events))
    rep = _make_replica("wire")
    server = ReplicaServer(rep.app)
    url = server.start()
    client = HttpReplicaClient("wire", url, timeout_s=30)
    try:
        ctx = obs.TraceContext.mint()
        meta = {}
        out = client.call("infer", [x], trace=ctx, meta=meta)
        assert np.allclose(out[0], 2.0)
        assert meta["phases"] and set(meta["phases"][0]) == set(PHASES)
        assert all(v >= 0 for v in meta["phases"][0].values())
        # LocalReplica parity: same meta/trace surface, same phase keys
        meta_local = {}
        LocalReplica(rep.app).call("infer", [x],
                                   trace=obs.TraceContext.mint(),
                                   meta=meta_local)
        assert meta_local["phases"] \
            and set(meta_local["phases"][0]) == set(PHASES)
        # attribution is unconditional — untraced calls carry phases too
        meta_untraced = {}
        client.call("infer", [x], meta=meta_untraced)
        assert meta_untraced["phases"]
    finally:
        server.close()
        rep.app.close()
        obs.configure_event_log(None)
    rows = [json.loads(l) for l in open(events)]
    serves = [r for r in rows if r.get("event") == "span"
              and r.get("name") == "replica_serve"]
    mine = [s for s in serves if s["trace"] == ctx.trace_id]
    assert mine and mine[0]["parent"] == ctx.span_id  # header roundtrip
    traces, _ = obs.assemble_traces(rows)
    engine_spans = [s for s in traces[ctx.trace_id]["spans"]
                    if s["name"] == "engine"]
    assert engine_spans and engine_spans[0]["parent"] == mine[0]["span"]


def test_fleet_tracing_assembles_and_reconciles(x, tmp_path):
    """THE r15 acceptance pin: every routed request's spans — router root,
    placement attempt, replica serve, engine + six phases — assemble into
    one tree whose durations reconcile with the latency histograms the SLO
    machinery already exports (the r11 5%-at-p50 bar, now cross-process),
    and the histograms' exemplars resolve to assembled traces."""
    import statistics

    events = tmp_path / "ev.jsonl"
    obs.configure_event_log(str(events))
    try:
        reg = obs.MetricsRegistry()  # shared by engines AND router so the
        # reconciliation reads histograms and exemplars from one place
        reps = [_make_replica(f"tr{i}", registry=reg) for i in range(2)]
        router = _router(reps, registry=reg)
        router.refresh()
        futs = [router.submit(x) for _ in range(24)]
        for f in futs:
            assert np.allclose(f.result(30), 2.0)
        # every router future carries a trace and the replica's phases
        from perceiver_io_tpu.inference.engine import PHASES

        assert all(f.trace is not None for f in futs)
        assert all(f.phases and set(f.phases[0]) == set(PHASES)
                   for f in futs)
        # close() joins the dispatch pool — the post-delivery root-span
        # bookkeeping (buffer add, exemplar) is complete after it
        _close(router, *reps)
        assert len(router.traces) == 24  # the exemplar-linked ring
    finally:
        obs.configure_event_log(None)

    traces, _ = obs.assemble_traces([json.loads(l) for l in open(events)])
    for f in futs:
        t = traces[f.trace.trace_id]
        names = [s["name"] for s in t["spans"]]
        assert t["root"]["name"] == "router_request"
        assert "router_attempt" in names and "replica_serve" in names
        assert "engine" in names
        assert sum(n.startswith("phase:") for n in names) >= 6
        # exclusive self-times reconcile with the root duration (5% bar)
        assert abs(t["span_sum_s"] - t["total_s"]) <= 0.05 * t["total_s"]
        # nesting: attempt within root, serve within attempt (one clock
        # here — the cross-clock alignment case is pinned in test_reqtrace)
        by = {s["name"]: s for s in t["spans"]}
        assert by["router_attempt"]["dur_s"] <= t["total_s"]
        assert by["replica_serve"]["dur_s"] \
            <= by["router_attempt"]["dur_s"] + 1e-6

    # root durations vs the router latency histogram: the SAME e2e the SLO
    # machinery measures, within 5% at p50
    hist = reg.histogram("router_latency_seconds",
                         labels={"router": "router"})
    assert hist.count == 24
    p50_hist = statistics.median(hist.values())
    p50_root = statistics.median(
        traces[f.trace.trace_id]["total_s"] for f in futs)
    assert abs(p50_root - p50_hist) <= 0.05 * p50_hist, (p50_root, p50_hist)

    # engine span (phase sum, assembled from the replica side of the RPC)
    # vs serving_latency_seconds: the r11 reconciliation, now cross-process
    engine_durs = []
    for f in futs:
        engine_durs.extend(
            s["dur_s"] for s in traces[f.trace.trace_id]["spans"]
            if s["name"] == "engine")
    served = []
    for i in range(2):
        for bucket in (1, 2, 4):
            served.extend(reg.histogram(
                "serving_latency_seconds",
                labels={"engine": f"tr{i}-infer",
                        "bucket": str(bucket)}).values())
    assert len(served) == 24
    p50_engine = statistics.median(engine_durs)
    p50_served = statistics.median(served)
    assert abs(p50_engine - p50_served) <= 0.05 * p50_served, \
        (p50_engine, p50_served)

    # exemplars: the p99-gauge → concrete-trace link
    exemplars = hist.exemplars()
    assert exemplars
    assert all(e["trace"] in traces for e in exemplars)


def test_chaos_kill_trace_shows_reroute_hop_zero_lost(x, tmp_path):
    """Chaos drill with tracing: kill one of three replicas under traffic —
    zero accepted requests lost, and every rerouted request's ASSEMBLED
    trace shows the failover hop (failed attempt on the victim, reroute
    span, successful attempt elsewhere)."""
    events = tmp_path / "ev.jsonl"
    obs.configure_event_log(str(events))
    try:
        reps = [_make_replica(f"ck{i}") for i in range(3)]
        router = _router(reps)
        futs = [router.submit(x) for _ in range(10)]
        reps[0].kill()
        futs += [router.submit(x) for _ in range(30)]
        for f in futs:
            assert np.allclose(f.result(30), 2.0)
        stats = router.stats()
        assert stats["failed"] == 0  # lost_accepted = 0
        assert stats["reroutes"] >= 1
        rerouted = [f for f in futs if f.attempts > 1]
        assert rerouted, "the kill never displaced a request"
        _close(router, *reps)
    finally:
        obs.configure_event_log(None)
    traces, _ = obs.assemble_traces([json.loads(l) for l in open(events)])
    for f in rerouted:
        t = traces[f.trace.trace_id]
        assert t["flags"]["reroute"], t["trace"]
        names = [s["name"] for s in t["spans"]]
        assert "router_reroute" in names
        attempts = [s for s in t["spans"] if s["name"] == "router_attempt"]
        assert any(s.get("ok") is False and s.get("replica") == "ck0"
                   for s in attempts), attempts
        ok_attempts = [s for s in attempts if s.get("ok")]
        assert ok_attempts and all(s["replica"] != "ck0"
                                   for s in ok_attempts)
        assert t["root"]["ok"] and t["root"]["replica"] != "ck0"
    # tail sampling always retains the failover traces
    kept = obs.tail_sample(traces, slow_pct=1.0, sample=0.0)
    assert {f.trace.trace_id for f in rerouted} <= set(kept)


# -- real-process drills (slow tier) ------------------------------------------


@pytest.mark.slow  # tier-1 budget (r12): real 3-process fleet + open-loop
# traffic + SIGKILL — the failover/zero-lost/reroute LOGIC stays tier-1 in
# test_router_failover_zero_lost_accepted; the load_bench fleet schema stays
# tier-1 in test_load_bench_dry_fleet_schema. This drill adds only the real
# process/socket/SIGKILL layer.
def test_chaos_drill_kill9_under_load_bench_traffic():
    """THE acceptance drill: open-loop load through the router over 3 real
    replica processes; kill -9 one mid-window; zero lost accepted requests
    and the supervisor restarts the victim."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "load_bench.py"),
         "--cpu", "--preset", "tiny", "--replicas", "3", "--replica_mode", "process",
         "--kill_replica_at", "0.5", "--kill_point", "0",
         "--duration_s", "2", "--rate_factors", "0.8",
         "--calibration_waves", "2", "--calibration_wave_size", "12"],
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout  # one-JSON-line contract holds
    record = json.loads(lines[0])
    fleet = record["fleet"]
    assert fleet["replicas"] == 3 and fleet["mode"] == "process"
    assert fleet["killed"] is not None
    assert fleet["lost_accepted"] == 0, fleet  # the drill's verdict
    assert fleet["reroutes"] >= 1
    assert fleet["restarts"] >= 1  # the supervisor brought the victim back
    point = record["sweep"][0]
    assert point["failed"] == 0
    assert point["completed"] > 0


@pytest.mark.slow  # tier-1 budget (r12): 2-process fleet bring-up + restart
# + rejoin gating + rolling swap over real sockets (~90s). The rejoin/ready
# gating LOGIC is tier-1 via LocalReplica scrapes (router JOINING state in
# test_fleet_health_degrades_label_not_router) and the rollback logic via
# test_rolling_swap_auto_rollback_on_injected_slo_burn.
def test_supervisor_restart_rejoins_only_when_ready_and_rolls():
    from perceiver_io_tpu.serving import ReplicaSupervisor

    with ReplicaSupervisor(
        count=2,
        extra_args=["--cpu", "--preset", "tiny", "--max_batch", "4"],
    ) as sup:
        clients = sup.start()
        sup.wait_ready(timeout_s=600)
        with Router(clients, scrape_interval_s=0.1) as router:
            router.refresh()
            ids = np.zeros((1, 64), np.int32)
            pad = np.zeros((1, 64), bool)
            pos = np.zeros((1, 2), np.int32)
            out = router.predict(ids, pad, pos, timeout=120)
            assert out.shape == (1, 2, 503)

            victim = clients[0].name
            sup.kill(victim)  # SIGKILL; babysitter restarts with backoff
            futs = [router.submit(ids, pad, pos) for _ in range(8)]
            for f in futs:  # zero lost through the kill
                assert f.result(120).shape == (1, 2, 503)
            # the restarted replica must pass through JOINING (ready=False)
            # before the router dispatches to it again: first wait for the
            # scrape loop to observe the death (the pre-kill "serving" view
            # is stale), then for the gated rejoin
            deadline = time.monotonic() + 600
            saw_down = saw_joining = False
            while time.monotonic() < deadline:
                state = router.statuses()[victim]["state"]
                saw_down = saw_down or state == "down"
                saw_joining = saw_joining or state == "joining"
                if saw_down and state == "serving":
                    break
                time.sleep(0.05)
            assert saw_down, "the scrape loop never observed the kill"
            assert router.statuses()[victim]["state"] == "serving"
            assert saw_joining, "rejoin must gate on engine_ready"
            assert sup.restarts(victim) == 1

            # rolling swap across the process fleet: zero dropped requests
            report = router.rolling_update({"kind": "reinit", "seed": 3},
                                           bake_s=0.3)
            assert report["updated"] and not report["rolled_back"]
            assert router.predict(ids, pad, pos,
                                  timeout=120).shape == (1, 2, 503)
            assert router.stats()["failed"] == 0


@pytest.mark.slow  # tier-1 budget (r12): trains a checkpoint and brings up
# a 2-process checkpoint-replica fleet (~2 min). Routing/affinity/rollout
# logic stays tier-1 in the in-process router tests above; the wire
# protocol in test_replica_http_rpc_roundtrip.
def test_serve_cli_fleet_matches_single_process(tmp_path):
    """serve.py --replicas 2 end to end over a real checkpoint: the fleet's
    fills equal the single-process engine's, --cached affinity works, and
    --rolling_swap_step hot-swaps the fleet without a rollback."""
    import glob

    from perceiver_io_tpu.cli import serve, train_mlm

    run_dir = train_mlm.main([
        "--synthetic", "--no_tensorboard",
        "--root", str(tmp_path / "cache"),
        "--logdir", str(tmp_path / "logs"), "--experiment", "fleetmlm",
        "--num_latents", "4", "--num_latent_channels", "16",
        "--num_encoder_layers", "1",
        "--num_self_attention_layers_per_block", "1",
        "--num_cross_attention_heads", "2", "--num_self_attention_heads", "2",
        "--dtype", "float32", "--synthetic_size", "64", "--batch_size", "16",
        "--max_seq_len", "32", "--vocab_size", "120", "--max_steps", "2",
        "--log_every_n_steps", "1",
    ])
    ckpt = os.path.join(run_dir, "checkpoints")
    tok = glob.glob(str(tmp_path / "cache" / "*tokenizer*.json"))[0]
    base = ["--cpu", "--checkpoint", ckpt, "--tokenizer", tok,
            "--max_batch", "4", "--k", "3", "--no_warmup"]
    texts = ["a [MASK] b", "no mask here"]

    events = str(tmp_path / "fleet_events.jsonl")
    single = serve.main(base + ["--texts", *texts])
    fleet = serve.main(base + ["--replicas", "2", "--drain_timeout_s", "30",
                               "--rolling_swap_step", "2",
                               "--rolling_bake_s", "0.2",
                               "--events_jsonl", events,
                               "--texts", *texts])
    assert [l["fills"] for l in fleet] == [l["fills"] for l in single]

    # r15 tracing e2e: the router's log plus each replica process's own
    # <events>.<name> log assemble into CROSS-PROCESS traces for the served
    # requests (one text has a mask -> one routed request)
    import glob as _glob

    log_paths = sorted(_glob.glob(events + "*"))
    assert events in log_paths and len(log_paths) >= 3, log_paths
    records = []
    for p in log_paths:
        records.extend(json.loads(l) for l in open(p) if l.strip())
    traces, _ = obs.assemble_traces(records)
    assert traces, "no traces assembled from the fleet run"
    routed = [t for t in traces.values()
              if t["root"]["name"] == "router_request"]
    assert routed
    full = [t for t in routed
            if len(t["processes"]) > 1
            and any(s["name"] == "replica_serve" for s in t["spans"])
            and any(s["name"] == "engine" for s in t["spans"])]
    assert full, "no cross-process trace with replica+engine spans"
    for t in full:  # the reconciliation bar holds over the real RPC too
        assert abs(t["span_sum_s"] - t["total_s"]) <= 0.05 * t["total_s"]

    cached = serve.main(base + ["--replicas", "2", "--cached",
                                "--drain_timeout_s", "30",
                                "--texts", texts[0]])
    assert cached[0]["fills"] == single[0]["fills"]


@pytest.mark.slow  # tier-1 budget (r12): trains a checkpoint and runs a
# serve.py subprocess (~60s). The signal-handler contract stays tier-1 in
# test_serve_drain_handler_contract; fleet routing logic in the in-process
# router tests above.
def test_serve_cli_sigterm_drains_and_exits_zero(tmp_path):
    """serve.py --stdin under SIGTERM: admission stops, every line already
    submitted is ANSWERED on stdout, and the process exits 0 — a supervisor
    rotation never drops the queue."""
    import glob

    from perceiver_io_tpu.cli import train_mlm

    run_dir = train_mlm.main([
        "--synthetic", "--no_tensorboard",
        "--root", str(tmp_path / "cache"),
        "--logdir", str(tmp_path / "logs"), "--experiment", "drainmlm",
        "--num_latents", "4", "--num_latent_channels", "16",
        "--num_encoder_layers", "1",
        "--num_self_attention_layers_per_block", "1",
        "--num_cross_attention_heads", "2", "--num_self_attention_heads", "2",
        "--dtype", "float32", "--synthetic_size", "64", "--batch_size", "16",
        "--max_seq_len", "32", "--vocab_size", "120", "--max_steps", "2",
        "--log_every_n_steps", "1",
    ])
    ckpt = os.path.join(run_dir, "checkpoints")
    tok = glob.glob(str(tmp_path / "cache" / "*tokenizer*.json"))[0]
    events = tmp_path / "events.jsonl"
    err_path = tmp_path / "serve.stderr"
    with open(err_path, "w") as err_file:
        proc = subprocess.Popen(
            [sys.executable, "-m", "perceiver_io_tpu.cli.serve", "--cpu",
             "--checkpoint", ckpt, "--tokenizer", tok, "--stdin",
             "--no_warmup", "--k", "2", "--drain_timeout_s", "60",
             "--events_jsonl", str(events)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=err_file, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        try:
            # signal only once admission is LIVE (the marker line): a
            # SIGTERM during startup is its own — also graceful — path
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if "admitting stdin" in err_path.read_text():
                    break
                assert proc.poll() is None, "serve died during startup"
                time.sleep(0.2)
            proc.stdin.write("a [MASK] b\nthe [MASK] was\n")
            proc.stdin.flush()
            time.sleep(0.5)  # let the two lines be read and submitted
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=240)
        finally:
            if proc.poll() is None:
                proc.kill()
                out, _ = proc.communicate()
    err = err_path.read_text()
    assert proc.returncode == 0, f"drain must exit 0\n{err[-3000:]}"
    lines = [json.loads(l) for l in out.splitlines() if l.strip()]
    assert len(lines) == 2, f"accepted lines dropped: {out!r}\n{err[-2000:]}"
    assert all(len(l["fills"]) == 1 for l in lines)
    assert "drain requested" in err
    assert events.exists()  # the event log was flushed on the drain path
