"""Elastic autoscaling + admission control: the serving control loop.

Tier-1 coverage runs IN-PROCESS over trivial jitted engines behind
``LocalReplica`` shims (the test_fabric idiom): WFQ/token-bucket units, the
admission gate's shed classification, the noisy-neighbor isolation pin, the
policy's hold-down/hysteresis state machine with injected clocks, the
end-to-end scale-up/scale-down loop over a live router, the spawn-failure
backoff chaos drill, and the supervisor's drain-then-SIGTERM retire path
(stub child processes — no jax import in the children, so the real
SIGTERM/port semantics stay tier-1 cheap).
"""

import os
import socket
import sys
import textwrap
import time

import numpy as np
import pytest

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.inference import ServingEngine
from perceiver_io_tpu.resilience import (
    FaultInjector,
    FaultSpec,
    RejectedError,
    faults,
)
from perceiver_io_tpu.serving import (
    AdmissionController,
    Autoscaler,
    AutoscalePolicy,
    CallbackPool,
    LocalReplica,
    PriorityClass,
    ReplicaApp,
    ReplicaSupervisor,
    Router,
    TokenBucket,
    parse_priority_classes,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_replica(name, scale=2.0, registry=None, **engine_kw):
    """One in-process replica over a trivial jitted apply fn (the
    test_fabric idiom: the control loop is model-agnostic)."""

    def infer(p, x):
        return x * p

    engines = {
        "infer": ServingEngine(infer, np.float32(scale), max_batch=4,
                               name=f"{name}-infer",
                               **({"registry": registry}
                                  if registry is not None else {}),
                               **engine_kw)
    }
    app = ReplicaApp(engines, np.float32(scale), name=name,
                     assume_ready=True,
                     **({"registry": registry}
                        if registry is not None else {}))
    return LocalReplica(app)


def _router(replicas, **kw):
    kw.setdefault("scrape_interval_s", 0.02)
    kw.setdefault("registry", obs.MetricsRegistry())
    return Router(replicas, **kw)


@pytest.fixture
def x():
    return np.ones((2, 3), np.float32)


# -- units: token bucket + WFQ ------------------------------------------------


def test_token_bucket_rate_and_burst():
    b = TokenBucket(rate_per_s=10.0, burst=5.0, now=0.0)
    # a fresh bucket holds a full burst
    assert all(b.try_take(now=0.0) for _ in range(5))
    assert not b.try_take(now=0.0)
    # refill at the sustained rate, capped at the burst ceiling
    assert b.try_take(now=0.1)  # 1 token accrued
    assert not b.try_take(now=0.1)
    assert sum(b.try_take(now=10.0) for _ in range(8)) == 5  # capped at burst
    with pytest.raises(ValueError):
        TokenBucket(rate_per_s=0.0, burst=1.0)
    with pytest.raises(ValueError):
        TokenBucket(rate_per_s=1.0, burst=0.5)


def test_wfq_shares_service_by_weight():
    """Under backlog, pops interleave classes proportionally to weight
    (start-time fair queueing), FIFO within a class."""
    adm = AdmissionController(
        classes=[PriorityClass("gold", weight=4.0),
                 PriorityClass("bronze", weight=1.0)],
        queue_limit=1000, registry=obs.MetricsRegistry())
    for i in range(100):
        t = adm.admit(priority="gold")
        adm.enqueue(t, ("gold", i))
    for i in range(100):
        t = adm.admit(priority="bronze")
        adm.enqueue(t, ("bronze", i))
    popped = [adm.pop()[1][0] for _ in range(50)]
    gold = [p for p in popped if p[0] == "gold"]
    bronze = [p for p in popped if p[0] == "bronze"]
    # 4:1 weights → ~40 gold / ~10 bronze among the first 50
    assert len(gold) == pytest.approx(40, abs=2), (len(gold), len(bronze))
    # FIFO within each class
    assert [g[1] for g in gold] == sorted(g[1] for g in gold)
    assert [b[1] for b in bronze] == sorted(b[1] for b in bronze)
    # idle queue → None, and drain_queue empties the rest
    drained = adm.drain_queue()
    assert len(drained) == 150
    assert adm.pop() is None
    assert adm.queued() == 0


def test_parse_priority_classes_and_validation():
    assert [c.weight for c in parse_priority_classes("a:2,b")] == [2.0, 1.0]
    with pytest.raises(ValueError):
        parse_priority_classes("a:1,a:2")
    with pytest.raises(ValueError):
        AdmissionController(classes=[PriorityClass("x")], default_class="y",
                            registry=obs.MetricsRegistry())
    with pytest.raises(ValueError):
        PriorityClass("x", weight=0.0)


def test_admission_sheds_with_reason_and_burns_own_class():
    """Over-quota sheds carry reason='quota' and burn the CLIENT'S class
    SLO; a full class queue sheds reason='class_queue_full' while the
    other class's slots stay free."""
    reg = obs.MetricsRegistry()
    adm = AdmissionController(
        classes=[PriorityClass("gold", weight=4.0),
                 PriorityClass("bronze", weight=1.0)],
        default_class="gold",
        quota=(10.0, 2.0),
        client_classes={"abuser": "bronze"},
        queue_limit=10,  # gold share 8, bronze share 2
        slo=obs.SLO(latency_target_s=0.1, name="adm"),
        registry=reg)
    now = time.monotonic()
    # the abuser's burst (2 tokens) admits, the third sheds on quota
    for _ in range(2):
        adm.admit(client="abuser", now=now)
    with pytest.raises(RejectedError) as ei:
        adm.admit(client="abuser", now=now)
    assert ei.value.reason == "quota"
    # the quota shed burned the ABUSER'S class only: gold is untouched
    assert adm.stats()["slo_burn"]["bronze"] > 0.0
    assert adm.stats()["slo_burn"]["gold"] == 0.0
    # quota-less traffic (no client id) never draws a bucket; gold's share
    # of the queue (8 of 10) fills, then sheds name the class bound — while
    # bronze's 2 slots stay ITS slots (the abuser's earlier admits hold
    # them: the bound is per-class, not global)
    t_gold = [adm.admit(priority="gold", now=now) for _ in range(8)]
    with pytest.raises(RejectedError) as ei:
        adm.admit(priority="gold", now=now)
    assert ei.value.reason == "class_queue_full"
    assert "gold" in str(ei.value)
    # gold's own shed burns gold's budget — self-inflicted, by design
    for t in t_gold:
        adm.on_result(t, 0.01, ok=True)
    stats = adm.stats()
    assert stats["slo_burn"]["bronze"] > 0.0
    assert stats["slo_burn"]["gold"] > 0.0
    assert stats["shed"]["bronze:quota"] == 1
    assert stats["shed"]["gold:class_queue_full"] == 1
    assert stats["classes"]["gold"]["queue_limit"] == 8
    assert stats["classes"]["bronze"]["queue_limit"] == 2
    adm.close()


# -- router integration: noisy neighbor ---------------------------------------


def test_router_admission_isolates_noisy_neighbor(x):
    """The tier-1 noisy-neighbor pin: an abuser flooding past its quota
    sheds in ITS class while the victim's requests all complete and the
    victim's class burns nothing."""
    reg = obs.MetricsRegistry()
    adm = AdmissionController(
        classes=[PriorityClass("gold", weight=4.0),
                 PriorityClass("bronze", weight=1.0)],
        client_quotas={"abuser": (50.0, 8.0)},  # the victim is unlimited
        queue_limit=400,
        slo=obs.SLO(latency_target_s=5.0, name="nn"),
        registry=reg)
    r0, r1 = _make_replica("nn0", registry=reg), _make_replica(
        "nn1", registry=reg)
    router = _router([r0, r1], registry=reg, admission=adm)
    try:
        victim_futs, abuser_shed, abuser_futs = [], 0, []
        for i in range(120):
            # the abuser floods 4x the victim's rate from one client id
            for _ in range(2):
                try:
                    abuser_futs.append(router.submit(
                        x, client="abuser", priority="bronze"))
                except RejectedError as e:
                    assert e.reason in ("quota", "class_queue_full")
                    abuser_shed += 1
            if i % 2 == 0:
                victim_futs.append(router.submit(
                    x, client="victim", priority="gold"))
        for f in victim_futs:  # every victim request completes
            np.testing.assert_allclose(f.result(timeout=30), x * 2.0)
        for f in abuser_futs:
            f.result(timeout=30)
        assert abuser_shed > 0  # the flood DID overrun the quota
        stats = adm.stats()
        assert stats["slo_burn"]["gold"] == 0.0  # the victim paid nothing
        assert stats["slo_burn"]["bronze"] > 0.0  # the abuser paid itself
        assert stats["classes"]["gold"]["admitted"] == len(victim_futs)
    finally:
        router.close()
        r0.app.close()
        r1.app.close()


def test_router_admit_fault_site_sheds_cleanly(x):
    """The router.admit fault site: an injected raise at the gate sheds
    the request without leaking a pending slot or a queue token."""
    reg = obs.MetricsRegistry()
    adm = AdmissionController(queue_limit=8, registry=reg)
    rep = _make_replica("fs0", registry=reg)
    router = _router([rep], registry=reg, admission=adm)
    prev = faults.install(FaultInjector([
        FaultSpec(site="router.admit", kind="fatal", at=(2,))]))
    try:
        np.testing.assert_allclose(
            router.submit(x).result(timeout=30), x * 2.0)
        with pytest.raises(faults.InjectedFatalError):
            router.submit(x)
        # accounting is clean: the shed request was never pending, and the
        # next request flows
        np.testing.assert_allclose(
            router.submit(x).result(timeout=30), x * 2.0)
        assert router.stats()["pending"] == 0
        assert adm.queued() == 0
    finally:
        faults.install(prev)
        router.close()
        rep.app.close()


# -- the policy state machine (injected clock) --------------------------------


def _policy(**kw):
    kw.setdefault("rps_per_replica", 100.0)
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("window_s", 5.0)
    kw.setdefault("hold_up_s", 1.0)
    kw.setdefault("hold_down_s", 3.0)
    kw.setdefault("cooldown_up_s", 2.0)
    kw.setdefault("cooldown_down_s", 5.0)
    return AutoscalePolicy(**kw)


class _FakeRouter:
    """The autoscaler's router surface over a hand-fed series store."""

    def __init__(self):
        self.series = obs.SeriesStore()
        self.name = "fake"
        self._replicas = ["r0"]
        self.drained = []

    def replicas(self):
        return list(self._replicas)

    def statuses(self):
        return {n: {"state": "serving", "router_inflight": 0,
                    "queue_depth": 0} for n in self._replicas}

    def add_replica(self, client):
        self._replicas.append(client.name)

    def drain_replica(self, name, timeout_s=None, detach=False):
        self.drained.append(name)
        if detach:
            self._replicas.remove(name)
        return True

    def latency_exemplars(self, n=4):
        return []


class _FakeClient:
    def __init__(self, name):
        self.name = name


class _FakePool:
    def __init__(self, fail=0):
        self.spawned = 0
        self.retired = []
        self.fail = fail  # first N spawns raise

    def spawn(self):
        self.spawned += 1
        if self.spawned <= self.fail:
            raise OSError("fork failed (injected)")
        return _FakeClient(f"s{self.spawned}")

    def retire(self, name):
        self.retired.append(name)


def _feed_demand(router, rps, n_replicas, t0, now, step=0.5):
    """Write a requests_total counter ramp at ``rps`` per replica into the
    fake fleet store between monotonic stamps t0..now."""
    for i, name in enumerate(router.replicas()[:n_replicas]):
        key = obs.series_key("fleet_replica_requests_total",
                             {"fleet": router.name, "replica": name})
        t = t0
        while t <= now:
            router.series.record(key, rps * (t - t0), "counter",
                                 t=t, mono=t)
            t += step


def test_policy_validation():
    with pytest.raises(ValueError):
        _policy(rps_per_replica=0.0)
    with pytest.raises(ValueError):
        _policy(scale_down_utilization=0.8, target_utilization=0.7)
    with pytest.raises(ValueError):
        _policy(down_burn=2.0, up_burn=1.0)
    with pytest.raises(ValueError):
        _policy(min_replicas=3, max_replicas=2)
    # the capacity-fit seed: sustainable rps over the measured fleet size
    p = AutoscalePolicy.from_capacity(
        {"slo_sustainable_rps": 300.0}, replicas_measured=3)
    assert p.rps_per_replica == 100.0


def test_autoscaler_hold_down_blocks_one_tick_spike():
    """A demand spike shorter than hold_up_s never scales (the bursty-
    minute flap guard); sustained demand does — bounded by max_step — and
    the cooldown blocks an immediate second step."""
    router, pool = _FakeRouter(), _FakePool()
    auto = Autoscaler(router, pool, _policy(), registry=obs.MetricsRegistry())
    t0 = 1000.0
    # sustained 300 rps against 100 rps/replica @ 0.7 target → desired 4
    _feed_demand(router, 300.0, 1, t0 - 6.0, t0 + 4.0)
    # first tick: condition starts holding — no action yet (hold_up_s=1)
    assert auto.tick(now=t0) is None
    assert pool.spawned == 0
    # still inside the hold window
    assert auto.tick(now=t0 + 0.5) is None
    # held long enough → acts (max_step=2 bounds the jump below desired 4)
    dec = auto.tick(now=t0 + 1.2)
    assert dec is not None and dec["action"] == "scale_up"
    assert pool.spawned == 2 and len(router.replicas()) == 3
    # demand still wants 4, the hold re-arms...
    assert auto.tick(now=t0 + 1.4) is None
    # ...and even with the hold satisfied again, the cooldown (until
    # t0+3.2) blocks the second step
    assert auto.tick(now=t0 + 2.5) is None
    assert pool.spawned == 2
    # past the cooldown the held condition finally takes the last step
    dec2 = auto.tick(now=t0 + 3.3)
    assert dec2 is not None and dec2["action"] == "scale_up"
    assert len(router.replicas()) == 4
    auto.close()


def test_autoscaler_scale_down_hysteresis_and_drain():
    """Scale-down engages only after the low condition holds hold_down_s,
    via drain-then-retire (never a kill), and the dead band between the
    up/down utilization bounds never flaps."""
    router, pool = _FakeRouter(), _FakePool()
    router._replicas = ["r0", "r1", "r2"]
    auto = Autoscaler(router, pool, _policy(), registry=obs.MetricsRegistry())
    t0 = 2000.0
    # 40 rps over 3 replicas → demand/(2*100) = 0.2 < 0.45: down territory
    _feed_demand(router, 40.0 / 3, 3, t0 - 6.0, t0 + 4.0)
    assert auto.tick(now=t0) is None  # hold starts
    assert auto.tick(now=t0 + 1.0) is None  # still holding
    dec = auto.tick(now=t0 + 3.1)
    assert dec is not None and dec["action"] == "scale_down"
    assert router.drained == pool.retired  # drain-THEN-retire, same victim
    assert len(router.replicas()) == 2
    # the dead band: utilization between down (0.45) and up (0.7) bounds
    # with 2 replicas — 120 rps → desired ceil(120/70)=2 == n, and the
    # down check 120/(1*100)=1.2 > 0.45 → neither direction ever moves
    router2, pool2 = _FakeRouter(), _FakePool()
    router2._replicas = ["r0", "r1"]
    auto2 = Autoscaler(router2, pool2, _policy(),
                       registry=obs.MetricsRegistry())
    _feed_demand(router2, 60.0, 2, t0 + 14.0, t0 + 32.0)
    for dt in (0.0, 1.5, 3.5, 6.0, 10.0):
        assert auto2.tick(now=t0 + 20.0 + dt) is None
    assert pool2.spawned == 0 and pool2.retired == []
    auto.close()
    auto2.close()


def test_autoscaler_spawn_failure_backs_off_capped(x):
    """The chaos drill's core: failing spawns defer the next attempt with
    capped exponential backoff — the autoscaler never hammers spawn in a
    tight loop, and recovery resets the failure count."""
    from perceiver_io_tpu.resilience import RetryPolicy

    router = _FakeRouter()
    pool = _FakePool(fail=3)
    reg = obs.MetricsRegistry()
    auto = Autoscaler(router, pool, _policy(hold_up_s=0.0, cooldown_up_s=0.0),
                      spawn_backoff=RetryPolicy(max_retries=8, base_s=0.5,
                                                max_s=30.0, jitter=0.0),
                      registry=reg)
    t0 = 3000.0
    _feed_demand(router, 300.0, 1, t0 - 6.0, t0 + 60.0)
    dec = auto.tick(now=t0)
    assert dec["action"] == "spawn_failed" and pool.spawned == 1
    backoff1 = dec["backoff_s"]
    # inside the backoff window: NO spawn attempt despite demand
    assert auto.tick(now=t0 + backoff1 / 2) is None
    assert pool.spawned == 1
    # past it: the next attempt fires, fails again, backs off LONGER
    dec2 = auto.tick(now=t0 + backoff1 + 0.01)
    assert dec2["action"] == "spawn_failed" and pool.spawned == 2
    assert dec2["backoff_s"] > backoff1
    dec3 = auto.tick(now=t0 + backoff1 + dec2["backoff_s"] + 0.1)
    assert dec3["action"] == "spawn_failed" and pool.spawned == 3
    # recovery: the 4th attempt succeeds, failure state resets
    t_ok = t0 + backoff1 + dec2["backoff_s"] + dec3["backoff_s"] + 0.2
    dec4 = auto.tick(now=t_ok)
    assert dec4["action"] == "scale_up"
    assert reg.gauge("autoscale_spawn_backoff_s",
                     labels={"router": "fake"}).value == 0.0
    assert auto.stats()["spawn_failures"] == 3
    auto.close()


# -- end-to-end over a live router --------------------------------------------


def test_autoscaler_scales_live_fleet_up_and_down(x):
    """The closed loop over real engines: offered load grows the fleet
    (spawned replica JOINs and serves), load stops and the fleet drains
    back down — with the retired replica's gauges and series leaving the
    fleet store, and zero lost accepted requests throughout."""
    reg = obs.MetricsRegistry()
    made = []

    def spawn():
        rep = _make_replica(f"dyn{len(made)}", registry=reg)
        made.append(rep)
        return rep

    def retire(name):
        for rep in made:
            if rep.name == name:
                rep.app.close()

    first = spawn()
    router = _router([first], registry=reg)
    policy = AutoscalePolicy(
        rps_per_replica=200.0, min_replicas=1, max_replicas=3,
        window_s=2.0, hold_up_s=0.05, hold_down_s=0.2,
        cooldown_up_s=0.1, cooldown_down_s=0.2, max_step=1,
        drain_timeout_s=10.0)
    auto = Autoscaler(router, CallbackPool(spawn, retire), policy,
                      registry=reg)
    futs = []
    try:
        deadline = time.monotonic() + 20.0
        # offered load well past one replica's 200 rps fit
        while len(router.replicas()) < 2 and time.monotonic() < deadline:
            for _ in range(8):
                futs.append(router.submit(x))
            router.refresh()
            auto.tick()
            time.sleep(0.02)
        assert len(router.replicas()) >= 2, "never scaled up"
        assert auto.stats()["scale_ups"] >= 1
        assert reg.gauge("fleet_target_replicas",
                         labels={"router": router.name}).value >= 2
        for f in futs:  # nothing accepted was lost across the scale event
            np.testing.assert_allclose(f.result(timeout=30), x * 2.0)
        # demand stops → the fleet drains back to min, drain-then-retire
        deadline = time.monotonic() + 20.0
        while len(router.replicas()) > 1 and time.monotonic() < deadline:
            router.refresh()
            auto.tick()
            time.sleep(0.02)
        assert len(router.replicas()) == 1, "never scaled down"
        assert auto.stats()["scale_downs"] >= 1
        gone = [r.name for r in made if r.name not in router.replicas()]
        assert gone, "no replica retired"
        victim = gone[0]
        # the retired replica's telemetry left the fleet store with it
        assert not router.series.match(obs.series_key(
            "fleet_replica_up", {"fleet": router.name, "replica": victim}))
        snap_keys = [k for k in reg.snapshot()["gauges"]
                     if "fleet_replica_up" in k and f'"{victim}"' in k]
        assert snap_keys == []
        assert int(router.stats()["failed"]) == 0  # lost_accepted == 0
    finally:
        auto.close()
        router.close()
        for rep in made:
            rep.app.close()


def test_autoscale_chaos_injected_spawn_failure_no_flap(x):
    """The acceptance chaos drill (satellite 1): PIT-FAULTS-style injected
    spawn failure at autoscale.scale → backoff engages, the fleet never
    flaps (no retire follows the failed grow), and lost_accepted stays 0."""
    reg = obs.MetricsRegistry()
    made = []

    def spawn():
        rep = _make_replica(f"cx{len(made)}", registry=reg)
        made.append(rep)
        return rep

    first = spawn()
    router = _router([first], registry=reg)
    policy = AutoscalePolicy(
        rps_per_replica=200.0, min_replicas=1, max_replicas=2,
        window_s=2.0, hold_up_s=0.0, hold_down_s=5.0,
        cooldown_up_s=0.0, cooldown_down_s=5.0, max_step=1)
    auto = Autoscaler(router, CallbackPool(spawn), policy, registry=reg)
    prev = faults.install(FaultInjector([
        FaultSpec(site="autoscale.scale", kind="transient", at=(1,))]))
    futs = []
    try:
        replica_counts = set()
        spawned_ok = False
        deadline = time.monotonic() + 20.0
        while not spawned_ok and time.monotonic() < deadline:
            for _ in range(8):
                futs.append(router.submit(x))
            router.refresh()
            dec = auto.tick()
            replica_counts.add(len(router.replicas()))
            if dec is not None and dec["action"] == "scale_up":
                spawned_ok = True
            time.sleep(0.02)
        st = auto.stats()
        assert st["spawn_failures"] == 1  # the injected failure fired
        assert spawned_ok, "never recovered past the injected spawn failure"
        assert st["scale_downs"] == 0  # no flap: growth pressure never
        # produced a retire, and the count moved monotonically 1 → 2
        assert replica_counts <= {1, 2}
        for f in futs:
            np.testing.assert_allclose(f.result(timeout=30), x * 2.0)
        assert int(router.stats()["failed"]) == 0  # lost_accepted == 0
    finally:
        faults.install(prev)
        auto.close()
        router.close()
        for rep in made:
            rep.app.close()


# -- supervisor retire path (stub children: real signals, no jax) -------------

_STUB_REPLICA = textwrap.dedent("""\
    import json, signal, sys
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    port = int(sys.argv[sys.argv.index("--port") + 1])
    state = {"drained": False}

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass
        def _reply(self, body):
            body = json.dumps(body).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        def do_GET(self):
            self._reply({"replica": {"ready": True, "up": True}})
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            if self.path.startswith("/admin/drain"):
                state["drained"] = True
            self._reply({"drained": True})

    httpd = ThreadingHTTPServer(("127.0.0.1", port), H)
    httpd.daemon_threads = True
    signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
    print("stub replica on", port, file=sys.stderr, flush=True)
    httpd.serve_forever()
""")


@pytest.mark.slow  # tier-1 budget (r21): drain-then-retire scale-down
# semantics (lost_accepted == 0) stay tier-1 in the in-process
# CallbackPool autoscale tests; the real-process SIGTERM/port drill runs
# in the full tier
def test_supervisor_retire_drains_sigterms_and_releases_port(tmp_path):
    """The retire path (satellite 3): graceful drain RPC → SIGTERM exit 0
    → port released; the babysitter never restarts a retirement; and
    add_replica grows the supervised set at runtime."""
    stub = tmp_path / "stub_replica.py"
    stub.write_text(_STUB_REPLICA)

    def argv(name, port):
        return [sys.executable, str(stub), "--port", str(port),
                "--name", name]

    reg = obs.MetricsRegistry()
    sup = ReplicaSupervisor(count=1, argv_builder=argv, cpu=True,
                            poll_s=0.05, registry=reg,
                            log_dir=str(tmp_path))
    try:
        clients = sup.start()
        sup.wait_ready(timeout_s=20.0)
        # runtime growth: a second replica joins the supervised set
        extra = sup.add_replica()
        sup.wait_ready(timeout_s=20.0, names=[extra.name])
        assert {c.name for c in sup.clients()} == {clients[0].name,
                                                   extra.name}
        port = next(rep.port for n, rep in sup._replicas.items()
                    if n == extra.name)
        proc = sup._replicas[extra.name].proc
        # retire: drain-then-SIGTERM; the child's handler exits 0
        assert sup.retire(extra.name, drain_timeout_s=5.0) is True
        assert proc.poll() == 0, "SIGTERM did not produce a graceful exit 0"
        assert extra.name not in {c.name for c in sup.clients()}
        with pytest.raises(KeyError):
            sup.retire(extra.name)
        # the port is RELEASED (bindable again)
        deadline = time.monotonic() + 5.0
        while True:
            try:
                with socket.socket() as s:
                    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                    s.bind(("127.0.0.1", port))
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.1)
        # the babysitter never restarted the retirement
        time.sleep(0.3)
        assert extra.name not in sup._replicas
        # ...and its restart counter left /metrics with it (autoscale churn
        # mints new names forever — dead counters must not accumulate)
        assert not any(f'replica="{extra.name}"' in k
                       for k in reg.snapshot()["counters"])
        # the surviving replica is untouched
        assert clients[0].scrape().get("ready")
    finally:
        sup.stop(timeout_s=10.0)


def test_serve_cli_autoscale_flag_validation():
    """serve.py refuses --autoscale without a fleet or without a MEASURED
    per-replica capacity fit (a guessed fit is how fleets flap), before
    touching any backend."""
    from perceiver_io_tpu.cli import serve

    base = ["--checkpoint", "/nonexistent", "--tokenizer", "/nonexistent",
            "--texts", "x"]
    with pytest.raises(SystemExit, match="--replicas"):
        serve.main([*base, "--autoscale",
                    "--autoscale_rps_per_replica", "100"])
    with pytest.raises(SystemExit, match="rps_per_replica"):
        serve.main([*base, "--replicas", "2", "--autoscale"])
    with pytest.raises(SystemExit, match="--replicas"):
        serve.main([*base, "--priority_classes", "gold:2,bronze:1"])
    # a process fleet off --cpu is refused before anything is spawned:
    # unpinned replica processes would all claim the same chips
    with pytest.raises(SystemExit, match="--cpu"):
        serve.main([*base, "--replicas", "2"])


@pytest.mark.slow  # tier-1 budget (r17): a real load_bench schedule run is
# ~60 s of open-loop traffic; the control loop's logic coverage is retained
# tier-1 by test_autoscaler_scales_live_fleet_up_and_down and
# test_autoscale_chaos_injected_spawn_failure_no_flap above, and the dry
# schema by test_cli.test_load_bench_dry_emits_schema_json_line
def test_load_bench_autoscale_schedule_contract():
    """The acceptance run end-to-end through the CLI: a step schedule with
    --autoscale emits ONE JSON line whose autoscale block shows the fleet
    growing and shrinking with zero lost accepted requests and fewer
    replica-seconds than the static peak fleet."""
    import json
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "load_bench.py"),
         "--cpu", "--preset", "tiny", "--replicas", "1", "--autoscale", "--schedule", "step",
         "--schedule_period_s", "3", "--max_replicas", "3"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    a = record["autoscale"]
    assert a["enabled"] and a["schedule"] == "step"
    assert a["scale_ups"] >= 1 and a["peak_replicas"] >= 2
    assert a["lost_accepted"] == 0
    assert a["replica_seconds"] < a["static_replica_seconds"]
    assert record["sweep"], "schedule segments must ride the sweep array"


def test_router_detach_removes_gauges_and_series(x):
    """Router.drain_replica(detach=True): the replica's per-replica gauges
    leave /metrics and its history leaves the fleet series store (the
    scale-down cleanup contract, pinned at the router level)."""
    reg = obs.MetricsRegistry()
    r0, r1 = _make_replica("dt0", registry=reg), _make_replica(
        "dt1", registry=reg)
    router = _router([r0, r1], registry=reg)
    try:
        for _ in range(4):
            router.submit(x).result(timeout=30)
        router.refresh()
        up_key = obs.series_key(
            "fleet_replica_up", {"fleet": router.name, "replica": "dt1"})
        assert router.series.match(up_key)
        assert any(k.startswith("fleet_") and 'replica="dt1"' in k
                   for k in reg.snapshot()["gauges"])
        assert router.drain_replica("dt1", timeout_s=10.0, detach=True)
        assert "dt1" not in router.replicas()
        assert not router.series.match(up_key)
        router.refresh()  # a post-detach sweep must not resurrect it
        assert not router.series.match(up_key)
        assert not any(k.startswith("fleet_") and 'replica="dt1"' in k
                       for k in reg.snapshot()["gauges"])
        # the tombstone: a scrape sweep that snapshotted the fleet BEFORE
        # the removal (simulated by publishing directly) must not
        # re-register the retired replica's gauges
        router._gauges.publish("dt1", up=1.0, queue_depth=3.0)
        assert not any(k.startswith("fleet_") and 'replica="dt1"' in k
                       for k in reg.snapshot()["gauges"])
    finally:
        router.close()
        r0.app.close()
        r1.app.close()
