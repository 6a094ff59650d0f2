"""Unified runtime telemetry (perceiver_io_tpu.obs): registry, tracing,
HTTP sidecar, heartbeat health, and the in-loop self-profiling watchdog."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from perceiver_io_tpu import obs
from perceiver_io_tpu.inference import ServingEngine


# -- registry ----------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = obs.MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("depth")
    g.set(3.5)
    assert g.value == 3.5
    g.inc(-1.5)
    assert g.value == 2.0

    h = reg.histogram("lat_seconds", window=100)
    for v in range(100):
        h.observe(v / 100)
    p = h.percentiles()
    assert h.count == 100 and abs(h.sum - 49.5) < 1e-9
    assert p[0.5] == pytest.approx(0.5) and p[0.95] == pytest.approx(0.95)
    # bounded window: old observations roll off, count/sum stay lifetime
    for _ in range(200):
        h.observe(1.0)
    assert h.count == 300 and len(h.values()) == 100


def test_registry_identity_and_type_conflicts():
    reg = obs.MetricsRegistry()
    a = reg.counter("x_total", labels={"k": "1"})
    b = reg.counter("x_total", labels={"k": "1"})
    other = reg.counter("x_total", labels={"k": "2"})
    assert a is b and a is not other
    with pytest.raises(TypeError):
        reg.gauge("x_total", labels={"k": "1"})
    with pytest.raises(TypeError):  # same name, new labels, wrong kind
        reg.histogram("x_total", labels={"k": "9"})


def test_registry_thread_safety_exact_counts():
    reg = obs.MetricsRegistry()
    c = reg.counter("hammer_total")
    h = reg.histogram("hammer_seconds")

    def worker():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


def test_prometheus_text_exposition_format():
    reg = obs.MetricsRegistry()
    reg.counter("serving_requests_total", "reqs", {"engine": "e1"}).inc(7)
    reg.gauge("queue_depth", "depth").set(2)
    h = reg.histogram("lat_seconds", "latency", {"engine": "e1"})
    h.observe(0.25)
    text = reg.prometheus_text()
    assert "# TYPE serving_requests_total counter" in text
    assert 'serving_requests_total{engine="e1"} 7' in text
    assert "# TYPE queue_depth gauge" in text
    assert "queue_depth 2" in text
    assert "# TYPE lat_seconds summary" in text
    assert 'lat_seconds{engine="e1",quantile="0.5"} 0.25' in text
    assert 'lat_seconds_count{engine="e1"} 1' in text
    # every non-comment line: name{labels} value
    import re

    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        assert re.fullmatch(
            r'[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+', line
        ), line


def test_sanitize_metric_name():
    assert obs.sanitize_metric_name("val_loss") == "val_loss"
    assert obs.sanitize_metric_name("bucket64.p95") == "bucket64_p95"
    assert obs.sanitize_metric_name("9lives") == "_9lives"


def test_snapshot_shape():
    reg = obs.MetricsRegistry()
    reg.counter("a_total").inc(2)
    reg.gauge("b").set(1)
    reg.histogram("c_seconds").observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"]["a_total"] == 2
    assert snap["gauges"]["b"] == 1
    assert snap["histograms"]["c_seconds"]["count"] == 1
    json.dumps(snap)  # must stay JSON-able (the /statz body)


# -- tracing -----------------------------------------------------------------


def test_event_log_span_and_event(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure_event_log(path)
    try:
        obs.event("compile", engine="e1", bucket=4)
        with obs.span("warmup", engine="e1"):
            pass
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("x")
    finally:
        obs.configure_event_log(None)
    obs.event("after_close")  # must be a silent no-op
    rows = [json.loads(l) for l in open(path)]
    assert [r["event"] for r in rows] == ["compile", "warmup", "boom"]
    assert rows[0]["bucket"] == 4 and "t" in rows[0]
    assert rows[1]["ok"] is True and rows[1]["dur_s"] >= 0
    assert rows[2]["ok"] is False and rows[2]["error"] == "RuntimeError"


def _new_spans(since_id):
    return [r for r in obs.spans() if r["id"] > since_id]


def _last_span_id():
    return max((r["id"] for r in obs.spans()), default=0)


def test_span_records_start_end_parent_and_thread():
    """Every finished span is kept in memory with its ends on the monotonic
    clock, the span that was open around it on the same thread, and its
    thread; a span on another thread has no parent here."""
    since = _last_span_id()
    lo = time.monotonic_ns()
    with obs.span("t_outer", engine="e1"):
        with obs.span("t_inner"):
            pass

        def on_thread():
            with obs.span("t_thread"):
                pass

        worker = threading.Thread(target=on_thread)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
        obs.add_span("t_added", lo, lo + 5, n=1)
    hi = time.monotonic_ns()
    got = {r["name"]: r for r in _new_spans(since)}
    outer, inner = got["t_outer"], got["t_inner"]
    assert lo <= outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
        <= outer["end_ns"] <= hi
    assert outer["parent"] is None and inner["parent"] == outer["id"]
    assert outer["engine"] == "e1" and outer["ok"]
    assert outer["thread"] == threading.get_ident()
    assert got["t_thread"]["parent"] is None
    assert got["t_thread"]["thread"] != outer["thread"]
    # a span measured elsewhere: its own ends, the open span as its parent
    added = got["t_added"]
    assert (added["start_ns"], added["end_ns"]) == (lo, lo + 5)
    assert added["parent"] == outer["id"] and added["n"] == 1
    # oldest first, and by name
    assert [r["id"] for r in obs.spans("t_inner")][-1] == inner["id"]
    starts = [r["start_ns"] for r in obs.spans()]
    assert starts == sorted(starts)


def test_span_exception_path_and_decorator():
    since = _last_span_id()
    with pytest.raises(RuntimeError):
        with obs.span("t_boom"):
            with obs.span("t_boom_child"):
                raise RuntimeError("x")
    with obs.span("t_after"):
        pass

    @obs.span("t_decorated", kind="call")
    def work(x):
        return x + 1

    assert work(1) == 2 and work(2) == 3
    got = _new_spans(since)
    by_name = {r["name"]: r for r in got}
    assert by_name["t_boom"]["ok"] is False
    assert by_name["t_boom_child"]["ok"] is False
    assert by_name["t_boom_child"]["parent"] == by_name["t_boom"]["id"]
    # the failed spans were closed: the next one is nobody's child
    assert by_name["t_after"]["parent"] is None and by_name["t_after"]["ok"]
    calls = [r for r in got if r["name"] == "t_decorated"]
    assert len(calls) == 2 and calls[0]["id"] != calls[1]["id"]
    assert all(r["kind"] == "call" and r["ok"] for r in calls)
    # a span object used twice gives two records, neither carrying the
    # other's keys, and its own fields stay as they were given
    twice = obs.span("t_twice", kind="with")
    with twice:
        pass
    with twice:
        pass
    first, second = obs.spans("t_twice")[-2:]
    assert first["id"] != second["id"] and first is not second
    assert twice.fields == {"kind": "with"}


def test_span_buffer_is_bounded_per_name():
    """A flood of one name drops its own oldest records (counted) and can
    never evict another name's."""
    from perceiver_io_tpu.obs import tracing

    with obs.span("t_rare"):
        pass
    rare = obs.spans("t_rare")[-1]
    dropped_before = obs.spans().dropped.get("t_flood", 0)
    held_before = len(obs.spans("t_flood"))
    extra = 10
    for i in range(tracing.SPAN_DEPTH - held_before + extra):
        obs.add_span("t_flood", i, i + 1, i=i)
    flood = obs.spans("t_flood")
    assert len(flood) == tracing.SPAN_DEPTH
    assert flood.dropped["t_flood"] == dropped_before + extra
    assert flood[-1]["i"] == tracing.SPAN_DEPTH - held_before + extra - 1
    assert obs.spans("t_rare")[-1] == rare
    assert "t_rare" not in obs.spans().dropped


def test_span_jsonl_record_is_unchanged(tmp_path):
    """With a sink the span still writes ONE event with dur_s / ok / error
    and the fields, and none of the in-memory record's keys."""
    path = str(tmp_path / "events.jsonl")
    obs.configure_event_log(path)
    try:
        with obs.span("t_jsonl", engine="e1"):
            pass
        obs.add_span("t_jsonl_added", 1, 2)  # memory only
    finally:
        obs.configure_event_log(None)
    rows = [json.loads(l) for l in open(path)]
    assert len(rows) == 1
    assert set(rows[0]) == {"t", "mono", "pid", "event", "dur_s", "ok", "engine"}
    assert obs.spans("t_jsonl")[-1]["engine"] == "e1"


def test_spans_survive_concurrent_writers():
    """More writers than cores under a short switch interval: no record is
    lost (kept + dropped == written, per name) and ids stay unique."""
    import sys

    from perceiver_io_tpu.obs import tracing

    writers, each = 16, 600
    base_kept = len(obs.spans("t_shared"))
    base_dropped = obs.spans().dropped.get("t_shared", 0)
    since = _last_span_id()

    def write(k):
        for i in range(each):
            with obs.span("t_shared"):
                obs.add_span(f"t_own_{k}", i, i + 1)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write, args=(k,)) for k in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    snap = obs.spans()
    shared = [r for r in snap if r["name"] == "t_shared"]
    assert (len(shared) - base_kept) + (snap.dropped.get("t_shared", 0)
                                        - base_dropped) == writers * each
    assert len(shared) <= tracing.SPAN_DEPTH
    for k in range(writers):
        own = [r for r in snap if r["name"] == f"t_own_{k}"]
        assert len(own) == each
        # each was entered inside ITS thread's open t_shared span
        assert len({r["thread"] for r in own}) == 1
    ids = [r["id"] for r in snap if r["id"] > since]
    assert len(ids) == len(set(ids))


def test_compile_listener_enters_trace_lower_compile_spans():
    """The one jax.monitoring listener also enters the compile path's spans;
    the count of real compilations keeps its meaning."""
    counter = obs.install_compile_counter(obs.get_registry())
    since, compiles = _last_span_id(), counter.value
    lo = time.monotonic_ns()

    def long_to_trace(x):  # trace events under a millisecond are not entered
        for i in range(300):
            x = jnp.tanh(x) * (1.0 + i) + 0.25
        return x

    jax.jit(long_to_trace)(jnp.ones((5, 3))).block_until_ready()
    hi = time.monotonic_ns()
    got = _new_spans(since)
    for name in ("jax.trace", "jax.lower", "jax.backend_compile"):
        mine = [r for r in got if r["name"] == name]
        assert mine, (name, [r["name"] for r in got])
        assert all(lo <= r["end_ns"] <= hi and r["start_ns"] <= r["end_ns"]
                   for r in mine)
    built = [r for r in got if r["name"] == "jax.backend_compile"]
    assert all(r["cache_hit"] is False for r in built)  # conftest: cache off
    assert counter.value - compiles == len(built)
    # the short inner trace events (jnp.tanh's own jit, ...) are left out;
    # every lower and backend compile is in, one of each per program
    from perceiver_io_tpu.obs import watchdog

    assert all(r["end_ns"] - r["start_ns"] >= watchdog._MIN_TRACE_SPAN_NS
               for r in got if r["name"] == "jax.trace")
    assert len([r for r in got if r["name"] == "jax.lower"]) == len(built)


def test_event_log_size_capped_rotation(tmp_path):
    """A long load run cannot grow events.jsonl unboundedly: the sink
    rotates at max_bytes keeping N numbered segments, every surviving line
    stays valid JSONL, and the oldest segment is dropped."""
    path = str(tmp_path / "events.jsonl")
    log = obs.EventLog(path, max_bytes=2000, backups=2)
    try:
        for i in range(200):
            log.write({"event": "spam", "i": i})
    finally:
        log.close()
    import os

    segments = sorted(f for f in os.listdir(tmp_path)
                      if f.startswith("events.jsonl"))
    assert segments == ["events.jsonl", "events.jsonl.1", "events.jsonl.2"]
    seen = []
    for name in segments:
        p = tmp_path / name
        assert p.stat().st_size <= 2000
        for line in open(p):
            seen.append(json.loads(line)["i"])
    # newest records survive contiguously; the oldest rolled off the end
    assert max(seen) == 199
    assert sorted(seen) == list(range(min(seen), 200))
    assert min(seen) > 0  # something WAS dropped — the cap is real

    # rotation disabled: one unbounded file, nothing dropped
    path2 = str(tmp_path / "nocap.jsonl")
    log = obs.EventLog(path2, max_bytes=None)
    try:
        for i in range(50):
            log.write({"event": "spam", "i": i})
    finally:
        log.close()
    assert len(open(path2).readlines()) == 50


def test_process_metrics_refresh_at_scrape(tmp_path):
    """install_process_metrics registers RSS/uptime/threads/GC gauges that
    refresh via the registry's collector hook at every export."""
    reg = obs.MetricsRegistry()
    obs.install_process_metrics(reg)
    snap = reg.snapshot()
    g = snap["gauges"]
    assert g["process_rss_bytes"] > 1e6  # a python + jax process is > 1 MB
    assert g["process_uptime_seconds"] > 0
    assert g["process_threads"] >= 1
    assert g["process_gc_collections"] >= 0
    text = reg.prometheus_text()
    assert "# TYPE process_rss_bytes gauge" in text
    # the collector refreshes: uptime strictly advances between scrapes
    time.sleep(0.05)
    assert (reg.snapshot()["gauges"]["process_uptime_seconds"]
            > g["process_uptime_seconds"])


def test_registry_collector_errors_never_break_the_scrape():
    reg = obs.MetricsRegistry()
    reg.counter("ok_total").inc()
    calls = []
    reg.register_collector(lambda: calls.append(1))

    def broken():
        raise RuntimeError("collector bug")

    reg.register_collector(broken)
    snap = reg.snapshot()  # must not raise
    assert snap["counters"]["ok_total"] == 1 and calls
    reg.snapshot()  # the broken collector was dropped, the good one stays
    assert len(calls) == 2


# -- health / heartbeat ------------------------------------------------------


def test_heartbeat_stall_detection_and_recovery(capsys):
    diag_called = []
    hb = obs.Heartbeat(
        "t-dispatch", deadline_s=0.15,
        diagnostics=lambda: diag_called.append(1) or {"queue": 3},
    )
    try:
        assert hb.healthy()  # disarmed = healthy
        hb.arm()
        assert hb.healthy()
        time.sleep(0.4)  # no beat within deadline
        assert hb.stalled()
        ok, detail = obs.healthz()
        assert not ok and detail["heartbeats"]["t-dispatch"]["stalled"]
        # the monitor thread dumped a diagnostic snapshot exactly once
        deadline = time.monotonic() + 2
        while not diag_called and time.monotonic() < deadline:
            time.sleep(0.02)
        assert diag_called
        err = capsys.readouterr().err
        assert "STALLED" in err and "queue: 3" in err
        assert "thread" in err  # stack dump present
        hb.beat()  # a completion arrives: healthy again
        assert hb.healthy()
        hb.disarm()
    finally:
        hb.close()
    ok, _ = obs.healthz()
    assert ok  # closed heartbeats leave the aggregate


def test_healthz_empty_is_healthy():
    ok, detail = obs.healthz()
    assert ok and detail["status"] == "ok"


# -- HTTP sidecar ------------------------------------------------------------


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode(), r.headers.get("Content-Type")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), e.headers.get("Content-Type")


def test_obs_server_endpoints():
    reg = obs.MetricsRegistry()
    reg.counter("hits_total", "hits").inc(3)
    with obs.ObsServer(registry=reg, port=0) as server:
        assert server.port > 0
        code, body, ctype = _get(f"{server.url}/metrics")
        assert code == 200 and "hits_total 3" in body
        assert "text/plain" in ctype
        code, body, _ = _get(f"{server.url}/statz")
        assert code == 200
        assert json.loads(body)["counters"]["hits_total"] == 3
        code, body, _ = _get(f"{server.url}/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        code, _, _ = _get(f"{server.url}/nope")
        assert code == 404
    assert server.port is None  # closed


def test_healthz_flips_unhealthy_on_stalled_dispatch():
    """The acceptance drill: a dispatch that never completes (stalled fake
    device call) flips /healthz to 503 with the stalled heartbeat named;
    releasing the stall recovers it."""
    release = threading.Event()
    reg = obs.MetricsRegistry()

    def apply_fn(p, x):
        return x + p

    eng = ServingEngine(
        apply_fn, jnp.float32(1.0), max_batch=2, name="stall_t",
        registry=reg, heartbeat_deadline_s=0.2,
    )
    real_jitted = eng._jitted

    def stalling_jitted(p, cols):
        release.wait(30)  # the wedged device: dispatch never returns
        return real_jitted(p, cols)

    eng._jitted = stalling_jitted
    try:
        with obs.ObsServer(registry=reg, port=0) as server:
            fut = eng.submit(np.zeros((1, 2), np.float32))
            deadline = time.monotonic() + 10
            code = None
            while time.monotonic() < deadline:
                code, body, _ = _get(f"{server.url}/healthz")
                if code == 503:
                    break
                time.sleep(0.05)
            assert code == 503, body
            assert json.loads(body)["heartbeats"]["stall_t-dispatch"]["stalled"]
            release.set()  # the device un-wedges: request completes
            out = fut.result(timeout=60)
            np.testing.assert_allclose(out, 1.0)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                code, body, _ = _get(f"{server.url}/healthz")
                if code == 200:
                    break
                time.sleep(0.05)
            assert code == 200, body
    finally:
        release.set()
        eng.close()


# -- self-profiling watchdog -------------------------------------------------


def test_selfprofiler_cpu_window_publishes_host_gauges(monkeypatch):
    """On CPU the xplane analysis finds no TPU plane — the watchdog publishes
    the host step time under its own name and NO MFU, even with a peak
    patched in for the cpu device kind: a host timing never stands in under
    a device metric's name."""
    from perceiver_io_tpu.utils import profiling

    monkeypatch.setitem(profiling._PEAKS, "cpu", (1e12, 1e11))
    reg = obs.MetricsRegistry()
    prof = obs.SelfProfiler(
        every_n=2, trace_steps=2, prefix="t", registry=reg,
        # tiny fake FLOPs so mfu = flops/step_time/peak stays << 1 no
        # matter how fast the window runs
        flops_per_step=1e6, deadline_s=30.0,
    )
    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((4, 4))
    published = None
    for _ in range(8):
        f(x).block_until_ready()
        out = prof.tick(sync=lambda: None)
        if out is not None:
            published = out
            break
    assert published is not None, "no capture window closed in 8 ticks"
    assert published["selfprofile_host_step_ms"] > 0
    assert "selfprofile_mfu" not in published
    labels = {"loop": "t"}
    assert reg.gauge("selfprofile_host_step_ms", labels=labels).value > 0
    assert reg.counter("selfprofile_windows_total", labels=labels).value == 1
    # no TPU plane on CPU → the window degraded (counted) but host numbers
    # stand; device gauge untouched
    assert reg.counter("selfprofile_failures_total", labels=labels).value >= 1
    assert "selfprofile_device_step_ms" not in published


def test_selfprofiler_normalizes_multi_step_dispatches():
    """Under steps_per_dispatch=K each trace window is one K-step dispatch:
    the window must close after trace_steps DISPATCHES and publish
    per-OPTIMIZER-STEP host time (elapsed / K*dispatches), not per-dispatch
    — the r4 in-loop-MFU unit bug, pinned here for the watchdog."""
    reg = obs.MetricsRegistry()
    prof = obs.SelfProfiler(
        every_n=4, trace_steps=2, prefix="k", registry=reg, deadline_s=30.0,
    )
    K = 4
    dispatch_s = 0.05
    out = prof.tick(K)  # since_window hits every_n → window opens
    assert out is None
    time.sleep(dispatch_s)
    assert prof.tick(K) is None  # dispatch 1 of 2 — window stays open
    time.sleep(dispatch_s)
    published = prof.tick(K)  # dispatch 2 of 2 → closes, 8 steps total
    assert published is not None
    host_ms = published["selfprofile_host_step_ms"]
    # ~100ms over 8 optimizer steps ⇒ ~12.5ms/step; the per-dispatch bug
    # would report ~50ms. Midpoint bound: clearly per-step, not per-dispatch
    assert host_ms < 30, host_ms
    assert reg.counter("selfprofile_windows_total",
                       labels={"loop": "k"}).value == 1


def test_compile_counter_counts_new_shapes():
    reg = obs.get_registry()
    counter = obs.install_compile_counter(reg)
    before = counter.value
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones((3,))).block_until_ready()
    f(jnp.ones((3,))).block_until_ready()  # cache hit: no new compile
    mid = counter.value
    assert mid >= before + 1
    f(jnp.ones((7,))).block_until_ready()  # new shape: recompile
    assert counter.value >= mid + 1


# -- Trainer / MetricsLogger one-source-of-truth -----------------------------


def test_metrics_logger_publishes_registry_gauges(tmp_path):
    from perceiver_io_tpu.training.metrics import MetricsLogger, read_metrics

    reg = obs.MetricsRegistry()
    with MetricsLogger(str(tmp_path), use_tensorboard=False,
                       registry=reg) as logger:
        logger.log_scalars(7, {"train_loss": 1.25, "mfu": 0.5})
    rows = read_metrics(str(tmp_path))
    assert rows[0]["train_loss"] == 1.25
    assert reg.gauge("train_loss").value == 1.25
    assert reg.gauge("mfu").value == 0.5
    assert reg.gauge("logged_step").value == 7


def test_trainer_smoke_publishes_step_time_and_mfu_gauges(tmp_path, monkeypatch):
    """The acceptance drill: a CPU Trainer run with the watchdog on publishes
    step-time + MFU gauges through the SAME registry that feeds metrics.jsonl
    — and the jsonl rows carry the same selfprofile metrics (one source of
    truth). On CPU the device plane is absent, so only the host step-time
    gauge moves and no selfprofile MFU is published; the trainer's own
    wall-clock MFU still flows once the cost-analysis FLOPs land (peak
    patched in for the cpu device kind)."""
    from test_trainer import _make_parts

    from perceiver_io_tpu.training import Trainer, TrainerConfig
    from perceiver_io_tpu.training.metrics import read_metrics
    from perceiver_io_tpu.utils import profiling

    monkeypatch.setitem(profiling._PEAKS, "cpu", (1e12, 1e11))
    base, (train_loader, _) = _make_parts(tmp_path)
    cfg = TrainerConfig(
        max_steps=6, log_every_n_steps=2,
        logdir=str(tmp_path / "logs_sp"), experiment="sp",
        use_tensorboard=False, compute_mfu=True,
        selfprofile_every_n_steps=2, selfprofile_steps=2,
    )
    trainer = Trainer(
        base._raw_train_step, None, base.state, cfg,
        example_batch=base._example_batch,
    )
    with trainer:
        trainer.fit(train_loader)
        rows = read_metrics(trainer.run_dir)
    base.close()

    sp_rows = [r for r in rows if "selfprofile_host_step_ms" in r]
    assert sp_rows, rows
    assert sp_rows[0]["selfprofile_host_step_ms"] > 0
    assert not any("selfprofile_mfu" in r for r in rows)  # no device plane
    assert any("mfu" in r for r in rows)  # the wall-clock in-loop MFU

    reg = obs.get_registry()  # the registry MetricsLogger fed
    labels = {"loop": "train"}
    assert reg.gauge("selfprofile_host_step_ms", labels=labels).value > 0
    # the logger mirrored every jsonl scalar into the same registry
    train_rows = [r for r in rows if "train_loss" in r]
    assert reg.gauge("train_loss").value == train_rows[-1]["train_loss"]


# -- per-request phase tracing (SLO observability) ---------------------------


def test_phase_tracing_reconciles_with_end_to_end_latency(tmp_path):
    """The tentpole self-check: every served part records all six lifecycle
    phases; the per-part phase SUM reconciles with the end-to-end latency
    within 5% at p50 (acceptance bar); the phases export as
    serving_phase_seconds{phase=...} histograms AND as JSONL request_phases
    spans; stats() carries the per-phase windows in the same locked deep-copy
    as latency_s_by_bucket."""
    import statistics

    from perceiver_io_tpu.inference import ServingEngine
    from perceiver_io_tpu.inference.engine import PHASES

    events = str(tmp_path / "events.jsonl")
    obs.configure_event_log(events)
    reg = obs.MetricsRegistry()
    eng = ServingEngine(
        lambda p, x: x * p, jnp.float32(2.0), max_batch=1,
        name="phase_t", registry=reg,
        # this test pins the r11 per-part request_phases span flow; traced
        # requests ride the compact per-batch record instead (r15), pinned
        # by tests/test_fabric.py and test_reqtrace.py
        trace_sample=0.0,
    )
    try:
        futs = [eng.submit(np.ones((1, 4), np.float32)) for _ in range(24)]
        for f in futs:
            np.testing.assert_allclose(f.result(timeout=60), 2.0)
    finally:
        eng_stats = eng.stats()
        eng.close()
        obs.configure_event_log(None)

    # every future exposes its part's phase record, covering all phases
    recs = futs[0].phases
    assert len(recs) == 1 and set(recs[0]) == set(PHASES)
    assert all(v >= 0 for v in recs[0].values())

    # stats(): phase windows ride the same locked deep-copied snapshot, and
    # (max_batch=1 ⇒ one bucket, appended in completion order) align with
    # the latency window part-for-part — sum reconciles within 5% at p50
    lat = eng_stats["latency_s_by_bucket"][1]
    ph = eng_stats["phase_s"]
    assert set(ph) == set(PHASES)
    assert all(len(ph[k]) == len(lat) for k in PHASES)
    sums = [sum(vals) for vals in zip(*(ph[k] for k in PHASES))]
    ratio = statistics.median(sums) / statistics.median(lat)
    assert 0.95 <= ratio <= 1.05, ratio
    # elementwise too: each part's phase sum brackets its own latency
    for s, l in zip(sums, lat):
        assert s >= l > 0

    # mutating the snapshot never touches live state (deep copy)
    ph["device"].append(1e9)
    assert 1e9 not in eng.stats().get("phase_s", {}).get("device", [])

    # registry: one histogram per phase, observed once per part
    for phase in PHASES:
        h = reg.histogram("serving_phase_seconds",
                          labels={"engine": "phase_t", "phase": phase})
        assert h.count == 24, (phase, h.count)
    assert 0.95 <= reg.gauge(
        "serving_phase_sum_ratio", labels={"engine": "phase_t"}).value <= 1.05

    # JSONL spans: one request_phases event per part with the phase fields
    rows = [json.loads(l) for l in open(events)]
    spans = [r for r in rows if r.get("event") == "request_phases"]
    assert len(spans) == 24
    assert spans[0]["engine"] == "phase_t"
    for phase in PHASES:
        assert phase in spans[0], spans[0]
    assert spans[0]["total_s"] > 0


def test_phase_attribution_separates_queueing_from_dispatch():
    """The attribution claim itself: hold the FIRST dispatch on a gate while
    five more requests queue behind it — the held request's time lands in
    its DISPATCH phase, the queued requests' time lands in their QUEUE
    phase, and device time stays tiny for all. 'p99 is high' is now 'p99 is
    high because queueing', not a guess."""
    from perceiver_io_tpu.inference import ServingEngine

    reg = obs.MetricsRegistry()
    release = threading.Event()
    eng = ServingEngine(lambda p, x: x + p, jnp.float32(1.0), max_batch=1,
                        name="attr_t", registry=reg)
    real_jitted = eng._jitted

    def gated_jitted(p, cols):
        release.wait(30)  # blocks the first dispatch; no-op once released
        return real_jitted(p, cols)

    eng._jitted = gated_jitted
    try:
        futs = [eng.submit(np.zeros((1, 2), np.float32)) for _ in range(6)]
        time.sleep(0.3)  # the gate holds dispatch 1; parts 2..6 queue
        release.set()
        for f in futs:
            f.result(timeout=60)
        first, last = futs[0].phases[0], futs[-1].phases[0]
        assert first["dispatch"] >= 0.25, first
        assert last["queue"] >= 0.25, last
        assert last["queue"] > 10 * max(last["device"], 1e-6), last
    finally:
        release.set()
        eng.close()


# -- SLO: burn rate + capacity model -----------------------------------------


def test_slo_tracker_burn_rate_math_and_health_wire():
    reg = obs.MetricsRegistry()
    # 10% error budget, alert at burn 2.0, health live after 10 samples
    slo = obs.SLO(latency_target_s=0.1, availability_target=0.9,
                  name="unit", burn_alert=2.0, min_samples=10)
    assert slo.error_budget == pytest.approx(0.1)
    tracker = obs.SLOTracker(slo, registry=reg)
    try:
        for _ in range(8):
            tracker.record(latency_s=0.05, ok=True)   # good
        tracker.record(latency_s=0.5, ok=True)        # latency breach
        tracker.record(ok=False)                      # shed/error breach
        assert tracker.good_fraction() == pytest.approx(0.8)
        # bad fraction 0.2 over budget 0.1 = burning 2x
        assert tracker.burn_rate() == pytest.approx(2.0)
        labels = {"slo": "unit"}
        assert reg.gauge("slo_error_budget_burn_rate",
                         labels=labels).value == pytest.approx(2.0)
        assert reg.counter("slo_breaches_total",
                           labels={**labels, "reason": "latency"}).value == 1
        assert reg.counter("slo_breaches_total",
                           labels={**labels, "reason": "error"}).value == 1
        # at burn exactly 2.0 (== alert) health holds; one more bad breaches
        ok, _ = obs.healthz()
        assert ok
        tracker.record(ok=False)
        ok, detail = obs.healthz()
        assert not ok
        assert detail["sources"]["slo:unit"]["burn_rate"] > 2.0
    finally:
        tracker.close()
    ok, _ = obs.healthz()
    assert ok  # closed trackers leave the aggregate


def test_slo_tracker_health_quiet_below_min_samples():
    slo = obs.SLO(latency_target_s=0.1, availability_target=0.9,
                  burn_alert=1.0, min_samples=5, name="quiet")
    tracker = obs.SLOTracker(slo, registry=obs.MetricsRegistry())
    try:
        tracker.record(ok=False)  # 100% bad, but only 1 sample
        name, ok, detail = tracker.health_status()
        assert ok and detail["samples"] == 1
        for _ in range(5):
            tracker.record(ok=False)
        _, ok, _ = tracker.health_status()
        assert not ok
    finally:
        tracker.close()


def test_slo_validation():
    with pytest.raises(ValueError, match="latency_target_s"):
        obs.SLO(latency_target_s=0.0)
    with pytest.raises(ValueError, match="availability_target"):
        obs.SLO(latency_target_s=0.1, availability_target=1.0)


def test_fit_capacity_knee_and_slo_sustainable():
    """The capacity model over a synthetic textbook sweep: p50 floor at
    light load, p99 departing the floor past the knee, shedding at
    overload, achieved plateauing at capacity."""
    floor = 0.010
    points = [
        dict(offered_rps=100, achieved_rps=99, p50_s=floor, p99_s=0.015,
             shed_rate=0.0),
        dict(offered_rps=200, achieved_rps=198, p50_s=0.011, p99_s=0.020,
             shed_rate=0.0),
        dict(offered_rps=400, achieved_rps=390, p50_s=0.014, p99_s=0.040,
             shed_rate=0.0),
        dict(offered_rps=800, achieved_rps=610, p50_s=0.080, p99_s=0.400,
             shed_rate=0.05),   # past the knee: p99 departed, shedding
        dict(offered_rps=1600, achieved_rps=600, p50_s=0.120, p99_s=0.900,
             shed_rate=0.5),    # plateau
    ]
    slo = obs.SLO(latency_target_s=0.050, availability_target=0.99,
                  name="cap")
    fit = obs.fit_capacity(points, slo=slo)
    assert fit["service_floor_s"] == pytest.approx(floor)
    assert fit["p99_floor_s"] == pytest.approx(0.015)
    # 400 sustains (p99 0.040 < 3x floor 0.045, no shed, achieved tracks);
    # 800 does not (shedding, p99 departed)
    assert fit["knee_rps"] == 400
    assert fit["capacity_rps"] == 610
    # SLO: p99 <= 50ms and shed within the 1% budget — 400 qualifies
    assert fit["slo_sustainable_rps"] == 400
    assert fit["slo"]["name"] == "cap"

    # a sweep that starts past saturation: knee/sustainable report 0.0
    fit2 = obs.fit_capacity(points[-1:], slo=slo)
    assert fit2["knee_rps"] == 0.0 and fit2["slo_sustainable_rps"] == 0.0
    with pytest.raises(ValueError):
        obs.fit_capacity([])


def test_engine_slo_wiring_records_completions_and_sheds():
    """ServingEngine(slo=...): completions classify against the latency
    target, queue-full sheds burn the error budget, and the tracker's
    burn-rate gauge rides the engine's registry."""
    from perceiver_io_tpu.inference import ServingEngine
    from perceiver_io_tpu.resilience import RejectedError

    reg = obs.MetricsRegistry()
    release = threading.Event()

    slo = obs.SLO(latency_target_s=60.0, availability_target=0.9,
                  name="wire", burn_alert=None)
    eng = ServingEngine(lambda p, x: x + p, jnp.float32(1.0), max_batch=1,
                        name="slo_t", registry=reg, queue_limit=2, slo=slo)
    real_jitted = eng._jitted

    def gated_jitted(p, cols):
        release.wait(30)  # holds the worker so the backlog bound trips
        return real_jitted(p, cols)

    eng._jitted = gated_jitted
    try:
        futs = [eng.submit(np.zeros((1, 2), np.float32)) for _ in range(2)]
        # queue full (2 parts backlogged; the worker may have pulled one —
        # keep submitting until the bound trips)
        with pytest.raises(RejectedError):
            for _ in range(4):
                futs.append(eng.submit(np.zeros((1, 2), np.float32)))
        release.set()
        for f in futs:
            f.result(timeout=60)
        labels = {"slo": "wire", "engine": "slo_t"}
        good = reg.counter("slo_requests_total", labels=labels).value
        assert good >= 3  # completions + the shed all classified
        assert reg.counter(
            "slo_breaches_total",
            labels={**labels, "reason": "error"}).value >= 1
        assert eng.slo_tracker.good_fraction() < 1.0
    finally:
        release.set()
        eng.close()


# -- the start-up timeline: `import` spans ------------------------------------

_IMPORT_SPANS_CHILD = """
import json, sys
import perceiver_io_tpu.cli.common
from perceiver_io_tpu import obs
found = obs.spans("import")
json.dump({"spans": list(found), "dropped": obs.spans().dropped,
           "names": sorted({r["name"] for r in obs.spans()})}, sys.stdout)
"""


@pytest.fixture(scope="module")
def import_spans():
    """What ONE fresh process holds after ``import perceiver_io_tpu.cli.common``
    (the module every training entry point and benchmark builder imports
    first): its ``import`` spans, the drops, and every span name."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root}
    child = subprocess.run([sys.executable, "-c", _IMPORT_SPANS_CHILD], env=env, cwd=root,
                           capture_output=True, text=True, timeout=120)
    assert child.returncode == 0, child.stderr[-2000:]
    return json.loads(child.stdout)


def _import_span(import_spans, module):
    mine = [r for r in import_spans["spans"] if r["module"] == module]
    assert len(mine) == 1, (module, [r["module"] for r in import_spans["spans"]])
    return mine[0]


def test_import_spans_name_the_package_and_its_entry_module(import_spans):
    """The package's own import and ``cli.common``'s are spans; the package
    is imported before ``cli.common``'s body runs, so the two lie apart and
    the package's comes first."""
    package = _import_span(import_spans, "perceiver_io_tpu")
    common = _import_span(import_spans, "perceiver_io_tpu.cli.common")
    assert package["end_ns"] <= common["start_ns"]
    assert import_spans["spans"][0]["start_ns"] == package["start_ns"]  # the timeline's first
    # importing is all the process has done: nothing else is on the timeline
    assert import_spans["names"] == ["import"]


@pytest.mark.parametrize("inner, outer", [
    ("jax", "perceiver_io_tpu"),
    ("flax", "perceiver_io_tpu"),
    ("jax.experimental.pallas", "perceiver_io_tpu"),
    ("optax", "perceiver_io_tpu.cli.common"),
    ("orbax.checkpoint", "perceiver_io_tpu.cli.common"),
])
def test_third_party_import_span_lies_inside_its_importer(import_spans, inner, outer):
    """A wrapped third-party import is timed where the program first performs
    it, so its interval lies inside its importer's: readers take unions."""
    inside, around = _import_span(import_spans, inner), _import_span(import_spans, outer)
    assert around["start_ns"] <= inside["start_ns"] < inside["end_ns"] <= around["end_ns"]


def test_import_spans_are_few_whole_and_none_dropped(import_spans):
    records = import_spans["spans"]
    assert 7 <= len(records) <= 15
    assert all(r["start_ns"] < r["end_ns"] and r["ok"] for r in records)
    assert all(isinstance(r["module"], str) and r["module"] for r in records)
    assert len({r["module"] for r in records}) == len(records)  # one a module
    assert import_spans["dropped"] == {}


def test_tracing_module_still_imports_no_jax():
    """The recorder's contract (its docstring): entry points pick their
    platform before the first ``import jax``, so ``obs/tracing.py`` never
    imports it, at any depth of its source; now that the package's first
    statement imports it BEFORE jax, that is what keeps the package's order."""
    import ast

    from perceiver_io_tpu.obs import tracing

    with open(tracing.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported and "jax" not in imported and "jaxlib" not in imported
    assert not hasattr(tracing, "jax")
