"""Zero-copy replica transport (r22): the raw array codec, the shmem slot
state machine, and ONE parametrized fabric-contract suite that runs the r12
wire contract — classification round-trip, session pins, trace propagation, phase
attribution, drain, piggybacked health, at-most-once — identically over all
three transports (http / uds / shmem).

Tier-1 coverage is IN-PROCESS (real sockets + real shared memory, but one
process); the real-fleet kill -9 drills per transport are ``slow``-marked,
each naming the tier-1 test that retains its logic coverage.
"""

import json
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.inference import ServingEngine
from perceiver_io_tpu.resilience import (
    AffinityLost,
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
)
from perceiver_io_tpu.serving.supervisor import default_replica_argv
from perceiver_io_tpu.serving.transport import (
    FREE,
    LOST,
    READING,
    READY,
    TRANSPORTS,
    WRITING,
    SlotRing,
    attach_slab,
    create_slab,
    make_client,
    pack_raw_arrays,
    raw_arrays_nbytes,
    read_raw_arrays,
    serve_transport,
    shm_slab_name,
    uds_path_for,
    write_raw_arrays,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_replica(name, scale=2.0, **engine_kw):
    """One in-process replica over trivial jitted apply fns (the fabric's
    transport layer is model-agnostic and tier-1 time is precious)."""

    def infer(p, x):
        return x * p

    def encode(p, x):
        return x + p

    def decode(p, latents, positions):
        return latents * positions

    engines = {
        kind: ServingEngine(fn, np.float32(scale), max_batch=4,
                            name=f"{name}-{kind}", **engine_kw)
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


# -- raw array codec (the framed wire format) ---------------------------------


def test_raw_codec_roundtrip_preserves_dtype_and_shape():
    """Every array shape class the engines emit survives the framed codec:
    0-d scalars (np.ascontiguousarray would promote them to 1-d — the
    guarded path must not), empty arrays, bools, and non-contiguous inputs."""
    arrays = [
        np.arange(6, dtype=np.float32).reshape(2, 3),
        np.float64(3.5).reshape(()),          # 0-d
        np.empty((0, 3), np.float64),          # empty
        np.array([True, False, True]),
        np.arange(12, dtype=np.int32).reshape(3, 4).T,  # non-contiguous
        np.arange(4, dtype=np.float16),
    ]
    buf = pack_raw_arrays(arrays)
    out = read_raw_arrays(buf)
    assert len(out) == len(arrays)
    for a, b in zip(arrays, out):
        assert b.dtype == a.dtype and b.shape == a.shape
        assert np.array_equal(b, np.asarray(a))
    assert out[1].shape == ()  # the 0-d guard held
    out[0][0, 0] = 99.0  # copy=True arrays are owned and writable


def test_raw_codec_zero_copy_views_alias_the_buffer():
    """copy=False returns frombuffer views INTO the buffer — the shmem
    read path: mutating the slab under a held slot changes the view."""
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    backing = bytearray(raw_arrays_nbytes([a]))
    n = write_raw_arrays(memoryview(backing), [a])
    view = read_raw_arrays(memoryview(backing)[:n], copy=False)[0]
    assert view.base is not None  # a view, not an owned copy
    assert np.array_equal(view, a)
    struct_off = len(backing) - a.nbytes  # payload bytes sit at the tail
    backing[struct_off:struct_off + 4] = np.float32(42.0).tobytes()
    assert view[0, 0] == 42.0  # the view saw the slab write


def test_write_raw_arrays_rejects_oversized_payload():
    a = np.ones((8, 8), np.float32)
    with pytest.raises(ValueError, match="exceeds buffer"):
        write_raw_arrays(memoryview(bytearray(16)), [a])


# -- SlotRing: the shmem slot state machine -----------------------------------


def _ring(slots=3, slot_bytes=64):
    shm = types.SimpleNamespace(
        buf=bytearray(64 + slots * slot_bytes), close=lambda: None)
    return SlotRing(shm, slots, slot_bytes)


def test_slot_ring_forward_transitions_and_release():
    ring = _ring()
    idx = ring.acquire(timeout_s=0.1)
    assert ring.counts()[WRITING] == 1
    ring.mark_ready(idx)
    ring.mark_reading(idx)
    ring.release(idx)
    assert ring.counts() == {FREE: 3}
    ring.release(idx)  # idempotent: double release is a no-op
    assert ring.counts() == {FREE: 3}


def test_slot_ring_illegal_transition_raises():
    """An out-of-order touch is a protocol bug, not a recoverable state."""
    ring = _ring()
    idx = ring.acquire(timeout_s=0.1)
    with pytest.raises(RuntimeError, match="illegal slot transition"):
        ring.mark_reading(idx)  # WRITING -> READING skips READY
    ring.mark_ready(idx)
    with pytest.raises(RuntimeError, match="illegal slot transition"):
        ring.mark_ready(idx)  # READY -> READY replays


def test_slot_ring_quarantine_survives_release():
    """A LOST slot (response never arrived on a live connection — the
    replica may still write into it) is never handed to a new request;
    only invalidate() reclaims it."""
    ring = _ring(slots=2)
    idx = ring.acquire(timeout_s=0.1)
    ring.mark_ready(idx)
    ring.quarantine(idx)
    ring.release(idx)  # the call's finally-release must NOT free it
    assert ring.counts()[LOST] == 1
    other = ring.acquire(timeout_s=0.1)
    assert other != idx
    ring.release(other)
    ring.invalidate()
    assert ring.counts() == {FREE: 2}


def test_slot_ring_acquire_times_out_under_pressure():
    ring = _ring(slots=2)
    held = [ring.acquire(timeout_s=0.1) for _ in range(2)]
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="no free shmem slot"):
        ring.acquire(timeout_s=0.05)
    assert time.monotonic() - t0 < 2.0
    for idx in held:
        ring.release(idx)
    assert ring.acquire(timeout_s=0.1) in held


def test_slot_ring_views_are_disjoint():
    ring = _ring(slots=2, slot_bytes=32)
    a, b = ring.acquire(timeout_s=0.1), ring.acquire(timeout_s=0.1)
    va, vb = ring.view(a), ring.view(b)
    va[:] = b"\xaa" * 32
    vb[:] = b"\xbb" * 32
    assert bytes(va) == b"\xaa" * 32  # no overlap tore the first slot


# -- slab geometry discovery --------------------------------------------------


def test_slab_header_geometry_discovery():
    """Clients DISCOVER slots/slot_bytes from the slab header rather than
    assuming them; a torn/foreign segment (bad magic) is a ConnectionError."""
    port = 49000 + (os.getpid() % 1000)
    slab = create_slab(port, slots=3, slot_bytes=128)
    try:
        shm, slots, slot_bytes = attach_slab(port)
        assert (slots, slot_bytes) == (3, 128)
        shm.close()
        slab.buf[0:8] = b"GARBAGE!"  # tear the magic
        with pytest.raises(ConnectionError, match="no geometry header"):
            attach_slab(port)
    finally:
        slab.unlink()
        slab.close()


def test_endpoint_names_keyed_by_port():
    """uds path and slab name derive from the replica's (host-unique) HTTP
    port, so a restart on the same port lands on the same endpoints."""
    assert uds_path_for(1234).endswith("pit-uds-1234.sock")
    assert uds_path_for(1234, root="/x") == "/x/pit-uds-1234.sock"
    assert shm_slab_name(1234) == "pit_shm_1234"


def test_default_replica_argv_carries_transport():
    argv = default_replica_argv("r0", 1234, extra=("--cpu",),
                                transport="shmem")
    assert argv[argv.index("--transport") + 1] == "shmem"
    assert argv[-1] == "--cpu"
    assert "--transport" not in default_replica_argv("r0", 1234)


# -- the fabric contract, identical over all three transports -----------------


class _Fabric:
    """One live in-process replica serving HTTP plus the selected data
    plane, and the matching router-side client."""

    def __init__(self, transport, slots=4, slot_bytes=1 << 16, **app_kw):
        self.transport = transport
        self.rep = _make_replica(f"t-{transport}", **app_kw)
        self.server = ReplicaServer(self.rep.app)
        self.server.start()
        self.extra = serve_transport(self.rep.app, transport,
                                     self.server.port, slots=slots,
                                     slot_bytes=slot_bytes)
        self.client = make_client(transport, f"t-{transport}",
                                  self.server.port, timeout_s=30)

    def close(self):
        self.client.close()
        if self.extra is not None:
            self.extra.close()
        self.server.close()
        self.rep.app.close()


@pytest.fixture(params=TRANSPORTS)
def fabric(request):
    fab = _Fabric(request.param)
    yield fab
    fab.close()


def test_transport_contract_roundtrip(fabric, x):
    """The r12 wire contract over every transport: arrays round-trip,
    sessions stay resident (and AffinityLost mirrors for unknown pins),
    admin verbs work, drain rejects with the draining classification, and phases
    ride the response metadata."""
    from perceiver_io_tpu.inference.engine import PHASES

    client = fabric.client
    meta = {}
    out = client.call("infer", [x], meta=meta)
    assert np.allclose(out[0], 2.0)
    assert meta["phases"] and set(meta["phases"][0]) == set(PHASES)
    # session pins: encode establishes residency, decode consumes it
    ack = client.call("encode", [x], session="s1")
    assert list(ack[0]) == [2, 3]
    dec = client.call("decode", [np.ones((2, 3), np.float32)], session="s1")
    assert dec[0].shape == (2, 3)
    with pytest.raises(AffinityLost):
        client.call("decode", [np.ones((2, 3), np.float32)],
                    session="never-encoded")
    status = client.scrape()
    assert status["up"] and status["ready"]
    assert client.update_params({"kind": "scale", "factor": 0.5}) == 1
    assert np.allclose(client.call("infer", [x])[0], 1.0)
    assert client.update_params({"kind": "rollback"}) == 2
    assert client.drain(timeout_s=10)
    with pytest.raises(RejectedError, match="draining"):
        client.call("infer", [x])
    client.resume()
    assert np.allclose(client.call("infer", [x])[0], 2.0)


def test_transport_trace_headers_parent_replica_spans(fabric, x, tmp_path):
    """The propagated TraceContext parents the replica_serve span on every
    transport — the assembled-trace reconciliation the r15 pin depends on."""
    events = tmp_path / "ev.jsonl"
    obs.configure_event_log(str(events))
    try:
        ctx = obs.TraceContext.mint()
        assert np.allclose(fabric.client.call("infer", [x], trace=ctx)[0],
                           2.0)
    finally:
        obs.configure_event_log(None)
    rows = [json.loads(l) for l in open(events)]
    serves = [r for r in rows if r.get("event") == "span"
              and r.get("name") == "replica_serve"
              and r.get("trace") == ctx.trace_id]
    assert serves and serves[0]["parent"] == ctx.span_id


def test_transport_pipelined_concurrency(fabric, x):
    """16 threads over ONE client: responses are id-matched on the shared
    pipelined connections (uds/shmem) and every caller gets ITS result.
    Values are thread-distinct so a cross-matched response would be seen.
    For shmem, 16 > 4 slots also exercises pressure fallback inline."""
    errs = []

    def worker(i):
        xi = np.full((2, 3), float(i + 1), np.float32)
        try:
            for _ in range(4):
                out = fabric.client.call("infer", [xi])
                assert np.allclose(out[0], 2.0 * (i + 1))
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs, errs
    if fabric.transport == "shmem":
        assert fabric.client.ring().counts() == {FREE: 4}  # no leaks


def test_transport_dead_replica_is_reroutable_connection_error(x):
    """A dead replica raises ConnectionError on every transport — the
    failover classification's reroute class (vs DeadlineExceeded, which FAILs:
    at-most-once means never re-route work that may have executed)."""
    policy = FailoverPolicy()
    for transport in TRANSPORTS:
        fab = _Fabric(transport)
        assert np.allclose(fab.client.call("infer", [x])[0], 2.0)
        fab.close()  # server down; the client outlives it
        with pytest.raises(ConnectionError) as ei:
            fab.client.call("infer", [x])
        assert policy.should_reroute(ei.value, 1), (transport, ei.value)


# -- shmem specifics ----------------------------------------------------------


def test_shmem_oversized_payload_falls_back_inline(x):
    """Payloads that outgrow a slot ride the inline uds frames — geometry
    bounds memory, never request size — and no slot leaks either way."""
    fab = _Fabric("shmem", slots=4, slot_bytes=1 << 16)
    try:
        big = np.ones((300, 300), np.float32)  # 360 KB > 64 KB slots
        assert raw_arrays_nbytes([big]) > fab.client.ring().slot_bytes
        out = fab.client.call("infer", [big])
        assert out[0].shape == (300, 300) and np.allclose(out[0], 2.0)
        assert np.allclose(fab.client.call("infer", [x])[0], 2.0)  # slotted
        assert fab.client.ring().counts() == {FREE: 4}
    finally:
        fab.close()


def test_shmem_health_piggybacks_on_responses(x):
    """Every uds/shmem response frame carries a liveness sample — the
    router gets a fresh read with every reply, between scrapes."""
    fab = _Fabric("shmem")
    try:
        assert fab.client.health is None
        fab.client.call("infer", [x])
        assert fab.client.health is not None
        assert set(fab.client.health) == {"ready", "draining", "queue_depth"}
        assert fab.client.health["ready"] and not fab.client.health["draining"]
        assert fab.client.health_stamp > 0
    finally:
        fab.close()


def test_shmem_severed_replica_drops_ring_and_reattaches(x):
    """The restart contract: a dead replica's slab can never be reused (its
    restart creates a FRESH segment under the same name), so the client
    drops its mapping on ConnectionError and lazily re-attaches the new
    slab — with every slot FREE — once the data plane is back."""
    fab = _Fabric("shmem")
    try:
        assert np.allclose(fab.client.call("infer", [x])[0], 2.0)
        assert fab.client.ring() is not None
        fab.extra.close()  # the data plane dies (slab unlinked)
        with pytest.raises(ConnectionError):
            fab.client.call("infer", [x])
        assert fab.client._ring is None  # mapping dropped, not reused
        # the replica restarts its data plane on the same port
        fab.extra = serve_transport(fab.rep.app, "shmem", fab.server.port,
                                    slots=4, slot_bytes=1 << 16)
        assert np.allclose(fab.client.call("infer", [x])[0], 2.0)
        assert fab.client.ring().counts() == {FREE: 4}  # fresh slab, no LOST
    finally:
        fab.close()


# -- fault sites --------------------------------------------------------------


def test_transport_fault_sites_registered():
    assert "transport.send" in faults.SITES
    assert "transport.recv" in faults.SITES


@pytest.mark.parametrize("site", ["transport.send", "transport.recv"])
def test_transport_fault_injection_releases_slots(site, x):
    """An injected failure on the data plane surfaces to the caller —
    raised locally (client-side send) or mirrored over the wire (the
    server's recv hook) — and, the shmem invariant, the slot held across
    the exchange is still released (the finally-release covers the error
    path). The site counter is shared by both halves of the exchange, so
    the injector is armed AFTER the warm call: the next site hit is the
    client's send (or the server's recv) of the faulted call."""
    fab = _Fabric("shmem")
    try:
        assert np.allclose(fab.client.call("infer", [x])[0], 2.0)
        prev = faults.install(FaultInjector([
            FaultSpec(site=site, kind="transient", at=(1,)),
        ]))
        try:
            with pytest.raises(Exception, match="injected"):
                fab.client.call("infer", [x])
        finally:
            faults.install(prev)
        assert fab.client.ring().counts() == {FREE: 4}, \
            "injected fault leaked a slot"
        assert np.allclose(fab.client.call("infer", [x])[0], 2.0)
    finally:
        fab.close()


# -- the no-40ms pin (satellite: pooled HTTP connections, TCP_NODELAY) --------


def test_http_small_frames_have_no_40ms_mode(x):
    """Regression pin for the delayed-ACK/Nagle interaction: small framed
    requests on the pooled HTTP connections must not show the ~40 ms
    latency mode. Warm p50 well under that bound proves TCP_NODELAY is on
    the pooled sockets (without it, this suite measured p50 >= 40 ms)."""
    rep = _make_replica("nodelay")
    server = ReplicaServer(rep.app)
    url = server.start()
    client = HttpReplicaClient("nodelay", url, timeout_s=30)
    try:
        for _ in range(3):  # warm the pool + jit
            client.call("infer", [x])
        lat = []
        for _ in range(30):
            t0 = time.monotonic()
            client.call("infer", [x])
            lat.append(time.monotonic() - t0)
        lat.sort()
        p50 = lat[len(lat) // 2]
        assert p50 < 0.035, f"p50 {p50 * 1e3:.1f} ms: the 40 ms mode is back"
    finally:
        server.close()
        rep.app.close()


# -- real-process drills (slow tier) ------------------------------------------


@pytest.mark.slow  # tier-1 budget (r22): real 2-process fleet + SIGKILL per
# transport (~60s each). The zero-lost/reroute LOGIC stays tier-1 in
# test_transport_dead_replica_is_reroutable_connection_error and
# test_shmem_severed_replica_drops_ring_and_reattaches; the wire contract in
# test_transport_contract_roundtrip. This drill adds only the real
# process/SIGKILL/slab-across-processes layer.
@pytest.mark.parametrize("transport", ["uds", "shmem"])
def test_chaos_drill_kill9_transport_fleet_zero_lost(transport):
    """kill -9 one replica mid-window with open-loop traffic on the uds or
    shmem data plane: zero lost accepted requests, the supervisor restarts
    the victim, and (shmem) no request ever lands on the stale slab."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "load_bench.py"),
         "--cpu", "--preset", "tiny", "--replicas", "2", "--replica_mode", "process",
         "--transport", transport,
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
    assert fleet["transport"] == transport
    assert fleet["killed"] is not None
    assert fleet["lost_accepted"] == 0, fleet  # the drill's verdict
    assert fleet["restarts"] >= 1
    point = record["sweep"][0]
    assert point["failed"] == 0 and point["completed"] > 0
