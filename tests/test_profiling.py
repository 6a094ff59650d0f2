"""utils/profiling.py (MFU accounting, deadline-guarded tracing) and
training/metrics.py next_version_dir — the previously-untested host-side
observability helpers."""

import os
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu.training.metrics import next_version_dir
from perceiver_io_tpu.utils import profiling


# -- FLOPs / MFU accounting --------------------------------------------------


def test_compiled_flops_from_cost_analysis():
    f = jax.jit(lambda a, b: a @ b)
    a, b = jnp.ones((8, 16)), jnp.ones((16, 4))
    flops = profiling.compiled_flops(f, a, b)
    # CPU XLA exposes a cost model: 8*16*4 MACs = 1024 flops (2x under some
    # conventions) — pin "positive and sane", not the backend's convention
    assert flops is not None and 512 <= flops <= 4096


def test_compiled_flops_none_on_failure():
    assert profiling.compiled_flops(lambda x: x, 1.0) is None  # not jitted


def test_device_peak_flops_unknown_device_is_none():
    # the CPU backend's device_kind is not in the public TPU peak table
    assert profiling.device_peak_flops() is None
    assert profiling.mfu(1e12, 0.1) is None  # unknown peak → undefined MFU


def test_device_peak_flops_known_kinds(monkeypatch):
    class FakeDevice:
        device_kind = "TPU v5e"

    assert profiling.device_peak_flops(FakeDevice()) == 197e12


def test_mfu_arithmetic(monkeypatch):
    monkeypatch.setitem(profiling._PEAKS, "cpu", (1e12, 1e11))
    # 5e11 flops in 1s on a 1e12-peak chip = 50%
    assert profiling.mfu(5e11, 1.0) == pytest.approx(0.5)
    # whole-program flops over 2 chips: peak doubles
    assert profiling.mfu(5e11, 1.0, num_devices=2) == pytest.approx(0.25)
    assert profiling.mfu(5e11, 0.0) is None  # degenerate step time


# -- call_with_deadline / deadline-guarded trace -----------------------------


def test_call_with_deadline_completes_and_times_out():
    ok, result = profiling.call_with_deadline(lambda: 41 + 1, 5.0)
    assert ok and result == 42
    ok, result = profiling.call_with_deadline(lambda: 7, None)  # inline path
    assert ok and result == 7

    release = threading.Event()
    try:
        t0 = time.monotonic()
        ok, result = profiling.call_with_deadline(
            lambda: release.wait(30), 0.2, "wedged")
        assert not ok and result is None
        assert time.monotonic() - t0 < 5  # returned at the deadline, not 30s
    finally:
        release.set()

    with pytest.raises(ZeroDivisionError):  # errors inside fn propagate
        profiling.call_with_deadline(lambda: 1 / 0, 5.0)


def test_trace_degrades_on_wedged_start(tmp_path, monkeypatch):
    """A hanging start_trace must not freeze the caller: the
    context yields after the deadline with a warning, and the body runs."""
    release = threading.Event()
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda logdir: release.wait(30)
    )
    stopped = []
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: stopped.append(1)
    )
    ran = []
    try:
        t0 = time.monotonic()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with profiling.trace(str(tmp_path), deadline_s=0.2):
                ran.append(1)
        assert time.monotonic() - t0 < 10
        assert ran
        assert any("start_trace" in str(w.message) for w in caught)
    finally:
        release.set()


def test_trace_real_roundtrip(tmp_path):
    """The undamaged path still captures a real (CPU) trace."""
    with profiling.trace(str(tmp_path / "tr"), deadline_s=60.0):
        jax.jit(lambda x: x * 2)(jnp.ones((4,))).block_until_ready()
    profile_dir = tmp_path / "tr" / "plugins" / "profile"
    assert profile_dir.is_dir() and any(profile_dir.iterdir())


# -- next_version_dir --------------------------------------------------------


def test_next_version_dir_picks_smallest_unused(tmp_path):
    logdir = str(tmp_path)
    first = next_version_dir(logdir, "exp")
    assert first.endswith(os.path.join("exp", "version_0"))
    assert os.path.isdir(first)
    # existing versions (with gaps and junk) → max + 1, junk ignored
    os.makedirs(os.path.join(logdir, "exp", "version_7"))
    os.makedirs(os.path.join(logdir, "exp", "not_a_version"))
    open(os.path.join(logdir, "exp", "version_x"), "w").close()
    nxt = next_version_dir(logdir, "exp")
    assert nxt.endswith("version_8")
    # a different experiment starts fresh
    other = next_version_dir(logdir, "other")
    assert other.endswith(os.path.join("other", "version_0"))
