"""The span readers: their arithmetic on a hand-made span list, and all six
metrics after a run of the training loop at tiny sizes on the CPU."""

import dataclasses
import time

import pytest

from benchmarks import run as run_mod, spans
from benchmarks.loops import train_fit
from benchmarks.tests import tiny

MS = 1_000_000
NEW_METRICS = ("jit_trace_lower_s.setup", "jit_backend_compile_s.setup",
               "trainer_init_s.setup", "fit_loader_next_ms.train",
               "fit_dispatch_ms.train", "fit_loop_self_ms.train")


def _span(name, start_ms, end_ms, span_id=0, parent=None, **fields):
    return {"name": name, "id": span_id, "parent": parent, "thread": 1,
            "start_ns": start_ms * MS, "end_ns": end_ms * MS, "ok": True, **fields}


def _step(start_ms, span_id, parent, loader_ms=1, dispatch_ms=2, length_ms=10):
    return _span("train.step", start_ms, start_ms + length_ms, span_id, parent,
                 loader_ns=loader_ms * MS, dispatch_ns=dispatch_ms * MS)


def _hand_made():
    """Set-up (nested traces, a lower, a cache load, the Trainer's init), a
    warm-up fit, the window's fit of 40 iterations with one 5 s wait in the
    loader, and what the reference compiles after the window."""
    made = [
        _span("trainer.init", 0, 300, 1),
        _span("jax.trace", 1000, 3000, 2),        # the step's trace ...
        _span("jax.trace", 1200, 1500, 3),        # ... and two inner jits
        _span("jax.trace", 2000, 2900, 4),        # inside it
        _span("jax.lower", 3000, 4000, 5),
        _span("jax.backend_compile", 4000, 4700, 6, cache_hit=True),
        _span("train.fit", 5000, 5100, 7),
        _step(5010, 8, parent=7, loader_ms=50),
        _span("train.fit", 6000, 12000, 9),
    ]
    for i in range(40):
        made.append(_step(6000 + 10 * i, 100 + i, parent=9,
                          loader_ms=5000 if i == 17 else 1))
    made += [  # after the window: the reference's programs
        _span("jax.trace", 13000, 15000, 200),
        _span("jax.lower", 15000, 16000, 201),
        _span("jax.backend_compile", 16000, 19000, 202, cache_hit=False),
    ]
    return made


def test_window_is_the_last_fit_and_setup_ends_where_it_begins():
    made = _hand_made()
    fit = spans.window_fit(made)
    assert fit["id"] == 9
    early = spans.before(made, fit["start_ns"])
    assert {s["id"] for s in early} == {1, 2, 3, 4, 5, 6, 7, 8}
    # nested trace spans are not counted twice: 2.0 s of trace + 1.0 s of lower
    assert spans.union_s(spans.named(early, "jax.trace", "jax.lower")) == pytest.approx(3.0)
    assert sum(s["end_ns"] - s["start_ns"]
               for s in spans.named(early, "jax.trace", "jax.lower")) == 4200 * MS
    # the reference's 3 s compile after the window is left out
    assert spans.union_s(spans.named(early, "jax.backend_compile")) == pytest.approx(0.7)
    assert spans.union_s(spans.named(early, "trainer.init")) == pytest.approx(0.3)


def test_one_long_wait_does_not_move_the_median():
    steps = spans.window_steps(_hand_made())
    assert len(steps) == 40 and all(s["parent"] == 9 for s in steps)
    assert spans.median_ms(steps, "loader_ns") == pytest.approx(1.0)
    assert sum(s["loader_ns"] for s in steps) / len(steps) / MS > 100  # the mean is moved
    assert spans.median_ms(steps, "dispatch_ns") == pytest.approx(2.0)
    assert spans.median_ms(steps, spans.self_ns) == pytest.approx(10 - 1 - 2)


def test_dispatch_is_read_where_the_host_ran_ahead():
    """A full queue holds a dispatch back for one device step: 32 iterations
    of 3 ms run ahead, then 68 of 40 ms wait for the device (a log interval of
    100 steps). The median over all reads the device; over those that ran
    ahead, the host. With no short iteration all are read."""
    steps = [_step(3 * i, i, 9, dispatch_ms=2, length_ms=3) for i in range(32)]
    steps += [_step(96 + 40 * i, 32 + i, 9, dispatch_ms=39, length_ms=40)
              for i in range(68)]
    assert spans.median_ms(steps, "dispatch_ns") == pytest.approx(39.0)
    ahead = spans.ran_ahead(steps)
    assert [s["id"] for s in ahead] == list(range(32))
    assert spans.median_ms(ahead, "dispatch_ns") == pytest.approx(2.0)
    even = steps[32:]
    assert spans.ran_ahead(even) == even and spans.ran_ahead([]) == []
    window = [_span("train.fit", 0, 3000, 9)] + steps
    import unittest.mock

    with unittest.mock.patch.object(spans, "program_spans", lambda: window):
        assert run_mod.read_metric("fit_dispatch_ms.train", {}) == pytest.approx(2.0)


def test_no_fit_span_gives_none(monkeypatch):
    made = [s for s in _hand_made() if s["name"] != "train.fit"]
    assert spans.window_fit(made) is None and spans.window_steps(made) == []
    assert spans.median_ms([], "loader_ns") is None
    monkeypatch.setattr(spans, "program_spans", lambda: made)
    for name in NEW_METRICS:
        assert run_mod.read_metric(name, {}) is None
    # a program that keeps no spans (the parent of the PR that added them)
    from perceiver_io_tpu.obs import tracing

    monkeypatch.undo()
    monkeypatch.delattr(tracing, "spans")
    assert spans.program_spans() == []
    for name in NEW_METRICS:
        assert run_mod.read_metric(name, {}) is None


@pytest.mark.parametrize("make", [tiny.mlm, tiny.images], ids=["mlm", "images"])
def test_six_metrics_after_a_tiny_run(make, monkeypatch):
    """After ``train_fit.run`` the process holds the window's ``train.fit``
    span: as long as the loop's own window (the program's clock against the
    benchmark's), with exactly ``steps`` ``train.step`` records; all six
    readers return numbers, and the named set-up parts fit inside set-up."""
    from perceiver_io_tpu.obs.watchdog import install_compile_counter

    install_compile_counter()  # as benchmarks.run does: the listener enters jax.*
    cell, cfg, mix, builder = make("float32")
    start, start_ns = time.monotonic(), time.monotonic_ns()
    probes = run_mod.Probes(clock=lambda: time.monotonic() - start, compiles=lambda: 0)


    def log_every_step(trainer):
        # each iteration then reads its loss, so fit ends with the device, as
        # the loop's window does (at the default cadence the CPU's queue holds
        # steps that outlast fit's return and only the loop's final sync sees)
        trainer.config = dataclasses.replace(trainer.config, log_every_n_steps=1)

    result = train_fit.run(cell, cfg, mix, builder, 2**31 + 29, 0.5, False, probes,
                           break_program=log_every_step)
    assert result["verdict"]["correct"]

    # one process is one run for the benchmark; here it is several tests
    everything = spans.program_spans()
    assert not everything.dropped
    made = [s for s in everything if s["start_ns"] >= start_ns]
    monkeypatch.setattr(spans, "program_spans", lambda: made)
    fit = spans.window_fit(made)
    fit_s = (fit["end_ns"] - fit["start_ns"]) / 1e9
    assert abs(fit_s - result["window_s"]) <= max(0.02 * result["window_s"], 0.005)
    steps = spans.window_steps(made)
    assert len(steps) == result["steps"]
    for s in steps:
        assert 0 <= spans.self_ns(s) <= s["end_ns"] - s["start_ns"]

    values = {name: run_mod.read_metric(name, dict(result)) for name in NEW_METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["jit_trace_lower_s.setup"] > 0
    assert values["jit_backend_compile_s.setup"] > 0
    assert values["trainer_init_s.setup"] > 0
    assert values["fit_dispatch_ms.train"] > 0
    named_s = sum(values[n] for n in NEW_METRICS[:3])
    assert named_s < result["setup_s"]
    # the inside twin of the benchmark's own loader span: the Trainer's wait
    # covers the loader's, iteration by iteration
    inside_s = sum(s["loader_ns"] for s in steps) / 1e9
    assert inside_s >= 0.5 * result["loader_wait_s"]
