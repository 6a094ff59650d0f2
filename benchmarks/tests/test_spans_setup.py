"""The three readers that split ``setup_s`` by the program's own spans
(``import_s.setup``, ``setup_fit_s.setup``, ``setup_unnamed_s.setup``): their
arithmetic on hand-made spans, and all three after a tiny run of the loop."""

import time

import pytest

from benchmarks import run as run_mod, spans, spans_setup
from benchmarks.loops import train_fit
from benchmarks.tests import tiny
from benchmarks.tests.test_spans import _span, _step

SETUP_METRICS = ("import_s.setup", "setup_fit_s.setup", "setup_unnamed_s.setup")


def _tiled():
    """A start-up whose spans tile it: the package's import (jax's inside),
    ``cli.common``'s (orbax's inside), the model's build with its traces, the
    Trainer's init, three check fits and the warm-up, then the window and the
    reference's compile after it. 0 .. 20,000 ms to the window's fit."""
    return [
        _span("import", 0, 3000, 2, module="perceiver_io_tpu"),
        _span("import", 100, 1800, 1, module="jax"),
        _span("import", 3000, 7000, 4, module="perceiver_io_tpu.cli.common"),
        _span("import", 3300, 6900, 3, module="orbax.checkpoint"),
        _span("model.build", 7000, 7500, 5),            # a name no reader asks for
        _span("jax.trace", 7500, 9000, 6),
        _span("jax.lower", 9000, 9500, 7),
        _span("trainer.init", 9500, 10000, 8),
        _span("train.fit", 10000, 17000, 9),         # check step 1: the step's
        _span("jax.trace", 10100, 14000, 10, parent=9),   # trace, lowering and
        _span("jax.lower", 14000, 15000, 11, parent=9),   # cache load inside
        _span("jax.backend_compile", 15000, 16500, 12, parent=9, cache_hit=True),
        _step(16900, 13, parent=9),
        _span("train.fit", 17000, 17500, 14),
        _span("train.fit", 17500, 18000, 15),
        _span("train.fit", 18000, 20000, 16),        # the warm-up
        _span("train.fit", 20000, 30000, 17),        # the window
        _step(20000, 18, parent=17),
        _span("jax.trace", 31000, 33000, 19),        # the reference, after it
    ]


def _read(monkeypatch, made):
    monkeypatch.setattr(spans, "program_spans", lambda: made)
    return {name: run_mod.read_metric(name, {}) for name in SETUP_METRICS}


def test_nested_imports_count_once_and_tiled_setup_has_nothing_unnamed(monkeypatch):
    got = _read(monkeypatch, _tiled())
    # 3.0 + 4.0 s: jax's 1.7 s and orbax's 3.6 s lie inside and add nothing
    assert got["import_s.setup"] == pytest.approx(7.0)
    assert sum(s["end_ns"] - s["start_ns"] for s in spans.named(_tiled(), "import")) \
        == 12_300 * 1_000_000
    # the three check fits and the warm-up; the window's own fit is left out
    assert got["setup_fit_s.setup"] == pytest.approx(10.0)
    assert got["setup_unnamed_s.setup"] == pytest.approx(0.0, abs=1e-9)


def test_a_fit_that_ends_after_the_windows_start_is_left_out(monkeypatch):
    """A fit on another thread that is still running when the window's begins
    has not ended before it: no reader counts it, so its stretch is a hole."""
    made = [s for s in _tiled() if s["id"] != 16]
    made.append(_span("train.fit", 18000, 20500, 16))
    got = _read(monkeypatch, made)
    assert got["setup_fit_s.setup"] == pytest.approx(8.0)
    assert got["setup_unnamed_s.setup"] == pytest.approx(2.0)
    assert got["import_s.setup"] == pytest.approx(7.0)


def test_unnamed_is_the_hole_where_one_is_left(monkeypatch):
    """Work in set-up that no span covers (here: the model's build loses its
    span, and 1.5 s of the first trace) is what the metric reads, whatever
    the names around it."""
    made = [s for s in _tiled() if s["name"] != "model.build"]
    assert _read(monkeypatch, made)["setup_unnamed_s.setup"] == pytest.approx(0.5)
    made = [dict(s, start_ns=s["start_ns"] + 1_500_000_000) if s["id"] == 6 else s
            for s in made]
    got = _read(monkeypatch, made)
    assert got["setup_unnamed_s.setup"] == pytest.approx(2.0)
    assert got["import_s.setup"] == pytest.approx(7.0)  # the others do not move
    assert got["setup_fit_s.setup"] == pytest.approx(10.0)


def test_the_stretch_starts_at_the_first_span_whatever_its_name(monkeypatch):
    """What comes before the program's first span (the interpreter, the
    harness's imports, the runtime) is not the program's to name."""
    made = [dict(s, start_ns=s["start_ns"] + 50_000_000_000,
                 end_ns=s["end_ns"] + 50_000_000_000) for s in _tiled()]
    assert _read(monkeypatch, made)["setup_unnamed_s.setup"] == pytest.approx(0.0, abs=1e-9)


def test_none_without_a_fit_and_on_a_program_without_import_spans(monkeypatch):
    no_fit = [s for s in _tiled() if s["name"] != "train.fit"]
    assert _read(monkeypatch, no_fit) == dict.fromkeys(SETUP_METRICS)
    assert _read(monkeypatch, []) == dict.fromkeys(SETUP_METRICS)
    # the parent of the PR that added `import`: its fits are spans, so the
    # set-up fits read; the other two find nothing and the line leaves them out
    parent = [s for s in _tiled() if s["name"] != "import"]
    got = _read(monkeypatch, parent)
    assert got["import_s.setup"] is None and got["setup_unnamed_s.setup"] is None
    assert got["setup_fit_s.setup"] == pytest.approx(10.0)
    # a program that keeps no spans at all (the parent of PR 29)
    from perceiver_io_tpu.obs import tracing

    monkeypatch.undo()
    monkeypatch.delattr(tracing, "spans")
    assert {n: run_mod.read_metric(n, {}) for n in SETUP_METRICS} == dict.fromkeys(SETUP_METRICS)


def test_declared_in_all_five_cells_under_their_layers():
    bench = run_mod.load_benchmark()
    cells = [c["name"] for c in bench["workloads"]]
    declared = {m["name"]: m for m in bench["per_layer"] if m["name"] in SETUP_METRICS}
    assert sorted(declared) == sorted(SETUP_METRICS)
    for m in declared.values():
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("s", "lower", "program_span", "setup_s")
        assert m["workloads"] == cells
    assert declared["setup_fit_s.setup"]["layer"] == "trainer"
    assert declared["import_s.setup"]["layer"] == declared["setup_unnamed_s.setup"]["layer"] \
        == "entry points"
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(SETUP_METRICS)


def test_three_metrics_after_a_tiny_run(monkeypatch):
    """After ``train_fit.run`` the set-up holds four ``train.fit`` spans (three
    check steps and the warm-up) before the window's; the three readers give
    numbers that fit inside set-up, and the unnamed part and the union of the
    spans make up the stretch."""
    from perceiver_io_tpu.obs.watchdog import install_compile_counter

    install_compile_counter()
    cell, cfg, mix, builder = tiny.mlm("float32")
    start, start_ns = time.monotonic(), time.monotonic_ns()
    probes = run_mod.Probes(clock=lambda: time.monotonic() - start, compiles=lambda: 0)
    result = train_fit.run(cell, cfg, mix, builder, 2**31 + 31, 0.3, False, probes)
    assert result["verdict"]["correct"]

    everything = spans.program_spans()
    assert {"perceiver_io_tpu", "perceiver_io_tpu.cli.common", "orbax.checkpoint"} \
        <= {s["module"] for s in spans.named(everything, "import")}
    # one process is one run for the benchmark; here it is several tests, and
    # an earlier one may have imported the program's modules: a millisecond of
    # `import` opens this run's timeline (the builder's lazy imports follow it
    # where this test is the first to need them)
    made = [_span("import", 0, 1, module="perceiver_io_tpu")]
    made[0].update(start_ns=start_ns - 1_000_000, end_ns=start_ns)
    made += [s for s in everything if s["start_ns"] >= start_ns]
    monkeypatch.setattr(spans, "program_spans", lambda: made)
    early, fit_start = spans_setup.stretch()
    assert len(spans.named(early, "train.fit")) == 4
    values = {name: run_mod.read_metric(name, dict(result)) for name in SETUP_METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    whole = (fit_start - made[0]["start_ns"]) / 1e9
    assert values["import_s.setup"] >= 0.001
    assert 0 < values["setup_fit_s.setup"] < result["setup_s"]
    assert values["import_s.setup"] + values["setup_fit_s.setup"] \
        + values["setup_unnamed_s.setup"] < whole + 1e-6
    assert values["setup_unnamed_s.setup"] + spans.union_s(early) == pytest.approx(whole)
