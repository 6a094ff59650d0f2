"""The trace reduction on a small recorded trace: two devices' worth of
operations and step programs, and the benchmark's host spans."""

import pytest

from benchmarks import trace as T

MS = 1_000_000

# device 0: three executions of the step program (10 ms each) with 2 ms and
# 4 ms gaps, a warm-up program before them, ops covering 9 of each 10 ms
MODULES = [("jit_init", 0, 5 * MS), ("jit_train_step", 100 * MS, 10 * MS),
           ("jit_train_step", 112 * MS, 10 * MS), ("jit_train_step", 126 * MS, 10 * MS)]
OPS = [("fusion.1", 100 * MS, 6 * MS), ("dot.2", 106 * MS, 3 * MS),
       ("fusion.1", 112 * MS, 6 * MS), ("dot.2", 117 * MS, 4 * MS),  # overlaps by 1
       ("fusion.1", 126 * MS, 6 * MS), ("dot.2", 132 * MS, 3 * MS)]
HOST = [("bench.trainer_fit", 90 * MS, 60 * MS), ("bench.loader_next", 121 * MS, 4 * MS)]


def test_union_and_gaps():
    assert T.union_ns([(0, 5), (3, 8), (10, 12)]) == 10
    assert T.union_ns([]) == 0
    assert T.gaps_ns([(0, 5), (3, 8), (10, 12)]) == [(8, 10)]


def test_summary_of_recorded_trace():
    dev = T.DeviceTrace(ops=OPS, modules=MODULES[1:])
    s = T.summarize([dev], HOST)
    assert s.step_name == "jit_train_step"
    assert s.step_durations_ms == [10.0, 10.0, 10.0]
    assert s.step_gaps_ms == [2.0, 4.0]
    # window: first step's start (100) .. last op's end (135) = 35 ms
    assert s.window_s == pytest.approx(0.035)
    # busy: 9 + 9 (6 + 4 overlapping by 1) + 9 = 27 ms
    assert s.busy_s == pytest.approx(0.027)
    assert 100 * (1 - s.busy_s / s.window_s) == pytest.approx(100 * 8 / 35)
    assert s.device_ops[0] == ("fusion.1", pytest.approx(0.018))
    # the 5 ms hole 121..126 lies under the loader's span, the others under fit
    gaps = dict(s.idle_gaps)
    assert gaps["bench.loader_next"] == pytest.approx(0.005)
    assert gaps["bench.trainer_fit"] == pytest.approx(0.003)


def test_step_program_is_the_one_with_most_device_time():
    dev = T.DeviceTrace(ops=OPS, modules=MODULES)
    assert T.summarize([dev], []).step_name == "jit_train_step"


def test_no_device_operation_gives_nothing():
    assert T.summarize([T.DeviceTrace()], HOST) is None


def test_mean_over_chips():
    a = T.DeviceTrace(ops=[("x", 0, 10 * MS)], modules=[("m", 0, 10 * MS)])
    b = T.DeviceTrace(ops=[("x", 0, 5 * MS), ("y", 15 * MS, 5 * MS)], modules=[("m", 0, 20 * MS)])
    s = T.summarize([a, b], [])
    assert s.window_s == pytest.approx(0.015) and s.busy_s == pytest.approx(0.010)
