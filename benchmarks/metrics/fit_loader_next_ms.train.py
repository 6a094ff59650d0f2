"""Median wait of the Trainer in ``next()`` on its loader, per iteration of
the window's fit (``loader_ns`` of the program's ``train.step`` records): the
inside twin of ``loader_wait_pct.train``, blind to the profiler's start and
stop."""

from benchmarks import spans


def read(ctx):
    return spans.window_median_ms("loader_ns")
