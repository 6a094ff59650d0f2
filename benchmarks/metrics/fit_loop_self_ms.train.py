"""Median self time of an iteration of the window's fit: its length less the
loader's wait and the dispatch (the program's ``train.step`` records): the
Trainer's own Python between the calls. The one iteration in a log interval
that logs holds its syncs too; a median does not see it."""

from benchmarks import spans


def read(ctx):
    return spans.window_median_ms(spans.self_ns)
