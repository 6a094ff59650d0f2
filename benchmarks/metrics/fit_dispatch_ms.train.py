"""Median host time of one dispatch in the window's fit that the runtime did
not hold back (``dispatch_ns`` of the program's ``train.step`` records: the
host-to-device put plus the call of the jitted step), over the iterations in
which the host ran ahead of the device (``spans.ran_ahead``: shorter than half
the mean iteration). A dispatch into a full queue waits one device step and
says nothing of the host; where no iteration ran ahead the median is over all
of them, and reads the device's step if the queue never drained."""

from benchmarks import spans


def read(ctx):
    return spans.window_median_ms("dispatch_ns", only=spans.ran_ahead)
