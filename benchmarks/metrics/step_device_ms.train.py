"""Median device duration of one execution of the step program (device
trace)."""

import statistics


def read(ctx):
    summary = ctx.get("summary")
    if summary is None or not summary.step_durations_ms:
        return None
    return statistics.median(summary.step_durations_ms)
