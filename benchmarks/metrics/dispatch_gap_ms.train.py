"""Median idle gap on the device between consecutive executions of the step
program (device trace)."""

import statistics


def read(ctx):
    summary = ctx.get("summary")
    if summary is None or not summary.step_gaps_ms:
        return None
    return statistics.median(summary.step_gaps_ms)
