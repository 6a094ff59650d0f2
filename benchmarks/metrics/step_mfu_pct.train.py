"""The whole step's share of the chip's peak: operations the forward and
backward passes require per step (benchmarks/flops.py, recomputation not
counted) x step executions in the traced window / its length / peak."""


def read(ctx):
    summary = ctx.get("summary")
    if summary is None or not summary.step_durations_ms or "flops_per_step" not in ctx:
        return None
    done = len(summary.step_durations_ms) * ctx["flops_per_step"]
    return 100.0 * done / summary.window_s / ctx["peak_flops"]
