"""95th percentile over ALL requests of the window, submit to answer in the
client's hands; a failed request counts as infinitely late (host clock)."""

from benchmarks import stats


def read(ctx):
    if not ctx.get("latencies_ms"):
        return None
    return stats.percentile(ctx["latencies_ms"], 95)
