"""``rows / batches`` of the fused engine over the window."""


def read(ctx):
    counts = ctx.get("engine_counts")
    if not counts or counts["batches"] == 0:
        return None
    return counts["rows"] / counts["batches"]
