"""``rows / (rows + padded_rows)`` of the fused engine over the window
(``ServingEngine.stats()``)."""


def read(ctx):
    counts = ctx.get("engine_counts")
    if not counts or counts["rows"] + counts["padded_rows"] == 0:
        return None
    return 100.0 * counts["rows"] / (counts["rows"] + counts["padded_rows"])
