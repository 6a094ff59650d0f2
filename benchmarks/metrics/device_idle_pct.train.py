"""1 - union of device-operation intervals over the traced window."""


def read(ctx):
    summary = ctx.get("summary")
    if summary is None:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
