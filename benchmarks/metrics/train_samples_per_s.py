"""Every sample of every optimizer step that completed in the window, over
the whole window (device sync at both ends; host clock)."""


def read(ctx):
    if "steps" not in ctx:
        return None
    return ctx["steps"] * ctx["batch_size"] / ctx["window_s"]
