"""Process start to the opening of the window (host clock)."""


def read(ctx):
    return ctx["setup_s"]
