"""Every request answered (a well-formed top-k per [MASK]) in the window,
over the whole window: release of the clients to the last answer (host
clock)."""


def read(ctx):
    if "requests_answered" not in ctx:
        return None
    return ctx["requests_answered"] / ctx["window_s"]
