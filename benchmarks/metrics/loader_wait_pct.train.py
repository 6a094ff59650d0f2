"""Share of the window that ``fit`` spent inside the loader's ``__next__``
(the benchmark loader's own span, host clock)."""


def read(ctx):
    if "loader_wait_s" not in ctx:
        return None
    return 100.0 * ctx["loader_wait_s"] / ctx["window_s"]
