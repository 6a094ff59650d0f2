"""Set-up seconds inside jax's backend compile (union of the program's
``jax.backend_compile`` spans that ended before the window's ``train.fit``
began): the persistent cache's read and deserialise on a warm machine, XLA's
compile on a cold one."""

from benchmarks import spans


def read(ctx):
    return spans.setup_union_s("jax.backend_compile")
