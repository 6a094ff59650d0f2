"""Set-up seconds inside jax's trace and lower of a program (union of the
program's ``jax.trace`` and ``jax.lower`` spans that ended before the window's
``train.fit`` began)."""

from benchmarks import spans


def read(ctx):
    return spans.setup_union_s("jax.trace", "jax.lower")
