"""XLA backend compilations during set-up (``jax_compilations_total``, which
leaves persistent-cache hits out): 0 on a warm cache."""


def read(ctx):
    return ctx.get("setup_xla_compiles")
