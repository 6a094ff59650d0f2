"""Device milliseconds a step of a ``nemotron_h`` program spends in operations under none of its component scopes: block norms, residual adds, the optimizer, casts, every operation without a scope and the ``conditional``s' own events."""

from benchmarks import components_nemotron_h


def read(ctx):
    return components_nemotron_h.step_ms(ctx.get("summary"), components_nemotron_h.OTHER)
