"""Device milliseconds a step of an ``sdar_moe`` program spends in operations under none of its component scopes: the noise (``bd_noise``), layer norms, residual adds, the optimizer, casts, every operation without a scope and the ``conditional``s' own events."""

from benchmarks import components_sdar_moe


def read(ctx):
    return components_sdar_moe.step_ms(ctx.get("summary"), components_sdar_moe.OTHER)
