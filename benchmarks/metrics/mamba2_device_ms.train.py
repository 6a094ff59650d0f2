"""Device milliseconds a step spends under the ``mamba2`` scopes of a ``nemotron_h`` step: the Mamba-2 mixers' two projections, convolution, scan, gate and grouped norm, forward, recomputation and backward."""

from benchmarks import components_nemotron_h


def read(ctx):
    return components_nemotron_h.step_ms(ctx.get("summary"), "mamba2")
