"""Device milliseconds a step of an ``ouro`` program spends in its exits (scope ``ut_exits``): the R exit gates, the R heads over the whole vocabulary with their cross-entropies (each recomputed in the backward pass), and the expected-exit loss."""

from benchmarks import components_ouro


def read(ctx):
    return components_ouro.step_ms(ctx.get("summary"), "ut_exits")
