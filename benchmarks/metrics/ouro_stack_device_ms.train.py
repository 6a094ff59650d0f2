"""Device milliseconds a step of an ``ouro`` program spends in its looped stack (scope ``ut_stack``, the flax module ``loop``): the R passes of the N blocks and the final norm over one weight set, forward, the blocks' recomputation and backward."""

from benchmarks import components_ouro


def read(ctx):
    return components_ouro.step_ms(ctx.get("summary"), "ut_stack")
