"""Device milliseconds a step of an ``ouro`` program spends outside the stack and the exits: the embedding, AdamW, casts and operations without a scope, taken as the step program's median device time less the two (the runtime's event for a ``while`` loop encloses its body's operations, ``components_ouro.py``)."""

from benchmarks import components_ouro


def read(ctx):
    return components_ouro.step_ms(ctx.get("summary"), components_ouro.OTHER)
