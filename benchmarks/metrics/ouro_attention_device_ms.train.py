"""Device milliseconds a step of an ``ouro`` program spends under the ``gqa_attention`` scope: the R x N multi-head attention sublayers (projections, rotary and the three causal kernels, forward, recomputation and backward), a PART of ``ouro_stack_device_ms.train``."""

from benchmarks import components_ouro


def read(ctx):
    return components_ouro.attention_ms(ctx.get("summary"))
