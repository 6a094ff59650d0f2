"""Device milliseconds a step spends under the ``gqa_attention`` scope of a ``nemotron_h`` step: the attention block's four projections and its three causal kernels."""

from benchmarks import components_nemotron_h


def read(ctx):
    return components_nemotron_h.step_ms(ctx.get("summary"), "gqa_attention")
