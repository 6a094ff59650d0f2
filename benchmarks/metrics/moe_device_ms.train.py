"""Device milliseconds a step spends under the main stack's ``moe/*`` scopes: router, dispatch, the held experts' grouped matmuls, combine, the shared expert."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.step_ms(ctx.get("summary"), "moe")
