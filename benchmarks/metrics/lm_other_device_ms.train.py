"""Device milliseconds a step spends in operations under none of the decoder's component scopes: norms, residuals and the dense SwiGLU of the leading layer, the optimizer, casts, and every operation without a scope."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.step_ms(ctx.get("summary"), components_decoder_lm.OTHER)
