"""Device milliseconds a step spends in the multi-token-prediction module as a whole: its embedding, projection, block (attention and experts), final norm, head and loss."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.step_ms(ctx.get("summary"), "mtp")
