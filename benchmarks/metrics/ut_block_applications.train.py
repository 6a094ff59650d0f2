"""Block applications a step of a looped stack, R x N (the program's gauge ``ut_block_applications``, set where the model is built): a constant of the traced program; nothing where the program builds no looped stack."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.gauge("ut_block_applications")
