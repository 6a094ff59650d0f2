"""Device milliseconds a step spends under ``embed`` and the main ``head_loss`` (the vocabulary projection and its cross-entropy, recomputed once in the backward pass)."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.step_ms(ctx.get("summary"), "embed_head_loss")
