"""Masked share of the noised half's tokens in the window's LAST step of a block-diffusion program (the program's gauge ``bd_masked_pct``, published at the end of ``Trainer.fit``): each block's noise level is uniform on [1e-3, 1], so a step of 2,048 blocks reads near 50; nothing where the program trains no such objective."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.gauge("bd_masked_pct")
