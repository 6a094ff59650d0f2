"""Assignments of the busiest held expert over the mean of the held experts, mean over the expert layers, of the window's LAST step (the program's gauge, published at the end of ``Trainer.fit``): 1 is a perfect balance."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.gauge("moe_load_max_over_mean")
