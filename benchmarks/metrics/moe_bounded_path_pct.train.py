"""Share of the expert layers that ran their routed part over the bounded row buffer (``ops/moe.py`` ``capacity_tiles``) and not over the worst-case one, at the window's LAST step (the program's gauge, mean over the expert layers, published at the end of ``Trainer.fit``): 100 is every layer on the common path."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.gauge("moe_bounded_path_pct")
