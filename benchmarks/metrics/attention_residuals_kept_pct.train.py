"""Whether the decoder's rematerialised blocks kept the causal kernel's output and row statistics, so that their backward pass recomputes everything but the kernel (``DecoderLM._remat_policy``): the program's gauge of the window's LAST step, published at the end of ``Trainer.fit``; 100 kept, 0 the bare remat (the forward kernel then runs twice a block)."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.gauge("attention_residuals_kept_pct")
