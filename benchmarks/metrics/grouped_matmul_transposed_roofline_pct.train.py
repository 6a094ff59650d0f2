"""The Pallas kernel ``grouped_matmul_transposed``'s share of its roofline (``components_decoder_lm.kernel_roofline_pct``; operations and bytes: ``flops_decoder_lm.py``)."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.kernel_roofline_pct(ctx, "grouped_matmul_transposed")
