"""The Pallas kernel ``grouped_matmul``'s share of its roofline in a ``nemotron_h`` step (``components_nemotron_h.kernel_roofline_pct``; operations and bytes: ``flops_nemotron_h.py``)."""

from benchmarks import components_nemotron_h


def read(ctx):
    return components_nemotron_h.kernel_roofline_pct(ctx, "grouped_matmul")
