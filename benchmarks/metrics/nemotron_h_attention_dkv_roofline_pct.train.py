"""The Pallas kernel ``fused_attention_dkv``'s share of its roofline in a ``nemotron_h`` step, keys and values counted once a group of 16 query heads (``components_nemotron_h.kernel_roofline_pct``; operations and bytes: ``flops_nemotron_h.py``)."""

from benchmarks import components_nemotron_h


def read(ctx):
    return components_nemotron_h.kernel_roofline_pct(ctx, "fused_attention_dkv")
