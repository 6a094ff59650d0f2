"""The Pallas kernel ``fused_attention_fwd``'s share of its roofline in an ``sdar_moe`` step under the block-diffusion mask: operations and bytes of the mask's VISIBLE pairs, keys and values counted once a group of 8 query heads (``flops_sdar_moe.py``), x executions, over its device time (``components_sdar_moe.kernel_roofline_pct``)."""

from benchmarks import components_sdar_moe


def read(ctx):
    return components_sdar_moe.kernel_roofline_pct(ctx, "fused_attention_fwd")
