"""The Pallas kernel ``fused_attention_dkv``'s share of its roofline in an ``ouro`` step: operations and bytes of one block application over the causal triangle, a group of 1 (``flops_ouro.py``), x its executions a whole step (R for each instruction in the scan's body), over its device time (``components_ouro.kernel_roofline_pct``)."""

from benchmarks import components_ouro


def read(ctx):
    return components_ouro.kernel_roofline_pct(ctx, "fused_attention_dkv")
