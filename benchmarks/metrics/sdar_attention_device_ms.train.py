"""Device milliseconds a step spends under the ``gqa_attention`` scope of an ``sdar_moe`` step trained by block diffusion: the four projections, q/k norms and rotary of the 2L positions ``[x_t ; x]``, and the three block-diffusion kernels."""

from benchmarks import components_sdar_moe


def read(ctx):
    return components_sdar_moe.step_ms(ctx.get("summary"), "gqa_attention")
