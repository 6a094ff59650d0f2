"""Device milliseconds a step spends under the main stack's ``mla_attention`` scopes (latent projections, rotary, the causal kernels), forward, recomputation and backward; the MTP module's attention counts to ``mtp_device_ms.train``."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.step_ms(ctx.get("summary"), "mla_attention")
