from perceiver_io_tpu.ops.attention import (
    MultiHeadAttention,
    CrossAttention,
    SelfAttention,
    CrossAttentionLayer,
    SelfAttentionLayer,
    SelfAttentionBlock,
    MLP,
)
from perceiver_io_tpu.ops.fourier import (
    spatial_positions,
    fourier_position_encodings,
    num_position_encoding_channels,
)
from perceiver_io_tpu.ops.masking import IGNORE_LABEL, TextMasking, apply_text_masking

# Pallas kernels resolve lazily (PEP 562) so `import perceiver_io_tpu.ops`
# stays light — jax.experimental.pallas only loads when a kernel is touched,
# matching the deferred imports on MultiHeadAttention's dispatch path.
_LAZY = {"fused_attention", "seq_parallel_fused_attention"}


def __getattr__(name):
    if name in _LAZY:
        from perceiver_io_tpu.ops import pallas_attention

        return getattr(pallas_attention, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "MultiHeadAttention",
    "CrossAttention",
    "SelfAttention",
    "CrossAttentionLayer",
    "SelfAttentionLayer",
    "SelfAttentionBlock",
    "MLP",
    "spatial_positions",
    "fourier_position_encodings",
    "num_position_encoding_channels",
    "IGNORE_LABEL",
    "TextMasking",
    "apply_text_masking",
    "fused_attention",
    "seq_parallel_fused_attention",
]
