"""Named model presets shared by the benchmarks and the driver entry points.

One definition of the flagship config so ``bench.py``,
``tools/e2e_configs_bench.py`` and ``__graft_entry__.py`` cannot drift apart
(the PERF.md table is sourced from these).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from perceiver_io_tpu.models.adapters import TextInputAdapter, TextOutputAdapter
from perceiver_io_tpu.models.perceiver import (
    PerceiverARLM,
    PerceiverDecoder,
    PerceiverEncoder,
    PerceiverMLM,
)
from perceiver_io_tpu.ops.masking import TextMasking


def flagship_tpu_mlm(
    vocab_size: int = 10003,
    max_seq_len: int = 512,
    num_latents: int = 256,
    num_channels: int = 512,
    num_layers: int = 3,
    num_self_attention_layers_per_block: int = 6,
    dtype: jnp.dtype = jnp.bfloat16,
    attn_impl: str = "xla",
    remat: bool = False,
    decoder_attn_impl: Optional[str] = None,
) -> PerceiverMLM:
    """The MLM recipe at TPU-native widths (BASELINE.md north-star, closed
    from the other end).

    ``attn_impl`` defaults to 'xla' rather than 'auto': the area rule would
    route the (64, 4, 256, 512, d128) encoder cross to the fused kernel,
    which wins 1.85x at KERNEL level but measures 43.16 vs 42.08 ms END TO
    END (roofline device trace, r4) — XLA overlaps the logits traffic it
    materializes, the same dilution as PERF.md negative (10b).

    Identical recipe *shape* to the reference ``train_mlm.py:93-106`` — same
    tokenizer, masking, 512-token sequences, 256 latents, 3 encoder layers x
    (cross-attention + 6-layer self-attention block), text in/out adapters —
    but with the channel width raised from the reference's GPU-sized C=64
    (head depth 16, which caps MXU efficiency at ~12.5%; PERF.md's d=16
    structural bound) to C=512 with the default 4 heads, i.e. head depth 128:
    the full MXU contraction depth, the same head geometry that measures
    65.5% MFU on the ImageNet paper config. This is what the MLM task looks
    like when sized for the hardware instead of for 8 GB GPUs."""
    return flagship_mlm(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        num_latents=num_latents,
        num_channels=num_channels,
        num_layers=num_layers,
        num_self_attention_layers_per_block=num_self_attention_layers_per_block,
        dtype=dtype,
        attn_impl=attn_impl,
        remat=remat,
        decoder_attn_impl=decoder_attn_impl,
    )


def tiny_mlm(
    vocab_size: int = 503,
    max_seq_len: int = 64,
    num_latents: int = 16,
    num_channels: int = 32,
    num_layers: int = 2,
    num_self_attention_layers_per_block: int = 1,
    dtype: jnp.dtype = jnp.float32,
    attn_impl: str = "auto",
) -> PerceiverMLM:
    """The CPU-scale twin of the flagship recipe — same code path, minutes
    not hours. One definition shared by the offline (tier-1) modes of the
    serving benches (``tools/inference_bench.py --preset tiny``,
    ``tools/quant_bench.py --cpu``) and the quant parity tests, so the
    "tiny preset" they all quote is the same model."""
    return flagship_mlm(
        vocab_size=vocab_size,
        max_seq_len=max_seq_len,
        num_latents=num_latents,
        num_channels=num_channels,
        num_layers=num_layers,
        num_self_attention_layers_per_block=num_self_attention_layers_per_block,
        dtype=dtype,
        attn_impl=attn_impl,
    )


def flagship_ar(
    vocab_size: int = 10003,
    max_seq_len: int = 512,
    num_latents: int = 256,
    num_channels: int = 512,
    num_layers: int = 3,
    num_self_attention_layers_per_block: int = 6,
    dtype: jnp.dtype = jnp.bfloat16,
    attn_impl: str = "auto",
) -> PerceiverARLM:
    """The generative (Perceiver-AR causal decode) task at the flagship
    TPU-native widths: same encoder recipe shape as ``flagship_tpu_mlm``
    (3 layers × (cross + 6-layer self block), C=512 / head depth 128), with
    the causal latent window covering the last ``num_latents`` positions and
    a causal query decode predicting each successor token.

    ``attn_impl`` stays 'auto', which currently resolves every CAUSAL call
    to XLA — the decode-shape kernel sweep that would set Pallas thresholds
    has not been run on a chip (PERF.md); dispatch thresholds only
    move with measurements."""
    return _build_ar(
        vocab_size=vocab_size, max_seq_len=max_seq_len,
        num_latents=num_latents, num_channels=num_channels,
        num_layers=num_layers,
        num_self_attention_layers_per_block=num_self_attention_layers_per_block,
        dtype=dtype, attn_impl=attn_impl,
    )


def tiny_ar(
    vocab_size: int = 503,
    max_seq_len: int = 64,
    num_latents: int = 16,
    num_channels: int = 32,
    num_layers: int = 2,
    num_self_attention_layers_per_block: int = 1,
    dtype: jnp.dtype = jnp.float32,
    attn_impl: str = "auto",
) -> PerceiverARLM:
    """CPU-scale twin of :func:`flagship_ar` — the generation engine /
    serving / chaos tests and the offline modes of the benches all build
    exactly this model (one definition, like :func:`tiny_mlm`)."""
    return _build_ar(
        vocab_size=vocab_size, max_seq_len=max_seq_len,
        num_latents=num_latents, num_channels=num_channels,
        num_layers=num_layers,
        num_self_attention_layers_per_block=num_self_attention_layers_per_block,
        dtype=dtype, attn_impl=attn_impl,
    )


def _build_ar(
    vocab_size: int,
    max_seq_len: int,
    num_latents: int,
    num_channels: int,
    num_layers: int,
    num_self_attention_layers_per_block: int,
    dtype: jnp.dtype,
    attn_impl: str,
) -> PerceiverARLM:
    return PerceiverARLM(
        input_adapter=TextInputAdapter(
            vocab_size=vocab_size, max_seq_len=max_seq_len,
            num_channels=num_channels, dtype=dtype,
        ),
        output_adapter=TextOutputAdapter(
            vocab_size=vocab_size, max_seq_len=max_seq_len,
            num_output_channels=num_channels, dtype=dtype,
        ),
        num_latents=num_latents,
        num_layers=num_layers,
        num_self_attention_layers_per_block=num_self_attention_layers_per_block,
        dtype=dtype,
        attn_impl=attn_impl,
    )


def flagship_mlm(
    vocab_size: int = 10003,
    max_seq_len: int = 512,
    num_latents: int = 256,
    num_channels: int = 64,
    num_layers: int = 3,
    num_self_attention_layers_per_block: int = 6,
    dtype: jnp.dtype = jnp.float32,
    attn_impl: str = "auto",
    remat: bool = False,
    decoder_attn_impl: Optional[str] = None,
) -> PerceiverMLM:
    """The BASELINE.md north-star config: reference train_mlm shapes
    (SURVEY.md §3.1 — 512-token sequences, 256 latents, 3 encoder layers ×
    (cross-attention + 6-layer self-attention block), text in/out adapters).

    ``decoder_attn_impl``: override the DECODER's attention impl separately
    (None = same as ``attn_impl``) — the encoder's long-KV streaming shapes
    and the decoder's many-queries/few-keys gather-decode shape can prefer
    different paths (PERF.md r5 long-context decomposition)."""
    latent_shape = (num_latents, num_channels)
    return PerceiverMLM(
        encoder=PerceiverEncoder(
            input_adapter=TextInputAdapter(
                vocab_size=vocab_size, max_seq_len=max_seq_len,
                num_channels=num_channels, dtype=dtype,
            ),
            latent_shape=latent_shape,
            num_layers=num_layers,
            num_self_attention_layers_per_block=num_self_attention_layers_per_block,
            dtype=dtype,
            attn_impl=attn_impl,
            remat=remat,
        ),
        decoder=PerceiverDecoder(
            output_adapter=TextOutputAdapter(
                vocab_size=vocab_size, max_seq_len=max_seq_len,
                num_output_channels=num_channels, dtype=dtype,
            ),
            latent_shape=latent_shape,
            dtype=dtype,
            attn_impl=decoder_attn_impl or attn_impl,
        ),
        masking=TextMasking(
            vocab_size=vocab_size, unk_token_id=1, mask_token_id=2,
            num_special_tokens=3,
        ),
    )
