"""Task-specific input/output adapters.

The adapter contract is the reference's central extensibility mechanism
(``perceiver/adapter.py:9-32``), preserved here as flax modules satisfying a
shape contract:

- input adapters map task input to ``(B, M, C_in)`` and expose
  ``num_input_channels`` (read by the encoder to size cross-attention KV,
  reference ``model.py:153``);
- output adapters map generic decoder output ``(B, K, C_out)`` to task output
  and expose ``output_shape == (K, C_out)`` (read by the decoder to size its
  learned query array, reference ``model.py:213-222``).

Because flax modules are dataclasses, both properties are derivable from
constructor fields on *unbound* instances — so the encoder/decoder can read
them at construction time exactly like the reference does.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_io_tpu import obs

with obs.span("import", module="flax"):
    from flax import linen as nn

from perceiver_io_tpu.ops.attention import (
    _LinearParams,
    torch_linear_bias_init,
    torch_linear_kernel_init,
)
from perceiver_io_tpu.ops.pallas_matmul import linear_apply
from perceiver_io_tpu.ops.fourier import (
    fourier_position_encodings,
    num_position_encoding_channels,
    spatial_positions,
)

Array = jax.Array


def uniform_init(low: float, high: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, low, high)

    return init


class InputAdapter(nn.Module):
    """ABC for input adapters (reference ``adapter.py:9-19``)."""

    @property
    def num_input_channels(self) -> int:
        raise NotImplementedError

    def __call__(self, x: Array) -> Array:
        raise NotImplementedError


class OutputAdapter(nn.Module):
    """ABC for output adapters (reference ``adapter.py:22-32``)."""

    @property
    def output_shape(self) -> Tuple[int, int]:
        raise NotImplementedError

    def __call__(self, x: Array) -> Array:
        raise NotImplementedError


class ImageInputAdapter(InputAdapter):
    """Flatten image to (B, H*W, C) and concat Fourier position encodings.

    Reference ``adapter.py:35-109``: coordinates evenly spaced in [-1, 1] per
    spatial dim; ``num_frequency_bands`` linearly spaced frequencies
    1.0 → size/2 with sin+cos plus raw positions; encodings computed once per
    shape and folded into the compiled program as a constant.
    """

    image_shape: Tuple[int, ...] = (28, 28, 1)
    num_frequency_bands: int = 32
    dtype: jnp.dtype = jnp.float32

    @property
    def spatial_shape(self) -> Tuple[int, ...]:
        return self.image_shape[:-1]

    @property
    def num_image_channels(self) -> int:
        return self.image_shape[-1]

    @property
    def num_input_channels(self) -> int:
        return self.num_image_channels + num_position_encoding_channels(
            len(self.spatial_shape), self.num_frequency_bands
        )

    @nn.compact
    def __call__(self, x: Array) -> Array:
        b, *d = x.shape
        if tuple(d) != tuple(self.image_shape):
            raise ValueError(
                f"Input image shape {tuple(d)} different from required shape "
                f"{tuple(self.image_shape)}"
            )

        pos = spatial_positions(self.spatial_shape)
        enc = fourier_position_encodings(pos, self.num_frequency_bands)
        enc = enc.reshape(-1, enc.shape[-1]).astype(self.dtype)  # (M, C_pos)

        x = x.reshape(b, -1, self.num_image_channels).astype(self.dtype)
        enc = jnp.broadcast_to(enc, (b, *enc.shape))
        return jnp.concatenate([x, enc], axis=-1)


class _ScaledEmbed(nn.Embed):
    """``nn.Embed`` whose table is pre-scaled by ``scale`` BEFORE the gather.

    Bit-identical to ``embed(x) * scale`` — each gathered element is the same
    compute-dtype multiply either way — but the multiply streams the
    (vocab, C) table instead of the (B, L, C) output: at seq 131072 the
    output-side mul measures 1.3 ms at 716 GB/s on the device trace
    (hbm_roofline, PERF.md r5) while the table-side mul is noise. Param tree
    unchanged (``{name}/embedding``)."""

    scale: float = 1.0

    def __call__(self, inputs: Array) -> Array:
        if not jnp.issubdtype(inputs.dtype, jnp.integer):
            raise ValueError("Input type must be an integer or unsigned integer.")
        # the free-function spelling (flax.linen.dtypes) — what nn.Embed
        # itself calls; the Module-method spelling doesn't exist on every
        # flax release this runs under
        from flax.linen.dtypes import promote_dtype

        (embedding,) = promote_dtype(
            self.embedding, dtype=self.dtype, inexact=False
        )
        return jnp.take(embedding * self.scale, inputs, axis=0)


class TextInputAdapter(InputAdapter):
    """Token embedding * sqrt(C) + learned position encodings.

    Reference ``adapter.py:112-133``: embedding init U(-0.1, 0.1), position
    encodings (max_seq_len, C) init U(-0.5, 0.5), sliced to actual length.
    """

    vocab_size: int = 10003
    max_seq_len: int = 512
    num_channels: int = 64
    dtype: jnp.dtype = jnp.float32

    @property
    def num_input_channels(self) -> int:
        return self.num_channels

    @nn.compact
    def __call__(self, x: Array, positions: Optional[Array] = None) -> Array:
        """``positions``: optional (B, L) int — the absolute position of each
        token, for callers whose rows do NOT start at position 0 (the AR
        decode step embeds ONE token at its true sequence position). Default
        (None) keeps the contiguous ``[0, L)`` slice — bit-identical to the
        historical behavior, and the gather-free fast path."""
        b, l = x.shape
        if l > self.max_seq_len:
            raise ValueError(f"sequence length {l} exceeds max_seq_len {self.max_seq_len}")

        emb = _ScaledEmbed(
            num_embeddings=self.vocab_size,
            features=self.num_channels,
            embedding_init=uniform_init(-0.1, 0.1),
            dtype=self.dtype,
            scale=math.sqrt(self.num_channels),
            name="text_embedding",
        )(x)
        pos_enc = self.param(
            "pos_encoding",
            uniform_init(-0.5, 0.5),
            (self.max_seq_len, self.num_channels),
        )
        if positions is not None:
            return emb + jnp.take(pos_enc, positions, axis=0).astype(self.dtype)
        return emb + pos_enc[:l].astype(self.dtype)


class ClassificationOutputAdapter(OutputAdapter):
    """Linear head over decoder output; squeezes the query dim when K == 1.

    Reference ``adapter.py:136-149``: output_shape = (num_outputs, C_out) with
    C_out defaulting to num_classes; torch-default Linear init.

    ``pad_classes_to``: round the projection width up to a multiple (e.g. 128
    — one TPU lane tile), emitting logits of that padded width with the extra
    entries pinned to a large negative so softmax/CE/argmax/top-k ignore
    them. This is what makes the vocab projection *tensor-shardable*: the
    reference vocab (10,003) divides no mesh axis, so without padding the
    framework's biggest matmul stays replicated under tp > 1 (the
    ``sharding_for_tree`` divisibility fallback). SURVEY.md §7's
    "vocab-sharded output projection" hard part.
    """

    num_classes: int = 2
    num_outputs: int = 1
    num_output_channels: Optional[int] = None
    dtype: jnp.dtype = jnp.float32
    pad_classes_to: Optional[int] = None

    @property
    def output_shape(self) -> Tuple[int, int]:
        c = self.num_output_channels if self.num_output_channels is not None else self.num_classes
        return (self.num_outputs, c)

    @property
    def padded_num_classes(self) -> int:
        if self.pad_classes_to is None:
            return self.num_classes
        m = self.pad_classes_to
        if m < 1:
            raise ValueError(f"pad_classes_to must be >= 1, got {m}")
        return ((self.num_classes + m - 1) // m) * m

    def masked_head(self, adapter_params) -> Tuple[Array, Array]:
        """(kernel, bias) of the linear head with padded classes masked out
        of the bias — the single source of truth for the ``pad_classes_to``
        scheme when a caller fuses the head into the loss
        (``pallas_linear_cross_entropy_with_ignore``) instead of applying this
        adapter. Mirrors the -inf-stand-in masking ``__call__`` applies to
        its logits: padded columns get a large-negative bias, so they vanish
        from any downstream softmax/logsumexp and receive zero gradient."""
        kernel = adapter_params["linear"]["kernel"]
        bias = adapter_params["linear"]["bias"]
        if self.padded_num_classes != self.num_classes:
            col = jnp.arange(bias.shape[-1])
            bias = jnp.where(col < self.num_classes, bias, jnp.asarray(-1e9, bias.dtype))
        return kernel, bias

    @nn.compact
    def __call__(self, x: Array) -> Array:
        c_in = self.output_shape[-1]
        n_out = self.padded_num_classes
        w, b = _LinearParams(
            x.shape[-1], n_out, kernel_init=torch_linear_kernel_init,
            bias_init=torch_linear_bias_init(c_in), name="linear")()
        # the vocab head is the single biggest weight stream in the serving
        # forward — linear_apply routes a quantized tree's kernel through
        # the fused dequant-matmul
        x = linear_apply(x, w, b, self.dtype)
        if n_out != self.num_classes:
            # finite stand-in for -inf: exp() underflows to exactly 0 in the
            # downstream softmax/logsumexp, and no argmax/top-k can pick it
            pad = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
            x = jnp.where(pad < self.num_classes, x, jnp.asarray(-1e30, x.dtype))
        # Squeeze on the CONFIGURED query count, not the runtime shape: a
        # positions-gathered decode (PerceiverDecoder positions=...) may pass
        # K=1 rows of a multi-query adapter, which must stay (B, 1, C).
        if self.num_outputs == 1 and x.shape[1] == 1:
            x = jnp.squeeze(x, axis=1)
        return x


def TextOutputAdapter(
    vocab_size: int,
    max_seq_len: int,
    num_output_channels: Optional[int] = None,
    dtype: jnp.dtype = jnp.float32,
    pad_classes_to: Optional[int] = None,
) -> ClassificationOutputAdapter:
    """Per-position vocab logits: a classification adapter with one output
    query per sequence position (reference ``adapter.py:152-159``)."""
    return ClassificationOutputAdapter(
        num_classes=vocab_size,
        num_outputs=max_seq_len,
        num_output_channels=num_output_channels,
        dtype=dtype,
        pad_classes_to=pad_classes_to,
    )
