"""The generic Perceiver IO core: encoder, decoder, and composed models.

Architecture (reference ``perceiver/model.py``): arbitrary-modality inputs are
cross-attended into a small fixed-size latent array — decoupling compute from
input length M (the architectural long-context mechanism: all O(M) work is a
single cross-attention per layer; quadratic self-attention touches only the N
latents) — then decoded by cross-attending task-specific output queries
against the latents.

Key structural semantics preserved:

- encoder layer 1 has unique weights; layers 2..num_layers share ONE weight
  set applied recurrently (reference ``model.py:162-166,185-187``). In flax,
  re-calling the same bound submodule shares parameters, and JAX autodiff
  accumulates gradients across applications exactly like torch autograd.
- learned latent / output-query arrays init ~N(0, 0.02) clamped to ±2
  (reference ``model.py:169-174,222-227``).
- the decoder validates the latent shape (reference ``model.py:232-233``) —
  here at trace time, so the check costs nothing at run time.

TPU-first choices: modules take a ``dtype`` (bfloat16 compute, f32 params),
an ``attn_impl`` switch ('xla' einsum vs. fused Pallas kernel), and an
optional ``remat`` flag that rematerializes each perceiver layer to trade
FLOPs for HBM when the recurrent stack is deep. Rematerialization is
selective where it pays: a layer whose cross-attention materializes its
logits over a long input (the XLA path, S >= ``AUTO_PALLAS_MIN_KV``) keeps
those logits, the weighted sum's output and the K/V projections for its
backward pass, as long as their bytes fit a share of the device's memory, and
recomputes the rest (norms, q projection, softmax, MLP, the whole
self-attention block); everywhere else the whole layer is recomputed
(``PerceiverEncoder._remat_policy``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu import obs
from perceiver_io_tpu.ops.attention import (
    AUTO_PALLAS_MIN_KV,
    REMAT_CROSS_CONTEXT,
    REMAT_CROSS_KV,
    REMAT_CROSS_LOGITS,
    CrossAttentionLayer,
    SelfAttentionBlock,
    auto_attention_impl,
)
from perceiver_io_tpu.ops.masking import IGNORE_LABEL, TextMasking
from perceiver_io_tpu.parallel.mesh import AXIS_DATA, active_step_mesh

Array = jax.Array

# The share of one device's memory (``bytes_limit``) that the residuals kept
# under ``remat=True`` may take, all encoder layers together. Placed from chip
# runs of the ImageNet configuration on a 16.9 GB v5e (PERF.md §6, PR 30): its
# set is 34% of the chip at batch 8 (engaged, the peak is 10.4 GB) and 69% at
# batch 16, where the engaged step does not fit beside the rest; at 40% the
# switch lies where the engaged step is estimated at two thirds of the chip.
REMAT_KEEP_FRACTION = 0.4


def _device_bytes_limit() -> Optional[int]:
    """``bytes_limit`` of one local device, or None where the backend reports
    no memory statistics (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def remat_keeps(saved: int, batch: int, layers: int) -> bool:
    """Whether rematerialised layers may keep ``saved`` bytes of named
    residuals (all ``layers`` together, for ``batch`` rows): per device where
    a mesh shares the batch, they must fit ``REMAT_KEEP_FRACTION`` of the
    device's memory. ``saved`` 0 (nothing on this path is worth a name) and a
    device that reports no limit keep nothing. Called once per trace by the
    model that owns the layers; reports the decision as one ``remat.policy``
    event."""
    mesh = active_step_mesh()
    shards = mesh.shape.get(AXIS_DATA, 1) if mesh is not None else 1
    if batch % shards == 0:
        saved //= shards
    limit = _device_bytes_limit()
    budget = None if limit is None else int(limit * REMAT_KEEP_FRACTION)
    engaged = budget is not None and 0 < saved <= budget
    obs.event("remat.policy", engaged=engaged, layers=layers,
              saved_bytes=saved, budget_bytes=budget)
    return engaged


def latent_init(std: float = 0.02, clamp: float = 2.0):
    """~N(0, std) clamped to ±clamp (reference ``model.py:169-174``)."""

    def init(key, shape, dtype=jnp.float32):
        return jnp.clip(jax.random.normal(key, shape) * std, -clamp, clamp).astype(dtype)

    return init


class PerceiverLayer(nn.Module):
    """One encoder layer: cross-attention (latent ← input) + self-attention block
    (reference ``model.py:150-160``)."""

    num_latent_channels: int
    num_input_channels: int
    num_cross_attention_heads: int
    num_self_attention_heads: int
    num_self_attention_layers_per_block: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x_latent, x_input, pad_mask=None, deterministic=True,
                 kv=None):
        """Always returns ``(x_latent, kv)``: ``kv`` is the cross-attention's
        (k, v) projection of ``x_input`` — computed here when the ``kv``
        argument is None, or the caller's cached tensors passed through
        (the shared-weight recurrence, ``PerceiverEncoder.reuse_kv``). The
        unconditional tuple return keeps the signature remat-safe: no static
        bool crosses the ``nn.remat`` boundary, and ``kv`` is a pytree."""
        x_latent, kv = CrossAttentionLayer(
            num_q_channels=self.num_latent_channels,
            num_kv_channels=self.num_input_channels,
            num_heads=self.num_cross_attention_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            # this KV stream is the adapted input — the tensor shard_seq=True
            # shards over the mesh's seq axis — so it may route to the
            # sequence-parallel kernel when that regime is active
            seq_shard_kv=True,
            name="cross_attention_layer",
        )(x_latent, x_input, pad_mask=pad_mask, deterministic=deterministic,
          kv=kv, return_kv=True)
        x_latent = SelfAttentionBlock(
            num_layers=self.num_self_attention_layers_per_block,
            num_channels=self.num_latent_channels,
            num_heads=self.num_self_attention_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            name="self_attention_block",
        )(x_latent, deterministic=deterministic)
        return x_latent, kv


class PerceiverEncoder(nn.Module):
    """Generic Perceiver IO encoder (reference ``model.py:119-189``).

    ``input_adapter`` is injected by the caller (the reference's inversion of
    control, ``model.py:121,145``); its ``num_input_channels`` sizes the
    cross-attention KV stream.
    """

    input_adapter: nn.Module
    latent_shape: Tuple[int, int]
    num_layers: int
    num_cross_attention_heads: int = 4
    num_self_attention_heads: int = 4
    num_self_attention_layers_per_block: int = 2
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"
    remat: bool = False
    # Reuse the shared layer_n cross-attention K/V projections across its
    # recurrent applications: identical weights × identical input ⇒ identical
    # k/v, so the repeat is pure recompute. Exact (the cached tensors are
    # reused, not re-derived); the win is mostly the BACKWARD projection pass
    # autodiff would otherwise emit per application — measured 2.3 ms/step on
    # the 131k-token MLM config (PERF.md r5). Off: recompute per application
    # (marginally less live memory under a remat that recomputes whole
    # layers; where remat keeps the cross-attention's residuals, every
    # application then keeps a K/V set of its own).
    reuse_kv: bool = True

    def _remat_policy(self, b: int, s: int):
        """The ``jax.checkpoint`` policy of the layers under ``remat=True``
        for a (B, S, C) input; None recomputes whole layers (the bare
        ``nn.remat``). Decided once per trace, from shapes and the device,
        and reported as one ``remat.policy`` event.

        The ``REMAT_CROSS_*`` residuals are kept when (1) the cross-attention
        takes the XLA path over a long stream, S >= ``AUTO_PALLAS_MIN_KV``
        (recomputing its logits costs 2d operations per byte kept away; the
        Pallas kernels carry residuals of their own; a short stream's logits
        are not worth keeping), and (2) their bytes over all layers, per
        device where a mesh shares the batch, fit ``REMAT_KEEP_FRACTION`` of
        the device's memory. The shared layer's K/V is an output of its first
        application either way: naming it only saves its recomputation.
        """
        t, e = self.latent_shape
        h = self.num_cross_attention_heads
        impl = self.attn_impl
        if impl == "auto":
            impl = auto_attention_impl(b, t, s, h, e // h)
        saved = 0
        if impl == "xla" and s >= AUTO_PALLAS_MIN_KV:
            shared = self.num_layers - 1
            kv_sets = 1 + (min(shared, 1) if self.reuse_kv else shared)
            saved = (self.num_layers * (b * h * t * s + b * t * e)
                     + kv_sets * 2 * b * s * e) * jnp.dtype(self.dtype).itemsize
        if not remat_keeps(saved, b, self.num_layers):
            return None
        return jax.checkpoint_policies.save_only_these_names(
            REMAT_CROSS_LOGITS, REMAT_CROSS_CONTEXT, REMAT_CROSS_KV)

    def _make_layer(self, name: str, remat_policy=None) -> nn.Module:
        cls = PerceiverLayer
        if self.remat:
            cls = nn.remat(PerceiverLayer, policy=remat_policy)
        return cls(
            num_latent_channels=self.latent_shape[1],
            num_input_channels=self.input_adapter.num_input_channels,
            num_cross_attention_heads=self.num_cross_attention_heads,
            num_self_attention_heads=self.num_self_attention_heads,
            num_self_attention_layers_per_block=self.num_self_attention_layers_per_block,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            name=name,
        )

    @nn.compact
    def __call__(self, x, pad_mask=None, deterministic=True):
        # batch size comes from the adapted (B, M, C) stream, not the raw
        # input — multimodal adapters take a dict of arrays
        x = self.input_adapter(x)
        b = x.shape[0]

        latent = self.param("latent", latent_init(), self.latent_shape)
        x_latent = jnp.broadcast_to(latent.astype(self.dtype), (b, *self.latent_shape))

        policy = self._remat_policy(b, x.shape[1]) if self.remat else None
        x_latent, _ = self._make_layer("layer_1", policy)(
            x_latent, x, pad_mask=pad_mask, deterministic=deterministic
        )
        if self.num_layers > 1:
            # One weight set used recurrently for layers 2..num_layers
            # (reference model.py:162-166,185-187). Its K/V projection of the
            # (unchanging) input is identical across applications — cache and
            # reuse it (reuse_kv above).
            layer_n = self._make_layer("layer_n", policy)
            kv = None
            for _ in range(self.num_layers - 1):
                x_latent, kv_out = layer_n(
                    x_latent, x, pad_mask=pad_mask, deterministic=deterministic,
                    kv=kv,
                )
                if self.reuse_kv:
                    kv = kv_out
        return x_latent


class PerceiverDecoder(nn.Module):
    """Generic Perceiver IO decoder (reference ``model.py:192-237``).

    A learned output-query array of shape ``output_adapter.output_shape``
    cross-attends against the latents, then the injected output adapter maps
    the result to task output.
    """

    output_adapter: nn.Module
    latent_shape: Tuple[int, int]
    num_cross_attention_heads: int = 4
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, deterministic=True, positions: Optional[Array] = None,
                 return_features: bool = False):
        """``positions``: optional (B, K) int — decode only these rows of the
        learned output-query array. Each output query attends to the latents
        independently (no query-query interaction anywhere in the decoder), so
        decoding a subset is exactly the corresponding rows of the full decode.
        This is the TPU-first answer to the reference's decoder memory hot spot
        (the (B, 512, vocab) logits, SURVEY.md §3.1): callers that only need a
        few positions (e.g. the ~15% masked MLM positions) skip the dominant
        vocab-projection FLOPs for the rest.

        ``return_features=True`` skips the output adapter and returns the
        (B, K, C) decoder stream — for callers that fuse the head into the
        loss (``pallas_linear_cross_entropy_with_ignore``).
        """
        b, *d = x.shape
        if tuple(d) != tuple(self.latent_shape):
            raise ValueError(
                f"Latent shape {tuple(d)} different from required shape "
                f"{tuple(self.latent_shape)}"
            )

        output_shape = self.output_adapter.output_shape
        output = self.param("output", latent_init(), tuple(output_shape))
        if positions is not None:
            # (B, K, C): per-batch rows of the learned query array
            x_output = jnp.take(output, positions, axis=0).astype(self.dtype)
        else:
            x_output = jnp.broadcast_to(output.astype(self.dtype), (b, *output_shape))

        x_output = CrossAttentionLayer(
            num_q_channels=output_shape[-1],
            num_kv_channels=self.latent_shape[1],
            num_heads=self.num_cross_attention_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            name="cross_attention_layer",
        )(x_output, x, deterministic=deterministic)
        if return_features:
            return x_output
        return self.output_adapter(x_output)


class PerceiverIO(nn.Module):
    """encoder → decoder (reference ``model.py:321-325``).

    ``encoder_deterministic`` overrides the dropout mode for the encoder alone —
    the transfer-learning case where a frozen pretrained encoder runs in eval
    mode while the decoder head trains with dropout (the reference's
    ``freeze()`` = requires_grad False + ``.eval()``, ``train/utils.py:5-8``).
    """

    encoder: PerceiverEncoder
    decoder: PerceiverDecoder

    def __call__(self, x, pad_mask=None, deterministic=True,
                 encoder_deterministic: Optional[bool] = None):
        enc_det = deterministic if encoder_deterministic is None else encoder_deterministic
        x_latent = self.encoder(x, pad_mask=pad_mask, deterministic=enc_det)
        return self.decoder(x_latent, deterministic=deterministic)

    def encode(self, x, pad_mask=None, deterministic=True) -> Array:
        """Encoder half only: inputs → (B, N, C) latents.

        The latent array is the model's entire summary of the input —
        Perceiver IO's analogue of a KV cache. Serving callers run this once
        per input and then :meth:`decode` arbitrarily many query sets against
        the cached latents (``model.apply(vars, x, method="encode")``),
        amortizing all O(M) encoder work across decodes.
        """
        return self.encoder(x, pad_mask=pad_mask, deterministic=deterministic)

    def decode(self, x_latent: Array, deterministic=True,
               positions: Optional[Array] = None, return_features: bool = False):
        """Decoder half only: cached latents (+ optional (B, K) query
        ``positions``) → task output. Exactly the fused forward's decoder —
        each output query attends to the latents independently, so
        ``decode(encode(x))`` is the fused ``__call__`` computation."""
        return self.decoder(
            x_latent, deterministic=deterministic, positions=positions,
            return_features=return_features,
        )


class PerceiverARLayer(nn.Module):
    """One causal encoder layer for the Perceiver-AR decode path: causal
    cross-attention (latent window ← full input prefix) + causal latent
    self-attention block.

    Same submodule names as :class:`PerceiverLayer`
    (``cross_attention_layer`` / ``self_attention_block``) so the param tree
    keeps the torch-mirrored leaf names every sharding regex and interop
    mapping matches on. Three call modes share the one weight set:

    - **dense** (training / prefill / the parity oracle): ``causal_offset``
      masks the cross-attention (query i at absolute position offset+i sees
      keys ``<= offset+i``), the self-attention block is square-causal.
      ``return_cache=True`` additionally harvests the tensors an incremental
      decode caches — the cross (k, v) of the input stream and each
      self-attention sub-layer's (k, v) — from the SAME computation.
    - **kv_only**: project one new token's cross (k, v) for the cache ring.
    - **incremental** (``latent_cache``): ``x_latent`` is the (B, 1, C) new
      latent row; cross-attention runs against the caller-updated input ring
      (``kv`` + ``pad_mask`` ring validity), the self-attention block writes
      and attends its per-sub-layer rings at ``latent_index``.
    """

    num_latent_channels: int
    num_input_channels: int
    num_cross_attention_heads: int
    num_self_attention_heads: int
    num_self_attention_layers_per_block: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x_latent, x_input, pad_mask=None, deterministic=True,
                 kv=None, causal_offset=None, kv_only=False,
                 return_cache=False, latent_cache=None, latent_index=None,
                 latent_pad=None):
        xlayer = CrossAttentionLayer(
            num_q_channels=self.num_latent_channels,
            num_kv_channels=self.num_input_channels,
            num_heads=self.num_cross_attention_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            name="cross_attention_layer",
        )
        if kv_only:
            return xlayer(x_latent, x_input, kv_only=True)
        x_latent, kv_out = xlayer(
            x_latent, x_input, pad_mask=pad_mask, deterministic=deterministic,
            kv=kv, return_kv=True, causal_offset=causal_offset,
        )
        block = SelfAttentionBlock(
            num_layers=self.num_self_attention_layers_per_block,
            num_channels=self.num_latent_channels,
            num_heads=self.num_self_attention_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            name="self_attention_block",
        )
        if latent_cache is not None:
            x_latent, rings = block(
                x_latent, deterministic=deterministic, cache=latent_cache,
                cache_index=latent_index, cache_pad=latent_pad,
            )
            return x_latent, rings
        if return_cache:
            x_latent, self_kvs = block(
                x_latent, deterministic=deterministic, causal_offset=0,
                return_kv=True,
            )
            return x_latent, kv_out, self_kvs
        x_latent = block(x_latent, deterministic=deterministic,
                         causal_offset=0)
        return x_latent, kv_out


class PerceiverARLM(nn.Module):
    """Perceiver-AR causal language model (Hawthorne et al., 2022) on the
    Perceiver IO component set: an arbitrary-length token prefix is
    cross-attended into a small causal latent window covering the LAST N
    positions, a causal latent self-attention stack refines it, and a causal
    query decode predicts each window position's successor token.

    Layout (torch-mirrored leaf names, PARAM_RULES-compatible):

    - ``input_adapter``: token embedding + learned positions — the SAME
      adapter the MLM stack uses, so the long-prefix encode rides the r5
      long-context machinery unchanged (streaming fused cross-attention,
      ``attn_impl='auto'`` KV-block tiers).
    - ``latent``: ONE learned (1, C) latent row added to every window
      query. Per-position identity comes from the (position-stable) input
      embedding — a per-slot learned array would re-assign rows as the
      window advances and break incremental-vs-dense parity.
    - ``layer_1`` / ``layer_n``: the encoder recurrence of
      :class:`PerceiverEncoder` (layer 1 unique, layers 2..num_layers ONE
      shared weight set, cross K/V reused across applications), causal.
    - ``output`` + ``cross_attention_layer`` + ``output_adapter``: the
      decode — learned per-position output queries cross-attend the latent
      window DIAGONALLY-causally (query i sees latents ``<= i``; without
      this, a future latent would leak its token into an earlier
      prediction), then the vocab projection.

    Window rule: a length-L input with ``latent_offset`` o (default
    ``L - min(num_latents, L)``) computes ``n = L - o`` latents for absolute
    positions ``[o, L)``; logits row i predicts token ``o + i + 1``.

    Incremental decode (:meth:`prefill` / :meth:`step`): prefill runs the
    dense forward once over the (padded) prefix and harvests every tensor
    the dense path attends over into fixed-capacity cache rings — input
    cross (k, v) per cross weight set, latent (k, v) per (application,
    sub-layer), final-latent (k, v) for the decode — so step t's single-row
    recompute is attending over EXACTLY the dense forward's tensors. That is
    the correctness spine: token-t logits from the cached step match a dense
    full-prefix forward at 2e-5 on the f32 path (pinned tier-1).
    """

    input_adapter: nn.Module
    output_adapter: nn.Module
    num_latents: int
    num_layers: int
    num_cross_attention_heads: int = 4
    num_self_attention_heads: int = 4
    num_self_attention_layers_per_block: int = 2
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    def setup(self):
        c = self.input_adapter.num_input_channels
        self.latent = self.param("latent", latent_init(), (1, c))
        common = dict(
            num_latent_channels=c,
            num_input_channels=c,
            num_cross_attention_heads=self.num_cross_attention_heads,
            num_self_attention_heads=self.num_self_attention_heads,
            num_self_attention_layers_per_block=(
                self.num_self_attention_layers_per_block),
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
        )
        self.layer_1 = PerceiverARLayer(**common)
        if self.num_layers > 1:
            self.layer_n = PerceiverARLayer(**common)
        self.output = self.param(
            "output", latent_init(), tuple(self.output_adapter.output_shape)
        )
        self.cross_attention_layer = CrossAttentionLayer(
            num_q_channels=self.output_adapter.output_shape[-1],
            num_kv_channels=c,
            num_heads=self.num_cross_attention_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
        )

    def _offset(self, l: int, latent_offset: Optional[int]) -> int:
        o = l - min(self.num_latents, l) if latent_offset is None else latent_offset
        if not 0 <= o < l:
            raise ValueError(f"latent_offset {o} outside [0, {l})")
        if l - o > self.num_latents:
            raise ValueError(
                f"latent window {l - o} exceeds num_latents {self.num_latents}"
            )
        return o

    def _encode_window(self, h, pad_mask, o: int, deterministic: bool,
                       return_cache: bool):
        """Shared dense trunk: embedded input → causal latent window."""
        q = h[:, o:] + self.latent.astype(self.dtype)
        caches = []
        if return_cache:
            x, kv1, skvs = self.layer_1(
                q, h, pad_mask=pad_mask, deterministic=deterministic,
                causal_offset=o, return_cache=True)
            caches.append(skvs)
        else:
            x, kv1 = self.layer_1(q, h, pad_mask=pad_mask,
                                  deterministic=deterministic,
                                  causal_offset=o)
        kvn = None
        for _ in range(self.num_layers - 1):
            if return_cache:
                x, kvn, skvs = self.layer_n(
                    x, h, pad_mask=pad_mask, deterministic=deterministic,
                    kv=kvn, causal_offset=o, return_cache=True)
                caches.append(skvs)
            else:
                x, kvn = self.layer_n(x, h, pad_mask=pad_mask,
                                      deterministic=deterministic, kv=kvn,
                                      causal_offset=o)
        return x, kv1, kvn, caches

    def _decode_window(self, x, o: int, n: int, deterministic: bool,
                       return_kv: bool):
        queries = jnp.broadcast_to(
            self.output[o: o + n].astype(self.dtype),
            (x.shape[0], n, self.output.shape[-1]),
        )
        out = self.cross_attention_layer(
            queries, x, deterministic=deterministic, causal_offset=0,
            return_kv=return_kv,
        )
        if return_kv:
            out, final_kv = out
            return self.output_adapter(out), final_kv
        return self.output_adapter(out)

    def __call__(self, token_ids: Array, pad_mask: Optional[Array] = None,
                 deterministic: bool = True,
                 latent_offset: Optional[int] = None) -> Array:
        """Dense causal forward — training and the incremental-parity
        oracle: (B, L) token ids → (B, L - offset, vocab) logits, row i
        predicting token ``offset + i + 1``."""
        h = self.input_adapter(token_ids)
        l = h.shape[1]
        o = self._offset(l, latent_offset)
        x, _, _, _ = self._encode_window(h, pad_mask, o, deterministic, False)
        return self._decode_window(x, o, l - o, deterministic, False)

    def prefill(self, token_ids: Array, pad_mask: Optional[Array] = None,
                length: Optional[Array] = None,
                latent_offset: Optional[int] = None,
                deterministic: bool = True):
        """Dense forward over the (possibly right-padded) prefix + cache
        harvest: returns ``(logits, cache)``. ``length`` (scalar int32
        array) is the REAL token count — slots at positions ``>= length``
        hold pad garbage, are masked by the cache validity rules, and are
        overwritten as generation proceeds. The cache pytree:

        ``len``    scalar int32 — real tokens resident,
        ``cross``  per cross weight set, (k, v) rings (B, W, E) over the
                   input stream (+ the prefix pad mask folded into ``pad``),
        ``pad``    (B, W) bool — True where the ring slot is invalid
                   (beyond ``len``, or a prefix pad token),
        ``latent`` per encoder application, per self-attention sub-layer,
                   (k, v) rings (B, N, E),
        ``final``  (k, v) ring (B, N, E) of decoded latent states.
        """
        h = self.input_adapter(token_ids)
        b, l = token_ids.shape
        o = self._offset(l, latent_offset)
        n = l - o
        if length is None:
            length = jnp.asarray(l, jnp.int32)
        x, kv1, kvn, latent_caches = self._encode_window(
            h, pad_mask, o, deterministic, True)
        logits, final_kv = self._decode_window(x, o, n, deterministic, True)
        invalid = jnp.arange(l, dtype=jnp.int32)[None, :] >= length
        if pad_mask is not None:
            invalid = invalid | pad_mask
        cross = {"layer_1": kv1}
        if self.num_layers > 1:
            cross["layer_n"] = kvn
        cache = {
            "len": jnp.asarray(length, jnp.int32),
            "cross": cross,
            "pad": jnp.broadcast_to(invalid, (b, l)),
            "latent": latent_caches,
            "final": final_kv,
        }
        return logits, cache

    def step(self, cache, token: Array, deterministic: bool = True):
        """One incremental decode step: append ``token`` (B, 1) at position
        ``cache['len']``, recompute ONLY the new latent row against the
        cache rings, and return ``(next_logits (B, vocab), new_cache)`` —
        the logits for position ``len + 1``. Shape-stable in everything but
        the (donatable) cache, so the whole generation loop is one compiled
        program chained by ``lax.fori_loop`` (no host round trip per
        token)."""
        lax = jax.lax
        k1 = cache["cross"]["layer_1"][0]
        b, w, _ = k1.shape
        n_cap = cache["final"][0].shape[1]
        o = w - n_cap
        p = cache["len"]                      # the new token's position
        s = p - o                             # its latent window slot
        zero = jnp.zeros((), jnp.int32)

        pos = jnp.broadcast_to(jnp.reshape(p, (1, 1)), (b, 1))
        h = self.input_adapter(token, positions=pos)

        # append this token's cross k/v per weight set (same projections the
        # dense forward applies — PerceiverARLayer kv_only)
        cross = {}
        layers = {"layer_1": self.layer_1}
        if self.num_layers > 1:
            layers["layer_n"] = self.layer_n
        for name, layer in layers.items():
            k_new, v_new = layer(h, h, kv_only=True)
            k_ring, v_ring = cache["cross"][name]
            cross[name] = (
                lax.dynamic_update_slice(
                    k_ring, k_new.astype(k_ring.dtype), (zero, p, zero)),
                lax.dynamic_update_slice(
                    v_ring, v_new.astype(v_ring.dtype), (zero, p, zero)),
            )
        # ring validity: the new slot becomes live, stale pad slots beyond
        # stay masked (True = masked out)
        live = jnp.arange(w, dtype=jnp.int32)[None, :] == p
        kv_pad = jnp.broadcast_to(
            (cache["pad"] | (jnp.arange(w, dtype=jnp.int32)[None, :] > p))
            & ~live,
            (b, w))
        lat_pad = jnp.broadcast_to(
            jnp.arange(n_cap, dtype=jnp.int32)[None, :] > s, (b, n_cap))

        x = h + self.latent.astype(self.dtype)
        new_latent = []
        apps = [("layer_1", 0)] + [
            ("layer_n", a) for a in range(1, self.num_layers)
        ]
        for name, a in apps:
            x, rings = layers[name](
                x, h, pad_mask=kv_pad, deterministic=deterministic,
                kv=cross[name], latent_cache=cache["latent"][a],
                latent_index=s, latent_pad=lat_pad,
            )
            new_latent.append(rings)

        # decode: append the new final-latent k/v, query = output[p]
        fk, fv = self.cross_attention_layer(x, x, kv_only=True)
        final = (
            lax.dynamic_update_slice(
                cache["final"][0], fk.astype(cache["final"][0].dtype),
                (zero, s, zero)),
            lax.dynamic_update_slice(
                cache["final"][1], fv.astype(cache["final"][1].dtype),
                (zero, s, zero)),
        )
        query = jnp.broadcast_to(
            jnp.take(self.output, jnp.reshape(p, (1,)), axis=0
                     ).astype(self.dtype)[None],
            (b, 1, self.output.shape[-1]),
        )
        dec = self.cross_attention_layer(
            query, x, pad_mask=lat_pad, kv=final,
            deterministic=deterministic,
        )
        logits = self.output_adapter(dec)[:, 0, :]
        new_cache = {
            "len": p + 1,
            "cross": cross,
            "pad": cache["pad"] & ~live,
            "latent": new_latent,
            "final": final,
        }
        return logits, new_cache


class PerceiverMLM(nn.Module):
    """masking → encoder → decoder, logits truncated to input length
    (reference ``model.py:296-318``).

    Masking consumes the ``'masking'`` RNG stream, so a forward with
    ``masking=True`` must be applied with ``rngs={'masking': key}``.
    """

    encoder: PerceiverEncoder
    decoder: PerceiverDecoder
    masking: TextMasking

    def __call__(
        self,
        x_input: Array,
        pad_mask: Optional[Array] = None,
        masking: bool = True,
        deterministic: bool = True,
        loss_gather_capacity: Optional[int] = None,
        return_features: bool = False,
        positions: Optional[Array] = None,
    ) -> Tuple[Array, Optional[Array]]:
        """``loss_gather_capacity``: when set (and ``masking=True``), decode
        only the masked positions — up to that many per row — instead of all L.

        ``positions`` (B, K) int, ``masking=False`` only: decode ONLY these
        positions and return (B, K, vocab) logits (labels None) — the
        inference-side counterpart of the gather decode (each output query
        attends to the latents independently, so this is exactly the
        corresponding rows of the full decode). Long-context fill-mask needs
        this: a full (B, L, vocab) decode at L = 32k+ is a GB-scale tensor
        for a handful of [MASK] positions.

        CE ignores label-(-100) positions entirely, and un-decoded output
        queries receive zero gradient in the full computation too (their logits
        never touch the loss), so loss AND gradients are bit-equivalent to the
        full decode as long as no row has more masked positions than the
        capacity (use ≥ 2·mask_p·L; overflow odds are negligible — at the
        reference config, Binomial(512, 0.15) > 154 is a >13σ event). Skips
        ~(1 − K/L) of the vocab-projection FLOPs, the step's dominant matmul
        (SURVEY.md §3.1 hot spots).
        """
        _, l = x_input.shape

        if positions is not None and masking:
            raise ValueError(
                "positions= is an inference-path argument (masking=False); "
                "training's masked-position gather is loss_gather_capacity="
            )

        if masking:
            key = self.make_rng("masking")
            x_masked, x_labels = self.masking(key, x_input, pad_mask)
        else:
            x_masked = x_input
            x_labels = None

        x_latent = self.encoder(x_masked, pad_mask=pad_mask, deterministic=deterministic)

        if positions is not None:
            x_out = self.decoder(
                x_latent, deterministic=deterministic, positions=positions,
                return_features=return_features,
            )
            return x_out, None

        if masking and loss_gather_capacity is not None:
            # First-K masked indices per row (lax.top_k is index-stable), then
            # earliest unmasked indices; the latter carry label -100 already,
            # so gathered labels mark the padding slots ignored for free.
            # Capacity clamps to the (static) batch width: bucketed-width
            # batches shorter than the configured capacity decode l positions
            # (a permutation of the full decode), never the max_seq_len
            # query count the unclamped full-decode branch would cost.
            capacity = min(loss_gather_capacity, l)
            valid = (x_labels != IGNORE_LABEL).astype(jnp.float32)
            _, gather_positions = jax.lax.top_k(valid, capacity)
            x_out = self.decoder(
                x_latent, deterministic=deterministic,
                positions=gather_positions,
                return_features=return_features,
            )
            return x_out, jnp.take_along_axis(x_labels, gather_positions, axis=1)

        x_out = self.decoder(
            x_latent, deterministic=deterministic,
            return_features=return_features,
        )[:, :l, :]
        return x_out, x_labels

    def encode(self, x_input: Array, pad_mask: Optional[Array] = None,
               deterministic: bool = True) -> Array:
        """Encoder half, inference path (no masking): token ids → latents.

        Encode once, then :meth:`decode` any number of position sets against
        the cached latents — multi-position fill-mask and multi-task decode
        heads pay the encoder cross-attention (all the O(L) work) once.
        Apply with ``model.apply(vars, ids, pad, method="encode")``.
        """
        return self.encoder(x_input, pad_mask=pad_mask, deterministic=deterministic)

    def decode(self, x_latent: Array, deterministic: bool = True,
               positions: Optional[Array] = None,
               return_features: bool = False) -> Array:
        """Decoder half over cached latents: (B, K) ``positions`` → (B, K,
        vocab) logits (None = the full max_seq_len decode — the caller
        truncates to its input length, as ``__call__`` does internally).
        Bit-equivalent to the fused forward's decode: queries never interact,
        so a subset decode is exactly the corresponding rows."""
        return self.decoder(
            x_latent, deterministic=deterministic, positions=positions,
            return_features=return_features,
        )
