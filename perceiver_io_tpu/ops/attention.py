"""Attention primitives for the Perceiver core, built TPU-first on flax/XLA.

Semantics intentionally match the reference composition so that golden-parity
tests against a torch-assembled model pass bit-for-bit (up to float tolerance):

- ``MultiHeadAttention``: the behavior of ``torch.nn.MultiheadAttention`` with
  ``kdim=vdim=num_kv_channels, batch_first=True`` (reference
  ``perceiver/model.py:59-74``): separate q/k/v projections (with bias),
  1/sqrt(head_dim) scaling, ``key_padding_mask`` (True = ignore), dropout on
  attention probabilities, and an output projection.
- ``CrossAttention``: pre-LN on both query and kv streams, embedding dim = query
  channels (reference ``perceiver/model.py:77-99``).
- ``SelfAttention``: single pre-LN, q = kv (reference ``perceiver/model.py:102-116``).
- ``Residual``: ``dropout(f(*args)) + args[0]`` — the residual applies to the
  *first* positional argument (reference ``perceiver/model.py:47-56``).
- ``MLP``: LayerNorm → Linear → GELU(exact) → Linear at constant width
  (reference ``perceiver/model.py:20-26``).

Initialization matches torch defaults so quality parity holds from step 0:
xavier-uniform q/k/v projections with zero biases, U(±1/sqrt(fan_in)) for
plain Linear layers (torch ``nn.Linear`` default), zero out-proj bias.
LayerNorm uses torch's epsilon (1e-5, vs flax's 1e-6 default) — material on
the low-variance latent stream (init std 0.02), where the epsilon shifts the
normalized output by ~0.1%.

The attention inner product is pluggable: ``attn_impl='xla'`` uses pure
jnp/einsum (XLA fuses this well on the MXU); ``attn_impl='pallas'`` dispatches
to the streaming fused Pallas kernel in ``perceiver_io_tpu.ops.pallas_attention``;
``'auto'`` (default) picks per call site: the fused kernel for long KV streams
(image/flow inputs) and for big-logits self-attention stacks, XLA for
small/shallow shapes (text) — see ``auto_attention_impl``.

Sequence parallelism: under an active regime
(``parallel.mesh.sequence_parallel_context`` — entered by
``make_sharded_train_step(shard_seq=True)``), attention calls marked
``seq_shard_kv=True`` (the encoder cross-attention, whose KV stream is the
seq-sharded input) route the kernel path through
``seq_parallel_fused_attention``: each device's ``pallas_call`` streams only
its S/n KV shard and softmax statistics merge with O(B·H·T) collectives,
instead of GSPMD all-gathering the KV stream around the kernel.
``attn_impl='pallas_sp'`` forces the kernel path with sp routing (degrading
to plain 'pallas' where sp doesn't apply); ``'auto'`` picks sp whenever it
would have picked the kernel and the regime is active.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

# Every projection in this module applies through ``linear_apply``: plain
# tensors take the exact flax-Dense math (promote_dtype + x @ w + b), while
# a quantized tree's ``QKernel`` leaves (quant/int8.py) dispatch to the
# fused dequant-matmul kernel — the model code itself never branches on
# quantization beyond the fused-stack special case below.
from perceiver_io_tpu.ops.pallas_matmul import linear_apply
from perceiver_io_tpu.quant.int8 import QKernel

Array = jax.Array

# torch nn.Linear default init: U(±1/sqrt(fan_in)) for weight and bias
# (kaiming_uniform(a=sqrt(5)) reduces to this bound for the weight).
torch_linear_kernel_init = nn.initializers.variance_scaling(
    scale=1.0 / 3.0, mode="fan_in", distribution="uniform"
)

# torch nn.LayerNorm default epsilon (flax defaults to 1e-6)
LN_EPS = 1e-5

# 'auto' attention dispatch (v5e measurements, tools/attn_shapes_bench.py).
# The XLA path materializes (B, H, T, S) logits, so its cost per logit byte is
# ~d/2 FLOPs: deep-contraction heads (d >= 1024) are compute-bound and XLA's
# matmul emitter wins (1.4x at ImageNet's 1-head d=1024 cross-attn); shallow
# heads are HBM-bound on the logits and the fused kernel wins (2.4x fwd+bwd
# at d=128, S=50k). d=512 measures a wash on time, where the kernel's O(S)
# memory breaks the tie. Short streams (text, S<=512 latents) are always XLA:
# those MXU-hostile d=16 shapes express worse in Mosaic than in the einsum.
#
# A second, area-based trigger covers big SELF-attention stacks whose S sits
# under the KV threshold: at flow's (2, 2048, 2048, 8, 64) the materialized
# logits are 67M elements and the kernel measures 2.0x fwd+bwd (1.44 vs
# 2.85 ms — it never writes the 134 MB/layer logits). The d >= 32 guard keeps
# the MXU-hostile d=16 text shapes on XLA at any batch (measured 6x slower in
# Mosaic at d=16).
AUTO_PALLAS_MIN_KV = 4096
AUTO_PALLAS_MAX_HEAD_DIM = 512
AUTO_PALLAS_MIN_LOGITS = 32 * 1024 * 1024  # B·H·T·S elements
AUTO_PALLAS_AREA_MIN_HEAD_DIM = 32

# ``jax.checkpoint`` names of what the XLA path of a CROSS-attention leaves
# for its backward pass: the materialized (B, H, T, S) logits, the weighted
# sum's (B, T, E) output (``out_proj``'s weight gradient reads it), and the
# (k, v) projected here. A rematerialization policy that names them keeps
# them (``PerceiverEncoder``, ``remat=True``); recomputing the logits and the
# weighted sum costs two S-long matmuls, 2d operations per logit byte. Under a
# bare ``jax.checkpoint`` and outside one the names lower to nothing.
# Self-attention logits are not named: a latent stack's residuals are many
# and small, and stay recomputed.
REMAT_CROSS_LOGITS = "cross_attention_logits"
REMAT_CROSS_CONTEXT = "cross_attention_context"
REMAT_CROSS_KV = "cross_attention_kv"


def auto_attention_impl(
    b: int, t: int, s: int, h: int, d: int, backend: Optional[str] = None
) -> str:
    """Resolve ``attn_impl='auto'`` for a (B, T, S, H, D) attention call.

    Pallas iff the backend is TPU, D ≤ 512, and either the KV stream is long
    (S ≥ 4096 — the streaming-cross case) or the materialized logits would be
    large with a non-tiny head (B·H·T·S ≥ 32M and D ≥ 32 — the big
    self-attention case). Encodes the `tools/attn_shapes_bench.py`
    measurements in PERF.md; change only with new rows there.
    """
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" or d > AUTO_PALLAS_MAX_HEAD_DIM:
        return "xla"
    long_kv = s >= AUTO_PALLAS_MIN_KV
    big_logits = (
        b * h * t * s >= AUTO_PALLAS_MIN_LOGITS
        and d >= AUTO_PALLAS_AREA_MIN_HEAD_DIM
    )
    return "pallas" if (long_kv or big_logits) else "xla"


def layer_norm(dtype, name: str) -> nn.LayerNorm:
    return nn.LayerNorm(epsilon=LN_EPS, dtype=dtype, name=name)


def torch_linear_bias_init(fan_in: int):
    bound = 1.0 / (fan_in**0.5)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def _dot_product_attention(
    q: Array,
    k: Array,
    v: Array,
    pad_mask: Optional[Array],
    attn_mask: Optional[Array],
    dropout_rate: float,
    dropout_rng: Optional[Array],
    deterministic: bool,
    name_residuals: bool = False,
) -> Array:
    """Scaled dot-product attention over (B, T, H, D) tensors.

    pad_mask: (B, S) bool, True = position is padding (masked OUT) — the
    ``key_padding_mask`` convention of the reference's torch MHA.
    attn_mask: (T, S) or (B, T, S) additive-style bool, True = masked OUT.
    name_residuals: give the logits and the output their ``REMAT_CROSS_*``
    checkpoint names (cross-attention callers).
    """
    d = q.shape[-1]
    scale = d**-0.5
    # (B, H, T, S) logits: contract head dim. For f32 operands request
    # HIGHEST precision (the MXU's default single bf16 pass costs ~3 decimal
    # digits; the Pallas kernel does the same — ops/pallas_attention) and
    # keep f32 logits. For bf16 operands, *store* the materialized logits in
    # bf16: the MXU still accumulates in f32 and only the stored value is
    # rounded (~2⁻⁸ relative), while softmax math below upcasts to f32 inside
    # the fused reduction. The (B, H, T, S) logits are the dominant HBM
    # traffic of the latent self-attention stack, and XLA cannot fuse across
    # the two matmuls — halving their bytes is a measured ~30% step-time win
    # on the flagship MLM config (PERF.md).
    if q.dtype == jnp.float32:
        precision, logits_dtype = jax.lax.Precision.HIGHEST, jnp.float32
    else:
        precision, logits_dtype = None, q.dtype
    logits = jnp.einsum(
        "bthd,bshd->bhts", q * scale, k,
        preferred_element_type=logits_dtype, precision=precision,
    )

    neg = jnp.finfo(logits.dtype).min
    if pad_mask is not None:
        logits = jnp.where(pad_mask[:, None, None, :], neg, logits)
    if attn_mask is not None:
        if attn_mask.ndim == 2:
            attn_mask = attn_mask[None]
        logits = jnp.where(attn_mask[:, None, :, :], neg, logits)
    if name_residuals:
        logits = checkpoint_name(logits, REMAT_CROSS_LOGITS)

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)

    probs = probs.astype(v.dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, v, precision=precision)
    if name_residuals:
        out = checkpoint_name(out, REMAT_CROSS_CONTEXT)
    return out


class _LinearParams(nn.Module):
    """Declare a Linear's kernel/bias without applying it — the param tree is
    identical to ``nn.Dense`` (``{name: {kernel, bias}}``), so checkpoints,
    sharding path rules, and the torch-parity mapping are unchanged, while the
    caller is free to fuse several projections into one matmul."""

    in_features: int
    features: int
    kernel_init: Any = nn.initializers.xavier_uniform()
    bias_init: Any = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self) -> Tuple[Array, Array]:
        kernel = self.param(
            "kernel", self.kernel_init, (self.in_features, self.features)
        )
        bias = self.param("bias", self.bias_init, (self.features,))
        return kernel, bias


class MultiHeadAttention(nn.Module):
    """Multi-head attention with distinct query / key-value channel counts.

    Mirrors torch ``nn.MultiheadAttention(embed_dim=num_q_channels,
    kdim=vdim=num_kv_channels, batch_first=True)`` as used at reference
    ``perceiver/model.py:59-74``.
    """

    num_q_channels: int
    num_kv_channels: int
    num_heads: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"  # 'auto' | 'xla' | 'pallas' | 'pallas_sp'
    # Structural marker set by the ENCODER on its cross-attention: this call's
    # KV stream is the adapted input whose sequence axis shards over the mesh's
    # seq axis under shard_seq=True. Only such calls may route to the
    # sequence-parallel kernel — the latent self-attention and decoder
    # cross-attention have replicated (latent-sized) KV, where sp routing
    # would be legal but pointless collective traffic.
    seq_shard_kv: bool = False

    @nn.compact
    def __call__(
        self,
        x_q: Array,
        x_kv: Array,
        pad_mask: Optional[Array] = None,
        attn_mask: Optional[Array] = None,
        deterministic: bool = True,
        kv: Optional[Tuple[Array, Array]] = None,
        return_kv: bool = False,
        causal_offset: Optional[int] = None,
        kv_only: bool = False,
    ) -> Any:
        """``kv``: optional precomputed (k, v) projections — (B, S, E) in
        compute dtype, as returned by a previous call with ``return_kv=True``.
        When the same weights attend the same KV stream repeatedly (the
        encoder's shared ``layer_n`` recurrence), the K/V projections are
        identical across applications; passing them back in skips the repeat.
        Exact by construction — same tensors, not a re-computation. The
        forward dedup XLA's CSE sometimes finds anyway; the real win is the
        BACKWARD, where autodiff otherwise emits a full dW/dx projection pass
        per application (measured on the 131k-token MLM config, PERF.md r5).

        ``causal_offset``: static int — query row i may attend key positions
        ``<= i + causal_offset`` (``ops.masking.causal_mask``), composed with
        ``pad_mask``/``attn_mask`` by OR. The explicit kernel path applies it
        in-kernel (``fused_attention(causal_offset=)``); 'auto' dispatches
        causal shapes to XLA for now — the decode-shape sweep that would set
        kernel thresholds has not been run on a chip (PERF.md), and
        an unmeasured dispatch flip is exactly what the threshold invariants
        forbid.

        ``kv_only``: project and return ONLY this call's (k, v) of ``x_kv``
        — no attention, no output projection. The incremental-decode path
        uses it to append one new row to a KV cache ring with the SAME
        weights the dense path projects with (cache parity by construction).
        """
        e = self.num_q_channels
        h = self.num_heads
        if e % h != 0:
            raise ValueError(f"num_q_channels {e} not divisible by num_heads {h}")
        if self.attn_impl not in ("auto", "xla", "pallas", "pallas_sp"):
            # a typo'd impl must not silently fall through to the XLA branch
            # and get benchmarked under the wrong label (PERF.md discipline)
            raise ValueError(
                f"unknown attn_impl {self.attn_impl!r}; expected one of "
                "'auto', 'xla', 'pallas', 'pallas_sp'"
            )
        d = e // h

        if kv_only:
            # k/v projections only — q_proj/out_proj are neither declared
            # nor touched (their in_features belong to the query stream,
            # which this call does not have)
            wk, bk = _LinearParams(x_kv.shape[-1], e, name="k_proj")()
            wv, bv = _LinearParams(x_kv.shape[-1], e, name="v_proj")()
            return (linear_apply(x_kv, wk, bk, self.dtype),
                    linear_apply(x_kv, wv, bv, self.dtype))

        wq, bq = _LinearParams(x_q.shape[-1], e, name="q_proj")()
        wk, bk = _LinearParams(x_kv.shape[-1], e, name="k_proj")()
        wv, bv = _LinearParams(x_kv.shape[-1], e, name="v_proj")()
        if kv is not None:
            k, v = kv
            q = linear_apply(x_q, wq, bq, self.dtype)
        elif isinstance(wq, QKernel) and x_q is x_kv:
            # quantized self-attention: the fused-stack trick below cannot
            # stack int kernels with distinct scale grids, so the three
            # projections apply separately through the dequant-matmul
            # kernel. The stack's win was reading the input once on the
            # TRAINING path; on the quantized serving path the weight
            # stream is the bill, and that still streams int bytes here.
            q = linear_apply(x_q, wq, bq, self.dtype)
            k = linear_apply(x_kv, wk, bk, self.dtype)
            v = linear_apply(x_kv, wv, bv, self.dtype)
        elif x_q is x_kv:
            # self-attention: one fused matmul instead of three — the input
            # is read once and the three skinny gemms become one (measured
            # ~6% step win on the flagship MLM config, PERF.md). Identical
            # math: each output column is an independent dot product.
            # The fusion stacks the weights on a FRESH leading axis, (3, C,
            # E), rather than concatenating to (C, 3E): the three kernels
            # are tensor-parallel-sharded over their LAST axis (PARAM_RULES
            # (None, 'model')), and a concat along that sharded axis forces
            # an interleaving reshard that this XLA build's SPMD partitioner
            # miscompiles (repro'd: ~10 abs error on a 2-way model mesh; the
            # stacked form is bitwise-identical unsharded and exact sharded).
            w = jnp.stack([wq, wk, wv])
            bias = jnp.stack([bq, bk, bv])
            x, w, bias = nn.dtypes.promote_dtype(x_q, w, bias, dtype=self.dtype)
            qkv = jnp.einsum("btc,nce->btne", x, w) + bias
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            q = linear_apply(x_q, wq, bq, self.dtype)
            k = linear_apply(x_kv, wk, bk, self.dtype)
            v = linear_apply(x_kv, wv, bv, self.dtype)
            if return_kv:
                # projected HERE and handed on (the encoder's shared-layer
                # cache): a caller's cached ``kv`` above is an input of the
                # layer and needs no name
                k = checkpoint_name(k, REMAT_CROSS_KV)
                v = checkpoint_name(v, REMAT_CROSS_KV)

        b, t = q.shape[:2]
        s = k.shape[1]

        dropout_active = self.dropout > 0.0 and not deterministic
        dropout_rng = self.make_rng("dropout") if dropout_active else None

        # The fused kernels cover the Perceiver hot path: pad-masked or
        # unmasked attention without prob-dropout. attn_mask / prob-dropout
        # fall back to the XLA path (never silently dropped).
        #
        # 'auto' (the default) picks per call site — long KV stream with
        # shallow heads → streaming fused kernel; everything else → XLA
        # einsum.
        impl = self.attn_impl
        # Sequence-parallel routing: active regime (make_sharded_train_step
        # shard_seq=True over a mesh with seq > 1) + this call marked as the
        # seq-sharded KV consumer + KV length divisible by the axis. Explicit
        # 'pallas_sp' degrades to 'pallas' wherever sp doesn't apply, so one
        # model-level flag flips only the encoder cross-attention.
        sp = None
        if (self.seq_shard_kv and causal_offset is None
                and impl in ("auto", "pallas", "pallas_sp")):
            from perceiver_io_tpu.parallel.mesh import active_sequence_parallel

            ctx = active_sequence_parallel()
            if ctx is not None and s % ctx.mesh.shape[ctx.axis] == 0 and (
                ctx.batch_axis is None
                or b % ctx.mesh.shape[ctx.batch_axis] == 0
            ):
                # both divisibility guards matter: shard_map's in_specs
                # require exact splits, and eval batches (e.g. a drop_last=
                # False tail) may not divide the data axis — those fall back
                # to the plain kernel/XLA path, which GSPMD handles
                sp = ctx
        if impl == "pallas_sp":
            impl = "pallas"
        if impl == "auto":
            # TPU-only (off-TPU the kernel would run in interpreter mode,
            # orders of magnitude slower; explicit 'pallas' keeps that
            # fallback for tests): long KV streams and big-logits
            # self-attention go to the fused kernel, everything else to XLA
            # (see auto_attention_impl). Mesh-aware: under an active
            # seq-parallel regime the same shapes route to the sp kernel.
            # Causal (AR decode) shapes resolve CONSERVATIVELY to XLA until
            # the decode-shape sweep lands (tools/attn_shapes_bench.py
            # --decode; queued in PERF.md §Generation — dispatch thresholds
            # only move with measurements). Explicit 'pallas' takes the
            # kernel's in-kernel causal flag.
            impl = ("xla" if causal_offset is not None
                    else auto_attention_impl(b, t, s, h, d))
        fusable = attn_mask is None and not dropout_active
        if impl == "pallas" and fusable and sp is not None:
            from perceiver_io_tpu.ops.pallas_attention import (
                seq_parallel_fused_attention,
            )

            head_axis = sp.head_axis
            if head_axis is not None and h % sp.mesh.shape[head_axis]:
                head_axis = None  # indivisible heads replicate over tp
            out = seq_parallel_fused_attention(
                q.reshape(b, t, h, d), k.reshape(b, s, h, d),
                v.reshape(b, s, h, d), pad_mask=pad_mask,
                mesh=sp.mesh, axis=sp.axis, batch_axis=sp.batch_axis,
                head_axis=head_axis,
            ).reshape(b, t, e)
        elif impl == "pallas" and fusable:
            from perceiver_io_tpu.ops.pallas_attention import fused_attention

            out = fused_attention(
                q.reshape(b, t, h, d), k.reshape(b, s, h, d),
                v.reshape(b, s, h, d), pad_mask=pad_mask,
                causal_offset=causal_offset,
            ).reshape(b, t, e)
        else:
            if causal_offset is not None:
                from perceiver_io_tpu.ops.masking import causal_mask

                cmask = causal_mask(t, s, causal_offset)
                attn_mask = (cmask if attn_mask is None
                             else attn_mask | cmask)
            out = _dot_product_attention(
                q.reshape(b, t, h, d), k.reshape(b, s, h, d),
                v.reshape(b, s, h, d), pad_mask, attn_mask,
                self.dropout, dropout_rng, deterministic,
                name_residuals=x_q is not x_kv,
            ).reshape(b, t, e)
        wo, bo = _LinearParams(e, e, kernel_init=torch_linear_kernel_init,
                               name="out_proj")()
        out = linear_apply(out, wo, bo, self.dtype)
        if return_kv:
            return out, (k, v)
        return out


class CrossAttention(nn.Module):
    """Pre-LN cross-attention; embedding dim = query channels.

    Reference ``perceiver/model.py:77-99`` (including its documented
    simplification: the attention embedding dimension equals the number of
    query channels rather than being independently configurable).
    """

    num_q_channels: int
    num_kv_channels: int
    num_heads: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"
    seq_shard_kv: bool = False

    @nn.compact
    def __call__(self, x_q, x_kv, pad_mask=None, attn_mask=None, deterministic=True,
                 kv=None, return_kv=False, causal_offset=None, kv_only=False):
        """``kv``/``return_kv``: precomputed K/V reuse across shared-weight
        applications (see ``MultiHeadAttention``). With ``kv`` given, the
        kv_norm + k/v projections are skipped entirely — the cached tensors
        already include them. ``kv_only``: kv_norm + k/v projections of
        ``x_kv`` ONLY (no query side at all) — what a decode step appends to
        its cache ring, bit-identical to what a dense forward would have
        projected for the same rows. ``causal_offset``: see
        :class:`MultiHeadAttention`."""
        mha = MultiHeadAttention(
            num_q_channels=self.num_q_channels,
            num_kv_channels=self.num_kv_channels,
            num_heads=self.num_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            seq_shard_kv=self.seq_shard_kv,
            name="attention",
        )
        if kv_only:
            x_kv = layer_norm(self.dtype, "kv_norm")(x_kv)
            return mha(x_kv, x_kv, kv_only=True)
        x_q = layer_norm(self.dtype, "q_norm")(x_q)
        if kv is None:
            x_kv = layer_norm(self.dtype, "kv_norm")(x_kv)
        return mha(x_q, x_kv, pad_mask=pad_mask, attn_mask=attn_mask,
                   deterministic=deterministic, kv=kv, return_kv=return_kv,
                   causal_offset=causal_offset)


class SelfAttention(nn.Module):
    """Pre-LN self-attention, q = kv (reference ``perceiver/model.py:102-116``)."""

    num_channels: int
    num_heads: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, pad_mask=None, attn_mask=None, deterministic=True,
                 causal_offset=None, kv=None, kv_only=False):
        """``causal_offset``/``kv``/``kv_only``: the causal + KV-cache
        surface (see :class:`MultiHeadAttention`) — ``kv_only`` returns this
        stream's post-norm (k, v) rows for a decode cache ring; ``kv`` runs
        the query side of ``x`` against a caller-held ring instead of
        re-projecting the stream."""
        x = layer_norm(self.dtype, "norm")(x)
        mha = MultiHeadAttention(
            num_q_channels=self.num_channels,
            num_kv_channels=self.num_channels,
            num_heads=self.num_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            name="attention",
        )
        if kv_only:
            return mha(x, x, kv_only=True)
        return mha(x, x, pad_mask=pad_mask, attn_mask=attn_mask,
                   deterministic=deterministic, causal_offset=causal_offset,
                   kv=kv)


class MLP(nn.Module):
    """LayerNorm → Linear → GELU(exact) → Linear, constant width.

    Reference ``perceiver/model.py:20-26``. torch-default Linear init.
    """

    num_channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.num_channels
        x = layer_norm(self.dtype, "norm")(x)
        w1, b1 = _LinearParams(
            x.shape[-1], c, kernel_init=torch_linear_kernel_init,
            bias_init=torch_linear_bias_init(c), name="dense_1")()
        x = linear_apply(x, w1, b1, self.dtype)
        x = nn.gelu(x, approximate=False)
        w2, b2 = _LinearParams(
            c, c, kernel_init=torch_linear_kernel_init,
            bias_init=torch_linear_bias_init(c), name="dense_2")()
        x = linear_apply(x, w2, b2, self.dtype)
        return x


class CrossAttentionLayer(nn.Module):
    """Residual(CrossAttention) → Residual(MLP) on the query stream.

    Reference ``perceiver/model.py:29-34``: the residual adds the *first*
    positional argument — for cross-attention, the query/latent stream.
    """

    num_q_channels: int
    num_kv_channels: int
    num_heads: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"
    seq_shard_kv: bool = False

    @nn.compact
    def __call__(self, x_q, x_kv, pad_mask=None, deterministic=True,
                 kv=None, return_kv=False, causal_offset=None,
                 kv_only=False):
        # Residual adds the FIRST positional arg (reference model.py:47-56):
        # for cross-attention that is the query/latent stream.
        drop = nn.Dropout(rate=self.dropout)
        xattn = CrossAttention(
            num_q_channels=self.num_q_channels,
            num_kv_channels=self.num_kv_channels,
            num_heads=self.num_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            seq_shard_kv=self.seq_shard_kv,
            name="cross_attention",
        )
        if kv_only:
            # the decode-step cache append: kv_norm + k/v projections of
            # x_kv only, no query/residual/MLP work (see CrossAttention)
            return xattn(x_q, x_kv, kv_only=True)
        attn_out = xattn(x_q, x_kv, pad_mask=pad_mask,
                         deterministic=deterministic, kv=kv,
                         return_kv=return_kv, causal_offset=causal_offset)
        if return_kv:
            attn_out, kv_out = attn_out
        x = drop(attn_out, deterministic=deterministic) + x_q
        mlp_out = MLP(self.num_q_channels, dtype=self.dtype, name="mlp")(x)
        out = drop(mlp_out, deterministic=deterministic) + x
        if return_kv:
            return out, kv_out
        return out


class SelfAttentionLayer(nn.Module):
    """Residual(SelfAttention) → Residual(MLP) (reference ``perceiver/model.py:37-40``)."""

    num_channels: int
    num_heads: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, deterministic=True, attn_mask=None,
                 causal_offset=None, return_kv=False,
                 cache=None, cache_index=None, cache_pad=None):
        """Three modes sharing one weight set:

        - plain (default): the MLM path, unchanged.
        - dense causal (``causal_offset``/``attn_mask``): the AR training /
          prefill forward. ``return_kv=True`` additionally returns this
          layer's post-norm (k, v) of the full stream — exactly the rows a
          decode cache ring holds, so prefill builds its caches from the
          SAME tensors the dense forward attends over (parity by
          construction).
        - incremental (``cache``): ``x`` is the (B, 1, C) new-row stream;
          the layer projects the row's k/v, writes them at ``cache_index``
          (scalar int array) into the (B, S_cap, E) rings, attends the
          single query over the updated rings under ``cache_pad`` (B, S_cap;
          True = empty/invalid slot), and returns ``(out, updated_cache)``.
        """
        import jax.lax as lax

        drop = nn.Dropout(rate=self.dropout)
        attn = SelfAttention(
            num_channels=self.num_channels,
            num_heads=self.num_heads,
            dropout=self.dropout,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            name="self_attention",
        )
        if cache is not None:
            k_ring, v_ring = cache
            k_new, v_new = attn(x, kv_only=True)
            zero = jnp.zeros((), jnp.int32)
            k_ring = lax.dynamic_update_slice(
                k_ring, k_new.astype(k_ring.dtype), (zero, cache_index, zero))
            v_ring = lax.dynamic_update_slice(
                v_ring, v_new.astype(v_ring.dtype), (zero, cache_index, zero))
            attn_out = attn(x, pad_mask=cache_pad, kv=(k_ring, v_ring),
                            deterministic=deterministic)
        elif return_kv:
            k_full, v_full = attn(x, kv_only=True)
            attn_out = attn(x, attn_mask=attn_mask,
                            causal_offset=causal_offset,
                            kv=(k_full, v_full),
                            deterministic=deterministic)
        else:
            attn_out = attn(x, attn_mask=attn_mask,
                            causal_offset=causal_offset,
                            deterministic=deterministic)
        x = drop(attn_out, deterministic=deterministic) + x
        mlp_out = MLP(self.num_channels, dtype=self.dtype, name="mlp")(x)
        out = drop(mlp_out, deterministic=deterministic) + x
        if cache is not None:
            return out, (k_ring, v_ring)
        if return_kv:
            return out, (k_full, v_full)
        return out


class SelfAttentionBlock(nn.Module):
    """N stacked self-attention layers, each with its own weights.

    Reference ``perceiver/model.py:43-44``. Inside an encoder layer, the whole
    block's weights are shared across recurrent applications (see
    ``PerceiverEncoder``), but layers *within* a block are distinct.
    """

    num_layers: int
    num_channels: int
    num_heads: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, deterministic=True, attn_mask=None,
                 causal_offset=None, return_kv=False,
                 cache=None, cache_index=None, cache_pad=None):
        """Causal/cache surface mirrors :class:`SelfAttentionLayer`, with
        ``cache`` (and the ``return_kv`` harvest) as a LIST of per-layer
        (k, v) pairs — each stacked layer owns one ring."""
        kvs = []
        updated = []
        for i in range(self.num_layers):
            layer = SelfAttentionLayer(
                num_channels=self.num_channels,
                num_heads=self.num_heads,
                dropout=self.dropout,
                dtype=self.dtype,
                attn_impl=self.attn_impl,
                name=f"layer_{i}",
            )
            if cache is not None:
                x, ring = layer(x, deterministic=deterministic,
                                cache=cache[i], cache_index=cache_index,
                                cache_pad=cache_pad)
                updated.append(ring)
            elif return_kv:
                x, kv = layer(x, deterministic=deterministic,
                              attn_mask=attn_mask,
                              causal_offset=causal_offset, return_kv=True)
                kvs.append(kv)
            else:
                x = layer(x, deterministic=deterministic,
                          attn_mask=attn_mask, causal_offset=causal_offset)
        if cache is not None:
            return x, updated
        if return_kv:
            return x, kvs
        return x
