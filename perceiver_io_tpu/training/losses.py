"""Loss and metric functions.

Semantics match the reference training layer: cross-entropy with an
ignore-index of -100 for MLM (reference ``lightning.py:88,131-134`` — torch
``CrossEntropyLoss`` default mean over non-ignored elements), plain CE + top-1
accuracy for classification (reference ``lightning.py:153-160``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.ops.masking import IGNORE_LABEL

Array = jax.Array


@jax.custom_vjp
def softmax_ce_integer(logits: Array, labels: Array) -> Array:
    """Per-position CE (lse − label logit), memory-lean.

    Equivalent to ``optax.softmax_cross_entropy_with_integer_labels`` on
    f32-upcast logits, but with a custom VJP so the (…, C) tensor is never
    materialized in f32: the forward keeps row statistics only (f32
    logsumexp; reductions accumulate in f32 straight off the bf16 logits),
    and the backward recomputes ``softmax − onehot`` as one fusion producing
    the logits dtype. At the MLM decode shapes ((B, 160, 10003) vocab
    logits) the f32 upcast and its multi-consumer residuals dominated HBM
    traffic in the loss.
    """
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - ll.astype(jnp.float32)


def _ce_fwd(logits, labels):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - ll.astype(jnp.float32), (logits, labels, lse)


def _ce_bwd(res, g):
    logits, labels, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = (
        jax.lax.broadcasted_iota(labels.dtype, logits.shape, logits.ndim - 1)
        == labels[..., None]
    )
    d = (p - onehot) * g[..., None]
    return d.astype(logits.dtype), np.zeros(labels.shape, jax.dtypes.float0)


softmax_ce_integer.defvjp(_ce_fwd, _ce_bwd)


def pallas_linear_cross_entropy_with_ignore(
    features: Array,
    kernel: Array,
    bias: Array,
    labels: Array,
    ignore_label: int = IGNORE_LABEL,
) -> Array:
    """Mean CE of a linear head applied to ``features``, ignoring
    ``ignore_label`` positions — :func:`cross_entropy_with_ignore` semantics
    with the head matmul fused into the loss on the Pallas flash-CE kernel
    (``ops.pallas_ce``): head matmul + online-logsumexp CE in one kernel, the
    vocab loop a sequential grid inside it; the (..., V) logits never
    materialize, forward or backward."""
    from perceiver_io_tpu.ops.pallas_ce import pallas_linear_ce_integer

    valid = labels != ignore_label
    safe_labels = jnp.where(valid, labels, 0)
    per_pos = pallas_linear_ce_integer(features, kernel, bias, safe_labels)
    denom = jnp.maximum(valid.sum(), 1)
    return jnp.where(valid, per_pos, 0.0).sum() / denom


def cross_entropy_with_ignore(
    logits: Array, labels: Array, ignore_label: int = IGNORE_LABEL
) -> Array:
    """Mean CE over positions where ``labels != ignore_label``.

    logits: (..., C); labels: (...) int. Matches torch
    ``CrossEntropyLoss(ignore_index=-100)`` 'mean' reduction.
    """
    valid = labels != ignore_label
    safe_labels = jnp.where(valid, labels, 0)
    per_pos = softmax_ce_integer(logits, safe_labels)
    denom = jnp.maximum(valid.sum(), 1)
    return jnp.where(valid, per_pos, 0.0).sum() / denom


def classification_loss_and_accuracy(
    logits: Array, labels: Array
) -> Tuple[Array, Array]:
    """(mean CE, top-1 accuracy) for (B, C) logits and (B,) int labels."""
    loss = softmax_ce_integer(logits, labels).mean()
    acc = (jnp.argmax(logits, axis=-1) == labels).mean()
    return loss, acc
