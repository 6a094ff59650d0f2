"""Fused linear + cross-entropy Pallas kernel (TPU): the classifier head
matmul and the softmax CE in ONE kernel, so the (rows, vocab) logits tensor
never exists in HBM — forward or backward.

Motivation (device-trace measurement, PERF.md round 3): on the flagship MLM
config the unfused head complex — vocab matmul, CE reductions, softmax-grad
matmuls — costs ~1.4 ms of a 10.4 ms step, nearly all of it streaming the
206 MB (64, 160, 10003) bf16 logits tensor at HBM peak (~5 passes ≈ 1 GB of
traffic per step). This kernel runs an online-logsumexp recurrence over
vocab blocks INSIDE one ``pallas_call`` — the vocab axis is the innermost
sequential grid dimension, per-block logits live only in VMEM, and the MXU
stays on one stream of (rows × vocab-block) matmuls.

Layout notes:

- grid ``(R/r_blk, V/v_blk)``, vocab innermost: running max ``m``, sum ``s``
  and the picked label logit ``ll`` live in VMEM scratch across vocab blocks
  (flash-attention's recurrence applied to a classifier head).
- the label pick needs no gather: each block compares its global column iota
  to the row's label and sums the single hit — a VPU-friendly masked
  reduction.
- backward recomputes per-block probabilities from the saved row logsumexp
  and fuses the softmax gradient into both transposed matmuls: a dx kernel
  (vocab sequential) and a dw/db kernel (rows sequential) — the same
  two-kernel split as the flash-attention backward in ``pallas_attention``.
- vocab is padded to a block multiple with ``bias = PAD_BIAS`` columns
  (exp → 0 against any live logit; labels never point at padding).

Sharding: this kernel is a single-device op. Under tensor parallelism the
vocab projection shards over the ``model`` axis and the UNFUSED path (whose
collectives GSPMD manages) remains the default; the fused head is the
single-chip / long-decode memory-and-bandwidth lever (``make_mlm_steps``
``fused_head=``).

Reference behavior replaced: the ``(B, 512, vocab)`` logits + CE identified
as the reference's memory hot spot (SURVEY.md §3.1, reference
``lightning.py:131-134``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_LANES = 128
# Test hook (tests/test_pallas_ce.py fuzz): force the compiled sublane
# alignment in interpret-mode runs so CPU property tests exercise the same
# row-block padding rule hardware takes (see the matching hook in
# pallas_attention.py). None = derive from ``interpret``.
_TEST_ALIGNMENT = None
# Finite stand-ins (see pallas_attention): PAD_BIAS marks kernel-added vocab
# padding; exp(PAD_BIAS - anything_live) underflows to exactly 0.
MASK_VALUE = -1e30
PAD_BIAS = 2.0 * MASK_VALUE

DEFAULT_R_BLOCK = 512
DEFAULT_V_BLOCK = 1024


def _dot(a, b, contract):
    precision = (jax.lax.Precision.HIGHEST
                 if a.dtype == jnp.float32 and b.dtype == jnp.float32 else None)
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )


def _block_logits(x_ref, w_ref, b_ref):
    """(r_blk, v_blk) f32 logits for this grid step: x @ w + bias.

    The weight block is cast to the feature dtype in VMEM: the matmul runs
    in the compute dtype (matching the unfused path's promote_dtype) while
    the weight stays f32 in HBM so its COTANGENT keeps f32 precision."""
    x = x_ref[:]
    logits = _dot(x, w_ref[:].astype(x.dtype), (1, 0))
    return logits + b_ref[0][None, :]  # (1, v_blk) broadcasts over rows


def _fwd_kernel(labels_ref, x_ref, w_ref, b_ref, loss_ref, lse_ref,
                m_ref, s_ref, ll_ref, *, v_blk: int):
    v_idx = pl.program_id(1)

    @pl.when(v_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        s_ref[:] = jnp.zeros_like(s_ref)
        ll_ref[:] = jnp.zeros_like(ll_ref)

    logits = _block_logits(x_ref, w_ref, b_ref)  # (r_blk, v_blk) f32

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    s_new = alpha * s_ref[:, :1] + jnp.sum(
        jnp.exp(logits - m_new), axis=-1, keepdims=True
    )

    # label pick: one masked reduction instead of a gather
    label = labels_ref[:, :1]  # (r_blk, 1) int32
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + v_idx * v_blk
    picked = jnp.sum(jnp.where(col == label, logits, 0.0), axis=-1,
                     keepdims=True)

    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    s_ref[:] = jnp.broadcast_to(s_new, s_ref.shape)
    ll_ref[:] = ll_ref[:] + jnp.broadcast_to(picked, ll_ref.shape)

    @pl.when(v_idx == pl.num_programs(1) - 1)
    def _finish():
        lse = m_ref[:, :1] + jnp.log(s_ref[:, :1])
        loss_ref[:] = jnp.broadcast_to(lse - ll_ref[:, :1], loss_ref.shape)
        lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_probs_grad(labels_ref, x_ref, w_ref, b_ref, lse_ref, g_ref, v_idx,
                    v_blk: int):
    """Recompute this block's softmax-grad ``d = (p − onehot(label))·g``."""
    logits = _block_logits(x_ref, w_ref, b_ref)
    p = jnp.exp(logits - lse_ref[:, :1])
    label = labels_ref[:, :1]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + v_idx * v_blk
    onehot = (col == label).astype(jnp.float32)
    return (p - onehot) * g_ref[:, :1]


def _bwd_dx_kernel(labels_ref, x_ref, w_ref, b_ref, lse_ref, g_ref,
                   dx_ref, acc_ref, *, v_blk: int):
    v_idx = pl.program_id(1)

    @pl.when(v_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    d = _bwd_probs_grad(labels_ref, x_ref, w_ref, b_ref, lse_ref, g_ref,
                        v_idx, v_blk)
    d = d.astype(x_ref.dtype)  # softmax grad in the compute dtype (as unfused)
    acc_ref[:] += _dot(d, w_ref[:].astype(d.dtype), (1, 1))  # (r_blk, C)

    @pl.when(v_idx == pl.num_programs(1) - 1)
    def _finish():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def _bwd_dw_kernel(labels_ref, x_ref, w_ref, b_ref, lse_ref, g_ref,
                   dw_ref, db_ref, dw_acc, db_acc, *, v_blk: int):
    r_idx = pl.program_id(1)

    @pl.when(r_idx == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)
        db_acc[:] = jnp.zeros_like(db_acc)

    v_idx = pl.program_id(0)
    d = _bwd_probs_grad(labels_ref, x_ref, w_ref, b_ref, lse_ref, g_ref,
                        v_idx, v_blk)
    db_acc[:] += jnp.sum(d, axis=0, keepdims=True)  # (1, v_blk) f32
    d = d.astype(x_ref.dtype)
    dw_acc[:] += _dot(x_ref[:], d, (0, 0))  # (C, v_blk), f32 accumulation

    @pl.when(r_idx == pl.num_programs(1) - 1)
    def _finish():
        dw_ref[:] = dw_acc[:].astype(dw_ref.dtype)
        db_ref[:] = db_acc[:].astype(db_ref.dtype)


def _pad_inputs(kernel: Array, bias: Array, v_blk: int):
    v = kernel.shape[-1]
    pad = -v % v_blk
    if pad:
        kernel = jnp.pad(kernel, ((0, 0), (0, pad)))
        bias = jnp.pad(bias, (0, pad), constant_values=PAD_BIAS)
    return kernel, bias


@functools.partial(
    jax.jit, static_argnames=("r_blk", "v_blk", "interpret")
)
def _fused_ce_fwd_impl(
    x: Array, w: Array, b: Array, labels: Array,
    r_blk: int, v_blk: int, interpret: bool,
) -> Tuple[Array, Array]:
    r, c = x.shape
    v = w.shape[1]
    grid = (r // r_blk, v // v_blk)
    labels_b = jnp.broadcast_to(
        labels.astype(jnp.int32)[:, None], (r, _LANES)
    )
    lane_spec = pl.BlockSpec((r_blk, _LANES), lambda ri, vi: (ri, 0))
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, v_blk=v_blk),
        grid=grid,
        in_specs=[
            lane_spec,  # labels
            pl.BlockSpec((r_blk, c), lambda ri, vi: (ri, 0)),     # x
            pl.BlockSpec((c, v_blk), lambda ri, vi: (0, vi)),     # w
            pl.BlockSpec((1, v_blk), lambda ri, vi: (0, vi)),     # bias
        ],
        out_specs=(lane_spec, lane_spec),
        out_shape=(
            jax.ShapeDtypeStruct((r, _LANES), jnp.float32),  # per-row loss
            jax.ShapeDtypeStruct((r, _LANES), jnp.float32),  # lse (residual)
        ),
        scratch_shapes=[
            pltpu.VMEM((r_blk, _LANES), jnp.float32),  # running max
            pltpu.VMEM((r_blk, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((r_blk, _LANES), jnp.float32),  # label logit
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(labels_b, x, w, b[None, :])
    return loss[:, 0], lse


@functools.partial(
    jax.jit, static_argnames=("r_blk", "v_blk", "interpret")
)
def _fused_ce_bwd_impl(
    x: Array, w: Array, b: Array, labels: Array, lse: Array, g: Array,
    r_blk: int, v_blk: int, interpret: bool,
):
    r, c = x.shape
    v = w.shape[1]
    labels_b = jnp.broadcast_to(
        labels.astype(jnp.int32)[:, None], (r, _LANES)
    )
    g_b = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (r, _LANES))

    lane_spec = pl.BlockSpec((r_blk, _LANES), lambda ri, vi: (ri, 0))
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, v_blk=v_blk),
        grid=(r // r_blk, v // v_blk),  # vocab sequential
        in_specs=[
            lane_spec,
            pl.BlockSpec((r_blk, c), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((c, v_blk), lambda ri, vi: (0, vi)),
            pl.BlockSpec((1, v_blk), lambda ri, vi: (0, vi)),
            lane_spec,
            lane_spec,
        ],
        out_specs=pl.BlockSpec((r_blk, c), lambda ri, vi: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((r_blk, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(labels_b, x, w, b[None, :], lse, g_b)

    # dw/db: rows sequential (same index maps, swapped grid positions)
    lane_spec2 = pl.BlockSpec((r_blk, _LANES), lambda vi, ri: (ri, 0))
    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, v_blk=v_blk),
        grid=(v // v_blk, r // r_blk),
        in_specs=[
            lane_spec2,
            pl.BlockSpec((r_blk, c), lambda vi, ri: (ri, 0)),
            pl.BlockSpec((c, v_blk), lambda vi, ri: (0, vi)),
            pl.BlockSpec((1, v_blk), lambda vi, ri: (0, vi)),
            lane_spec2,
            lane_spec2,
        ],
        out_specs=(
            pl.BlockSpec((c, v_blk), lambda vi, ri: (0, vi)),
            pl.BlockSpec((1, v_blk), lambda vi, ri: (0, vi)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((c, v), jnp.float32),
            jax.ShapeDtypeStruct((1, v), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((c, v_blk), jnp.float32),
            pltpu.VMEM((1, v_blk), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(labels_b, x, w, b[None, :], lse, g_b)
    return dx, dw, db[0]


def _row_block(r: int, requested: int, interpret: bool) -> int:
    """Row-block size for R rows: the requested block, shrunk (aligned) only
    when R itself is smaller. Rows are PADDED up to a block multiple by the
    caller — never the reverse (a smaller exact-divisor block): awkward row
    counts otherwise explode the sequential grid. Measured at seq-131072 MLM
    (R = 39328 = 32·1229, 1229 prime): the largest aligned divisor is 32,
    giving a 12,290-step grid and 16.6 ms of a 38 ms step; padding 96 dead
    rows keeps the 512-row block and a 770-step grid instead."""
    align = _TEST_ALIGNMENT or (1 if interpret else 8)  # f32 sublane tile
    requested = max(align, requested - requested % align)
    return min(requested, -(-r // align) * align)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_ce(x, w, b, labels, r_blk, v_blk, interpret):
    loss, _ = _fused_ce_fwd_impl(x, w, b, labels, r_blk, v_blk, interpret)
    return loss


def _fused_ce_fwd(x, w, b, labels, r_blk, v_blk, interpret):
    loss, lse = _fused_ce_fwd_impl(x, w, b, labels, r_blk, v_blk, interpret)
    return loss, (x, w, b, labels, lse)


def _fused_ce_bwd(r_blk, v_blk, interpret, res, g):
    x, w, b, labels, lse = res
    dx, dw, db = _fused_ce_bwd_impl(
        x, w, b, labels, lse, g, r_blk, v_blk, interpret
    )
    import numpy as np

    return (
        dx,
        dw.astype(w.dtype),
        db.astype(b.dtype),
        np.zeros(labels.shape, jax.dtypes.float0),
    )


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def pallas_linear_ce_integer(
    features: Array,
    kernel: Array,
    bias: Array,
    labels: Array,
    r_block_size: int = DEFAULT_R_BLOCK,
    v_block_size: int = DEFAULT_V_BLOCK,
    interpret: bool | None = None,
) -> Array:
    """Per-position CE of ``features @ kernel + bias`` vs integer ``labels``
    as one fused Pallas kernel — the (..., V) logits never reach HBM.

    features: (..., C); kernel: (C, V); bias: (V,); labels: (...) int.
    Returns f32 per-position losses shaped like ``labels``. Gradients flow to
    features/kernel/bias (flash-style recomputation; see module docstring).
    Off-TPU backends run in interpreter mode (slow — tests only).
    """
    if features.shape[:-1] != labels.shape:
        raise ValueError(
            f"features {features.shape} and labels {labels.shape} disagree"
        )
    if kernel.shape[0] != features.shape[-1] or kernel.shape[1] != bias.shape[0]:
        raise ValueError(
            f"kernel {kernel.shape} does not match features "
            f"{features.shape} / bias {bias.shape}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    lead = features.shape[:-1]
    c = features.shape[-1]
    x = features.reshape(-1, c)
    lab = labels.reshape(-1)
    r = x.shape[0]

    w, b = _pad_inputs(kernel, bias, v_block_size)
    v_blk = v_block_size  # _pad_inputs made V a (>= 1) multiple of it
    r_blk = _row_block(r, r_block_size, interpret)
    r_pad = -r % r_blk
    if r_pad:
        # dead rows: label 0, zero features. Their per-row losses are sliced
        # off below, so their loss cotangent is exactly zero — the recomputed
        # softmax grad ``(p - onehot)·g`` vanishes and dw/db stay exact; the
        # padded dx rows are discarded by the same slice.
        x = jnp.pad(x, ((0, r_pad), (0, 0)))
        lab = jnp.pad(lab, (0, r_pad))

    loss = _fused_ce(x, w, b.astype(jnp.float32), lab, r_blk, v_blk, interpret)
    if r_pad:
        loss = loss[:r]
    return loss.reshape(lead)
