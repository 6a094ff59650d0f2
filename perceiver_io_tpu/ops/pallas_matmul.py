"""Fused dequant-matmul Pallas kernel (TPU) for weight-only quantized serving.

The serving roofline (PERF.md, `tools/hbm_roofline.py`) is bound by the HBM
weight stream, and r20's continuous batching made the decode weight stream
essentially the whole bill. r8's weight-only int8 (`quant/int8.py`) leans on
XLA to fuse ``convert × scale`` into the consuming matmul's operand read —
which works, but leaves the fusion decision to XLA and cannot express the
grouped-int4 layout at all. This kernel closes the loop: the int8/int4
weight tiles themselves are what streams from HBM, the ``convert × scale``
runs in VMEM per tile, and the matmul accumulates in f32 scratch across the
K grid — the same streamed-operand + sequential-reduction shape as the
flash-attention/flash-CE kernels in this repo.

Design:

- grid ``(M/bm, N/bn, K/bk)`` with the contraction axis INNERMOST
  (sequential): the f32 accumulator lives in VMEM scratch across K blocks,
  zeroed at ``k==0`` and flushed to the output dtype at ``k==n_k-1``.
- weight tile dequant: ``q_tile.astype(f32) * scale_row``. Per-channel
  scales ride as a ``(1, N)`` array blocked ``(1, bn)`` (same block for
  every K step). Grouped scales ``(K/gs, N)`` ride with the WHOLE group
  axis (padded to a sublane multiple) in one ``(G, bn)`` block — a
  ``(1, bn)`` block over a ``(K/gs, N)`` array is refused by the TPU
  lowering (second-minor block dims must be a multiple of 8 or the full
  axis). The K-block size is pinned to ``group_size``, so grid step ``k``
  loads row ``k`` of that block with a dynamic sublane slice and the
  multiply is a plain broadcast over the ``bk`` rows.
- f32 activations keep ``Precision.HIGHEST`` (multi-pass MXU — same policy
  as ``pallas_attention._dot`` and the XLA f32 parity path); bf16
  activations take the fast single pass with f32 accumulation via
  ``preferred_element_type``.
- M/N/K are padded to the resolved blocks with zeros (zero K rows
  contribute nothing; padded N columns are sliced off), so arbitrary
  serving shapes — batch-1 decode rows included — hit one code path.

VMEM budget (budget math, not a block sweep — the defaults compiled and
matched the XLA path on a v5e chip at the shapes ``chip_smoke.py`` runs,
PERF.md §State on the chip; no other tier has been tried there): per
grid step the kernel holds x ``bm·bk·xB``, the weight tile ``bk·bn`` int
bytes plus its ``bk·bn·4`` f32 dequant temp, the ``bm·bn·4`` accumulator,
and the ``bm·bn`` output tile, ×2 on the streamed refs for the pipeline's
double buffering. The defaults (bm 128, bn 512, bk 512) total ~2.3 MB f32
— an order of magnitude inside the measured ~16 MB scoped-VMEM boundary
(r3), and ``_auto_blocks`` halves bn/bk if a custom request would cross
``QMM_VMEM_BUDGET`` (half the boundary, same guard philosophy as
``_auto_kv_block``).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_io_tpu import obs

with obs.span("import", module="jax.experimental.pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

from flax.linen import dtypes as _flax_dtypes

from perceiver_io_tpu.quant.int8 import QKernel

Array = jax.Array

_LANES = 128
_SUBLANES = 8
# int8 min tile is (32, 128) on TPU; blocks must keep the second-minor dim a
# multiple of 32 when compiled (interpret mode has no tiling constraint)
_INT_SUBLANES = 32

DEFAULT_M_BLOCK = 128
DEFAULT_N_BLOCK = 512
DEFAULT_K_BLOCK = 512
# Half the measured ~16 MB scoped-VMEM boundary (PERF.md r3): headroom for
# Mosaic's own scratch and the double-buffered pipeline. Conservative until
# the real-TPU block sweep lands (§r10 pending) — NOT a measured tier.
QMM_VMEM_BUDGET = 8 * 1024 * 1024

_VALID_IMPLS = ("pallas", "xla")


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _tile_vmem_bytes(bm: int, bk: int, bn: int, x_itemsize: int,
                     out_itemsize: int) -> int:
    """Budget-math VMEM residency of one grid step (documented above).
    Weight tiles count at 1 B/element even for int4 — whether Mosaic keeps
    s4 packed in VMEM is unmeasured, so the guard assumes it does not."""
    x_b = bm * bk * x_itemsize
    q_b = bk * bn  # int bytes (int4 counted unpacked — conservative)
    w_b = bk * bn * 4  # f32 dequant temp
    acc_b = bm * bn * 4
    out_b = bm * bn * out_itemsize
    return 2 * (x_b + q_b) + w_b + acc_b + out_b


def _auto_blocks(m: int, k: int, n: int, x_itemsize: int, out_itemsize: int,
                 group_size: Optional[int]) -> Tuple[int, int, int]:
    """Resolve (bm, bk, bn). Grouped scales pin bk to ``group_size`` (one
    scale row per grid step); otherwise blocks start at the defaults,
    shrink to the (padded) dims when those are smaller, and halve bn then
    bk until the budget math clears ``QMM_VMEM_BUDGET``. Every choice here
    is conservative-until-measured (module docstring) — re-tier only with
    real-TPU sweep rows in PERF.md."""
    bm = min(DEFAULT_M_BLOCK, _ceil_to(m, _SUBLANES))
    bn = min(DEFAULT_N_BLOCK, _ceil_to(n, _LANES))
    if group_size is not None:
        bk = group_size
    else:
        bk = min(DEFAULT_K_BLOCK, _ceil_to(k, _LANES))
    while (_tile_vmem_bytes(bm, bk, bn, x_itemsize, out_itemsize)
           > QMM_VMEM_BUDGET and bn > _LANES):
        bn //= 2
    while (group_size is None
           and _tile_vmem_bytes(bm, bk, bn, x_itemsize, out_itemsize)
           > QMM_VMEM_BUDGET and bk > _LANES):
        bk //= 2
    return bm, bk, bn


def _dequant_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, grouped):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # convert × scale in VMEM: the only HBM-side weight traffic is q's int
    # bytes (+ the skinny scales). Per-channel s_ref is (1, bn), the same
    # row every K step; grouped s_ref holds every group's row and this K
    # block (= group k_idx) loads its own. Either way the multiply
    # broadcasts one (1, bn) row over the bk rows.
    scale = s_ref[pl.ds(k_idx, 1), :] if grouped else s_ref[...]
    w = q_ref[...].astype(jnp.float32) * scale
    x = x_ref[...]
    if x.dtype == jnp.float32:
        # f32 parity path: multi-pass MXU, same policy as the attention
        # kernel's _dot — a single bf16 pass would cost ~3 decimal digits
        # and break the 2e-5 golden bound
        acc_ref[...] += jax.lax.dot_general(
            x, w, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    else:
        acc_ref[...] += jax.lax.dot_general(
            x, w.astype(x.dtype), dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "group_size", "block_m", "block_n",
                     "block_k", "interpret"),
)
def dequant_matmul(
    x: Array,
    q: Array,
    scale: Array,
    out_dtype=None,
    group_size: Optional[int] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> Array:
    """``x (M, K) @ dequant(q (K, N), scale)`` with in-VMEM dequantization.

    ``scale`` is ``(N,)`` per-channel or ``(K/group_size, N)`` grouped (pass
    ``group_size`` for the latter — it must divide K; `quant.quantize_array`
    guarantees that by falling back to per-channel when it would not).
    Explicit ``block_*`` are still budget-guarded by ``_auto_blocks``'s
    shrink loop semantics only when auto-resolved; callers overriding blocks
    own the VMEM math (kernel_smoke pins the boundary geometries).
    """
    m, k = x.shape
    k2, n = q.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: x {x.shape} vs q {q.shape}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if group_size is not None:
        if k % group_size:
            raise ValueError(
                f"group_size {group_size} does not divide K={k}")
        if scale.shape != (k // group_size, n):
            raise ValueError(
                f"grouped scale shape {scale.shape} != {(k // group_size, n)}")
        s2d = scale
    else:
        if scale.shape != (n,):
            raise ValueError(f"per-channel scale shape {scale.shape} != ({n},)")
        s2d = scale.reshape(1, n)

    bm, bk, bn = _auto_blocks(m, k, n, x.dtype.itemsize, out_dtype.itemsize,
                              group_size)
    if block_m is not None:
        bm = block_m
    if block_n is not None:
        bn = block_n
    if block_k is not None and group_size is None:
        bk = block_k

    mp, np_, kp = _ceil_to(m, bm), _ceil_to(n, bn), _ceil_to(k, bk)
    if mp != m or kp != k:
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if kp != k or np_ != n:
        q = jnp.pad(q, ((0, kp - k), (0, np_ - n)))
    # grouped: the whole group axis in one block, padded to a sublane
    # multiple (rows past K/gs are never indexed: the K grid has K/gs steps)
    s_rows = _ceil_to(s2d.shape[0], _SUBLANES) if group_size else 1
    if np_ != n or s_rows != s2d.shape[0]:
        # padded columns are sliced off below; 1.0 keeps the scales benign
        s2d = jnp.pad(s2d, ((0, s_rows - s2d.shape[0]), (0, np_ - n)),
                      constant_values=1.0)

    out = pl.pallas_call(
        functools.partial(_dequant_matmul_kernel,
                          grouped=group_size is not None),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((s_rows, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # M/N tiles are independent; only K carries the accumulator
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, q, s2d)
    if mp != m or np_ != n:
        out = out[:m, :n]
    return out


def _resolve_impl(impl: Optional[str]) -> str:
    impl = impl or os.environ.get("PIT_QMM_IMPL") or (
        "pallas" if jax.default_backend() == "tpu" else "xla")
    if impl not in _VALID_IMPLS:
        # a typo'd impl must not silently fall through to the XLA branch and
        # get benchmarked under the wrong label (same rule as attn_impl)
        raise ValueError(
            f"unknown quantized-matmul impl {impl!r}; expected one of "
            f"{_VALID_IMPLS} (PIT_QMM_IMPL overrides)")
    return impl


def _blocks_compile_safe(bm: int, bk: int, bn: int) -> bool:
    """Mosaic tiling legality for COMPILED kernels: int8/int4 weight tiles
    need second-minor multiples of 32 and lane multiples of 128. Interpret
    mode (CPU tests) has no such constraint and skips this gate."""
    return bm % _SUBLANES == 0 and bk % _INT_SUBLANES == 0 and bn % _LANES == 0


def quantized_matmul(x: Array, w: QKernel, impl: Optional[str] = None) -> Array:
    """``x (..., K) @ w`` for a :class:`QKernel` weight, in its compute dtype.

    Dispatch: ``impl`` arg > ``PIT_QMM_IMPL`` env (read at trace time, like
    ``PIT_DRYRUN_ATTN``) > backend default (pallas on TPU, xla elsewhere —
    off-TPU the kernel only runs in interpreter mode, orders of magnitude
    slower; explicit ``impl='pallas'`` runs it there for tests). On TPU the
    backend default gives way to the XLA dequant path for a geometry the
    tiling gate cannot prove legal; a kernel asked for BY NAME (argument or
    env) that the gate rejects raises — a result labelled 'pallas' is never
    the XLA path's.
    """
    by_name = impl or os.environ.get("PIT_QMM_IMPL")
    impl = _resolve_impl(impl)
    compute = jnp.dtype(w.compute_dtype)
    k, n = w.q.shape
    gs = w.group_size
    if impl == "pallas":
        lead = x.shape[:-1]
        x2 = x.reshape(-1, k).astype(compute)
        m = x2.shape[0]
        interpret = jax.default_backend() != "tpu"
        bm, bk, bn = _auto_blocks(m, k, n, compute.itemsize, compute.itemsize,
                                  gs)
        if interpret or _blocks_compile_safe(bm, bk, bn):
            out = dequant_matmul(
                x2, w.q, w.scale, out_dtype=compute, group_size=gs,
                interpret=interpret,
            )
            return out.reshape(*lead, n)
        if by_name:
            raise ValueError(
                f"quantized-matmul impl 'pallas' was asked for by name but "
                f"blocks (bm={bm}, bk={bk}, bn={bn}) for x{tuple(x.shape)} @ "
                f"q{(k, n)} group_size={gs} are not a legal compiled tiling "
                f"(int weight tiles need bk % {_INT_SUBLANES} == 0)")
    # XLA path: dequantize feeds the matmul operand read (r8 fusion)
    return (x.astype(compute) @ w.dequantize()).astype(compute)


def linear_apply(x: Array, w, b, dtype) -> Array:
    """The ``_LinearParams`` apply: ``x @ w + b`` under flax dtype promotion
    — except a :class:`QKernel` weight routes to :func:`quantized_matmul`,
    which is the whole point of carrying quantized kernels through the tree
    as structured leaves rather than pre-dequantized tensors."""
    if isinstance(w, QKernel):
        y = quantized_matmul(x, w)
        if b is not None:
            y = y + jnp.asarray(b, y.dtype)
        return y
    if b is None:
        x, w = _flax_dtypes.promote_dtype(x, w, dtype=dtype)
        return x @ w
    x, w, b = _flax_dtypes.promote_dtype(x, w, b, dtype=dtype)
    return x @ w + b
