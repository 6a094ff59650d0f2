"""Grouped matmul over the experts a chip holds (Pallas, TPU).

``out[r] = lhs[r] @ rhs[group of r]`` for rows sorted by group, where every
group starts at a multiple of the row tile: the dispatch (``ops/moe.py``) pads
each group up to whole tiles, so one tile belongs to one group and the kernel
is a plain tiled matmul whose weight block is chosen by a prefetched scalar.
``tile_group[t]`` names tile ``t``'s group; the value ``num_groups`` marks a
tile past the last group. Such a tile is not computed and not fetched (its
block indices stay where the last real tile left them; a block whose index
does not change is not copied again) and its output is zeros. The buffer holds
a bounded multiple of the expected load (``ops/moe.py`` ``capacity_tiles``:
four times that of a small share, twice that of a quarter; the worst routing
only where a step overflows it), so such tiles are half to three quarters of
it, not nearly all.

Backward: the gradient of ``lhs`` is the same product against the transposed
weights; the gradient of ``rhs`` (``grouped_matmul_transposed``) sums
``lhs[tile]^T @ grad[tile]`` over the tiles of each group, in float32, in the
output block itself, which stays in VMEM while consecutive tiles name the same
group. A group with no tile is never visited; the wrapper zeroes it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# ``name=`` of the two pallas_calls: what a device trace calls them, and a
# scope of their own in it (``.../grouped_matmul/pallas_call``)
KERNEL_GMM = "grouped_matmul"
KERNEL_TGMM = "grouped_matmul_transposed"

_LANES = 128
COLUMN_BLOCK = 512  # output columns per grid step of 2-byte operands, where the width divides


def _block(dim: int, target: int, interpret: bool, itemsize: int = 2) -> int:
    """Largest lane-aligned divisor of ``dim`` up to ``target`` (halved for
    4-byte operands: float32 blocks of the 2-byte size do not fit VMEM at
    2048 x 768). A dimension with no such divisor (1856 = 14.5 x 128) gets the
    lane-aligned target itself and a last block that is part outside the
    array: every blocked dimension here is one of the OUTPUT's (the contracted
    one is never blocked), what a block reads outside its operand only reaches
    what it would write outside the output, and that is not written. (One
    block of the whole 1856 is legal too and does not fit VMEM beside a
    2688-deep operand.)"""
    target = target * 2 // max(itemsize, 2)
    if interpret or dim <= target:
        return dim
    for cand in range(target - target % _LANES, 0, -_LANES):
        if dim % cand == 0:
            return cand
    return target - target % _LANES


def _gmm_kernel(group_ref, fetch_ref, lhs_ref, rhs_ref, out_ref, *, num_groups: int):
    del fetch_ref  # read by the index maps only
    active = group_ref[pl.program_id(1)] < num_groups

    @pl.when(active)
    def _compute():
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(active))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)


def _tgmm_kernel(group_ref, fetch_ref, lhs_ref, grad_ref, out_ref, *, num_groups: int):
    del fetch_ref
    t = pl.program_id(2)
    group = group_ref[t]
    first = jnp.logical_or(t == 0, group != group_ref[jnp.maximum(t - 1, 0)])
    active = group < num_groups

    @pl.when(jnp.logical_and(active, first))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active)
    def _accumulate():
        out_ref[0] += jax.lax.dot_general(
            lhs_ref[...], grad_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fetch_tiles(tile_group: Array, num_groups: int) -> Array:
    """The tile whose rows a grid step fetches: its own for a real tile, the
    last real tile's for the tiles behind it (real tiles come first)."""
    tiles = tile_group.shape[0]
    last = jnp.maximum(jnp.sum(tile_group < num_groups) - 1, 0)
    return jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), last.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def _gmm(lhs: Array, rhs: Array, tile_group: Array, tile_rows: int, interpret: bool) -> Array:
    rows, k = lhs.shape
    num_groups, _, n = rhs.shape
    tiles = rows // tile_rows
    tn = _block(n, COLUMN_BLOCK, interpret, lhs.dtype.itemsize)
    kernel = pl.pallas_call(
        functools.partial(_gmm_kernel, num_groups=num_groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # tiles innermost: a column block of one group's weights stays
            # put over that group's tiles, and over all the tiles past the last
            # group nothing is fetched at all
            grid=(pl.cdiv(n, tn), tiles),
            in_specs=[
                pl.BlockSpec((tile_rows, k), lambda j, t, grp, fetch: (fetch[t], 0)),
                pl.BlockSpec((1, k, tn), lambda j, t, grp, fetch: (
                    jnp.minimum(grp[t], num_groups - 1), 0, j)),
            ],
            out_specs=pl.BlockSpec((tile_rows, tn), lambda j, t, grp, fetch: (t, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_GMM,
    )
    return kernel(tile_group, _fetch_tiles(tile_group, num_groups), lhs, rhs)


@functools.partial(jax.jit, static_argnames=("num_groups", "tile_rows", "interpret"))
def _tgmm(lhs: Array, grad: Array, tile_group: Array, num_groups: int,
          tile_rows: int, interpret: bool) -> Array:
    rows, k = lhs.shape
    n = grad.shape[1]
    tiles = rows // tile_rows
    tk = _block(k, COLUMN_BLOCK, interpret, lhs.dtype.itemsize)
    tn = _block(n, 2 * COLUMN_BLOCK, interpret, lhs.dtype.itemsize)
    kernel = pl.pallas_call(
        functools.partial(_tgmm_kernel, num_groups=num_groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), tiles),
            in_specs=[
                pl.BlockSpec((tile_rows, tk), lambda i, j, t, grp, fetch: (fetch[t], i)),
                pl.BlockSpec((tile_rows, tn), lambda i, j, t, grp, fetch: (fetch[t], j)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn), lambda i, j, t, grp, fetch: (
                jnp.minimum(grp[t], num_groups - 1), i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_TGMM,
    )
    out = kernel(tile_group, _fetch_tiles(tile_group, num_groups), lhs, grad)
    visited = jnp.zeros((num_groups + 1,), bool).at[tile_group].set(True)[:num_groups]
    return jnp.where(visited[:, None, None], out, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(lhs, rhs, tile_group, tile_rows, interpret):
    return _gmm(lhs, rhs, tile_group, tile_rows, interpret)


def _grouped_fwd(lhs, rhs, tile_group, tile_rows, interpret):
    return _gmm(lhs, rhs, tile_group, tile_rows, interpret), (lhs, rhs, tile_group)


def _grouped_bwd(tile_rows, interpret, residuals, g):
    lhs, rhs, tile_group = residuals
    d_lhs = _gmm(g, jnp.swapaxes(rhs, 1, 2), tile_group, tile_rows, interpret)
    d_rhs = _tgmm(lhs, g, tile_group, rhs.shape[0], tile_rows, interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), None


_grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs: Array, rhs: Array, tile_group: Array, tile_rows: int,
                   interpret: Optional[bool] = None) -> Array:
    """``lhs`` (tiles * tile_rows, K) against ``rhs`` (groups, K, N) by
    ``tile_group`` (tiles,) int32 in [0, groups]: (tiles * tile_rows, N), zeros
    in the tiles of group ``groups``. Differentiable in ``lhs`` and ``rhs``.
    Off a TPU the kernel runs in interpret mode (slow: for tests)."""
    if lhs.shape[0] != tile_group.shape[0] * tile_rows or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(f"grouped_matmul shapes {lhs.shape=} {rhs.shape=} "
                         f"{tile_group.shape=} {tile_rows=}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _grouped_matmul(lhs, rhs, tile_group.astype(jnp.int32), tile_rows, interpret)


def grouped_matmul_xla(lhs: Array, rhs: Array, tile_group: Array, tile_rows: int) -> Array:
    """The same product in plain XLA: every group's weights against every
    row, masked (groups x the work): what the kernel is tested against, and
    what a backend without Mosaic runs."""
    num_groups = rhs.shape[0]
    row_group = jnp.repeat(tile_group, tile_rows)
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(num_groups):
        y = jnp.dot(lhs, rhs[g], preferred_element_type=jnp.float32)
        out = out + jnp.where((row_group == g)[:, None], y, 0.0)
    return out.astype(lhs.dtype)
