"""Fused latent-attention Pallas kernel (TPU).

Covers the Perceiver hot path — attention of a small resident query block
(latents or output queries) against a KV stream — as one fused kernel:
QK^T, masking, online softmax, and PV accumulation never round-trip to HBM,
and the KV sequence is streamed block-by-block so the input length M is never
fully resident in VMEM (the blockwise cross-attention called for in
SURVEY.md §5). This replaces the reference's ``torch.nn.MultiheadAttention``
CUDA kernels (reference ``perceiver/model.py:66-74``).

Design:

- grid ``(B, H, T/T_blk, S/S_blk)`` over the QUERY heads (keys and values may
  have fewer: a query head's block index maps name its group's key/value
  head, ``fused_attention``); the KV axis is the innermost (sequential)
  grid dimension, so the running max / denominator / PV accumulator live in
  VMEM scratch across KV blocks (the standard TPU flash-attention recurrence).
  The query axis is blocked too, so large query counts (e.g. the flow
  decoder's dense 2D queries) stay inside the ~16MB VMEM scoped limit.
- logits and the accumulator are f32 regardless of input dtype; the P·V
  matmul feeds the MXU in the input dtype with f32 accumulation.
- padding (``pad_mask`` True = masked out) enters as a finite additive bias,
  reproducing the XLA path's semantics including the fully-masked-row case
  (uniform probabilities) without NaNs.
- backward: fused flash backward — two Pallas kernels (dq; dk/dv) recompute
  the probabilities blockwise as exp(logits − m)/l from the saved softmax
  max ``m`` and denominator ``l``, so the (T, S) logits never materialize in
  HBM in either direction. The kernels write and read ``m``/``l``
  lane-broadcast as (B, H, T, 128) f32 (the layout jax's own TPU
  flash-attention kernel uses — sublane↔lane moves are not free on Mosaic),
  but the ``custom_vjp`` saves one float a row, (B, H, T), and broadcasts it
  back for the backward kernels: 128 times less to hold between the two
  passes. (Not (B, H, T, 1): the TPU's tiled layout pads a minor dimension
  of 1 to the 128 lanes again, and the slice becomes a bitcast that saves
  nothing; seen in a compile for a described v5e.) They are kept separate
  rather than folded into a logsumexp, which would absorb log l on fully
  padded rows (m = -1e30 in f32); ``delta = Σ_d g·out`` is computed in XLA
  and passed in the lane-broadcast layout too.
  The saved output and the two statistics carry ``checkpoint_name``s
  (``REMAT_FUSED_OUT``, ``REMAT_FUSED_STATS``): a ``jax.checkpoint`` whose
  policy keeps those names recomputes its function without this forward
  kernel (``models/decoder_lm.py``); without such a policy the names do
  nothing. On a fully padded row the probabilities recompute as
  uniform 1/l (the -1e30 bias absorbs the logits in f32 rounding), ``dv``
  keeps the uniform contribution, and ``ds`` is zeroed so dq/dk match the
  XLA path's where-style masking (zero grads through the mask).

Contract (enforced by the dispatcher in ``ops.attention``): no attention-prob
dropout, optional key padding mask only.
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops import masking

Array = jax.Array

# Finite stand-in for -inf: exp() underflows to exactly 0 against any live
# logit, while a fully-masked row still softmaxes to uniform (XLA-path parity).
MASK_VALUE = -1e30
# Bias for keys the kernel itself padded in: strictly below MASK_VALUE so that
# even a fully-masked row's uniform softmax excludes them (exp(PAD - MASK) = 0).
PAD_BIAS = 2.0 * MASK_VALUE

_LANES = 128
# ``name=`` of the three pallas_calls: what a device trace calls them, and a
# scope of their own in it (``.../fused_attention_fwd/pallas_call``)
KERNEL_FWD = "fused_attention_fwd"
KERNEL_DQ = "fused_attention_dq"
KERNEL_DKV = "fused_attention_dkv"
# ``checkpoint_name``s of what ``_fused_attention`` saves for its backward: the
# output, and the two row statistics (one name for both)
REMAT_FUSED_OUT = "fused_attention_out"
REMAT_FUSED_STATS = "fused_attention_stats"
DEFAULT_KV_BLOCK = 512
DEFAULT_Q_BLOCK = 512
# Test hook (tests/test_pallas_attention.py fuzz): force the COMPILED lane
# alignment while running the kernel in interpret mode, so CPU property
# tests drive the exact divisor/padding/full-residency resolution branches
# hardware takes (interpret alone resolves with alignment=1, which skips
# them all — the two resolution bugs on record, the 131k row-divisor
# pathology and the awkward-S guard ordering, were only ever reachable at
# lane alignment). None = derive from ``interpret`` as usual.
_TEST_ALIGNMENT: Optional[int] = None
# Larger query blocks measure +3.7-5.1% at streamed-KV shapes (flow
# encoder-cross sweep, PERF.md r3), but VMEM safety depends on the RESOLVED
# block triple, not the raw shape: the sweep's compile boundary at d=512 is
# (t_blk 1024, s_blk 256) OK vs (t_blk 1024, s_blk 512) an 18 MB > 16 MB
# scoped-VMEM OOM in the dkv backward. The auto bump (``q_block_size=None``,
# applied inside ``_prepare_blocks`` AFTER s_blk resolution) therefore
# requires ALL of: the resolved s_blk·d product within the measured-safe
# 256×512 bound, d within the sweep's measured range (≤ 512 — a deeper head
# would grow the 1024-row query block + f32 accumulator past anything
# measured even when s_blk·d stays small), and T dividing the big block
# exactly (no query padding and no widening of the full-residency
# ``t <= 2·q_block`` fallback — shapes the sweep never measured).
LONG_KV_Q_BLOCK = 1024
LONG_KV_SAFE_SBLK_D = 256 * 512
LONG_KV_MAX_D = 512
# The q bump additionally keeps the per-block probs area t_blk·s_blk inside
# the measured compile region: 1024·1024 and 512·2048 elements compile,
# 1024·2048 is a scoped-VMEM compile OOM (long-context kv sweep).
LONG_KV_SAFE_PROBS = 1024 * 1024

# Auto KV-block sizing (``kv_block_size=None``): streaming more keys per
# sequential grid step amortizes per-step kernel overhead, and how much VMEM
# that costs scales with d. Measured (PERF.md r3 kv sweep, fwd+bwd): d=16
# S=131k kv 512→2048 is 3.47→2.45 ms (and 2048 + q capped at 512 beats
# 512 + q 1024 everywhere tried); d=64 S=2048 (flow-self) 1.34→0.98 ms;
# d=128 was 1024 through r4 (S=50k in-8h 8.55→6.44, with 2048 measuring "no
# better" that session) — re-swept in r5 at the TPU-width long-context
# shapes, where the sequential grid is longer and b·h parallelism smaller:
# kv2048 wins 9-12% at (1,256,131k,4,128)/(8,256,8k,4,128) AND re-measures
# ahead at in-8h itself (7.44-7.65 vs 7.81-7.85 ms, interleaved ×2), so the
# d≤128 tier is now 2048. kv4096 measured a further ~3% at t=256 shapes but
# is a REAL scoped-VMEM compile OOM at in-8h's t=512 (probs area 512·4096 = 2M >
# the 1M boundary — the guard below must shrink it, so the tier stays 2048);
# d=512 kv ≥ 1024 is the flow sweep's measured scoped-VMEM OOM, so deep
# heads stay at 512. The measured KV-side footprint envelope is now
# s_blk·d ≤ 2048·128 = 262144 (compile-checked at the r5 sweep shapes and
# by tools/kernel_smoke.py per round); S shorter than the block resolves to
# full-dim/divisor blocks exactly as an explicit request would.


def _auto_kv_block(
    s: int, d: int, t: int, alignment: int, q_block_size: Optional[int]
) -> int:
    if d <= 128:
        kv = 2048
    else:
        return DEFAULT_KV_BLOCK
    # The widened KV block must keep the resolved (t_blk, s_blk) probs area
    # inside the measured compile boundary for EVERY way t_blk can resolve:
    # an explicit q_block_size (mirroring _prepare_blocks's resolution), and
    # the full-residency fallback (t_blk = t when T has no aligned divisor
    # but fits two blocks). The auto q-bump branch carries its own guard.
    qb = DEFAULT_Q_BLOCK if q_block_size is None else q_block_size
    tb = _kv_block_size(t, qb, alignment)
    if tb == 0:
        t_bound = t if t <= 2 * qb else max(qb - qb % alignment, alignment)
    else:
        t_bound = tb
    while kv > DEFAULT_KV_BLOCK and t_bound * kv > LONG_KV_SAFE_PROBS:
        kv //= 2
    if (kv > DEFAULT_KV_BLOCK
            and _kv_block_size(s, kv, alignment) == 0
            and 4 * DEFAULT_KV_BLOCK < s <= 4 * kv):
        # Checked against the POST-shrink kv (the probs loop above can halve
        # it, changing which divisors exist): S with no block-aligned divisor
        # that sits inside the widened block's full-residency fallback window
        # (s <= 4·kv ⇒ s_blk = s, unmeasured probs/VMEM territory) but
        # outside the default's keeps the tuned 512 path; larger awkward S
        # takes the pad-to-block path and keeps the widened block.
        return DEFAULT_KV_BLOCK
    return kv


def _dot(a, b, contract):
    """MXU matmul contracting ``contract`` = (a_dim, b_dim), f32 accumulation.

    The MXU multiplies in bf16; for f32 operands a single pass loses ~3
    decimal digits vs XLA's einsum (which defaults to multi-pass for f32), so
    request HIGHEST precision there. bf16 operands keep the fast single pass —
    the production bf16 training path pays nothing for this.
    """
    precision = (jax.lax.Precision.HIGHEST
                 if a.dtype == jnp.float32 and b.dtype == jnp.float32 else None)
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )


def _kv_block_size(s: int, requested: int, alignment: int) -> int:
    """KV length to stream per grid step: a divisor of S, aligned to the TPU
    tile constraint (Mosaic requires block dims to be lane/sublane multiples
    or equal to the full array dim). Returns 0 when S must be padded instead."""
    requested = max(requested - requested % alignment, alignment)
    if s <= requested:
        return s  # single block — full-dim blocks are always legal
    best = 0
    for cand in range(requested, alignment - 1, -alignment):
        if s % cand == 0:
            best = cand
            break
    # a tiny block (many grid steps) is worse than padding to a full block
    return best if best * 2 >= requested else 0


def _causal_bias(t_blk: int, s_blk: int, t_idx, s_idx, offset: int):
    """(T_blk, S_blk) additive causal bias for the current grid tile: query
    row i (GLOBAL row ``t_idx*t_blk + i``, absolute position row + offset)
    may attend key ``j <= row + offset`` — the in-kernel twin of
    ``ops.masking.causal_mask``. Additive MASK_VALUE (not a where) so a
    fully-masked row keeps the uniform-softmax semantics of the pad path."""
    rows = t_idx * t_blk + jax.lax.broadcasted_iota(
        jnp.int32, (t_blk, s_blk), 0)
    cols = s_idx * s_blk + jax.lax.broadcasted_iota(
        jnp.int32, (t_blk, s_blk), 1)
    return jnp.where(cols > rows + offset, MASK_VALUE, 0.0)


def _tile_visible(t_idx, s_idx, t_blk: int, s_blk: int, offset: int):
    """False where the causal rule masks the whole (T_blk, S_blk) tile: its
    first key lies past the last query row's ``row + offset``."""
    return s_idx * s_blk <= t_idx * t_blk + t_blk - 1 + offset


def _last_visible_kv(t_idx, t_blk: int, s_blk: int, offset: int):
    """Index of the last KV block that query block ``t_idx`` can see."""
    return (t_idx * t_blk + t_blk - 1 + offset) // s_blk


def _kv_block_index(t_idx, s_idx, *, t_blk: int, s_blk: int, offset: Optional[int],
                    skip_masked: bool):
    """The KV block a grid step fetches: its own, or with ``skip_masked`` the
    last one its query block can see (an unchanged index is not copied)."""
    if not skip_masked:
        return s_idx
    return jnp.minimum(s_idx, _last_visible_kv(t_idx, t_blk, s_blk, offset))


def _kv_head(h_idx, group: int):
    """The key/value head that query head ``h_idx`` reads: ``group`` query
    heads share one (1: every head its own, and the index is left as it is)."""
    return h_idx if group == 1 else h_idx // group


def _first_visible_q(s_idx, t_blk: int, s_blk: int, offset: int):
    """Index of the first query block that can see KV block ``s_idx``."""
    return jnp.maximum(s_idx * s_blk - offset, 0) // t_blk


# -- the block-diffusion mask's tiles ----------------------------------------
#
# Under ``ops.masking.block_diffusion_visible`` what a tile of queries reads is
# TWO ranges of keys, and what a tile of keys is read by two ranges of
# queries: the noised part, then the clean part, in ascending order. A grid
# axis walks the blocks of the first range and then those of the second, and
# holds its last block where its tile has fewer than the axis's length (an
# unchanged block index is not copied again, and the step computes nothing).
# The functions take ``xp``: ``jnp`` on the grid's scalar indices, ``np`` on
# every index at once for the axis's static length.
#
# What a visited tile pays for the mask follows its class, decided once a
# grid step from the step's scalar indices (``_bd_tile_class``): a WHOLE tile,
# every pair of it visible (most of the clean prefix a query tile reads), adds
# no mask; a tile inside one quadrant of ``[x_t ; x]`` x ``[x_t ; x]`` that
# the mask cuts (CUT_NN noised queries against noised keys, CUT_NC noised
# against clean, CUT_CC clean against clean) adds one compare of shifted row
# and column indices; a MIXED tile, across the two halves or over padding,
# adds ``masking.block_diffusion_visible`` as a whole.
_NONE = 2**30  # the low end of an empty range
WHOLE, CUT_NN, CUT_NC, CUT_CC, MIXED = range(5)


class BlockDiffusion(NamedTuple):
    """The mask of a row ``[x_t ; x]`` of two halves of ``half`` positions,
    in blocks of ``block`` (a power of two)."""
    half: int
    block: int


def _bd_keys_read(t_idx, t_blk: int, bd: BlockDiffusion, xp):
    """The keys query tile ``t_idx`` reads: ``(noised lo, hi, clean lo,
    hi)``, positions of ``[x_t ; x]``. Query rows past the row (padding) read
    as clean rows of the last block."""
    half, blk = bd
    first, last = t_idx * t_blk, t_idx * t_blk + t_blk - 1
    noised, clean = first < half, last >= half
    last_noised = xp.minimum(last, half - 1) // blk * blk  # the first key of its block
    last_clean = xp.minimum(xp.clip(last - half, 0, half - 1) // blk * blk + blk, half)
    clean_hi = xp.maximum(xp.where(noised, last_noised, 0), xp.where(clean, last_clean, 0))
    return (xp.where(noised, first // blk * blk, 0),
            xp.where(noised, xp.minimum(last_noised + blk, half), 0), half, half + clean_hi)


def _bd_tile_class(t_idx, s_idx, t_blk: int, s_blk: int, bd: BlockDiffusion, xp):
    """The class of the tile of query tile ``t_idx`` against key tile
    ``s_idx`` (global indices): WHOLE, CUT_NN, CUT_NC, CUT_CC or MIXED. A
    tile of clean queries against noised keys, which no grid visits, is
    MIXED."""
    half, blk = bd
    shift = blk.bit_length() - 1
    r0, r1 = t_idx * t_blk, t_idx * t_blk + t_blk - 1
    c0, c1 = s_idx * s_blk, s_idx * s_blk + s_blk - 1
    mixed = (((r0 < half) & (r1 >= half)) | ((c0 < half) & (c1 >= half))
             | (r1 >= 2 * half) | (c1 >= 2 * half))
    # whole where its least visible pair is: the last key against the first query
    one_block = (((r0 >> shift) == (r1 >> shift)) & ((c0 >> shift) == (c1 >> shift))
                 & ((r0 >> shift) == (c0 >> shift)))
    nn = xp.where(one_block, WHOLE, CUT_NN)
    nc = xp.where(((c1 - half) >> shift) < (r0 >> shift), WHOLE, CUT_NC)
    cc = xp.where(((c1 - half) >> shift) <= ((r0 - half) >> shift), WHOLE, CUT_CC)
    clean_keys = c0 >= half
    kind = xp.where(r0 >= half, xp.where(clean_keys, cc, MIXED), xp.where(clean_keys, nc, nn))
    return xp.where(mixed, MIXED, kind)


def _bd_queries_reading(s_idx, s_blk: int, rows: int, bd: BlockDiffusion, xp):
    """The queries that read key tile ``s_idx``: ``(noised lo, hi, clean lo,
    hi)``, positions of ``[x_t ; x]`` (``rows``: the query axis, padding
    included). Key columns past the row (padding) are read by none."""
    half, blk = bd
    first, last = s_idx * s_blk, s_idx * s_blk + s_blk - 1
    noised, clean = first < half, (last >= half) & (first < 2 * half)
    # noised keys: the noised queries of their blocks
    own_lo = first // blk * blk
    own_hi = xp.minimum(xp.minimum(last, half - 1) // blk * blk + blk, half)
    # clean keys: the noised queries of later blocks, the clean ones of theirs and later
    first_clean = xp.maximum(first - half, 0) // blk * blk
    later = clean & (first_clean + blk < half)
    lo = xp.minimum(xp.where(noised, own_lo, _NONE), xp.where(later, first_clean + blk, _NONE))
    hi = xp.maximum(xp.where(noised, own_hi, 0), xp.where(later, half, 0))
    return (xp.where(lo < _NONE, lo, 0), hi, xp.where(clean, half + first_clean, 0),
            xp.where(clean, rows, 0))


def _bd_blocks(ranges, size: int, xp):
    """Two ascending position ranges -> ``(first, count)`` of blocks of
    ``size`` each, the second range's blocks starting past the first's."""
    lo_a, hi_a, lo_b, hi_b = ranges
    count_a = xp.where(hi_a > lo_a, (hi_a - 1) // size - lo_a // size + 1, 0)
    first_b = xp.where(count_a > 0, xp.maximum(lo_b // size, lo_a // size + count_a), lo_b // size)
    count_b = xp.where(hi_b > lo_b, xp.maximum((hi_b - 1) // size - first_b + 1, 0), 0)
    return lo_a // size, count_a, first_b, count_b


def _bd_step(blocks, step, xp=jnp):
    """``(block index, visible)`` of grid step ``step`` along the two ranges."""
    first_a, count_a, first_b, count_b = blocks
    last = xp.maximum(xp.where(count_b > 0, first_b + count_b, first_a + count_a) - 1, 0)
    index = xp.where(step < count_a, first_a + step, first_b + step - count_a)
    visible = step < count_a + count_b
    return xp.where(visible, index, last), visible


def _bd_kv_step(t_idx, step, t_blk: int, s_blk: int, bd: BlockDiffusion):
    return _bd_step(_bd_blocks(_bd_keys_read(t_idx, t_blk, bd, jnp), s_blk, jnp), step)


def _bd_q_step(s_idx, step, t_blk: int, s_blk: int, rows: int, bd: BlockDiffusion):
    return _bd_step(_bd_blocks(_bd_queries_reading(s_idx, s_blk, rows, bd, jnp), t_blk, jnp), step)


def _bd_steps(tiles: int, ranges_of, size: int) -> int:
    """A grid axis's static length: the most blocks any tile visits."""
    _, count_a, _, count_b = _bd_blocks(ranges_of(np.arange(tiles)), size, np)
    return max((count_a + count_b).tolist())


def _bd_kv_steps(t: int, t_blk: int, s_blk: int, bd: BlockDiffusion) -> int:
    return _bd_steps(t // t_blk, lambda i: _bd_keys_read(i, t_blk, bd, np), s_blk)


def _bd_q_steps(s: int, s_blk: int, t: int, t_blk: int, bd: BlockDiffusion) -> int:
    return _bd_steps(s // s_blk, lambda i: _bd_queries_reading(i, s_blk, t, bd, np), t_blk)


def _bd_tile_classes(t: int, t_blk: int, s_blk: int, bd: BlockDiffusion):
    """The class of every tile the kernels visit, over the forward's grid
    (the backward's grids visit the same tiles)."""
    t_idx = np.arange(t // t_blk)[:, None]
    step = np.arange(_bd_kv_steps(t, t_blk, s_blk, bd))[None, :]
    s_idx, visible = _bd_step(_bd_blocks(_bd_keys_read(t_idx, t_blk, bd, np), s_blk, np), step, np)
    return np.broadcast_to(_bd_tile_class(t_idx, s_idx, t_blk, s_blk, bd, np), visible.shape)[visible]


def _bd_kinds(t: int, t_blk: int, s_blk: int, bd: BlockDiffusion):
    """The classes that occur among the visited tiles: a kernel builds a
    branch for each and no other."""
    return tuple(int(kind) for kind in np.unique(_bd_tile_classes(t, t_blk, s_blk, bd)))


def _bd_bias(t_blk: int, s_blk: int, t_idx, s_idx, bd: BlockDiffusion):
    """(T_blk, S_blk) additive bias of the block-diffusion mask for query
    tile ``t_idx`` against key block ``s_idx`` (global indices)."""
    rows = t_idx * t_blk + jax.lax.broadcasted_iota(jnp.int32, (t_blk, s_blk), 0)
    cols = s_idx * s_blk + jax.lax.broadcasted_iota(jnp.int32, (t_blk, s_blk), 1)
    return jnp.where(masking.block_diffusion_visible(rows, cols, *bd), 0.0, MASK_VALUE)


def _bd_logits(logits, bias_ref, kind: int, t_idx, s_idx, bd: BlockDiffusion):
    """A visited tile's (T_blk, S_blk) logits under the block-diffusion mask,
    at the cost of its class ``kind`` (static). Only a MIXED tile can hold a
    padded key, so only it adds the pad bias (zero on every other key); a cut
    tile compares a row's block with a column's, each counted from the start
    of its half: a (T_blk, 1) column of blocks against a (1, S_blk) row of
    them, so the one compare is the only operation a score. Every class
    gives the logits the MIXED path gives: a masked score MASK_VALUE, a
    visible one unchanged."""
    if kind == WHOLE:
        return logits
    t_blk, s_blk = logits.shape
    if kind == MIXED:
        return logits + bias_ref[0] + _bd_bias(t_blk, s_blk, t_idx, s_idx, bd)
    half, blk = bd
    shift = blk.bit_length() - 1
    row0 = t_idx * t_blk - (half if kind == CUT_CC else 0)
    col0 = s_idx * s_blk - (0 if kind == CUT_NN else half)
    rows = (row0 + jax.lax.broadcasted_iota(jnp.int32, (t_blk, 1), 0)) >> shift
    cols = (col0 + jax.lax.broadcasted_iota(jnp.int32, (1, s_blk), 1)) >> shift
    visible = {CUT_NN: operator.eq, CUT_NC: operator.lt, CUT_CC: operator.le}[kind](cols, rows)
    return jnp.where(visible, logits, MASK_VALUE)


def _bd_branches(tile, kinds, visible, t_idx, s_idx, t_blk: int, s_blk: int, bd: BlockDiffusion):
    """Run ``tile(kind)`` on a visible step, under the branch of its class."""
    kind = _bd_tile_class(t_idx, s_idx, t_blk, s_blk, bd, jnp)
    for each in kinds:
        pl.when(visible & (kind == each))(functools.partial(tile, each))


def _attention_kernel(bias_ref, q_ref, k_ref, v_ref, out_ref, *rest,
                      scale: float, with_lse: bool,
                      causal_offset: Optional[int], skip_masked: bool,
                      block_diffusion: Optional[BlockDiffusion] = None, bd_kinds=()):
    if with_lse:
        m_out, l_out, m_ref, l_ref, acc_ref = rest
        lse_ref = (m_out, l_out)
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    # read outside the conditional tile body (interpret mode cannot lower a
    # program_id inside a cond)
    t_idx, s_idx = pl.program_id(2), pl.program_id(3)
    if block_diffusion is not None:  # the grid's KV step -> the key block it reads
        kv_idx, visible = _bd_kv_step(t_idx, s_idx, q_ref.shape[2], k_ref.shape[2],
                                      block_diffusion)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(kind=None):
        q = q_ref[0, 0]  # (T_blk, D)
        k = k_ref[0, 0]  # (S_blk, D)
        logits = _dot(q, k, (1, 1)) * scale  # (T_blk, S_blk)
        if block_diffusion is not None:
            logits = _bd_logits(logits, bias_ref, kind, t_idx, kv_idx, block_diffusion)
        else:
            logits += bias_ref[0]  # (1, S_blk) broadcasts over T_blk
        if causal_offset is not None:
            logits += _causal_bias(q.shape[0], k.shape[0], t_idx, s_idx,
                                   causal_offset)

        m_prev = m_ref[:, :1]  # (T_blk, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)  # (T_blk, S_blk)

        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = _dot(p.astype(v_ref.dtype), v_ref[0, 0], (1, 0))  # (T_blk, Dv)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if block_diffusion is not None:
        _bd_branches(_tile, bd_kinds, visible, t_idx, kv_idx, q_ref.shape[2], k_ref.shape[2],
                     block_diffusion)
    elif skip_masked:
        pl.when(_tile_visible(t_idx, s_idx, q_ref.shape[2],
                              k_ref.shape[2], causal_offset))(_tile)
    else:
        _tile()

    @pl.when(s_idx == pl.num_programs(3) - 1)
    def _finish():
        out_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(out_ref.dtype)
        if with_lse:
            m_out_ref, l_out_ref = lse_ref
            m_out_ref[0, 0] = m_ref[:]
            l_out_ref[0, 0] = l_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=("t_blk", "s_blk", "interpret", "with_lse",
                     "causal_offset", "skip_masked", "block_diffusion"),
)
def _fused_attention_fwd_impl(
    q: Array, k: Array, v: Array, bias: Array,
    t_blk: int, s_blk: int, interpret: bool, with_lse: bool = False,
    causal_offset: Optional[int] = None, skip_masked: bool = False,
    block_diffusion: Optional[BlockDiffusion] = None,
):
    """(B, H, T, D) q against (B, Hkv, S, D) k and (B, Hkv, S, Dv) v with (B,
    S) additive bias; the output is (B, H, T, Dv). Query head ``h`` reads
    key/value head ``h // (H // Hkv)``: the block index maps say so, and no
    repeated copy of K / V exists. ``t_blk``/``s_blk`` must divide T/S (the
    wrapper guarantees it).
    With ``with_lse`` also returns the softmax running max ``m`` and
    denominator ``l``, each lane-broadcast to (B, H, T, LANES) f32, for the
    fused backward. They are saved separately — not as ``m + log l`` — so a
    fully padded row (m pinned at MASK_VALUE, which absorbs log l in f32)
    still recomputes exactly as exp(logits − m)/l.
    ``skip_masked``: tiles that the causal rule masks whole are neither
    computed nor fetched (the KV index stays at the last visible block, and
    a block whose index does not change is not copied again).
    ``block_diffusion``: the block-diffusion mask in place of the causal
    rule; the KV axis walks the key blocks each query tile reads (its noised
    and its clean range) and no other, and a step masks as its tile's class
    asks (``_bd_logits``)."""
    b, h, t, d = q.shape
    s, dv = k.shape[2], v.shape[3]
    scale = d**-0.5
    grid = (b, h, t // t_blk, s // s_blk)
    kv_head = functools.partial(_kv_head, group=h // k.shape[1])

    kv_block = functools.partial(_kv_block_index, t_blk=t_blk, s_blk=s_blk,
                                 offset=causal_offset, skip_masked=skip_masked)
    bd_kinds = ()
    if block_diffusion is not None:
        grid = (b, h, t // t_blk, _bd_kv_steps(t, t_blk, s_blk, block_diffusion))
        bd_kinds = _bd_kinds(t, t_blk, s_blk, block_diffusion)

        def kv_block(ti, si):
            return _bd_kv_step(ti, si, t_blk, s_blk, block_diffusion)[0]

    out_shape = jax.ShapeDtypeStruct((b, h, t, dv), q.dtype)
    out_specs = pl.BlockSpec((1, 1, t_blk, dv), lambda bi, hi, ti, si: (bi, hi, ti, 0))
    if with_lse:
        lm_shape = jax.ShapeDtypeStruct((b, h, t, _LANES), jnp.float32)
        lm_spec = pl.BlockSpec((1, 1, t_blk, _LANES),
                               lambda bi, hi, ti, si: (bi, hi, ti, 0))
        out_shape = (out_shape, lm_shape, lm_shape)
        out_specs = (out_specs, lm_spec, lm_spec)

    bias = bias[:, None, :]  # (B, 1, S)
    kernel = pl.pallas_call(
        functools.partial(_attention_kernel, scale=scale, with_lse=with_lse,
                          causal_offset=causal_offset, skip_masked=skip_masked,
                          block_diffusion=block_diffusion, bd_kinds=bd_kinds),
        grid=grid,
        in_specs=[
            # (B, 1, S) so the block's trailing dims satisfy TPU tiling
            pl.BlockSpec((1, 1, s_blk),
                         lambda bi, hi, ti, si: (bi, 0, kv_block(ti, si))),
            pl.BlockSpec((1, 1, t_blk, d), lambda bi, hi, ti, si: (bi, hi, ti, 0)),
            pl.BlockSpec((1, 1, s_blk, d),
                         lambda bi, hi, ti, si: (bi, kv_head(hi), kv_block(ti, si), 0)),
            pl.BlockSpec((1, 1, s_blk, dv),
                         lambda bi, hi, ti, si: (bi, kv_head(hi), kv_block(ti, si), 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((t_blk, _LANES), jnp.float32),  # running max
            pltpu.VMEM((t_blk, _LANES), jnp.float32),  # running denominator
            pltpu.VMEM((t_blk, dv), jnp.float32),  # PV accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            # batch/head/query-block grid steps are independent; only the KV
            # axis carries the softmax recurrence
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=KERNEL_FWD,
    )
    return kernel(bias, q, k, v)


def _recompute_probs_and_ds(bias_ref, q_ref, k_ref, v_ref, g_ref,
                            m_ref, l_ref, di_ref, *, scale: float,
                            causal_offset: Optional[int],
                            t_idx, s_idx, block_diffusion=None, bd_kind=None):
    """Shared backward tile math: recompute p = exp(logits − m)/l for this
    (T_blk, S_blk) tile and the softmax gradient ds = p·(dp − delta).

    ds is zeroed on fully padded rows (m pinned at MASK_VALUE) so dq/dk
    reproduce the XLA path's where-masking; p is left intact there (uniform
    1/l) because dv keeps the uniform contribution on that path. With a
    ``causal_offset`` the tile recomputes the same in-kernel causal bias the
    forward applied (``t_idx``/``s_idx`` are the GLOBAL query/key block
    indices — the two backward kernels run swapped grids, so the caller
    passes whichever program_id carries each axis). With a
    ``block_diffusion`` mask the same: ``t_idx``/``s_idx`` are the blocks the
    grid step reads, and ``bd_kind`` (static) the tile's class."""
    q = q_ref[0, 0]  # (T_blk, D)
    k = k_ref[0, 0]  # (S_blk, D)
    g = g_ref[0, 0]  # (T_blk, Dv)
    logits = _dot(q, k, (1, 1)) * scale  # (T_blk, S_blk)
    if block_diffusion is not None:
        logits = _bd_logits(logits, bias_ref, bd_kind, t_idx, s_idx, block_diffusion)
    else:
        logits += bias_ref[0]  # (1, S_blk) broadcasts over T_blk
    if causal_offset is not None:
        logits += _causal_bias(q.shape[0], k.shape[0], t_idx, s_idx,
                               causal_offset)
    m = m_ref[0, 0][:, :1]  # (T_blk, 1)
    l = l_ref[0, 0][:, :1]
    p = jnp.exp(logits - m) / l
    dp = _dot(g, v_ref[0, 0], (1, 1))  # (T_blk, S_blk)
    ds = p * (dp - di_ref[0, 0][:, :1])
    ds = jnp.where(m <= 0.5 * MASK_VALUE, 0.0, ds)
    return p, ds, q, k, g


def _bwd_dq_kernel(bias_ref, q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, di_ref,
                   dq_ref, acc_ref, *, scale: float,
                   causal_offset: Optional[int], skip_masked: bool,
                   block_diffusion: Optional[BlockDiffusion] = None, bd_kinds=()):
    t_idx, s_idx = pl.program_id(2), pl.program_id(3)
    kv_idx = s_idx
    if block_diffusion is not None:  # the grid's KV step -> the key block it reads
        kv_idx, visible = _bd_kv_step(t_idx, s_idx, q_ref.shape[2], k_ref.shape[2],
                                      block_diffusion)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(kind=None):
        _, ds, _, k, _ = _recompute_probs_and_ds(
            bias_ref, q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, di_ref,
            scale=scale, causal_offset=causal_offset,
            t_idx=t_idx, s_idx=kv_idx, block_diffusion=block_diffusion, bd_kind=kind,
        )
        acc_ref[:] += _dot(ds.astype(k.dtype), k, (1, 0))  # (T_blk, D)

    if block_diffusion is not None:
        _bd_branches(_tile, bd_kinds, visible, t_idx, kv_idx, q_ref.shape[2], k_ref.shape[2],
                     block_diffusion)
    elif skip_masked:
        pl.when(_tile_visible(t_idx, s_idx, q_ref.shape[2],
                              k_ref.shape[2], causal_offset))(_tile)
    else:
        _tile()

    @pl.when(s_idx == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(bias_ref, q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, di_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal_offset: Optional[int], skip_masked: bool,
                    group: int, q_blocks: int,
                    block_diffusion: Optional[BlockDiffusion] = None, rows: int = 0,
                    bd_kinds=()):
    # the sequential axis runs over the query blocks of every query head of
    # this key/value head's group, one head after the other: dk and dv are
    # sums over all of them, and the accumulators live across the whole axis
    # (with a block-diffusion mask ``q_blocks`` steps a head, walking the
    # query blocks of the ``rows`` queries that read this key block)
    s_idx, step = pl.program_id(2), pl.program_id(3)
    t_idx = step if group == 1 else step % q_blocks
    if block_diffusion is not None:
        t_idx, visible = _bd_q_step(s_idx, t_idx, q_ref.shape[2], k_ref.shape[2], rows,
                                    block_diffusion)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _tile(kind=None):
        p, ds, q, _, g = _recompute_probs_and_ds(
            bias_ref, q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, di_ref,
            scale=scale, causal_offset=causal_offset,
            t_idx=t_idx, s_idx=s_idx, block_diffusion=block_diffusion, bd_kind=kind,
        )
        # contract the query axis: (T_blk, S_blk)ᵀ·(T_blk, D) → (S_blk, D)
        dv_acc[:] += _dot(p.astype(g.dtype), g, (0, 0))
        dk_acc[:] += _dot(ds.astype(q.dtype), q, (0, 0))

    if block_diffusion is not None:
        _bd_branches(_tile, bd_kinds, visible, t_idx, s_idx, q_ref.shape[2], k_ref.shape[2],
                     block_diffusion)
    elif skip_masked:
        pl.when(_tile_visible(t_idx, s_idx, q_ref.shape[2],
                              k_ref.shape[2], causal_offset))(_tile)
    else:
        _tile()

    @pl.when(step == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("t_blk", "s_blk", "interpret", "causal_offset",
                     "skip_masked", "block_diffusion"),
)
def _fused_attention_bwd_impl(
    q: Array, k: Array, v: Array, bias: Array, out: Array,
    m: Array, l: Array,
    g: Array, t_blk: int, s_blk: int, interpret: bool,
    causal_offset: Optional[int] = None, skip_masked: bool = False,
    block_diffusion: Optional[BlockDiffusion] = None,
):
    b, h, t, d = q.shape
    s, dv = k.shape[2], v.shape[3]
    scale = d**-0.5
    kv_heads = k.shape[1]
    group = h // kv_heads
    kv_head = functools.partial(_kv_head, group=group)

    # delta = Σ_d g·out per query row, lane-broadcast like lse
    di = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di[..., None], (b, h, t, _LANES))

    kv_block = functools.partial(_kv_block_index, t_blk=t_blk, s_blk=s_blk,
                                 offset=causal_offset, skip_masked=skip_masked)
    kv_steps, q_blocks = s // s_blk, t // t_blk
    bd_kinds = ()
    if block_diffusion is not None:  # the grids walk the blocks the mask leaves
        kv_steps = _bd_kv_steps(t, t_blk, s_blk, block_diffusion)
        q_blocks = _bd_q_steps(s, s_blk, t, t_blk, block_diffusion)
        bd_kinds = _bd_kinds(t, t_blk, s_blk, block_diffusion)

        def kv_block(ti, si):
            return _bd_kv_step(ti, si, t_blk, s_blk, block_diffusion)[0]

    bias = bias[:, None, :]  # (B, 1, S)
    q_spec = pl.BlockSpec((1, 1, t_blk, d), lambda bi, hi, ti, si: (bi, hi, ti, 0))
    g_spec = pl.BlockSpec((1, 1, t_blk, dv), lambda bi, hi, ti, si: (bi, hi, ti, 0))
    k_spec = pl.BlockSpec((1, 1, s_blk, d),
                          lambda bi, hi, ti, si: (bi, kv_head(hi), kv_block(ti, si), 0))
    v_spec = pl.BlockSpec((1, 1, s_blk, dv),
                          lambda bi, hi, ti, si: (bi, kv_head(hi), kv_block(ti, si), 0))
    lm_spec = pl.BlockSpec((1, 1, t_blk, _LANES),
                           lambda bi, hi, ti, si: (bi, hi, ti, 0))
    bias_spec = pl.BlockSpec((1, 1, s_blk),
                             lambda bi, hi, ti, si: (bi, 0, kv_block(ti, si)))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale,
                          causal_offset=causal_offset, skip_masked=skip_masked,
                          block_diffusion=block_diffusion, bd_kinds=bd_kinds),
        grid=(b, h, t // t_blk, kv_steps),  # KV axis sequential
        in_specs=[bias_spec, q_spec, k_spec, v_spec, g_spec,
                  lm_spec, lm_spec, lm_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((t_blk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=KERNEL_DQ,
    )(bias, q, k, v, g, m, l, di)

    # dkv grid: one key/value head a grid row, and innermost (sequential) the
    # query blocks of each query head of its group in turn; the same index
    # maps apply, with the query block and head read from that one position

    def q_head(hi, step):
        return hi if group == 1 else hi * group + step // q_blocks

    def q_block(si, step):
        ti = step if group == 1 else step % q_blocks
        if block_diffusion is not None:
            return _bd_q_step(si, ti, t_blk, s_blk, t, block_diffusion)[0]
        if not skip_masked:
            return ti
        # a KV block past every row's reach has no visible query block: its
        # tiles are all skipped, but what it fetches must still exist
        first = jnp.minimum(_first_visible_q(si, t_blk, s_blk, causal_offset),
                            q_blocks - 1)
        return jnp.maximum(ti, first)

    q_spec2 = pl.BlockSpec((1, 1, t_blk, d),
                           lambda bi, hi, si, ti: (bi, q_head(hi, ti), q_block(si, ti), 0))
    g_spec2 = pl.BlockSpec((1, 1, t_blk, dv),
                           lambda bi, hi, si, ti: (bi, q_head(hi, ti), q_block(si, ti), 0))
    k_spec2 = pl.BlockSpec((1, 1, s_blk, d), lambda bi, hi, si, ti: (bi, hi, si, 0))
    v_spec2 = pl.BlockSpec((1, 1, s_blk, dv), lambda bi, hi, si, ti: (bi, hi, si, 0))
    lm_spec2 = pl.BlockSpec((1, 1, t_blk, _LANES),
                            lambda bi, hi, si, ti: (bi, q_head(hi, ti), q_block(si, ti), 0))
    bias_spec2 = pl.BlockSpec((1, 1, s_blk), lambda bi, hi, si, ti: (bi, 0, si))
    dk, dv_out = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale,
                          causal_offset=causal_offset, skip_masked=skip_masked,
                          group=group, q_blocks=q_blocks,
                          block_diffusion=block_diffusion, rows=t, bd_kinds=bd_kinds),
        grid=(b, kv_heads, s // s_blk, group * q_blocks),  # query axis sequential
        in_specs=[bias_spec2, q_spec2, k_spec2, v_spec2, g_spec2,
                  lm_spec2, lm_spec2, lm_spec2],
        out_specs=(k_spec2, v_spec2),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((s_blk, d), jnp.float32),
                        pltpu.VMEM((s_blk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=KERNEL_DKV,
    )(bias, q, k, v, g, m, l, di)
    return dq, dk, dv_out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _fused_attention(q, k, v, bias, t_blk, s_blk, interpret, causal_offset,
                     skip_masked=False, block_diffusion=None):
    return _fused_attention_fwd_impl(q, k, v, bias, t_blk, s_blk, interpret,
                                     causal_offset=causal_offset,
                                     skip_masked=skip_masked,
                                     block_diffusion=block_diffusion)


def _fwd(q, k, v, bias, t_blk, s_blk, interpret, causal_offset, skip_masked,
         block_diffusion):
    out, m, l = _fused_attention_fwd_impl(
        q, k, v, bias, t_blk, s_blk, interpret, with_lse=True,
        causal_offset=causal_offset, skip_masked=skip_masked,
        block_diffusion=block_diffusion,
    )
    # the primal output is the named one too: whatever reads it in a
    # recomputation then reads the kept copy and needs no kernel
    out = checkpoint_name(out, REMAT_FUSED_OUT)
    m1 = checkpoint_name(m[..., 0], REMAT_FUSED_STATS)
    l1 = checkpoint_name(l[..., 0], REMAT_FUSED_STATS)
    return out, (q, k, v, bias, out, m1, l1)


def _bwd(t_blk, s_blk, interpret, causal_offset, skip_masked, block_diffusion,
         residuals, g):
    q, k, v, bias, out, m1, l1 = residuals
    m, l = (jnp.broadcast_to(x[..., None], x.shape + (_LANES,)) for x in (m1, l1))
    dq, dk, dv = _fused_attention_bwd_impl(
        q, k, v, bias, out, m, l, g, t_blk, s_blk, interpret,
        causal_offset=causal_offset, skip_masked=skip_masked,
        block_diffusion=block_diffusion,
    )
    return dq, dk, dv, jnp.zeros_like(bias)


_fused_attention.defvjp(_fwd, _bwd)


def _prepare_blocks(q, k, v, bias, kv_block_size, q_block_size, interpret):
    """Shared preamble: heads-major transpose, KV/query block sizing, and
    tiling-legal padding. Returns ``(q, k, v, bias, t_blk, s_blk, t_pad)``
    with q/k/v in (B, H, T/S, D) layout. ``q_block_size=None`` resolves per
    shape after s_blk is known (see LONG_KV_Q_BLOCK)."""
    # heads-major layout so each (b, h) grid step reads contiguous KV rows
    q = jnp.transpose(q, (0, 2, 1, 3))
    k = jnp.transpose(k, (0, 2, 1, 3))
    v = jnp.transpose(v, (0, 2, 1, 3))

    t_blk, t_pad, s_blk, s_pad = _block_sizes(q.shape[2], k.shape[2], q.shape[-1],
                                              kv_block_size, q_block_size, interpret)
    if s_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, s_pad)), constant_values=PAD_BIAS)
    if t_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    return q, k, v, bias, t_blk, s_blk, t_pad


def _block_sizes(t: int, s: int, d: int, kv_block_size, q_block_size, interpret: bool):
    """``(t_blk, t_pad, s_blk, s_pad)`` of T queries against S keys, ``d``
    deep: the blocks and the padding ``_prepare_blocks`` gives them."""
    # Stream the KV axis in blocks. Compiled TPU blocks must be lane-aligned
    # (a multiple of 128, or the full dim); when S has no aligned divisor, pad
    # it up to a block multiple with PAD_BIAS keys (excluded from the softmax
    # even on fully-masked rows).
    alignment = _TEST_ALIGNMENT or (1 if interpret else _LANES)
    if kv_block_size is None:
        kv_block_size = _auto_kv_block(s, d, t, alignment, q_block_size)
    s_pad = 0
    s_blk = _kv_block_size(s, kv_block_size, alignment)
    if s_blk == 0:
        if s <= 4 * kv_block_size:
            s_blk = s  # full-dim blocks are always tiling-legal; skip padding
        else:
            s_blk = max(kv_block_size - kv_block_size % alignment, alignment)
            s_pad = -s % s_blk

    if q_block_size is None:
        # auto: the big query block only in its measured-safe regime (see
        # the LONG_KV_Q_BLOCK note — both guards are load-bearing)
        if (t % LONG_KV_Q_BLOCK == 0 and d <= LONG_KV_MAX_D
                and s_blk * d <= LONG_KV_SAFE_SBLK_D
                and s_blk * LONG_KV_Q_BLOCK <= LONG_KV_SAFE_PROBS):
            q_block_size = LONG_KV_Q_BLOCK
        else:
            q_block_size = DEFAULT_Q_BLOCK

    # Block the query axis too: a fully resident query block (plus its f32
    # accumulator and double-buffered output) blows the VMEM scoped limit once
    # T reaches a few thousand (e.g. dense flow decoder queries). Padded query
    # rows attend normally and are sliced off after.
    t_pad = 0
    t_blk = _kv_block_size(t, q_block_size, alignment)
    if t_blk == 0:
        if t <= 2 * q_block_size:
            t_blk = t
        else:
            t_blk = max(q_block_size - q_block_size % alignment, alignment)
            t_pad = -t % t_blk
    return t_blk, t_pad, s_blk, s_pad


def block_diffusion_unmasked_tiles_pct(t: int, d: int, bd: BlockDiffusion,
                                       kv_block_size: Optional[int] = None,
                                       q_block_size: Optional[int] = None,
                                       interpret: Optional[bool] = None) -> float:
    """Share, in %, of the tiles that ``fused_attention(..., block_diffusion=
    bd)`` visits at T = S = ``t`` positions, ``d`` deep, with these blocks,
    that run with no mask (the WHOLE class): the classes the kernels branch on,
    over the grid they build."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t_blk, t_pad, s_blk, _ = _block_sizes(t, t, d, kv_block_size, q_block_size, interpret)
    classes = _bd_tile_classes(t + t_pad, t_blk, s_blk, bd)
    return 100.0 * np.count_nonzero(classes == WHOLE) / classes.size


def fused_attention(
    q: Array,
    k: Array,
    v: Array,
    pad_mask: Optional[Array] = None,
    kv_block_size: Optional[int] = None,
    q_block_size: Optional[int] = None,
    interpret: Optional[bool] = None,
    causal_offset: Optional[int] = None,
    block_diffusion: Optional[BlockDiffusion] = None,
) -> Array:
    """Fused multi-head attention over (B, T, H, D) q, (B, S, Hkv, D) k and
    (B, S, Hkv, Dv) v; the output is (B, T, H, Dv). Dv may differ from D (a
    latent-attention head: 192-deep scores, 128-deep values); the scores are
    scaled by ``D ** -0.5``. ``Hkv`` may be a divisor of ``H`` (grouped-query
    attention): query head ``h`` reads key/value head ``h // (H // Hkv)``
    through the kernels' block index maps, K / V are never repeated in HBM,
    and ``dk`` / ``dv`` come out with ``Hkv`` heads, each summed over its
    group's query heads inside the dk/dv kernel (its sequential axis runs over
    the group). Measured against per-query-head float32 gradients summed in
    XLA (my chip run, PR 36: 2 x 8192, 32 / 8 heads of 64, forward + backward):
    41.60 against 42.52 ms at blocks of 1024 x 1024, 44.84 against 45.31 at 512
    x 1024; the two agree to 3.7e-4 of the largest gradient in bfloat16.

    ``pad_mask``: optional (B, S) bool, True = key position masked out (the
    torch ``key_padding_mask`` convention). ``causal_offset``: static int —
    query row i may attend key positions ``<= i + causal_offset`` (the
    ``ops.masking.causal_mask`` rule applied IN-KERNEL as an additive bias,
    never a materialized (T, S) mask; composes with ``pad_mask`` by
    addition, i.e. OR). 0 = square causal self-attention; L − N = the
    Perceiver-AR latent-window cross-attention. Covers forward AND both
    backward kernels. ``kv_block_size=None`` (default) resolves per shape —
    wider KV streaming for shallow heads at long S (see ``_auto_kv_block``);
    ``q_block_size=None`` (default) resolves per shape after KV-block sizing
    (see LONG_KV_Q_BLOCK). Off-TPU backends run the kernel in interpreter
    mode (slow — for tests), overridable via ``interpret``.

    With a ``causal_offset >= 0`` and no ``pad_mask`` the tiles that lie
    wholly above the diagonal are neither fetched nor computed, forward and
    backward: about half of a square causal attention. Exact there, because
    every row sees a key in its first tile, so a masked tile adds
    exp(-1e30 - m) = 0. With a pad mask a row may see padding only, and then
    owes its uniform softmax to the masked tiles too; under a negative offset
    the first rows see no key at all: both keep every tile.

    ``block_diffusion``: the block-diffusion mask of a row ``[x_t ; x]``
    (``ops.masking.block_diffusion_visible``) in place of the causal rule,
    without a ``pad_mask``; T = S = 2 x ``half``. Computed in-kernel from
    row and column indices, forward and backward, and the tiles it hides
    whole are neither fetched nor computed: a noised query tile reads its own
    noised block and the clean prefix before it, a clean one the clean prefix
    up to its end (about L^2 of the 4 L^2 pairs are visible). Exact for the
    same reason as the causal skipping: every row reads itself. A visited tile
    pays for the mask by its class (``_bd_tile_class``, once a grid step): a
    tile wholly visible adds no mask, a tile that the mask cuts inside one
    quadrant of noised / clean queries by noised / clean keys adds one compare
    of shifted row and column indices, and only a tile across the two halves
    or over padding adds the general rule.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected (B, T/S, H, D) tensors, got {q.shape=} {k.shape=}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, t, h, d = q.shape
    s = k.shape[1]
    if h % k.shape[2] or v.shape[2] != k.shape[2]:
        raise ValueError(f"{h} query heads cannot share {k.shape[2]} key and "
                         f"{v.shape[2]} value heads")
    if block_diffusion is not None and (
            pad_mask is not None or causal_offset is not None or t != s
            or t != 2 * block_diffusion.half
            or block_diffusion.block & (block_diffusion.block - 1)):
        raise ValueError(f"the block-diffusion mask takes T = S = 2 x {block_diffusion.half} "
                         "and blocks of a power of two, without a pad mask or a causal offset")
    if pad_mask is None:
        bias = jnp.zeros((b, s), jnp.float32)
    else:
        bias = jnp.where(pad_mask, MASK_VALUE, 0.0).astype(jnp.float32)

    skip_masked = causal_offset is not None and causal_offset >= 0 and pad_mask is None
    q, k, v, bias, t_blk, s_blk, t_pad = _prepare_blocks(
        q, k, v, bias, kv_block_size, q_block_size, interpret
    )
    out = _fused_attention(
        q, k, v, bias, t_blk, s_blk, interpret,
        None if causal_offset is None else int(causal_offset),
        skip_masked, block_diffusion,
    )
    if t_pad:
        out = out[:, :, :t]
    return jnp.transpose(out, (0, 2, 1, 3))


# -- sequence-parallel fused attention ---------------------------------------
#
# The distributed-flash combine: each device runs the streaming kernel over
# its LOCAL KV shard, then the per-shard softmax statistics (running max m,
# denominator l) merge across the mesh axis with one pmax + two psums — the
# Perceiver-shaped equivalent of ring attention (latents/queries are
# replicated along the axis and S is the only long dimension, so a single
# all-reduce of O(B·H·T) stats replaces a ring of KV exchanges). The
# backward reruns the flash backward per shard against the GLOBAL (m, l)
# and psums only dq (dk/dv stay shard-local).


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sp_fused(q, k, v, bias, t_blk, s_blk, interpret, axis):
    out, _, _ = _sp_forward(q, k, v, bias, t_blk, s_blk, interpret, axis)
    return out


def _sp_forward(q, k, v, bias, t_blk, s_blk, interpret, axis):
    out_l, m_l, l_l = _fused_attention_fwd_impl(
        q, k, v, bias, t_blk, s_blk, interpret, with_lse=True
    )
    # the kernel saves stats lane-broadcast as (B, H, T, LANES); collect the
    # collectives on the [:, :, :, :1] slice so each stat all-reduce moves
    # O(B·H·T), not 128x that, then re-broadcast for the backward residuals
    m_g = jax.lax.pmax(m_l[..., :1], axis)
    # a shard whose keys are all padded has m_l pinned at MASK_VALUE: its
    # weight underflows to exactly 0 against any real shard, and when EVERY
    # shard is padded (fully masked row) the weights reduce to l_l > 0 — the
    # same uniform-attention semantics as the single-device kernel
    w = jnp.exp(m_l[..., :1] - m_g) * l_l[..., :1]  # (B, H, T, 1) f32
    l_g = jax.lax.psum(w, axis)
    out = jax.lax.psum(out_l.astype(jnp.float32) * (w / l_g), axis)
    bcast = lambda x: jnp.broadcast_to(x, x.shape[:-1] + (m_l.shape[-1],))
    return out.astype(out_l.dtype), bcast(m_g), bcast(l_g)


def _sp_fwd(q, k, v, bias, t_blk, s_blk, interpret, axis):
    out, m_g, l_g = _sp_forward(q, k, v, bias, t_blk, s_blk, interpret, axis)
    return out, (q, k, v, bias, out, m_g, l_g)


def _sp_bwd(t_blk, s_blk, interpret, axis, residuals, g):
    q, k, v, bias, out, m_g, l_g = residuals
    # JAX-version sensitivity: the scaling below encodes shard_map's
    # check_rep=False transpose convention as observed on jax 0.9.x. It is
    # not a documented contract — a future upgrade could change it SILENTLY
    # (gradients off by exactly the product of some mesh axis sizes, forward
    # unchanged). The canary is TestSeqParallelFusedAttention
    # .test_gradients_match_single_device (dp/tp/sp parametrized): if it
    # fails with grads wrong by an integer factor after a JAX upgrade, this
    # is the first place to look.
    #
    # shard_map's transpose conventions under check_rep=False (empirically
    # pinned by the gradient tests across dp/tp/sp mesh mixes): the
    # cotangent of an output replicated over mesh axes arrives DIVIDED by
    # the product of those axis sizes, and the returned input cotangents are
    # psum'd over each input's own unmentioned axes on the way out. Those
    # outgoing psums already restore the factor for every replicated NON-seq
    # axis (each of its replicas computes an identical cotangent), so the
    # only factor to reconstruct here is the seq axis itself — its replicas
    # hold genuinely PARTIAL contributions, not copies.
    g = jax.lax.psum(g, axis)
    # global (m, l) make each shard's recomputed tile probabilities the
    # GLOBAL softmax restricted to its keys; out/g are replicated, so the
    # in-kernel delta = Σ g·out is already global
    dq_partial, dk, dv = _fused_attention_bwd_impl(
        q, k, v, bias, out, m_g, l_g, g, t_blk, s_blk, interpret
    )
    return dq_partial, dk, dv, jnp.zeros_like(bias)


_sp_fused.defvjp(_sp_fwd, _sp_bwd)


def seq_parallel_fused_attention(
    q: Array,
    k: Array,
    v: Array,
    pad_mask: Optional[Array] = None,
    *,
    mesh,
    axis: str = "seq",
    batch_axis: Optional[str] = None,
    head_axis: Optional[str] = None,
    kv_block_size: Optional[int] = None,
    q_block_size: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """:func:`fused_attention` with the KV axis SHARDED over a mesh axis.

    Sequence/context parallelism for the kernel path: under plain ``jit``
    GSPMD cannot partition a ``pallas_call``, so a seq-sharded KV stream gets
    all-gathered before the kernel — the memory benefit of sharding M is
    lost exactly where it matters (SURVEY.md §5's long-context plan). This
    wrapper runs the kernel under ``shard_map`` instead: every device
    processes only its S/n_shards slice of keys/values (O(S/n) HBM and VMEM),
    and the softmax statistics merge with one ``pmax`` + two ``psum`` of
    O(B·H·T) — no ring, because Perceiver attention has replicated queries
    and a single long axis. Gradients: flash backward per shard against the
    global statistics; only dq is psum'd (dk/dv are shard-local like k/v).

    Args mirror :func:`fused_attention`, plus:
      mesh: the ``jax.sharding.Mesh`` to shard over.
      axis: mesh axis name carrying the KV shards (default ``'seq'``).
      batch_axis: optional mesh axis for the leading batch dimension (compose
        with data parallelism).
      head_axis: optional mesh axis for the head dimension (compose with
        tensor parallelism: each device keeps only its H/tp heads — without
        this, a tp mesh axis is unmentioned in the specs and shard_map forces
        an all-gather of all heads onto every device). Heads are independent
        in every matmul and in the softmax-stat merge (the collectives reduce
        over ``axis`` only), so the math is unchanged. The axis size must
        divide H (e.g. 8 heads on tp=4: two heads per device).
    Inputs may be global ``jax.Array``s (sharded or not) or host arrays; S
    must divide evenly by the axis size.
    """
    # jax >= 0.8 moved shard_map to the top level and renamed check_rep to
    # check_vma; support both spellings (this build may ship either)
    try:
        from jax import shard_map
        check_kw = "check_vma"
    except ImportError:
        from jax.experimental.shard_map import shard_map
        check_kw = "check_rep"
    from jax.sharding import PartitionSpec as P

    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected (B, T/S, H, D) tensors, got {q.shape=} {k.shape=}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_shards = mesh.shape[axis]
    b, t, h, d = q.shape
    s = k.shape[1]
    if s % n_shards:
        raise ValueError(
            f"KV length {s} must be divisible by the '{axis}' mesh axis "
            f"size ({n_shards}) — pad S to a multiple"
        )
    if head_axis is not None and h % mesh.shape[head_axis]:
        raise ValueError(
            f"head count {h} must be divisible by the '{head_axis}' mesh "
            f"axis size ({mesh.shape[head_axis]})"
        )
    # q_block_size=None resolves inside _prepare_blocks, which runs on the
    # shard_map-LOCAL arrays — the auto choice sees each device's actual
    # S/n slice and resolved s_blk

    if pad_mask is None:
        bias = jnp.zeros((b, s), jnp.float32)
    else:
        bias = jnp.where(pad_mask, MASK_VALUE, 0.0).astype(jnp.float32)

    def local(q_l, k_l, v_l, bias_l):
        qh, kh, vh, bias_p, t_blk, s_blk, t_pad = _prepare_blocks(
            q_l, k_l, v_l, bias_l, kv_block_size, q_block_size, interpret
        )
        out = _sp_fused(qh, kh, vh, bias_p, t_blk, s_blk, interpret, axis)
        if t_pad:
            out = out[:, :, :t]
        return jnp.transpose(out, (0, 2, 1, 3))

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(batch_axis, None, head_axis),
            P(batch_axis, axis, head_axis),
            P(batch_axis, axis, head_axis),
            P(batch_axis, axis),
        ),
        out_specs=P(batch_axis, None, head_axis),
        # disable replication/varying-manual-axes checking (check_rep, or its
        # jax>=0.8 successor check_vma) — custom_vjp + collectives confuse
        # it. The transpose convention _sp_bwd compensates for is pinned by
        # the gradient-parity tests; see its docstring.
        **{check_kw: False},
    )(q, k, v, bias)

