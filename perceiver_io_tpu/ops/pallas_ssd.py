"""The Mamba-2 chunked scan as a pair of Pallas kernels (TPU).

What ``ops/mamba2.ssd_scan`` computes in XLA einsums (its module docstring has
the four steps), computed a chunk and a group at a time with every large
intermediate in VMEM: the decay matrix ``L = exp(cs_t - cs_s)`` (masked BEFORE
the ``exp``), ``C B^T``, ``Delta x``, ``x_end`` and the state never reach HBM
in the forward pass, and the backward pass rebuilds them there. The precisions
are the einsum form's: contractions in the operands' dtype with float32
accumulation; running sums, ``exp``, the state and its carry float32.

Grid: (row, group, chunk), the chunk axis sequential. One grid step holds the
``J = H / G`` heads that share a group's ``B`` and ``C``, so ``C B^T`` is
computed once for them. ``x`` (B, T, H P) is read in blocks of (chunk, J P),
``B`` and ``C`` (B, T, G N) in blocks of (chunk, N) picked by the group: the
layouts the mixer has, no transpose to heads-major. What the kernels need a
token and head is made OUTSIDE them from ``Delta`` (B, T, H), 64 times smaller
than ``x`` (``_operands``): the running sum ``cs`` of ``Delta A`` inside each
chunk, a token a lane (B, H, T), and ``cs`` and ``Delta`` of a group's heads
as columns, a token a sublane (B, G, T, 128), which scale rows of ``x``. In
the kernel the running sum (seven dependent rotates) and the transpose to
columns were a chain of 1.2 us that every grid step waited for before its
first product: 0.6 of the forward kernel's 1.07 ms a layer (PERF.md section 6,
PR 39).

- **Forward** (``ssd_scan_fwd``). The state ENTERING the chunk lives in a
  float32 scratch, transposed, (N, J P): the carry between chunks is the
  recurrence ``S <- exp(cs_last) S + B^T (exp(cs_last - cs) Delta x)`` itself,
  which replaces the einsum form's ``chunks x chunks`` product. The elementwise
  work runs on slabs of 128 lanes (two heads of 64 side by side, ``_slab``);
  the within-chunk product runs a head at a time against the whole slab and
  keeps the head's lanes: at a head depth of 64 the MXU's pass is 128 wide
  either way.
- **Backward** (``ssd_scan_bwd``). Chunks in REVERSE order, the cotangent of
  the state that LEAVES a chunk in float32 scratch; a step rebuilds ``L`` and
  ``C B^T`` in VMEM and emits ``dx``, ``dB`` and ``dC`` (summed over the
  group's heads in the kernel), the cotangents of ``cs`` and of ``Delta`` as
  columns (``dA`` and the running sum's transpose are reduced outside, in
  float32), and ``D``'s partial sums (float32 products of ``dy`` and ``x``,
  summed over the tokens in an output block that stays in VMEM over the
  chunks). The cotangent of ``cs`` needs no pass over ``dL``: ``sum_s dM_ts
  M_ts = <dy_t, y_t>`` and ``sum_t dM_ts M_ts = <Delta x_s, d(Delta x)_s>``, a
  token's inner products.
- The states entering each chunk, which the backward needs, are written by the
  ``custom_vjp``'s forward (``state_bytes`` of ``ops/mamba2.py``: 134 MB a row
  and layer at the published sizes). A sweep of the states alone in the
  backward costs a whole forward kernel's time and lost by 4.1 ms a step
  (PERF.md section 6, PR 39).

A row that is no multiple of the chunk is padded with ``Delta = 0`` tokens, as
in the einsum form. ``interpret=True`` runs both kernels on a CPU (slow: for
tests).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_io_tpu.ops.pallas_attention import _dot

Array = jax.Array

# ``name=`` of the two pallas_calls: what a device trace calls them
KERNEL_FWD = "ssd_scan_fwd"
KERNEL_BWD = "ssd_scan_bwd"

_LANES = 128
_SUBLANES = 8


def _slab(heads: int, head_dim: int) -> int:
    """Heads whose channels the elementwise work takes side by side: as many
    of a group's ``heads`` as fill 128 lanes."""
    side = max(1, _LANES // head_dim)
    while heads % side:
        side -= 1
    return side


def kernel_fits(heads: int, head_dim: int, groups: int, state: int, chunk: int,
                tokens: int) -> bool:
    """Whether the chip's compiler takes the kernels' blocks: a group's
    channels, the state and a chunk of the row (the whole row, if shorter)
    whole lane tiles, a group's heads whole sublane tiles."""
    per_group = heads // groups
    return (per_group * head_dim % _LANES == 0 and state % _LANES == 0
            and per_group % _SUBLANES == 0 and min(chunk, tokens) % _LANES == 0)


def _by_head(cols: Array, first: int, slab: int, p: int) -> Array:
    """(q, slab * p): column ``first + i`` of ``cols`` over the lanes of the
    slab's head ``i``."""
    q = cols.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, slab * p), 1)
    out = jnp.broadcast_to(cols[:, first:first + 1], lane.shape)
    for i in range(1, slab):
        out = jnp.where(lane >= i * p, cols[:, first + i:first + i + 1], out)
    return out


def _masked_decay(cols: Array, cs: Array, h: int, lower: Array) -> Array:
    """``L`` of head ``h``: ``exp(cs_t - cs_s)`` where ``s <= t``, else 0; the
    mask first, so that a positive exponent never reaches the ``exp``."""
    return jnp.exp(jnp.where(lower, cols[:, h:h + 1] - cs[h:h + 1, :], -jnp.inf))


def _lower(q: int) -> Array:
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(x_ref, b_ref, c_ref, cs_ref, cols_ref, d_ref, y_ref, *rest,
                p: int, slab: int):
    """``rest``: the entering states' output, if asked for, and the scratch."""
    st_ref = rest[-1]  # (N, J P) float32: the state entering the chunk, transposed

    @pl.when(pl.program_id(2) == 0)
    def _row_start():
        st_ref[...] = jnp.zeros_like(st_ref)

    if len(rest) == 2:
        rest[0][0, 0] = st_ref[...]
    q, j, w = x_ref.shape[1], cs_ref.shape[1], slab * p
    b, c = b_ref[0], c_ref[0]
    dtype = b.dtype
    cs, cols = cs_ref[0], cols_ref[0, 0]
    cb = _dot(c, b, (1, 1))  # (q, q): C_t . B_s
    lower = _lower(q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1)
    for k in range(j // slab):
        lanes = slice(k * w, (k + 1) * w)
        h0 = k * slab
        x32 = x_ref[0, :, lanes].astype(jnp.float32)
        cs_s = _by_head(cols, h0, slab, p)
        last_s = cs_s[q - 1:]  # (1, w): the chunk's whole log decay, a head
        dx = x32 * _by_head(cols, j + h0, slab, p)
        dxb = dx.astype(dtype)
        st = st_ref[:, lanes]
        y = jnp.exp(cs_s) * _dot(c, st.astype(dtype), (1, 0))
        for i in range(slab):
            m = (_masked_decay(cols, cs, h0 + i, lower) * cb).astype(dtype)
            mine = _dot(m, dxb, (1, 0))
            within = mine if i == 0 else jnp.where(lane >= i * p, mine, within)
        y_ref[0, :, lanes] = (y + within + d_ref[:, lanes] * x32).astype(y_ref.dtype)
        to_end = (dx * jnp.exp(last_s - cs_s)).astype(dtype)
        st_ref[:, lanes] = jnp.exp(last_s) * st + _dot(b, to_end, (0, 0))


def _bwd_kernel(x_ref, b_ref, c_ref, cs_ref, cols_ref, d_ref, g_ref, states_ref,
                dx_ref, db_ref, dc_ref, dcols_ref, dd_ref, dst_ref, *, p: int, slab: int):
    # dst_ref (N, J P) float32: the cotangent of the state LEAVING the chunk

    @pl.when(pl.program_id(2) == 0)  # the row's last chunk
    def _row_end():
        dst_ref[...] = jnp.zeros_like(dst_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    q, j, w = x_ref.shape[1], cs_ref.shape[1], slab * p
    b, c = b_ref[0], c_ref[0]
    dtype = b.dtype
    cs, cols = cs_ref[0], cols_ref[0, 0]
    cb = _dot(c, b, (1, 1))
    lower = _lower(q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1)
    of_head = [jnp.logical_and(lane >= i * p, lane < (i + 1) * p) for i in range(slab)]
    out_lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    last_token = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    out_cols = jnp.zeros(cols.shape, jnp.float32)  # column h: d cs, column J + h: d Delta
    dcb = jnp.zeros((q, q), jnp.float32)
    db = jnp.zeros(b.shape, jnp.float32)
    dc = jnp.zeros(c.shape, jnp.float32)
    for k in range(j // slab):
        lanes = slice(k * w, (k + 1) * w)
        h0 = k * slab
        x32 = x_ref[0, :, lanes].astype(jnp.float32)
        g = g_ref[0, :, lanes]
        g32 = g.astype(jnp.float32)
        cs_s, delta_s = _by_head(cols, h0, slab, p), _by_head(cols, j + h0, slab, p)
        last_s = cs_s[q - 1:]
        decay, to_end = jnp.exp(cs_s), jnp.exp(last_s - cs_s)
        whole = jnp.exp(last_s)  # (1, w): the chunk's whole decay, a head
        dx = x32 * delta_s
        dxb = dx.astype(dtype)
        x_end = dx * to_end
        st, dst = states_ref[0, 0, :, lanes], dst_ref[:, lanes]
        stb, dstb = st.astype(dtype), dst.astype(dtype)

        dd_ref[0, :, lanes] += jnp.sum(g32 * x32, axis=0, keepdims=True)
        # what the entering state added to the chunk's tokens
        carried = _dot(c, stb, (1, 0))
        dk = (decay * g32).astype(dtype)
        dc += _dot(dk, stb, (1, 1))
        # the chunk's own part of the state that leaves it
        dx_end = _dot(b, dstb, (1, 0))
        db += _dot(x_end.astype(dtype), dstb, (1, 1))
        u = dx_end * x_end
        ends = (jnp.sum(u, axis=0, keepdims=True)
                + jnp.sum(dst * st, axis=0, keepdims=True) * whole)  # (1, w)
        dst_ref[:, lanes] = whole * dst + _dot(c, dk, (0, 0))
        # within the chunk, a head at a time
        for i, mine in enumerate(of_head):
            decay_m = _masked_decay(cols, cs, h0 + i, lower)
            m = (decay_m * cb).astype(dtype)
            y_i = _dot(m, dxb, (1, 0))
            d_i = _dot(m, g, (0, 0))
            dm = _dot(jnp.where(mine, g32, 0.0).astype(dtype), dxb, (1, 1))
            dcb += dm * decay_m
            within = y_i if i == 0 else jnp.where(mine, y_i, within)
            d_within = d_i if i == 0 else jnp.where(mine, d_i, d_within)
        d_dx = d_within + dx_end * to_end
        dx_ref[0, :, lanes] = (delta_s * d_dx + d_ref[:, lanes] * g32).astype(dx_ref.dtype)
        # d cs a token: <dy, y's scan part> - <Delta x, its cotangent within
        # the chunk> - u; the chunk's last token also takes what reaches cs_last
        r = g32 * (within + decay * carried) - dxb.astype(jnp.float32) * d_within - u
        d_delta = d_dx * x32
        for i, mine in enumerate(of_head):
            end = jnp.sum(jnp.where(mine[:1], ends, 0.0), axis=1, keepdims=True)
            d_cs = (jnp.sum(jnp.where(mine, r, 0.0), axis=1, keepdims=True)
                    + jnp.where(last_token, end, 0.0))
            out_cols = jnp.where(out_lane == h0 + i, d_cs, out_cols)
            out_cols = jnp.where(
                out_lane == j + h0 + i,
                jnp.sum(jnp.where(mine, d_delta, 0.0), axis=1, keepdims=True), out_cols)
    dcbb = dcb.astype(dtype)
    dc_ref[0] = (dc + _dot(dcbb, b, (1, 0))).astype(dc_ref.dtype)
    db_ref[0] = (db + _dot(dcbb, c, (0, 0))).astype(db_ref.dtype)
    dcols_ref[0, 0] = out_cols


class _Sizes(NamedTuple):
    """A call's static sizes: heads a group, a head's channels, the state, a
    chunk's tokens, the row's padding to whole chunks, its chunks, and the
    lanes of the per-token columns (``cs`` and ``Delta`` of a group's heads)."""
    j: int
    p: int
    n: int
    q: int
    pad: int
    chunks: int
    lanes: int


def _sizes(x, b, heads: int, groups: int, chunk: int) -> _Sizes:
    t = x.shape[1]
    q = min(chunk, t)
    pad = -t % q
    j = heads // groups
    return _Sizes(j, x.shape[2] // heads, b.shape[2] // groups, q, pad, (t + pad) // q,
                  -(-2 * j // _LANES) * _LANES)


def _specs(sz: _Sizes, chunk_of):
    """Block specs for a grid of (row, group, step), ``chunk_of`` the step's
    chunk: of ``x``-like and ``B``-like arrays, of the running sums a token a
    lane (B, H, T) and the columns a token a sublane (B, G, T, lanes), of ``D``
    and of the states."""
    j, p, n, q = sz.j, sz.p, sz.n, sz.q
    return dict(
        x=pl.BlockSpec((1, q, j * p), lambda r, g, z: (r, chunk_of(z), g)),
        b=pl.BlockSpec((1, q, n), lambda r, g, z: (r, chunk_of(z), g)),
        cs=pl.BlockSpec((1, j, q), lambda r, g, z: (r, g, chunk_of(z))),
        cols=pl.BlockSpec((1, 1, q, sz.lanes), lambda r, g, z: (r, g, chunk_of(z), 0)),
        d=pl.BlockSpec((1, j * p), lambda r, g, z: (0, g)),
        states=pl.BlockSpec((1, 1, n, j * p), lambda r, g, z: (r, chunk_of(z), 0, g)))


def _by_group(v, sz: _Sizes):
    """(B, T, H) -> (B, G, T, J): a group's heads side by side."""
    rows, t, h = v.shape
    return v.reshape(rows, t, h // sz.j, sz.j).transpose(0, 2, 1, 3)


def _operands(delta, a, d, sz: _Sizes, *per_token):
    """What the kernels read a token and head, made here from ``Delta`` (B, T,
    H), 64 times smaller than ``x``: ``cs``, the running sum of ``Delta A``
    inside each chunk (padded with ``Delta = 0`` tokens to whole chunks), a
    token a lane (B, H, T); ``cs`` and ``Delta`` of a group's heads as columns,
    a token a sublane (B, G, T, lanes), which scale rows of ``x``; ``D`` a
    channel a lane; then ``per_token``, padded alike."""
    pad = ((0, 0), (0, sz.pad), (0, 0))
    delta = jnp.pad(delta, pad)
    rows, t, h = delta.shape
    cs = jnp.cumsum((delta * a).reshape(rows, sz.chunks, sz.q, h), axis=2).reshape(rows, t, h)
    cols = jnp.concatenate([_by_group(cs, sz), _by_group(delta, sz)], axis=-1)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, sz.lanes - 2 * sz.j),))
    d_row = jnp.repeat(d.astype(jnp.float32), sz.p)[None]
    return (delta, jnp.swapaxes(cs, 1, 2), cols, d_row,
            *(jnp.pad(v, pad) if sz.pad else v for v in per_token))


_SEMANTICS = pltpu.CompilerParams(
    # rows and groups are independent; the chunks carry the state or its cotangent
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("heads", "groups", "chunk", "interpret",
                                             "with_states"))
def _forward(x, delta, a, b, c, d, heads: int, groups: int, chunk: int, interpret: bool,
             with_states: bool = False):
    """``y`` (B, T, H P) and, if asked for, the states entering each chunk (B,
    chunks, N, H P), float32."""
    rows, t, inner = x.shape
    sz = _sizes(x, b, heads, groups, chunk)
    _, cs, cols, d_row, x, b, c = _operands(delta, a, d, sz, x, b, c)
    spec = _specs(sz, lambda z: z)
    out_shape, out_specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)], [spec["x"]]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((rows, sz.chunks, sz.n, inner), jnp.float32))
        out_specs.append(spec["states"])
    y, *states = pl.pallas_call(
        functools.partial(_fwd_kernel, p=sz.p, slab=_slab(sz.j, sz.p)),
        grid=(rows, groups, sz.chunks),
        in_specs=[spec[k] for k in ("x", "b", "b", "cs", "cols", "d")],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((sz.n, sz.j * sz.p), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=KERNEL_FWD,
    )(x, b, c, cs, cols, d_row)
    return [y[:, :t], *states]


@functools.partial(jax.jit, static_argnames=("heads", "groups", "chunk", "interpret"))
def _backward(x, delta, a, b, c, d, states, g, heads: int, groups: int, chunk: int,
              interpret: bool):
    rows, t, inner = x.shape
    sz = _sizes(x, b, heads, groups, chunk)
    delta_p, cs, cols, d_row, x_p, b_p, c_p, g = _operands(delta, a, d, sz, x, b, c, g)
    spec = _specs(sz, lambda z: sz.chunks - 1 - z)
    dx, db, dc, dcols, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, p=sz.p, slab=_slab(sz.j, sz.p)),
        grid=(rows, groups, sz.chunks),
        in_specs=[spec[k] for k in ("x", "b", "b", "cs", "cols", "d", "x", "states")],
        out_specs=[spec["x"], spec["b"], spec["b"], spec["cols"],
                   pl.BlockSpec((1, 1, sz.j * sz.p), lambda r, g, z: (r, 0, g))],
        out_shape=[jax.ShapeDtypeStruct(x_p.shape, x.dtype),
                   jax.ShapeDtypeStruct(b_p.shape, b.dtype),
                   jax.ShapeDtypeStruct(c_p.shape, c.dtype),
                   jax.ShapeDtypeStruct(cols.shape, jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1, inner), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((sz.n, sz.j * sz.p), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name=KERNEL_BWD,
    )(x_p, b_p, c_p, cs, cols, d_row, g, states)

    def by_token(first):  # (B, G, T, J) columns from ``first`` -> (B, chunks, q, H)
        v = dcols[..., first:first + sz.j].transpose(0, 2, 1, 3)
        return v.reshape(rows, sz.chunks, sz.q, heads)

    # cs is a running sum of Delta A inside a chunk: its cotangent sums from the chunk's end
    d_log_decay = jnp.flip(jnp.cumsum(jnp.flip(by_token(0), 2), axis=2), 2)
    d_delta = (by_token(sz.j) + d_log_decay * a).reshape(rows, -1, heads)[:, :t]
    d_a = jnp.sum(d_log_decay.reshape(delta_p.shape) * delta_p, axis=(0, 1))
    d_d = jnp.sum(dd.reshape(rows, heads, sz.p), axis=(0, 2))
    return dx[:, :t], d_delta, d_a, db[:, :t], dc[:, :t], d_d


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _ssd_scan(x, delta, a, b, c, d, heads, groups, chunk, interpret):
    return _forward(x, delta, a, b, c, d, heads, groups, chunk, interpret)[0]


def _ssd_scan_fwd(x, delta, a, b, c, d, heads, groups, chunk, interpret):
    y, states = _forward(x, delta, a, b, c, d, heads, groups, chunk, interpret, with_states=True)
    return y, (x, delta, a, b, c, d, states)


def _ssd_scan_bwd(heads, groups, chunk, interpret, residuals, g):
    x, delta, a, b, c, d, states = residuals
    dx, d_delta, d_a, db, dc, d_d = _backward(
        x, delta, a, b, c, d, states, g, heads, groups, chunk, interpret)
    return dx, d_delta, d_a.astype(a.dtype), db, dc, d_d.astype(d.dtype)


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


def ssd_scan(x: Array, delta: Array, a: Array, b: Array, c: Array, d: Array, heads: int,
             groups: int, chunk: int, interpret: Optional[bool] = None) -> Array:
    """``y`` (B, T, H P) of the recurrence in ``ops/mamba2.py``'s docstring.

    ``x`` (B, T, H P) and ``b``, ``c`` (B, T, G N) in the compute dtype, as the
    mixer's convolution leaves them; ``delta`` (B, T, H), ``a`` (H,) (negative)
    and ``d`` (H,) float32. Differentiable in all six. Off a TPU the kernels
    run in interpret mode."""
    if x.shape[2] % heads or b.shape[2] % groups or heads % groups or b.shape != c.shape:
        raise ValueError(f"ssd_scan shapes {x.shape=} {b.shape=} {c.shape=} {heads=} {groups=}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ssd_scan(x, delta.astype(jnp.float32), a.astype(jnp.float32), b, c, d, heads, groups,
                     chunk, interpret)
