"""A routed expert layer that is told which experts it holds.

The layer routes every token over ALL ``num_experts`` experts (sigmoid
scores in float32, the ``top_k`` largest of ``score + selection bias``, gates
``scale * score / sum of the selected scores``: the aux-loss-free "noaux_tc"
router of the DeepSeek-V3 family) and computes the terms of the experts
``[expert_offset, expert_offset + experts_held)`` only. With all experts held
it is the whole layer; with a share it is what one expert-parallel rank
computes, and what the absent experts would add is left out. Nothing stands
in for the other ranks or for the exchange with them.

No assignment is ever dropped and there is no capacity factor that drops: an
expert's rows are padded up to whole tiles of ``tile_rows`` in a row buffer,
and the grouped matmul (``ops/pallas_grouped_matmul``) skips the tiles no
expert owns. The buffer's size follows the load. A router that favours no
expert sends ``tokens * top_k * held / num_experts`` rows here; the layer
sizes its buffer for ``CAPACITY_FACTOR`` times that (``capacity_tiles``, from
its own static shapes), counts the tiles the step's routing needs, and a
``lax.cond`` runs the routed part over that buffer where the routing fits and
over the worst-case buffer (every token choosing ``min(top_k, experts_held)``
experts held here: ``worst_case_tiles``) where it does not. Where the
capacity is no smaller than the worst case (all experts held, or a large
share) there is one path and no branch.

Moving rows. Over the bounded buffer everything is done in buffer space: a row
knows its token and its gate, tokens become rows by a gather of buffer rows
and rows become tokens by a float32 sum by token (a scatter-add), which are
each other's transposes; no pass is as long as ``tokens * top_k``. Over the
worst-case buffer it is gathers in both directions (``_gather_tokens`` /
``_gather_buffer``: the transpose of a gather by a one-to-one map is the
gather by its inverse, which XLA cannot know and a ``custom_vjp`` can say).

Scopes (``jax.named_scope``) the device trace is cut by: ``moe/router``,
``moe/dispatch``, ``moe/experts``, ``moe/combine``, ``moe/shared_expert``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu.ops.attention import torch_linear_kernel_init
from perceiver_io_tpu.ops.pallas_grouped_matmul import grouped_matmul, grouped_matmul_xla

Array = jax.Array

TILE_ROWS = 256  # rows of one tile of the grouped matmul: an expert's rows are padded to these
# the bounded buffer holds this many times the rows of a router that favours
# no expert: a share trained alone was seen to drift from 1x to 2.6-2.9x
CAPACITY_FACTOR = 4


def worst_case_tiles(assignments: int, held: int, tile_rows: int) -> int:
    """Tiles that hold ANY routing of ``assignments`` over ``held`` experts:
    sum of ceil(size / tile) <= assignments / tile + held."""
    return -(-assignments // tile_rows) + held


def capacity_tiles(tokens: int, top_k: int, held: int, num_experts: int, tile_rows: int) -> int:
    """Tiles of the buffer the common path runs over: ``CAPACITY_FACTOR``
    times the rows a router that favours no expert sends to ``held`` of
    ``num_experts``, and ``held`` more (an expert's last tile is part
    padding); the worst case where that is no larger."""
    bounded = -(-CAPACITY_FACTOR * tokens * top_k * held // (num_experts * tile_rows)) + held
    return min(bounded, worst_case_tiles(tokens * top_k, held, tile_rows))


class Routing(NamedTuple):
    experts: Array  # (N, top_k) int32: the experts each token selected
    gates: Array    # (N, top_k) float32: their weights, normalised over ALL selected


def route(scores: Array, selection_bias: Array, top_k: int, scale: float,
          normalize: bool = True) -> Routing:
    """``scores`` (N, E) float32 in (0, 1). The bias decides the selection
    and nothing else: it carries no gradient and is not in the gates."""
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(selection_bias), top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return Routing(experts.astype(jnp.int32), scale * picked)


class Plan(NamedTuple):
    """The assignments sorted by held expert and each expert's run of tiles:
    what a row buffer of any length is laid out from."""
    order: Array       # (N * top_k,) int32: sorted position -> assignment (token * top_k + slot)
    sizes: Array       # (held,) int32: assignments per held expert
    starts: Array      # (held,) int32: an expert's first sorted position
    first_tile: Array  # (held,) int32: an expert's first tile
    tile_group: Array  # (worst-case tiles,) int32: the tile's local expert, ``held`` past the last
    needed: Array      # () int32: tiles the routing fills, sum of ceil(size / tile_rows)


class Buffer(NamedTuple):
    """The rows of a buffer's first ``tiles`` tiles."""
    tile_group: Array  # (tiles,) int32
    source: Array      # (rows,) int32: the assignment a buffer row holds
    filled: Array      # (rows,) bool: the row holds one (the rest is padding)


class Inverse(NamedTuple):
    """Where each assignment sits in the worst-case buffer."""
    dest: Array   # (N * top_k,) int32: the buffer row of an assignment
    local: Array  # (N * top_k,) bool: its expert is held here


def plan_dispatch(local_expert: Array, held: int, tile_rows: int) -> Plan:
    """``local_expert`` (A,) int32: an assignment's expert as an index into
    the held ones, or ``held`` if it is not held here. Integer work on A
    elements: one sort and a few small sums."""
    order = jnp.argsort(local_expert, stable=True)
    sizes = jnp.sum(local_expert[:, None] == jnp.arange(held, dtype=jnp.int32), axis=0,
                    dtype=jnp.int32)
    tiles_of = (sizes + tile_rows - 1) // tile_rows
    tile_ends = jnp.cumsum(tiles_of)
    tiles = worst_case_tiles(local_expert.shape[0], held, tile_rows)
    tile_group = jnp.searchsorted(
        tile_ends, jnp.arange(tiles, dtype=jnp.int32), side="right").astype(jnp.int32)
    return Plan(order.astype(jnp.int32), sizes, jnp.cumsum(sizes) - sizes, tile_ends - tiles_of,
                tile_group, tile_ends[-1])


def buffer_rows(plan: Plan, tiles: int, tile_rows: int) -> Buffer:
    """The first ``tiles`` tiles of the buffer, which hold every held
    assignment where ``tiles >= plan.needed``."""
    held, count = plan.sizes.shape[0], plan.order.shape[0]
    tile_group = plan.tile_group[:tiles]
    group = jnp.minimum(tile_group, held - 1)
    within = ((jnp.arange(tiles, dtype=jnp.int32) - plan.first_tile[group]) * tile_rows)[:, None] \
        + jnp.arange(tile_rows, dtype=jnp.int32)
    filled = (tile_group < held)[:, None] & (within < plan.sizes[group][:, None])
    position = jnp.clip(plan.starts[group][:, None] + within, 0, count - 1)
    source = jnp.where(filled, _take_rows(plan.order, position), 0)
    return Buffer(tile_group, source.reshape(-1), filled.reshape(-1))


def invert(plan: Plan, local_expert: Array, tile_rows: int) -> Inverse:
    """A second sort: the worst-case buffer alone goes from rows back to
    assignments."""
    held = plan.sizes.shape[0]
    position = jnp.argsort(plan.order)  # assignment -> sorted position
    local = local_expert < held
    mine = jnp.minimum(local_expert, held - 1)
    dest = plan.first_tile[mine] * tile_rows + position - plan.starts[mine]
    return Inverse(jnp.where(local, dest, 0).astype(jnp.int32), local)


def _take_rows(a: Array, index: Array) -> Array:
    return a.at[index].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_tokens(x: Array, buffer: Buffer, inverse: Inverse, top_k: int) -> Array:
    """(N, D) tokens -> (rows, D) buffer: each filled row its token, padding 0."""
    return jnp.where(buffer.filled[:, None], _take_rows(x, buffer.source // top_k), 0)


def _gather_tokens_fwd(x, buffer, inverse, top_k):
    return _gather_tokens(x, buffer, inverse, top_k), (inverse, x.shape[0])


def _gather_tokens_bwd(top_k, residuals, g):
    inverse, tokens = residuals
    per_assignment = jnp.where(inverse.local[:, None], _take_rows(g, inverse.dest), 0)
    dx = per_assignment.reshape(tokens, top_k, g.shape[-1]).sum(axis=1, dtype=jnp.float32)
    return dx.astype(g.dtype), None, None


_gather_tokens.defvjp(_gather_tokens_fwd, _gather_tokens_bwd)


@jax.custom_vjp
def _gather_buffer(y: Array, buffer: Buffer, inverse: Inverse) -> Array:
    """(rows, D) buffer -> (N * top_k, D): each held assignment its row, the others 0."""
    return jnp.where(inverse.local[:, None], _take_rows(y, inverse.dest), 0)


def _gather_buffer_fwd(y, buffer, inverse):
    return _gather_buffer(y, buffer, inverse), buffer


def _gather_buffer_bwd(buffer, g):
    return jnp.where(buffer.filled[:, None], _take_rows(g, buffer.source), 0), None, None


_gather_buffer.defvjp(_gather_buffer_fwd, _gather_buffer_bwd)


def _sum_by_token(rows: Array, token: Array, tokens: int) -> Array:
    """(rows, D) float32, padding 0 -> (N, D) float32: the sum of each
    token's rows. Its transpose is the plain gather ``g[token]``."""
    return jnp.zeros((tokens, rows.shape[-1]), jnp.float32).at[token].add(
        rows, mode="promise_in_bounds")


@jax.custom_vjp
def _rows_of_tokens(x: Array, token: Array, filled: Array) -> Array:
    """(N, D) tokens -> (rows, D) buffer, as ``_gather_tokens``, from the
    buffer's side alone: a token's cotangent is the float32 sum of its rows'."""
    return jnp.where(filled[:, None], _take_rows(x, token), 0)


def _rows_of_tokens_fwd(x, token, filled):
    return _rows_of_tokens(x, token, filled), (token, filled, x.shape[0])


def _rows_of_tokens_bwd(residuals, g):
    token, filled, tokens = residuals
    rows = jnp.where(filled[:, None], g, 0).astype(jnp.float32)
    return _sum_by_token(rows, token, tokens).astype(g.dtype), None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


def _swiglu(x: Array, gate: Array, up: Array, down: Array, matmul) -> Array:
    return matmul(jax.nn.silu(matmul(x, gate)) * matmul(x, up), down)


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``, no biases."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=torch_linear_kernel_init, name=name)

        hidden = jax.nn.silu(dense("gate", self.width)(x)) * dense("up", self.width)(x)
        return dense("down", x.shape[-1])(hidden)


class Kernel(nn.Module):
    """One ``kernel`` leaf of a given shape under the module's name."""

    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self) -> Array:
        return self.param("kernel", torch_linear_kernel_init, self.shape)


def _either(fits: Array, common, fallback, x, gates, kernels, *integers):
    """``common(x, gates, kernels, *integers)`` where ``fits``, else ``fallback``
    of the same, differentiable in ``x``, ``gates`` and ``kernels``. One
    ``lax.cond`` forward and one backward, each branch of the second
    recomputing its own forward: a ``cond`` left to autodiff hands the
    backward pass the residuals of BOTH branches, the untaken one's (the
    worst-case buffers) written out as zeros."""

    @jax.custom_vjp
    def run(fits, x, gates, kernels, *integers):
        return jax.lax.cond(fits, common, fallback, x, gates, kernels, *integers)

    def pull(branch):
        def back(cotangent, x, gates, kernels, *integers):
            return jax.vjp(lambda *primal: branch(*primal, *integers), x, gates, kernels)[1](cotangent)
        return back

    def bwd(operands, cotangent):
        fits, *rest = operands
        grads = jax.lax.cond(fits, pull(common), pull(fallback), cotangent, *rest)
        return (None, *grads, *(None for _ in rest[3:]))

    run.defvjp(lambda *operands: (run(*operands), operands), bwd)
    return run(fits, x, gates, kernels, *integers)


class MoELayer(nn.Module):
    """Router over ``num_experts``, the held experts' SwiGLUs of ``width``,
    and ``num_shared`` always-on shared experts (one SwiGLU of ``num_shared *
    width``). The held experts' rows go through a buffer of ``capacity_tiles``
    tiles where the step's routing fits it and through the worst-case buffer
    where it does not (the module docstring). Returns ``(y, stats)``;
    ``stats`` are float32 scalars: ``load_max_over_mean`` (assignments of the
    busiest held expert over the mean of the held ones; 1 if none has any),
    ``local_assignment_pct`` (share of the N * top_k assignments computed
    here), ``dropped_assignments`` (held assignments that got no buffer row:
    0 by construction, counted all the same) and ``bounded_path_pct`` (100
    where the routing fitted the bounded buffer or the layer has one path, 0
    where the step took the worst-case buffer)."""

    num_experts: int
    top_k: int
    width: int
    num_shared: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    experts_held: Optional[int] = None
    expert_offset: int = 0
    tile_rows: int = TILE_ROWS
    expert_impl: str = "auto"  # 'pallas' | 'xla' | 'auto' (the kernel on a TPU)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Tuple[Array, dict]:
        held = self.num_experts if self.experts_held is None else self.experts_held
        if not 0 < held <= self.num_experts - self.expert_offset:
            raise ValueError(f"experts_held {held} at offset {self.expert_offset} "
                             f"of {self.num_experts} experts")
        shape, d = x.shape, x.shape[-1]
        x = x.reshape(-1, d)
        n, top_k, tile_rows = x.shape[0], self.top_k, self.tile_rows
        worst = worst_case_tiles(n * top_k, held, tile_rows)
        capacity = capacity_tiles(n, top_k, held, self.num_experts, tile_rows)

        with jax.named_scope("moe/router"):
            router = Kernel((d, self.num_experts), name="router")()
            bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                              (self.num_experts,))
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
            routing = route(scores, bias, top_k, self.routed_scaling_factor,
                            self.norm_topk_prob)

        with jax.named_scope("moe/dispatch"):
            local_expert = routing.experts.reshape(-1) - self.expert_offset
            local_expert = jnp.where((local_expert >= 0) & (local_expert < held),
                                     local_expert, held)
            plan = plan_dispatch(local_expert, held, tile_rows)

        kernels = tuple(Kernel((held, *shape_), name=f"experts_{name}")()
                        for name, shape_ in (("gate", (d, self.width)), ("up", (d, self.width)),
                                             ("down", (self.width, d))))
        impl = self.expert_impl
        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        product = grouped_matmul if impl == "pallas" else grouped_matmul_xla

        def experts(rows, kernels, tile_group):
            with jax.named_scope("moe/experts"):
                return _swiglu(rows, *kernels, lambda a, w: product(
                    a, w.astype(self.dtype), tile_group, tile_rows))

        def over_worst_case(x, gates, kernels, plan, local_expert):
            """``(y, rows filled)`` by gathers in both directions over a
            buffer that holds any routing."""
            with jax.named_scope("moe/dispatch"):
                buffer = buffer_rows(plan, worst, tile_rows)
                inverse = invert(plan, local_expert, tile_rows)
                rows = _gather_tokens(x, buffer, inverse, top_k)
            out_rows = experts(rows, kernels, buffer.tile_group)
            with jax.named_scope("moe/combine"):
                per_assignment = _gather_buffer(out_rows, buffer, inverse).reshape(n, top_k, d)
                gates = jnp.where(inverse.local.reshape(n, top_k), gates, 0.0)
                y = jnp.einsum("nkd,nk->nd", per_assignment, gates.astype(self.dtype),
                               preferred_element_type=jnp.float32).astype(self.dtype)
            return y, jnp.sum(buffer.filled, dtype=jnp.float32)

        def over_capacity(x, gates, kernels, plan, local_expert):
            """The same from the buffer's side alone, where ``plan.needed <=
            capacity``: no pass is longer than the bounded buffer."""
            del local_expert
            with jax.named_scope("moe/dispatch"):
                buffer = buffer_rows(plan, capacity, tile_rows)
                token = buffer.source // top_k
                rows = _rows_of_tokens(x, token, buffer.filled)
            out_rows = experts(rows, kernels, buffer.tile_group)
            with jax.named_scope("moe/combine"):
                # padding rows are zero rows, whatever gate they read
                gate = _take_rows(gates.reshape(-1), buffer.source).astype(self.dtype)
                weighted = out_rows.astype(jnp.float32) * gate[:, None]
                y = _sum_by_token(weighted, token, n).astype(self.dtype)
            return y, jnp.sum(buffer.filled, dtype=jnp.float32)

        operands = (x, routing.gates, kernels, plan, local_expert)
        if capacity >= worst:
            fits = jnp.bool_(True)
            y, filled = over_worst_case(*operands)
        else:
            fits = plan.needed <= capacity
            y, filled = _either(fits, over_capacity, over_worst_case, *operands)

        if self.num_shared:
            with jax.named_scope("moe/shared_expert"):
                y = y + SwiGLU(self.num_shared * self.width, dtype=self.dtype,
                               name="shared_expert")(x)

        sizes = plan.sizes.astype(jnp.float32)
        assigned = jnp.sum(sizes)
        stats = {
            # 1 where no assignment fell to a held expert: nothing is out of balance
            "load_max_over_mean": jnp.where(
                assigned > 0, jnp.max(sizes) * held / jnp.maximum(assigned, 1.0), 1.0),
            "local_assignment_pct": 100.0 * assigned / (n * top_k),
            "dropped_assignments": assigned - filled,
            "bounded_path_pct": jnp.where(fits, 100.0, 0.0),
        }
        return y.reshape(shape), stats
