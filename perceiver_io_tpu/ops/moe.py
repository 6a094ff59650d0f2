"""A routed expert layer that is told which experts it holds.

The layer routes every token over ALL ``num_experts`` experts (sigmoid
scores in float32, the ``top_k`` largest of ``score + selection bias``, gates
``scale * score / sum of the selected scores``: the aux-loss-free "noaux_tc"
router of the DeepSeek-V3 family) and computes the terms of the experts
``[expert_offset, expert_offset + experts_held)`` only. With all experts held
it is the whole layer; with a share it is what one expert-parallel rank
computes, and what the absent experts would add is left out. Nothing stands
in for the other ranks or for the exchange with them.

No assignment is ever dropped and there is no capacity factor: the row buffer
is sized for the worst routing (every token choosing ``min(top_k,
experts_held)`` experts held here), each expert's rows are padded up to whole
tiles of ``tile_rows``, and the grouped matmul (``ops/pallas_grouped_matmul``)
skips the tiles no expert owns. Moving rows is gathers in both directions
(``_gather_tokens`` / ``_gather_buffer``: the transpose of a gather by a
one-to-one map is the gather by its inverse, which XLA cannot know and a
``custom_vjp`` can say), never a scatter-add.

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
    """Where each assignment of a held expert sits in the row buffer."""
    tile_group: Array  # (tiles,) int32: the tile's local expert, ``held`` past the last
    source: Array      # (rows,) int32: the assignment (token * top_k + slot) a buffer row holds
    filled: Array      # (rows,) bool: the row holds one (the rest is padding)
    dest: Array        # (N * top_k,) int32: the buffer row of an assignment
    local: Array       # (N * top_k,) bool: its expert is held here
    sizes: Array       # (held,) int32: assignments per held expert


def plan_dispatch(local_expert: Array, held: int, tile_rows: int) -> Plan:
    """``local_expert`` (A,) int32: an assignment's expert as an index into
    the held ones, or ``held`` if it is not held here. Integer work on A
    elements: two sorts and a few small gathers."""
    count = local_expert.shape[0]
    order = jnp.argsort(local_expert, stable=True)  # sorted position -> assignment
    position = jnp.argsort(order)                   # assignment -> sorted position
    sizes = jnp.sum(local_expert[:, None] == jnp.arange(held, dtype=jnp.int32), axis=0,
                    dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    tiles_of = (sizes + tile_rows - 1) // tile_rows
    tile_ends = jnp.cumsum(tiles_of)
    row_starts = (tile_ends - tiles_of) * tile_rows
    # every routing fits: sum of ceil(size / tile) <= count / tile + held
    tiles = -(-count // tile_rows) + held
    tile_group = jnp.searchsorted(
        tile_ends, jnp.arange(tiles, dtype=jnp.int32), side="right").astype(jnp.int32)

    rows = jnp.arange(tiles * tile_rows, dtype=jnp.int32)
    group = jnp.minimum(tile_group[rows // tile_rows], held - 1)
    within = rows - row_starts[group]
    filled = (tile_group[rows // tile_rows] < held) & (within < sizes[group])
    source = order[jnp.clip(starts[group] + within, 0, count - 1)]

    local = local_expert < held
    mine = jnp.minimum(local_expert, held - 1)
    dest = row_starts[mine] + position - starts[mine]
    return Plan(tile_group, jnp.where(filled, source, 0).astype(jnp.int32), filled,
                jnp.where(local, dest, 0).astype(jnp.int32), local, sizes)


def _take_rows(a: Array, index: Array) -> Array:
    return a.at[index].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_tokens(x: Array, plan: Plan, top_k: int) -> Array:
    """(N, D) tokens -> (rows, D) buffer: each filled row its token, padding 0."""
    return jnp.where(plan.filled[:, None], _take_rows(x, plan.source // top_k), 0)


def _gather_tokens_fwd(x, plan, top_k):
    return _gather_tokens(x, plan, top_k), (plan, x.shape[0])


def _gather_tokens_bwd(top_k, residuals, g):
    plan, tokens = residuals
    per_assignment = jnp.where(plan.local[:, None], _take_rows(g, plan.dest), 0)
    dx = per_assignment.reshape(tokens, top_k, g.shape[-1]).sum(axis=1, dtype=jnp.float32)
    return dx.astype(g.dtype), None


_gather_tokens.defvjp(_gather_tokens_fwd, _gather_tokens_bwd)


@jax.custom_vjp
def _gather_buffer(y: Array, plan: Plan) -> Array:
    """(rows, D) buffer -> (N * top_k, D): each held assignment its row, the others 0."""
    return jnp.where(plan.local[:, None], _take_rows(y, plan.dest), 0)


def _gather_buffer_fwd(y, plan):
    return _gather_buffer(y, plan), plan


def _gather_buffer_bwd(plan, g):
    return jnp.where(plan.filled[:, None], _take_rows(g, plan.source), 0), None


_gather_buffer.defvjp(_gather_buffer_fwd, _gather_buffer_bwd)


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


class MoELayer(nn.Module):
    """Router over ``num_experts``, the held experts' SwiGLUs of ``width``,
    and ``num_shared`` always-on shared experts (one SwiGLU of ``num_shared *
    width``). Returns ``(y, stats)``; ``stats`` are float32 scalars:
    ``load_max_over_mean`` (assignments of the busiest held expert over the
    mean of the held ones; 1 if none has any), ``local_assignment_pct`` (share of the N * top_k
    assignments computed here) and ``dropped_assignments`` (held assignments
    that got no buffer row: 0 by construction, counted all the same)."""

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
        n = x.shape[0]

        with jax.named_scope("moe/router"):
            router = Kernel((d, self.num_experts), name="router")()
            bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                              (self.num_experts,))
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
            routing = route(scores, bias, self.top_k, self.routed_scaling_factor,
                            self.norm_topk_prob)

        with jax.named_scope("moe/dispatch"):
            local_expert = routing.experts.reshape(-1) - self.expert_offset
            local_expert = jnp.where((local_expert >= 0) & (local_expert < held),
                                     local_expert, held)
            plan = plan_dispatch(local_expert, held, self.tile_rows)
            rows = _gather_tokens(x, plan, self.top_k)

        with jax.named_scope("moe/experts"):
            experts = {name: Kernel((held, *shape_), name=f"experts_{name}")()
                       for name, shape_ in (("gate", (d, self.width)), ("up", (d, self.width)),
                                            ("down", (self.width, d)))}
            impl = self.expert_impl
            if impl == "auto":
                impl = "pallas" if jax.default_backend() == "tpu" else "xla"
            product = grouped_matmul if impl == "pallas" else grouped_matmul_xla

            def matmul(a, w):
                return product(a, w.astype(self.dtype), plan.tile_group, self.tile_rows)

            out_rows = _swiglu(rows, experts["gate"], experts["up"], experts["down"], matmul)

        with jax.named_scope("moe/combine"):
            per_assignment = _gather_buffer(out_rows, plan).reshape(n, self.top_k, d)
            gates = jnp.where(plan.local.reshape(n, self.top_k), routing.gates, 0.0)
            y = jnp.einsum("nkd,nk->nd", per_assignment, gates.astype(self.dtype),
                           preferred_element_type=jnp.float32).astype(self.dtype)

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
            "local_assignment_pct": 100.0 * assigned / (n * self.top_k),
            "dropped_assignments": assigned - jnp.sum(plan.filled, dtype=jnp.float32),
        }
        return y.reshape(shape), stats
