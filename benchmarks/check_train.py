"""The comparison that decides ``correct`` for a training cell.

The program's side is read from the timed object itself: the Trainer that
the window drives runs its first three optimizer steps through the window's
own call (``Trainer.fit``) and feed (the pooled loader), and after step 1 and
step 3 the benchmark reads norms off its state. The reference's side follows
the same three batches in float32 (``benchmarks/reference``), from weights,
an rng and batches that the benchmark made.

Numbers compared (each has a limit in ``benchmarks/limits/<cell>.json``;
how each was set is in PERF.md):

- ``loss1_gap``: |loss - ref| / |ref| of step 1.
- ``loss23_gap``: the larger such gap of steps 2 and 3.
- ``grad_gap``: worst leaf of | ||g|| - ||g_ref|| | over max(||g_ref|| of the
  leaf, of the median leaf): the first gradient as the optimizer got it,
  recovered from Adam's first moment after one step (mu = (1 - b1) g).
- ``delta_gap``: the same measure for ||p_3 - p_0||, the parameters' change
  after three steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (a leaf with no gradient to rounding moves
  under Adam by round-off alone).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import perceiver as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ADAM_B1 = 0.9
STEPS = 3


@jax.jit
def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_diff_norms(a, b) -> jax.Array:
    return leaf_norms(jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def first_moment(opt_state) -> Any:
    """Adam's ``mu`` out of an optax state, wherever the chain holds it."""
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0].mu


def reference_readings(task: Dict[str, Any], params0, rng, batches: List[dict],
                       arith: Optional[ref.Arith] = None,
                       half_batch: bool = False) -> Dict[str, Any]:
    """Follow the first ``STEPS`` optimizer steps in the reference.
    ``half_batch`` plants the fault "half of the batch left out, the mean
    taken over the rest" for the readings a limit is set against."""
    arith = arith or ref.F32
    grad_fn = ref.blocked_value_and_grad(task["ce_sum"](arith), task["block_rows"])
    opt = ref.Adam(params0, task["learning_rate"], task["weight_decay"])
    params, losses, grad_norms = params0, [], None
    for step in range(STEPS):
        batch, count = task["prepare"](batches[step], rng, step)
        if half_batch:
            half = len(next(iter(batch.values()))) // 2
            batch = {k: v[:half] for k, v in batch.items()}
            count = float((batch["labels"] != ref.IGNORE).sum()) if "labels" in batch else float(half)
        loss, grads = grad_fn(params, batch, count)
        losses.append(float(loss))
        if step == 0:
            grad_norms = np.asarray(leaf_norms(grads))
        params = opt.update(params, grads)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": np.asarray(leaf_diff_norms(params, params0))}


def _worst_leaf(got: np.ndarray, want: np.ndarray, keep: np.ndarray) -> float:
    floor = np.maximum(want, np.median(want))
    gaps = np.abs(got - want) / floor
    return float(np.max(gaps[keep]))


def compare(program: Dict[str, Any], reference: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared, from two sets of readings."""
    pl, rl = program["losses"], reference["losses"]
    rel = [abs(p - r) / abs(r) if np.isfinite(p) else float("inf") for p, r in zip(pl, rl)]
    gref = np.asarray(reference["grad_norms"], np.float64)
    moved = gref >= 1e-3 * np.median(gref)
    everything = np.ones_like(moved)
    return {
        "loss1_gap": rel[0],
        "loss23_gap": max(rel[1:]),
        "grad_gap": _worst_leaf(np.asarray(program["grad_norms"], np.float64), gref, everything),
        "delta_gap": _worst_leaf(np.asarray(program["delta_norms"], np.float64),
                                 np.asarray(reference["delta_norms"], np.float64), moved),
    }


def load_limits(workload: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Any]:
    """``{'correct': bool, 'compared': {name: {'value', 'limit'}}}``: a
    number is compared only where the cell's file gives it a limit."""
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared.values())
    return {"correct": bool(ok), "compared": compared}
