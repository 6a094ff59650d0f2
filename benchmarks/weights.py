"""Weights made by the benchmark, on the device, in ONE jitted call from the
seed: the system under test and the plain reference are both handed this
tree, so neither takes anything the other has made.

The rule for a leaf follows from its name and shape alone (the published
initialisation families of the architecture, not the program's initialisers):

- ``scale`` -> 1; a ``bias`` under a ``*norm`` -> 0
- ``kernel`` (in, out) and linear ``bias`` -> U(-1/sqrt(in), 1/sqrt(in)) resp.
  U(-0.02, 0.02): every bias is non-zero so that its gradient path is tested
- ``embedding`` -> U(-0.1, 0.1); ``pos_encoding`` -> U(-0.5, 0.5)
- learned arrays (``latent``, ``output``) -> N(0, 0.02) clipped to +-2
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """A seed of any size as two 31-bit words (``--seed`` may pass 2**31)."""
    seed = int(seed)
    return np.uint32(seed & 0x7FFFFFFF), np.uint32((seed >> 31) & 0x7FFFFFFF)


def seed_key(lo, hi, stream: int = 0):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)
    return jax.random.fold_in(key, stream)


@jax.jit
def train_rng(lo, hi):
    """The train state's rng of a seed (stream 2; the weights are stream 1)."""
    return seed_key(lo, hi, stream=2)


def _leaf(key, names: Tuple[str, ...], shape, dtype):
    last, parent = names[-1], (names[-2] if len(names) > 1 else "")
    if last == "scale":
        return jnp.ones(shape, dtype)
    if last == "bias" and parent.endswith("norm"):
        return jnp.zeros(shape, dtype)
    if last == "kernel":
        bound = 1.0 / np.sqrt(shape[0])
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    if last == "bias":
        return jax.random.uniform(key, shape, dtype, -0.02, 0.02)
    if last == "embedding":
        return jax.random.uniform(key, shape, dtype, -0.1, 0.1)
    if last == "pos_encoding":
        return jax.random.uniform(key, shape, dtype, -0.5, 0.5)
    return jnp.clip(jax.random.normal(key, shape, dtype) * 0.02, -2.0, 2.0)


def leaf_names(tree: Any):
    """``[(names, leaf), ...]`` in the tree's canonical order."""
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out.append((tuple(str(getattr(k, "key", k)) for k in path), leaf))
    return out


def make_weights_fn(shapes: Any):
    """``shapes``: a nested dict of ``ShapeDtypeStruct`` (the layout of the
    parameter tree). Returns a jitted ``(lo, hi) -> tree`` whose program does
    not depend on the seed's value."""
    named = leaf_names(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    @jax.jit
    def make(lo, hi):
        base = seed_key(lo, hi, stream=1)
        leaves = [
            _leaf(jax.random.fold_in(base, i), names, tuple(s.shape), s.dtype)
            for i, (names, s) in enumerate(named)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make
