"""Rotary position embedding (Su et al., RoFormer) on interleaved pairs.

A position is a rotation, not a table: channel pair ``(2i, 2i + 1)`` of a
head is turned by ``position * theta ** (-2i / dim)``, so a dot product of a
rotated query and a rotated key depends on their distance only. Nothing is
learned and nothing grows with the context (``models/adapters.py`` has the
learned and the Fourier positions). Angles and the rotation are float32; the
result takes the input's dtype.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def rotary_angles(positions: Array, dim: int, theta: float) -> Tuple[Array, Array]:
    """``(cos, sin)``, each ``positions.shape + (dim // 2,)`` float32."""
    if dim % 2:
        raise ValueError(f"rotary dimension must be even, got {dim}")
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary_interleaved(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate the pairs ``(x[..., 2i], x[..., 2i + 1])`` of ``x`` (B, T, H, D)
    by the angles of ``cos`` / ``sin`` (T, D // 2): the row's index is the
    position, every head gets the same turn."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    out = jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
