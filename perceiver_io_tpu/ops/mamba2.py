"""The Mamba-2 mixer of the Nemotron-H family (its ``M`` blocks): a selective
state-space layer whose recurrence is computed as a chunked scan.

    [z | xBC | dt] = u W_in                     (D -> 2 H P + 2 G N + H, no bias)
    xBC_t = silu(b + sum_j k_j * xBC_{t-(L-1)+j})   depthwise, causal, L taps, with bias
    [x | B | C] = xBC                           x: H heads of P; B, C: G groups of N
    Delta_t = softplus(dt_t + dt_bias)          one a head
    A = -exp(A_log)                             one a head
    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T        a head's state, P x N; S before
                                                            the row's first token is 0
    y_t = S_t C_t + D x_t                       head h reads group h // (H / G)
    out = GroupNorm(y * silu(z)) W_out          the gate BEFORE the norm: an RMSNorm over
                                                each of the G groups of H P / G channels on
                                                its own, times one learned scale of H P

The scan (:func:`ssd_scan`) never walks the tokens. In chunks of ``chunk``
tokens (``chunk_size``, 128 as published), with ``a_t = Delta_t A`` and
``cs`` its running sum inside a chunk:

- within a chunk, matrix products: ``Y = (L * (C B^T)) (Delta x)`` with ``L_ts
  = exp(cs_t - cs_s)`` for ``s <= t`` and 0 above the diagonal (the exponent
  is masked BEFORE the ``exp``: above the diagonal it is positive and may
  overflow);
- a chunk's own state at its end: ``sum_s exp(cs_last - cs_s) Delta_s x_s
  B_s^T``, one product a chunk;
- between chunks the state is carried: the state ENTERING chunk ``z`` is
  ``sum_{c < z} exp(sum of the whole chunks between) state_c``, one small
  float32 product over the chunk axis (``T / chunk`` squared, a head);
- what the entering state adds to a chunk's tokens: ``exp(cs_t) C_t S``.

Every exponent is a sum of ``a_t <= 0``, so every ``exp`` is in (0, 1]. The
running sums, ``exp``, softplus, the taps and the grouped norm are float32; the
contractions run in ``dtype`` with float32 accumulation, but for the carry
between chunks, which stays float32 (``Precision.HIGHEST``) like the state
itself. A row whose length is no multiple of the chunk is padded with tokens
of ``Delta = 0``, which neither decay the state nor add to it.

Two forms of the scan, picked by what the code observes (:func:`scan_impl`: the
backend and the sizes), never by a flag:

- on a TPU the pair of Pallas kernels of ``ops/pallas_ssd.py`` under one
  ``jax.custom_vjp``: a chunk's ``L``, ``C B^T``, ``Delta x`` and state stay
  in VMEM, forward and backward (only ``cs``, a number a token and head, is
  made outside), and the carry between chunks is the float32 recurrence
  itself in a scratch. What waits for the backward pass is
  the scan's inputs and the states ENTERING each chunk (``T / chunk`` states of
  ``H P N`` float32: :func:`state_bytes`, 134 MB a row of 8,192 at the
  published sizes; every token's state would be ``chunk`` times that, 17 GB),
  alive between a block's recomputation and its backward pass, a layer at a
  time;
- elsewhere :func:`ssd_scan`, plain XLA einsums under a ``jax.checkpoint`` of
  their own: what waits is the inputs (``x``, ``B``, ``C``, ``Delta``: ``T (H P
  + 2 G N + H)`` numbers), and the backward pass recomputes the chunked form,
  whose largest arrays are ``L`` (``T x chunk x H``) and the chunks' states. It
  is the CPU's path and the kernels' oracle.

Everything here sits under the ``mamba2`` scope of a device trace, the scan
alone (from the split of ``xBC`` and ``Delta`` to ``y`` before the gate,
backward included) under ``mamba2/ssd_scan``: PERF.md section 5 has its share
of the step in the cell that runs it.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu.ops import pallas_ssd
from perceiver_io_tpu.ops.attention import torch_linear_kernel_init
from perceiver_io_tpu.ops.short_conv import causal_depthwise_conv

Array = jax.Array


def state_bytes(tokens: int, chunk: int, heads: int, head_dim: int, state: int) -> int:
    """Float32 bytes of the states that the scan's backward pass holds for a
    row of ``tokens``: one ``heads x head_dim x state`` state a chunk."""
    return -(-tokens // min(chunk, tokens)) * heads * head_dim * state * 4


def scan_impl(heads: int, head_dim: int, groups: int, state: int, chunk: int,
              tokens: int) -> str:
    """Which form of the scan a mixer of these sizes runs here over rows of
    ``tokens``: ``'pallas'``, the kernel pair, on a TPU whose compiler takes
    their blocks (``pallas_ssd.kernel_fits``: the published sizes do);
    ``'xla'``, the einsums, elsewhere."""
    if jax.default_backend() == "tpu" and pallas_ssd.kernel_fits(
            heads, head_dim, groups, state, chunk, tokens):
        return "pallas"
    return "xla"


def _masked_exp(exponent: Array, keep: Array) -> Array:
    """``exp(exponent)`` where ``keep``, else 0; the mask first, so that an
    exponent that is not kept never reaches the ``exp``."""
    return jnp.exp(jnp.where(keep, exponent, -jnp.inf))


def ssd_scan(x: Array, delta: Array, a: Array, b: Array, c: Array, d: Array,
             chunk: int) -> Array:
    """``y`` (B, T, H, P) of the recurrence in the module docstring.

    ``x`` (B, T, H, P) and ``b``, ``c`` (B, T, G, N) in the compute dtype;
    ``delta`` (B, T, H), ``a`` (H,) (negative) and ``d`` (H,) float32."""
    rows, t, h, p = x.shape
    g, n = b.shape[2:]
    j = h // g  # heads a group: head (group, member) reads the group's B and C
    dtype = x.dtype
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, delta, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                          for v in (x, delta, b, c))
    chunks = (t + pad) // q

    skip = d[:, None] * x[:, :t].astype(jnp.float32)
    log_decay = (delta * a).reshape(rows, chunks, q, h)  # a_t <= 0
    cs = jnp.cumsum(log_decay, axis=2)
    delta_x = x.astype(jnp.float32) * delta[..., None]
    x_in = delta_x.astype(dtype).reshape(rows, chunks, q, g, j, p)
    b = b.reshape(rows, chunks, q, g, n)
    c = c.reshape(rows, chunks, q, g, n)

    # within a chunk
    by_head = cs.transpose(0, 1, 3, 2)  # (rows, chunks, H, q)
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = _masked_exp(by_head[..., :, None] - by_head[..., None, :], lower)
    cb = jnp.einsum("rctgn,rcsgn->rcgts", c, b, preferred_element_type=jnp.float32)
    mixed = (decay.reshape(rows, chunks, g, j, q, q) * cb[:, :, :, None]).astype(dtype)
    y = jnp.einsum("rcgjts,rcsgjp->rctgjp", mixed, x_in, preferred_element_type=jnp.float32)

    if chunks > 1:
        # each chunk's own state at its end
        to_end = jnp.exp(cs[:, :, -1:] - cs)  # (rows, chunks, q, H)
        x_end = (delta_x.reshape(rows, chunks, q, h, p) * to_end[..., None]).astype(dtype)
        states = jnp.einsum("rcsgjp,rcsgn->rcgjpn", x_end.reshape(rows, chunks, q, g, j, p), b,
                            preferred_element_type=jnp.float32)
        # the state entering chunk z: every earlier chunk's, decayed by the
        # whole chunks between (float32 throughout)
        ends = jnp.cumsum(cs[:, :, -1], axis=1)      # (rows, chunks, H): log decay up to a chunk's end
        starts = ends - cs[:, :, -1]                 # ... up to its start
        earlier = jnp.tril(jnp.ones((chunks, chunks), bool), -1)[:, :, None]
        carry = _masked_exp(starts[:, :, None] - ends[:, None], earlier)  # (rows, z, c, H)
        entering = jnp.einsum("rzcgj,rcgjpn->rzgjpn", carry.reshape(rows, chunks, chunks, g, j),
                              states, precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
        carried = jnp.einsum("rctgn,rcgjpn->rctgjp", c, entering.astype(dtype),
                             preferred_element_type=jnp.float32)
        y = y + carried * jnp.exp(cs).reshape(rows, chunks, q, g, j, 1)

    y = y.reshape(rows, chunks * q, h, p)[:, :t]
    return (y + skip).astype(dtype)


class GatedGroupNorm(nn.Module):
    """``RMSNorm_g(y * silu(z)) * scale``: the gate first, then each of the
    ``groups`` groups of channels normalised on its own, then one learned
    ``scale`` over all the channels. Computed in float32."""

    groups: int
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y: Array, z: Array) -> Array:
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        grouped = gated.reshape(*gated.shape[:-1], self.groups, -1)
        normed = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + self.eps)
        return (normed.reshape(gated.shape) * scale).astype(self.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A`` uniform in [1, 16] (the family's published range)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(time_step_min: float, time_step_max: float, time_step_floor: float):
    """``Delta`` log-uniform in [min, max], no smaller than the floor, at a
    zero projection: the bias is softplus's inverse of that ``Delta``."""
    def init(key, shape, dtype=jnp.float32):
        delta = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(time_step_min),
                                           math.log(time_step_max)))
        delta = jnp.maximum(delta, time_step_floor)
        return delta + jnp.log(-jnp.expm1(-delta))
    return init


class CausalConv1d(nn.Module):
    """Depthwise causal taps ``kernel`` (taps, channels) and a ``bias`` a channel."""

    taps: int

    @nn.compact
    def __call__(self, x: Array) -> Array:
        # (window, channels): ``in`` of a kernel is its last but one axis
        kernel = self.param("kernel", torch_linear_kernel_init, (self.taps, x.shape[-1]))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        return causal_depthwise_conv(x, kernel) + bias


class Mamba2Mixer(nn.Module):
    """The module docstring's lines for (B, T, D) inputs."""

    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: Array) -> Array:
        rows, t, width = u.shape
        h, p, g, n = self.num_heads, self.head_dim, self.n_groups, self.state_size
        inner, bc = h * p, g * n

        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=torch_linear_kernel_init, name=name)

        with jax.named_scope("mamba2"):
            z, xbc, dt = jnp.split(dense("in_proj", 2 * inner + 2 * bc + h)(u),
                                   [inner, 2 * inner + 2 * bc], axis=-1)
            xbc = jax.nn.silu(CausalConv1d(self.conv_kernel, name="conv1d")(xbc)).astype(self.dtype)
            a_log = self.param("A_log", _a_log_init, (h,))
            dt_bias = self.param("dt_bias", dt_bias_init(
                self.time_step_min, self.time_step_max, self.time_step_floor), (h,))
            d = self.param("D", nn.initializers.ones, (h,))
            with jax.named_scope("ssd_scan"):
                x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
                delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
                if scan_impl(h, p, g, n, self.chunk_size, t) == "pallas":
                    y = pallas_ssd.ssd_scan(x, delta, -jnp.exp(a_log), b, c, d, h, g,
                                            self.chunk_size)
                else:
                    y = jax.checkpoint(ssd_scan, static_argnums=(6,))(
                        x.reshape(rows, t, h, p), delta, -jnp.exp(a_log),
                        b.reshape(rows, t, g, n), c.reshape(rows, t, g, n), d, self.chunk_size)
            y = GatedGroupNorm(g, self.eps, self.dtype, name="norm")(y.reshape(rows, t, inner), z)
            return dense("out_proj", width)(y)
