"""Grouped-query causal attention for a token-level decoder (the LFM2
family's ``full_attention`` layers and the Nemotron-H family's ``*`` blocks;
most public decoders share the form).

``q = x W_q`` as ``num_heads`` heads of ``head_dim``; ``k = x W_k`` and ``v =
x W_v`` as ``num_kv_heads`` heads: query head ``h`` reads key/value head ``h
// (num_heads // num_kv_heads)``. With ``qk_norm`` every query head and every
key head is RMS-normalised over its own ``head_dim`` channels (one learned
scale vector for the queries, one for the keys); with ``rotary`` both are then
turned by the rotary embedding over the WHOLE head in the half-split pairing
(``ops/rotary.py``). LFM2 has both, Nemotron-H neither: its attention blocks
see no position but the causal mask's. Scores are ``q . k / sqrt(head_dim)``,
causal softmax, ``concat_h(P v) W_o``. No biases.

The inner product is ``ops.latent_attention.causal_attention``, which takes
the grouped operands as they are: the Pallas kernel's block index maps send a
query head to its key/value head, the blocked XLA path contracts (key/value
head, group member) against the head; neither repeats K / V in memory.

Everything here sits under the ``gqa_attention`` scope of a device trace.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu.ops.attention import torch_linear_kernel_init
from perceiver_io_tpu.ops.latent_attention import RMSNorm, causal_attention
from perceiver_io_tpu.ops.rotary import apply_rotary_half, rotary_angles

Array = jax.Array


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    qk_norm: bool = True  # the per-head RMSNorm of queries and keys
    rotary: bool = True   # the rotary embedding of queries and keys
    attn_impl: str = "auto"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        b, t, d = x.shape
        h, kv, depth = self.num_heads, self.num_kv_heads, self.head_dim

        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=torch_linear_kernel_init, name=name)

        def heads(name, count, normed):
            y = dense(f"{name}_proj", count * depth)(x).reshape(b, t, count, depth)
            if normed and self.qk_norm:
                y = RMSNorm(self.rms_norm_eps, self.dtype, name=f"{name}_layernorm")(y)
            return y

        with jax.named_scope("gqa_attention"):
            q, k, v = heads("q", h, True), heads("k", kv, True), heads("v", kv, False)
            if self.rotary:
                cos, sin = rotary_angles(jnp.arange(t), depth, self.rope_theta)
                q, k = apply_rotary_half(q, cos, sin), apply_rotary_half(k, cos, sin)
            out = causal_attention(q, k, v, self.attn_impl)
            return dense("out_proj", d)(out.reshape(b, t, h * depth))
