"""Multi-head latent attention (MLA, DeepSeek-V2/V3) for a causal decoder.

Queries and keys/values pass through low-rank latents: ``c_q = RMSNorm(x
W_qa)``, ``q = c_q W_qb`` split per head into a no-position part and a rotary
part; ``[c_kv | k_rope] = x W_kva`` with ``c_kv = RMSNorm(c_kv)`` expanded by
``W_kvb`` into per-head ``k_nope`` and ``v``, while the ONE rotary key
``k_rope`` is shared by all heads. Scores are ``(q_nope . k_nope + q_rope .
k_rope) / sqrt(d_nope + d_rope)``; the value depth may differ from the score
depth (128 against 192 at the published sizes).

The causal inner product never materialises (B, H, T, T) logits:

- ``'pallas'``: ``ops.pallas_attention.fused_attention`` with
  ``causal_offset=0``, its value depth taken from ``v`` (the kernel was
  extended for that; nothing is padded) and the tiles above the diagonal
  skipped;
- ``'xla'``: blocks of queries against the keys up to the block's end, each
  block under ``jax.checkpoint`` so that one block's float32 logits live at a
  time, forward and backward;
- ``'auto'``: the kernel on a TPU, the blocked path elsewhere. Measured on the
  v5e at 4 x 4096 x 32 heads (my chip runs, PR 32; forward + backward): the
  kernel 38.56 ms, the blocked path 62.56 ms (blocks of 512; 69.06 of 1024);
  the kernel without the skipping 60.25; fed values padded to 192: 47.17.

Everything here sits under the ``mla_attention`` scope of a device trace.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from perceiver_io_tpu.ops.attention import torch_linear_kernel_init
from perceiver_io_tpu.ops.rotary import apply_rotary_interleaved, rotary_angles

Array = jax.Array

XLA_QUERY_BLOCK = 512
# The kernel's blocks at 4 x 4096 x 32 heads, scores 192 deep, values 128 deep
# (my chip runs, PR 32; forward / forward + backward, ms): queries 512 x keys
# 1024: 11.48 / 38.56; the kernel's own resolution (1024 x 512): 16.27 / 43.26;
# 512 x 512: 15.72 / 43.44; 256 x 512: 18.42 / 52.17; 1024 x 256: 27.22 / 60.84;
# 1024 x 1024 does not fit VMEM (the dkv kernel). Move only with new rows.
PALLAS_QUERY_BLOCK, PALLAS_KV_BLOCK = 512, 1024
MASK_VALUE = -1e30


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``, computed in float32."""

    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def _causal_block(q: Array, k: Array, v: Array, first_row: int) -> Array:
    """Rows ``first_row ..`` of the queries against keys ``0 .. first_row +
    rows``: float32 logits and softmax, the mask only on the diagonal block."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32) * scale
    rows = first_row + jnp.arange(q.shape[1])[:, None]
    logits = jnp.where(jnp.arange(k.shape[1])[None, :] <= rows, logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v, preferred_element_type=jnp.float32
                      ).astype(v.dtype)


def resolve_causal_impl(impl: str) -> str:
    """What ``'auto'`` stands for: the kernel on a TPU, the blocked path
    elsewhere. Any other string is returned as it is."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def causal_attention(q: Array, k: Array, v: Array, impl: str = "auto",
                     query_block: int = XLA_QUERY_BLOCK) -> Array:
    """Causal self-attention of (B, T, H, D) q and k with (B, T, H, Dv) v:
    row i sees keys 0..i. Returns (B, T, H, Dv)."""
    impl = resolve_causal_impl(impl)
    if impl == "pallas":
        from perceiver_io_tpu.ops.pallas_attention import fused_attention

        return fused_attention(q, k, v, causal_offset=0,
                               q_block_size=PALLAS_QUERY_BLOCK,
                               kv_block_size=PALLAS_KV_BLOCK)
    if impl != "xla":
        raise ValueError(f"attn_impl must be 'auto', 'pallas' or 'xla', got {impl!r}")
    t = q.shape[1]
    if t <= query_block or t % query_block:
        return _causal_block(q, k, v, 0)
    block = jax.checkpoint(_causal_block, static_argnums=(3,))
    out = [block(q[:, lo:lo + query_block], k[:, :lo + query_block],
                 v[:, :lo + query_block], lo)
           for lo in range(0, t, query_block)]
    return jnp.concatenate(out, axis=1)


class MultiHeadLatentAttention(nn.Module):
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    attn_impl: str = "auto"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        b, t, d = x.shape
        h, nope, rope = self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim

        def dense(name, features):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=torch_linear_kernel_init, name=name)

        def norm(name):
            return RMSNorm(self.rms_norm_eps, self.dtype, name=name)

        with jax.named_scope("mla_attention"):
            q = dense("q_b", h * (nope + rope))(norm("q_a_norm")(dense("q_a", self.q_lora_rank)(x)))
            q = q.reshape(b, t, h, nope + rope)
            kv_a = dense("kv_a", self.kv_lora_rank + rope)(x)
            c_kv, k_rope = kv_a[..., :self.kv_lora_rank], kv_a[..., self.kv_lora_rank:]
            kv = dense("kv_b", h * (nope + self.v_head_dim))(norm("kv_a_norm")(c_kv))
            kv = kv.reshape(b, t, h, nope + self.v_head_dim)
            k_nope, v = kv[..., :nope], kv[..., nope:]

            cos, sin = rotary_angles(jnp.arange(t), rope, self.rope_theta)
            q_rope = apply_rotary_interleaved(q[..., nope:], cos, sin)
            k_rope = apply_rotary_interleaved(k_rope[:, :, None, :], cos, sin)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, h, rope))], axis=-1)

            out = causal_attention(q, k, v, self.attn_impl)
            return dense("o", d)(out.reshape(b, t, h * self.v_head_dim))
