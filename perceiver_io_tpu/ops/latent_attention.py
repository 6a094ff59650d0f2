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
from perceiver_io_tpu.ops import masking
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


# The same at 2 x 8192 x 32 query heads over 8 key/value heads, scores and
# values 64 deep (my chip run, PR 36; forward / forward + backward, ms): 1024 x
# 1024: 11.07 / 41.60; 512 x 2048: 11.94 / 43.73; 512 x 1024: 13.00 / 44.84;
# 1024 x 512: 20.85 / 55.11; 512 x 512: 21.26 / 58.14; 1024 x 2048 and 2048 x
# anything do not fit VMEM (sandbox compile for a described v5e).
SHALLOW_DEPTH, SHALLOW_BLOCKS = 64, (1024, 1024)


def pallas_blocks(depth: int):
    """(query block, key block) of the causal kernel for scores ``depth``
    deep: the measured pair of the nearest measured depth."""
    if depth <= SHALLOW_DEPTH:
        return SHALLOW_BLOCKS
    return PALLAS_QUERY_BLOCK, PALLAS_KV_BLOCK


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
    rows``: float32 logits and softmax, the mask only on the diagonal block.
    With fewer key/value heads than query heads the queries are taken as
    (key/value head, member of its group): no copy of K / V is made."""
    def visible(t, s):
        rows = first_row + jnp.arange(t)[:, None]
        return jnp.arange(s)[None, :] <= rows

    return _masked_block(q, k, v, visible)


def _block_diffusion_block(q: Array, k: Array, v: Array, first_row: int, block: int) -> Array:
    """Rows ``first_row ..`` of the queries of ``[x_t ; x]`` against every
    key, under the block-diffusion mask (``ops.masking``)."""
    def visible(t, s):
        return masking.block_diffusion_visible(
            first_row + jnp.arange(t)[:, None], jnp.arange(s)[None, :], s // 2, block)

    return _masked_block(q, k, v, visible)


def _masked_block(q: Array, k: Array, v: Array, visible) -> Array:
    """Float32 logits and softmax of the T queries against the S keys
    where the (T, S) ``visible(T, S)`` is True."""
    b, t, h, d = q.shape
    grouped = k.shape[2] != h
    scores, weighted = "bthd,bshd->bhts", "bhts,bshd->bthd"
    if grouped:
        q = q.reshape(b, t, k.shape[2], h // k.shape[2], d)
        scores, weighted = "btkgd,bskd->bkgts", "bkgts,bskd->btkgd"
    logits = jnp.einsum(scores, q, k, preferred_element_type=jnp.float32) * d ** -0.5
    logits = jnp.where(visible(t, k.shape[1]), logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum(weighted, probs, v, preferred_element_type=jnp.float32).astype(v.dtype)
    return out.reshape(b, t, h, v.shape[-1]) if grouped else out


def resolve_causal_impl(impl: str) -> str:
    """What ``'auto'`` stands for: the kernel on a TPU, the blocked path
    elsewhere. Any other string is returned as it is."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def block_diffusion_unmasked_tiles_pct(t: int, depth: int, block: int, impl: str = "auto") -> float:
    """Share, in %, of the tiles that ``causal_attention`` visits over T = ``t``
    positions of ``[x_t ; x]``, scores ``depth`` deep, under the mask of
    blocks of ``block``, that run with no mask: 0 on the blocked XLA path,
    which runs no kernel."""
    if resolve_causal_impl(impl) != "pallas":
        return 0.0
    from perceiver_io_tpu.ops.pallas_attention import (BlockDiffusion,
                                                       block_diffusion_unmasked_tiles_pct as pct)

    q_block, kv_block = pallas_blocks(depth)
    return pct(t, depth, BlockDiffusion(t // 2, block), kv_block_size=kv_block, q_block_size=q_block)


def causal_attention(q: Array, k: Array, v: Array, impl: str = "auto",
                     query_block: int = XLA_QUERY_BLOCK, block_diffusion: int = 0) -> Array:
    """Causal self-attention of (B, T, H, D) q with (B, T, Hkv, D) k and (B,
    T, Hkv, Dv) v: row i sees keys 0..i, query head h reads key/value head
    ``h // (H // Hkv)`` (``Hkv`` = ``H``: every head its own). Returns (B, T,
    H, Dv). The kernel's blocks follow the score depth (``pallas_blocks``).

    ``block_diffusion`` B > 0: the row is ``[x_t ; x]``, two halves of T / 2,
    under the block-diffusion mask with blocks of B
    (``ops.masking.block_diffusion_visible``) in place of the causal rule: the
    kernel skips the tiles it hides, the blocked XLA path takes blocks of
    queries against every key."""
    impl = resolve_causal_impl(impl)
    if impl == "pallas":
        from perceiver_io_tpu.ops.pallas_attention import BlockDiffusion, fused_attention

        q_block, kv_block = pallas_blocks(q.shape[-1])
        if block_diffusion:
            return fused_attention(q, k, v, q_block_size=q_block, kv_block_size=kv_block,
                                   block_diffusion=BlockDiffusion(q.shape[1] // 2, block_diffusion))
        return fused_attention(q, k, v, causal_offset=0,
                               q_block_size=q_block, kv_block_size=kv_block)
    if impl != "xla":
        raise ValueError(f"attn_impl must be 'auto', 'pallas' or 'xla', got {impl!r}")
    t = q.shape[1]
    if block_diffusion:
        block = jax.checkpoint(_block_diffusion_block, static_argnums=(3, 4))
        return jnp.concatenate([block(q[:, lo:lo + query_block], k, v, lo, block_diffusion)
                                for lo in range(0, t, query_block)], axis=1)
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
