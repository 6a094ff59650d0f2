"""BERT-style MLM text masking as a pure function of (rng key, batch).

Reproduces the reference corruption scheme exactly (``perceiver/model.py:240-293``),
including its nested-draw idiosyncrasy:

- special positions = ``(x == unk_id) | pad_mask``; only non-special positions
  are candidates,
- ``selected``   = Bernoulli(mask_p) ∧ candidate            (15% default),
- ``selected_1`` = selected ∧ Bernoulli(0.9)                 (these become [MASK]),
- ``selected_2`` = selected_1 ∧ Bernoulli(1/9)               (then overwritten with a
  random non-special token — note selected_2 ⊆ selected_1, so the random
  tokens are drawn *from the masked set*, giving the 80/10/10 marginal split),
- labels are ``-100`` everywhere except selected positions.

Random replacement tokens are uniform over ``[num_special_tokens, vocab_size)``,
relying on the same contract as the reference (``model.py:284-289``): special
tokens occupy the first ids.

The device RNG is a threaded ``jax.random`` key, so masking is deterministic
given (key, batch) — the TPU-native replacement for per-step CUDA RNG.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

IGNORE_LABEL = -100


# -- causal attention masks (the Perceiver-AR decode path) --------------------
#
# Mask convention throughout ops/attention.py: True = masked OUT (the torch
# ``key_padding_mask`` sense). A causal mask is a pure function of the query
# row's absolute position: query row i sits at position ``offset + i`` and may
# attend key j iff ``j <= offset + i``. offset = 0 is the square causal
# self-attention mask; offset = L - N is the Perceiver-AR cross-attention
# mask, where N latent queries cover the LAST N positions of an L-token input
# and each latent sees the full prefix up to (and including) its own token.


def causal_mask(num_queries: int, num_keys: int, offset: int = 0) -> Array:
    """(T, S) bool causal mask, True = masked out: query row ``i`` (absolute
    position ``offset + i``) may attend key positions ``<= offset + i``.

    Composes with a (B, S) pad mask by OR — ``ops.attention`` applies both
    independently, which is exactly that composition (a position is masked
    when padded OR acausal). The fused Pallas kernel takes the same rule as
    a ``causal_offset`` flag and applies it in-kernel instead of reading a
    materialized (T, S) mask (``ops.pallas_attention.fused_attention``)."""
    rows = jnp.arange(num_queries, dtype=jnp.int32)[:, None]
    cols = jnp.arange(num_keys, dtype=jnp.int32)[None, :]
    return cols > rows + offset


# -- the block-diffusion mask (BD3-LM, arXiv:2503.09573) ----------------------
#
# A row of L tokens is trained as 2L positions ``[x_t ; x]``: the noised copy,
# then the clean one. Position p is noised iff p < L, its token is p mod L,
# and its block is ``(p mod L) // block``. A noised query reads the noised
# keys of its own block (both ways) and the clean keys of the blocks before
# it; a clean query reads the clean keys of its own block and of those
# before it (causal between blocks); no clean query reads a noised key.


def block_diffusion_visible(rows: Array, cols: Array, half: int, block: int) -> Array:
    """bool, True where query ``rows`` may read key ``cols`` (positions of
    ``[x_t ; x]``, broadcast against each other) under the block-diffusion
    mask of two halves of ``half`` and blocks of ``block`` positions, a power
    of two. Only comparisons, shifts and logical operations: the attention
    kernels compute it in-kernel (``ops/pallas_attention.py``) and the
    blocked XLA path from the same function."""
    shift = block.bit_length() - 1
    q_clean, k_clean = rows >= half, cols >= half
    q_block = jnp.where(q_clean, rows - half, rows) >> shift
    k_block = jnp.where(k_clean, cols - half, cols) >> shift
    return ((q_clean & k_clean & (k_block <= q_block))
            | (~q_clean & k_clean & (k_block < q_block))
            | (~q_clean & ~k_clean & (k_block == q_block)))


def combine_attention_masks(
    pad_mask: Optional[Array],
    attn_mask: Optional[Array],
    num_queries: Optional[int] = None,
) -> Optional[Array]:
    """The effective (B, T, S) True=masked-out mask the attention paths apply
    — pad (B, S) OR'd with a (T, S)/(B, T, S) structural mask. The dense
    oracle the masking-parity tests check the kernel paths against; returns
    None when neither input masks anything."""
    if pad_mask is None and attn_mask is None:
        return None
    if attn_mask is not None and attn_mask.ndim == 2:
        attn_mask = attn_mask[None]
    if pad_mask is None:
        return attn_mask
    pad = pad_mask[:, None, :]
    if num_queries is not None:
        pad = jnp.broadcast_to(
            pad, (pad_mask.shape[0], num_queries, pad_mask.shape[-1])
        )
    if attn_mask is None:
        return pad
    return pad | attn_mask


def shift_ar_labels(token_ids: Array, pad_mask: Optional[Array],
                    latent_offset: int = 0) -> Array:
    """Next-token labels for the causal AR window: the query at absolute
    position ``latent_offset + i`` predicts ``token_ids[:, latent_offset + i
    + 1]``. Returns (B, L - latent_offset) int32 labels with
    :data:`IGNORE_LABEL` at the final position (no successor) and wherever
    the TARGET token is padding — the same ignore convention MLM's CE uses,
    so ``cross_entropy_with_ignore`` applies unchanged."""
    b, l = token_ids.shape
    n = l - latent_offset
    # Successor ids via roll-then-slice, NOT concat: under a seq-sharded
    # batch (shard_seq=True with tp x sp meshes) this XLA build's SPMD
    # partitioner miscompiles a concat along the sharded axis (the r6
    # fused-QKV repro — here it surfaced as NaN loss in the dp2/tp2/sp2
    # dry run); roll lowers to a collective permute, which partitions
    # correctly. The wrapped-around element lands at the final slot, which
    # is ignored anyway (no successor exists there).
    succ = jnp.roll(token_ids, -1, axis=1)[:, latent_offset:]
    labels = succ.astype(jnp.int32)
    last = jnp.arange(n, dtype=jnp.int32)[None, :] == n - 1
    invalid = jnp.broadcast_to(last, (b, n))
    if pad_mask is not None:
        invalid = invalid | jnp.roll(pad_mask, -1, axis=1)[:, latent_offset:]
    return jnp.where(invalid, IGNORE_LABEL, labels)


def apply_text_masking(
    key: Array,
    x: Array,
    pad_mask: Array,
    *,
    vocab_size: int,
    unk_token_id: int,
    mask_token_id: int,
    num_special_tokens: int,
    mask_p: float = 0.15,
) -> Tuple[Array, Array]:
    """Corrupt token ids ``x`` (B, L) for MLM; returns ``(x_masked, labels)``.

    ``pad_mask`` is True at padding positions. Labels are ``IGNORE_LABEL`` at
    non-selected positions.
    """
    k_sel, k_mask90, k_rand19, k_tok = jax.random.split(key, 4)
    shape = x.shape

    if pad_mask is None:
        pad_mask = jnp.zeros(shape, dtype=bool)

    is_special = (x == unk_token_id) | pad_mask
    is_input = ~is_special

    is_selected = (jax.random.uniform(k_sel, shape) < mask_p) & is_input
    is_selected_1 = is_selected & (jax.random.uniform(k_mask90, shape) < 0.9)
    is_selected_2 = is_selected_1 & (jax.random.uniform(k_rand19, shape) < 1.0 / 9.0)

    random_tokens = jax.random.randint(
        k_tok, shape, num_special_tokens, vocab_size, dtype=x.dtype
    )

    x_masked = jnp.where(is_selected_1, jnp.asarray(mask_token_id, x.dtype), x)
    x_masked = jnp.where(is_selected_2, random_tokens, x_masked)

    # Labels must be signed so IGNORE_LABEL=-100 cannot wrap for unsigned
    # token-id dtypes.
    labels = jnp.where(is_selected, x.astype(jnp.int32), IGNORE_LABEL)
    return x_masked, labels


class TextMasking:
    """Config holder mirroring the reference's ``TextMasking`` module surface
    (``perceiver/model.py:240-263``), as a plain dataclass-style callable —
    masking itself is stateless and keyed."""

    def __init__(
        self,
        vocab_size: int,
        unk_token_id: int,
        mask_token_id: int,
        num_special_tokens: int,
        mask_p: float = 0.15,
    ):
        self.vocab_size = vocab_size
        self.unk_token_id = unk_token_id
        self.mask_token_id = mask_token_id
        self.num_special_tokens = num_special_tokens
        self.mask_p = mask_p

    @classmethod
    def create(cls, tokenizer, **kwargs):
        """Build from a tokenizer exposing vocab_size / token_to_id, mirroring
        ``TextMasking.create`` (reference ``model.py:254-260``)."""
        from perceiver_io_tpu.data.tokenizer import UNK_TOKEN, MASK_TOKEN, SPECIAL_TOKENS

        return cls(
            vocab_size=tokenizer.get_vocab_size(),
            unk_token_id=tokenizer.token_to_id(UNK_TOKEN),
            mask_token_id=tokenizer.token_to_id(MASK_TOKEN),
            num_special_tokens=len(SPECIAL_TOKENS),
            **kwargs,
        )

    def __call__(self, key: Array, x: Array, pad_mask: Array) -> Tuple[Array, Array]:
        return apply_text_masking(
            key,
            x,
            pad_mask,
            vocab_size=self.vocab_size,
            unk_token_id=self.unk_token_id,
            mask_token_id=self.mask_token_id,
            num_special_tokens=self.num_special_tokens,
            mask_p=self.mask_p,
        )
