"""The plain float32 reference of the ``sdar_moe`` family: SDAR-30B-A3B-Chat
(a Qwen3-MoE stack, ``model_type`` ``sdar_moe``) trained by block diffusion
(SDAR, arXiv:2510.06303; the objective of BD3-LM, arXiv:2503.09573, in its
vectorised form). Over ``common.py`` only; nothing of ``perceiver_io_tpu``,
and of the other references their plain ``rms_norm``.

Straightforward on purpose: a Python loop over the layers (each under
``jax.checkpoint``, which changes what is kept and not what is computed);
attention in checkpointed blocks of queries against EVERY key, one block
after another (``lax.map``, so that one block's float32 scores live at a time
in the backward pass too), with K and V REPEATED to the query heads and the
mask written out as a (queries, keys) array; DENSE per-expert arithmetic:
every held expert runs on every position and is weighted by the position's
gate for that expert (0 where it was not selected); the noise drawn with the
same ``jax.random`` calls as the program from the same key. Every contraction
goes through ``arith.einsum``.

Equations (a row x of L tokens; RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale):

- noise: blocks of B tokens, ``b(i) = i // B``; block b draws ``t_b = k /
  1024``, k uniform on 1..1024; token i becomes the mask id iff ``u_i <
  t_{b(i)}``, u uniform on [0, 1): ``x_t``. The stack runs over the 2L
  positions ``[x_t ; x]``, both halves at positions 0..L-1.
- layer ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``.
- ``Attn``: ``q = u W_q`` as H heads, ``k = u W_k``, ``v = u W_v`` as Hkv
  heads of d; q and k RMS-normalised over each head's d channels (one scale
  vector each) and turned by the rotary embedding over the whole head in the
  half-split pairing (pair (i, i + d/2) of position p times ``exp(1j p
  theta^(-2i/d))``); query head h reads key/value head ``h // (H / Hkv)``;
  scores ``/ sqrt(d)``; softmax over the keys the block-diffusion mask shows
  (``visible``); ``concat_h(P v) W_o``.
- ``Experts``: ``s = softmax(u W_r)`` over ALL experts; the ``top_k`` largest;
  ``g = s_sel / sum s_sel``; ``y = sum over the HELD experts e of g_e
  down_e(silu(gate_e(u)) * up_e(u))``. No shared expert.
- a final RMSNorm of the noised half; logits ``h W_head``; loss of a block of
  rows: ``sum over masked i of CE(logits_i, x_i) / t_{b(i)}``, which the
  caller divides by ``rows * L``.

Departures from the published model, each stated in the configuration's
``assumed`` too: the noise levels are a grid of 1024 steps of [1/1024, 1]
(U[1e-3, 1] as a grid, so that the program's draws and these are the same
bits); the block length (4), the noise schedule (linear, weight 1/t), the mask
id (the vocabulary slice's last row) and the per-head q/k norms are not in
``config.json`` and are set by the configuration; no auxiliary balance loss;
what the experts that are not held would add is left out (the chip's share of
a deployment), and the vocabulary is a slice.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import common
from benchmarks.reference.common import Arith, Array, Params
from benchmarks.reference.decoder_lm import rms_norm

QUERY_BLOCK = 256
NOISE_LEVELS = 1024


def masking_key(rng: Array, step: int) -> Array:
    """The ``'masking'`` key of optimizer step ``step`` (0-based) as the
    train state derives its one stream from its ``rng``."""
    return jax.random.split(jax.random.fold_in(rng, step), 1)[0]


def noise(key: Array, ids: Array, block_length: int, mask_id: int):
    """``(x_t, weights)``: ``weights`` is ``1 / t_b`` where a token was masked
    and 0 elsewhere (full rows: no padding)."""
    rows, length = ids.shape
    k_level, k_token = jax.random.split(key)
    level = jax.random.randint(k_level, (rows, length // block_length), 1, NOISE_LEVELS + 1)
    level = jnp.repeat(level.astype(jnp.float32) * (1.0 / NOISE_LEVELS), block_length, axis=1)
    masked = jax.random.uniform(k_token, (rows, length)) < level
    return jnp.where(masked, mask_id, ids), jnp.where(masked, 1.0 / level, 0.0)


def visible(rows: Array, cols: Array, half: int, block: int) -> Array:
    """True where position ``rows`` of ``[x_t ; x]`` may read position
    ``cols``: a noised token its own noised block and the clean blocks before
    it, a clean token the clean blocks up to its own, nothing else."""
    noised_q, noised_k = rows < half, cols < half
    q_block, k_block = (rows % half) // block, (cols % half) // block
    return jnp.where(noised_q,
                     jnp.where(noised_k, k_block == q_block, k_block < q_block),
                     ~noised_k & (k_block <= q_block))


def rotate_halves(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding of (rows, T, heads, d) at ``positions`` (T,)."""
    d = x.shape[-1]
    freq = jnp.exp(-math.log(theta) * jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    re, im = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    return jnp.concatenate([re * cos - im * sin, re * sin + im * cos], axis=-1)


def _attend(ar: Arith, q: Array, k: Array, v: Array, lo: Array, block: int) -> Array:
    """Queries ``lo ..`` (one block) against every key, under the mask."""
    rows = lo + jnp.arange(q.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    scores = ar.einsum("rthd,rshd->rhts", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(visible(rows, cols, k.shape[1] // 2, block),
                                     scores, -jnp.inf), axis=-1)
    return ar.einsum("rhts,rshd->rthd", probs, v)


def grouped_query_attention(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    r, t, _ = x.shape
    h, kv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = ar.einsum("rtd,de->rte", x, p["q_proj"]["kernel"]).reshape(r, t, h, d)
    k = ar.einsum("rtd,de->rte", x, p["k_proj"]["kernel"]).reshape(r, t, kv, d)
    v = ar.einsum("rtd,de->rte", x, p["v_proj"]["kernel"]).reshape(r, t, kv, d)
    positions = jnp.arange(t) % (t // 2)  # both halves at 0..L-1
    q = rotate_halves(rms_norm(q, p["q_layernorm"]["scale"], sz["eps"]), positions, sz["theta"])
    k = rotate_halves(rms_norm(k, p["k_layernorm"]["scale"], sz["eps"]), positions, sz["theta"])
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    rows = min(QUERY_BLOCK, t)
    blocks = jnp.moveaxis(q.reshape(r, t // rows, rows, h, d), 1, 0)
    block = jax.checkpoint(lambda at: _attend(ar, at[0], k, v, at[1], sz["block_length"]))
    out = jax.lax.map(block, (blocks, jnp.arange(0, t, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(r, t, h * d)
    return ar.einsum("rte,ed->rtd", out, p["out_proj"]["kernel"])


def gates(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    """(rows, T, held): a held expert's gate for each position, 0 where it
    was not selected."""
    held, offset = sz["experts_held"], sz["expert_offset"]
    scores = jax.nn.softmax(ar.einsum("rtd,de->rte", x, p["router"]["kernel"]), axis=-1)
    _, chosen = jax.lax.top_k(scores, sz["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = sz["scale"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
    mine = chosen[..., None] == (offset + jnp.arange(held))
    return jnp.sum(jnp.where(mine, picked[..., None], 0.0), axis=-2)


def expert_layer(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    """What the HELD experts add: each expert's hidden units times its gate,
    then all of them through their down projections in one contraction (the
    sum over experts and units)."""
    weight = gates(ar, p, x, sz)
    hidden = (jax.nn.silu(ar.einsum("rtd,edw->ertw", x, p["experts_gate"]["kernel"]))
              * ar.einsum("rtd,edw->ertw", x, p["experts_up"]["kernel"]))
    hidden = hidden * jnp.moveaxis(weight, -1, 0)[..., None]
    return ar.einsum("ertw,ewd->rtd", hidden, p["experts_down"]["kernel"])


def block(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    h = x + grouped_query_attention(ar, p["attn"], rms_norm(x, p["attn_norm"]["scale"], sz["eps"]),
                                    sz)
    return h + expert_layer(ar, p["moe"], rms_norm(h, p["ffn_norm"]["scale"], sz["eps"]), sz)


def hidden_states(ar: Arith, params: Params, noisy: Array, ids: Array,
                  sz: Dict[str, Any]) -> Array:
    """The noised half of the stack's output over ``[x_t ; x]`` after the
    final norm, (rows, L, D)."""
    layer = jax.checkpoint(lambda p, x: block(ar, p, x, sz))
    x = params["embed"]["embedding"][jnp.concatenate([noisy, ids], axis=1)]
    for i in range(sz["layers"]):
        x = layer(params[f"layer_{i}"], x)
    return rms_norm(x[:, :ids.shape[1]], params["final_norm"]["scale"], sz["eps"])


def logits(ar: Arith, params: Params, noisy: Array, ids: Array, sz: Dict[str, Any]) -> Array:
    """(rows, L, vocab): ``logits[:, i]`` scores ``x_i``."""
    return ar.einsum("rtd,dv->rtv", hidden_states(ar, params, noisy, ids, sz),
                     params["head"]["kernel"])


def bd_loss_sum(ar: Arith, params: Params, block_: Dict[str, Array], sz: Dict[str, Any]) -> Array:
    """Sum over a block of rows of the masked tokens' cross-entropies, each
    over its block's noise level."""
    ids = block_["token_ids"]
    h = hidden_states(ar, params, block_["noisy_ids"], ids, sz)

    def weighted_sum(hidden, head):
        scores = ar.einsum("rtd,dv->rtv", hidden, head)
        return jnp.sum(common.cross_entropy(scores, ids) * block_["weights"])

    return jax.checkpoint(weighted_sum)(h, params["head"]["kernel"])
