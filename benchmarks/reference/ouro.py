"""The plain float32 reference of the ``ouro`` family: Ouro-2.6B trained as a
looped language model (the Ouro LoopLM, arXiv:2510.25741). Over ``common.py``
only; nothing of ``perceiver_io_tpu``, and of the other references the two
plain functions ``rms_norm`` and ``swiglu`` of ``decoder_lm.py``.

Straightforward on purpose: the R loop steps are a plain Python loop over the
SAME weights, and inside it a Python loop over the layers (each application
under ``jax.checkpoint``, which changes what is kept and not what is
computed); attention in checkpointed blocks of queries against EVERY key with
the causal mask written out as a (queries, keys) array, one block after
another (``lax.map``: one block's float32 scores alive at a time); the exits
one after another (``lax.map`` of checkpointed heads: one exit's (tokens,
vocabulary) logits alive at a time). Every contraction goes through
``arith.einsum``.

Equations (x is (rows, T, D); RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale,
eps 1e-6, one learned scale each; no projection has a bias):

- block l, the sandwich norm:
  ``a = x + N2_l(Attn_l(N1_l(x)))``,
  ``y = a + N4_l(W_down(silu(N3_l(a) W_gate) * N3_l(a) W_up))``.
- ``Attn``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v`` as H heads of d (as
  many key/value heads as query heads); q and k turned by the rotary
  embedding over the whole head in the half-split pairing (pair (i, i + d/2)
  of position p times ``exp(1j p theta^(-2i/d))``), positions 0..T-1 at every
  loop step; no q/k norm; scores ``/ sqrt(d)``, causal softmax,
  ``concat_h(P v) W_o``.
- the loop: ``h^0 = E[ids]``, ``h^t = N_f(Block_N o ... o Block_1(h^{t-1}))``
  for t = 1..R, the same weights at every t.
- the exits, per token: ``lambda_t = sigmoid(h^t w_g + b_g)``; ``p_t =
  lambda_t prod_{j<t}(1 - lambda_j)`` for t < R, ``p_R = prod_{j<R}(1 -
  lambda_j)``; ``CE_t`` the cross-entropy of ``h^t W_head`` (untied) against
  the next token.
- the loss of a block of rows: the sum over the tokens that have a next token
  of ``sum_t p_t CE_t + beta sum_t p_t log p_t``, which the caller divides
  by ``rows * (T - 1)``.

Departures from the published model, each stated in the configuration's
``assumed`` too: the sandwich norms and the final norm between loop steps
follow the family's paper and public code (no key of ``config.json`` names
them); the gate's form; beta, the entropy weight of the paper's first
training stage.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmarks.reference import common
from benchmarks.reference.common import Arith, Array, Params
from benchmarks.reference.decoder_lm import rms_norm, swiglu

QUERY_BLOCK = 512


def rotate_halves(x: Array, theta: float) -> Array:
    """Rotary embedding of (rows, T, heads, d) at positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    freq = jnp.exp(-math.log(theta) * jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    re, im = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    return jnp.concatenate([re * cos - im * sin, re * sin + im * cos], axis=-1)


def _attend(ar: Arith, q: Array, k: Array, v: Array, lo: Array) -> Array:
    """Queries ``lo ..`` (one block) against every key, under the causal mask."""
    rows = lo + jnp.arange(q.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    scores = ar.einsum("rthd,rshd->rhts", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(cols <= rows, scores, -jnp.inf), axis=-1)
    return ar.einsum("rhts,rshd->rthd", probs, v)


def attention(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    r, t, _ = x.shape
    h, d = sz["heads"], sz["head_dim"]
    q = rotate_halves(ar.einsum("rtd,de->rte", x, p["q_proj"]["kernel"]).reshape(r, t, h, d),
                      sz["theta"])
    k = rotate_halves(ar.einsum("rtd,de->rte", x, p["k_proj"]["kernel"]).reshape(r, t, h, d),
                      sz["theta"])
    v = ar.einsum("rtd,de->rte", x, p["v_proj"]["kernel"]).reshape(r, t, h, d)
    rows = min(QUERY_BLOCK, t)
    blocks = jnp.moveaxis(q.reshape(r, t // rows, rows, h, d), 1, 0)
    block = jax.checkpoint(lambda at: _attend(ar, at[0], k, v, at[1]))
    out = jax.lax.map(block, (blocks, jnp.arange(0, t, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(r, t, h * d)
    return ar.einsum("rte,ed->rtd", out, p["out_proj"]["kernel"])


def block(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    eps = sz["eps"]
    a = x + rms_norm(attention(ar, p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps), sz),
                     p["attn_post_norm"]["scale"], eps)
    return a + rms_norm(swiglu(ar, p["mlp"], rms_norm(a, p["ffn_norm"]["scale"], eps)),
                        p["ffn_post_norm"]["scale"], eps)


def states(ar: Arith, params: Params, ids: Array, sz: Dict[str, Any]) -> List[Array]:
    """``[h^1, ..., h^R]``: every loop step's state after the final norm."""
    stack = params["loop"]
    layer = jax.checkpoint(lambda p, x: block(ar, p, x, sz))
    x = params["embed"]["embedding"][ids]
    out = []
    for _ in range(sz["steps"]):
        for i in range(sz["layers"]):
            x = layer(stack[f"layer_{i}"], x)
        x = rms_norm(x, stack["final_norm"]["scale"], sz["eps"])
        out.append(x)
    return out


def exit_probs(ar: Arith, params: Params, hs: List[Array]) -> Array:
    """(R, rows, T): the probability ``p_t`` of leaving at step t."""
    gate = params["exit_gate"]
    lam = [jax.nn.sigmoid(ar.einsum("rtd,do->rto", h, gate["kernel"])[..., 0] + gate["bias"][0])
           for h in hs]
    probs, stay = [], jnp.ones_like(lam[0])
    for t in range(len(hs) - 1):
        probs.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(probs + [stay])


def logits(ar: Arith, params: Params, ids: Array, sz: Dict[str, Any]):
    """``(logits, p)``: every exit's (R, rows, T, vocab) logits, ``[t, :, i]``
    scoring ``ids[:, i + 1]``, and the exit probabilities (R, rows, T)."""
    hs = states(ar, params, ids, sz)
    head = params["head"]["kernel"]
    return (jnp.stack([ar.einsum("rtd,dv->rtv", h, head) for h in hs]),
            exit_probs(ar, params, hs))


def ut_loss_sum(ar: Arith, params: Params, block_: Dict[str, Array], sz: Dict[str, Any]) -> Array:
    """Sum over a block of rows, over the tokens that have a next token, of
    the expected exit's cross-entropy plus beta times ``sum_t p_t log p_t``."""
    ids, labels = block_["token_ids"], block_["labels"]
    valid = labels != common.IGNORE
    targets = jnp.where(valid, jnp.roll(ids, -1, axis=1), 0)
    hs = states(ar, params, ids, sz)
    p = exit_probs(ar, params, hs)

    @jax.checkpoint
    def exit_ce(h):
        return common.cross_entropy(ar.einsum("rtd,dv->rtv", h, params["head"]["kernel"]),
                                    targets)

    ce = jax.lax.map(exit_ce, jnp.stack(hs))
    per_token = jnp.sum(p * ce, axis=0) + sz["beta"] * jnp.sum(p * jnp.log(p), axis=0)
    return jnp.sum(jnp.where(valid, per_token, 0.0))
