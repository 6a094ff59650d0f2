"""The plain float32 reference of the ``decoder_lm`` family: a causal decoder
with multi-head latent attention (MLA), a routed expert layer that is told
which experts it holds, and one multi-token-prediction (MTP) module, as the
DeepSeek-V3 family of public ``config.json``s describes it (``joyai_llm_flash``
is one). Over ``common.py`` only; nothing of ``perceiver_io_tpu``.

Straightforward on purpose: a Python loop over the layers (each under
``jax.checkpoint``, which changes what is kept and not what is computed),
attention in blocks of queries against the keys up to the block's end with a
dense mask on the diagonal block, and DENSE per-expert arithmetic: every held
expert's SwiGLU runs on every token and is weighted by the token's gate for
that expert (0 where it was not selected). No sort, no gather of rows, no
kernel, no capacity. Every contraction goes through ``arith.einsum``.

Equations (x is (rows, T, D); RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale):

- block: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; FFN is a
  SwiGLU ``(silu(x Wg) * (x Wu)) Wd`` in the first ``first_k_dense_replace``
  layers and the expert layer after.
- MLA: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads x [q_nope | q_rope];
  ``[c_kv | k_rope] = x W_kva``; ``RMSNorm(c_kv) W_kvb`` -> heads x [k_nope | v];
  rotary on interleaved pairs (pair i of a position p turned by ``p *
  theta^(-2i/d)``) of q_rope and of the one k_rope all heads share; scores
  ``(q_nope . k_nope + q_rope . k_rope) / sqrt(d_nope + d_rope)``, causal
  softmax, ``concat_h(P v) W_o``.
- expert layer: ``s = sigmoid(x W_r)`` over ALL experts; the ``top_k`` largest
  of ``s + b`` (b: the selection bias, no gradient); ``g = scale * s_sel /
  (sum s_sel + 1e-20)``; ``y = sum over the HELD experts e of g_e SwiGLU_e(x) +
  SwiGLU_shared(x)``. What the experts that are not held would add is left
  out (the chip's share of a deployment, ``model-configs`` guide section 4).
- MTP: ``h' = [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)] W_eh``, one block of
  the expert kind, a final RMSNorm of its own, the shared head; predicts
  ``t_{i+2}``. ``h_i``: the main stack's output after its final norm.
- loss of a batch of full rows: ``mean CE_main + factor * mean CE_mtp``. A
  block returns ``sum CE_main + factor * (T-1)/(T-2) * sum CE_mtp``, which the
  caller divides by the batch's ``rows * (T-1)``: both means have fixed counts,
  so the sum carries their ratio.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import common
from benchmarks.reference.common import Arith, Array, Params

QUERY_BLOCK = 512


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotate_pairs(x: Array, theta: float) -> Array:
    """Rotary position embedding on (rows, T, heads, d): the pair (2i, 2i+1)
    of position p is multiplied, as a complex number, by ``exp(1j * p *
    theta^(-2i/d))``."""
    t, d = x.shape[1], x.shape[-1]
    freq = jnp.exp(-math.log(theta) * jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    re, im = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    turned = jnp.stack([re * cos - im * sin, re * sin + im * cos], axis=-1)
    return turned.reshape(x.shape)


def latent_attention(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    r, t, _ = x.shape
    h, nope, rope, dv = sz["heads"], sz["nope"], sz["rope"], sz["v"]
    c_q = rms_norm(ar.einsum("rtd,dq->rtq", x, p["q_a"]["kernel"]), p["q_a_norm"]["scale"], sz["eps"])
    q = ar.einsum("rtq,qe->rte", c_q, p["q_b"]["kernel"]).reshape(r, t, h, nope + rope)
    kv_a = ar.einsum("rtd,dc->rtc", x, p["kv_a"]["kernel"])
    c_kv = rms_norm(kv_a[..., :sz["kv_rank"]], p["kv_a_norm"]["scale"], sz["eps"])
    kv = ar.einsum("rtc,ce->rte", c_kv, p["kv_b"]["kernel"]).reshape(r, t, h, nope + dv)
    q_nope, q_rope = q[..., :nope], rotate_pairs(q[..., nope:], sz["theta"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = rotate_pairs(kv_a[..., sz["kv_rank"]:][:, :, None, :], sz["theta"])[:, :, 0, :]
    scale = 1.0 / math.sqrt(nope + rope)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        scores = (ar.einsum("rthd,rshd->rhts", q_nope[:, lo:hi], k_nope[:, :hi])
                  + ar.einsum("rthd,rsd->rhts", q_rope[:, lo:hi], k_rope[:, :hi])) * scale
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(ar.einsum("rhts,rshd->rthd", probs, v[:, :hi]))
    out = jnp.concatenate(out, axis=1).reshape(r, t, h * dv)
    return ar.einsum("rte,ed->rtd", out, p["o"]["kernel"])


def swiglu(ar: Arith, p: Params, x: Array) -> Array:
    hidden = (jax.nn.silu(ar.einsum("rtd,dw->rtw", x, p["gate"]["kernel"]))
              * ar.einsum("rtd,dw->rtw", x, p["up"]["kernel"]))
    return ar.einsum("rtw,wd->rtd", hidden, p["down"]["kernel"])


def expert_layer(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    held, offset = sz["experts_held"], sz["expert_offset"]
    scores = jax.nn.sigmoid(ar.einsum("rtd,de->rte", x, p["router"]["kernel"]))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["e_score_correction_bias"]),
                              sz["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = sz["scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    # (rows, T, held): a held expert's gate for each token, 0 where not selected
    mine = chosen[..., None] == (offset + jnp.arange(held))
    weight = jnp.sum(jnp.where(mine, gates[..., None], 0.0), axis=-2)
    hidden = (jax.nn.silu(ar.einsum("rtd,edw->ertw", x, p["experts_gate"]["kernel"]))
              * ar.einsum("rtd,edw->ertw", x, p["experts_up"]["kernel"]))
    each = ar.einsum("ertw,ewd->ertd", hidden, p["experts_down"]["kernel"])
    routed = ar.einsum("rte,ertd->rtd", weight, each)
    return routed + swiglu(ar, p["shared_expert"], x)


def block(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    h = x + latent_attention(ar, p["attn"], rms_norm(x, p["attn_norm"]["scale"], sz["eps"]), sz)
    inner = rms_norm(h, p["ffn_norm"]["scale"], sz["eps"])
    if "mlp" in p:
        return h + swiglu(ar, p["mlp"], inner)
    return h + expert_layer(ar, p["moe"], inner, sz)


def hidden_states(ar: Arith, params: Params, ids: Array, sz: Dict[str, Any]):
    """``(h, h_mtp)``: the outputs of the main stack and of the MTP module
    after their final norms, each (rows, T, D)."""
    layer = jax.checkpoint(lambda p, x: block(ar, p, x, sz))
    x = params["embed"]["embedding"][ids]
    for i in range(sz["layers"]):
        x = layer(params[f"layer_{i}"], x)
    h = rms_norm(x, params["final_norm"]["scale"], sz["eps"])
    following = params["embed"]["embedding"][jnp.roll(ids, -1, axis=1)]
    joined = jnp.concatenate([rms_norm(following, params["mtp_enorm"]["scale"], sz["eps"]),
                              rms_norm(h, params["mtp_hnorm"]["scale"], sz["eps"])], axis=-1)
    x = layer(params["mtp_block"], ar.einsum("rte,ed->rtd", joined, params["mtp_eh_proj"]["kernel"]))
    return h, rms_norm(x, params["mtp_final_norm"]["scale"], sz["eps"])


def logits(ar: Arith, params: Params, ids: Array, sz: Dict[str, Any]):
    """``(main, mtp)`` logits, each (rows, T, vocab): ``main[:, i]`` scores
    ``t_{i+1}``, ``mtp[:, i]`` scores ``t_{i+2}``."""
    h, h_mtp = hidden_states(ar, params, ids, sz)
    head = params["head"]["kernel"]
    return ar.einsum("rtd,dv->rtv", h, head), ar.einsum("rtd,dv->rtv", h_mtp, head)


def _ce_sum_ahead(ar: Arith, hidden: Array, head: Array, ids: Array, ahead: int) -> Array:
    """Sum of the CE of positions 0 .. T-ahead-1 against the token ``ahead`` on."""
    t = ids.shape[1]
    scores = ar.einsum("rtd,dv->rtv", hidden[:, :t - ahead], head)
    return jnp.sum(common.cross_entropy(scores, ids[:, ahead:]))


def lm_ce_sum(ar: Arith, params: Params, block_: Dict[str, Array], sz: Dict[str, Any]) -> Array:
    """``sum CE_main + factor * (T-1)/(T-2) * sum CE_mtp`` over a block of full
    rows (the docstring's last item)."""
    ids = block_["token_ids"]
    t = ids.shape[1]
    h, h_mtp = hidden_states(ar, params, ids, sz)
    head = params["head"]["kernel"]
    main = jax.checkpoint(lambda hd, w: _ce_sum_ahead(ar, hd, w, ids, 1))(h, head)
    mtp = jax.checkpoint(lambda hd, w: _ce_sum_ahead(ar, hd, w, ids, 2))(h_mtp, head)
    return main + sz["mtp_loss_factor"] * (t - 1) / (t - 2) * mtp
