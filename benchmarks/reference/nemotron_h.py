"""The plain float32 reference of the ``nemotron_h`` family: the Nemotron-H
stack as Nemotron-Labs-TwoTower-30B-A3B-Base's ``config.json`` configures it,
a causal decoder of ONE sublayer a block (a Mamba-2 state-space mixer,
grouped-query attention without positions, or a routed expert layer told
which experts it holds, beside a shared expert), an untied output head. Over
``common.py`` only; nothing of ``perceiver_io_tpu``, and of the other
references their plain functions ``rms_norm`` and the causal query block.

Straightforward on purpose: a Python loop over the blocks (each under
``jax.checkpoint``, which changes what is kept and not what is computed); the
state-space recurrence TOKEN BY TOKEN (``lax.scan`` over the row, a
``jax.checkpoint`` every ``SCAN_SEGMENT`` tokens so that a row keeps one
state a segment and not one a token: it shares no algorithm with the
program's chunked form); the convolution as an explicit sum over its taps;
attention in checkpointed blocks of queries with K and V REPEATED to the
query heads; DENSE per-expert arithmetic: every held expert runs on every
token and is weighted by the token's gate for that expert (0 where it was not
selected); the shared expert once. Every contraction goes through
``arith.einsum``.

Equations (x is (rows, T, D); RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale):

- block ``l``: ``y = x + Sub_l(RMSNorm(x))``; what ``Sub_l`` is follows from
  the parameters the block holds.
- ``mamba``: ``[z | xBC | dt] = u W_in``; ``xBC_t <- silu(b + sum_{j < L} k_j *
  xBC_{t-(L-1)+j})`` (one tap vector and one bias a channel, zeros before the
  row's first token); ``[x | B | C] = xBC`` with x as H heads of P and B, C as
  G groups of N (head h reads group ``h // (H / G)``); ``Delta_t = softplus(dt_t
  + dt_bias)``, ``A = -exp(A_log)``, one of each a head; a head's state, P x N,
  starts at 0: ``S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T``, ``y_t = S_t
  C_t + D x_t``; then ``y <- y * silu(z)``, an RMSNorm over each of the G groups
  of channels on its own times one learned scale, and ``out = y W_out``.
- ``attn``: ``q = u W_q`` as H heads, ``k = u W_k`` and ``v = u W_v`` as Hkv
  heads of d; query head h reads key/value head ``h // (H / Hkv)``; scores ``/
  sqrt(d)``, causal softmax, ``concat_h(P v) W_o``. No norm of queries or keys
  and no position encoding.
- ``moe``: ``s = sigmoid(u W_r)`` over ALL experts; the ``top_k`` largest of ``s
  + b`` (b: the selection bias, no gradient); ``g = scale * s_sel / (sum s_sel +
  1e-20)``; ``y = sum over the HELD experts e of g_e down_e(relu(up_e(u))^2) +
  down_s(relu(up_s(u))^2)``, the last term the shared expert, on every token.
- a final RMSNorm; logits ``h W_head``; loss of a batch of full rows: mean
  next-token cross-entropy. A block returns the SUM, which the caller divides
  by the batch's ``rows * (T - 1)``.

Departures from the published model, each stated in the configuration's
``not_included`` or ``assumed`` too: the SECOND (denoiser) TOWER that the
model's card describes (adaptive norms, attention that is bidirectional inside
a block of tokens, conditioning across the towers, a diffusion loss and
generation by diffusion over blocks) is absent: ``config.json`` has no key for
it, and this is the language model its keys define, trained by next-token
cross-entropy; no position encoding and no query/key norm (the family's public
implementation applies none); the selection bias is never moved by a balance
rule; no auxiliary loss; what the experts that are not held would add is left
out (the chip's share of a deployment, ``model-configs`` guide section 4), and
the vocabulary may be a slice.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import common
from benchmarks.reference.common import Arith, Array, Params
from benchmarks.reference.decoder_lm import rms_norm
from benchmarks.reference.lfm2_moe import QUERY_BLOCK, _attend

SCAN_SEGMENT = 128  # tokens between two kept states of the recurrence
GATE_EPS = 1e-20


def recurrence(ar: Arith, x: Array, delta: Array, a: Array, b: Array, c: Array) -> Array:
    """The state-space recurrence, one token after another. ``x`` (rows, T,
    G, J, P): head (g, j) of P channels; ``delta`` (rows, T, G, J); ``a`` (G,
    J); ``b``, ``c`` (rows, T, G, N). Returns ``S_t C_t`` as (rows, T, G, J,
    P), with ``S_t = exp(delta_t a) S_{t-1} + delta_t x_t b_t^T`` from ``S =
    0``."""
    rows, t, g, j, p = x.shape

    def token(state, at):
        x_t, delta_t, b_t, c_t = at
        keep = jnp.exp(delta_t * a)[..., None, None]
        state = keep * state + (delta_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        return state, ar.einsum("rgjpn,rgn->rgjp", state, c_t)

    @jax.checkpoint
    def segment(state, tokens):
        return jax.lax.scan(token, state, tokens)

    def time_major(v, lo, hi):
        return jnp.moveaxis(v[:, lo:hi], 1, 0)

    whole = t - t % SCAN_SEGMENT
    state = jnp.zeros((rows, g, j, p, b.shape[-1]), jnp.float32)
    out = []
    if whole:
        segments = tuple(time_major(v, 0, whole).reshape(-1, SCAN_SEGMENT, *v.shape[:1], *v.shape[2:])
                         for v in (x, delta, b, c))
        state, y = jax.lax.scan(segment, state, segments)
        out.append(y.reshape(whole, rows, g, j, p))
    if t > whole:
        _, y = segment(state, tuple(time_major(v, whole, t) for v in (x, delta, b, c)))
        out.append(y)
    return jnp.moveaxis(jnp.concatenate(out, axis=0), 0, 1)


def causal_conv(z: Array, taps: Array, bias: Array) -> Array:
    """``bias + sum_j taps[j] * z[t - (L - 1) + j]``, zeros before the row."""
    t, length = z.shape[1], taps.shape[0]
    out = jnp.zeros_like(z) + bias
    for j in range(length):
        back = length - 1 - j  # tap j reads the token ``back`` positions earlier
        shifted = jnp.concatenate([jnp.zeros_like(z[:, :back]), z[:, :t - back]], axis=1)
        out = out + taps[j] * shifted
    return out


def gated_group_norm(y: Array, z: Array, scale: Array, groups: int, eps: float) -> Array:
    gated = y * jax.nn.silu(z)
    out = []
    for group in jnp.split(gated, groups, axis=-1):
        out.append(group / jnp.sqrt(jnp.mean(jnp.square(group), axis=-1, keepdims=True) + eps))
    return jnp.concatenate(out, axis=-1) * scale


def mamba2_mixer(ar: Arith, p: Params, u: Array, sz: Dict[str, Any]) -> Array:
    rows, t, _ = u.shape
    h, pd, g, n = sz["mamba_heads"], sz["mamba_head_dim"], sz["groups"], sz["state"]
    inner, bc, j = h * pd, g * n, h // g
    z, xbc, dt = jnp.split(ar.einsum("rtd,de->rte", u, p["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv1d"]["kernel"], p["conv1d"]["bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    x = x.reshape(rows, t, g, j, pd)
    delta = jax.nn.softplus(dt + p["dt_bias"]).reshape(rows, t, g, j)
    a = -jnp.exp(p["A_log"]).reshape(g, j)
    y = recurrence(ar, x, delta, a, b.reshape(rows, t, g, n), c.reshape(rows, t, g, n))
    y = y + p["D"].reshape(g, j, 1) * x
    y = gated_group_norm(y.reshape(rows, t, inner), z, p["norm"]["scale"], g, sz["eps"])
    return ar.einsum("rte,ed->rtd", y, p["out_proj"]["kernel"])


def grouped_query_attention(ar: Arith, p: Params, u: Array, sz: Dict[str, Any]) -> Array:
    r, t, _ = u.shape
    h, kv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = ar.einsum("rtd,de->rte", u, p["q_proj"]["kernel"]).reshape(r, t, h, d)
    k = ar.einsum("rtd,de->rte", u, p["k_proj"]["kernel"]).reshape(r, t, kv, d)
    v = ar.einsum("rtd,de->rte", u, p["v_proj"]["kernel"]).reshape(r, t, kv, d)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        block_ = jax.checkpoint(lambda q_, k_, v_, lo=lo: _attend(ar, q_, k_, v_, lo))
        out.append(block_(q[:, lo:hi], k[:, :hi], v[:, :hi]))
    out = jnp.concatenate(out, axis=1).reshape(r, t, h * d)
    return ar.einsum("rte,ed->rtd", out, p["out_proj"]["kernel"])


def routed_experts(ar: Arith, p: Params, u: Array, sz: Dict[str, Any]) -> Array:
    """What the HELD experts add (the shared expert is not in it)."""
    held, offset = sz["experts_held"], sz["expert_offset"]
    scores = jax.nn.sigmoid(ar.einsum("rtd,de->rte", u, p["router"]["kernel"]))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["expert_bias"]["scale"]),
                              sz["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = sz["scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + GATE_EPS)
    # (rows, T, held): a held expert's gate for each token, 0 where not selected
    mine = chosen[..., None] == (offset + jnp.arange(held))
    weight = jnp.sum(jnp.where(mine, gates[..., None], 0.0), axis=-2)
    hidden = jnp.square(jax.nn.relu(ar.einsum("rtd,edw->ertw", u, p["experts_up"]["kernel"])))
    each = ar.einsum("ertw,ewd->ertd", hidden, p["experts_down"]["kernel"])
    return ar.einsum("rte,ertd->rtd", weight, each)


def shared_expert(ar: Arith, p: Params, u: Array) -> Array:
    hidden = jnp.square(jax.nn.relu(ar.einsum("rtd,dw->rtw", u, p["up"]["kernel"])))
    return ar.einsum("rtw,wd->rtd", hidden, p["down"]["kernel"])


def expert_layer(ar: Arith, p: Params, u: Array, sz: Dict[str, Any]) -> Array:
    return routed_experts(ar, p, u, sz) + shared_expert(ar, p["shared_expert"], u)


def block(ar: Arith, p: Params, x: Array, sz: Dict[str, Any]) -> Array:
    """One block of one sublayer; which follows from the parameters it holds."""
    inner = rms_norm(x, p["norm"]["scale"], sz["eps"])
    if "mamba" in p:
        return x + mamba2_mixer(ar, p["mamba"], inner, sz)
    if "attn" in p:
        return x + grouped_query_attention(ar, p["attn"], inner, sz)
    return x + expert_layer(ar, p["moe"], inner, sz)


def hidden_states(ar: Arith, params: Params, ids: Array, sz: Dict[str, Any]) -> Array:
    """The stack's output after its final norm, (rows, T, D)."""
    layer = jax.checkpoint(lambda p, x: block(ar, p, x, sz))
    x = params["embed"]["embedding"][ids]
    for i in range(sz["layers"]):
        x = layer(params[f"layer_{i}"], x)
    return rms_norm(x, params["final_norm"]["scale"], sz["eps"])


def logits(ar: Arith, params: Params, ids: Array, sz: Dict[str, Any]) -> Array:
    """(rows, T, vocab): ``logits[:, i]`` scores ``t_{i+1}``."""
    return ar.einsum("rtd,dv->rtv", hidden_states(ar, params, ids, sz),
                     params["head"]["kernel"])


def lm_ce_sum(ar: Arith, params: Params, block_: Dict[str, Array], sz: Dict[str, Any]) -> Array:
    """Sum of the next-token cross-entropies of a block of full rows."""
    ids = block_["token_ids"]
    h = hidden_states(ar, params, ids, sz)

    def ce_sum(hidden, head):
        scores = ar.einsum("rtd,dv->rtv", hidden[:, :-1], head)
        return jnp.sum(common.cross_entropy(scores, ids[:, 1:]))

    return jax.checkpoint(ce_sum)(h, params["head"]["kernel"])
