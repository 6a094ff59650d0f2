"""Operations and bytes of the ``decoder_lm`` family, counted from shapes.

Two kinds of count, both of what the mathematics REQUIRES (a causal score
matrix is its lower triangle, a routed expert sees its expected share of the
assignments; recomputation under remat never counts):

- a training step's operations, by part, for ``step_mfu_pct.train``
  (``train_flops_per_sample``, ``forward_parts``);
- each Pallas kernel's operations and HBM bytes for ONE execution, for its
  ``<kernel>_roofline_pct.train`` (``KERNELS``); how many times a step runs
  it is asked of the trace (``components_decoder_lm.executions``).

Only contractions are counted (2 x multiply-adds), like ``flops.py``: norms,
softmax, SiLU, rotary, sigmoid, top-k and the embedding's gather are left
out. ``sz`` is the configuration as run (``configs/<config>.json``): the
published keys, ``n_routed_experts`` being the experts HELD and
``deployment.n_routed_experts_published`` the router's width.
"""

from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4
LANES = 128  # the attention kernel keeps its softmax statistics lane-broadcast


def triangle(t: float) -> float:
    """Query-key pairs of a causal square: the diagonal and below."""
    return t * (t + 1) / 2.0


def routed_layers(sz: Dict[str, Any]) -> int:
    """Expert layers a step runs: the stack's and the MTP module's block."""
    return sz["num_hidden_layers"] - sz["first_k_dense_replace"] + sz["num_nextn_predict_layers"]


def attention_layers(sz: Dict[str, Any]) -> int:
    return sz["num_hidden_layers"] + sz["num_nextn_predict_layers"]


def held_assignments(sz: Dict[str, Any], tokens: float) -> float:
    """Expected (token, expert) assignments of the held experts: a uniform
    router sends each of ``tokens * top_k`` to a held expert with
    probability held / published."""
    return (tokens * sz["num_experts_per_tok"] * sz["n_routed_experts"]
            / sz["deployment"]["n_routed_experts_published"])


def forward_parts(sz: Dict[str, Any], t: int) -> Dict[str, float]:
    """Forward operations of ONE row of ``t`` tokens, by part."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    qk, dv = sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"], sz["v_head_dim"]
    w, v = sz["moe_intermediate_size"], sz["vocab_size"]
    mla = (2.0 * t * d * sz["q_lora_rank"] + 2.0 * t * sz["q_lora_rank"] * h * qk
           + 2.0 * t * d * (sz["kv_lora_rank"] + sz["qk_rope_head_dim"])
           + 2.0 * t * sz["kv_lora_rank"] * h * (sz["qk_nope_head_dim"] + dv)
           + 2.0 * triangle(t) * h * (qk + dv)
           + 2.0 * t * h * dv * d)
    mtp = sz["num_nextn_predict_layers"]
    return {
        "mla": attention_layers(sz) * mla,
        "dense_ffn": sz["first_k_dense_replace"] * 3 * 2.0 * t * d * sz["intermediate_size"],
        "shared_experts": routed_layers(sz) * sz["n_shared_experts"] * 3 * 2.0 * t * d * w,
        "routed_experts": routed_layers(sz) * 3 * 2.0 * held_assignments(sz, t) * d * w,
        "router": routed_layers(sz) * 2.0 * t * d * sz["deployment"]["n_routed_experts_published"],
        "mtp_projection": mtp * 2.0 * t * 2 * d * d,
        # position i predicts t_{i+1} (t-1 targets) and, in the MTP module, t_{i+2}
        "heads": 2.0 * ((t - 1) + mtp * (t - 2)) * d * v,
    }


def train_flops_per_sample(sz: Dict[str, Any], t: int) -> float:
    """Forward + backward of one row: every contraction's backward costs
    twice its forward (both operands carry a gradient)."""
    return 3.0 * sum(forward_parts(sz, t).values())


# -- the Pallas kernels of a step ---------------------------------------------
#
# name -> (operations, bytes) of one execution at batch b, rows t. The names
# are the kernels' ``name=`` in the program (ops/pallas_attention.py,
# ops/pallas_grouped_matmul.py) and what the trace calls them.


def _attention(sz, b, t, matmuls_qk: int, matmuls_v: int, reads: float, writes: float):
    h = sz["num_attention_heads"]
    qk, dv = sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"], sz["v_head_dim"]
    ops = 2.0 * b * h * triangle(t) * (matmuls_qk * qk + matmuls_v * dv)
    row = b * h * t
    return ops, BF16 * row * (reads + writes)


def attention_fwd(sz, b, t):
    """q k^T and p v; reads q, k, v, writes the output and the two statistics."""
    qk, dv = sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"], sz["v_head_dim"]
    ops, moved = _attention(sz, b, t, 1, 1, 2 * qk + dv, dv)
    return ops, moved + 2 * F32 * LANES * b * sz["num_attention_heads"] * t


def attention_dq(sz, b, t):
    """q k^T again, g v^T, ds k; reads q, k, v, g and three statistics, writes dq."""
    qk, dv = sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"], sz["v_head_dim"]
    ops, moved = _attention(sz, b, t, 2, 1, 2 * qk + 2 * dv, qk)
    return ops, moved + 3 * F32 * LANES * b * sz["num_attention_heads"] * t


def attention_dkv(sz, b, t):
    """q k^T again, g v^T, p^T g, ds^T q; writes dk and dv."""
    qk, dv = sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"], sz["v_head_dim"]
    ops, moved = _attention(sz, b, t, 2, 2, 2 * qk + 2 * dv, qk + dv)
    return ops, moved + 3 * F32 * LANES * b * sz["num_attention_heads"] * t


def grouped_matmul(sz, b, t):
    """One of the three products of the held experts' SwiGLUs (all are
    hidden x expert width): the expected assignments' rows in and out, and the
    held experts' weights once. The zeros written to the tiles no expert owns
    are not required bytes."""
    d, w, held = sz["hidden_size"], sz["moe_intermediate_size"], sz["n_routed_experts"]
    rows = held_assignments(sz, b * t)
    return 2.0 * rows * d * w, BF16 * (rows * (d + w) + held * d * w)


def grouped_matmul_transposed(sz, b, t):
    """The weight gradient of one such product: both row buffers in, a float32
    gradient of every held expert's weights out."""
    d, w, held = sz["hidden_size"], sz["moe_intermediate_size"], sz["n_routed_experts"]
    rows = held_assignments(sz, b * t)
    return 2.0 * rows * d * w, BF16 * rows * (d + w) + F32 * held * d * w


KERNELS = {
    "fused_attention_fwd": attention_fwd,
    "fused_attention_dq": attention_dq,
    "fused_attention_dkv": attention_dkv,
    "grouped_matmul": grouped_matmul,
    "grouped_matmul_transposed": grouped_matmul_transposed,
}
