"""Operations and bytes of the ``nemotron_h`` family, counted from shapes.

Two kinds of count, both of what the mathematics REQUIRES (a causal score
matrix is its lower triangle, a routed expert sees its expected share of the
assignments, the state-space scan is the recurrence's state update and
read-out and not what a chunked algorithm does to compute them; recomputation
under remat never counts):

- a training step's operations, by part, for ``step_mfu_pct.train``
  (``train_flops_per_sample``, ``forward_parts``);
- each Pallas kernel's operations and HBM bytes for ONE execution, for its
  roofline reader (``KERNELS``); how many times a step runs it is asked of
  the trace (``components_decoder_lm.executions``);
- the scan's operations and bytes for one layer's forward AND backward
  (``ssd_scan``), counted as the RECURRENCE needs them, so that whatever
  algorithm or kernel computes it is read against the same work.

Only contractions are counted (2 x multiply-adds), like ``flops.py``: norms,
softmax, SiLU, squared ReLU, softplus, sigmoid, top-k, the embedding's gather,
the taps of the convolution, the gate and the ``D`` term are left out. ``sz``
is the configuration as run (``configs/<config>.json``): the published keys,
``n_routed_experts`` being the experts HELD and
``deployment.n_routed_experts_published`` the router's width.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.flops_lfm2_moe import BF16, F32, LANES, triangle


def blocks_of(sz: Dict[str, Any], kind: str) -> int:
    """Blocks of ``kind`` (a character of ``hybrid_override_pattern``)."""
    return sz["hybrid_override_pattern"].count(kind)


def held_assignments(sz: Dict[str, Any], tokens: float) -> float:
    """Expected (token, expert) assignments of the held experts: a uniform
    router sends each of ``tokens * top_k`` to a held expert with
    probability held / published."""
    return (tokens * sz["num_experts_per_tok"] * sz["n_routed_experts"]
            / sz["deployment"]["n_routed_experts_published"])


def state_size(sz: Dict[str, Any]) -> int:
    """Numbers in one layer's state: heads x a head's channels x ``ssm_state_size``."""
    return sz["mamba_num_heads"] * sz["mamba_head_dim"] * sz["ssm_state_size"]


def forward_parts(sz: Dict[str, Any], t: int) -> Dict[str, float]:
    """Forward operations of ONE row of ``t`` tokens, by part."""
    d, h, kv, hd = (sz["hidden_size"], sz["num_attention_heads"],
                    sz["num_key_value_heads"], sz["head_dim"])
    inner = sz["mamba_num_heads"] * sz["mamba_head_dim"]
    in_proj = 2 * inner + 2 * sz["n_groups"] * sz["ssm_state_size"] + sz["mamba_num_heads"]
    mamba, attention, experts = blocks_of(sz, "M"), blocks_of(sz, "*"), blocks_of(sz, "E")
    return {
        "mamba_projections": mamba * 2.0 * t * d * (in_proj + inner),
        # the state's update and its read-out, a multiply-add a state element each
        "ssd_scan": mamba * 2.0 * t * 2 * state_size(sz),
        # q, k, v and the output projection
        "attention_projections": attention * 2.0 * t * d * (2 * h + 2 * kv) * hd,
        # q k^T and p v over the causal triangle, every query head
        "attention_kernels": attention * 2.0 * triangle(t) * h * 2 * hd,
        # up and down: the experts are not gated
        "routed_experts": experts * 2 * 2.0 * held_assignments(sz, t) * d
        * sz["moe_intermediate_size"],
        "shared_expert": experts * sz["n_shared_experts"] * 2 * 2.0 * t * d
        * sz["moe_shared_expert_intermediate_size"],
        "router": experts * 2.0 * t * d * sz["deployment"]["n_routed_experts_published"],
        # position i predicts t_{i+1}: t-1 targets
        "head": 2.0 * (t - 1) * d * sz["vocab_size"],
    }


def train_flops_per_sample(sz: Dict[str, Any], t: int) -> float:
    """Forward + backward of one row: every contraction's backward costs
    twice its forward (both operands carry a gradient)."""
    return 3.0 * sum(forward_parts(sz, t).values())


def ssd_scan(sz: Dict[str, Any], b: int, t: int):
    """(operations, bytes) of ONE Mamba-2 layer's scan, forward and backward,
    as the recurrence needs them. Operations: the state update and the
    read-out, a multiply-add a state element each, a token; three times for
    forward and backward. Bytes: ``x``, ``B``, ``C`` (the compute dtype) and
    ``Delta`` (float32) in and ``y`` out once, and their cotangents once; a
    state is never written to memory."""
    heads, p = sz["mamba_num_heads"], sz["mamba_head_dim"]
    bc = sz["n_groups"] * sz["ssm_state_size"]
    ops = 3 * 2.0 * b * t * 2 * state_size(sz)
    moved = 2 * b * t * (BF16 * (2 * heads * p + 2 * bc) + F32 * heads)
    return ops, moved


# -- the Pallas kernels of a step ---------------------------------------------
#
# name -> (operations, bytes) of one execution at batch b, rows t. The names
# are the kernels' ``name=`` in the program (ops/pallas_attention.py,
# ops/pallas_grouped_matmul.py) and what the trace calls them. Keys and values
# (and their gradients) count once a GROUP of 16 query heads: the program holds
# them with ``num_key_value_heads`` heads and never repeats them.


def _attention(sz, b, t, matmuls: int, per_query_head: float, per_kv_head: float,
               statistics: int):
    h, kv, hd = sz["num_attention_heads"], sz["num_key_value_heads"], sz["head_dim"]
    ops = 2.0 * b * h * triangle(t) * matmuls * hd
    moved = BF16 * b * t * hd * (h * per_query_head + kv * per_kv_head)
    return ops, moved + statistics * F32 * LANES * b * h * t


def attention_fwd(sz, b, t):
    """q k^T and p v; reads q, k, v, writes the output and the two statistics."""
    return _attention(sz, b, t, 2, 2, 2, 2)


def attention_dq(sz, b, t):
    """q k^T again, g v^T, ds k; reads q, g, k, v and three statistics, writes dq."""
    return _attention(sz, b, t, 3, 3, 2, 3)


def attention_dkv(sz, b, t):
    """q k^T again, g v^T, p^T g, ds^T q; reads q, g, k, v and three
    statistics, writes dk and dv (a key/value head's, summed over its group)."""
    return _attention(sz, b, t, 4, 2, 4, 3)


def grouped_matmul(sz, b, t):
    """One of the two products of the held experts' FFNs (both are hidden x
    expert width): the expected assignments' rows in and out, and the held
    experts' weights once. The zeros written to the tiles no expert owns are
    not required bytes."""
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
