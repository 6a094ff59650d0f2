"""Operations and bytes of the ``sdar_moe`` family trained by block
diffusion, counted from shapes.

What the mathematics REQUIRES, as ``flops_nemotron_h.py`` counts it: a row of
L tokens runs as the 2L positions ``[x_t ; x]``; the block-diffusion score
matrix is its VISIBLE pairs (``visible_pairs``: a noised token's own block, the
clean blocks before it, a clean token's clean blocks up to its own: L (L + B)
of the 4 L^2), not the causal triangle of 2L nor the square; a routed expert
sees its expected share of the assignments of all 2L positions; the head and
its loss are the noised half's L positions; recomputation under remat never
counts. Only contractions are counted (2 x multiply-adds): norms, softmax,
SiLU, top-k, the embedding's gather and the noise are left out. ``sz`` is the
configuration as run (``configs/<config>.json``): ``num_experts`` the experts
HELD and ``deployment.num_experts_published`` the router's width.

- a training step's operations, by part, for ``step_mfu_pct.train``
  (``train_flops_per_sample``, ``forward_parts``);
- each attention kernel's operations and HBM bytes for ONE execution, for its
  roofline reader (``KERNELS``); how many times a step runs it is asked of
  the trace (``components_decoder_lm.executions``).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.flops_lfm2_moe import BF16, F32, LANES


def visible_pairs(length: int, block: int) -> float:
    """Query-key pairs the block-diffusion mask shows for a row of ``length``
    tokens in blocks of ``block`` (a multiple): noised to noised ``L B``,
    noised to clean ``L (L - B) / 2``, clean to clean ``L (L + B) / 2``."""
    return float(length * block + length * (length - block) / 2 + length * (length + block) / 2)


def held_assignments(sz: Dict[str, Any], positions: float) -> float:
    """Expected (position, expert) assignments of the held experts."""
    return (positions * sz["num_experts_per_tok"] * sz["num_experts"]
            / sz["deployment"]["num_experts_published"])


def forward_parts(sz: Dict[str, Any], t: int) -> Dict[str, float]:
    """Forward operations of ONE row of ``t`` tokens (2t positions), by part."""
    d, h, kv, hd = (sz["hidden_size"], sz["num_attention_heads"],
                    sz["num_key_value_heads"], sz["head_dim"])
    layers, positions = sz["num_hidden_layers"], 2 * t
    return {
        # q, k, v and the output projection
        "attention_projections": layers * 2.0 * positions * d * (2 * h + 2 * kv) * hd,
        # q k^T and p v over the visible pairs, every query head
        "attention_kernels": layers * 2.0 * visible_pairs(t, sz["block_length"]) * h * 2 * hd,
        # gate, up and down
        "routed_experts": layers * 3 * 2.0 * held_assignments(sz, positions) * d
        * sz["moe_intermediate_size"],
        "router": layers * 2.0 * positions * d * sz["deployment"]["num_experts_published"],
        # the noised half's t positions each score their own token
        "head": 2.0 * t * d * sz["vocab_size"],
    }


def train_flops_per_sample(sz: Dict[str, Any], t: int) -> float:
    """Forward + backward of one row: every contraction's backward costs
    twice its forward (both operands carry a gradient)."""
    return 3.0 * sum(forward_parts(sz, t).values())


# -- the attention kernels of a step ------------------------------------------
#
# name -> (operations, bytes) of one execution at batch b, rows of t tokens
# (2t positions). The names are the kernels' ``name=`` in the program
# (ops/pallas_attention.py) and what the trace calls them. Keys and values
# (and their gradients) count once a GROUP of query heads.


def _attention(sz, b, t, matmuls: int, per_query_head: float, per_kv_head: float,
               statistics: int):
    h, kv, hd = sz["num_attention_heads"], sz["num_key_value_heads"], sz["head_dim"]
    positions = 2 * t
    ops = 2.0 * b * h * visible_pairs(t, sz["block_length"]) * matmuls * hd
    moved = BF16 * b * positions * hd * (h * per_query_head + kv * per_kv_head)
    return ops, moved + statistics * F32 * LANES * b * h * positions


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


KERNELS = {
    "fused_attention_fwd": attention_fwd,
    "fused_attention_dq": attention_dq,
    "fused_attention_dkv": attention_dkv,
}
