"""Operations and bytes of the ``ouro`` family (a looped stack), counted from
shapes.

What the mathematics REQUIRES, as ``flops_lfm2_moe.py`` counts it: a causal
score matrix is its lower triangle; every one of the R x N block applications
counts, and each of the R exits (gate and head); recomputation under remat
never counts. Only contractions are counted (2 x multiply-adds): norms,
softmax, SiLU, rotary, the sigmoid of the gate and the embedding's gather are
left out. ``sz`` is the configuration as run (``configs/<config>.json``).

- a training step's operations, by part, for ``step_mfu_pct.train``
  (``train_flops_per_sample``, ``forward_parts``);
- each attention kernel's operations and HBM bytes for ONE execution (one
  block application), for its roofline reader (``KERNELS``); how many times a
  step runs it is asked of the trace (``components_ouro.executions``): under
  the scan, one HLO instruction runs R times a step.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.flops_lfm2_moe import BF16, F32, LANES, triangle


def applications(sz: Dict[str, Any]) -> int:
    """Block applications a step: every layer, once a loop step."""
    return sz["total_ut_steps"] * sz["num_hidden_layers"]


def forward_parts(sz: Dict[str, Any], t: int) -> Dict[str, float]:
    """Forward operations of ONE row of ``t`` tokens, by part."""
    d, h, kv, hd = (sz["hidden_size"], sz["num_attention_heads"],
                    sz["num_key_value_heads"], sz["head_dim"])
    n, exits = applications(sz), sz["total_ut_steps"]
    return {
        # q, k, v and the output projection
        "attention_projections": n * 2.0 * t * d * (2 * h + 2 * kv) * hd,
        # q k^T and p v over the causal triangle, every head
        "attention_kernels": n * 2.0 * triangle(t) * h * 2 * hd,
        # gate, up and down
        "ffn": n * 3 * 2.0 * t * d * sz["intermediate_size"],
        # every exit: position i predicts t_{i+1} (t-1 targets), and its gate
        "exit_heads": exits * 2.0 * (t - 1) * d * sz["vocab_size"],
        "exit_gates": exits * 2.0 * t * d,
    }


def train_flops_per_sample(sz: Dict[str, Any], t: int) -> float:
    """Forward + backward of one row: every contraction's backward costs
    twice its forward (both operands carry a gradient)."""
    return 3.0 * sum(forward_parts(sz, t).values())


# -- the attention kernels of a step ------------------------------------------
#
# name -> (operations, bytes) of one execution at batch b, rows t: ONE block
# application. The names are the kernels' ``name=`` in the program
# (ops/pallas_attention.py) and what the trace calls them. Keys and values
# count once a key/value head: here one per query head (a group of 1).


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
    statistics, writes dk and dv."""
    return _attention(sz, b, t, 4, 2, 4, 3)


KERNELS = {
    "fused_attention_fwd": attention_fwd,
    "fused_attention_dq": attention_dq,
    "fused_attention_dkv": attention_dkv,
}
