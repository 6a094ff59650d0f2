"""Operations a Perceiver IO step REQUIRES, counted from shapes.

Only contractions are counted (2 x multiply-adds): projections, attention
scores and weighted sums, MLPs, the output head. Layer norms, softmax, GELU,
the embedding gather and the optimizer are left out (well under 1% at these
widths). Nothing is counted twice that the mathematics needs once: the
encoder's shared layers 2..n project K/V of the same input with the same
weights, so that projection counts once however the program schedules it, and
rematerialised operations never count. A training step is forward + backward:
a contraction's backward costs twice its forward (one product for each
operand's gradient), except where an operand needs no gradient (raw image
pixels and their position features).

All functions return operations for ONE sample.
"""

from __future__ import annotations


def linear(rows: float, n_in: int, n_out: int) -> float:
    return 2.0 * rows * n_in * n_out


def attention_core(t: float, s: float, width: int) -> float:
    """Scores (T x S over ``width`` = heads x depth) and the weighted sum."""
    return 2.0 * (2.0 * t * s * width)


def cross_attention_layer(t: float, s: float, c_q: int, c_kv: int,
                          project_kv: bool = True) -> dict:
    """Forward operations of one cross-attention layer on T queries and S
    keys, split by whether the backward pass needs both operands' gradients
    (``two_sided``) or only the weights' (``kv_in``: the K/V projections,
    whose input may be raw data)."""
    two_sided = (linear(t, c_q, c_q)            # q projection
                 + attention_core(t, s, c_q)
                 + linear(t, c_q, c_q)          # output projection
                 + 2 * linear(t, c_q, c_q))     # MLP, constant width
    kv_in = 2 * linear(s, c_kv, c_q) if project_kv else 0.0
    return {"two_sided": two_sided, "kv_in": kv_in}


def self_attention_layer(n: float, c: int) -> float:
    return (3 * linear(n, c, c) + attention_core(n, n, c) + linear(n, c, c)
            + 2 * linear(n, c, c))


def perceiver_io(*, input_positions: float, input_channels: int,
                 num_latents: int, num_channels: int, num_encoder_layers: int,
                 num_self_attention_layers_per_block: int,
                 output_queries: float, output_classes: int,
                 input_needs_grad: bool, training: bool) -> float:
    """Operations per sample of encoder + decoder + linear head.

    ``output_queries``: how many output positions the task needs decoded
    (for MLM the selected positions, for a classifier 1)."""
    n, c = num_latents, num_channels
    two_sided = 0.0
    kv_in = 0.0
    for layer in range(num_encoder_layers):
        # layers 2..n share weights AND input: their K/V projection is one
        part = cross_attention_layer(n, input_positions, c, input_channels,
                                     project_kv=layer < 2)
        two_sided += part["two_sided"]
        kv_in += part["kv_in"]
        two_sided += num_self_attention_layers_per_block * self_attention_layer(n, c)
    dec = cross_attention_layer(output_queries, n, c, c)
    two_sided += dec["two_sided"] + dec["kv_in"]
    two_sided += linear(output_queries, c, output_classes)
    if not training:
        return two_sided + kv_in
    return 3.0 * two_sided + (3.0 if input_needs_grad else 2.0) * kv_in
