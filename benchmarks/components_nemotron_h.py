"""Which scopes make which component of a ``nemotron_h`` training step, for
its ``*_device_ms.train`` readers, the scan's reader and roofline, and the
family's kernel readers (the family's own table, beside
``components_decoder_lm.py`` and ``components_lfm2_moe.py``).

A scope is the path an operation was traced under (``trace.py``); the program
names its parts with ``jax.named_scope`` and flax module names
(``models/decoder_lm.py``: the three families run one skeleton, so ``moe/*``,
``embed`` and ``head_loss`` are the SAME scopes in all, and the accepted
readers ``moe_device_ms.train`` and ``embed_head_loss_device_ms.train`` read
this family's step right: a test holds the tables to that). The patterns are
tried IN ORDER and an operation belongs to the first that matches, so the
components are disjoint and add up to the step: the Mamba-2 mixers
(projections, convolution, the scan, gate and norm), the attention block
(projections and the three causal kernels), the expert layers (shared expert
included), the embedding and the head with its loss. ``nemotron_h_other`` is
what matches none: block norms, residual adds, the optimizer, casts, and
every operation without a scope (``copy-done`` / ``slice-done``, and the
``conditional``s' own events). The scan (``mamba2/ssd_scan``) is a PART of
``mamba2``, read on its own.

Nothing here reads what a parent of this family's program lacks: on a
program without the ``mamba2`` scope every reader returns None.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks import trace
from benchmarks.components_decoder_lm import executions

NEMOTRON_H_STEP = (
    ("mamba2", r"/mamba2/"),
    ("gqa_attention", r"/gqa_attention/"),
    ("moe", r"/moe/"),
    ("embed_head_loss", r"/(embed|head_loss)/"),
)
OTHER = "nemotron_h_other"
SSD_SCAN = r"/mamba2/ssd_scan(/|$)"


def component_of(scope: str) -> str:
    for name, pattern in NEMOTRON_H_STEP:
        if re.search(pattern, scope):
            return name
    return OTHER


def step_seconds(summary: trace.Summary) -> Dict[str, float]:
    """Component -> device seconds summed over the whole executions of the
    step program in the traced window; the components add up to all of it."""
    out = {name: 0.0 for name, _ in NEMOTRON_H_STEP}
    out[OTHER] = 0.0
    for scope, seconds in summary.step_scope_seconds.items():
        out[component_of(scope)] += seconds
    return out


def step_ms(summary: Optional[trace.Summary], component: str) -> Optional[float]:
    """Device milliseconds of ``component`` per execution of the step
    program; None where the capture holds no whole execution, or no operation
    under the family's own mixer scope (another family's step)."""
    if summary is None or not summary.whole_steps:
        return None
    seconds = step_seconds(summary)
    if not seconds["mamba2"]:
        return None
    return 1e3 * seconds[component] / summary.whole_steps


def ssd_scan_ms(summary: Optional[trace.Summary]) -> Optional[float]:
    """Device milliseconds a step spends under ``mamba2/ssd_scan``: every
    Mamba-2 layer's scan, forward, recomputations and backward."""
    if summary is None or not summary.whole_steps:
        return None
    seconds = trace.seconds_under(summary, SSD_SCAN, whole_steps=True)
    return 1e3 * seconds / summary.whole_steps if seconds else None


def _built_and_peaks(built, device_kind: Optional[str]):
    from benchmarks import peaks
    from benchmarks.configs import nemotron_h as builder

    built = built or builder.BUILT
    if built is None:
        return None, None
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    return built, peaks.PEAKS[device_kind]


def ssd_scan_roofline_pct(ctx, built=None, device_kind: Optional[str] = None
                          ) -> Optional[float]:
    """The scan's share of its RECURRENCE roofline: ``max(operations / peak
    FLOP/s, bytes / peak B/s)`` of one layer's forward and backward
    (``flops_nemotron_h.ssd_scan``) x the configuration's Mamba-2 layers, over
    the scan's device time a step. None where the trace has no operation
    under the scan's scope or the process built no model of this family."""
    from benchmarks import flops_nemotron_h

    ms = ssd_scan_ms(ctx.get("summary"))
    built, peak = _built_and_peaks(built, device_kind)
    if ms is None or built is None:
        return None
    ops, moved = flops_nemotron_h.ssd_scan(built["cfg"], built["batch_size"], built["width"])
    least = max(ops / peak[0], moved / peak[1]) * flops_nemotron_h.blocks_of(built["cfg"], "M")
    return 100.0 * least / (1e-3 * ms)


def kernel_roofline_pct(ctx, kernel: str, built=None, device_kind: Optional[str] = None
                        ) -> Optional[float]:
    """A Pallas kernel's share of its roofline in THIS family's step:
    ``max(operations / peak FLOP/s, bytes / peak B/s)`` of one execution
    (``flops_nemotron_h.KERNELS``) x its executions a step (counted in the
    trace: ``components_decoder_lm.executions``), over its device time a step:
    the operations under the ``named_scope`` of the kernel's name, over the
    whole executions of the step program. ``built`` is what this process
    built (``configs/nemotron_h.BUILT``). None where the trace has no
    operation under that scope or the process built no model of this family."""
    from benchmarks import flops_nemotron_h

    summary = ctx.get("summary")
    if summary is None or not summary.whole_steps:
        return None
    built, peak = _built_and_peaks(built, device_kind)
    seconds = trace.seconds_under(summary, rf"/{re.escape(kernel)}(/|$)", whole_steps=True)
    count = executions(summary, kernel)
    if built is None or not seconds or not count:
        return None
    ops, moved = flops_nemotron_h.KERNELS[kernel](
        built["cfg"], built["batch_size"], built["width"])
    least = max(ops / peak[0], moved / peak[1]) * count
    return 100.0 * least * summary.whole_steps / seconds
