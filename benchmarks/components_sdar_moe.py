"""Which scopes make which component of an ``sdar_moe`` training step (block
diffusion), for its ``sdar_*_device_ms.train`` readers and its attention
kernels' rooflines (the family's own table, beside ``components_decoder_lm.py``
and those of the other decoder families).

The program names its parts with ``jax.named_scope`` and flax module names
(``models/decoder_lm.py``, ``training/steps.py``); ``moe/*``, ``embed`` and
``head_loss`` are the SAME scopes in every decoder family, so the accepted
readers ``moe_device_ms.train`` and ``embed_head_loss_device_ms.train`` read
this family's step right. The patterns are tried IN ORDER and an operation
belongs to the first that matches: the attention layers (projections, q/k
norms, rotary and the three block-diffusion kernels), the expert layers, the
embedding and the head with its loss. ``sdar_other`` is what matches none:
the noise (``bd_noise``), layer norms, residual adds, the optimizer, casts,
and every operation without a scope (``copy-done`` / ``slice-done``, the
``conditional``s' own events).

The readers read only what THIS process built (``configs/sdar_moe.BUILT``):
the LFM2 family's step has a ``gqa_attention`` scope too, and a program
without this family builds no such model, so every reader returns None there.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks import trace
from benchmarks.components_decoder_lm import executions

SDAR_STEP = (
    ("gqa_attention", r"/gqa_attention/"),
    ("moe", r"/moe/"),
    ("embed_head_loss", r"/(embed|head_loss)/"),
)
OTHER = "sdar_other"


def _built():
    from benchmarks.configs import sdar_moe as builder

    return builder.BUILT


def component_of(scope: str) -> str:
    for name, pattern in SDAR_STEP:
        if re.search(pattern, scope):
            return name
    return OTHER


def step_seconds(summary: trace.Summary) -> Dict[str, float]:
    """Component -> device seconds summed over the whole executions of the
    step program in the traced window; the components add up to all of it."""
    out = {name: 0.0 for name, _ in SDAR_STEP}
    out[OTHER] = 0.0
    for scope, seconds in summary.step_scope_seconds.items():
        out[component_of(scope)] += seconds
    return out


def step_ms(summary: Optional[trace.Summary], component: str, built=None) -> Optional[float]:
    """Device milliseconds of ``component`` per execution of the step
    program; None where the capture holds no whole execution or this process
    built no model of this family."""
    if summary is None or not summary.whole_steps or (built or _built()) is None:
        return None
    return 1e3 * step_seconds(summary)[component] / summary.whole_steps


def kernel_roofline_pct(ctx, kernel: str, built=None, device_kind: Optional[str] = None
                        ) -> Optional[float]:
    """A block-diffusion attention kernel's share of its roofline:
    ``max(operations / peak FLOP/s, bytes / peak B/s)`` of one execution
    (``flops_sdar_moe.KERNELS``: the VISIBLE pairs of the mask) x its
    executions a step (counted in the trace), over its device time a step:
    the operations under the ``named_scope`` of the kernel's name, over the
    whole executions of the step program. None where the trace has no
    operation under that scope or this process built no model of this
    family."""
    from benchmarks import flops_sdar_moe, peaks

    summary = ctx.get("summary")
    built = built or _built()
    if summary is None or not summary.whole_steps or built is None:
        return None
    seconds = trace.seconds_under(summary, rf"/{re.escape(kernel)}(/|$)", whole_steps=True)
    count = executions(summary, kernel)
    if not seconds or not count:
        return None
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    ops, moved = flops_sdar_moe.KERNELS[kernel](built["cfg"], built["batch_size"], built["width"])
    flops_peak, bytes_peak, _ = peaks.PEAKS[device_kind]
    least = max(ops / flops_peak, moved / bytes_peak) * count
    return 100.0 * least * summary.whole_steps / seconds
