"""Which scopes make which component of a ``decoder_lm`` training step, for
its ``*_device_ms.train`` readers (the family's own table, beside
``components.py``, which is the Perceiver's).

A scope is the path an operation was traced under (``trace.py``); the program
names its parts with ``jax.named_scope`` and flax module names
(``models/decoder_lm.py``). The patterns are tried IN ORDER and an operation
belongs to the first that matches, so the components are disjoint: the MTP
module counts whole (its attention, experts, embedding and head-and-loss
included), then the main stack's attention and expert layers, then the
embedding and the main head with its loss. ``lm_other`` is what matches none:
norms, residual adds and the dense SwiGLU of layer 0, the optimizer, casts,
and every operation without a scope (``copy-done`` / ``slice-done``).
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks import trace

LM_STEP = (
    ("mtp", r"/mtp[/_]"),
    ("mla_attention", r"/mla_attention/"),
    ("moe", r"/moe/"),
    ("embed_head_loss", r"/(embed|head_loss)/"),
)
OTHER = "lm_other"


def component_of(scope: str) -> str:
    for name, pattern in LM_STEP:
        if re.search(pattern, scope):
            return name
    return OTHER


def step_seconds(summary: trace.Summary) -> Dict[str, float]:
    """Component -> device seconds summed over the whole executions of the
    step program in the traced window; the components add up to all of it."""
    out = {name: 0.0 for name, _ in LM_STEP}
    out[OTHER] = 0.0
    for scope, seconds in summary.step_scope_seconds.items():
        out[component_of(scope)] += seconds
    return out


def step_ms(summary: Optional[trace.Summary], component: str) -> Optional[float]:
    """Device milliseconds of ``component`` per execution of the step
    program; None where the capture holds no whole execution, or no operation
    under any of the family's scopes (another family's step)."""
    if summary is None or not summary.whole_steps:
        return None
    seconds = step_seconds(summary)
    if not any(seconds[name] for name, _ in LM_STEP):
        return None
    return 1e3 * seconds[component] / summary.whole_steps


def executions(summary: trace.Summary, kernel: str) -> int:
    """Times a step runs ``kernel``, asked of the traced program: the HLO
    instructions named for it (a pallas_call's ``name=`` names its custom
    call: ``%fused_attention_fwd.15 = ...``; the trace names an operation by
    its HLO line). Each runs once a step: the stack is a Python loop and the
    step program has no ``while``. So a step that stops recomputing a kernel
    is counted with fewer executions, not read as a faster kernel."""
    rx = re.compile(rf"%?{re.escape(kernel)}(\.\d+)? = ")
    return sum(1 for op in summary.op_seconds if rx.match(op))


def kernel_roofline_pct(ctx, kernel: str, built=None, device_kind: Optional[str] = None
                        ) -> Optional[float]:
    """A Pallas kernel's share of its roofline: ``max(operations / peak
    FLOP/s, bytes / peak B/s)`` of one execution (``flops_decoder_lm.KERNELS``)
    x its executions a step (``executions``), over its device time a step:
    the operations under the ``named_scope`` of the kernel's name, which the
    program puts around the call alone, over the whole executions of the step
    program. ``built`` is what this process built (``configs/decoder_lm.BUILT``).
    None where the trace has no operation under that scope (a program without
    the kernel) or the process built no model of this family."""
    from benchmarks import flops_decoder_lm, peaks
    from benchmarks.configs import decoder_lm as builder

    summary = ctx.get("summary")
    built = built or builder.BUILT
    if summary is None or not summary.whole_steps or built is None:
        return None
    seconds = trace.seconds_under(summary, rf"/{re.escape(kernel)}(/|$)", whole_steps=True)
    count = executions(summary, kernel)
    if not seconds or not count:
        return None
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    ops, moved = flops_decoder_lm.KERNELS[kernel](
        built["cfg"], built["batch_size"], built["width"])
    flops_peak, bytes_peak, _ = peaks.PEAKS[device_kind]
    least = max(ops / flops_peak, moved / bytes_peak) * count
    return 100.0 * least * summary.whole_steps / seconds


def gauge(name: str) -> Optional[float]:
    """A gauge of the program's registry (the last train step's metrics are
    published there at the end of ``Trainer.fit``); None where the program
    has no such gauge."""
    from perceiver_io_tpu import obs

    return obs.get_registry().snapshot()["gauges"].get(name)


def local_assignment_gap_pct(built=None) -> Optional[float]:
    """Distance, in points, of the share of assignments computed here
    (the program's ``moe_local_assignment_pct`` gauge) from held / published
    experts x 100, what a router that favours no expert gives this chip: a
    router that drifts to the held experts (only they pass it a gradient on
    one rank's share) or away from them changes the routed work a step."""
    from benchmarks.configs import decoder_lm as builder

    share, built = gauge("moe_local_assignment_pct"), built or builder.BUILT
    if share is None or built is None:
        return None
    deployment = built["cfg"]["deployment"]
    return abs(share - 100.0 * deployment["experts_held"]
               / deployment["n_routed_experts_published"])
