"""Which scopes make which component of an ``ouro`` training step (a looped
stack), for its ``ouro_*_device_ms.train`` readers and its attention kernels'
rooflines (the family's own table, beside ``components_decoder_lm.py`` and
those of the other decoder families).

The program names its parts with ``jax.named_scope`` and flax module names
(``models/decoder_lm.py``): ``ut_stack`` around the ONE scan over the loop
steps (the R passes: forward, the blocks' recomputation and backward), whose
body is the flax module ``loop``, and ``ut_exits`` around the gates, the
heads and the loss. The compiler hoists a few loop-invariant operations of
the body (the rotary angles) out of the loop: they keep ``loop`` in their
scope and lose ``ut_stack``, so the stack is either name. The patterns are
tried IN ORDER and an operation belongs to the first that matches: the
stack, the exits. ``ouro_other`` is the rest of the step: the embedding,
AdamW, casts, and operations without a scope (``copy-done`` /
``slice-done``). It is taken as the step program's device time less the two,
and not as the sum of the unscoped operations: the runtime reports each
``while`` loop (the stack's two, the exits' two) as one device event without
a scope that encloses its body's operations, as it does a ``conditional``
(PERF.md section 7.8), and ``trace.py`` sums every event, so that sum counts
the loops' bodies twice (459 ms for a step of 461 in the first capture).
``gqa_attention`` (projections, rotary and the three causal kernels) is a
PART of the stack, read apart (``attention_ms``).

The readers read only what THIS process built (``configs/ouro.BUILT``): the
other decoder families' steps have ``gqa_attention`` scopes too, and a
program without this family builds no such model, so every reader returns
None there.
"""

from __future__ import annotations

import re
import statistics
from typing import Optional

from benchmarks import trace
from benchmarks.components_decoder_lm import executions as instructions

OURO_STEP = (
    ("ut_stack", r"/(ut_stack|loop)/"),
    ("ut_exits", r"/ut_exits/"),
)
OTHER = "ouro_other"
ATTENTION = r"/gqa_attention/"


def _built():
    from benchmarks.configs import ouro as builder

    return builder.BUILT


def component_of(scope: str) -> str:
    for name, pattern in OURO_STEP:
        if re.search(pattern, scope):
            return name
    return OTHER


def step_ms(summary: Optional[trace.Summary], component: str, built=None) -> Optional[float]:
    """Device milliseconds of ``component`` per execution of the step
    program, over its whole executions in the traced window; ``OTHER`` is the
    median execution's device time less the stack and the exits, so that the
    three add up to the step. None where the capture holds no whole execution
    or this process built no model of this family."""
    if summary is None or not summary.whole_steps or (built or _built()) is None:
        return None
    ms = {name: 0.0 for name, _ in OURO_STEP}
    for scope, seconds in summary.step_scope_seconds.items():
        name = component_of(scope)
        if name != OTHER:
            ms[name] += 1e3 * seconds / summary.whole_steps
    if component == OTHER:
        return statistics.median(summary.step_durations_ms) - sum(ms.values())
    return ms[component]


def attention_ms(summary: Optional[trace.Summary], built=None) -> Optional[float]:
    """Device milliseconds a step under ``gqa_attention``: the R x N
    attention sublayers, forward, recomputation and backward."""
    if summary is None or not summary.whole_steps or (built or _built()) is None:
        return None
    return 1e3 * trace.seconds_under(summary, ATTENTION, whole_steps=True) / summary.whole_steps


def executions(summary: trace.Summary, kernel: str, built) -> int:
    """Times a step runs ``kernel``, per whole step. The trace names an
    operation by its HLO instruction and keeps no count of its events
    (``trace.Summary``), and under the scan ONE instruction in the loop's body
    runs R times a step. Every instruction of the three causal kernels lies in
    that body: N of each, or 2N of the forward kernel where the blocks
    recompute it (sandbox compile for a described v5e; the compiler keeps the
    loop, it does not unroll it). So the instructions found x R, unless they
    already number R x N or more, which only an unrolled loop gives."""
    cfg = built["cfg"]
    loop, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    found = instructions(summary, kernel)
    return found if found >= loop * layers else found * loop


def kernel_roofline_pct(ctx, kernel: str, built=None, device_kind: Optional[str] = None
                        ) -> Optional[float]:
    """A causal attention kernel's share of its roofline: ``max(operations /
    peak FLOP/s, bytes / peak B/s)`` of one execution (``flops_ouro.KERNELS``:
    one block application, a group of 1) x its executions a step
    (``executions``), over its device time a step: the operations under the
    ``named_scope`` of the kernel's name, over the whole executions of the
    step program. None where the trace has no operation under that scope or
    this process built no model of this family."""
    from benchmarks import flops_ouro, peaks

    summary = ctx.get("summary")
    built = built or _built()
    if summary is None or not summary.whole_steps or built is None:
        return None
    seconds = trace.seconds_under(summary, rf"/{re.escape(kernel)}(/|$)", whole_steps=True)
    count = executions(summary, kernel, built)
    if not seconds or not count:
        return None
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    ops, moved = flops_ouro.KERNELS[kernel](built["cfg"], built["batch_size"], built["width"])
    flops_peak, bytes_peak, _ = peaks.PEAKS[device_kind]
    least = max(ops / flops_peak, moved / bytes_peak) * count
    return 100.0 * least * summary.whole_steps / seconds
