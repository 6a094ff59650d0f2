"""Tracing / profiling utilities — the observability layer the reference lacks.

The reference ships nothing beyond Lightning's progress bar (SURVEY.md §5);
here profiling is first-class and TPU-native:

- ``start_profiler_server`` / ``trace``: the ``jax.profiler`` trace server and
  programmatic trace capture, viewable in TensorBoard's profile plugin or
  Perfetto.
- ``annotate_step``: ``StepTraceAnnotation`` wrapper so each training step
  shows up as a named step in the trace timeline.
- ``compiled_flops`` + ``device_peak_flops`` + ``mfu``: model-FLOPs-utilization
  accounting from XLA's own cost analysis of the compiled step — the number
  the BASELINE.md target (≥45% MFU on v5e) is measured in.
"""

from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Callable, Iterator, Optional, Tuple

import jax

# THE peak table, keyed by ``device_kind``: (dense bf16 matmul FLOP/s, HBM
# bytes/s) per chip. Public figures from the Google Cloud TPU documentation
# (cloud.google.com/tpu/docs, the per-generation system-architecture pages;
# v2/v3 are per-chip = 2 cores). A device that is not here has no peak: MFU
# and roofline shares are errors or omitted for it, never defaulted.
_PEAKS = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1200e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def start_profiler_server(port: int = 9012) -> None:
    """Start the profiler server so TensorBoard can capture live traces."""
    jax.profiler.start_server(port)


def call_with_deadline(
    fn: Callable[[], object],
    deadline_s: Optional[float],
    name: str = "call",
) -> Tuple[bool, object]:
    """Run ``fn`` with a wall-clock deadline: ``(completed, result)``.

    The in-loop self-profiler starts and stops traces from inside a serving
    or training loop; a profiler call that does not return must cost that
    loop one window, not freeze it. The call runs on a daemon worker thread; on timeout the caller gets ``(False,
    None)`` and moves on — the stuck thread is abandoned (it holds no locks
    of ours and dies with the process). ``deadline_s=None`` calls inline.
    Exceptions raised by ``fn`` before the deadline propagate unchanged.
    """
    if deadline_s is None:
        return True, fn()
    box: dict = {}
    done = threading.Event()

    def _run() -> None:
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            box["error"] = e
        finally:
            done.set()

    worker = threading.Thread(
        target=_run, name=f"deadline-{name}", daemon=True
    )
    worker.start()
    if not done.wait(deadline_s):
        return False, None
    if "error" in box:
        raise box["error"]
    return True, box.get("result")


@contextlib.contextmanager
def trace(logdir: str, deadline_s: Optional[float] = None) -> Iterator[None]:
    """Capture a profiler trace into ``logdir`` (TensorBoard-compatible).

    With ``deadline_s``, ``start_trace``/``stop_trace`` each run under a
    deadline: if either hangs, the context degrades to a
    no-op with a warning instead of freezing the loop — callers keep their
    host timing and simply get no trace to analyze.
    """
    started, _ = call_with_deadline(
        lambda: jax.profiler.start_trace(logdir), deadline_s, "start_trace"
    )
    if not started:
        warnings.warn(
            f"jax.profiler.start_trace did not complete within {deadline_s}s "
            "— proceeding WITHOUT a trace",
            stacklevel=2,
        )
    try:
        yield
    finally:
        # even when start timed out it may have completed late on its worker
        # thread — best-effort stop either way, never letting a profiler
        # session leak into the process (stop on a never-started trace raises
        # harmlessly into the except arm)
        try:
            stopped, _ = call_with_deadline(
                jax.profiler.stop_trace, deadline_s, "stop_trace"
            )
            if not stopped:
                warnings.warn(
                    f"jax.profiler.stop_trace did not complete within "
                    f"{deadline_s}s — the trace "
                    f"under {logdir!r} may be unusable",
                    stacklevel=2,
                )
        except Exception:
            if started:
                raise


def annotate_step(step_num: int) -> jax.profiler.StepTraceAnnotation:
    """Mark a training step in the trace timeline."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step_num)


def compiled_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """Total FLOPs of one invocation, from XLA's cost analysis of the lowered
    computation. None when the backend doesn't expose an estimate."""
    try:
        cost = jitted_fn.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax: one dict per device
            cost = cost[0]
        flops = cost.get("flops")
        return float(flops) if flops else None
    except Exception:
        return None


def device_peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak bf16 FLOP/s for a device, or None when unknown (e.g. CPU)."""
    device = device or jax.devices()[0]
    peaks = _PEAKS.get(getattr(device, "device_kind", ""))
    return peaks[0] if peaks else None


def device_peaks(device_kind: str) -> Tuple[float, float]:
    """``(bf16 FLOP/s, HBM bytes/s)`` for a ``device_kind``; a kind the
    table does not hold is an error, not a default."""
    try:
        return _PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(utils/profiling._PEAKS holds {sorted(_PEAKS)})") from None


def mfu(
    flops_per_step: float,
    step_time_s: float,
    num_devices: int = 1,
    device: Optional[jax.Device] = None,
) -> Optional[float]:
    """Model FLOPs utilization in [0, 1]: achieved / peak.

    ``flops_per_step`` is the whole program's FLOPs (all devices), so peak is
    scaled by ``num_devices``.
    """
    peak = device_peak_flops(device)
    if peak is None or step_time_s <= 0:
        return None
    return flops_per_step / step_time_s / (peak * num_devices)
