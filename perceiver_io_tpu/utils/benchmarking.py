"""Train-step timing, shared by ``bench.py`` and the tools.

jax dispatch is asynchronous, so a timing has to end in a sync and must not
count the sync itself as step time. One recipe, shared by ``bench.py`` and
``tools/e2e_configs_bench.py``:

- jit with a donated state and CHAIN iterations through it (every output
  feeds the next step, so nothing is dead code),
- sync ONCE per window by fetching the loss scalar of the last step (it
  depends on the whole chain),
- subtract a 1-iteration run so the fetch itself doesn't count.
"""

from __future__ import annotations

import time
from typing import Tuple

import jax


def time_train_step(
    train_step, state, batch, steps: int, windows: int = 1, jitted=None
) -> Tuple[float, object]:
    """Seconds per step of ``(state, batch) → (state, metrics)``; returns
    ``(seconds_per_step, final_state)``. Compiles/warms once before timing.

    ``steps`` is a lower bound: when the measured delta doesn't dwarf the
    one-iteration run (sub-millisecond steps), the
    iteration count grows until it does — otherwise round-trip jitter swamps
    the signal (and can even make the subtraction negative).

    ``windows``: number of measurement windows; the MEDIAN is returned. A
    host that shares its cores shows occasional slow windows; with one
    window a single outlier becomes the recorded number.

    ``jitted``: pass a pre-built ``jax.jit(train_step, donate_argnums=(0,))``
    wrapper to reuse its compiled executable (e.g. when the caller already
    lowered it for cost analysis) — a fresh wrapper would compile again."""
    step = jitted if jitted is not None else jax.jit(train_step, donate_argnums=(0,))

    for _ in range(3):
        state, metrics = step(state, batch)
    float(metrics["loss"])  # sync: the scalar depends on every step so far

    def timed(n: int) -> float:
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = step(state, batch)
        float(metrics["loss"])
        return time.perf_counter() - t0

    def one_window() -> float:
        t_one = timed(1)  # fetch round trip + one step
        n = steps
        while True:
            delta = timed(n + 1) - t_one
            if delta > max(4.0 * t_one, 0.25) or n >= 65536:
                return max(delta, 0.0) / n
            n *= 4

    samples = sorted(one_window() for _ in range(max(windows, 1)))
    return samples[len(samples) // 2], state


def time_train_step_device(
    train_step, state, batch, steps: int, jitted=None, trace_dir=None
) -> Tuple[float, int, object]:
    """DEVICE-measured seconds/step via a ``jax.profiler`` trace.

    The host-clock recipe above includes whatever the host adds (dispatch,
    scheduling on shared cores). The device trace records each step's
    hardware duration on the TPU itself — it is the basis of the headline
    metric (``bench.py``); the host clock is reported beside it, never in
    its place.

    Returns ``(seconds_per_step, n_steps_used, final_state)``. Raises on
    backends/toolchains where the trace cannot be captured or parsed.
    """
    import tempfile

    from perceiver_io_tpu.utils.xplane import device_step_seconds

    step = jitted if jitted is not None else jax.jit(train_step, donate_argnums=(0,))
    for _ in range(3):
        state, metrics = step(state, batch)
    float(metrics["loss"])  # sync before the trace window opens

    if trace_dir is None:
        trace_dir = tempfile.mkdtemp(prefix="pit_bench_trace_")
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(steps):
            state, metrics = step(state, batch)
        float(metrics["loss"])  # device sync INSIDE the trace window
    finally:
        jax.profiler.stop_trace()

    seconds, n_used = device_step_seconds(trace_dir)
    return seconds, n_used, state
