"""In-loop self-profiling: live device-clock step time, MFU, and recompiles.

``tools/hbm_roofline.py`` proved the methodology offline: capture a short
``jax.profiler`` trace, read the DEVICE-recorded per-step windows from the
xplane, take the lower quartile — the device's own clock, which host
scheduling cannot move. ``SelfProfiler`` runs exactly that analysis
*in-process, periodically, during the loop it is measuring*: every
``every_n`` ticks it captures ``trace_steps`` dispatches, analyzes the trace,
and publishes gauges through the metrics registry — host step time always
(under its own name), device step time when a TPU plane is present, MFU from
the DEVICE step time when a FLOP count is known (a window without a device
reading publishes no MFU: the host number never stands in under a device
metric's name), and the
process-lifetime jax compilation count (steady state should hold it flat; a
climbing count during serving is the recompile bug the bucket programs
exist to prevent).

Trace start/stop run under a deadline (``utils.profiling.call_with_deadline``)
so a profiler call that does not return costs the loop one window (host
timing only, with a warning) instead of freezing the loop it watches.

jax is imported lazily — constructing a profiler must not initialize a
backend before the entry point has chosen one.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional, Union

from perceiver_io_tpu.obs import registry as _registry_mod
from perceiver_io_tpu.obs import tracing

# jax.profiler supports ONE active trace per process; concurrent profilers
# (engine + trainer, or three engines) take turns instead of erroring
_TRACE_SLOT = threading.Lock()

# weakrefs: every live registry's counter gets each event; a registry no
# longer referenced anywhere (tests build private ones) must stay
# collectable — the process-lifetime listener must not pin it
_COMPILE_COUNTERS: list = []  # list of weakref.ref[Counter]
_COMPILE_LISTENER_INSTALLED = False
_COMPILE_LOCK = threading.Lock()

# jax.monitoring duration events of the compile path -> the span the listener
# enters for each (obs.tracing.add_span; end = when the event fired, start =
# end - duration). Trace events NEST (an inner jit traced inside the step's
# trace fires its own): readers take the union of intervals, never the sum.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
}
# Tracing one flax step fires thousands of inner TRACE events of microseconds
# each (8,711 / 11,558 under a millisecond in the set-up of the benchmark's two
# cells, 0.32 / 0.62 s summed, beside 497 / 1,188 longer ones; PERF.md, PR
# 29): entered one by one they would push the step's own trace out of the
# bounded buffer. A trace event shorter than this is not entered;
# most lie inside a longer trace span that is. Every lower and backend-compile
# event is entered whatever its length (dozens a process).
_MIN_TRACE_SPAN_NS = 1_000_000


def _enter_compile_span(span_name: str, duration: float, **fields) -> None:
    """One duration event of the compile path, fired at its end on the
    thread that did the work."""
    length_ns = int(duration * 1e9)
    if span_name == "jax.trace" and length_ns < _MIN_TRACE_SPAN_NS:
        return
    end_ns = time.monotonic_ns()
    tracing.add_span(span_name, end_ns - length_ns, end_ns, **fields)


def install_compile_counter(registry=None):
    """Count every XLA backend compilation into the
    ``jax_compilations_total`` counter of ``registry`` (idempotent per
    registry; returns the counter).

    Rides ``jax.monitoring``: ``backend_compile_duration`` fires once per
    program jax has to build — never for its in-memory jit cache, never for
    a deserialized AOT executable — but it wraps the persistent compilation
    cache lookup too, so a program that cache ANSWERED fires it as well
    (jax 0.9.0). The ``cache_hits`` event precedes it on the same thread;
    the listener pairs the two and counts only the compiles XLA really did,
    which makes the counter a live recompile detector with the persistent
    cache on. One process-wide listener fans out to every registry that
    asked (tests use private registries; production uses the default one).

    The same listener enters the compile path's spans (``_COMPILE_SPANS``):
    ``jax.trace``, ``jax.lower`` and ``jax.backend_compile``, the last with
    the field ``cache_hit`` from that pairing (on a hit its length is the
    persistent cache's read + deserialise, on a miss XLA's compile). Trace
    events under a millisecond are left out (``_MIN_TRACE_SPAN_NS``).
    """
    global _COMPILE_LISTENER_INSTALLED
    registry = registry or _registry_mod.get_registry()
    counter = registry.counter(
        "jax_compilations_total",
        "XLA backend compilations observed in this process",
    )
    import weakref

    with _COMPILE_LOCK:
        if not any(r() is counter for r in _COMPILE_COUNTERS):
            _COMPILE_COUNTERS.append(weakref.ref(counter))
        if not _COMPILE_LISTENER_INSTALLED:
            try:
                import jax.monitoring

                answered = threading.local()  # this thread's last lookup hit

                def _on_event(name: str, **kwargs) -> None:
                    if name == "/jax/compilation_cache/cache_hits":
                        answered.hit = True

                def _listener(name: str, duration: float, **kwargs) -> None:
                    span_name = _COMPILE_SPANS.get(name)
                    if span_name is None:
                        return
                    if span_name != "jax.backend_compile":
                        _enter_compile_span(span_name, duration)
                        return
                    hit = getattr(answered, "hit", False)
                    _enter_compile_span(span_name, duration, cache_hit=hit)
                    if hit:
                        answered.hit = False  # the persistent cache's, not XLA's
                        return
                    dead = False
                    for r in list(_COMPILE_COUNTERS):
                        c = r()
                        if c is None:
                            dead = True
                        else:
                            c.inc()
                    if dead:
                        with _COMPILE_LOCK:
                            _COMPILE_COUNTERS[:] = [
                                r for r in _COMPILE_COUNTERS
                                if r() is not None
                            ]

                jax.monitoring.register_event_listener(_on_event)
                jax.monitoring.register_event_duration_secs_listener(_listener)
                _COMPILE_LISTENER_INSTALLED = True
            except Exception as e:  # monitoring API moved: degrade, not crash
                print(f"[obs] compile counter unavailable: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
    return counter


class SelfProfiler:
    """Periodic in-loop trace capture + xplane analysis + gauge publication.

    The owning loop calls ``tick(steps)`` once per DISPATCH (``steps`` =
    optimizer steps / batches that dispatch carried — 1 for the engine, K
    under the Trainer's ``steps_per_dispatch``). ``every_n`` counts steps
    between windows; a window spans ``trace_steps`` dispatches (each dispatch
    is one ``StepTraceAnnotation`` window in the trace). All published
    numbers are normalized PER STEP: the xplane windows are per-dispatch, so
    device time divides by the window's mean dispatch width — without this a
    K-step dispatch reported K× step time and K×-understated MFU (the r4
    in-loop-MFU bug; see ``trainer._maybe_compute_flops``). When a window
    closes, the published metrics are also returned as a dict so the caller
    can forward them to its own logger (the Trainer writes them into
    ``metrics.jsonl`` — same numbers, every sink).

    Published gauges (``<prefix>_…``):
      - ``selfprofile_device_step_ms`` — lower-quartile device step time
        (only when the trace carries a TPU plane);
      - ``selfprofile_host_step_ms`` — host wall-clock per step over the
        window (always; the host-clock number, kept for contrast);
      - ``selfprofile_mfu`` — from the device step time; absent without one
        (requires ``flops_per_step`` and a known device peak);
      - ``selfprofile_windows_total`` / ``selfprofile_failures_total``
        counters, and the process-wide ``jax_compilations_total``.
    """

    def __init__(
        self,
        every_n: int,
        trace_steps: int = 4,
        prefix: str = "train",
        registry=None,
        flops_per_step: Union[None, float, Callable[[], Optional[float]]] = None,
        num_devices: int = 1,
        deadline_s: Optional[float] = 30.0,
        keep_trace_dirs: bool = False,
    ):
        if every_n <= 0:
            raise ValueError(f"every_n must be positive, got {every_n}")
        self.every_n = every_n
        self.trace_steps = max(1, int(trace_steps))
        self.prefix = prefix
        self.deadline_s = deadline_s
        self.keep_trace_dirs = keep_trace_dirs
        self._flops_per_step = flops_per_step
        self._num_devices = num_devices
        reg = registry or _registry_mod.get_registry()
        self._registry = reg
        labels = {"loop": prefix}
        self._g_device_ms = reg.gauge(
            "selfprofile_device_step_ms",
            "lower-quartile device step time from the in-loop trace", labels)
        self._g_host_ms = reg.gauge(
            "selfprofile_host_step_ms",
            "host wall-clock per step over the in-loop trace window", labels)
        self._g_mfu = reg.gauge(
            "selfprofile_mfu",
            "model FLOPs utilization from the in-loop trace", labels)
        self._c_windows = reg.counter(
            "selfprofile_windows_total",
            "in-loop trace windows analyzed", labels)
        self._c_failures = reg.counter(
            "selfprofile_failures_total",
            "in-loop trace windows that degraded (no device plane, deadline, "
            "or capture error)", labels)
        self._c_compiles = install_compile_counter(reg)

        self._since_window = 0
        self._window_dispatches = 0
        self._window_steps = 0
        self._tracing = False
        self._trace_dir: Optional[str] = None
        self._t0 = 0.0
        # guards the _tracing transition between the loop's tick() thread
        # and close() from another thread (engine shutdown with the worker
        # mid-capture): exactly one side may tear the window down and
        # release the trace slot
        self._state_lock = threading.Lock()

    def _flops(self) -> Optional[float]:
        f = self._flops_per_step
        return f() if callable(f) else f

    def _claim_end(self) -> bool:
        """Atomically claim the open capture window for teardown; False when
        there is none (or another thread already claimed it)."""
        with self._state_lock:
            if not self._tracing:
                return False
            self._tracing = False
            return True

    def tick(self, steps: int = 1,
             sync: Optional[Callable[[], Any]] = None) -> Optional[Dict[str, float]]:
        """Advance by one dispatch carrying ``steps`` optimizer steps;
        returns published metrics when a capture window just closed, else
        None. ``sync`` (e.g. block_until_ready on the step output) runs
        before the trace stops so the captured windows are complete."""
        if self._tracing:
            self._window_dispatches += 1
            self._window_steps += steps
            if self._window_dispatches >= self.trace_steps:
                return self._finish(sync)
            return None
        self._since_window += steps
        if self._since_window >= self.every_n:
            self._since_window = 0
            self._start()
        return None

    def _start(self) -> None:
        from perceiver_io_tpu.utils import profiling

        if not _TRACE_SLOT.acquire(blocking=False):
            return  # someone else (trainer profile capture, another engine)
        trace_dir = tempfile.mkdtemp(prefix=f"selfprofile_{self.prefix}_")
        try:
            import jax

            ok, _ = profiling.call_with_deadline(
                lambda: jax.profiler.start_trace(trace_dir),
                self.deadline_s, "start_trace",
            )
        except Exception as e:
            ok = False
            print(f"[obs] selfprofile start_trace failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
        if not ok:
            self._c_failures.inc()
            shutil.rmtree(trace_dir, ignore_errors=True)
            _TRACE_SLOT.release()
            return
        with self._state_lock:
            self._trace_dir = trace_dir
            self._tracing = True
        self._window_dispatches = 0
        self._window_steps = 0
        self._t0 = time.perf_counter()

    def _finish(self, sync) -> Optional[Dict[str, float]]:
        from perceiver_io_tpu.utils import profiling

        if not self._claim_end():  # close() got there first
            return None
        host_elapsed = 0.0
        try:
            if sync is not None:
                try:
                    sync()
                except Exception:
                    pass
            # the window ends when the synced work does — stop_trace's own
            # export time (file writes) must not inflate the host number
            host_elapsed = time.perf_counter() - self._t0
            import jax

            ok, _ = profiling.call_with_deadline(
                jax.profiler.stop_trace, self.deadline_s, "stop_trace")
        except Exception as e:
            # a telemetry failure must never crash the loop it watches —
            # stop_trace errors (disk full, proto issues, profiler state)
            # degrade this window, they don't kill the engine/Trainer
            ok = None
            if not host_elapsed:
                host_elapsed = time.perf_counter() - self._t0
            print(f"[obs] selfprofile stop_trace failed: "
                  f"{type(e).__name__}: {e} — publishing host timing only",
                  file=sys.stderr)
        finally:
            _TRACE_SLOT.release()
        trace_dir, self._trace_dir = self._trace_dir, None
        metrics: Dict[str, float] = {}
        steps = max(self._window_steps, 1)
        dispatches = max(self._window_dispatches, 1)
        host_ms = host_elapsed / steps * 1e3
        self._g_host_ms.set(host_ms)
        metrics["selfprofile_host_step_ms"] = host_ms
        dev_s = None
        if not ok:
            self._c_failures.inc()
            if ok is False:  # deadline (None = already-reported error)
                print(f"[obs] selfprofile stop_trace exceeded the "
                      f"{self.deadline_s}s deadline — publishing host timing "
                      f"only", file=sys.stderr)
        else:
            try:
                from perceiver_io_tpu.utils import xplane

                # the trace's step windows are per-DISPATCH; normalize by
                # the window's mean dispatch width (K under the Trainer's
                # steps_per_dispatch, 1 for the engine)
                dev_dispatch_s, _ = xplane.device_step_seconds(
                    trace_dir, skip_first=1)
                dev_s = dev_dispatch_s * dispatches / steps
                self._g_device_ms.set(dev_s * 1e3)
                metrics["selfprofile_device_step_ms"] = dev_s * 1e3
            except Exception:
                # no TPU plane (CPU), proto import missing, empty trace:
                # the window is counted failed; only the host gauge moved
                dev_s = None
                self._c_failures.inc()
        flops = self._flops()
        if flops and dev_s is not None:
            from perceiver_io_tpu.utils import profiling as _p

            u = _p.mfu(flops, dev_s, num_devices=self._num_devices)
            if u is not None:
                self._g_mfu.set(u)
                metrics["selfprofile_mfu"] = u
        self._c_windows.inc()
        # snapshot of the process-lifetime counter, gauge-named so callers
        # can forward the dict to MetricsLogger without a kind conflict
        metrics["selfprofile_jax_compilations"] = self._c_compiles.value
        tracing.event("selfprofile_window", loop=self.prefix,
                      **{k: round(v, 6) for k, v in metrics.items()})
        if not self.keep_trace_dirs and trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        elif trace_dir:
            print(f"[obs] selfprofile trace kept at {trace_dir}",
                  file=sys.stderr)
        return metrics

    def close(self) -> None:
        """Abort an open capture window (error/shutdown paths)."""
        if not self._claim_end():  # no window, or tick()'s _finish owns it
            return
        try:
            import jax

            from perceiver_io_tpu.utils import profiling

            profiling.call_with_deadline(
                jax.profiler.stop_trace, self.deadline_s, "stop_trace")
        except Exception:
            pass
        finally:
            _TRACE_SLOT.release()
            if self._trace_dir:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                self._trace_dir = None
