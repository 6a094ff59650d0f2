"""Loop kind ``train_fit``: the window drives ``Trainer.fit``.

Set-up builds ONE Trainer (the compiled step with its state), drives it from
the seed through its first three optimizer steps and a few warm-up steps
through the window's own call (``trainer.fit(loader, ())``) and feed (the
pooled loader), and hands that same object to the window. The loader ends the
epoch when the window's seconds are up; ``max_epochs=1`` makes ``fit`` return
there. An empty validation loader keeps evaluation and the end-of-fit
checkpoint out (``Trainer._validate_and_checkpoint`` saves only where there
is no validation loader or a monitored metric).
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from benchmarks import check_train, trace as trace_mod
from benchmarks.weights import make_weights_fn, seed_words, train_rng

TRACE_START_S = 1.0   # into the window
TRACE_LENGTH_S = 3.0  # a few seconds: a long capture came back empty (PERF.md)


class PoolLoader:
    """Cycles a pool of host batches made in set-up. One pass (``fit``'s
    epoch) ends after ``limit`` batches or at ``deadline`` (perf_counter
    seconds), whichever is set. Time inside ``__next__`` is the loader's own
    span; with ``trace_dir`` set the pass also starts and stops the profiler
    at fixed offsets, from the thread that drives ``fit``."""

    def __init__(self, pool: List[Dict[str, np.ndarray]]):
        self.pool = pool
        self.cursor = 0
        self.limit: Optional[int] = None
        self.deadline: Optional[float] = None
        self.trace_dir: Optional[str] = None
        self.trace_at: Optional[float] = None
        self.tracing = False
        self.traced = False
        self.wait_s = 0.0
        self.served = 0

    def stop_trace(self) -> None:
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False
            self.traced = True

    def _profiler(self, now: float) -> None:
        if self.trace_dir is None or self.traced:
            return
        if not self.tracing and now >= self.trace_at:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.tracing = True
            self.trace_at = time.perf_counter() + TRACE_LENGTH_S
        elif self.tracing and now >= self.trace_at:
            self.stop_trace()

    def __iter__(self):
        n = 0
        while True:
            self._profiler(time.perf_counter())
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.loader_next"):
                done = ((self.limit is not None and n >= self.limit)
                        or (self.deadline is not None and t0 >= self.deadline))
                if not done:
                    batch = self.pool[self.cursor % len(self.pool)]
                    self.cursor += 1
                    n += 1
            self.wait_s += time.perf_counter() - t0
            if done:
                return
            self.served += 1
            yield batch


def peak_device_bytes(device) -> int:
    """Peak bytes held on one chip as JAX reports them: live buffers at their
    peak plus the scratch space the runtime reserved for the programs'
    temporaries (``peak_bytes_reserved``: on this TPU runtime a program's
    temporaries are reserved, not counted in ``peak_bytes_in_use``; the two
    add up to bytes_limit - largest_free_block, my chip runs, PR 26)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def _fit(trainer, loader: PoolLoader, *, limit=None, deadline=None):
    loader.limit, loader.deadline = limit, deadline
    with jax.profiler.TraceAnnotation("bench.trainer_fit"):
        state = trainer.fit(loader, ())
    return state


def program_first_steps(trainer, loader: PoolLoader, fresh_params) -> Dict[str, Any]:
    """Drive the timed object through optimizer steps 1..3 by the window's
    own call and read what ``correct`` compares. ``fresh_params()`` makes the
    initial weights again (the state's own copy was donated at step 1)."""
    losses, grad_norms = [], None
    for step in range(check_train.STEPS):
        _fit(trainer, loader, limit=1)
        # fit() keeps the loss of the epoch's last step for its end-of-epoch
        # bookkeeping; one step per fit makes that this step's loss
        losses.append(float(trainer._last_train_loss))
        if step == 0:
            mu = check_train.first_moment(trainer.state.opt_state)
            grad_norms = np.asarray(check_train.leaf_norms(mu)) / (1.0 - check_train.ADAM_B1)
    delta = np.asarray(check_train.leaf_diff_norms(trainer.state.params, fresh_params()))
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def run(cell: Dict[str, Any], cfg: Dict[str, Any], mix: Dict[str, Any], builder,
        seed: int, seconds: float, trace: bool, probes, *,
        break_program=None, limits: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """One run of a training cell. ``probes`` (run.Probes) gives the
    process's age and compile count; ``break_program(trainer)`` is the tests'
    hook to plant a fault under the timed path."""
    from benchmarks import traffic

    phases = {"start": probes.clock()}  # seconds since the process started
    lo, hi = seed_words(seed)
    pool = traffic.make_batches(mix, seed)
    weights_fn = make_weights_fn(builder.param_shapes(cfg))
    rng = train_rng(lo, hi)
    workdir = tempfile.mkdtemp(prefix="bench_")
    try:
        phases["inputs"] = probes.clock()
        trainer = builder.build_trainer(cfg, mix, weights_fn(lo, hi), rng, pool[0],
                                        f"{workdir}/logs")
        phases["trainer_built"] = probes.clock()
        if break_program is not None:
            break_program(trainer)
        loader = PoolLoader(pool)
        first_batches = [pool[i % len(pool)] for i in range(check_train.STEPS)]
        program = program_first_steps(trainer, loader, lambda: weights_fn(lo, hi))
        phases["first_steps"] = probes.clock()
        _fit(trainer, loader, limit=mix["warmup_steps"])
        jax.block_until_ready(trainer.state)
        steps_before = loader.served

        # -- the window ------------------------------------------------------
        setup_s = probes.clock()
        setup_compiles = probes.compiles()
        loader.wait_s = 0.0
        if trace:
            loader.trace_dir = f"{workdir}/trace"
            loader.trace_at = time.perf_counter() + TRACE_START_S
        t0 = time.perf_counter()
        state = _fit(trainer, loader, deadline=t0 + seconds)
        jax.block_until_ready(state)
        t1 = time.perf_counter()
        loader.stop_trace()
        # -- closed ----------------------------------------------------------
        window_s = t1 - t0
        phases["window_closed"] = probes.clock()
        window_compiles = probes.compiles() - setup_compiles
        steps = loader.served - steps_before
        final_step = int(jax.device_get(state.step))
        final_loss = float(trainer._last_train_loss)
        memory_peak = max(peak_device_bytes(d) for d in jax.devices())
        result: Dict[str, Any] = {
            "setup_s": setup_s, "window_s": window_s, "steps": steps,
            "batch_size": mix["batch_size"], "memory_peak_bytes": int(memory_peak),
            "loader_wait_s": loader.wait_s, "setup_xla_compiles": setup_compiles,
            "flops_per_step": builder.train_flops_per_sample(cfg, mix, pool) * mix["batch_size"],
            "attempted": steps, "failed": 0 if np.isfinite(final_loss) else steps,
        }
        summary = None
        if trace:
            devices, host_spans = trace_mod.load(loader.trace_dir)
            summary = trace_mod.summarize(devices, host_spans)
        result["summary"] = summary
        phases["trace_read"] = probes.clock()
        memory_stats = {k: int(v) for k, v in (jax.devices()[0].memory_stats() or {}).items()}

        # free the program's state before the reference touches the chip
        trainer.close()
        del trainer, state
        gc.collect()

        reference = check_train.reference_readings(
            builder.reference_task(cfg), weights_fn(lo, hi), rng, first_batches)
        numbers = check_train.compare(program, reference)
        phases["reference_done"] = probes.clock()
        verdict = check_train.verdict(
            numbers, limits if limits is not None else check_train.load_limits(cell["name"]))
        expected = check_train.STEPS + mix["warmup_steps"] + steps
        # exact: every batch the loader served became one optimizer step
        verdict["compared"]["steps_missing"] = {"value": abs(expected - final_step), "limit": 0}
        if final_step != expected or not np.isfinite(final_loss):
            verdict["correct"] = False
        result["verdict"] = verdict
        result["numbers"] = numbers
        result["details"] = {
            "steps": steps, "window_s": window_s,
            "tokens_per_s": steps * mix["batch_size"] * mix.get("samples_unit_tokens", 0) / window_s,
            "window_xla_compiles": window_compiles,
            "losses_program": program["losses"], "losses_reference": reference["losses"],
            "loss_final": final_loss, "numbers": numbers, "memory_stats": memory_stats,
            "phases_s": phases,
        }
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
