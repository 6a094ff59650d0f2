"""The training driver: epochs, eval, checkpointing, metrics, profiling.

The TPU-native replacement for the reference's ``pl.Trainer`` usage
(reference ``train_mlm.py:59-76``): the loop owns

- the jitted/pjitted step (single device, or SPMD over a mesh — the DDP
  replacement; pass a ``Mesh`` and the batch axis shards over ``data``),
- per-epoch (or every-N-steps) validation with weighted metric averaging,
- best-by-``val_loss`` top-k checkpointing with embedded hparams (reference
  ``train/utils.py:11-13`` + ``lightning.py:46`` semantics),
- TensorBoard/JSONL scalar logging incl. per-step LR (the reference's
  ``LearningRateMonitor``) and throughput/MFU accounting the reference lacks,
- optional profiler trace capture and per-step trace annotations,
- a ``predict_hook`` called after each validation pass — the sample-prediction
  channel (reference ``train_mlm.py:44-56``).

The trainer is model-agnostic: it drives any ``(state, batch) → (state,
metrics)`` train step and ``(state, batch, key) → metrics`` eval step over
dict-of-arrays loaders (``data/pipeline.py``).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import threading
import time
import warnings
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.parallel.mesh import AXIS_SEQ, sequence_parallel_context
from perceiver_io_tpu.resilience import (
    RetryPolicy,
    call_with_retry,
    faults,
    is_transient,
)
from perceiver_io_tpu.parallel.sharding import (
    PARAM_RULES,
    batch_shardings,
    make_sharded_train_step,
)
from perceiver_io_tpu.training.checkpoint import CheckpointManager
from perceiver_io_tpu.training.metrics import MetricsLogger, next_version_dir
from perceiver_io_tpu.utils import profiling

Batch = Dict[str, np.ndarray]
Metrics = Dict[str, Any]

# bit 0 of the coordination-flags bitmask: this host observed SIGTERM and
# asks the fleet to checkpoint-and-exit at the next agreed step boundary
_PREEMPT_BIT = 1

_END = object()  # the train loader is exhausted


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Loop-control surface (the reference's Trainer argparse flags)."""

    max_epochs: Optional[int] = None
    max_steps: Optional[int] = None
    log_every_n_steps: int = 50
    eval_every_n_steps: Optional[int] = None  # None → validate per epoch
    # Multi-step dispatch: lax.scan K optimizer steps per device call. On
    # dispatch-latency-bound hosts (very fast steps, a busy host) this is
    # what closes the trainer-loop vs device-step gap (PERF.md); K=1 keeps
    # classic per-step dispatch. Logging/eval cadences still count optimizer
    # steps (boundaries are honored at the next dispatch edge).
    steps_per_dispatch: int = 1
    logdir: str = "logs"
    experiment: str = "default"
    monitor: str = "val_loss"
    mode: str = "min"
    max_to_keep: int = 1
    async_checkpoint: bool = True
    use_tensorboard: bool = True
    # XLA cost-analysis FLOPs → in-loop MFU metric. Two caveats vs the
    # authoritative tools/hbm_roofline.py number: cost analysis counts ZERO
    # flops for Pallas custom-calls (configs whose hot ops run in the
    # kernels — e.g. flow — under-report here), and the denominator is WALL
    # time (dispatch stalls deflate it relative to device time).
    compute_mfu: bool = True
    profile_steps: int = 0  # capture a trace of this many steps after warmup
    profile_start_step: int = 10
    # In-loop self-profiling watchdog (obs.SelfProfiler): every N optimizer
    # steps, capture a short device trace, analyze it in-process (the
    # utils/xplane.py lower-quartile discipline — the device's own clock),
    # and publish device/host step time + MFU + compile count as
    # registry gauges AND metrics.jsonl rows. 0 disables. Unlike the in-loop
    # wall-clock MFU above, these numbers ride the DEVICE clock.
    selfprofile_every_n_steps: int = 0
    selfprofile_steps: int = 4  # dispatches per capture window
    # preemption safety (SURVEY.md §5, restart-on-failure): on SIGTERM, save
    # the CURRENT state to the checkpoint dir's unconditional last/ slot and
    # stop cleanly; restore_train_state(prefer_latest=True) resumes from it.
    checkpoint_on_sigterm: bool = True
    # failure detection (SURVEY.md §5): a non-finite train loss means the
    # params are already poisoned (NaN grads → NaN moments) and the run can
    # never recover — halt at the next log point instead of burning the rest
    # of the schedule. Checked only at log boundaries, where the loss scalar
    # is fetched anyway (no extra device sync on the hot path).
    halt_on_nonfinite: bool = True
    # NaN LOCALIZATION (the sanitizer tier above halt_on_nonfinite, which
    # only says THAT the run diverged): enables jax_debug_nans, so the first
    # dispatch producing a NaN/Inf re-runs de-optimized and raises
    # FloatingPointError pointing at the originating op. Debug mode: every
    # dispatch syncs to host, and the single-device path stops donating the
    # train state (the de-optimized re-run replays the same arguments, which
    # donation would have invalidated). Use for post-mortems, not production.
    debug_nans: bool = False
    # SELF-HEALING (SURVEY.md §5 actuation; perceiver_io_tpu.resilience).
    # skip_nonfinite_steps: check the loss after EVERY dispatch; a non-finite
    # step is SKIPPED (the pre-step state is kept, the poisoned update
    # discarded) instead of silently poisoning the moments the way the
    # halt_on_nonfinite log-boundary check can only report after the fact.
    # After rollback_after_bad_steps CONSECUTIVE bad steps the trainer
    # restores the newest checkpoint (prefer_latest — the last/ slot when
    # present; one is saved at fit() start if none exists yet) and continues.
    # Recovery mode costs one host sync per dispatch and disables train-state
    # donation (the kept pre-step state must stay alive) — a measured
    # robustness/throughput trade, off by default.
    skip_nonfinite_steps: bool = False
    rollback_after_bad_steps: int = 3
    # dispatch_error_retries: re-dispatch the SAME batch with exponential
    # backoff when the step raises an error the classification calls transient
    # (connection drops, PJRT UNAVAILABLE); fatal errors raise immediately.
    # Implies the per-dispatch sync too (async errors must surface inside
    # the retry scope). 0 disables.
    dispatch_error_retries: int = 0
    # fit_attempts: budget for fit_with_recovery's supervisor loop — on a
    # transient failure escaping the per-dispatch retries, auto-resume from
    # the newest checkpoint up to this many total attempts.
    fit_attempts: int = 1
    # MULTI-HOST FAULT TOLERANCE (resilience/multihost.py, PERF.md
    # §Multi-host recovery). step_timeout_s: bounded-exit deadline on the
    # dispatch cycle — if the host observes no step completion within this
    # window (the wedged-dead-collective signature) it dumps thread stacks
    # and exits with the TRANSIENT code so the restart-the-world supervisor
    # (--spawn_attempts) relaunches from the newest checkpoint. None = off.
    step_timeout_s: Optional[float] = None
    # peer_heartbeat_s: publish/scan cadence of the KV-store peer-liveness
    # monitor (multi-host only; detects a SILENTLY dead peer even between
    # collectives). Peer declared down after 5 missed beats. 0 = off.
    peer_heartbeat_s: float = 0.0
    # coord_check_dispatches: cadence (in dispatches) of the agreement-flag
    # READ on the coordination channel. The flag always rides every
    # dispatch on device; fetching its scalar is a host sync on the
    # previous dispatch, so 1 (the default, and what the chaos drills pin)
    # trades host run-ahead for a 2-dispatch preemption response, while a
    # host where a scalar fetch per dispatch costs more than the run-ahead
    # it forfeits (a host sync stalls the dispatch pipeline) should raise it — the schedule is identical on every
    # host for ANY value, so agreement stays deadlock-free, just later.
    coord_check_dispatches: int = 1
    # testing only: run the multi-host coordination channel on a single
    # process (agreement degenerates to one host's flags) — the tier-1
    # harness for the preemption-agreement plumbing, which otherwise only
    # executes under jax.process_count() > 1.
    force_coordination: bool = False
    # CONTINUOUS DEPLOYMENT (perceiver_io_tpu.deploy, PERF.md §Deployment):
    # every publish_every_n_steps optimizer steps, atomically publish the
    # CURRENT params to publish_dir with a manifest (step, val metrics,
    # content digest, package version) — the trainer half of the train→serve
    # loop. The serving side (cli/serve.py --watch_checkpoints) admission-
    # gates each publication before any replica sees it. Publication is
    # fail-soft: a failed publish warns and counts, never kills the run.
    # Single-process only (publishing device_gets the full tree; multi-host
    # global arrays are not host-addressable from one process).
    publish_dir: Optional[str] = None
    publish_every_n_steps: int = 0
    def __post_init__(self):
        if self.max_epochs is None and self.max_steps is None:
            raise ValueError("set max_epochs and/or max_steps")
        if self.dispatch_error_retries < 0:
            raise ValueError(
                f"dispatch_error_retries must be >= 0, got "
                f"{self.dispatch_error_retries}"
            )
        if self.fit_attempts < 1:
            raise ValueError(f"fit_attempts must be >= 1, got {self.fit_attempts}")
        if self.step_timeout_s is not None and self.step_timeout_s <= 0:
            raise ValueError(
                f"step_timeout_s must be positive, got {self.step_timeout_s}")
        if self.peer_heartbeat_s < 0:
            raise ValueError(
                f"peer_heartbeat_s must be >= 0, got {self.peer_heartbeat_s}")
        if self.coord_check_dispatches < 1:
            raise ValueError(
                f"coord_check_dispatches must be >= 1, got "
                f"{self.coord_check_dispatches}")
        if (self.publish_dir is None) != (self.publish_every_n_steps <= 0):
            raise ValueError(
                "checkpoint publication needs BOTH publish_dir and "
                "publish_every_n_steps > 0 (got "
                f"publish_dir={self.publish_dir!r}, "
                f"publish_every_n_steps={self.publish_every_n_steps})"
            )

    @property
    def recovery_active(self) -> bool:
        """True when fit() runs the per-dispatch recovery path (loss sync,
        no state donation)."""
        return self.skip_nonfinite_steps or self.dispatch_error_retries > 0


def _logged_name(metric: str) -> str:
    """A step metric's name in metrics.jsonl and the registry: the task's
    ``loss`` / ``acc`` take the ``train_`` prefix, a model's own keep theirs."""
    return f"train_{metric}" if metric in ("loss", "acc") else metric


class Trainer:
    """Drives jitted steps over data loaders; owns logging and checkpoints.

    Args:
      train_step: pure ``(state, batch) → (state, metrics)``.
      eval_step: pure ``(state, batch, key) → metrics`` (the key feeds
        stochastic eval such as MLM masking; ignore it for deterministic eval).
      state: initial ``TrainState``.
      example_batch: defines the step input contract (keys + shapes); loader
        batches may carry extra keys, which the trainer drops.
      mesh: optional ``jax.sharding.Mesh`` — SPMD mode: params/opt-state are
        placed by the sharding rules, the batch shards over ``data`` (and
        optionally ``seq``), gradient sync becomes a compiler-inserted psum.
      zero_opt: shard the optimizer state over ``data`` (ZeRO-style; SURVEY
        §2.3) — per-chip Adam mu/nu footprint drops by the dp size.
      hparams: JSON-serializable config embedded in checkpoints
        (``save_hyperparameters`` parity).
      predict_hook: ``(state, logger, step) → None`` called after each
        validation pass.
      tokens_per_example: when set, throughput is also logged as tokens/sec.
    """

    @obs.span("trainer.init")
    def __init__(
        self,
        train_step: Callable,
        eval_step: Optional[Callable],
        state,
        config: TrainerConfig,
        example_batch: Batch,
        mesh=None,
        shard_seq: bool = False,
        zero_opt: bool = False,
        rules: Sequence = PARAM_RULES,
        hparams: Optional[Dict[str, Any]] = None,
        predict_hook: Optional[Callable] = None,
        tokens_per_example: Optional[int] = None,
        run_dir: Optional[str] = None,
    ):
        self.config = config
        if ((config.dispatch_error_retries > 0 or config.fit_attempts > 1)
                and jax.process_count() > 1):
            # A dispatch retry RE-ENTERS a collective a peer already left
            # (the peers advanced past the program the retry replays), and a
            # fit_with_recovery restart does the same one level up — both
            # deadlock the job in mismatched programs, so they stay
            # single-process only. skip_nonfinite_steps is DIFFERENT since
            # r19: the skip is a device-side select driven by the globally
            # psummed loss (training/steps.py make_guarded_step), so every
            # host takes the identical branch and no program diverges.
            # Multi-host process-death recovery is restart-the-world
            # (--spawn_attempts supervision / --resume), which every host
            # performs identically.
            raise ValueError(
                "trainer dispatch retries / fit attempts "
                "(dispatch_error_retries / fit_attempts > 1) are "
                "single-process only — multi-host runs recover by "
                "restarting the world from the newest checkpoint "
                "(--spawn_attempts / --resume)"
            )
        if (config.skip_nonfinite_steps and jax.process_count() > 1
                and mesh is None):
            raise ValueError(
                "skip_nonfinite_steps under multiple processes needs a mesh: "
                "without one there is no collective for hosts to agree on "
                "the bad-step flag over (each host would train — and skip — "
                "independently)"
            )
        self._publisher = None
        if config.publish_dir:
            if jax.process_count() > 1:
                # publishing device_gets the FULL param tree; a multi-host
                # global array is not addressable from one process — the
                # multi-host deployment story is checkpoint-dir based
                raise ValueError(
                    "checkpoint publication (publish_dir) is single-process "
                    "only"
                )
            from perceiver_io_tpu.deploy import CheckpointPublisher

            self._publisher = CheckpointPublisher(config.publish_dir)
        self.mesh = mesh
        self.predict_hook = predict_hook
        self.tokens_per_example = tokens_per_example
        self._keys = tuple(sorted(example_batch))
        self._example_batch = {k: example_batch[k] for k in self._keys}

        self.run_dir = run_dir or next_version_dir(config.logdir, config.experiment)
        self.logger = MetricsLogger(self.run_dir, use_tensorboard=config.use_tensorboard)
        self.checkpoints = CheckpointManager(
            os.path.join(self.run_dir, "checkpoints"),
            max_to_keep=config.max_to_keep,
            monitor=config.monitor,
            mode=config.mode,
            hparams=hparams,
            async_save=config.async_checkpoint,
        )

        self._raw_train_step = train_step
        self._k = max(1, int(config.steps_per_dispatch))
        self._prev_debug_nans = None
        if config.debug_nans:
            # restored in __exit__ — a post-mortem Trainer must not leak
            # process-global debug mode into later work
            self._prev_debug_nans = jax.config.jax_debug_nans
            jax.config.update("jax_debug_nans", True)
        step_fn = train_step
        if config.skip_nonfinite_steps:
            # device-side collective-consistent skip: the select rides the
            # step itself, so the decision is bit-identical on every host
            # (and on every sub-step of a scanned window — wrap BEFORE scan)
            from perceiver_io_tpu.training.steps import make_guarded_step

            step_fn = make_guarded_step(step_fn)
        step_example = self._example_batch
        if self._k > 1:
            from perceiver_io_tpu.training.steps import make_scanned_step

            step_fn = make_scanned_step(step_fn)
            step_example = {
                k: np.stack([v]) for k, v in self._example_batch.items()
            }
        # Multi-host coordination channel (the preemption-agreement psum):
        # host-local flags ride every dispatch as a sharded int32 vector and
        # come back agreed (see parallel/sharding.py coord_flags_sharding).
        self._coord = (
            mesh is not None
            and config.checkpoint_on_sigterm
            and (jax.process_count() > 1 or config.force_coordination)
        )
        # donation is off under debug_nans (the de-optimized re-run replays
        # the original arguments) AND under recovery (a skipped bad step
        # keeps serving the PRE-step state, and a transient retry re-runs the
        # dispatch with it — donation would have invalidated both)
        no_donate = config.debug_nans or config.recovery_active
        self.donates_state = not no_donate
        if mesh is not None:
            self._train_step, self.state, self._batch_shardings = (
                make_sharded_train_step(
                    step_fn, mesh, state, step_example,
                    rules=rules, shard_seq=shard_seq, zero_opt=zero_opt,
                    stacked=self._k > 1,
                    donate_state=not no_donate,
                    coord_flags=self._coord,
                )
            )
            # Eval batches are never stacked (no scan axis) — with
            # steps_per_dispatch > 1 the train shardings above carry a leading
            # scan rank that would not match an eval array, so eval keeps its
            # own unstacked sharding plan.
            self._eval_batch_shardings = batch_shardings(
                self._example_batch, mesh, shard_seq
            )
        else:
            donate = () if no_donate else (0,)
            jitted = jax.jit(step_fn, donate_argnums=donate)
            self._train_step = lambda s, b: jitted(s, {k: b[k] for k in self._keys})
            self._train_step.jitted = jitted
            self.state = state
            self._batch_shardings = None
            self._eval_batch_shardings = None

        self._eval_step = None
        if eval_step is not None:
            if mesh is not None and shard_seq and mesh.shape[AXIS_SEQ] > 1:
                # same sequence-parallel kernel routing as the train step
                inner_eval = eval_step

                def eval_step(s, b, k):
                    with sequence_parallel_context(mesh):
                        return inner_eval(s, b, k)

            jitted_eval = jax.jit(eval_step)
            self._eval_step = lambda s, b, k: jitted_eval(
                s, {key: b[key] for key in self._keys}, k
            )

        self._flops_per_step: Optional[float] = None
        self._flops_attempted = False
        self._eval_key = jax.random.key(4242)

        # recovery telemetry: the chaos drills (tests/test_resilience.py)
        # assert these, and operators watch them the same way they watch the
        # serving shed/retry counters
        reg = obs.get_registry()
        self._m_bad_steps = reg.counter(
            "trainer_bad_steps_total", "non-finite train steps skipped")
        self._m_rollbacks = reg.counter(
            "trainer_rollbacks_total",
            "checkpoint rollbacks after consecutive bad steps")
        self._m_step_retries = reg.counter(
            "trainer_dispatch_retries_total",
            "transient train-dispatch retries")
        self._m_restarts = reg.counter(
            "trainer_fit_restarts_total",
            "fit_with_recovery auto-resumes after transient failures")
        self._m_preempt_saves = reg.counter(
            "trainer_preempt_saves_total",
            "SIGTERM-triggered preemption checkpoints (coordinated across "
            "all hosts under multi-process)")
        self._g_agreed = reg.gauge(
            "multihost_last_step_agreed",
            "optimizer step of the newest completed cross-host flag "
            "agreement round (coordination-channel liveness)")
        self._retry_policy = RetryPolicy(
            max_retries=config.dispatch_error_retries)
        self._bad_streak = 0
        self._sigterm = False
        self._pending_flags = None
        self._agreed_preempt = False
        self._coord_dispatch = 0
        self._last_val_metrics: Dict[str, float] = {}
        self._last_train_loss = float("nan")
        # the open iteration of fit's dispatch loop, on time.monotonic_ns():
        # its start, and the host's time so far in next() on the loader and
        # in _dispatch (``_iterations``)
        self._iter_start_ns = self._loader_ns = self._dispatch_ns = 0

        self._selfprof = None
        if config.selfprofile_every_n_steps > 0:
            from perceiver_io_tpu.obs import SelfProfiler

            self._selfprof = SelfProfiler(
                every_n=config.selfprofile_every_n_steps,
                trace_steps=config.selfprofile_steps,
                prefix="train",
                flops_per_step=lambda: self._flops_per_step,
                num_devices=(mesh.size if mesh is not None else 1),
            )

    # -- internals -----------------------------------------------------------

    def _to_global(self, batch: Batch, shardings=None) -> Batch:
        """Host-local loader batch → global sharded arrays (multi-host only).

        Per-host loaders yield each process's shard of the global batch
        (reference DDP semantics: Lightning's DistributedSampler gives every
        rank its own slice). A mesh-sharded jit consumes GLOBAL arrays, so in
        multi-process mode each local batch becomes this process's shard of a
        global ``jax.Array`` — the multi-host equivalent of device_put.

        ``shardings`` defaults to the train-step plan; eval passes its own
        (unstacked) plan, which differs whenever ``steps_per_dispatch > 1``.
        """
        if shardings is None:
            shardings = self._batch_shardings
        if shardings is None or jax.process_count() == 1:
            return batch
        return {
            k: jax.make_array_from_process_local_data(
                shardings[k], np.asarray(batch[k])
            )
            for k in self._keys
        }

    def _maybe_compute_flops(self, batch: Batch) -> None:
        """Lazily derive per-step FLOPs from XLA cost analysis (once).

        Only attempted on devices with a known peak (TPUs) — elsewhere MFU is
        undefined and the lowering is wasted work. The lowering reuses the
        exact jit wrapper driving training (same shardings/donation), so the
        compiled executable comes from jit's cache — no second compile.

        The dispatch width (``steps_per_dispatch``) deliberately does NOT
        enter here: XLA cost analysis counts a ``lax.scan`` body ONCE
        regardless of trip count (``test_scanned_step_cost_analysis_is_per_
        step``), so the K-step scanned executable's reported flops already
        ARE per-step flops. Dividing by K made the in-loop MFU metric K×
        too low under multi-step dispatch (r4: the flagship_tpu soak logged
        3.1% in-loop vs 53.6% trace-measured at K=16).
        """
        if self._flops_attempted or not self.config.compute_mfu:
            return
        self._flops_attempted = True
        if jax.process_count() > 1:
            # lowering with a host-local example would trace a second (wrong)
            # shape; per-host cost attribution is not meaningful anyway
            return
        if profiling.device_peak_flops() is None:
            return
        flops = profiling.compiled_flops(
            self._train_step.jitted,
            self.state,
            {k: batch[k] for k in self._keys},
        )
        self._flops_per_step = flops

    def _warn_if_trace_empty(self) -> None:
        """Post-capture sanity: very long profile windows (tens of device-
        seconds — e.g. profile_steps counting optimizer steps under a large
        steps_per_dispatch) can silently overflow the xplane export, leaving
        a 0-byte ``*.xplane.pb`` next to a populated json trace (observed
        r4: a 320-step K=16 window). Warn instead of letting the user
        discover it at analysis time."""
        import glob as _glob

        dirs = sorted(_glob.glob(os.path.join(
            self.run_dir, "plugins", "profile", "*")))
        if not dirs:
            return
        # newest capture dir only (timestamp-named), ANY empty per-host file
        # counts — one overflowed host must not hide behind another's
        # populated export
        paths = _glob.glob(os.path.join(dirs[-1], "*.xplane.pb"))
        if paths and any(os.path.getsize(p) == 0 for p in paths):
            warnings.warn(
                "profiler capture produced an EMPTY xplane.pb — the profile "
                "window was likely too long for the xplane export (note "
                "profile_steps counts OPTIMIZER steps: a K-step dispatch "
                "advances it by K). Use a window of at most a few seconds "
                "of device time.", stacklevel=2,
            )

    def _iterations(self, loader):
        """``_dispatch_batches`` with the iteration clock. An iteration of
        ``fit``'s dispatch loop runs from one resumption of this generator to
        the next, so each unit handed out becomes one ``train.step`` span
        (``obs.spans("train.step")``, child of the call's ``train.fit``) when
        the loop comes back for more; ``fit`` enters the one it breaks out
        of. Fields, in nanoseconds: ``loader_ns``, the Trainer's own wait in
        ``next()`` on the loader, and ``dispatch_ns``, its time inside
        ``_dispatch`` (the host-to-device put plus the call of the jitted
        step, which the runtime holds back once enough programs are in
        flight). What is left of the iteration is the loop's own Python and,
        where it has them, the log boundary's syncs, eval and checkpoints.
        The wait that finds the loader exhausted belongs to no iteration."""
        self._iter_start_ns = time.monotonic_ns()
        self._loader_ns = self._dispatch_ns = 0
        for unit in self._dispatch_batches(self._timed(loader)):
            yield unit
            self._end_iteration()

    def _timed(self, loader):
        src = iter(loader)
        while True:
            t0 = time.monotonic_ns()
            batch = next(src, _END)
            self._loader_ns += time.monotonic_ns() - t0
            if batch is _END:
                return
            yield batch

    def _end_iteration(self) -> None:
        end_ns = time.monotonic_ns()
        obs.add_span("train.step", self._iter_start_ns, end_ns,
                     loader_ns=self._loader_ns, dispatch_ns=self._dispatch_ns)
        self._iter_start_ns, self._loader_ns, self._dispatch_ns = end_ns, 0, 0

    def _dispatch_batches(self, loader):
        """Yield ``(batch, n_steps)`` dispatch units: single loader batches
        (K=1), or up to K of them stacked on a new leading scan axis. A
        window is flushed early when the next batch's SHAPES differ (width-
        bucketed text loaders emit same-width runs of K — data/pipeline.py
        ``group_size`` — so early flushes only happen at run boundaries);
        partial windows compile once per (length, shape) and are cached
        across epochs. Batches are always consumed in loader order, which is
        what keeps the mid-epoch resume arithmetic (``skip_next``) exact."""
        if self._k <= 1:
            for batch in loader:
                yield batch, 1
            return
        buf, sig = [], None
        for batch in loader:
            shapes = {k: np.asarray(batch[k]).shape for k in self._keys}
            if buf and shapes != sig:
                yield self._stack(buf), len(buf)
                buf = []
            buf.append(batch)
            sig = shapes
            if len(buf) == self._k:
                yield self._stack(buf), self._k
                buf = []
        if buf:
            yield self._stack(buf), len(buf)

    def _stack(self, batches):
        return {
            k: np.stack([np.asarray(b[k]) for b in batches])
            for k in self._keys
        }

    def _throughput_metrics(
        self, n_steps: int, elapsed: float, batch_size: int
    ) -> Dict[str, float]:
        out: Dict[str, float] = {}
        if elapsed <= 0 or n_steps == 0:
            return out
        steps_per_sec = n_steps / elapsed
        out["steps_per_sec"] = steps_per_sec
        out["examples_per_sec"] = steps_per_sec * batch_size
        if self.tokens_per_example:
            out["tokens_per_sec"] = out["examples_per_sec"] * self.tokens_per_example
        if self._flops_per_step:
            u = profiling.mfu(
                self._flops_per_step * n_steps, elapsed,
                num_devices=(self.mesh.size if self.mesh is not None else 1),
            )
            if u is not None:
                out["mfu"] = u
        return out

    # -- multi-host coordination (resilience) --------------------------------

    def _local_flags_array(self):
        """This host's flag bitmask as its shard of the coordination vector
        (one int32 per local device, all equal — see ``coord_flags_sharding``
        for why the per-device layout is irrelevant)."""
        bits = _PREEMPT_BIT if self._sigterm else 0
        n = jax.local_device_count()
        return jax.make_array_from_process_local_data(
            self._train_step.coord_flags_sharding,
            np.full((n,), bits, np.int32),
            (jax.device_count(),),
        )

    def _dispatch(self, batch):
        """One train dispatch; feeds the coordination flags when the
        multi-host agreement channel is active."""
        t0 = time.monotonic_ns()
        try:
            # chaos hook over the HOST-LOCAL batch: nan = one host's shard
            # corrupted (its NaN rides the global loss reduction to every peer
            # — the agreement drill), hang/slow = a wedged/throttled host
            batch = faults.fire("trainer.collective", batch)
            gb = self._to_global(batch)
            if self._coord:
                return self._train_step(
                    self.state, gb, self._local_flags_array())
            return self._train_step(self.state, gb)
        finally:
            self._dispatch_ns += time.monotonic_ns() - t0

    def _note_coord(self, metrics: Metrics, step_i: int) -> None:
        """Consume the agreed-flags output of THIS dispatch, and read the
        one from the PREVIOUS dispatch (already complete, so the read never
        waits on in-flight device work — though it IS one scalar fetch, a
        host round-trip the ``coord_check_dispatches`` cadence amortizes on
        dispatch-latency-bound transports). Every host runs this identical
        deterministic schedule over identical device-agreed values, so
        every host observes an agreed preemption at the same dispatch
        boundary — ``coord_check_dispatches + 1`` dispatches after the
        first host's SIGTERM at the latest."""
        if not self._coord or metrics is None:
            return
        flags = metrics.pop("coord_flags", None)
        prev, self._pending_flags = self._pending_flags, flags
        self._coord_dispatch += 1
        if prev is None or (
                self._coord_dispatch % self.config.coord_check_dispatches):
            return
        agreed = int(jax.device_get(prev))
        self._g_agreed.set(step_i)
        if agreed & _PREEMPT_BIT:
            self._agreed_preempt = True

    def _preempt_save(self, step_i: int) -> None:
        """The preemption checkpoint: save the CURRENT state to the
        unconditional ``last/`` slot and flush logs. Under multi-process
        every host reaches this at the SAME dispatch boundary (the agreed
        flag is device-replicated), so the Orbax save's internal collectives
        line up and every rank exits 0."""
        self.checkpoints.save_last(step_i, self.state)
        self._m_preempt_saves.inc()
        obs.event("trainer_preempt_save", step=step_i,
                  coordinated=self._coord)
        self.logger.log_text(
            "events", step_i,
            f"SIGTERM: saved last/ checkpoint at step {step_i}"
            + (" (coordinated across hosts)" if self._coord else ""),
        )
        self.logger.flush()

    # -- self-healing (resilience) -------------------------------------------

    def _ensure_rollback_target(self, step_i: int) -> None:
        """Make sure a rollback has somewhere to land: with no checkpoint yet
        (bad steps can hit before the first validation pass), save the
        CURRENT state to the unconditional ``last/`` slot."""
        if self.checkpoints.latest_step is None:
            self.checkpoints.save_last(step_i, self.state)

    def _rollback(self, step_i: int) -> None:
        """K consecutive bad steps: the in-memory state is presumed poisoned
        (NaN moments survive a skipped update's discard only if the corruption
        predates the streak) — restore the newest checkpoint and continue."""
        from perceiver_io_tpu.training.checkpoint import restore_train_state

        self.checkpoints.wait()
        restored = restore_train_state(
            self.checkpoints.directory, self.state, prefer_latest=True
        )
        self.state = restored
        self._bad_streak = 0
        self._m_rollbacks.inc()
        to_step = int(jax.device_get(restored.step))
        obs.event("trainer_rollback", from_step=step_i, to_step=to_step)
        self.logger.log_text(
            "events", step_i,
            f"{self.config.rollback_after_bad_steps} consecutive non-finite "
            f"steps: rolled back to checkpoint step {to_step}",
        )
        self.logger.flush()

    def _recovering_step(self, batch, step_i: int):
        """One dispatch under the recovery config: transient-error retry with
        backoff, per-dispatch finite check, skip / rollback. Returns
        ``(status, metrics)`` with status ``'ok'`` (state advanced),
        ``'skipped'`` (bad step discarded — the caller must re-read
        ``state.step``, since a scanned window may have applied its good
        sub-steps on device) or ``'rolled_back'`` (state restored from
        checkpoint — same re-read contract).

        The ``float(loss)`` here is the recovery mode's per-dispatch host
        sync: it surfaces async dispatch errors INSIDE the retry scope and
        feeds the finite guard (the documented robustness/throughput trade).

        The skip DECISION comes from two tiers: the device-agreed
        ``bad_step`` flag (``make_guarded_step`` — the select already kept
        the pre-step state on device, identically on every host), and — on a
        single process only — the host-observed loss value, which catches
        host-side corruption (the ``trainer.metrics`` chaos drills). Under
        multiple processes the host-side observation deliberately does NOT
        drive the decision: a per-host verdict on a per-host value is
        exactly the program divergence that deadlocks collectives.
        """
        cfg = self.config

        def attempt():
            faults.inject("trainer.dispatch")  # chaos hook (no-op unless
            with profiling.annotate_step(step_i):  # an injector is live)
                new_state, metrics = self._dispatch(batch)
            metrics = faults.corrupt("trainer.metrics", metrics)
            loss = float(metrics["loss"]) if "loss" in metrics else None
            return new_state, metrics, loss

        def on_retry(retry: int, error: BaseException, pause: float) -> None:
            self._m_step_retries.inc()
            obs.event("trainer_dispatch_retry", retry=retry,
                      error=type(error).__name__, backoff_s=round(pause, 4))
            self.logger.log_text(
                "events", step_i,
                f"transient dispatch error ({type(error).__name__}: {error});"
                f" retry {retry}/{self._retry_policy.max_retries} after "
                f"{pause:.2f}s",
            )

        new_state, metrics, loss = call_with_retry(
            attempt, policy=self._retry_policy, on_retry=on_retry
        )
        self._note_coord(metrics, step_i)
        flag = metrics.get("bad_step")
        # int32 flag: immune to host-side NaN corruption of the metrics, and
        # already the fleet-agreed verdict (see make_guarded_step)
        device_bad = flag is not None and int(jax.device_get(flag)) > 0
        host_bad = loss is not None and not np.isfinite(loss)
        single = jax.process_count() == 1
        if cfg.skip_nonfinite_steps and (device_bad or (host_bad and single)):
            if device_bad:
                # the device select already kept the pre-step state (and
                # applied any good sub-steps of a scanned window) — adopt it
                self.state = new_state
            self._bad_streak += 1
            self._m_bad_steps.inc()
            obs.event("trainer_bad_step", step=step_i, loss=str(loss),
                      streak=self._bad_streak)
            self.logger.log_text(
                "events", step_i,
                f"non-finite loss {loss} at step {step_i}: step skipped, "
                f"pre-step state kept (streak {self._bad_streak})",
            )
            if (cfg.rollback_after_bad_steps > 0
                    and self._bad_streak >= cfg.rollback_after_bad_steps):
                self._rollback(step_i)
                return "rolled_back", None
            return "skipped", None
        self._bad_streak = 0
        self.state = new_state
        return "ok", metrics

    def fit_with_recovery(self, train_loader, val_loader=None,
                          max_attempts: Optional[int] = None):
        """:meth:`fit` under a supervisor: an attempt that dies with a
        TRANSIENT error (``resilience.classify_error`` — connection drops, PJRT
        UNAVAILABLE; never divergence or shape bugs) auto-resumes from the
        newest checkpoint (``prefer_latest``, the same path ``--resume``
        takes — falling back to the in-memory state when none exists yet) and
        retries, up to ``max_attempts`` total attempts (default
        ``config.fit_attempts``). Completes the SIGTERM/resume story for
        failures that kill the step instead of the process."""
        from perceiver_io_tpu.training.checkpoint import restore_train_state

        attempts = max(1, int(self.config.fit_attempts if max_attempts is None
                              else max_attempts))
        for attempt in range(1, attempts + 1):
            try:
                return self.fit(train_loader, val_loader)
            except Exception as e:
                if attempt >= attempts or not is_transient(e):
                    raise
                self._m_restarts.inc()
                obs.event("trainer_fit_restart", attempt=attempt,
                          error=type(e).__name__)
                try:
                    self.checkpoints.wait()
                    self.state = restore_train_state(
                        self.checkpoints.directory, self.state,
                        prefer_latest=True,
                    )
                except FileNotFoundError:
                    pass  # nothing saved yet: resume from the in-memory state
                resumed = int(jax.device_get(self.state.step))
                self.logger.log_text(
                    "events", resumed,
                    f"fit attempt {attempt} failed with transient "
                    f"{type(e).__name__}: {e}; auto-resuming from step "
                    f"{resumed} ({attempts - attempt} attempts left)",
                )
                self.logger.flush()

    def _run_eval(self, val_loader) -> Dict[str, float]:
        totals: Dict[str, float] = {}
        weight = 0.0
        for i, batch in enumerate(val_loader):
            self._eval_key, key = jax.random.split(self._eval_key)
            metrics = self._eval_step(
                self.state,
                self._to_global(batch, self._eval_batch_shardings),
                key,
            )
            # weight by the LOCAL shard size: with global eval batches every
            # host computes identical metrics, and the cross-host sum below
            # then weights each global batch by its true global size
            n = len(batch[self._keys[0]])
            for k, v in metrics.items():
                totals[k] = totals.get(k, 0.0) + float(v) * n
            weight += n
        if jax.process_count() > 1:
            # every host evaluates its own shard; reduce sums so all hosts log
            # identical metrics and make identical best-checkpoint decisions
            from jax.experimental import multihost_utils

            names = sorted(totals)
            local = np.asarray([totals[k] for k in names] + [weight], np.float64)
            summed = np.sum(multihost_utils.process_allgather(local), axis=0)
            totals = dict(zip(names, summed[:-1]))
            weight = summed[-1]
        if weight == 0:
            return {}
        return {f"val_{k}": v / weight for k, v in totals.items()}

    def _publish(self, step_i: int) -> None:
        """Publish the CURRENT params (deploy.CheckpointPublisher — atomic,
        manifest-carrying, fail-soft). Metrics in the manifest: the newest
        validation pass plus the last logged train loss, so the serving-side
        gate (and operators) can see what quality the tree claims."""
        metrics = dict(self._last_val_metrics)
        if np.isfinite(self._last_train_loss):
            metrics.setdefault("train_loss", float(self._last_train_loss))
        self._publisher.publish(
            step_i, jax.device_get(self.state.params), val_metrics=metrics)

    def _validate_and_checkpoint(self, step_i: int, val_loader) -> Dict[str, float]:
        val_metrics = self._run_eval(val_loader) if val_loader is not None else {}
        self._last_val_metrics = dict(val_metrics)
        if val_metrics:
            self.logger.log_scalars(step_i, val_metrics)
        ckpt_metrics = dict(val_metrics)
        if self.config.monitor in ckpt_metrics or val_loader is None:
            if val_loader is None:
                ckpt_metrics = {self.config.monitor: self._last_train_loss}
            self.checkpoints.save(step_i, self.state, ckpt_metrics)
        if self.predict_hook is not None:
            self.predict_hook(self.state, self.logger, step_i)
        self.logger.flush()
        return val_metrics

    def test(self, test_loader) -> Dict[str, float]:
        """One evaluation pass over a held-out split, logged as ``test_*``
        (the reference's ``test_step``/``test_epoch`` path,
        ``lightning.py:141-147`` — there the IMDB test split doubles as val,
        ``imdb.py:133``, so this is the explicit variant)."""
        if self._eval_step is None:
            raise ValueError("Trainer.test() needs an eval_step; this trainer "
                             "was constructed with eval_step=None")
        metrics = {
            k.replace("val_", "test_", 1): v
            for k, v in self._run_eval(test_loader).items()
        }
        if metrics:
            step_i = int(jax.device_get(self.state.step))
            self.logger.log_scalars(step_i, metrics)
            self.logger.flush()
        return metrics

    # -- the loop ------------------------------------------------------------

    @obs.span("train.fit")
    def fit(self, train_loader, val_loader=None):
        """Run the training loop; returns the final state.

        ``train_loader`` is re-iterated per epoch (fresh shuffle each time);
        ``val_loader`` per validation pass. Each call is one ``train.fit``
        span (``obs.spans``), from entry to return, with one ``train.step``
        span per iteration of the dispatch loop under it (``_iterations``).
        """
        cfg = self.config
        step_i = int(jax.device_get(self.state.step))
        epoch = 0
        done = False
        self._last_train_loss = float("nan")

        # restoring a completed run is a no-op, not one extra step
        if cfg.max_steps is not None and step_i >= cfg.max_steps:
            return self.state

        # Deterministic resume (SURVEY.md §5, failure detection): a restored
        # state starts at step > 0 — fast-forward the loader to the epoch and
        # in-epoch offset that step corresponds to, so the resumed run sees
        # exactly the batches the uninterrupted run would have (the loader
        # shuffles by seed ⊕ epoch, so epoch alignment is all it takes).
        if step_i > 0:
            try:
                steps_per_epoch = len(train_loader)
            except TypeError:
                steps_per_epoch = 0
            if steps_per_epoch > 0 and hasattr(train_loader, "epoch"):
                epoch = step_i // steps_per_epoch
                train_loader.epoch = epoch
                skip = step_i % steps_per_epoch
                if skip and hasattr(train_loader, "skip_next"):
                    train_loader.skip_next(skip)

        window_start = time.perf_counter()
        window_steps = 0
        seen_shapes: set = set()
        profiling_active = False
        profile_captured = False
        last_validated_step = step_i
        self._bad_streak = 0
        if cfg.skip_nonfinite_steps and cfg.rollback_after_bad_steps > 0:
            self._ensure_rollback_target(step_i)

        # SIGTERM = preemption notice: finish the in-flight step, save the
        # newest state unconditionally, stop cleanly. The handler only sets a
        # flag — all real work happens on the main thread between steps.
        # Single-process: the flag is acted on directly at the next step
        # boundary. Multi-process (coordination channel active): hosts
        # observe SIGTERM at different step boundaries, and Orbax saves of
        # mesh-sharded arrays are multi-host collectives — so the local flag
        # only rides the next dispatch's agreement psum, and EVERY host acts
        # on the agreed verdict at the same boundary (one coordinated
        # save_last, every rank exits 0). Multi-process WITHOUT a mesh has
        # no agreement channel: the handler stays uninstalled, and recovery
        # is restart-the-world (--spawn_attempts / --resume).
        self._sigterm = False
        self._pending_flags = None
        self._agreed_preempt = False
        self._coord_dispatch = 0
        handler_installed = False
        prev_handler = None
        if (cfg.checkpoint_on_sigterm
                and (jax.process_count() == 1 or self._coord)
                and threading.current_thread() is threading.main_thread()):
            def _on_sigterm(signum, frame):
                self._sigterm = True

            prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
            handler_installed = True

        # bounded-exit machinery (resilience/multihost.py): a per-step
        # deadline on the dispatch cycle, and — multi-process — the KV-store
        # peer-liveness monitor, so a surviving host never blocks past the
        # configured window inside a collective whose peer died
        step_guard = None
        peer_monitor = None
        if cfg.step_timeout_s:
            from perceiver_io_tpu.resilience.multihost import StepDeadline

            step_guard = StepDeadline("trainer_step", cfg.step_timeout_s)
        if cfg.peer_heartbeat_s > 0 and jax.process_count() > 1:
            from perceiver_io_tpu.resilience.multihost import (
                PeerLivenessMonitor,
            )

            peer_monitor = PeerLivenessMonitor(
                interval_s=cfg.peer_heartbeat_s).start()

        metrics: Metrics = {}
        try:
            while not done:
                if cfg.max_epochs is not None and epoch >= cfg.max_epochs:
                    break
                steps_this_epoch = 0
                batches_this_epoch = 0
                for batch, ksteps in self._iterations(train_loader):
                    batches_this_epoch += 1
                    # single-process: act on the local flag directly;
                    # coordinated: only on the fleet-AGREED flag, which every
                    # host observes at the same boundary
                    if (self._agreed_preempt
                            or (self._sigterm and not self._coord)):
                        self._preempt_save(step_i)
                        done = True
                        break
                    if cfg.max_steps is not None:
                        # never overshoot max_steps: trim the final window
                        remaining = cfg.max_steps - step_i
                        if remaining < ksteps:
                            batch = {
                                k: v[:remaining] for k, v in batch.items()
                            }
                            ksteps = remaining
                    if (
                        cfg.profile_steps > 0
                        and not profiling_active
                        and not profile_captured
                        and step_i >= cfg.profile_start_step
                        # the watchdog may hold the process's one trace slot
                        and not (self._selfprof is not None
                                 and self._selfprof._tracing)
                    ):
                        jax.profiler.start_trace(self.run_dir)
                        profiling_active = True
                        profile_start = step_i

                    # the first dispatch of every NEW batch-shape signature
                    # carries a jit compile (tens of seconds to minutes
                    # for a whole step — and width-bucketed loaders
                    # introduce new shapes mid-run): the per-step deadline
                    # only means something on already-compiled shapes; a
                    # peer dead during a compile is the peer-liveness
                    # monitor's catch
                    sig = (ksteps,) + tuple(
                        np.asarray(batch[k]).shape for k in self._keys)
                    if step_guard is not None and sig in seen_shapes:
                        step_guard.arm()
                    seen_shapes.add(sig)
                    if cfg.recovery_active:
                        status, stepped = self._recovering_step(batch, step_i)
                        if step_guard is not None:
                            step_guard.disarm()  # the recovery path synced
                        if status == "rolled_back":
                            # the restored state's step is authoritative; the
                            # loader stream continues from its current
                            # position (recovery favors forward progress over
                            # exact batch replay — logged above)
                            step_i = int(jax.device_get(self.state.step))
                            window_start = time.perf_counter()
                            window_steps = 0
                            continue
                        if status == "skipped":
                            # batch consumed; a scanned window may still have
                            # applied its good sub-steps on device — the
                            # selected state's step is authoritative
                            step_i = int(jax.device_get(self.state.step))
                            continue
                        metrics = stepped
                    else:
                        with profiling.annotate_step(step_i):
                            self.state, metrics = self._dispatch(batch)
                        self._note_coord(metrics, step_i)
                    prev_step = step_i
                    step_i += ksteps
                    window_steps += ksteps
                    steps_this_epoch += ksteps

                    if profiling_active and step_i >= profile_start + cfg.profile_steps:
                        jax.block_until_ready(metrics["loss"])
                        jax.profiler.stop_trace()
                        profiling_active = False
                        profile_captured = True
                        self._warn_if_trace_empty()

                    if self._selfprof is not None and not profiling_active:
                        sp = self._selfprof.tick(
                            ksteps,
                            sync=lambda: jax.block_until_ready(metrics),
                        )
                        if sp:
                            self.logger.log_scalars(step_i, sp)

                    n = cfg.log_every_n_steps
                    if step_i // n > prev_step // n:
                        self._maybe_compute_flops(batch)
                        # the float() conversions are the only host syncs in the loop
                        host_metrics = {
                            _logged_name(k): float(v)
                            for k, v in metrics.items()
                        }
                        self._last_train_loss = host_metrics.get(
                            "train_loss", self._last_train_loss
                        )
                        if (
                            cfg.halt_on_nonfinite
                            and "train_loss" in host_metrics
                            and not np.isfinite(host_metrics["train_loss"])
                        ):
                            self.logger.log_scalars(step_i, host_metrics)
                            self.logger.flush()
                            raise FloatingPointError(
                                f"non-finite train loss "
                                f"{host_metrics['train_loss']} at step {step_i} — "
                                f"training diverged (disable with "
                                f"halt_on_nonfinite=False)"
                            )
                        now = time.perf_counter()
                        leaf = batch[self._keys[0]]
                        # per-step batch size: stacked dispatches carry the
                        # scan axis in front
                        batch_size = leaf.shape[1] if self._k > 1 else len(leaf)
                        if self.mesh is not None:
                            # loaders are per-host; the global batch spans processes
                            batch_size *= jax.process_count()
                        host_metrics.update(
                            self._throughput_metrics(
                                window_steps, now - window_start, batch_size
                            )
                        )
                        self.logger.log_scalars(step_i, host_metrics)
                        window_start, window_steps = now, 0

                    if step_guard is not None:
                        # DISARM (not beat) only now: the guard must cover
                        # every host sync that can block on THIS dispatch —
                        # _note_coord's pipelined flag read, the selfprof
                        # tick, the log-boundary metric fetches — but not
                        # the legitimately unbounded work past this point
                        # (first-eval compiles, checkpoint saves). With no
                        # sync this iteration the wedge is caught at the
                        # next one that blocks (bounded by the log cadence
                        # on the async fast path).
                        step_guard.disarm()

                    ev = cfg.eval_every_n_steps
                    if ev and step_i // ev > prev_step // ev:
                        self._validate_and_checkpoint(step_i, val_loader)
                        last_validated_step = step_i
                        window_start, window_steps = time.perf_counter(), 0

                    # train→serve publication cadence (AFTER a same-boundary
                    # eval, so the manifest carries the fresh val metrics)
                    pn = cfg.publish_every_n_steps
                    if (self._publisher is not None
                            and step_i // pn > prev_step // pn):
                        self._publish(step_i)

                    if cfg.max_steps is not None and step_i >= cfg.max_steps:
                        self._end_iteration()
                        done = True
                        break
                if (self._agreed_preempt
                        or (self._sigterm and not self._coord)):
                    break
                if batches_this_epoch == 0:
                    raise ValueError(
                        "train_loader produced no batches (dataset shard smaller "
                        "than the batch size with drop_last?)"
                    )
                if steps_this_epoch == 0:
                    # batches flowed but EVERY step was skipped as non-finite
                    # (and rollback is off or landed back in the same state):
                    # the run cannot progress — surface the real diagnosis
                    # instead of looping epochs forever
                    raise FloatingPointError(
                        f"every train step of epoch {epoch} was skipped as "
                        f"non-finite ({batches_this_epoch} batches) — the "
                        f"run cannot make progress; inspect with debug_nans "
                        f"or lower the learning rate"
                    )
                epoch += 1
                if not cfg.eval_every_n_steps:
                    if not np.isfinite(self._last_train_loss) and "loss" in metrics:
                        self._last_train_loss = float(metrics["loss"])
                    self._validate_and_checkpoint(step_i, val_loader)
                    last_validated_step = step_i
                    window_start, window_steps = time.perf_counter(), 0

        finally:
            # a halt_on_nonfinite raise (or any other error) must not leak
            # an active profiler trace into the process
            if profiling_active:
                jax.profiler.stop_trace()
            if self._selfprof is not None:
                self._selfprof.close()  # abort an open watchdog window
            if step_guard is not None:
                step_guard.close()
            if peer_monitor is not None:
                peer_monitor.close()
            if handler_installed:
                # signal.signal returned None when the prior disposition was
                # installed outside Python — restore the default, never leave
                # the flag-setter swallowing SIGTERM after fit() returns
                signal.signal(
                    signal.SIGTERM,
                    prev_handler if prev_handler is not None else signal.SIG_DFL,
                )
        # the final-interval guard must branch IDENTICALLY on every host:
        # under coordination only the fleet-agreed preemption counts (the
        # raw local flag is per-host and would diverge the final collectives)
        preempted = self._agreed_preempt or (
            self._sigterm and not self._coord)
        if step_i > last_validated_step and not preempted:
            # final partial interval (eval_every_n_steps runs): don't lose the
            # tail — validate and give the checkpointer a shot at it
            if not np.isfinite(self._last_train_loss) and "loss" in metrics:
                self._last_train_loss = float(metrics["loss"])
            self._validate_and_checkpoint(step_i, val_loader)
        self._publish_last_step(metrics)
        self.checkpoints.wait()
        self.logger.flush()
        return self.state

    def _publish_last_step(self, metrics: Metrics) -> None:
        """The last dispatched step's metrics as registry gauges, under the
        log boundary's names (``train_loss``, ``lr``, a model's own counters):
        a fit that ends between two boundaries, or is shorter than one
        interval, still leaves them readable. One wait on the last step's
        outputs, which the end-of-epoch bookkeeping above has usually paid
        already (``_last_train_loss``); no row is written to metrics.jsonl."""
        reg = obs.get_registry()
        for k, v in metrics.items():
            reg.gauge(_logged_name(k)).set(float(v))

    def set_flops_per_step(self, flops: Optional[float]) -> None:
        """Install the per-step FLOP count used for the MFU metric (compute it
        once via ``profiling.compiled_flops`` on the caller's jitted step)."""
        self._flops_per_step = flops

    def close(self) -> None:
        self.checkpoints.close()
        self.logger.close()
        if self._prev_debug_nans is not None:
            jax.config.update("jax_debug_nans", self._prev_debug_nans)
            self._prev_debug_nans = None

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
