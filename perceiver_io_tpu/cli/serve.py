"""Fill-mask serving entry point over the micro-batching engine.

The serving-side sibling of the ``train_*`` CLIs: load an MLM checkpoint
(hparams-embedded, ``MLMPredictor.from_checkpoint`` semantics) plus its
tokenizer, warm every (width, batch, query) bucket program ahead of time, and
serve fill-mask requests through ``inference/engine.py``'s continuous
micro-batcher — one JSON line per text on stdout.

Usage::

    python -m perceiver_io_tpu.cli.serve \
        --checkpoint logs/mlm/version_0/checkpoints \
        --tokenizer .cache/imdb-tokenizer-10003.json \
        --texts "this movie was [MASK]" "a [MASK] ending"

    # a stream on stdin (one text per line), width-bucketed, bf16 serving
    ... --stdin --bucket_widths 128 256 --dtype bfloat16

``--cached`` serves through the encode-once/decode-many latent-cache path
instead of the fused forward — same results (parity-tested), useful to smoke
the split pipeline a multi-query deployment would run.

``--quantize int8`` is the weight-only int8 serving path: matmul kernels are
quantized once at load (per-channel symmetric int8, f32 scales —
``perceiver_io_tpu.quant``) and dequantized inside the compiled programs, so
each micro-batch streams int8 weight bytes from HBM. The checkpoint stays
f32 on disk; parity error vs the f32 oracle is bounded and measured
(`tools/quant_bench.py`, PERF.md §Quantization).

Cold start (``perceiver_io_tpu.aot``): jax's persistent compilation cache is
always on, at ``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.cache/jax``, so
a warm restart's warmup performs zero backend compiles (every bucket program
is a disk hit; trace and lower are still paid). ``--compile_cache DIR`` is
the location of the serialized-EXECUTABLE entries, which skip trace and lower
too — but an executable the persistent cache served cannot be serialized a
second time (``aot/cache.py``), so entries are only WRITTEN by a process run
with ``JAX_ENABLE_COMPILATION_CACHE=false``; existing entries always load.
Warmup itself runs in the BACKGROUND by default
(priority-ordered, smallest buckets first): the first request is answered as
soon as its program is ready, not after the whole family is warm
(``--blocking_warmup`` restores the old wait). A missing/unusable cache dir
warns and serves uncached — a cache problem never refuses traffic.

``--slo_p99_ms`` declares a serving SLO (``perceiver_io_tpu.obs.slo``):
every answered/shed request classifies against the latency target, the
windowed error-budget burn rate rides ``/metrics``+``/statz`` as ``slo_*``
gauges, and ``/healthz`` degrades when the burn rate crosses
``--slo_burn_alert``. Per-request phase tracing
(``serving_phase_seconds{phase=...}``) attributes tail latency to
admission/queue/assembly/dispatch/device/complete; sweep offered load and
fit the capacity model with ``tools/load_bench.py`` (PERF.md §SLO).

``--replicas N`` serves through the multi-replica fabric
(``perceiver_io_tpu.serving``, PERF.md §Fabric): a supervisor spawns N
replica processes (each loads the checkpoint and warms its own AOT pool;
crashes restart with backoff and rejoin only once ``engine_ready``), and a
router does least-loaded health-aware dispatch with transparent failover —
``kill -9`` on a replica re-routes its in-flight requests instead of failing
them. ``--cached`` composes: sessions pin to the replica holding their
latents, and a dead pin surfaces as a re-encode. ``--rolling_swap_step``
rolls the fleet to another checkpoint step one replica at a time with
auto-rollback on post-swap SLO burn/breaker regression.

``--autoscale`` (fleet mode) closes the serving control loop
(``perceiver_io_tpu.serving.autoscale``, PERF.md §Autoscale): an
``Autoscaler`` grows/shrinks the supervised fleet between
``--min_replicas`` and ``--max_replicas`` from the windowed SLO-burn and
queue series the router's scrape loop maintains, seeded by the measured
``--autoscale_rps_per_replica`` capacity fit — hold-down + hysteresis so a
bursty minute never flaps the fleet, scale-down only via graceful
drain-then-retire (``lost_accepted`` stays 0), capped exponential backoff
on failed spawns. ``--priority_classes``/``--client_quota_rps`` add
admission control at the router's front door: weighted-fair dispatch
across service classes and per-client token buckets, so one bursting
client degrades its own SLO class while other classes' p99 stays flat.

``--watch_checkpoints DIR`` closes the train→serve loop
(``perceiver_io_tpu.deploy``, PERF.md §Deployment): the process polls DIR
(a trainer's ``publish_dir``) for atomically-published checkpoints, runs
each through the admission gate — manifest digest verification, all-finite
param scan, a golden-batch forward within ``--gate_quality_tol`` of the
incumbent — and hot-swaps only passing trees into live serving (rolling
one replica at a time under ``--replicas``, each replica re-verifying the
digest at load; re-quantized on the fly under ``--quantize int8``). A
failing publication is quarantined in place (sticky, never re-attempted);
a post-swap SLO-burn/breaker regression rolls back to the incumbent tree.

Graceful drain: SIGTERM/SIGINT stop admission, finish every accepted
request, flush the event log, and exit 0 (``--drain_timeout_s`` bounds the
wait) — in both single-process and fleet modes, so a supervisor rotation
never drops the queue. An in-progress gated swap completes (or rolls back)
before exit — never a half-swapped fleet.

``--metrics_port`` starts the localhost observability sidecar
(``/metrics`` Prometheus text, ``/healthz``, ``/statz`` JSON snapshot, now
including process self-metrics RSS/uptime/threads/GC at every scrape);
``--heartbeat_deadline_s`` arms the wedged-dispatch heartbeat;
``--selfprofile_every`` turns on the in-loop device-trace watchdog. All
telemetry output rides stderr/HTTP — stdout stays one JSON line per text.

``--series`` adds the historical half (``perceiver_io_tpu.obs.timeseries``,
PERF.md §Timeseries): every registry instrument sampled into a bounded
ring-buffer store each ``--series_interval_s``, served live as
``/seriesz`` and optionally persisted as rotating JSONL
(``--series_jsonl``). ``--alert_rules FILE`` evaluates declarative alert
rules (threshold / rate-of-change / absence over a window, with hold-down
and hysteresis) over those series: transitions land in the event log
(exemplar trace-linked), ``alert_state{rule=}`` rides ``/metrics``, and a
firing page-class alert degrades ``/healthz`` through the same aggregation
as stalls, breakers, and SLO burn.

Self-healing (``perceiver_io_tpu.resilience``, PERF.md §Reliability):
``--request_deadline_s`` sheds requests whose deadline expires before
dispatch, ``--queue_limit`` bounds the queue with fast-fail load shedding,
``--dispatch_retries`` re-dispatches transiently-failed micro-batches with
backoff, and ``--breaker_failures``/``--breaker_cooldown_s`` arm the circuit
breaker (consecutive failures or a heartbeat stall open it; submissions
fast-fail until a half-open probe succeeds; state rides /metrics + /healthz).
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
from typing import Optional, Sequence


class _DrainRequested(BaseException):
    """Raised (once) by the SIGTERM/SIGINT handler to unwind the admission
    loop. A BaseException so no library except-Exception swallows it."""


def _install_drain_handlers():
    """Graceful-drain signal handling: the FIRST SIGTERM/SIGINT raises
    :class:`_DrainRequested` in the main thread (stops admission — even out
    of a blocked stdin read, since a raising handler interrupts the retry
    loop PEP 475 would otherwise continue); later signals are ignored so the
    finish-in-flight phase cannot be aborted into dropping the queue.
    Returns ``(state, restore)`` — call ``restore()`` when done (serve.main
    also runs in-process under pytest; a leaked handler would break the
    host's Ctrl-C)."""
    state = {"draining": False}

    def handler(signum, frame):
        if state["draining"]:
            print(f"serve: signal {signum} during drain — still finishing "
                  "in-flight work", file=sys.stderr, flush=True)
            return
        state["draining"] = True
        raise _DrainRequested()

    previous = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            previous[sig] = signal.signal(sig, handler)
        except ValueError:  # not the main thread (programmatic use)
            pass

    def restore():
        for sig, h in previous.items():
            signal.signal(sig, h)

    return state, restore


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    g = parser.add_argument_group("serving")
    g.add_argument("--task", choices=("mlm", "generate"), default="mlm",
                   help="workload class: 'mlm' fills [MASK] positions; "
                        "'generate' streams Perceiver-AR continuations of "
                        "each input line (checkpoint from cli/train_ar.py; "
                        "single-process — fleet generation serves through "
                        "`python -m perceiver_io_tpu.serving.replica "
                        "--task generate` behind a Router)")
    gen = parser.add_argument_group("generation (--task generate)")
    gen.add_argument("--max_new_tokens", type=int, default=32,
                     help="continuation length per prompt")
    gen.add_argument("--temperature", type=float, default=0.0,
                     help="0 = greedy; otherwise categorical at this "
                          "temperature")
    gen.add_argument("--top_k", type=int, default=0,
                     help="truncate sampling to the k most likely tokens "
                          "(0 = full softmax)")
    gen.add_argument("--gen_seed", type=int, default=0,
                     help="sampling seed (position-folded: deterministic "
                          "per absolute position, reproducible across "
                          "re-encodes)")
    gen.add_argument("--generate_chunk", type=int, default=8,
                     help="decode steps per chunked dispatch (= streaming "
                          "granularity)")
    gen.add_argument("--decode_batching", action="store_true",
                     help="continuous batching: pool session caches into a "
                          "slotted arena, ONE batched step dispatch for all "
                          "active streams (identical token streams; pays "
                          "off at concurrency — prompts here run "
                          "sequentially, so this mostly exercises the path)")
    gen.add_argument("--decode_slots", type=int, default=8,
                     help="decode batching: initial arena slots per prefill "
                          "width (power-of-two-bucketed)")
    g.add_argument("--checkpoint", required=True,
                   help="checkpoint directory of a train_mlm run "
                        "(the version_N/checkpoints dir; hparams embedded)")
    g.add_argument("--tokenizer", required=True,
                   help="tokenizer json (the train run caches one under "
                        "--root, e.g. imdb-tokenizer-10003.json)")
    g.add_argument("--texts", nargs="*", default=None,
                   help="texts containing the [MASK] literal")
    g.add_argument("--stdin", action="store_true",
                   help="read one text per line from stdin instead")
    g.add_argument("--k", "--num_predictions", type=int, default=5,
                   help="top-k tokens per [MASK] position")
    g.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: best by val_loss)")
    g.add_argument("--max_batch", type=int, default=64,
                   help="micro-batch cap (power-of-two buckets below it)")
    g.add_argument("--max_delay_ms", type=float, default=0.0,
                   help="hold the first request of a batch this long for "
                        "stragglers (0 = pure continuous batching)")
    g.add_argument("--bucket_widths", type=int, nargs="+", default=None,
                   help="sequence-width serving buckets (the training "
                        "collator's rule): each request pads to the smallest "
                        "width holding it instead of max_seq_len")
    g.add_argument("--dtype", choices=("float32", "bfloat16"), default="float32",
                   help="serving compute dtype: float32 is the golden-parity "
                        "path; bfloat16 rebuilds the model at bf16 compute "
                        "and casts params once (the bf16 serving path)")
    g.add_argument("--quantize", choices=("none", "int8", "int4"),
                   default="none",
                   help="weight-only quantization: int8 stores the matmul "
                        "kernels as per-channel symmetric int8 (f32 scales), "
                        "int4 as grouped symmetric int4 (one scale per "
                        "--group_size rows of each column), dequantized "
                        "inside the compiled program — 0.5x/0.25x the weight "
                        "bytes streamed from HBM per micro-batch vs bf16 "
                        "(the measured serving bottleneck); on TPU the fused "
                        "dequant-matmul kernel streams the int tiles "
                        "directly. Params are quantized once at load; the "
                        "checkpoint stays f32 on disk. Composes with "
                        "--dtype: compute runs at --dtype, only weight "
                        "STORAGE is int8/int4")
    g.add_argument("--group_size", type=int, default=None,
                   help="rows per int4 scale group (default 128); int8 "
                        "stays per-channel unless set")
    g.add_argument("--cached", action="store_true",
                   help="serve via the latent-cache split (encode once, "
                        "decode the [MASK] queries) instead of the fused "
                        "forward")
    g.add_argument("--no_warmup", action="store_true",
                   help="skip ahead-of-time bucket compilation (first "
                        "requests then pay the compiles)")
    g.add_argument("--compile_cache", default=None, metavar="DIR",
                   help="directory of serialized bucket-program executables "
                        "(perceiver_io_tpu.aot): a warm restart deserializes "
                        "them, skipping trace, lower and compile. Entries "
                        "load always and are written only when jax's "
                        "persistent compile cache is off "
                        "(JAX_ENABLE_COMPILATION_CACHE=false); with it on, "
                        "that cache alone gives the zero-compile warm "
                        "start. Fail-soft: an unusable dir warns and serves "
                        "without it")
    g.add_argument("--blocking_warmup", action="store_true",
                   help="wait for the FULL bucket-program family before "
                        "serving (the pre-r10 behavior). Default: warmup "
                        "runs in the background, priority-ordered, and "
                        "serving starts immediately — a request is answered "
                        "as soon as its program is ready")
    g.add_argument("--stats", action="store_true",
                   help="print engine stats to stderr on exit")
    f = parser.add_argument_group(
        "multi-replica fabric (perceiver_io_tpu.serving; PERF.md §Fabric)")
    f.add_argument("--replicas", type=int, default=0, metavar="N",
                   help="serve through a router tier over N replica "
                        "PROCESSES (each loads the checkpoint, warms its "
                        "own AOT pool, and is babysat by a supervisor that "
                        "restarts crashes with backoff): least-loaded "
                        "health-aware dispatch, transparent failover when a "
                        "replica dies, latent-cache affinity under --cached. "
                        "0 (default) = the single-process engine")
    f.add_argument("--transport", choices=("http", "uds", "shmem"),
                   default="http",
                   help="with --replicas: the router→replica data plane for "
                        "array RPCs — 'http' (portable default), 'uds' "
                        "(pipelined unix-socket frames), 'shmem' (shared-"
                        "memory slot slab + uds control channel). Admin "
                        "verbs and streamed generate always ride HTTP")
    f.add_argument("--drain_timeout_s", type=float, default=60.0,
                   help="graceful-drain bound: on SIGTERM/SIGINT (and fleet "
                        "shutdown) stop admission and wait up to this long "
                        "for accepted work to finish before exiting 0")
    f.add_argument("--rolling_swap_step", type=int, default=None,
                   metavar="STEP",
                   help="with --replicas: after serving, roll the fleet to "
                        "this checkpoint step ONE REPLICA AT A TIME "
                        "(update_params hot-swap; warm pools carry over), "
                        "baking each swap against its SLO burn / breaker "
                        "and auto-rolling the whole fleet back on "
                        "regression; the report prints to stderr")
    f.add_argument("--rolling_bake_s", type=float, default=2.0,
                   help="post-swap observation window per replica")
    f.add_argument("--rolling_burn_threshold", type=float, default=2.0,
                   help="post-swap SLO burn rate above which the rollout "
                        "rolls back")
    a = parser.add_argument_group(
        "elastic autoscaling + admission control (fleet mode; "
        "perceiver_io_tpu.serving.autoscale / .admission)")
    a.add_argument("--autoscale", action="store_true",
                   help="with --replicas: run the serving control loop — "
                        "an Autoscaler spawns/retires supervised replica "
                        "processes from the windowed fleet SLO-burn and "
                        "queue series (hold-down + hysteresis, scale-down "
                        "only via graceful drain-then-retire, capped "
                        "exponential backoff on failed spawns). Requires "
                        "--autoscale_rps_per_replica — seed it from a "
                        "measured tools/load_bench.py capacity fit, never "
                        "a guess")
    a.add_argument("--autoscale_rps_per_replica", type=float, default=None,
                   metavar="RPS",
                   help="measured requests/s one replica sustains at the "
                        "SLO (fit_capacity's slo_sustainable_rps over the "
                        "sweep's replica count)")
    a.add_argument("--min_replicas", type=int, default=1,
                   help="autoscale floor")
    a.add_argument("--max_replicas", type=int, default=None,
                   help="autoscale ceiling (default: 2x --replicas)")
    a.add_argument("--autoscale_interval_s", type=float, default=1.0,
                   help="control-loop tick cadence")
    a.add_argument("--priority_classes", default=None, metavar="SPEC",
                   help="admission control: comma-separated "
                        "'name:weight' service classes (e.g. "
                        "'gold:8,silver:4,bronze:1' — first is the "
                        "default class). Admitted requests dispatch in "
                        "weighted-fair order; each class owns a weight-"
                        "proportional share of --admission_queue_limit, "
                        "so one bursting class sheds in ITS share while "
                        "other classes' tail stays flat")
    a.add_argument("--client_quota_rps", type=float, default=None,
                   help="per-client token-bucket rate (each distinct "
                        "client id draws from its own bucket; over-quota "
                        "requests shed with a reasoned RejectedError that "
                        "burns the CLIENT'S class SLO only)")
    a.add_argument("--client_quota_burst", type=float, default=None,
                   help="token-bucket burst ceiling (default: 2x the "
                        "rate)")
    a.add_argument("--admission_queue_limit", type=int, default=256,
                   help="total WFQ queue slots split weight-"
                        "proportionally across the priority classes")
    a.add_argument("--request_client", default=None, metavar="ID",
                   help="client id THIS process's requests present at the "
                        "admission gate (they draw that client's token "
                        "bucket; omitted = quota-exempt operator traffic). "
                        "Several serve processes with different ids "
                        "compose into a multi-tenant front")
    a.add_argument("--request_priority", default=None, metavar="CLASS",
                   help="priority class this process's requests ride in "
                        "(default: the admission controller's default "
                        "class)")
    d = parser.add_argument_group(
        "continuous deployment (perceiver_io_tpu.deploy; PERF.md "
        "§Deployment)")
    d.add_argument("--watch_checkpoints", default=None, metavar="DIR",
                   help="watch this publish directory (TrainerConfig."
                        "publish_dir) for new checkpoint publications and "
                        "hot-swap each one into live serving AFTER it "
                        "passes the admission gate (digest verification, "
                        "all-finite scan, golden-batch forward within "
                        "--gate_quality_tol of the incumbent). A failing "
                        "publication is quarantined in place and never "
                        "re-attempted; a post-swap SLO-burn/breaker "
                        "regression rolls back to the incumbent tree. "
                        "Works in both single-process and --replicas mode "
                        "(fleet swaps roll one replica at a time)")
    d.add_argument("--gate_quality_tol", type=float, default=0.5,
                   help="admission-gate quality bound: maximum relative "
                        "deviation of the candidate's golden-batch outputs "
                        "from the incumbent's (an online-refresh checkpoint "
                        "continues the same run — garbage trees deviate by "
                        "orders of magnitude)")
    d.add_argument("--publish_poll_s", type=float, default=2.0,
                   help="seconds between publish-directory polls")
    r = parser.add_argument_group(
        "resilience (PERF.md §Reliability: retry/shed/breaker semantics)")
    r.add_argument("--request_deadline_s", type=float, default=None,
                   help="per-request deadline: a request still waiting for "
                        "dispatch past this is SHED with DeadlineExceeded "
                        "(at admission and batch assembly) instead of "
                        "occupying the queue as dead work. Default: none")
    r.add_argument("--queue_limit", type=int, default=None,
                   help="bounded queue: admission fast-fails with "
                        "RejectedError once this many micro-batch parts are "
                        "backlogged (explicit load shedding instead of "
                        "unbounded growth). Default: unbounded")
    r.add_argument("--dispatch_retries", type=int, default=2,
                   help="transient dispatch/completion failures re-dispatch "
                        "the micro-batch with exponential backoff up to this "
                        "many times before failing its requests (the error "
                        "classification never retries fatal errors). 0 disables")
    r.add_argument("--breaker_failures", type=int, default=0,
                   help="circuit breaker: open after this many CONSECUTIVE "
                        "dispatch failures (or a heartbeat stall) and "
                        "fast-fail submissions until a cooldown probe "
                        "succeeds; state exported to /metrics and /healthz. "
                        "0 disables (default)")
    r.add_argument("--breaker_cooldown_s", type=float, default=5.0,
                   help="seconds an open breaker fast-fails before admitting "
                        "a half-open probe")
    o = parser.add_argument_group("observability")
    o.add_argument("--metrics_port", type=int, default=None,
                   help="start the localhost observability sidecar on this "
                        "port (/metrics Prometheus text, /healthz, /statz "
                        "JSON); 0 picks an ephemeral port — the bound port "
                        "is printed to stderr. Default: off")
    o.add_argument("--heartbeat_deadline_s", type=float, default=None,
                   help="dispatch heartbeat deadline: if no dispatch "
                        "completes within this many seconds while work is in "
                        "flight (a wedged dispatch), /healthz flips unhealthy and "
                        "a thread-stack diagnostic is dumped to stderr. "
                        "Default: off")
    o.add_argument("--selfprofile_every", type=int, default=0,
                   help="in-loop device-trace watchdog: every N micro-batches "
                        "capture a short jax.profiler trace, analyze it "
                        "in-process, and publish device-clock step time "
                        "gauges. Default: off")
    o.add_argument("--events_jsonl", default=None,
                   help="append runtime events (compiles, warmups, stalls, "
                        "per-request phase spans) as JSON lines to this file "
                        "(size-capped rotation: see --events_max_mb)")
    o.add_argument("--events_max_mb", type=float, default=64.0,
                   help="rotate the events file past this size, keeping 3 "
                        "numbered segments (a week of serving cannot grow "
                        "it unboundedly); 0 disables rotation")
    o.add_argument("--span_every", type=int, default=1,
                   help="emit a request_phases span for every Nth completed "
                        "request part (each span is a synchronous JSONL "
                        "write — sample at high request rates; the "
                        "serving_phase_seconds histograms keep the "
                        "full-rate view regardless)")
    o.add_argument("--trace_sample", type=float, default=1.0,
                   help="distributed request tracing head-sampling rate: "
                        "the fraction of requests that mint a TraceContext "
                        "(router/engine submit) and record spans at every "
                        "hop into --events_jsonl. In --replicas mode each "
                        "replica process writes its own "
                        "<events_jsonl>.<replica> log; assemble "
                        "per-request trace trees with "
                        "tools/trace_assemble.py. 0 disables; tail-based "
                        "retention happens at assembly")
    o.add_argument("--series", action="store_true",
                   help="sample every registry instrument into a bounded "
                        "in-memory time-series store at --series_interval_s "
                        "(counters as cumulative values, gauges as values, "
                        "histograms as windowed p50/p95/p99+count) and "
                        "serve it live as /seriesz on the --metrics_port "
                        "sidecar (?window_s=60 bounds the returned points). "
                        "Implied by --series_jsonl / --alert_rules")
    o.add_argument("--series_interval_s", type=float, default=1.0,
                   help="sampling cadence (PERF.md §Timeseries: overhead "
                        "at the 1 s default is below the CPU noise floor)")
    o.add_argument("--series_jsonl", default=None, metavar="PATH",
                   help="persist one series_sample JSON line per sweep "
                        "here (size-capped rotation like --events_jsonl) — "
                        "the on-disk history next to the event log")
    o.add_argument("--alert_rules", default=None, metavar="FILE",
                   help="JSON alert rules (a list of AlertRule objects: "
                        "name/metric/kind=threshold|rate|absence/op/"
                        "threshold/window_s/for_s/resolve_threshold/"
                        "severity) evaluated over the sampled series every "
                        "--series_interval_s: transitions emit alert_firing/"
                        "alert_resolved events into --events_jsonl, "
                        "alert_state{rule=} rides /metrics, and a firing "
                        "page-severity rule degrades /healthz")
    o.add_argument("--slo_p99_ms", type=float, default=None,
                   help="serving SLO latency target: a request answered "
                        "within this many ms counts good, sheds/errors and "
                        "slower answers burn the error budget. Enables the "
                        "slo_* burn-rate gauges on /metrics and /statz and "
                        "wires the burn alert into /healthz "
                        "(obs/slo.py; sweep with tools/load_bench.py)")
    o.add_argument("--slo_availability", type=float, default=0.999,
                   help="fraction of requests that must meet the SLO "
                        "(error budget = 1 - this)")
    o.add_argument("--slo_ttft_ms", type=float, default=None,
                   help="generate task, --replicas mode: per-stream time-"
                        "to-first-token target forwarded to every replica "
                        "— streams over it burn the stream SLO "
                        "(stream_burn on /statz; the router degrades and "
                        "the autoscaler scales on it)")
    o.add_argument("--slo_itl_ms", type=float, default=None,
                   help="generate task, --replicas mode: per-stream mean "
                        "inter-token-latency target (same wire as "
                        "--slo_ttft_ms)")
    o.add_argument("--slo_burn_alert", type=float, default=2.0,
                   help="/healthz degrades when the windowed error-budget "
                        "burn rate exceeds this (1.0 = spending the budget "
                        "exactly as it accrues); 0 disables the health wire")
    parser.add_argument("--cpu", action="store_true",
                        help="pin to the CPU backend (ensure_cpu_only before "
                             "jax initializes) — the offline/tier-1 mode")
    return parser


def main(argv: Optional[Sequence[str]] = None):
    args = build_parser().parse_args(argv)
    if not args.texts and not args.stdin:  # catches omitted AND empty --texts
        raise SystemExit("nothing to serve: pass --texts ... or --stdin")
    if args.autoscale:
        if args.replicas <= 0:
            raise SystemExit("--autoscale needs --replicas N (the control "
                             "loop lives at the router tier)")
        if not args.autoscale_rps_per_replica:
            raise SystemExit(
                "--autoscale needs --autoscale_rps_per_replica — seed it "
                "from a measured tools/load_bench.py capacity fit "
                "(slo_sustainable_rps / replicas), never a guess")
    if args.replicas > 0 and not args.cpu:
        raise SystemExit(
            "--replicas N needs --cpu: replica processes are not pinned to "
            "chips yet (ROADMAP.md), so on a TPU host every child would "
            "claim every chip and all but one would fail or hang")
    if (args.priority_classes or args.client_quota_rps) \
            and args.replicas <= 0:
        raise SystemExit("--priority_classes/--client_quota_rps need "
                         "--replicas N (admission lives at the router)")

    # drain handlers go in FIRST: a SIGTERM during the checkpoint load /
    # warmup must already mean "graceful exit 0", not the default kill
    drain_state, restore_handlers = _install_drain_handlers()

    if args.cpu:
        from perceiver_io_tpu.utils.platform import ensure_cpu_only

        ensure_cpu_only()
    if args.replicas <= 0:
        # a fleet parent never compiles (and stays off jax: its replicas
        # need the devices); each replica places its own cache
        from perceiver_io_tpu.aot import configure_compile_cache

        configure_compile_cache()

    import perceiver_io_tpu.obs as obs
    from perceiver_io_tpu.data.tokenizer import load_tokenizer
    from perceiver_io_tpu.inference import MLMServer, load_mlm_checkpoint

    if args.events_jsonl:
        obs.configure_event_log(
            args.events_jsonl,
            max_bytes=(int(args.events_max_mb * 1024 * 1024)
                       if args.events_max_mb > 0 else None),
        )
    obs_server = None
    sampler = None
    alert_engine = None
    if args.metrics_port is not None:
        # started BEFORE the checkpoint load / warmup so probes can watch a
        # slow bring-up; counters stay zero until requests arrive. stdout is
        # the result stream — the sidecar address goes to stderr. Process
        # self-metrics (RSS/uptime/threads/GC) refresh at every scrape so
        # saturation correlates with host pressure.
        obs.install_process_metrics()
        obs_server = obs.ObsServer(port=args.metrics_port)
        url = obs_server.start()
        if url is not None:
            print(f"serve: metrics on {url}/metrics (also /healthz /statz"
                  + ("/seriesz" if (args.series or args.series_jsonl
                                    or args.alert_rules) else "") + ")",
                  file=sys.stderr, flush=True)

    if args.series or args.series_jsonl or args.alert_rules:
        # the historical half: a bounded store sampled on a cadence,
        # installed as the process default so /seriesz serves it live;
        # optional JSONL persistence rides the same rotation contract as
        # the event log. Alert rules evaluate over the same store.
        store = obs.SeriesStore()
        obs.install_series_store(store)
        sampler = obs.Sampler(
            store=store, interval_s=args.series_interval_s,
            jsonl_path=args.series_jsonl, name="serve").start()
        print(f"serve: sampling series every {args.series_interval_s:g}s"
              + (f" -> {args.series_jsonl}" if args.series_jsonl else ""),
              file=sys.stderr, flush=True)
        if args.alert_rules:
            rules = obs.load_alert_rules(args.alert_rules)
            alert_engine = obs.AlertEngine(
                store, rules, interval_s=args.series_interval_s,
                name="serve").start()
            print(f"serve: {len(rules)} alert rule(s) active "
                  f"({', '.join(r.name for r in rules)}) — firing "
                  "page-class alerts degrade /healthz", file=sys.stderr,
                  flush=True)

    try:
        if args.task == "generate":
            if args.replicas > 0:
                raise SystemExit(
                    "--task generate serves single-process here; a "
                    "generation FLEET runs `python -m "
                    "perceiver_io_tpu.serving.replica --task generate` "
                    "replicas behind a serving.Router")
            return _serve_generate(args, load_tokenizer, drain_state)
        if args.replicas > 0:
            return _serve_fleet(args, drain_state)
        return _serve(args, MLMServer, load_tokenizer, load_mlm_checkpoint,
                      drain_state)
    except _DrainRequested:
        # the signal landed during startup (load/warmup), before any request
        # was admitted: nothing is in flight, exit 0 with nothing served
        print("serve: drain requested during startup — exiting with no "
              "requests admitted", file=sys.stderr, flush=True)
        return []
    finally:
        # an exception mid-serve must not leak the sidecar thread, the
        # drain signal handlers, or leave the process-global event log
        # bound to this run's file (serve.main is also called in-process by
        # tests/other tools). configure_event_log(None) FLUSHES and closes
        # the JSONL stream — the drain contract's "flush the event log".
        restore_handlers()
        if alert_engine is not None:
            # one last evaluation so an episode that ended during drain
            # still resolves into the event log before it closes
            try:
                alert_engine.evaluate()
            except Exception:
                pass
            print(f"serve: alerts {json.dumps(alert_engine.stats())}",
                  file=sys.stderr, flush=True)
            alert_engine.close()
        if sampler is not None:
            sampler.close()  # drains --series_jsonl to disk
            obs.install_series_store(None)
        if obs_server is not None:
            obs_server.close()
        if args.events_jsonl:
            obs.configure_event_log(None)


def _start_deployer(args, model, params, max_seq_len, target):
    """The serving half of the train→serve loop (``--watch_checkpoints``):
    poll the publish dir, admission-gate every publication (digest /
    finite / golden-forward-vs-incumbent quality), and hot-swap passing
    trees into ``target``. The gate is handed over as a FACTORY, so its
    golden-program compile happens lazily on the deployer thread — serve
    startup stays non-blocking (the r10 background-warmup property) even
    when no publication ever arrives. Publications at or below the booted
    checkpoint's step are ignored (a restart must not replay — or
    quarantine — the historical backlog). Runs on a daemon thread; the
    caller's drain path stops it via :func:`_stop_deployer`."""
    import numpy as np

    from perceiver_io_tpu.deploy import AdmissionGate, ModelDeployer
    from perceiver_io_tpu.inference.engine import mlm_apply_fns
    from perceiver_io_tpu.training.checkpoint import resolve_checkpoint_step

    golden = (np.zeros((1, max_seq_len), np.int32),
              np.zeros((1, max_seq_len), bool),
              np.zeros((1, 2), np.int32))

    def make_gate():
        return AdmissionGate(
            mlm_apply_fns(model)["infer"], golden, params,
            quality_tol=args.gate_quality_tol, name="serve",
        )

    try:
        min_step = resolve_checkpoint_step(args.checkpoint, args.step)
    except Exception:  # unranked/odd checkpoint dir: accept every step
        min_step = -1
    deployer = ModelDeployer(
        args.watch_checkpoints, make_gate, target,
        poll_s=args.publish_poll_s, name="serve", min_step=min_step,
    ).start()
    print(f"serve: watching {args.watch_checkpoints} for checkpoint "
          f"publications newer than step {min_step} (poll "
          f"{args.publish_poll_s:g}s, quality tol "
          f"{args.gate_quality_tol:g})", file=sys.stderr, flush=True)
    return deployer


def _stop_deployer(deployer, timeout_s: float) -> None:
    if deployer is None:
        return
    if not deployer.stop(timeout_s):
        print("serve: WARNING — deployment loop did not stop within "
              f"{timeout_s:g}s (a swap may still be in flight)",
              file=sys.stderr, flush=True)
    else:
        print(f"serve: deployment loop stopped "
              f"({json.dumps(deployer.stats())})", file=sys.stderr,
              flush=True)


def _serve(args, MLMServer, load_tokenizer, load_mlm_checkpoint,
           drain_state=None):
    tokenizer = load_tokenizer(args.tokenizer)
    model, params, max_seq_len = load_mlm_checkpoint(
        args.checkpoint, tokenizer, step=args.step,
        dtype="bfloat16" if args.dtype == "bfloat16" else None,
    )

    import perceiver_io_tpu.obs as obs

    slo = None
    if args.slo_p99_ms is not None:
        slo = obs.SLO(
            latency_target_s=args.slo_p99_ms / 1e3,
            availability_target=args.slo_availability,
            burn_alert=args.slo_burn_alert if args.slo_burn_alert > 0 else None,
        )

    results = []
    with MLMServer(
        model, params, tokenizer, max_seq_len,
        bucket_widths=args.bucket_widths,
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        compute_dtype="bfloat16" if args.dtype == "bfloat16" else None,
        quantize=None if args.quantize == "none" else args.quantize,
        group_size=args.group_size,
        heartbeat_deadline_s=args.heartbeat_deadline_s,
        selfprofile_every=args.selfprofile_every,
        request_deadline_s=args.request_deadline_s,
        queue_limit=args.queue_limit,
        dispatch_retries=args.dispatch_retries,
        breaker_failures=args.breaker_failures,
        breaker_cooldown_s=args.breaker_cooldown_s,
        compile_cache=args.compile_cache,
        slo=slo,
        span_every=args.span_every,
        trace_sample=args.trace_sample,
    ) as server:
        warmup_handle = None
        if not args.no_warmup:
            if args.blocking_warmup:
                n = server.warmup()
                print(f"serve: warmed {n} bucket programs", file=sys.stderr)
            else:
                warmup_handle = server.warmup(background=True)
                print("serve: warming bucket programs in the background; "
                      "serving immediately (--blocking_warmup restores the "
                      "wait)", file=sys.stderr)

        deployer = None
        if args.watch_checkpoints:
            from perceiver_io_tpu.deploy import EngineSwapTarget

            deployer = _start_deployer(
                args, model, params, max_seq_len,
                EngineSwapTarget(server, params,
                                 bake_s=args.rolling_bake_s,
                                 burn_threshold=args.rolling_burn_threshold),
            )

        def emit(text: str, fills) -> None:
            line = {"text": text, "fills": fills}
            results.append(line)
            print(json.dumps(line))

        # pending futures in emission order — tracked OUTSIDE the admission
        # loops so a drain signal that unwinds them still finds (and
        # finishes) every accepted request
        pending = []
        try:
            try:
                if args.texts:
                    if args.cached:
                        cached = server.encode(args.texts)
                        for text, f in zip(args.texts, server.fill_masks_cached(
                                cached, k=args.k)):
                            emit(text, f)
                    else:
                        for text in args.texts:
                            pending.append((text, server.submit(text, k=args.k)))
                if args.stdin:
                    if args.cached:
                        # cached mode batches the whole pipe: one encode sweep,
                        # one decode sweep — per-line sync round-trips would
                        # serialize into exactly the naive dispatch the engine
                        # exists to beat
                        lines = [l.rstrip("\n") for l in sys.stdin]
                        lines = [l for l in lines if l]
                        cached = server.encode(lines)
                        for text, f in zip(lines, server.fill_masks_cached(
                                cached, k=args.k)):
                            emit(text, f)
                    else:
                        # a line-per-request stream: submit as lines arrive,
                        # resolve in order — arrivals batch up behind the
                        # in-flight dispatch. The marker line tells a supervisor
                        # (and the drain test) admission is live.
                        print("serve: admitting stdin", file=sys.stderr,
                              flush=True)
                        for line in sys.stdin:
                            text = line.rstrip("\n")
                            if text:
                                pending.append(
                                    (text, server.submit(text, k=args.k)))
            except _DrainRequested:
                # graceful drain: admission stopped (the raise unwound the
                # loops); everything already accepted below still finishes and
                # the process exits 0 — a supervisor rotation never drops the
                # queue. Later signals are absorbed by the handler.
                print("serve: drain requested (signal) — admission stopped, "
                      f"finishing {len(pending)} in-flight request(s)",
                      file=sys.stderr, flush=True)
            # admission is over either way: mark draining so a FIRST signal
            # landing during the resolve loop below is absorbed by the handler
            # (printed, not raised) — finish-in-flight can never be unwound
            # into dropping accepted results
            signaled = drain_state is not None and drain_state.get("draining")
            if drain_state is not None:
                drain_state["draining"] = True
            for text, fut in pending:
                emit(text, fut.result())
            if signaled:
                server.drain(args.drain_timeout_s)
        finally:
            # the drain contract extends to the deployment loop: an
            # in-progress gated swap COMPLETES (or rolls back) before exit —
            # never a half-swapped server
            _stop_deployer(deployer, args.drain_timeout_s)
        if warmup_handle is not None and warmup_handle.done():
            try:
                n = warmup_handle.wait(0)
                print(f"serve: warmed {n} bucket programs (background)",
                      file=sys.stderr)
            except Exception as e:  # warmup failed; requests self-compiled
                print(f"serve: background warmup failed "
                      f"({type(e).__name__}: {e}) — programs were built "
                      "on demand", file=sys.stderr)
        if args.stats:
            print(f"serve: stats {json.dumps(server.stats())}", file=sys.stderr)
    return results


def _serve_generate(args, load_tokenizer, drain_state=None):
    """``--task generate``: stream Perceiver-AR continuations of each input
    line. One JSON result line per prompt on stdout ({"text",
    "continuation_ids", "continuation"}); chunk-by-chunk progress rides
    stderr. A drain signal stops admission; the tokens already streamed for
    an interrupted prompt still emit (accepted work is never dropped)."""
    from perceiver_io_tpu.inference.generate import (
        ARGenerator,
        SamplingConfig,
        load_ar_checkpoint,
    )

    tokenizer = load_tokenizer(args.tokenizer)
    model, params, max_seq_len = load_ar_checkpoint(
        args.checkpoint, tokenizer, step=args.step,
        dtype="bfloat16" if args.dtype == "bfloat16" else None,
    )
    if args.decode_batching:
        from perceiver_io_tpu.inference.batching import ContinuousBatcher

        gen = ContinuousBatcher(
            model, params, max_seq_len=max_seq_len,
            chunk=args.generate_chunk, slots=args.decode_slots,
            compute_dtype="bfloat16" if args.dtype == "bfloat16" else None,
            compile_cache=args.compile_cache,
            heartbeat_deadline_s=args.heartbeat_deadline_s,
        )
    else:
        gen = ARGenerator(
            model, params, max_seq_len=max_seq_len,
            chunk=args.generate_chunk,
            compute_dtype="bfloat16" if args.dtype == "bfloat16" else None,
        )
    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=args.top_k, seed=args.gen_seed)
    if not args.no_warmup:
        # warm the CONFIGURED sampling shape: greedy and top-k are distinct
        # compiled decode programs, and an unwarmed shape is a mid-stream
        # compile stall on the first prompt
        n = gen.warmup(sampling=sampling)
        print(f"serve: warmed {n} generation programs", file=sys.stderr)
    results = []

    def emit(text: str, tokens) -> None:
        line = {
            "text": text,
            "continuation_ids": list(tokens),
            "continuation": " ".join(
                tokenizer.id_to_token(int(t)) for t in tokens),
        }
        results.append(line)
        print(json.dumps(line))

    def run_one(text: str) -> None:
        prefix = tokenizer.encode_ids(text)
        if not prefix:
            emit(text, [])
            return
        streamed = []

        def on_chunk(tokens, info):
            streamed.extend(tokens)
            print(f"serve: +{len(tokens)} tokens @pos {info['pos']} "
                  f"({info['chunk_ms']:.1f} ms)", file=sys.stderr,
                  flush=True)

        try:
            tokens, _ = gen.generate(prefix, args.max_new_tokens, sampling,
                                     on_chunk=on_chunk)
        except _DrainRequested:
            emit(text, streamed)  # what was accepted still emits
            raise
        emit(text, tokens)

    try:
        if args.texts:
            for text in args.texts:
                run_one(text)
        else:
            for line in sys.stdin:
                line = line.strip()
                if line:
                    run_one(line)
    except _DrainRequested:
        print("serve: drain requested — admission stopped", file=sys.stderr,
              flush=True)
    if args.stats:
        print(json.dumps({"prompts": len(results)}), file=sys.stderr)
    return results


def _serve_fleet(args, drain_state):
    """``--replicas N``: the router-tier serving path. N replica processes
    each load the checkpoint and warm their own pools; the router does the
    tokenize/top-k host work and least-loaded dispatch; ``--cached`` runs
    encode-once/decode-many with session affinity (the latents stay on the
    replica that encoded them)."""
    import numpy as np

    from perceiver_io_tpu.data.tokenizer import (
        MASK_TOKEN,
        PAD_TOKEN,
        load_tokenizer,
    )
    from perceiver_io_tpu.inference.mlm import (
        masked_token_ids,
        pad_token_rows,
    )
    from perceiver_io_tpu.inference.predictor import bucket_size
    from perceiver_io_tpu.resilience import AffinityLost
    from perceiver_io_tpu.serving import ReplicaSupervisor, Router
    from perceiver_io_tpu.training.checkpoint import load_hparams

    tokenizer = load_tokenizer(args.tokenizer)
    max_seq_len = load_hparams(args.checkpoint)["max_seq_len"]
    mask_id = tokenizer.token_to_id(MASK_TOKEN)
    pad_id = tokenizer.token_to_id(PAD_TOKEN)

    extra = ["--checkpoint", args.checkpoint, "--tokenizer", args.tokenizer,
             "--max_batch", str(args.max_batch), "--dtype", args.dtype,
             "--max_delay_ms", str(args.max_delay_ms),
             "--drain_timeout_s", str(args.drain_timeout_s)]
    if args.bucket_widths is not None:
        # width bucketing is an MLMServer concern; replicas serve the
        # full-width rows the router prepares
        print("serve: --bucket_widths has no effect with --replicas "
              "(fleet requests are prepared at max_seq_len width)",
              file=sys.stderr, flush=True)
    if args.cpu:
        extra.append("--cpu")
    if args.step is not None:
        extra += ["--step", str(args.step)]
    if args.quantize != "none":
        extra += ["--quantize", args.quantize]
    if args.group_size is not None:
        extra += ["--group_size", str(args.group_size)]
    if args.compile_cache:
        extra += ["--compile_cache", args.compile_cache]
    if args.no_warmup:
        extra.append("--no_warmup")
    if args.queue_limit is not None:
        extra += ["--queue_limit", str(args.queue_limit)]
    if args.request_deadline_s is not None:
        extra += ["--request_deadline_s", str(args.request_deadline_s)]
    extra += ["--dispatch_retries", str(args.dispatch_retries)]
    if args.breaker_failures:
        extra += ["--breaker_failures", str(args.breaker_failures),
                  "--breaker_cooldown_s", str(args.breaker_cooldown_s)]
    if args.heartbeat_deadline_s is not None:
        extra += ["--heartbeat_deadline_s", str(args.heartbeat_deadline_s)]
    if args.slo_p99_ms is not None:
        extra += ["--slo_p99_ms", str(args.slo_p99_ms),
                  "--slo_availability", str(args.slo_availability)]
    if args.slo_ttft_ms is not None:
        extra += ["--slo_ttft_ms", str(args.slo_ttft_ms)]
    if args.slo_itl_ms is not None:
        extra += ["--slo_itl_ms", str(args.slo_itl_ms)]

    def prepare(text):
        row = masked_token_ids(tokenizer, text)[:max_seq_len]
        ids, pad = pad_token_rows([row], max_seq_len, pad_id)
        mask_pos = np.nonzero(ids[0] == mask_id)[0]
        kb = bucket_size(max(len(mask_pos), 1), max_seq_len)
        positions = np.zeros((1, kb), np.int32)
        positions[0, : len(mask_pos)] = mask_pos
        return ids, pad, mask_pos, positions

    def topk(logits, n_masks):
        out = []
        for slot in range(n_masks):
            top = np.argsort(-np.asarray(logits[0, slot], np.float32))[:args.k]
            out.append([tokenizer.id_to_token(int(t)) for t in top])
        return out

    results = []

    def emit(text, fills):
        line = {"text": text, "fills": fills}
        results.append(line)
        print(json.dumps(line))

    sup_kw = {}
    if args.events_jsonl:
        # every fleet process owns its own JSONL (concurrent writers on one
        # file would tear lines): the router writes args.events_jsonl, each
        # replica <events_jsonl>.<name> — trace_assemble merges them into
        # per-request trace trees with cross-process clock alignment. The
        # rotation bound rides along; --trace_sample deliberately does NOT
        # (the ROUTER owns the head-sampling decision — replicas default
        # to never self-minting, so an unsampled request stays unsampled
        # at every hop instead of double-sampling)
        from perceiver_io_tpu.serving.supervisor import default_replica_argv

        def _replica_argv(name, port):
            return default_replica_argv(
                name, port,
                extra=[*extra, "--events_jsonl",
                       f"{args.events_jsonl}.{name}",
                       "--events_max_mb", str(args.events_max_mb)],
                transport=args.transport)

        sup_kw["argv_builder"] = _replica_argv
    admission = None
    if args.priority_classes or args.client_quota_rps:
        from perceiver_io_tpu.serving import (
            AdmissionController,
            parse_priority_classes,
        )

        quota = None
        if args.client_quota_rps:
            # TokenBucket requires burst >= 1: the 2x-rate default would
            # crash a sub-0.5 req/s quota at startup
            quota = (args.client_quota_rps,
                     args.client_quota_burst
                     or max(1.0, 2 * args.client_quota_rps))
        classes = (parse_priority_classes(args.priority_classes)
                   if args.priority_classes else None)
        slo = None
        if args.slo_p99_ms is not None:
            import perceiver_io_tpu.obs as obs

            slo = obs.SLO(latency_target_s=args.slo_p99_ms / 1e3,
                          availability_target=args.slo_availability,
                          name="serve", burn_alert=None)
        admission = AdmissionController(
            classes=classes, quota=quota, slo=slo,
            queue_limit=args.admission_queue_limit, name="serve")
        print("serve: admission control — classes "
              f"{sorted(admission.classes)} (default "
              f"{admission.default_class!r})"
              + (f", per-client quota {quota[0]:g} req/s burst {quota[1]:g}"
                 if quota else ""), file=sys.stderr, flush=True)
    with ReplicaSupervisor(count=args.replicas, extra_args=extra,
                           cpu=args.cpu, transport=args.transport,
                           **sup_kw) as sup:
        clients = sup.start()
        print(f"serve: spawned {args.replicas} replicas; waiting for warm "
              "pools (engine_ready)", file=sys.stderr, flush=True)
        sup.wait_ready(timeout_s=600.0)
        with Router(clients, name="serve",
                    queue_limit=args.queue_limit,
                    trace_sample=args.trace_sample,
                    admission=admission) as router:
            router.refresh()
            autoscaler = None
            if args.autoscale:
                from perceiver_io_tpu.serving import (
                    Autoscaler,
                    AutoscalePolicy,
                    SupervisorPool,
                )

                policy = AutoscalePolicy(
                    rps_per_replica=args.autoscale_rps_per_replica,
                    min_replicas=args.min_replicas,
                    max_replicas=args.max_replicas or 2 * args.replicas,
                    drain_timeout_s=args.drain_timeout_s,
                )
                autoscaler = Autoscaler(
                    router,
                    SupervisorPool(sup,
                                   drain_timeout_s=args.drain_timeout_s),
                    policy,
                    interval_s=args.autoscale_interval_s).start()
                print(f"serve: autoscaling fleet [{policy.min_replicas}, "
                      f"{policy.max_replicas}] at "
                      f"{policy.rps_per_replica:g} req/s/replica "
                      f"(tick {args.autoscale_interval_s:g}s)",
                      file=sys.stderr, flush=True)
            deployer = None
            if args.watch_checkpoints:
                from perceiver_io_tpu.deploy import RouterSwapTarget
                from perceiver_io_tpu.inference import load_mlm_checkpoint

                # the gate needs a reference forward + incumbent tree in THIS
                # process (no replica may see a candidate before it passes);
                # passing trees then roll replica-by-replica as publication
                # specs each replica loads digest-verified
                model, params, _ = load_mlm_checkpoint(
                    args.checkpoint, tokenizer, step=args.step)
                deployer = _start_deployer(
                    args, model, params, max_seq_len,
                    RouterSwapTarget(
                        router, bake_s=args.rolling_bake_s,
                        burn_threshold=args.rolling_burn_threshold),
                )
            pending = []  # (text, future-or-None, n_masks)
            # the admission identity this process's requests present at
            # the gate (quota bucket + service class)
            adm_kw = {"client": args.request_client,
                      "priority": args.request_priority}

            def submit(text):
                ids, pad, mask_pos, positions = prepare(text)
                if len(mask_pos) == 0:
                    pending.append((text, None, 0))
                    return
                if args.cached:
                    # encode-once: the encode is ASYNC so successive lines
                    # overlap and micro-batch on the replicas (a per-line
                    # sync round-trip would serialize admission into naive
                    # dispatch). The decode is submitted at RESOLVE time,
                    # after its encode established the pin — submitting it
                    # now would race the pin and land on a replica without
                    # the latents.
                    session = f"t{len(pending)}"
                    enc = router.submit(ids, pad, kind="encode",
                                        session=session, **adm_kw)
                    fut = (session, ids, pad, positions, enc)
                else:
                    fut = router.submit(ids, pad, positions, **adm_kw)
                pending.append((text, fut, len(mask_pos)))

            def resolve(fut, n_masks):
                if not isinstance(fut, tuple):
                    return topk(fut.result(timeout=600), n_masks)
                session, ids, pad, positions, enc = fut
                enc.result(timeout=600)  # pin established
                try:
                    logits = router.decode(positions, session=session,
                                           timeout=600, **adm_kw)
                except AffinityLost:
                    # the pinned replica (and its latents) died:
                    # re-encode on a live replica — which re-pins —
                    # and decode there (spill-on-death)
                    router.encode(ids, pad, session=session, timeout=600,
                                  **adm_kw)
                    logits = router.decode(positions, session=session,
                                           timeout=600, **adm_kw)
                return topk(logits, n_masks)

            try:
                try:
                    for text in (args.texts or []):
                        submit(text)
                    if args.stdin:
                        print("serve: admitting stdin", file=sys.stderr,
                              flush=True)
                        for line in sys.stdin:
                            text = line.rstrip("\n")
                            if text:
                                submit(text)
                except _DrainRequested:
                    print("serve: drain requested (signal) — admission "
                          f"stopped, finishing {len(pending)} in-flight "
                          "request(s)", file=sys.stderr, flush=True)
                # admission is over either way: mark draining so a FIRST
                # signal landing during the resolve loop is absorbed by the
                # handler (printed, not raised) — finish-in-flight can never
                # be unwound into dropping accepted results
                signaled = drain_state.get("draining")
                drain_state["draining"] = True
                for text, fut, n_masks in pending:
                    emit(text, [] if fut is None else resolve(fut, n_masks))
                if args.rolling_swap_step is not None and not signaled:
                    report = router.rolling_update(
                        {"kind": "checkpoint", "path": args.checkpoint,
                         "step": args.rolling_swap_step},
                        bake_s=args.rolling_bake_s,
                        burn_threshold=args.rolling_burn_threshold,
                    )
                    print(f"serve: rolling swap {json.dumps(report)}",
                          file=sys.stderr, flush=True)
                if args.stats:
                    print(f"serve: fleet stats {json.dumps(router.stats())}",
                          file=sys.stderr)
                    if autoscaler is not None:
                        print("serve: autoscale stats "
                              f"{json.dumps(autoscaler.stats())}",
                              file=sys.stderr)
            finally:
                # the control loop stops FIRST (no scale action may race
                # the teardown), then the drain contract extends to the
                # deployment loop: an in-progress ROLLING swap completes
                # or rolls the fleet back before teardown — never a
                # half-swapped fleet
                if autoscaler is not None:
                    autoscaler.close()
                _stop_deployer(deployer, args.drain_timeout_s)
            # graceful fleet teardown: replicas finish accepted work before
            # the supervisor's quit/terminate sequence
            router.drain(args.drain_timeout_s)
    return results


if __name__ == "__main__":
    main()
