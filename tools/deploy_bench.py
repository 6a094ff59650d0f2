"""Train→serve deployment-loop bench: swap cadence, per-swap latency blip,
and zero-loss across N gated swaps under open-loop traffic.

The deploy subsystem's claim is that online model refresh is FREE from the
traffic's point of view: a publication is admission-gated off the serving
path, the hot-swap installs between micro-batches (fleet mode: one replica
at a time), the compiled programs and AOT warm pools carry over, and no
accepted request is ever lost to a swap. This bench measures that claim:

- a publisher publishes ``--swaps`` checkpoints on a ``--publish_every_s``
  cadence (each a slightly-perturbed copy of the serving tree, so the
  admission gate's quality bound passes);
- the deployment loop (``perceiver_io_tpu.deploy.ModelDeployer``) gates and
  hot-swaps each one into a live engine (default) or a ``--replicas N``
  router fleet (in-process replicas, ``Router.rolling_update``);
- an open-loop Poisson arrival stream (``--rate_factor`` × a calibrated
  closed-loop capacity) runs throughout; every completion is stamped;
- the record attributes p99 latency to ±``--blip_window_s`` windows around
  each swap vs steady state (``deploy.swap_window_stats`` — the same
  methodology ``load_bench --publish_every_s`` rides), reports per-swap
  gate/swap wall seconds and the swap cadence actually sustained, and
  pins ``lost_accepted`` (accepted-but-failed requests) which MUST be 0.

Emits exactly ONE JSON line on stdout (progress on stderr). ``--cpu`` pins
the CPU backend before jax initializes (tier-1 offline mode, tiny preset);
``--dry`` emits the record schema without touching a backend. Real-TPU runs
ride the PERF.md §r10 pending queue.

Usage::

    timeout 1800 python tools/deploy_bench.py --cpu [--swaps 4]
        [--publish_every_s 1.0] [--rate_factor 0.4] [--replicas 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from typing import List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.jsonline import emit_json_line
from perceiver_io_tpu.utils.platform import probe_backend

import numpy as np

RECORD_KEYS = (
    "metric", "dry", "backend", "preset", "mode", "replicas",
    "swaps_requested", "publishes", "swaps", "rejects", "rollbacks",
    "lost_accepted", "offered_rps", "achieved_rps", "completed", "failed",
    "shed", "swap_cadence_s", "gate_ms_mean", "swap_ms_mean", "per_swap",
    "p99_steady_ms", "p99_swap_ms", "blip_ratio", "blip_window_s",
)
PER_SWAP_KEYS = ("step", "action", "gate_ms", "swap_ms", "p99_ms", "n_window")


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(
        description="deployment-loop bench: gated swaps under open-loop load")
    parser.add_argument("--cpu", action="store_true",
                        help="pin to the CPU backend (ensure_cpu_only before "
                             "jax initializes) — the offline/tier-1 mode")
    parser.add_argument("--dry", action="store_true",
                        help="emit the record schema (one JSON line) without "
                             "touching any backend")
    parser.add_argument("--preset", choices=["auto", "tiny", "flagship"],
                        default="auto")
    parser.add_argument("--swaps", type=int, default=4,
                        help="checkpoint publications to push through the "
                             "loop")
    parser.add_argument("--publish_every_s", type=float, default=1.0,
                        help="publication cadence (the loop's poll rides at "
                             "a quarter of it)")
    parser.add_argument("--rate_factor", type=float, default=0.4,
                        help="offered rate as a fraction of the calibrated "
                             "closed-loop capacity (below the knee: the blip "
                             "must not hide in saturation queueing)")
    parser.add_argument("--blip_window_s", type=float, default=0.5,
                        help="half-width of the per-swap attribution window")
    parser.add_argument("--replicas", type=int, default=0,
                        help="run the fleet mode: a router over N in-process "
                             "replicas, swaps rolling one replica at a time "
                             "(0 = single engine hot-swap)")
    parser.add_argument("--bake_s", type=float, default=0.2,
                        help="post-swap bake window per swap (per replica in "
                             "fleet mode)")
    parser.add_argument("--max_batch", type=int, default=8)
    parser.add_argument("--calibration_waves", type=int, default=2)
    parser.add_argument("--calibration_wave_size", type=int, default=16)
    parser.add_argument("--timeout_s", type=float, default=120.0,
                        help="bound on waiting for the loop to process all "
                             "publications")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    if args.dry:
        record = {k: None for k in RECORD_KEYS}
        record.update(metric="deploy_bench", dry=True,
                      record_keys=list(RECORD_KEYS),
                      per_swap_keys=list(PER_SWAP_KEYS), per_swap=[])
        emit_json_line(record)
        return

    if args.cpu:
        from perceiver_io_tpu.utils.platform import ensure_cpu_only

        ensure_cpu_only()
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    import jax

    import perceiver_io_tpu.deploy as deploy
    import perceiver_io_tpu.obs as obs
    from perceiver_io_tpu.inference import ServingEngine
    from perceiver_io_tpu.models.presets import flagship_mlm, tiny_mlm

    backend = probe_backend().backend
    tiny = args.preset == "tiny" or (args.preset == "auto" and backend != "tpu")
    vocab = 503 if tiny else 10003
    max_seq_len = 64 if tiny else 512
    registry = obs.get_registry()
    mode = "fleet" if args.replicas > 0 else "engine"
    _log(f"backend: {backend}; preset {'tiny' if tiny else 'flagship'}; "
         f"mode {mode}"
         + (f" x{args.replicas}" if args.replicas else "")
         + f"; {args.swaps} swaps every {args.publish_every_s}s")

    build = tiny_mlm if tiny else flagship_mlm
    model = build(vocab_size=vocab, max_seq_len=max_seq_len)
    ids0 = np.zeros((1, max_seq_len), np.int32)
    params = model.init(
        {"params": jax.random.key(args.seed),
         "masking": jax.random.key(args.seed + 1)},
        ids0, ids0 == 0,
    )["params"]

    def gathered_apply(p, token_ids, pad_mask, pos):
        logits, _ = model.apply(
            {"params": p}, token_ids, pad_mask, masking=False,
            deterministic=True, positions=pos,
        )
        return logits

    rng = np.random.default_rng(args.seed)
    reqs = []
    for _ in range(32):
        ids = rng.integers(3, vocab, size=(1, max_seq_len),
                           dtype=np.int64).astype(np.int32)
        reqs.append((ids, np.zeros((1, max_seq_len), bool),
                     np.array([[1, 2]], np.int32)))

    # -- serving surface -----------------------------------------------------
    engines: List[ServingEngine] = []
    local_replicas = []
    router = None
    if args.replicas > 0:
        from perceiver_io_tpu.serving import LocalReplica, ReplicaApp, Router

        def pub_factory(spec):
            if spec.get("kind") != "publication":
                raise ValueError(f"bench replica got spec {spec!r}")
            return deploy.load_publication(spec["path"])[0]

        for i in range(args.replicas):
            eng = ServingEngine(gathered_apply, params,
                                max_batch=args.max_batch,
                                name=f"db_r{i}", registry=registry)
            eng.warmup(*reqs[0])
            engines.append(eng)
            app = ReplicaApp({"infer": eng}, params,
                             params_factory=pub_factory, name=f"r{i}",
                             registry=registry)
            local_replicas.append(LocalReplica(app))
        router = Router(local_replicas, name="deploy_bench",
                        registry=registry, scrape_interval_s=0.1)
        router.refresh()
        submit = lambda req: router.submit(*req)
        target = deploy.RouterSwapTarget(router, bake_s=args.bake_s,
                                         poll_s=0.02)
    else:
        eng = ServingEngine(gathered_apply, params, max_batch=args.max_batch,
                            name="deploy_bench", registry=registry)
        eng.warmup(*reqs[0])
        engines.append(eng)
        submit = lambda req: eng.submit(*req)
        target = deploy.EngineSwapTarget(eng, params, bake_s=args.bake_s,
                                         poll_s=0.02)
    _log(f"warmed {mode} serving surface")

    # -- deployment loop -----------------------------------------------------
    publish_dir = tempfile.mkdtemp(prefix="deploy_bench_pub_")
    gate = deploy.AdmissionGate(gathered_apply, reqs[0], params,
                                quality_tol=0.5, registry=registry,
                                name="deploy_bench")
    swap_times: List[float] = []

    def on_deployed(rec):
        if rec["action"] == "swapped":
            # the INTERVAL from install start to bake end: a fleet roll
            # spans seconds, and the early replicas' installs must not be
            # misattributed to steady state
            swap_times.append((rec["t_swap"], rec["t_done"]))
        _log(f"deploy: step {rec['step']} {rec['action']}"
             + (f" ({rec['reason']})" if rec.get("reason") else "")
             + f" gate {rec.get('gate_s', 0):.3f}s"
               f" swap {rec.get('swap_s', 0):.3f}s")

    deployer = deploy.ModelDeployer(
        publish_dir, gate, target, poll_s=max(args.publish_every_s / 4, 0.05),
        registry=registry, name="deploy_bench", on_deployed=on_deployed,
    ).start()

    # -- calibration (closed loop) -------------------------------------------
    lat0: List[float] = []
    cal_rates = []
    for _ in range(args.calibration_waves):
        t0 = time.monotonic()
        futs = [(submit(reqs[i % len(reqs)]), time.monotonic())
                for i in range(args.calibration_wave_size)]
        for f, ts in futs:
            f.result(timeout=300)
            lat0.append(time.monotonic() - ts)
        cal_rates.append(args.calibration_wave_size
                         / (time.monotonic() - t0))
    cal_rps = sorted(cal_rates)[len(cal_rates) // 2]
    rate = max(args.rate_factor * cal_rps, 1.0)
    _log(f"calibrated ~{cal_rps:.1f} req/s closed-loop; offering "
         f"{rate:.1f} req/s open-loop")

    # -- open-loop traffic + publications ------------------------------------
    completions: List[Tuple[float, float]] = []
    failed: List[str] = []
    shed = [0]
    stop = threading.Event()

    def traffic():
        from perceiver_io_tpu.resilience import (
            BreakerOpen,
            DeadlineExceeded,
            RejectedError,
        )

        i = 0
        next_at = time.monotonic()
        outstanding = []
        while not stop.is_set():
            now = time.monotonic()
            if now < next_at:
                time.sleep(min(next_at - now, 0.01))
                continue
            next_at += float(rng.exponential(1.0 / rate))
            try:
                outstanding.append((submit(reqs[i % len(reqs)]), now))
            except (RejectedError, DeadlineExceeded, BreakerOpen):
                shed[0] += 1
            except Exception as e:
                # anything else killing the traffic thread silently would
                # make the zero-loss verdict pass vacuously — count it
                failed.append(type(e).__name__)
            i += 1
            # resolve ready futures without blocking arrivals
            still = []
            for fut, ts in outstanding:
                if fut.done():
                    try:
                        fut.result(0)
                        completions.append((time.monotonic(),
                                            time.monotonic() - ts))
                    except Exception as e:
                        failed.append(type(e).__name__)
                else:
                    still.append((fut, ts))
            outstanding = still
        for fut, ts in outstanding:  # drain the tail
            try:
                fut.result(timeout=60)
                completions.append((time.monotonic(),
                                    time.monotonic() - ts))
            except Exception as e:
                failed.append(type(e).__name__)

    t_traffic = threading.Thread(target=traffic, daemon=True)
    t_traffic.start()
    t_start = time.monotonic()
    publishes = 0
    for i in range(1, args.swaps + 1):
        time.sleep(args.publish_every_s)
        scale = 1.0 + 1e-3 * i  # perturbed same-regime tree: gate passes
        tree = jax.tree.map(
            lambda x: x * scale
            if np.issubdtype(np.asarray(x).dtype, np.floating) else x,
            params)
        deploy.publish_params(publish_dir, i * 10, tree,
                              {"val_loss": 1.0 - 1e-3 * i})
        publishes += 1
    deadline = time.monotonic() + args.timeout_s
    while (len(deployer.history) < publishes
           and time.monotonic() < deadline):
        time.sleep(0.05)
    time.sleep(args.blip_window_s)  # let the last window fill
    stop.set()
    t_traffic.join(timeout=120)
    elapsed = time.monotonic() - t_start
    deployer.stop(args.timeout_s)

    stats = deployer.stats()
    blip = deploy.swap_window_stats(completions, swap_times,
                                    args.blip_window_s)
    swapped = [r for r in deployer.history if r["action"] == "swapped"]
    gate_ms = [1e3 * r["gate_s"] for r in deployer.history if "gate_s" in r]
    swap_ms = [1e3 * r["swap_s"] for r in swapped]
    swap_ends = [t[1] for t in swap_times]
    cadence = (None if len(swap_ends) < 2 else
               (swap_ends[-1] - swap_ends[0]) / (len(swap_ends) - 1))
    ms = lambda v: None if v is None else round(v * 1e3, 3)
    record = {
        "metric": "deploy_bench", "dry": False, "backend": backend,
        "preset": "tiny" if tiny else "flagship", "mode": mode,
        "replicas": args.replicas,
        "swaps_requested": args.swaps, "publishes": publishes,
        "swaps": stats["swaps"], "rejects": sum(stats["rejected"].values()),
        "rollbacks": stats["rollbacks"],
        # the zero-loss verdict: accepted requests that FAILED (sheds are
        # admission refusals, not losses)
        "lost_accepted": len(failed),
        "offered_rps": round(rate, 3),
        "achieved_rps": round(len(completions) / max(elapsed, 1e-9), 3),
        "completed": len(completions), "failed": len(failed),
        "shed": shed[0],
        "swap_cadence_s": None if cadence is None else round(cadence, 3),
        "gate_ms_mean": (round(float(np.mean(gate_ms)), 3)
                         if gate_ms else None),
        "swap_ms_mean": (round(float(np.mean(swap_ms)), 3)
                         if swap_ms else None),
        "per_swap": [
            {"step": r["step"], "action": r["action"],
             "gate_ms": round(1e3 * r.get("gate_s", 0.0), 3),
             "swap_ms": round(1e3 * r.get("swap_s", 0.0), 3),
             "p99_ms": ms(blip["per_swap_p99_s"][i])
             if i < len(blip["per_swap_p99_s"]) else None,
             "n_window": (blip["per_swap_n"][i]
                          if i < len(blip["per_swap_n"]) else 0)}
            for i, r in enumerate(swapped)
        ],
        "p99_steady_ms": ms(blip["p99_steady_s"]),
        "p99_swap_ms": ms(blip["p99_swap_s"]),
        "blip_ratio": (
            round(blip["p99_swap_s"] / blip["p99_steady_s"], 3)
            if blip["p99_swap_s"] and blip["p99_steady_s"] else None),
        "blip_window_s": args.blip_window_s,
    }
    _log(f"swaps {record['swaps']}/{publishes}, lost {len(failed)}, "
         f"steady p99 {record['p99_steady_ms']} ms, swap-window p99 "
         f"{record['p99_swap_ms']} ms (ratio {record['blip_ratio']})")

    if router is not None:
        router.close()
    for lr in local_replicas:
        lr.app.close()
    for e in engines:
        e.close()
    emit_json_line(record)


if __name__ == "__main__":
    main()
