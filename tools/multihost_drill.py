"""Measure multi-host recovery walls on the CPU sim. One JSON line (stdout).

Two drills, one record contract (progress on stderr, PIT-CONTRACT):

- **restart-the-world** (default, r19): launch a supervised
  ``--spawn_hosts 2`` MLM run, SIGKILL one rank after the first committed
  checkpoint, and time every phase the supervisor performs — detection,
  teardown, relaunch, back-to-training (first post-restart metrics row).
- **elastic** (``--elastic``, r23): spawn the 5-process elastic pool
  (``tests/elastic_worker.py``), kill one rank mid-epoch, and read the
  walls the survivors report — in-process resize (decision→resume),
  buddy-mirror restore bytes, hot-spare join — plus the zero-loss
  accounting: ``steps_lost`` (global steps not covered by any survivor)
  and the parity verdict (identical per-step losses and final state
  digests across the post-resize world).

``--paired`` runs BOTH arms in this one process (restart first) and emits
their same-process ``speedup`` — host-clock walls are only compared
within one process. ``--dry`` declares the record keys
without touching any backend.

The numbers feed PERF.md §Multi-host recovery / §Elastic training. They
are CPU-sim walls — the restart arm is dominated by the jit re-compile of
the restarted world — but the PHASE STRUCTURE is the product being
measured: how long a child death leaves the fleet idle before training
resumes, with no human in the loop.

Usage::

    python tools/multihost_drill.py [--steps 10] [--delay 0.4]
        [--workdir DIR] [--elastic] [--paired] [--dry]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from perceiver_io_tpu.utils.jsonline import emit_json_line  # noqa: E402

# the one-line record's key set, declared for --dry (bench_compare reads
# these; keep in sync with FLOOR_CLASSES' r23 elastic entries there)
KEYS = (
    "metric", "dry", "mode", "ok", "rc", "steps", "delay_s",
    # restart arm (r19)
    "kill_to_restart_decision_s", "kill_to_relaunch_s",
    "kill_to_training_again_s", "total_wall_s", "resumed_from", "final_step",
    # elastic arm (r23)
    "pool", "die_rank", "die_at", "resize_wall_s", "grow_wall_s",
    "join_wall_s", "buddy_restore_bytes", "steps_lost", "parity",
    # --paired
    "restart_baseline_s", "speedup",
)


def _pid_of_rank(rank: int, marker: str = "train_mlm"):
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().decode(errors="replace").split("\0")
        except OSError:
            continue
        if (marker in " ".join(argv) and "--process_id" in argv
                and argv[argv.index("--process_id") + 1] == str(rank)):
            return int(pid)
    return None


def _losses(logdir: str):
    """Per-step train_loss across every version dir, last write wins (a
    resumed run appends into the same metrics.jsonl)."""
    import glob

    rows = {}
    for path in sorted(glob.glob(
            os.path.join(logdir, "mlm", "version_*", "metrics.jsonl"))):
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if "train_loss" in row:
                    rows[row["step"]] = row["train_loss"]
    return rows


def wait_for(predicate, timeout_s, poll_s=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(poll_s)
    return None


def run_restart(args, workdir) -> dict:
    """The r19 arm: supervised world restart after a SIGKILL. Returns the
    record fragment (``ok`` + the kill_to_* walls)."""
    logdir = os.path.join(workdir, "logs")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["PIT_FAULTS"] = (
        f"trainer.collective:slow@every:1@delay:{args.delay}")
    cmd = [
        sys.executable, os.path.join(REPO, "train", "train_mlm.py"),
        "--spawn_hosts", "2", "--spawn_attempts", "3",
        "--synthetic", "--synthetic_size", "64", "--batch_size", "16",
        "--max_seq_len", "32", "--vocab_size", "90", "--num_latents", "8",
        "--num_latent_channels", "16", "--num_encoder_layers", "2",
        "--num_self_attention_layers_per_block", "1",
        "--num_cross_attention_heads", "2",
        "--num_self_attention_heads", "2", "--dtype", "float32",
        "--log_every_n_steps", "1", "--max_steps", str(args.steps),
        "--eval_every_n_steps", "2", "--max_to_keep", "3",
        "--step_timeout_s", str(args.step_timeout_s),
        "--logdir", logdir, "--root", os.path.join(workdir, "cache"),
    ]
    from perceiver_io_tpu.cli.common import _newest_resumable_run

    err_path = os.path.join(workdir, "launcher.err")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, env=env, stdout=subprocess.DEVNULL, stderr=open(err_path, "w"))

    record = {"ok": False, "steps": args.steps, "delay_s": args.delay}
    try:
        resumable = wait_for(
            lambda: _newest_resumable_run(logdir, "mlm"), timeout_s=240)
        if not resumable:
            record["error"] = "no committed checkpoint before kill window"
            proc.kill()
            return record
        victim = wait_for(lambda: _pid_of_rank(1), timeout_s=30)
        if victim is None:
            record["error"] = "rank-1 process not found to kill"
            proc.kill()
            return record
        pre_kill_steps = len(_losses(logdir))
        t_kill = time.monotonic()
        os.kill(victim, signal.SIGKILL)
        print(f"[drill] killed rank 1 (pid {victim}) at "
              f"t+{t_kill - t0:.1f}s", file=sys.stderr)

        def stderr_has(marker):
            with open(err_path) as f:
                return marker in f.read()

        restarted = wait_for(
            lambda: stderr_has("restarting all 2 hosts"), timeout_s=120)
        t_restart_decision = time.monotonic()
        relaunched = wait_for(
            lambda: open(err_path).read().count("launched 2 processes") >= 2,
            timeout_s=120)
        t_relaunch = time.monotonic()
        training_again = wait_for(
            lambda: len(_losses(logdir)) > pre_kill_steps, timeout_s=240)
        t_training = time.monotonic()
        proc.wait(timeout=480)
        t_done = time.monotonic()
        losses = _losses(logdir)
        record.update(
            ok=(proc.returncode == 0 and bool(restarted) and bool(relaunched)
                and bool(training_again)
                and len(losses) >= args.steps),
            rc=proc.returncode,
            kill_to_restart_decision_s=round(t_restart_decision - t_kill, 3),
            kill_to_relaunch_s=round(t_relaunch - t_kill, 3),
            kill_to_training_again_s=round(t_training - t_kill, 3),
            total_wall_s=round(t_done - t0, 3),
            resumed_from=str(resumable),
            final_step=max(losses) if losses else 0,
        )
    finally:
        if proc.poll() is None:
            proc.kill()
    return record


def run_elastic(args, workdir) -> dict:
    """The r23 arm: 4→3→4 in-process resize. Spawns the 5-process elastic
    pool and reduces the per-rank JSONs to the one-record walls."""
    from perceiver_io_tpu.cli.common import _pick_coordinator_port

    port = _pick_coordinator_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    worker = os.path.join(REPO, "tests", "elastic_worker.py")
    procs = []
    for rank in range(args.pool):
        log = open(os.path.join(workdir, f"elastic_r{rank}.log"), "w")
        procs.append(subprocess.Popen(
            [sys.executable, worker, "--rank", str(rank),
             "--pool", str(args.pool), "--port", str(port),
             "--workdir", workdir, "--steps", str(args.steps),
             "--die_rank", str(args.die_rank), "--die_at", str(args.die_at)],
            env=env, stdout=log, stderr=log))
    print(f"[drill] elastic pool of {args.pool} up (coordinator "
          f"localhost:{port}); rank {args.die_rank} dies at step "
          f"{args.die_at}", file=sys.stderr)
    record = {"ok": False, "pool": args.pool, "steps": args.steps,
              "die_rank": args.die_rank, "die_at": args.die_at}
    deadline = time.monotonic() + args.elastic_timeout_s
    rcs = []
    for p in procs:
        try:
            rcs.append(p.wait(timeout=max(1.0, deadline - time.monotonic())))
        except subprocess.TimeoutExpired:
            p.kill()
            rcs.append(None)
    # the deliberately-killed rank exits 1; every other rank must exit 0
    bad = [rc if rc is not None else -1
           for i, rc in enumerate(rcs) if i != args.die_rank and rc != 0]
    record["rc"] = bad[0] if bad else 0
    reports = {}
    for rank in range(args.pool):
        path = os.path.join(workdir, f"rank{rank}_elastic.json")
        if os.path.exists(path):
            with open(path) as f:
                reports[rank] = json.load(f)
    survivors = [r for r, rep in reports.items()
                 if r != args.die_rank and "final_step" in rep]
    if not survivors:
        record["error"] = "no surviving rank reported"
        return record
    # zero-loss accounting: every global step covered, identical losses
    covered = {}
    parity = "ok"
    for r in survivors:
        for step, loss in reports[r]["losses"].items():
            if step in covered and abs(covered[step] - loss) > 1e-6 * (
                    abs(loss) + 1e-12):
                parity = "divergent_losses"
            covered[step] = loss
    steps_lost = args.steps - len(covered)
    digests = {reports[r].get("final_digest") for r in survivors}
    if len(digests) != 1 or None in digests:
        parity = "divergent_digest"
    resize = [reports[r]["walls"].get("decision_to_resume_s")
              for r in survivors if "decision_to_resume_s"
              in reports[r]["walls"]]
    grow = [reports[r]["walls"].get("grow_s") for r in survivors
            if "grow_s" in reports[r]["walls"]]
    join = [reports[r]["walls"].get("join_s") for r in reports
            if "join_s" in reports[r]["walls"]]
    restored_bytes = [e["bytes"] for r in survivors
                      for e in reports[r]["events"]
                      if e.get("kind") == "mirror_restored" and "bytes" in e]
    record.update(
        ok=(steps_lost == 0 and parity == "ok" and bool(resize)
            and bool(restored_bytes) and not bad),
        resize_wall_s=round(max(resize), 3) if resize else None,
        grow_wall_s=round(max(grow), 3) if grow else None,
        join_wall_s=round(max(join), 3) if join else None,
        buddy_restore_bytes=max(restored_bytes) if restored_bytes else 0,
        steps_lost=steps_lost,
        parity=parity,
    )
    return record


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--delay", type=float, default=0.4,
                        help="injected per-step throttle for the restart arm "
                             "(widens the kill window; the recovery phases "
                             "measured are step-rate independent)")
    parser.add_argument("--workdir", default=None)
    parser.add_argument("--step_timeout_s", type=float, default=8.0)
    parser.add_argument("--elastic", action="store_true",
                        help="run the r23 in-process-resize drill instead "
                             "of the r19 restart-the-world drill")
    parser.add_argument("--paired", action="store_true",
                        help="run BOTH arms in this process (restart, then "
                             "elastic) and emit their same-process speedup")
    parser.add_argument("--pool", type=int, default=5)
    parser.add_argument("--die_rank", type=int, default=3)
    parser.add_argument("--die_at", type=int, default=4)
    parser.add_argument("--elastic_timeout_s", type=float, default=240.0)
    parser.add_argument("--dry", action="store_true",
                        help="declare the record keys without running "
                             "anything (stdout-contract check)")
    args = parser.parse_args(argv)
    if args.paired:
        args.elastic = True
        args.steps = max(args.steps, 12)

    if args.dry:
        record = {k: None for k in KEYS}
        record.update(metric="multihost_drill", dry=True,
                      mode="elastic" if args.elastic else "restart")
        emit_json_line(record)
        return 0

    workdir = args.workdir or tempfile.mkdtemp(prefix="multihost_drill_")
    record = {"metric": "multihost_drill", "dry": False,
              "mode": "elastic" if args.elastic else "restart"}
    baseline = None
    if args.paired or not args.elastic:
        restart_dir = os.path.join(workdir, "restart_arm")
        os.makedirs(restart_dir, exist_ok=True)
        rec = run_restart(args, restart_dir)
        baseline = rec.get("kill_to_training_again_s")
        record.update(rec)
    if args.elastic:
        elastic_dir = os.path.join(workdir, "elastic_arm")
        os.makedirs(elastic_dir, exist_ok=True)
        rec = run_elastic(args, elastic_dir)
        if args.paired:
            rec["restart_baseline_s"] = baseline
            if baseline and rec.get("resize_wall_s"):
                rec["speedup"] = round(baseline / rec["resize_wall_s"], 3)
            rec["ok"] = bool(rec.get("ok")) and bool(record.get("ok"))
        record.update(rec)
    emit_json_line(record)
    return 0 if record.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
