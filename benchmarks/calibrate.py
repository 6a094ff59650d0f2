"""Readings that the limits of a training cell's ``correct`` are set from
(PERF.md gives the readings; ``benchmarks/limits/<cell>.json`` the limits):

    python3 -m benchmarks.calibrate --workload <cell> --seeds 1,2,3 \
        [--program 1] [--control float8_e4m3fn] [--half_batch 1]

For each seed, in one process: the program's first three steps against the
float32 reference (the lower reading), the reference computed with operands
rounded to ``--control`` put in the program's place (the upper reading), and
the reference with half of the batch left out (a fault's reading). Not part
of a benchmark run. Needs the chip, like ``benchmarks.run``.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(argv=None) -> None:
    from benchmarks import check_train, run as run_mod, traffic
    from benchmarks.loops import train_fit
    from benchmarks.reference import perceiver as ref
    from benchmarks.weights import make_weights_fn, seed_words, train_rng

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--program", type=int, default=1)
    parser.add_argument("--control", default="float8_e4m3fn")
    parser.add_argument("--forward_only", type=int, default=0,
                        help="the control rounds forward operands only")
    parser.add_argument("--half_batch", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=4.0,
                        help="serving cells: the short window at the cell's own load")
    parser.add_argument("--allow_cpu", type=int, default=0)
    args = parser.parse_args(argv)

    bench = run_mod.load_benchmark()
    cell = run_mod.find_cell(bench, args.workload)
    cfg = run_mod.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    if not args.allow_cpu:
        run_mod.require_chips(cell["chips"])
    from perceiver_io_tpu.aot import configure_compile_cache
    configure_compile_cache()
    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    if mix["loop"] == "serve_closed":
        return calibrate_serve(args, cfg, mix, builder)
    task = builder.reference_task(cfg)
    weights_fn = make_weights_fn(builder.param_shapes(cfg))
    workdir = tempfile.mkdtemp(prefix="bench_cal_")
    trainer, shardings = None, None
    try:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.time()
            lo, hi = seed_words(seed)
            pool = traffic.make_batches(mix, seed)
            rng = train_rng(lo, hi)
            batches = pool[:check_train.STEPS]
            out = {"workload": args.workload, "seed": seed}
            program = None
            if args.program:
                if trainer is None:
                    trainer = builder.build_trainer(cfg, mix, weights_fn(lo, hi), rng,
                                                    pool[0], f"{workdir}/logs")
                    shardings = jax.tree.map(lambda x: x.sharding, trainer.state)
                else:
                    # same compiled step, a fresh state from this seed
                    from perceiver_io_tpu.training import TrainState
                    fresh = jax.jit(lambda p, k: TrainState.create(p, trainer.state.tx, k))(
                        weights_fn(lo, hi), rng)
                    trainer.state = jax.device_put(fresh, shardings)
                loader = train_fit.PoolLoader(pool)
                program = train_fit.program_first_steps(
                    trainer, loader, lambda: weights_fn(lo, hi))
                # park the state off the chip's books while the reference runs
                trainer.state = jax.tree.map(
                    lambda x: jnp.zeros((), x.dtype) if hasattr(x, "dtype") and x.ndim else x,
                    trainer.state)
                gc.collect()
            reference = check_train.reference_readings(task, weights_fn(lo, hi), rng, batches)
            if program is not None:
                out["program"] = check_train.compare(program, reference)
                out["losses_program"] = program["losses"]
            out["losses_reference"] = reference["losses"]
            if args.control:
                control = check_train.reference_readings(
                    task, weights_fn(lo, hi), rng, batches,
                    arith=ref.Arith(getattr(jnp, args.control), bool(args.forward_only)))
                out["control"] = check_train.compare(control, reference)
                out["losses_control"] = control["losses"]
            if args.half_batch:
                half = check_train.reference_readings(
                    task, weights_fn(lo, hi), rng, batches, half_batch=True)
                out["half_batch"] = check_train.compare(half, reference)
            out["seconds"] = time.time() - t0
            print(json.dumps(out))
            sys.stdout.flush()
    finally:
        if trainer is not None:
            trainer.close()
        shutil.rmtree(workdir, ignore_errors=True)


def calibrate_serve(args, cfg, mix, builder) -> None:
    """Serving cells: a short window at the cell's own load per seed on ONE
    warmed server (weights swapped by ``update_params``); the control is the
    token the ``--control`` reference puts first at the same positions; the
    fault is the served first token altered (its id + 1)."""
    import numpy as np

    from benchmarks import check_serve, traffic
    from benchmarks.loops import serve_closed
    from benchmarks.reference import perceiver as ref
    from benchmarks.weights import make_weights_fn, seed_words

    vocabulary = traffic.make_vocabulary(cfg["vocab_size"])
    token_id = {tok: i for i, tok in enumerate(vocabulary)}
    weights_fn = make_weights_fn(builder.param_shapes(cfg))
    logits_fn = builder.reference_logits_fn(cfg)
    server = None
    try:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.time()
            lo, hi = seed_words(seed)
            requests = traffic.make_requests(mix, cfg["vocab_size"], seed)
            if server is None:
                server = builder.build_server(cfg, mix, weights_fn(lo, hi), vocabulary)
                serve_closed.warm(server, mix)
            else:
                server.update_params(weights_fn(lo, hi))
            out = serve_closed.drive(server, requests, mix, args.seconds)
            sample, firsts = serve_closed.sampled_first_tokens(
                out["records"], requests, mix, token_id, seed)
            picked = [requests[i] for i in sample]
            width, rows = cfg["max_seq_len"], mix["check"]["block_rows"]
            reference = check_serve.reference_mask_logits(
                logits_fn(ref.F32), weights_fn(lo, hi), picked, width, rows)
            result = {"seed": seed, "requests": len(out["records"]),
                      "program": check_serve.gaps_below_best(reference, firsts)}
            if args.control:
                low = check_serve.reference_mask_logits(
                    logits_fn(ref.Arith(getattr(jnp, args.control), bool(args.forward_only))),
                    weights_fn(lo, hi),
                    picked, width, rows)
                result["control"] = check_serve.gaps_below_best(
                    reference, [np.argmax(x, axis=-1) for x in low])
            altered = [None if f is None else [(t + 1) % cfg["vocab_size"] for t in f]
                       for f in firsts]
            result["altered_token"] = check_serve.gaps_below_best(reference, altered)
            result["seconds"] = time.time() - t0
            print(json.dumps(result))
            sys.stdout.flush()
    finally:
        if server is not None:
            server.close()


if __name__ == "__main__":
    main()
