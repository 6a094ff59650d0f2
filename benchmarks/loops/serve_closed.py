"""Loop kind ``serve_closed``: a closed loop of clients over
``MLMServer.submit`` -> ``_Future.result``.

Each of ``clients`` threads submits one text, waits for its top-k in its own
hands (the top-k runs in the waiting client's thread, as the server has it)
and only then sends its next: annotation and scoring workers that call
fill-mask synchronously. Load comes from this one process. The window opens
when the clients are released and closes when the last client has the
answer to the request it had in flight at ``--seconds``.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from benchmarks import check_serve, stats, trace as trace_mod, traffic
from benchmarks.loops.train_fit import peak_device_bytes
from benchmarks.reference.perceiver import F32
from benchmarks.weights import make_weights_fn, seed_words

TRACE_START_S = 1.0
TRACE_LENGTH_S = 3.0
ANSWER_TIMEOUT_S = 60.0


class Client(threading.Thread):
    """One synchronous caller. Records ``(request index, sent, answered,
    answer or None)`` for every request it sends."""

    def __init__(self, server, requests: List[Dict[str, Any]], first: int, stride: int,
                 top_k: int, gate: threading.Event):
        super().__init__(daemon=True)
        self.server, self.requests = server, requests
        self.next, self.stride, self.top_k, self.gate = first, stride, top_k, gate
        self.deadline = 0.0
        self.limit: Optional[int] = None
        self.records: List[tuple] = []

    def run(self) -> None:
        self.gate.wait()
        sent = 0
        while (time.perf_counter() < self.deadline
               and (self.limit is None or sent < self.limit)):
            index = self.next % len(self.requests)
            self.next += self.stride
            sent += 1
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.client_submit"):
                    future = self.server.submit(self.requests[index]["text"], self.top_k)
                with jax.profiler.TraceAnnotation("bench.client_wait"):
                    answer = future.result(timeout=ANSWER_TIMEOUT_S)
            except Exception as error:  # a failed request is counted, not raised
                answer = error
            self.records.append((index, t0, time.perf_counter(), answer))


def drive(server, requests, mix, seconds: float, *, offset: int = 0,
          limit: Optional[int] = None, during=None) -> Dict[str, Any]:
    """Release ``clients`` threads for ``seconds`` (or ``limit`` requests
    each) and wait for every one to hold its last answer. ``during(t0)``
    runs on this thread meanwhile (the traced run's capture)."""
    gate = threading.Event()
    n = mix["clients"]
    clients = [Client(server, requests, offset + i, n, mix["top_k"], gate) for i in range(n)]
    for c in clients:
        c.limit = limit
        c.start()
    t0 = time.perf_counter()
    for c in clients:
        c.deadline = t0 + seconds
    gate.set()
    if during is not None:
        during(t0)
    for c in clients:
        c.join(seconds + 2 * ANSWER_TIMEOUT_S)
        if c.is_alive():
            raise RuntimeError("a client never got its answer")
    records = [r for c in clients for r in c.records]
    t1 = max((r[2] for r in records), default=time.perf_counter())
    return {"records": records, "t0": t0, "t1": t1, "next": max(c.next for c in clients)}


def well_formed(answer, masks: int, top_k: int) -> bool:
    return (isinstance(answer, list) and len(answer) == masks
            and all(isinstance(a, list) and len(a) == top_k for a in answer))


def sampled_first_tokens(records, requests, mix, token_id, seed: int):
    """The sample of finished requests that ``correct`` looks at, and for each
    the token ids the server put first at its masks (None: malformed)."""
    masks_of = [len(r["mask_positions"]) for r in requests]
    finished = [i for i, _, _, a in records if well_formed(a, masks_of[i], mix["top_k"])]
    last_answer = {i: a for (i, _, _, a) in records}
    lengths = [len(r["ids"]) for r in requests]
    sample = check_serve.draw_sample(finished or [0], lengths, mix["check"]["sample"], seed)
    firsts = []
    for i in sample:
        answer = last_answer.get(i)
        if not well_formed(answer, masks_of[i], mix["top_k"]):
            firsts.append(None)
            continue
        ids = [token_id.get(per_mask[0]) for per_mask in answer]
        firsts.append(None if any(t is None for t in ids) else ids)
    return sample, firsts


def warm(server, mix: Dict[str, Any]) -> None:
    """Blocking warm-up of exactly the fused programs this mix can reach:
    every width bucket x batch bucket x mask-count bucket."""
    spec = mix["server"]
    for width in server.widths:
        ids, pad = np.zeros((1, width), np.int32), np.zeros((1, width), bool)
        for kb in spec["query_buckets"]:
            server.engine.warmup(ids, pad, np.zeros((1, kb), np.int32),
                                 buckets=spec["batch_buckets"])


def run(cell: Dict[str, Any], cfg: Dict[str, Any], mix: Dict[str, Any], builder,
        seed: int, seconds: float, trace: bool, probes, *,
        break_program=None, limits: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    lo, hi = seed_words(seed)
    vocabulary = traffic.make_vocabulary(cfg["vocab_size"])
    token_id = {tok: i for i, tok in enumerate(vocabulary)}
    requests = traffic.make_requests(mix, cfg["vocab_size"], seed)
    weights_fn = make_weights_fn(builder.param_shapes(cfg))
    workdir = tempfile.mkdtemp(prefix="bench_")
    server = builder.build_server(cfg, mix, weights_fn(lo, hi), vocabulary)
    try:
        if break_program is not None:
            break_program(server)
        warm(server, mix)
        warmed = drive(server, requests, mix, 3600.0, limit=mix["warm_requests_per_client"])
        before = server.engine.stats()

        # -- the window ------------------------------------------------------
        setup_s = probes.clock()
        setup_compiles = probes.compiles()
        capture = {}

        def during(t0: float) -> None:
            if not trace:
                return
            time.sleep(TRACE_START_S)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(f"{workdir}/trace", profiler_options=options)
            time.sleep(TRACE_LENGTH_S)
            jax.profiler.stop_trace()
            capture["done"] = True

        out = drive(server, requests, mix, seconds, offset=warmed["next"], during=during)
        # -- closed ----------------------------------------------------------
        window_s = out["t1"] - out["t0"]
        window_compiles = probes.compiles() - setup_compiles
        after = server.engine.stats()
        records = out["records"]
        masks_of = [len(r["mask_positions"]) for r in requests]
        good = [well_formed(a, masks_of[i], mix["top_k"]) for i, _, _, a in records]
        latencies_ms = [(t1 - t0) * 1e3 if ok else float("inf")
                        for (_, t0, t1, _), ok in zip(records, good)]
        answered = sum(good)
        flops_done = sum(
            builder.serve_flops(cfg, len(requests[i]["ids"]), masks_of[i])
            for (i, _, _, _), ok in zip(records, good) if ok)
        memory_peak = max(peak_device_bytes(d) for d in jax.devices())
        counts = {k: after[k] - before[k] for k in ("requests", "rows", "batches", "padded_rows")}
        result: Dict[str, Any] = {
            "setup_s": setup_s, "window_s": window_s,
            "requests_answered": answered, "latencies_ms": latencies_ms,
            "engine_counts": counts, "flops_done": flops_done,
            "setup_xla_compiles": setup_compiles,
            "memory_peak_bytes": int(memory_peak),
            "attempted": len(records), "failed": len(records) - answered,
        }
        summary = None
        if trace and capture.get("done"):
            devices, host_spans = trace_mod.load(f"{workdir}/trace")
            summary = trace_mod.summarize(devices, host_spans)
        result["summary"] = summary

        # free the program's state before the reference touches the chip
        server.close()
        server = None
        gc.collect()

        spec = mix["check"]
        sample, firsts = sampled_first_tokens(records, requests, mix, token_id, seed)
        ref_logits = check_serve.reference_mask_logits(
            builder.reference_logits_fn(cfg)(F32), weights_fn(lo, hi),
            [requests[i] for i in sample], cfg["max_seq_len"], spec["block_rows"])
        numbers = check_serve.gaps_below_best(ref_logits, firsts)
        verdict = check_serve.verdict(
            numbers, limits if limits is not None else check_serve.load_limits(cell["name"]))
        result["verdict"] = verdict
        result["details"] = {
            "requests": len(records), "answered": answered, "window_s": window_s,
            "window_xla_compiles": window_compiles, "engine_counts": counts,
            "latency_p50_ms": stats.percentile(latencies_ms, 50) if latencies_ms else None,
            "sampled_requests": len(sample), "numbers": numbers,
        }
        return result
    finally:
        if server is not None:
            server.close()
        shutil.rmtree(workdir, ignore_errors=True)
