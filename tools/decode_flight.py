#!/usr/bin/env python
"""Decode-scheduler flight-recorder analysis: attribute idle slot-rounds.

The continuous-batching dispatcher records every scheduler round into a
bounded ring (``perceiver_io_tpu.inference.batching.DecodeFlightRecorder``)
and spools it to the event log as ``decode_flight_batch`` events (plus
``decode_flight_dump`` on watchdog stall / SIGTERM). This tool replays
those packed rows through the one row grammar (``parse_flight_row``) and
answers the post-mortem question the recorder exists for: *when arena
slots sat idle, why* — every idle slot-round attributed to a cause from
``FLIGHT_CAUSES`` (``no_pending | width_mismatch | arena_full |
draining``), plus eviction reasons, arena growth, and admission-queue
high-water marks.

Modes:

- ``--events FILE``: offline analysis of an events JSONL (the
  ``--events_jsonl`` file a replica / cli.serve run wrote).
- ``--drill``: in-process CPU drill — runs a tiny continuous batcher
  through mixed-width traffic, a drain, and a mid-stream kill, spools its
  flight ring to a temp event log, and analyzes that log through the SAME
  offline path. The acceptance gate rides this: ``attribution_frac`` must
  be >= 0.95 and the kill must land as an ``E|killed`` row.

Emits exactly ONE JSON line on stdout; progress rides stderr.
``--dry`` declares the record keys without touching any backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.jsonline import emit_json_line  # noqa: E402

RECORD_KEYS = (
    "metric", "dry", "mode", "engines", "rounds", "slot_rounds",
    "idle_slot_rounds", "attributed", "attribution_frac", "causes",
    "evicts", "grows", "admits", "retires", "pending_max", "batches",
    "dumps", "dump_reasons", "drill",
)


def _log(msg: str) -> None:
    print(f"decode_flight: {msg}", file=sys.stderr, flush=True)


def analyze_rows(rows_by_engine: Dict[str, List[str]],
                 batches: int = 0, dumps: int = 0,
                 dump_reasons: List[str] = ()) -> Dict[str, Any]:
    """Aggregate parsed flight rows into the attribution record (shared by
    ``--events`` and ``--drill``; the dedup key for dump-replayed rows is
    the round sequence number, so a ring tail re-emitted by a dump never
    double-counts)."""
    from perceiver_io_tpu.inference.batching import parse_flight_row

    agg = {
        "rounds": 0, "slot_rounds": 0, "idle_slot_rounds": 0,
        "attributed": 0, "causes": {}, "evicts": {}, "grows": 0,
        "admits": 0, "retires": 0, "pending_max": 0,
    }
    for engine, rows in rows_by_engine.items():
        seen_rounds = set()
        seen_other = set()
        for row in rows:
            rec = parse_flight_row(row)
            if rec["kind"] == "round":
                if rec["seq"] in seen_rounds:
                    continue
                seen_rounds.add(rec["seq"])
                agg["rounds"] += 1
                agg["admits"] += rec["admits"]
                agg["retires"] += rec["retires"]
                agg["pending_max"] = max(agg["pending_max"], rec["pending"])
                for arena in rec["arenas"]:
                    agg["slot_rounds"] += arena["slots"]
                    agg["idle_slot_rounds"] += (arena["slots"]
                                                - arena["active"])
                    for cause, n in arena["causes"].items():
                        agg["causes"][cause] = (
                            agg["causes"].get(cause, 0) + n)
                        agg["attributed"] += n
            elif rec["kind"] == "evict":
                if row in seen_other:
                    continue
                seen_other.add(row)
                agg["evicts"][rec["reason"]] = (
                    agg["evicts"].get(rec["reason"], 0) + 1)
            elif rec["kind"] == "grow":
                if row in seen_other:
                    continue
                seen_other.add(row)
                agg["grows"] += 1
    idle = agg["idle_slot_rounds"]
    agg["attribution_frac"] = (round(agg["attributed"] / idle, 4)
                               if idle else 1.0)
    agg["engines"] = sorted(rows_by_engine)
    agg["batches"] = batches
    agg["dumps"] = dumps
    agg["dump_reasons"] = sorted(set(dump_reasons))
    return agg


def analyze_events(path: str) -> Dict[str, Any]:
    """Pull every ``decode_flight_batch`` / ``decode_flight_dump`` event
    out of an events JSONL and aggregate their rows per engine."""
    rows_by_engine: Dict[str, List[str]] = {}
    batches = dumps = 0
    dump_reasons: List[str] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # a torn tail line must not kill the post-mortem
            kind = rec.get("event")
            if kind not in ("decode_flight_batch", "decode_flight_dump"):
                continue
            engine = rec.get("engine", "?")
            parts = rec.get("parts") or ""
            rows = [r for r in parts.split(";") if r]
            rows_by_engine.setdefault(engine, []).extend(rows)
            if kind == "decode_flight_batch":
                batches += 1
            else:
                dumps += 1
                dump_reasons.append(rec.get("reason", "?"))
    return analyze_rows(rows_by_engine, batches=batches, dumps=dumps,
                        dump_reasons=dump_reasons)


def run_drill(events_path: str) -> Dict[str, Any]:
    """The in-process cause-coverage drill (CPU): mixed-width traffic on a
    2-slot arena (no_pending + width_mismatch rounds), then a mid-stream
    close (a ``killed`` eviction + ``draining`` attribution), spooled to
    ``events_path`` and analyzed offline like any crash artifact."""
    import jax
    import numpy as np

    import perceiver_io_tpu.obs as obs
    from perceiver_io_tpu.inference.batching import ContinuousBatcher
    from perceiver_io_tpu.inference.generate import SamplingConfig
    from perceiver_io_tpu.models.presets import tiny_ar

    obs.configure_event_log(events_path)
    model = tiny_ar()
    max_seq_len = 64
    ids0 = np.zeros((1, max_seq_len), np.int32)
    params = model.init({"params": jax.random.key(0)}, ids0,
                        ids0 == 0)["params"]
    gen = ContinuousBatcher(model, params, max_seq_len=max_seq_len,
                            chunk=4, slots=2, max_slots=4,
                            name="flight-drill",
                            registry=obs.MetricsRegistry())
    sampling = SamplingConfig()
    rng = np.random.default_rng(0)

    def stream(plen: int, max_new: int):
        prefix = [int(t) for t in rng.integers(3, 100, plen)]
        return gen.generate(prefix, max_new, sampling)

    drill: Dict[str, Any] = {}
    try:
        # phase 1 — short prefixes, more streams than slots: admission
        # churn, then a tail of no_pending rounds as the queue drains
        _log("drill phase 1: 4 short-width streams on 2 slots")
        threads = [threading.Thread(target=stream, args=(4, 8), daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # phase 2 — two prefix populations planning different episode
        # widths (tiny_ar: 4 tokens -> width 16, 40 tokens -> width 46),
        # with the long-width arena OVERSUBSCRIBED (6 streams on <= 4
        # slots): while the queue holds only long-width work, the short-
        # width arena's idle slots attribute width_mismatch
        _log("drill phase 2: mixed widths, long-width arena oversubscribed")
        threads = ([threading.Thread(target=stream, args=(40, 12),
                                     daemon=True) for _ in range(6)]
                   + [threading.Thread(target=stream, args=(4, 4),
                                       daemon=True)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # phase 3 — the kill: a long stream dies mid-flight when the
        # engine closes under it (the replica-killed-mid-stream drill)
        _log("drill phase 3: close the engine under a live stream")
        killed_err: List[str] = []

        def doomed():
            try:
                stream(4, 400)
            except Exception as e:
                killed_err.append(type(e).__name__)

        t = threading.Thread(target=doomed, daemon=True)
        t.start()
        time.sleep(0.3)  # let it bind a slot and decode a few chunks
        gen.close()
        t.join(timeout=10)
        drill["killed_stream_error"] = (killed_err[0] if killed_err
                                        else None)
        drill["summary_in_process"] = gen.flight.summary()
    finally:
        try:
            gen.close()
        except Exception:
            pass
        obs.configure_event_log(None)  # flush + close the JSONL
    rec = analyze_events(events_path)
    rec["drill"] = drill
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--events", metavar="FILE",
                      help="analyze decode_flight_* events in this JSONL")
    mode.add_argument("--drill", action="store_true",
                      help="run the in-process CPU cause-coverage drill")
    mode.add_argument("--dry", action="store_true",
                      help="declare the record keys; no backend")
    ap.add_argument("--drill_events", default=None, metavar="FILE",
                    help="drill mode: write the drill's event log here "
                         "(default: a temp file, removed after)")
    args = ap.parse_args(argv)

    if args.dry:
        emit_json_line({"metric": "decode_flight", "dry": True,
                        "record_keys": list(RECORD_KEYS)})
        return 0
    if args.events:
        rec = analyze_events(args.events)
        rec.update(metric="decode_flight", dry=False, mode="events",
                   drill=None)
        emit_json_line(rec)
        return 0
    if args.drill:
        from perceiver_io_tpu.utils.platform import ensure_cpu_only

        ensure_cpu_only()  # the drill is a scheduler test, never a TPU job
        from perceiver_io_tpu.aot import configure_compile_cache

        configure_compile_cache()
        import tempfile

        path = args.drill_events
        cleanup = path is None
        if path is None:
            fd, path = tempfile.mkstemp(suffix=".jsonl",
                                        prefix="decode-flight-drill-")
            os.close(fd)
        try:
            rec = run_drill(path)
        finally:
            if cleanup:
                try:
                    os.unlink(path)
                except OSError:
                    pass
        rec.update(metric="decode_flight", dry=False, mode="drill")
        emit_json_line(rec)
        return 0
    ap.error("pick one of --events FILE, --drill, --dry")
    return 2


if __name__ == "__main__":
    sys.exit(main())
