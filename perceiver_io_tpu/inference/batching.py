"""Continuous batching for Perceiver-AR decode: a slotted cache arena plus
ONE batched step dispatch covering every active stream.

r18's :class:`~perceiver_io_tpu.inference.generate.ARGenerator` is correct
but serves each session on its own dispatch chain: at any concurrency the
chip runs batch-1 matmuls over the full weight stream per token, and the
serving roofline (PERF.md) says that path is HBM-WEIGHT-bound — the weights
are read once per step regardless of how many streams want a token. This
module amortizes that read:

- **slotted cache arena** (:class:`ContinuousBatcher` internals): the
  per-session fixed-capacity cache rings are pooled into ONE donated device
  buffer per episode width, leading axis = slot = session. Install is a
  ``dynamic_update_slice`` of a prefilled ring into its slot; retirement is
  free (the slot is simply re-labeled resident/free — nothing round-trips).
- **one batched step dispatch**: every active slot advances through a
  single ``lax.fori_loop`` chunk whose body is the *vmapped* per-session
  ``PerceiverARLM.step`` — the same module method the per-session engine
  chains, so incremental-vs-dense parity carries over unchanged. Per-slot
  ``steps_left`` masks exhausted/idle/free slots with ``where`` selects:
  inactive slots pass through bit-identically and cost no correctness.
- **continuous scheduling**: sessions are admitted and retired at CHUNK
  boundaries without breaking the running dispatch chain — a dedicated
  dispatcher thread owns the arena, caller threads enqueue streams and
  drain their own token queues (delivery stays on the caller's thread, so
  one slow consumer cannot stall the batch).
- **finite program family**: prefill widths already live on the fixed
  episode grid; arena capacities are power-of-two-bucketed; and per-slot
  sampling params (temperature/top_k/seed) are TRACED operands, so one
  decode program per (width, slots) serves every chunk fill, every partial
  budget, and every sampling shape — strictly smaller than the per-session
  chunk×sampling family, and AOT-warmable through the r10
  :class:`~perceiver_io_tpu.aot.ExecutableCache`.

Determinism contract: the position-folded sampling keys are reproduced
EXACTLY (``sample_logits_rows`` is value-identical to the per-session
``sample_logits`` — pinned by tests), so a stream decoded through the arena,
through a per-session chain, or re-encoded on another replica after a
mid-stream kill produces the identical token sequence — the r18 chaos
contract (``lost_accepted=0`` by content) is preserved verbatim.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from queue import SimpleQueue
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.inference.generate import (
    ARGenerator,
    SamplingConfig,
)
from perceiver_io_tpu.resilience import faults


def sample_logits_rows(logits, keys, temperature, top_k):
    """Per-row, fully-traced twin of :func:`generate.sample_logits`: one
    compiled program serves EVERY (temperature, top_k, greedy) combination
    — the per-slot sampling params ride as operands, never as program
    statics. Value-identical to the per-session path row by row (same
    greedy argmax over raw f32 logits, same ``max(t, 1e-6)`` scaling, same
    k-th-largest threshold mask, same ``jax.random.categorical`` draw from
    the same position-folded key), which is what lets a stream cross
    between the arena and a per-session chain without a token of drift."""
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # k-th largest per row with TRACED k: descending sort + gather equals
    # lax.top_k(x, k)[0][..., -1] for every k (the value is order-stable
    # under ties), without k shaping the program
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    k_idx = jnp.clip(top_k - 1, 0, vocab - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    use_topk = ((top_k > 0) & (top_k < vocab))[:, None]
    masked = jnp.where(use_topk & (scaled < kth),
                       jnp.finfo(jnp.float32).min, scaled)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temperature == 0.0, greedy_tok,
                     sampled.astype(jnp.int32))


class ArenaSession:
    """Host handle for a RESIDENT arena continuation: the accepted sequence
    plus a (width, slot, epoch) claim on the rings that encode it. The
    epoch is the staleness check — the arena bumps it whenever the slot is
    reclaimed or adopted, so a stored session whose slot moved on simply
    re-encodes from its prefix (the same spill path a dead replica takes).
    Duck-typed to :class:`generate.GenSession` where the session store and
    replica care (``seq``/``width``/``seed``/``remaining``)."""

    __slots__ = ("seq", "width", "seed", "steps", "slot", "epoch")

    def __init__(self, seq: List[int], width: int, seed: int, steps: int,
                 slot: int, epoch: int):
        self.seq = seq
        self.width = width
        self.seed = seed
        self.steps = steps
        self.slot = slot
        self.epoch = epoch

    def remaining(self) -> int:
        return self.width - len(self.seq)


_FREE, _ACTIVE, _RESIDENT = "free", "active", "resident"


class _Slot:
    __slots__ = ("state", "epoch", "stream", "last")

    def __init__(self):
        self.state = _FREE
        self.epoch = 0
        self.stream = None          # the _Stream while _ACTIVE
        self.last = 0.0             # LRU stamp for resident reclamation


class _Arena:
    """One episode width's pooled rings: the device buffer (leading axis =
    slot) plus the host slot table and the per-slot sampling operands.
    Touched ONLY by the dispatcher thread (device halves) or under the
    batcher's lock (host halves)."""

    __slots__ = ("width", "n_slots", "buf", "slots", "temp", "top_k",
                 "seeds")

    def __init__(self, width: int, n_slots: int, buf):
        self.width = width
        self.n_slots = n_slots
        self.buf = buf
        self.slots = [_Slot() for _ in range(n_slots)]
        self.temp = np.zeros((n_slots,), np.float32)
        self.top_k = np.zeros((n_slots,), np.int32)
        self.seeds = np.zeros((n_slots,), np.int32)


class _Stream:
    """One in-flight continuation: the dispatcher-side authoritative state
    (tokens produced, current placement) and the caller-side event queue
    (token chunks, then done/error) the ``generate()`` thread drains."""

    __slots__ = ("prefix", "max_new", "sampling", "adopt", "q", "tokens",
                 "width", "slot", "placed", "cancelled", "session_out",
                 "t_start", "wants_chunks", "t_queued", "t_bind",
                 "t_install", "t_first", "t_prev", "ctx")

    def __init__(self, prefix: List[int], max_new: int,
                 sampling: SamplingConfig, adopt: Optional[ArenaSession],
                 wants_chunks: bool = True, ctx=None):
        self.prefix = prefix
        self.max_new = max_new
        self.sampling = sampling
        self.adopt = adopt          # a valid resident session to resume
        self.q: "SimpleQueue" = SimpleQueue()
        self.tokens: List[int] = []  # dispatcher-authoritative
        self.width = 0
        self.slot = -1
        self.placed = False
        self.cancelled = False
        self.session_out: Optional[ArenaSession] = None
        self.t_start = time.monotonic()
        # lifecycle stamps (all monotonic): enqueue -> slot bind ->
        # prefill install -> first token -> per-chunk. t_queued resets at
        # every re-placement (episode boundary), so queue-wait observations
        # measure each wait, not the stream's whole life.
        self.t_queued = self.t_start
        self.t_bind = 0.0
        self.t_install = 0.0
        self.t_first: Optional[float] = None
        self.t_prev = self.t_start
        self.ctx = ctx              # per-stream TraceContext (or None)
        # no on_chunk consumer -> skip per-chunk queue events entirely; the
        # done event carries the full token list. On a shared-core host the
        # per-round caller wakeups are pure context-switch overhead.
        self.wants_chunks = wants_chunks

    def cur_len(self) -> int:
        return len(self.prefix) + len(self.tokens)


# admission waves bucket to powers of two up to this many prefills per
# dispatch — with the episode-grid widths this closes the prefill/install
# program family at (widths × 4 buckets)
_MAX_PREFILL_ROWS = 8


def _round_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


#: every reason an arena slot can sit idle for a scheduler round — the
#: closed cause vocabulary the flight recorder attributes with (the
#: acceptance bar: >=95% of idle slot-rounds carry one of these).
FLIGHT_CAUSES = ("no_pending", "width_mismatch", "arena_full", "draining")


def parse_flight_row(row: str) -> Dict[str, Any]:
    """Decode one packed flight-recorder row (the single definition of the
    row grammar — ``tools/decode_flight.py`` and the drill tests parse
    through here). Row kinds:

    - ``R|seq|t_ms|pending|admits|retires|W:slots:active:resident:c=n+c=n,…``
      — one scheduler round: queue depth after admission, admit/retire
      deltas, and per-arena occupancy with idle-slot cause attribution;
    - ``E|t_ms|reason|width|slot|steps`` — a resident eviction / stream
      kill freeing a slot (the kill-drill needle);
    - ``G|t_ms|width|slots`` — arena growth (doubling commit).
    """
    parts = row.split("|")
    kind = parts[0]
    if kind == "R":
        arenas = []
        if len(parts) > 6 and parts[6]:
            for blob in parts[6].split(","):
                w, n, act, res, causes_s = blob.split(":")
                causes = {}
                if causes_s:
                    for kv in causes_s.split("+"):
                        c, cnt = kv.split("=")
                        causes[c] = int(cnt)
                arenas.append({"width": int(w), "slots": int(n),
                               "active": int(act), "resident": int(res),
                               "causes": causes})
        return {"kind": "round", "seq": int(parts[1]),
                "t_ms": float(parts[2]), "pending": int(parts[3]),
                "admits": int(parts[4]), "retires": int(parts[5]),
                "arenas": arenas}
    if kind == "E":
        return {"kind": "evict", "t_ms": float(parts[1]),
                "reason": parts[2], "width": int(parts[3]),
                "slot": int(parts[4]), "steps": int(parts[5])}
    if kind == "G":
        return {"kind": "grow", "t_ms": float(parts[1]),
                "width": int(parts[2]), "slots": int(parts[3])}
    raise ValueError(f"unknown flight row kind {kind!r}")


class DecodeFlightRecorder:
    """Bounded ring of per-round scheduler decisions — the decode
    scheduler's black box. Each round the dispatcher records queue depth,
    admit/retire deltas, and per-arena occupancy with every idle slot
    attributed to a cause from :data:`FLIGHT_CAUSES`; evictions and arena
    growth land as their own rows. Rows are packed strings (one grammar,
    :func:`parse_flight_row`) so the ring costs bytes, not dicts.

    Spooling rides the ``request_phases_batch`` precedent: every
    ``spool_every`` rows one ``decode_flight_batch`` event carries the
    batch to the async event log (serialization amortized; nothing blocks
    the dispatcher). ``dump(reason)`` emits the ring tail as ONE
    ``decode_flight_dump`` event — the watchdog-stall / SIGTERM hook.
    """

    # pitlint PIT-LOCK: the ring is appended by the dispatcher but evict
    # rows arrive from RPC caller threads (session-store callbacks) and
    # stats/statz pollers read the aggregates — only under _lock.
    _guarded_by = {"_ring": "_lock", "_agg": "_lock", "_unspooled": "_lock"}

    def __init__(self, engine: str, capacity: int = 512,
                 spool_every: int = 64):
        self.engine = engine
        self.spool_every = spool_every
        self._lock = threading.Lock()
        self._ring: "deque[str]" = deque(maxlen=capacity)
        self._unspooled: List[str] = []
        self._seq = 0
        self._last = {"admits": 0, "retires": 0}
        self._agg = {
            "rounds": 0, "slot_rounds": 0, "idle_slot_rounds": 0,
            "attributed": 0, "causes": {c: 0 for c in FLIGHT_CAUSES},
            "evicts": {}, "grows": 0, "pending_max": 0,
        }

    def _push_locked(self, row: str) -> Optional[List[str]]:
        self._ring.append(row)
        self._unspooled.append(row)
        if len(self._unspooled) >= self.spool_every:
            batch, self._unspooled = self._unspooled, []
            return batch
        return None

    def _emit(self, batch: Optional[List[str]]) -> None:
        if batch:
            obs.event("decode_flight_batch", engine=self.engine,
                      n=len(batch), parts=";".join(batch))

    def record_round(self, pending: int, admitted: int, retired: int,
                     arenas: List[Tuple[int, int, int, int,
                                        Dict[str, int]]]) -> None:
        """One scheduler round, post-admission. ``arenas`` rows are
        ``(width, slots, active, resident, causes)`` with ``causes``
        attributing that arena's idle slots."""
        blobs = []
        for w, n, act, res, causes in arenas:
            causes_s = "+".join(f"{c}={k}" for c, k in sorted(causes.items()))
            blobs.append(f"{w}:{n}:{act}:{res}:{causes_s}")
        with self._lock:
            admits = admitted - self._last["admits"]
            retires = retired - self._last["retires"]
            self._last = {"admits": admitted, "retires": retired}
            self._seq += 1
            row = (f"R|{self._seq}|{time.monotonic() * 1e3:.1f}|{pending}"
                   f"|{admits}|{retires}|{','.join(blobs)}")
            agg = self._agg
            agg["rounds"] += 1
            agg["pending_max"] = max(agg["pending_max"], pending)
            for w, n, act, res, causes in arenas:
                agg["slot_rounds"] += n
                idle = n - act
                agg["idle_slot_rounds"] += idle
                for c, k in causes.items():
                    agg["causes"][c] = agg["causes"].get(c, 0) + k
                    agg["attributed"] += k
            batch = self._push_locked(row)
        self._emit(batch)

    def record_evict(self, reason: str, width: int, slot: int,
                     steps: int) -> None:
        with self._lock:
            self._agg["evicts"][reason] = (
                self._agg["evicts"].get(reason, 0) + 1)
            batch = self._push_locked(
                f"E|{time.monotonic() * 1e3:.1f}|{reason}|{width}|{slot}"
                f"|{steps}")
        self._emit(batch)

    def record_grow(self, width: int, slots: int) -> None:
        with self._lock:
            self._agg["grows"] += 1
            batch = self._push_locked(
                f"G|{time.monotonic() * 1e3:.1f}|{width}|{slots}")
        self._emit(batch)

    def tail(self, n: int = 64) -> List[str]:
        with self._lock:
            rows = list(self._ring)
        return rows[-n:]

    def summary(self) -> Dict[str, Any]:
        """Cumulative attribution aggregates (rides ``stats()`` /statz)."""
        with self._lock:
            agg = {**self._agg, "causes": dict(self._agg["causes"]),
                   "evicts": dict(self._agg["evicts"])}
        idle = agg["idle_slot_rounds"]
        agg["attribution_frac"] = (
            round(agg["attributed"] / idle, 4) if idle else 1.0)
        return agg

    def flush(self) -> None:
        """Spool any unbatched rows now (close/test determinism)."""
        with self._lock:
            batch, self._unspooled = self._unspooled, []
        self._emit(batch)

    def dump(self, reason: str, n: int = 128) -> Dict[str, Any]:
        """Emit the ring tail + aggregates as one ``decode_flight_dump``
        event (watchdog stall, SIGTERM) and return the same payload."""
        rows = self.tail(n)
        payload = {"engine": self.engine, "reason": reason,
                   "summary": self.summary(), "rows": rows}
        obs.event("decode_flight_dump", engine=self.engine, reason=reason,
                  n=len(rows), parts=";".join(rows))
        return payload


class ContinuousBatcher(ARGenerator):
    """Continuous-batching decode engine over one ``PerceiverARLM`` — the
    drop-in replacement for :class:`ARGenerator` wherever a replica serves
    concurrent streams. Same ``generate(prefix, max_new, sampling,
    on_chunk=..., session=...)`` surface, same streamed-chunk callbacks,
    same episode/width planning (inherited), same token streams (pinned);
    the difference is purely WHO runs the steps: a dispatcher thread packs
    every active stream's next chunk into one batched dispatch per arena.

    ``slots`` is the initial arena capacity per episode width
    (power-of-two-bucketed); arenas grow by doubling up to ``max_slots``
    when admissions outrun retirements, each growth step a new warmable
    (width, slots) program. A full arena queues admissions at the chunk
    boundary — open-loop honesty lives in the serving tier's admission
    control, not here.
    """

    # pitlint PIT-LOCK: the slot tables, admission queue, and dispatch
    # aggregates are shared between RPC caller threads and the dispatcher —
    # only under the condition's lock. Device buffers (arena.buf) are
    # dispatcher-owned and never touched by callers.
    _guarded_by = {"_arenas": "_cv", "_pending": "_cv", "_stats": "_cv"}
    _assumes_locked = ("_has_work", "_claim_slot", "_retire_slot",
                       "_bind_slot")

    def __init__(
        self,
        model,
        params,
        max_seq_len: int,
        chunk: int = 8,
        slots: int = 8,
        max_slots: int = 64,
        compute_dtype: Optional[str] = None,
        quantize: Optional[str] = None,
        group_size: Optional[int] = None,
        name: str = "generate",
        registry: Optional[obs.MetricsRegistry] = None,
        compile_cache: Optional[str] = None,
        heartbeat_deadline_s: Optional[float] = None,
    ):
        import jax
        import jax.numpy as jnp

        from perceiver_io_tpu.quant import apply_operands

        super().__init__(model, params, max_seq_len, chunk=chunk,
                         compute_dtype=compute_dtype, quantize=quantize,
                         group_size=group_size, name=name,
                         registry=registry)
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.slots = _round_pow2(slots)
        self.max_slots = max(_round_pow2(max_slots), self.slots)
        self._cv = threading.Condition()
        self._arenas: Dict[int, _Arena] = {}
        self._pending: "deque[_Stream]" = deque()
        self._stats = {"dispatches": 0, "steps": 0, "fill_sum": 0.0,
                       "admitted": 0, "retired": 0}
        self._closed = threading.Event()
        self.flight = DecodeFlightRecorder(name)
        # the dispatcher's watchdog: a wedged round (a device call that
        # never returns) dumps the flight-recorder tail with the thread stacks —
        # the "why was my stream stuck" evidence. None = no monitor.
        self._hb = obs.Heartbeat(
            f"{name}-arena-dispatch", deadline_s=heartbeat_deadline_s,
            diagnostics=lambda: {"flight": self.flight.summary(),
                                 "flight_tail": self.flight.tail(16)},
            on_stall=lambda: self.flight.dump("watchdog_stall"))

        reg = registry if registry is not None else obs.get_registry()
        labels = {"engine": name, "task": "generate"}
        self._m_occupancy = reg.gauge(
            "ar_decode_slot_occupancy",
            "active arena slots at the last batched dispatch (the decode "
            "batch fill the weight stream amortizes over)", labels)
        self._m_slots_total = reg.gauge(
            "ar_decode_slots", "allocated arena slots across widths", labels)
        self._m_admitted = reg.counter(
            "ar_arena_admitted_total",
            "streams admitted into an arena slot (prefill-install or "
            "resident-adopt)", labels)
        self._m_retired = reg.counter(
            "ar_arena_retired_total",
            "streams retired from their slot at a chunk boundary", labels)
        self._m_steps_per_dispatch = reg.histogram(
            "ar_decode_steps_per_dispatch",
            "decode steps advanced by one batched dispatch (sum over "
            "active slots)", labels)
        self._m_queue = reg.gauge(
            "ar_arena_admission_queue",
            "streams waiting for a slot at the next chunk boundary", labels)

        # -- the batched device programs (managed Compiled table: the
        # dispatch calls executables directly, so warmup/AOT and the live
        # path share exactly one build per (width, slots)) ------------------
        donate_decode = (1,) if jax.default_backend() == "tpu" else ()
        donate_install = (0,) if jax.default_backend() == "tpu" else ()

        def step_one(p, cache, token):
            # re-batch one slot to the (B=1, ...) shapes PerceiverARLM.step
            # was written for; vmap strips/restores the slot axis. The ring
            # length is the one SCALAR leaf (no batch axis in the session
            # cache — step's dynamic-slice indices need it 0-d), so it
            # passes through unbatched both ways.
            cache1 = {k: (v if k == "len"
                          else jax.tree.map(lambda x: x[None], v))
                      for k, v in cache.items()}
            logits, new = model.apply({"params": p}, cache1,
                                      token[None, None], method="step")
            new = {k: (v if k == "len"
                       else jax.tree.map(lambda x: x[0], v))
                   for k, v in new.items()}
            return logits[0].astype(jnp.float32), new

        def arena_decode_fn(p, buf, temperature, top_k, seeds, steps_left):
            n_slots = steps_left.shape[0]
            # quantized tree -> QKernel operands ONCE per dispatch; the
            # vmapped per-slot steps then share one int-byte weight stream —
            # batched decode over quantized weights is exactly the
            # compounding play (weight stream ~= the whole decode bill)
            p = apply_operands(p)

            def body(i, carry):
                buf_c, out = carry
                cache, logits = buf_c["cache"], buf_c["logits"]
                active = i < steps_left                       # (S,)
                pos = cache["len"]                            # (S,)
                keys = jax.vmap(
                    lambda sd, q: jax.random.fold_in(jax.random.key(sd), q)
                )(seeds, pos)
                tok = sample_logits_rows(logits, keys, temperature, top_k)
                new_logits, new_cache = jax.vmap(
                    step_one, in_axes=(None, 0, 0))(p, cache, tok)

                def sel(new, old):
                    mask = jnp.reshape(
                        active, (n_slots,) + (1,) * (new.ndim - 1))
                    return jnp.where(mask, new, old)

                out = out.at[:, i].set(jnp.where(active, tok, -1))
                return ({"cache": jax.tree.map(sel, new_cache, cache),
                         "logits": jnp.where(active[:, None], new_logits,
                                             logits)},
                        out)

            out0 = jnp.full((n_slots, self.chunk), -1, jnp.int32)
            return jax.lax.fori_loop(0, self.chunk, body, (buf, out0))

        def arena_install_fn(buf, cache, logits, slot):
            def put(b, c):
                val = jnp.reshape(c, (1,) + b.shape[1:]).astype(b.dtype)
                return jax.lax.dynamic_update_slice(
                    b, val, (slot,) + (0,) * (b.ndim - 1))

            return {
                "cache": jax.tree.map(put, buf["cache"], cache),
                "logits": jax.lax.dynamic_update_slice(
                    buf["logits"], logits.astype(buf["logits"].dtype),
                    (slot, 0)),
            }

        prefill_raw = self._prefill.__wrapped__  # unjitted, vmap-able

        def prefill_rows_fn(p, ids, pad, lengths):
            # one admission wave: (K, W) prompts with per-row true lengths
            # -> per-row next-token logits (K, 1, vocab) and session cache
            # leaves stacked on a leading K axis ((K,) for the scalar ring
            # length). ONE dispatch encodes the whole wave — on every
            # backend the K prompts share the weight stream the way the
            # decode arena shares it across slots.
            return jax.vmap(
                lambda i, m, le: prefill_raw(p, i[None], m[None], le),
                in_axes=(0, 0, 0))(ids, pad, lengths)

        def arena_install_rows_fn(buf, bcache, blogits, slots):
            # row-scatter a whole admission wave into the arena: K
            # (dynamic_update_slice) writes in ONE program instead of K
            # install dispatches. Pad rows repeat a real row's
            # (slot, content) pair — an idempotent duplicate write.
            def put(b, c, slot):
                val = jnp.reshape(c, (1,) + b.shape[1:]).astype(b.dtype)
                return jax.lax.dynamic_update_slice(
                    b, val, (slot,) + (0,) * (b.ndim - 1))

            for k in range(blogits.shape[0]):
                row = jax.tree.map(lambda x: x[k], bcache)
                buf = {
                    "cache": jax.tree.map(
                        lambda b, c: put(b, c, slots[k]),
                        buf["cache"], row),
                    "logits": jax.lax.dynamic_update_slice(
                        buf["logits"],
                        blogits[k].astype(buf["logits"].dtype),
                        (slots[k], 0)),
                }
            return buf

        self._jit_decode = jax.jit(arena_decode_fn,
                                   donate_argnums=donate_decode)
        self._jit_install = jax.jit(arena_install_fn,
                                    donate_argnums=donate_install)
        self._jit_prefill_rows = jax.jit(prefill_rows_fn)
        self._jit_install_rows = jax.jit(arena_install_rows_fn,
                                         donate_argnums=donate_install)
        self._prog_lock = threading.Lock()
        self._programs: Dict[Tuple[str, int, int], Any] = {}
        self._exec_cache = None
        self._fp_base: Optional[Dict[str, Any]] = None
        if compile_cache:
            from perceiver_io_tpu.aot import ExecutableCache

            self._exec_cache = ExecutableCache.open(compile_cache,
                                                    registry=reg)
        self._thread = threading.Thread(
            target=self._loop, name=f"{name}-arena-dispatch", daemon=True)
        self._thread.start()

    # -- program table -------------------------------------------------------

    def _program(self, kind: str, width: int, n_slots: int, example_args):
        """The compiled executable for one (kind, width, slots) point —
        from memory, the AOT disk cache, or a fresh lower+compile (then
        persisted). The whole batched family is closed and warmable: one
        decode + one install program per (width, slots bucket)."""
        import jax

        key = (kind, width, n_slots)
        with self._prog_lock:
            compiled = self._programs.get(key)
            if compiled is not None:
                return compiled
            jitted = (self._jit_decode if kind == "decode"
                      else self._jit_prefill_rows if kind == "prefill"
                      else self._jit_install_rows
                      if kind.startswith("install_rows")
                      else self._jit_install)
            if self._exec_cache is not None:
                from perceiver_io_tpu.aot import compile_via_cache

                compiled = compile_via_cache(
                    jitted, example_args, self._exec_cache,
                    self._fingerprint_base(),
                    extra=(kind, str(width), str(n_slots)))
            else:
                avals = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        np.shape(x), x.dtype,
                        sharding=getattr(x, "sharding", None)),
                    tuple(example_args))
                compiled = jitted.lower(*avals).compile()
            self._programs[key] = compiled
            return compiled

    def _fingerprint_base(self) -> Dict[str, Any]:
        if self._fp_base is None:
            from perceiver_io_tpu.aot import (
                callable_sources,
                environment_fingerprint,
            )

            base = dict(environment_fingerprint())
            base.update(chunk=self.chunk,
                        quantize=str(self.quantize),
                        group_size=str(self.group_size),
                        sources=tuple(callable_sources(self.model.apply)))
            self._fp_base = base
        return self._fp_base

    # -- arena allocation ----------------------------------------------------

    def _arena_zeros(self, width: int, n_slots: int):
        """Allocate a width's pooled buffer from eval_shape avals — no
        device prefill needed to learn the ring geometry."""
        import jax
        import jax.numpy as jnp

        ids = jax.ShapeDtypeStruct((1, width), jnp.int32)
        pad = jax.ShapeDtypeStruct((1, width), jnp.bool_)
        length = jax.ShapeDtypeStruct((), jnp.int32)
        logits_s, cache_s = jax.eval_shape(
            self._prefill, self.params, ids, pad, length)

        def z(s):
            return jnp.zeros((n_slots,) + tuple(s.shape[1:]), s.dtype)

        return {"cache": jax.tree.map(z, cache_s),
                "logits": jnp.zeros((n_slots,) + tuple(logits_s.shape[1:]),
                                    jnp.float32)}

    def _ensure_arena(self, width: int) -> _Arena:
        with self._cv:
            arena = self._arenas.get(width)
        if arena is not None:
            return arena
        buf = self._arena_zeros(width, self.slots)
        fresh = _Arena(width, self.slots, buf)
        with self._cv:
            arena = self._arenas.setdefault(width, fresh)
            self._m_slots_total.set(
                sum(a.n_slots for a in self._arenas.values()))
        return arena

    def _grow(self, arena: _Arena) -> bool:
        """Double the arena (power-of-two bucket) up to ``max_slots``.
        Dispatcher-thread only: the buffer is rebuilt outside the lock, the
        slot table commit is inside it."""
        import jax
        import jax.numpy as jnp

        if arena.n_slots >= self.max_slots:
            return False
        new_n = min(arena.n_slots * 2, self.max_slots)
        pad_n = new_n - arena.n_slots
        new_buf = jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.zeros((pad_n,) + tuple(x.shape[1:]), x.dtype)]),
            arena.buf)
        with self._cv:
            arena.buf = new_buf
            arena.n_slots = new_n
            arena.slots.extend(_Slot() for _ in range(pad_n))
            arena.temp = np.concatenate(
                [arena.temp, np.zeros((pad_n,), np.float32)])
            arena.top_k = np.concatenate(
                [arena.top_k, np.zeros((pad_n,), np.int32)])
            arena.seeds = np.concatenate(
                [arena.seeds, np.zeros((pad_n,), np.int32)])
            self._m_slots_total.set(
                sum(a.n_slots for a in self._arenas.values()))
        obs.event("arena_grow", engine=self.name, width=arena.width,
                  slots=new_n)
        self.flight.record_grow(arena.width, new_n)
        return True

    # -- slot lifecycle (all under self._cv — see _guarded_by) ---------------

    def _claim_slot(self, arena: _Arena) -> Optional[int]:
        for i, s in enumerate(arena.slots):
            if s.state == _FREE:
                s.epoch += 1
                return i
        # reclaim the least-recently-used resident (its session re-encodes
        # on return — the standing spill path, exercised constantly)
        lru, lru_t = None, None
        for i, s in enumerate(arena.slots):
            if s.state == _RESIDENT and (lru_t is None or s.last < lru_t):
                lru, lru_t = i, s.last
        if lru is None:
            return None
        s = arena.slots[lru]
        s.state = _FREE
        s.epoch += 1
        s.stream = None
        return lru

    def _bind_slot(self, arena: _Arena, slot: int, st: _Stream) -> None:
        s = arena.slots[slot]
        s.state = _ACTIVE
        s.epoch += 1           # stale out any stored handle to this slot
        s.stream = st
        s.last = time.monotonic()
        st.t_bind = s.last
        self._m_queue_wait_s.observe(
            s.last - st.t_queued,
            exemplar=st.ctx.trace_id if st.ctx is not None else None)
        arena.temp[slot] = st.sampling.temperature
        arena.top_k[slot] = st.sampling.top_k
        arena.seeds[slot] = st.sampling.seed
        st.width = arena.width
        st.slot = slot
        st.placed = True
        self._stats["admitted"] += 1

    def _retire_slot(self, arena: _Arena, slot: int,
                     resident: bool) -> None:
        s = arena.slots[slot]
        s.stream = None
        s.state = _RESIDENT if resident else _FREE
        if not resident:
            s.epoch += 1
        s.last = time.monotonic()
        self._stats["retired"] += 1

    def release_session(self, session, reason: str = "evicted") -> None:
        """Free the arena slot behind a stored :class:`ArenaSession` — the
        session store's eviction callback (FIFO overflow, kill wipe,
        finished retire). Epoch-checked: a stale handle no-ops."""
        if not isinstance(session, ArenaSession):
            return
        with self._cv:
            arena = self._arenas.get(session.width)
            if arena is None or session.slot >= arena.n_slots:
                return
            s = arena.slots[session.slot]
            freed = s.state == _RESIDENT and s.epoch == session.epoch
            if freed:
                s.state = _FREE
                s.epoch += 1
        if freed and reason != "finished":
            # the resident rings behind a would-be follow-up are gone: the
            # decode work they encode is wasted (an overlapping goodput
            # dimension — the tokens themselves WERE delivered)
            self._m_tokens["wasted_evicted"].inc(int(session.steps))
            self.flight.record_evict(reason, session.width, session.slot,
                                     int(session.steps))

    # -- warmup / AOT --------------------------------------------------------

    def warmup(self, widths: Optional[Sequence[int]] = None,
               sampling: SamplingConfig = SamplingConfig()) -> int:
        """Compile the admission-wave prefill/install family plus ONE
        batched decode program per (width, slots): per-slot sampling
        params are traced operands and partial chunks are masked, so —
        unlike the per-session engine's chunk×sampling family — this is
        the ENTIRE decode program set. Wave buckets are powers of two up
        to ``_MAX_PREFILL_ROWS``. ``sampling`` is accepted for signature
        parity with :class:`ARGenerator` (it does not shape any arena
        program). With ``compile_cache`` set, programs come from / go to
        the :class:`~perceiver_io_tpu.aot.ExecutableCache`
        (zero-recompile restarts). Returns the number of programs
        readied."""
        import jax

        del sampling  # traced per-slot: no sampling-shaped programs
        count = 0
        for w in widths if widths is not None else self.widths:
            arena = self._ensure_arena(w)
            n = arena.n_slots
            k_n = 1
            while k_n <= _MAX_PREFILL_ROWS:
                ids = np.zeros((k_n, w), np.int32)
                pad = np.zeros((k_n, w), bool)
                lengths = np.full((k_n,), max(1, w - self.capacity + 1),
                                  np.int32)
                prefill = self._program("prefill", w, k_n,
                                        (self.params, ids, pad, lengths))
                # execute (cheap) so the install program sees real avals
                blogits, bcache = prefill(self.params, ids, pad, lengths)
                jax.block_until_ready(blogits)
                slots_arr = np.zeros((k_n,), np.int32)
                self._program(f"install_rows{k_n}", w, n,
                              (arena.buf, bcache, blogits, slots_arr))
                count += 2
                k_n *= 2
            ops = (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
                   np.zeros((n,), np.int32), np.zeros((n,), np.int32))
            self._program("decode", w, n, (self.params, arena.buf) + ops)
            count += 1
        obs.event("generate_warmup", engine=self.name, programs=count,
                  batched=True)
        return count

    # -- the serving surface -------------------------------------------------

    def generate(
        self,
        prefix: Sequence[int],
        max_new: int,
        sampling: Optional[SamplingConfig] = None,
        on_chunk: Optional[Callable[[List[int], Dict[str, Any]], None]] = None,
        session=None,
        trace: Optional[obs.TraceContext] = None,
    ) -> Tuple[List[int], Optional[ArenaSession]]:
        """Same contract as :meth:`ARGenerator.generate` — tokens stream
        through ``on_chunk`` on THIS thread, episodes re-prefill on the
        fixed grid, a valid resident ``session`` resumes without a prefix
        encode — but the steps run inside the shared batched dispatch.
        ``trace`` attaches a ``decode_stream`` span (chunk children are
        recorded dispatcher-side at dispatch completion). The returned
        session is an :class:`ArenaSession` slot claim."""
        if self._closed.is_set():
            raise RuntimeError(f"batcher {self.name!r} is closed")
        sampling = (sampling or SamplingConfig()).normalized()
        prefix = [int(t) for t in prefix]
        if len(prefix) < 1:
            raise ValueError("generation needs a non-empty prefix")
        adopt = None
        if (isinstance(session, ArenaSession) and session.seq == prefix
                and session.seed == sampling.seed):
            adopt = session
        if adopt is None:
            self._m_sessions.inc()
        if max_new <= 0:
            return [], adopt
        ctx = trace.child() if trace is not None else None
        st = _Stream(prefix, max_new, sampling, adopt,
                     wants_chunks=on_chunk is not None, ctx=ctx)
        with self._cv:
            self._pending.append(st)
            self._m_queue.set(len(self._pending))
            self._cv.notify_all()
        produced: List[int] = []
        ok = False
        try:
            while True:
                kind, payload = st.q.get()
                if kind == "tokens":
                    tokens, info = payload
                    produced.extend(tokens)
                    if on_chunk is not None:
                        try:
                            on_chunk(tokens, info)
                        except BaseException:
                            # consumer died (a killed replica's gated frame
                            # callback): cancel OUR stream; the batch sails
                            # on
                            self.cancel(st)
                            raise
                elif kind == "done":
                    # the done payload is the dispatcher-authoritative
                    # token list — for no-on_chunk streams no per-chunk
                    # events flowed
                    ok = True
                    return payload, st.session_out
                else:  # "error"
                    raise payload
        finally:
            if ctx is not None:
                obs.record_span(
                    "decode_stream", ctx, st.t_start,
                    time.monotonic() - st.t_start, engine=self.name,
                    tokens=len(st.tokens), ok=ok,
                    queue_wait_s=(round(st.t_bind - st.t_start, 6)
                                  if st.t_bind else None),
                    ttft_s=(round(st.t_first - st.t_start, 6)
                            if st.t_first is not None else None))

    def cancel(self, st: _Stream) -> None:
        with self._cv:
            st.cancelled = True
            self._cv.notify_all()

    def close(self, timeout_s: float = 5.0) -> None:
        self._closed.set()
        with self._cv:
            self._cv.notify_all()
        self._thread.join(timeout=timeout_s)
        self.flight.flush()
        self._hb.close()

    def stats(self) -> Dict[str, Any]:
        """Cumulative dispatch aggregates (load_bench's record block) plus
        the goodput counters and the flight recorder's attribution summary
        (the /statz queryable view)."""
        with self._cv:
            d = dict(self._stats)
            d["slots"] = sum(a.n_slots for a in self._arenas.values())
        d["slot_occupancy_mean"] = (
            round(d.pop("fill_sum") / d["dispatches"], 4)
            if d["dispatches"] else None)
        d["steps_per_dispatch_mean"] = (
            round(d["steps"] / d["dispatches"], 3)
            if d["dispatches"] else None)
        d.update(self.token_stats())
        d["flight"] = self.flight.summary()
        return d

    def peek_logits(self, session: ArenaSession) -> Optional[np.ndarray]:
        """The resident next-token logits row for a session, or None when
        its slot moved on — the parity probe (tests pin these against the
        dense oracle at 2e-5)."""
        with self._cv:
            arena = self._arenas.get(session.width)
            if arena is None or session.slot >= arena.n_slots:
                return None
            s = arena.slots[session.slot]
            if s.state != _RESIDENT or s.epoch != session.epoch:
                return None
            row = arena.buf["logits"][session.slot]
        try:
            return np.asarray(row, np.float32)
        except RuntimeError:
            # the dispatcher donated this buffer between our ref-grab and
            # the fetch (TPU path) — same answer as a moved slot
            return None

    # -- the dispatcher ------------------------------------------------------

    def _has_work(self) -> bool:
        if self._pending:
            return True
        return any(s.state == _ACTIVE
                   for a in self._arenas.values() for s in a.slots)

    def _loop(self) -> None:
        self._hb.arm()
        while True:
            with self._cv:
                while not self._closed.is_set() and not self._has_work():
                    self._hb.disarm()
                    self._cv.wait(timeout=0.5)
                if self._closed.is_set():
                    pending = list(self._pending)
                    self._pending.clear()
                    actives = [s.stream for a in self._arenas.values()
                               for s in a.slots
                               if s.state == _ACTIVE and s.stream is not None]
                    break
            self._hb.arm()
            try:
                self._admit()
                self._flight_round()
                self._dispatch_round()
            except BaseException as e:  # defensive: fail streams, not the loop
                self._fail_all(e)
            self._hb.beat()
        self._hb.disarm()
        err = RuntimeError(f"batcher {self.name!r} closed")
        killed = 0
        for st in pending + actives:
            killed += len(st.tokens)
            self.flight.record_evict("draining", st.width, st.slot,
                                     len(st.tokens))
            st.q.put(("error", err))
        if killed:
            self._m_tokens["wasted_killed"].inc(killed)

    def _flight_round(self) -> None:
        """Record this scheduler round: post-admission queue depth plus
        per-arena occupancy, with every idle slot attributed to a cause
        (the decision tree is exhaustive over :data:`FLIGHT_CAUSES`, which
        is what makes the >=95% attribution bar structural, not lucky)."""
        with self._cv:
            draining = self._closed.is_set()
            pending_widths = set()
            for st in self._pending:
                try:
                    pending_widths.add(self.plan_width(st.cur_len()))
                except ValueError:
                    pass  # finishes at the next admit pass
            rows = []
            for w in sorted(self._arenas):
                a = self._arenas[w]
                active = sum(1 for s in a.slots if s.state == _ACTIVE)
                resident = sum(1 for s in a.slots if s.state == _RESIDENT)
                idle = a.n_slots - active
                causes: Dict[str, int] = {}
                if idle:
                    if draining:
                        causes["draining"] = idle
                    elif not pending_widths:
                        causes["no_pending"] = idle
                    elif w not in pending_widths:
                        causes["width_mismatch"] = idle
                    else:
                        # pending wants THIS width yet slots sit idle —
                        # the transient between a blocked claim and the
                        # next admit pass; the steady state is full-ACTIVE
                        causes["arena_full"] = idle
                rows.append((w, a.n_slots, active, resident, causes))
            pending_n = len(self._pending)
            admitted = self._stats["admitted"]
            retired = self._stats["retired"]
        if rows:
            self.flight.record_round(pending_n, admitted, retired, rows)

    def _fail_all(self, e: BaseException) -> None:
        with self._cv:
            streams = [s.stream for a in self._arenas.values()
                       for s in a.slots
                       if s.state == _ACTIVE and s.stream is not None]
            for a in self._arenas.values():
                for i, s in enumerate(a.slots):
                    if s.state == _ACTIVE:
                        self._retire_slot(a, i, resident=False)
            streams += list(self._pending)
            self._pending.clear()
            self._m_queue.set(0)
        killed = 0
        for st in streams:
            killed += len(st.tokens)
            self.flight.record_evict("killed", st.width, st.slot,
                                     len(st.tokens))
            st.q.put(("error", e))
        if killed:
            self._m_tokens["wasted_killed"].inc(killed)

    def _admit(self) -> None:
        """Place every pending stream it can: adopt a valid resident slot,
        or prefix-encode and install into a claimed slot. Same-width fresh
        encodes are grouped into ADMISSION WAVES — one vmapped prefill
        dispatch plus one row-scatter install per wave of up to
        ``_MAX_PREFILL_ROWS`` streams, instead of a dispatch pair per
        stream. Runs at chunk boundaries only (between dispatches) —
        admission never interrupts the running batch."""
        blocked: List[_Stream] = []
        while True:
            with self._cv:
                batch = list(self._pending)
                self._pending.clear()
                if not batch:
                    self._pending.extend(blocked)
                    self._m_queue.set(len(self._pending))
                    return
                self._m_queue.set(0)
            fresh: Dict[int, List[Tuple[_Stream, List[int]]]] = {}
            for st in batch:
                if st.cancelled:
                    self._m_tokens["wasted_cancelled"].inc(len(st.tokens))
                    st.q.put(("error", RuntimeError("stream cancelled")))
                    continue
                if st.adopt is not None and self._try_adopt(st):
                    continue
                cur = st.prefix + st.tokens
                if (len(cur) >= self.max_seq_len
                        or len(st.tokens) >= st.max_new):
                    self._finish(st, resident_ok=False)
                    continue
                fresh.setdefault(self.plan_width(len(cur)),
                                 []).append((st, cur))
            for width, items in fresh.items():
                arena = self._ensure_arena(width)
                placed: List[Tuple[_Stream, List[int], int]] = []
                for st, cur in items:
                    while True:
                        with self._cv:
                            slot = self._claim_slot(arena)
                            if slot is not None:
                                # reserve NOW: the wave claims several
                                # slots before any of them is bound
                                arena.slots[slot].state = _ACTIVE
                        if slot is not None:
                            placed.append((st, cur, slot))
                            break
                        if not self._grow(arena):
                            blocked.append(st)
                            break
                for lo in range(0, len(placed), _MAX_PREFILL_ROWS):
                    self._encode_group(arena,
                                       placed[lo:lo + _MAX_PREFILL_ROWS])

    def _try_adopt(self, st: _Stream) -> bool:
        """Resume onto the resident slot without a prefix encode; False =
        stale/exhausted handle (caller falls through to a fresh encode)."""
        ses = st.adopt
        st.adopt = None  # one shot — episode moves re-place normally
        with self._cv:
            arena = self._arenas.get(ses.width)
            s = (arena.slots[ses.slot]
                 if arena is not None and ses.slot < arena.n_slots
                 else None)
            if (s is not None and s.state == _RESIDENT
                    and s.epoch == ses.epoch
                    and ses.remaining() >= 1):
                st.tokens = []
                self._bind_slot(arena, ses.slot, st)
                self._m_admitted.inc()
                return True
        return False

    def _encode_group(self, arena: _Arena, rows) -> None:
        """One admission wave: prefix-encode up to ``_MAX_PREFILL_ROWS``
        same-width streams in ONE vmapped prefill dispatch, then scatter
        all of them into their claimed slots in ONE install program. Pad
        rows (bucket rounding) replay the last real row — idempotent."""
        g = len(rows)
        if g == 0:
            return
        width = arena.width
        k_n = 1
        while k_n < g:
            k_n *= 2
        ids = np.zeros((k_n, width), np.int32)
        pad = np.zeros((k_n, width), bool)
        lengths = np.zeros((k_n,), np.int32)
        slots_arr = np.zeros((k_n,), np.int32)
        for j, (st, cur, slot) in enumerate(rows):
            p = len(cur)
            ids[j, :p] = cur
            pad[j, p:] = True
            lengths[j] = p
            slots_arr[j] = slot
        for j in range(g, k_n):
            ids[j] = ids[g - 1]
            pad[j] = pad[g - 1]
            lengths[j] = lengths[g - 1]
            slots_arr[j] = slots_arr[g - 1]
        try:
            faults.inject("generation.prefill")
            t0 = time.monotonic()
            prefill = self._program("prefill", width, k_n,
                                    (self.params, ids, pad, lengths))
            blogits, bcache = prefill(self.params, ids, pad, lengths)
            install = self._program(
                f"install_rows{k_n}", width, arena.n_slots,
                (arena.buf, bcache, blogits, slots_arr))
            arena.buf = install(arena.buf, bcache, blogits, slots_arr)
            self._m_prefill_s.observe(time.monotonic() - t0)
        except BaseException as e:
            # the wave is the blast radius: free its claimed slots, error
            # its streams; the batch (other slots) sails on
            with self._cv:
                for _, _, slot in rows:
                    arena.slots[slot].state = _FREE
                    arena.slots[slot].epoch += 1
            killed = 0
            for st, _, _ in rows:
                killed += len(st.tokens)
                st.q.put(("error", e))
            if killed:
                self._m_tokens["wasted_killed"].inc(killed)
            return
        t_install = time.monotonic()
        with self._cv:
            for st, _, slot in rows:
                self._bind_slot(arena, slot, st)
                st.t_install = t_install
        self._m_prefills.inc(g)
        self._m_admitted.inc(g)

    def _finish(self, st: _Stream, resident_ok: bool) -> None:
        """Complete a stream: mint its session handle (a resident slot
        claim when the rings can still serve a follow-up) and signal the
        caller."""
        ses = None
        if st.placed:
            # a slot whose rings are exhausted (remaining 0) can't serve a
            # follow-up — freeing it beats hoarding a useless resident
            resident = resident_ok and st.width - st.cur_len() >= 1
            with self._cv:
                arena = self._arenas.get(st.width)
                s = arena.slots[st.slot]
                self._retire_slot(arena, st.slot, resident=resident)
                if resident:
                    ses = ArenaSession(st.prefix + st.tokens, st.width,
                                       st.sampling.seed, len(st.tokens),
                                       st.slot, s.epoch)
        st.session_out = ses
        if st.placed:
            self._m_retired.inc()
        self._m_tokens["delivered"].inc(len(st.tokens))
        st.q.put(("done", list(st.tokens)))

    def _dispatch_round(self) -> None:
        """One chunk boundary: per arena with active slots, LAUNCH one
        batched dispatch (jax dispatch is async — every arena's program is
        in flight before the first result is fetched, so multi-width rounds
        overlap on device), then distribute tokens, retire finished
        streams, and re-queue episode-boundary streams for re-placement."""
        with self._cv:
            widths = [w for w, a in self._arenas.items()
                      if any(s.state == _ACTIVE for s in a.slots)]
        launched = [self._launch_arena(w) for w in widths]
        for rec in launched:
            if rec is not None:
                self._complete_arena(*rec)

    def _launch_arena(self, width: int):
        with self._cv:
            arena = self._arenas[width]
            n = arena.n_slots
            steps_left = np.zeros((n,), np.int32)
            by_slot: Dict[int, _Stream] = {}
            for i, s in enumerate(arena.slots):
                if s.state != _ACTIVE:
                    continue
                st = s.stream
                if st.cancelled:
                    self._retire_slot(arena, i, resident=False)
                    self._m_tokens["wasted_cancelled"].inc(len(st.tokens))
                    st.q.put(("error", RuntimeError("stream cancelled")))
                    continue
                budget = st.max_new - len(st.tokens)
                ring = width - st.cur_len()
                steps_left[i] = max(0, min(self.chunk, budget, ring))
                by_slot[i] = st
            temp = arena.temp.copy()
            top_k = arena.top_k.copy()
            seeds = arena.seeds.copy()
        if not by_slot:
            return None
        total_steps = int(steps_left.sum())
        if total_steps == 0:
            # every bound stream is at an episode/absolute boundary:
            # pure bookkeeping, no device dispatch
            return (arena, by_slot, steps_left, None, 0.0, 0, 0)
        faults.inject("generation.batch_dispatch")
        active_n = int((steps_left > 0).sum())
        t0 = time.monotonic()
        compiled = self._program(
            "decode", width, n,
            (self.params, arena.buf, temp, top_k, seeds, steps_left))
        arena.buf, out = compiled(self.params, arena.buf, temp, top_k,
                                  seeds, steps_left)
        return (arena, by_slot, steps_left, out, t0, active_n, total_steps)

    def _complete_arena(self, arena, by_slot, steps_left, out, t0,
                        active_n, total_steps) -> None:
        n = arena.n_slots
        if out is None:
            out_np = np.full((n, self.chunk), -1, np.int32)
            wall = 0.0
        else:
            out_np = np.asarray(out)  # blocks until this arena's round lands
            wall = time.monotonic() - t0
            self._m_chunk_s.observe(wall)
            self._m_steps.inc(total_steps)
            self._m_tokens["generated"].inc(total_steps)
            self._m_steps_per_dispatch.observe(total_steps)
            self._m_occupancy.set(active_n)
            with self._cv:
                self._stats["dispatches"] += 1
                self._stats["steps"] += total_steps
                self._stats["fill_sum"] += active_n / max(n, 1)
        wall_ms = round(wall * 1e3, 3)
        now = time.monotonic()
        events: List[Tuple[_Stream, List[int], Dict[str, Any]]] = []
        requeue: List[_Stream] = []
        spans: List[Tuple[_Stream, int]] = []
        with self._cv:
            width = arena.width
            for i, st in by_slot.items():
                n_i = int(steps_left[i])
                toks = [int(t) for t in out_np[i, :n_i]]
                st.tokens.extend(toks)
                if toks:
                    # token-production stamps, taken HERE (dispatcher side)
                    # so wants_chunks=False streams measure identically —
                    # one queue-hop ahead of the caller's on_chunk clock,
                    # which is what the 5% reconciliation pin allows for
                    if st.t_first is None:
                        st.t_first = now
                        self._m_ttft_s.observe(
                            now - st.t_start,
                            exemplar=(st.ctx.trace_id
                                      if st.ctx is not None else None))
                    else:
                        self._m_itl_s.observe((now - st.t_prev) / len(toks))
                    st.t_prev = now
                    if st.ctx is not None:
                        spans.append((st, n_i))
                if toks and st.wants_chunks:
                    events.append((st, toks, {
                        "pos": st.cur_len(), "steps": n_i,
                        "chunk_ms": wall_ms, "batched": active_n,
                    }))
                done = (len(st.tokens) >= st.max_new
                        or st.cur_len() >= self.max_seq_len)
                boundary = st.cur_len() >= width
                if done:
                    pass  # finished below (needs the slot retire under cv)
                elif boundary:
                    # episode exhausted: free the slot, re-place at the
                    # next grid width (re-prefill from the extended prefix)
                    self._retire_slot(arena, i, resident=False)
                    st.placed = False
                    st.t_queued = now  # the next queue wait starts here
                    requeue.append(st)
            self._pending.extend(requeue)
            self._m_queue.set(len(self._pending))
        for st, n_i in spans:
            obs.record_span("decode_chunk", st.ctx.child(), t0, wall,
                            engine=self.name, steps=n_i,
                            pos=st.cur_len(), batched=active_n)
        for st, toks, info in events:
            st.q.put(("tokens", (toks, info)))
        finished = [st for st in by_slot.values()
                    if (len(st.tokens) >= st.max_new
                        or st.cur_len() >= self.max_seq_len)]
        for st in finished:
            resident_ok = st.cur_len() < self.max_seq_len
            self._finish(st, resident_ok=resident_ok)
