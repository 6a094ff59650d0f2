"""Incremental Perceiver-AR generation: prefix encode once, then step a
donated on-device latent/KV cache — the autoregressive serving engine.

The model half lives in ``models/perceiver.py`` (:class:`PerceiverARLM`):
``prefill`` runs ONE dense causal forward over the (width-bucketed) prefix
and harvests every tensor the dense path attends over into fixed-capacity
cache rings; ``step`` recomputes only the new token's latent row against
those rings. This module is the engine around that pair:

- **program discipline**: one compiled prefill program per (batch, width)
  bucket and one decode program per (batch, chunk, sampling-shape) — decode
  steps are chained ON DEVICE by ``lax.fori_loop`` inside a single dispatch
  with the cache donated between chunks, so the per-dispatch host
  latency amortizes over the chunk exactly like the training loop's
  ``steps_per_dispatch`` (never a host round trip per step).
- **seeded, position-folded sampling**: the PRNG key for the token at
  absolute position p is ``fold_in(key(seed), p)`` — a continuation that
  re-encodes from its prefix on ANOTHER replica (affinity spill, episode
  re-prefill) reproduces the identical stream, which is what lets the
  mid-stream chaos drill assert ``lost_accepted=0`` by content.
- **episodes**: one prefill serves at most ``capacity − 1`` decode steps
  (the latent window must still cover the last prefix token). Longer
  continuations re-prefill from the extended prefix — the same re-encode
  path a dead session pin takes, so it is exercised constantly, not only
  under chaos.
- **parity oracle**: the dense full-prefix forward
  (``PerceiverARLM.__call__`` over the same padded width and
  latent-window anchor) is the oracle the incremental path must match at
  2e-5 on the f32 path (the tier-1 correctness spine,
  ``tests/test_generate.py``).

``GenerateSessionStore`` is the replica-side resident-state half: bounded
session table (FIFO eviction), sessions keyed like the latent-cache
affinity sessions so the router pins them identically.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.resilience import faults

Array = Any

#: every way a generated token leaves the engine — the ``outcome`` label on
#: ``decode_tokens_total``. ``delivered`` = handed to the caller at a
#: successful stream completion; ``generated`` = sampled by a decode
#: dispatch (the denominator: goodput = delivered / generated); the
#: ``wasted_*`` outcomes attribute the gap — tokens a cancelled/killed
#: stream produced but never completed, plus resident decode state an
#: eviction discarded (an overlapping dimension: evicted tokens WERE
#: delivered, what is wasted is the cache work behind a follow-up).
DECODE_TOKEN_OUTCOMES = ("generated", "delivered", "wasted_cancelled",
                         "wasted_killed", "wasted_evicted")


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """How tokens are drawn from the step logits.

    ``temperature == 0`` is greedy argmax (the parity-friendly mode);
    otherwise logits/temperature with optional top-``k`` truncation feed a
    categorical draw. ``seed`` roots the position-folded key stream."""

    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    def normalized(self) -> "SamplingConfig":
        t = float(self.temperature)
        k = int(self.top_k)
        if t < 0:
            raise ValueError(f"temperature must be >= 0, got {t}")
        if k < 0:
            raise ValueError(f"top_k must be >= 0, got {k}")
        return dataclasses.replace(self, temperature=t, top_k=k,
                                   seed=int(self.seed))


def sample_logits(logits, key, temperature, top_k: int, greedy: bool):
    """Draw one token per row from (B, V) logits. ``top_k``/``greedy`` are
    static (they shape the program); ``temperature`` is a traced operand so
    one compiled program serves every temperature."""
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class GenSession:
    """Host-side handle for one generation stream: the device cache rings,
    the pending next-token logits, and the accepted token sequence (prompt +
    continuation) the cache state corresponds to."""

    __slots__ = ("cache", "next_logits", "seq", "width", "seed", "steps")

    def __init__(self, cache, next_logits, seq: List[int], width: int,
                 seed: int):
        self.cache = cache
        self.next_logits = next_logits
        self.seq = seq          # full accepted sequence the cache encodes
        self.width = width      # the cross-ring capacity (bucketed)
        self.seed = seed
        self.steps = 0          # decode steps taken over this session

    def remaining(self) -> int:
        """Decode steps this episode's rings can still absorb."""
        return self.width - len(self.seq)


class ARGenerator:
    """The incremental decode engine over one :class:`PerceiverARLM`.

    Prefill widths live on the GLOBAL EPISODE GRID ``capacity, capacity +
    (capacity−1), capacity + 2(capacity−1), …`` (capped at max_seq_len):
    grid spacing ``capacity − 1`` makes every grid point a legal window end
    for every prefix length inside its span, and a FIXED grid — never a
    function of the request — means a session re-encoded from its prefix at
    ANY point (affinity spill, episode boundary, follow-up call) anchors its
    latent window exactly where the uninterrupted stream would have,
    keeping the position-folded token stream bit-identical. It also bounds
    the prefill program family to ~max_seq_len/capacity widths (flagship:
    three), so serving compiles are a warmable closed set.

    ``chunk`` is the fori_loop trip count per decode dispatch (and the
    streaming granularity a serving caller observes).
    """

    def __init__(
        self,
        model,
        params,
        max_seq_len: int,
        chunk: int = 8,
        compute_dtype: Optional[str] = None,
        quantize: Optional[str] = None,
        group_size: Optional[int] = None,
        name: str = "generate",
        registry: Optional[obs.MetricsRegistry] = None,
    ):
        import jax

        from perceiver_io_tpu.inference.engine import (
            prepare_param_tree,
            resolve_params_mode,
        )
        from perceiver_io_tpu.quant import apply_operands, is_quantized

        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.model = model
        self.max_seq_len = max_seq_len
        self.capacity = int(model.num_latents)
        if self.capacity < 2:
            raise ValueError("generation needs num_latents >= 2")
        self.chunk = int(chunk)
        self.name = name
        widths, w = [], self.capacity
        while w < max_seq_len:
            widths.append(w)
            w += self.capacity - 1
        widths.append(max_seq_len)
        self.widths = widths
        # same mode surface as ServingEngine: quantize='int8'/'int4' (or the
        # compute_dtype='int8w'/'int4w' shorthands) store the projection
        # kernels as int bytes, and the batched step's GEMMs stream them
        # through the fused dequant-matmul at the linear_apply sites
        compute_dtype, quantize = resolve_params_mode(compute_dtype, quantize)
        prepared = prepare_param_tree(params, compute_dtype, quantize,
                                      group_size)
        if is_quantized(prepared):
            # read the mode off the PREPARED tree: covers pre-quantized
            # input and int4's default grouping in one place, so the AOT
            # fingerprint always names the effective layout
            quantize, group_size = prepared.mode, prepared.group_size
        self.quantize = quantize
        self.group_size = group_size
        self.params = jax.device_put(prepared)

        def prefill_fn(p, ids, pad, length):
            import jax.numpy as jnp

            p = apply_operands(p)  # quantized tree -> QKernel operands
            logits, cache = model.apply(
                {"params": p}, ids, pad, length=length, method="prefill")
            n_cap = logits.shape[1]
            w = ids.shape[1]
            # the next-token logits: window row of the LAST real token
            row = length - 1 - (w - n_cap)
            nxt = jax.lax.dynamic_index_in_dim(
                logits, row, axis=1, keepdims=False)
            return nxt.astype(jnp.float32), cache

        def decode_fn(p, cache, logits_in, temperature, key,
                      n_steps: int, top_k: int, greedy: bool):
            import jax.numpy as jnp

            b = logits_in.shape[0]

            p = apply_operands(p)  # quantized tree -> QKernel operands

            def body(i, carry):
                cache, logits, out = carry
                pos = cache["len"]  # the position being sampled
                k = jax.random.fold_in(key, pos)
                tok = sample_logits(logits, k, temperature, top_k, greedy)
                out = jax.lax.dynamic_update_slice(
                    out, tok[:, None], (jnp.zeros((), jnp.int32), i))
                logits, cache = model.apply(
                    {"params": p}, cache, tok[:, None], method="step")
                return cache, logits.astype(jnp.float32), out

            out0 = jnp.zeros((b, n_steps), jnp.int32)
            cache, logits, out = jax.lax.fori_loop(
                0, n_steps, body, (cache, logits_in, out0))
            return out, logits, cache

        self._prefill = jax.jit(prefill_fn)
        # the cache is DONATED: each chunk's rings feed the next dispatch's
        # buffers (ping-pong on device, nothing round-trips to host).
        # TPU/GPU only — CPU XLA ignores donation with a warning per program
        # (the ServingEngine rule).
        donate = (1,) if jax.default_backend() == "tpu" else ()
        self._decode = jax.jit(
            decode_fn,
            static_argnames=("n_steps", "top_k", "greedy"),
            donate_argnums=donate,
        )
        reg = registry if registry is not None else obs.get_registry()
        labels = {"engine": name, "task": "generate"}
        self._m_sessions = reg.counter(
            "generate_sessions_total",
            "generation sessions started (one prefix encode each)", labels)
        self._m_prefills = reg.counter(
            "generate_prefills_total",
            "prefix encodes (session starts + episode/spill re-encodes)",
            labels)
        self._m_steps = reg.counter(
            "generate_steps_total", "decode steps taken", labels)
        self._m_prefill_s = reg.histogram(
            "generate_prefill_seconds", "wall time of one prefix encode",
            labels)
        self._m_chunk_s = reg.histogram(
            "generate_chunk_seconds",
            "wall time of one chunked decode dispatch", labels)
        # -- per-stream token-level instruments (r21): the TTFT/ITL/goodput
        # surface of production LLM serving, shared by the continuous
        # batcher (same registration, dispatcher-side stamps there)
        self._m_ttft_s = reg.histogram(
            "decode_ttft_seconds",
            "time to first token: stream enqueue -> first token produced "
            "(exemplar-linked to the stream's trace id)", labels)
        self._m_itl_s = reg.histogram(
            "decode_itl_seconds",
            "inter-token latency: gap between consecutive chunks divided "
            "by the tokens the later chunk carries", labels)
        self._m_queue_wait_s = reg.histogram(
            "decode_queue_wait_seconds",
            "stream enqueue -> arena slot bind (admission queue wait; ~0 "
            "on the per-session engine, which never queues)", labels)
        self._m_tokens = {
            o: reg.counter(
                "decode_tokens_total",
                "tokens by lifecycle outcome (goodput = delivered / "
                "generated; wasted_* attributes the gap — see "
                "DECODE_TOKEN_OUTCOMES)", {**labels, "outcome": o})
            for o in DECODE_TOKEN_OUTCOMES}

    def token_stats(self) -> Dict[str, Any]:
        """Goodput accounting snapshot: cumulative ``decode_tokens_total``
        by outcome plus ``goodput = delivered / generated`` (None before
        any token was generated). Shared by both engines — the continuous
        batcher inherits it, and ``stats()`` embeds the same counters."""
        tokens = {o: int(c.value) for o, c in self._m_tokens.items()}
        gen = tokens["generated"]
        return {"tokens": tokens,
                "goodput": (round(tokens["delivered"] / gen, 4)
                            if gen else None)}

    # -- width / episode planning -------------------------------------------

    def plan_width(self, prefix_len: int) -> int:
        """The prefill width (= ring capacity = latent-window END) for a
        ``prefix_len`` prefix: the smallest episode-grid point past the
        prefix.

        A pure function of the prefix length over a FIXED global grid —
        load-bearing for determinism: the latent-window anchor
        ``o = W − capacity`` shapes every downstream logit, so a session
        re-encoded from its prefix at ANY point (affinity spill, episode
        boundary, follow-up call) must anchor exactly where the
        uninterrupted stream did, or the continuation diverges — the
        mid-stream chaos drill pins this by content. Grid spacing
        ``capacity − 1`` keeps every choice inside the window constraint
        ``W <= prefix_len − 1 + capacity`` (see ``PerceiverARLM``)."""
        if prefix_len >= self.max_seq_len:
            raise ValueError(
                f"prefix {prefix_len} leaves no room under max_seq_len "
                f"{self.max_seq_len}")
        for w in self.widths:
            if w > prefix_len:
                return w
        raise AssertionError("unreachable: grid ends at max_seq_len")

    # -- programs ------------------------------------------------------------

    def warmup(self, widths: Optional[Sequence[int]] = None,
               sampling: SamplingConfig = SamplingConfig()) -> int:
        """Compile the prefill family plus the decode programs for the
        given sampling shape — EVERY chunk size 1..chunk (the tail of a
        request and an episode boundary dispatch partial chunks, which are
        their own programs; an unwarmed one is a mid-STREAM compile stall).
        Returns the number of programs readied. Call once per sampling
        shape served (greedy and top-k are distinct programs)."""
        import jax

        sampling = sampling.normalized()
        count = 0
        for w in widths if widths is not None else self.widths:
            ids = np.zeros((1, w), np.int32)
            pad = np.zeros((1, w), bool)
            logits, cache = self._prefill(
                self.params, ids, pad, np.int32(max(1, w - self.capacity + 1)))
            jax.block_until_ready(logits)
            count += 1
            # decode programs are keyed by the CACHE SHAPES too — every
            # width owns its own chunk family, so each must warm per width
            # or the first stream crossing an episode boundary pays a
            # mid-stream compile stall
            for n in range(1, self.chunk + 1):
                out, logits, cache = self._run_decode(
                    cache, logits, sampling, n_steps=n)
                jax.block_until_ready(out)
                count += 1
        obs.event("generate_warmup", engine=self.name, programs=count)
        return count

    def _run_decode(self, cache, logits, sampling: SamplingConfig,
                    n_steps: Optional[int] = None):
        import jax

        greedy = sampling.temperature == 0.0
        key = jax.random.key(sampling.seed)
        return self._decode(
            self.params, cache, logits,
            np.float32(sampling.temperature), key,
            n_steps=self.chunk if n_steps is None else n_steps,
            top_k=sampling.top_k, greedy=greedy,
        )

    # -- the serving surface ---------------------------------------------------

    def start(self, prefix: Sequence[int], seed: int = 0) -> GenSession:
        """Prefix-encode a session (width = :meth:`plan_width`)."""
        prefix = [int(t) for t in prefix]
        p = len(prefix)
        if p < 1:
            raise ValueError("generation needs a non-empty prefix")
        faults.inject("generation.prefill")
        w = self.plan_width(p)
        ids = np.zeros((1, w), np.int32)
        ids[0, :p] = prefix
        pad = np.zeros((1, w), bool)
        pad[0, p:] = True
        t0 = time.monotonic()
        logits, cache = self._prefill(self.params, ids, pad, np.int32(p))
        self._m_prefill_s.observe(time.monotonic() - t0)
        self._m_prefills.inc()
        return GenSession(cache, logits, prefix, w, seed)

    def decode_chunk(self, session: GenSession,
                     sampling: SamplingConfig,
                     n_steps: Optional[int] = None) -> List[int]:
        """Advance one chunked decode dispatch; returns the new tokens (and
        extends ``session.seq`` — the session cache now encodes them)."""
        faults.inject("generation.step")
        n = self.chunk if n_steps is None else n_steps
        if n > session.remaining():
            raise ValueError(
                f"chunk {n} exceeds session ring capacity "
                f"(remaining {session.remaining()})")
        t0 = time.monotonic()
        out, logits, cache = self._run_decode(
            session.cache, session.next_logits,
            dataclasses.replace(sampling, seed=session.seed), n_steps=n)
        tokens = [int(t) for t in np.asarray(out)[0]]
        self._m_chunk_s.observe(time.monotonic() - t0)
        self._m_steps.inc(n)
        self._m_tokens["generated"].inc(n)
        session.cache = cache
        session.next_logits = logits
        session.seq = session.seq + tokens
        session.steps += n
        return tokens

    def generate(
        self,
        prefix: Sequence[int],
        max_new: int,
        sampling: Optional[SamplingConfig] = None,
        on_chunk: Optional[Callable[[List[int], Dict[str, Any]], None]] = None,
        session: Optional[GenSession] = None,
        trace: Optional[obs.TraceContext] = None,
    ) -> Tuple[List[int], GenSession]:
        """Generate up to ``max_new`` tokens after ``prefix``, streaming
        each chunk through ``on_chunk(tokens, info)``. Episodes re-prefill
        from the extended prefix when the latent window fills — the same
        re-encode a spilled session performs, with the position-folded key
        stream keeping the tokens identical either way. ``trace`` (the
        caller's propagated context) attaches one ``decode_stream`` span
        covering the stream's whole life plus a ``decode_chunk`` child per
        dispatch. Returns ``(new_tokens, session)``; pass the session back
        in (with the extended prefix) to continue without a fresh encode."""
        sampling = (sampling or SamplingConfig()).normalized()
        prefix = [int(t) for t in prefix]
        produced: List[int] = []
        if session is not None and (session.seq != prefix
                                    or session.seed != sampling.seed):
            session = None  # resident state diverged: re-encode
        if session is None:
            self._m_sessions.inc()
        t_enter = time.monotonic()
        ctx = trace.child() if trace is not None else None
        exemplar = ctx.trace_id if ctx is not None else None
        t_first: Optional[float] = None
        t_prev = t_enter
        ok = False
        try:
            while len(produced) < max_new:
                cur = prefix + produced
                if len(cur) >= self.max_seq_len:
                    break  # absolute position budget exhausted
                if session is None or session.remaining() < 1:
                    session = self.start(cur, seed=sampling.seed)
                n = min(self.chunk, max_new - len(produced),
                        session.remaining())
                t0 = time.monotonic()
                tokens = self.decode_chunk(session, sampling, n_steps=n)
                now = time.monotonic()
                produced.extend(tokens)
                if tokens:
                    if t_first is None:
                        t_first = now
                        # no admission queue on the per-session engine: the
                        # wait is entry -> first dispatch start (~0), kept
                        # so both engines export the same instrument set
                        self._m_queue_wait_s.observe(t0 - t_enter,
                                                     exemplar=exemplar)
                        self._m_ttft_s.observe(now - t_enter,
                                               exemplar=exemplar)
                    else:
                        self._m_itl_s.observe((now - t_prev) / len(tokens))
                    t_prev = now
                    if ctx is not None:
                        obs.record_span(
                            "decode_chunk", ctx.child(), t0, now - t0,
                            engine=self.name, steps=n,
                            pos=len(session.seq))
                if on_chunk is not None:
                    on_chunk(tokens, {
                        "pos": len(session.seq),
                        "steps": n,
                        "chunk_ms": round((now - t0) * 1e3, 3),
                    })
            ok = True
            self._m_tokens["delivered"].inc(len(produced))
            return produced, session
        finally:
            if not ok:
                # the stream died (engine error or a raising on_chunk
                # consumer): its tokens never reached a completed stream
                self._m_tokens["wasted_killed"].inc(len(produced))
            if ctx is not None:
                obs.record_span(
                    "decode_stream", ctx, t_enter,
                    time.monotonic() - t_enter, engine=self.name,
                    tokens=len(produced), ok=ok,
                    ttft_s=(None if t_first is None
                            else round(t_first - t_enter, 6)))


def load_ar_checkpoint(
    checkpoint_dir: str,
    tokenizer,
    step: Optional[int] = None,
    dtype: Optional[str] = None,
):
    """Rebuild a ``PerceiverARLM`` from the hparams embedded in a
    ``cli/train_ar.py`` checkpoint and restore its best/chosen step.
    Returns ``(model, params, max_seq_len)`` — the shared loading path of
    the serve CLI and the replica process (mirrors
    ``inference.mlm.load_mlm_checkpoint``)."""
    import jax
    from types import SimpleNamespace

    from perceiver_io_tpu.cli import common
    from perceiver_io_tpu.training.checkpoint import (
        load_hparams,
        restore_params,
    )

    hparams = load_hparams(checkpoint_dir)
    defaults = {
        "dtype": "float32", "attn_impl": "auto", "dropout": 0.0,
    }
    args = SimpleNamespace(**{**defaults, **hparams})
    if dtype is not None:
        args.dtype = dtype
    vocab_size = tokenizer.get_vocab_size()
    max_seq_len = hparams["max_seq_len"]
    model = common.build_ar(args, vocab_size, max_seq_len)

    ids = np.zeros((1, max_seq_len), np.int32)
    pad = np.zeros((1, max_seq_len), bool)
    like = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)}, ids, pad)
    )["params"]
    params = restore_params(checkpoint_dir, like, step=step)
    return model, params, max_seq_len


class GenerateSessionStore:
    """Replica-resident generation sessions: bounded, FIFO-evicted, keyed
    like the latent-cache affinity sessions so the router pins them the
    same way. ``match(session, seq)`` returns the resident
    :class:`GenSession` only when its accepted sequence is EXACTLY the
    caller's prefix — anything else (evicted, diverged, restarted replica)
    re-encodes from the prefix, which is the whole spill-on-death story."""

    # pitlint PIT-LOCK: the table is shared between RPC handler threads
    _guarded_by = {"_sessions": "_lock"}

    #: every way a resident session leaves the store — the ``reason`` label
    #: the chaos drills assert on (metrics, not log-scraping)
    RETIRE_REASONS = ("finished", "evicted", "killed")

    def __init__(self, max_sessions: int = 256,
                 registry: Optional[obs.MetricsRegistry] = None,
                 name: str = "replica",
                 on_evict: Optional[Callable[[Any, str], None]] = None):
        self._lock = threading.Lock()
        self._sessions: "OrderedDict[str, GenSession]" = OrderedDict()
        self.max_sessions = max_sessions
        self._on_evict = on_evict
        reg = registry if registry is not None else obs.get_registry()
        self._m_resident = reg.gauge(
            "generate_sessions_resident",
            "generation sessions resident on this replica",
            {"replica": name, "task": "generate"})
        self._m_retired = {
            r: reg.counter(
                "generate_sessions_retired_total",
                "resident generation sessions leaving the store, by reason "
                "(finished = absolute budget exhausted, evicted = FIFO/"
                "overwrite pressure, killed = replica death wiped the table)",
                {"replica": name, "task": "generate", "reason": r})
            for r in self.RETIRE_REASONS}

    def _dropped(self, dropped: List[Any], reason: str) -> None:
        """Account (and fan out) sessions that left the table — called
        OUTSIDE the lock: the eviction callback may take the generation
        engine's own lock (the arena frees the slot behind the session)."""
        for ses in dropped:
            self._m_retired[reason].inc()
            if self._on_evict is not None:
                try:
                    self._on_evict(ses, reason)
                except Exception:
                    pass  # a resource-release hook must never break serving

    def match(self, session_id: Optional[str],
              seq: Sequence[int]) -> Optional[GenSession]:
        if session_id is None:
            return None
        with self._lock:
            ses = self._sessions.get(session_id)
        if ses is None or ses.seq != [int(t) for t in seq]:
            return None
        return ses

    def put(self, session_id: Optional[str],
            session: Optional[GenSession]) -> None:
        if session_id is None or session is None:
            return  # anonymous stream, or a zero-step call that never ran
        dropped = []
        with self._lock:
            old = self._sessions.get(session_id)
            if old is not None and old is not session:
                dropped.append(old)  # overwritten: release its resources
            self._sessions[session_id] = session
            while len(self._sessions) > self.max_sessions:
                dropped.append(self._sessions.popitem(last=False)[1])
            self._m_resident.set(len(self._sessions))
        self._dropped(dropped, "evicted")

    def remove(self, session_id: Optional[str],
               reason: str = "finished") -> bool:
        """Retire one resident session (its continuation hit the absolute
        budget, or the caller is done with it); returns whether it was
        resident."""
        if session_id is None:
            return False
        with self._lock:
            ses = self._sessions.pop(session_id, None)
            self._m_resident.set(len(self._sessions))
        if ses is None:
            return False
        self._dropped([ses], reason)
        return True

    def clear(self, reason: str = "killed") -> None:
        with self._lock:
            dropped = list(self._sessions.values())
            self._sessions.clear()
            self._m_resident.set(0)
        self._dropped(dropped, reason)

    def __len__(self) -> int:
        with self._lock:
            return len(self._sessions)
