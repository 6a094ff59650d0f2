"""Serving engine: continuous micro-batching, AOT bucket warmup, the
encode/decode latent-cache split, and width-bucketed text serving."""

import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import perceiver_io_tpu as pit
from perceiver_io_tpu.data.tokenizer import (
    MASK_TOKEN,
    PAD_TOKEN,
    UNK_TOKEN,
    WordPieceTokenizer,
)
from perceiver_io_tpu.inference import (
    EngineClosed,
    MLMPredictor,
    MLMServer,
    ServingEngine,
    encode_masked_texts,
)
from perceiver_io_tpu.ops.masking import TextMasking


def _word_tokenizer():
    words = ["movie", "great", "terrible", "watch", "the", "was", "plot",
             "ending", "felt", "slow", "a", "b"]
    vocab = {PAD_TOKEN: 0, UNK_TOKEN: 1, MASK_TOKEN: 2}
    for w in words:
        vocab[w] = len(vocab)
    return WordPieceTokenizer(vocab=vocab)


def _tiny_mlm(vocab_size, max_seq_len=16, c=16):
    return pit.PerceiverMLM(
        encoder=pit.PerceiverEncoder(
            input_adapter=pit.TextInputAdapter(
                vocab_size=vocab_size, max_seq_len=max_seq_len, num_channels=c
            ),
            latent_shape=(4, c),
            num_layers=2,
            num_self_attention_layers_per_block=1,
            num_cross_attention_heads=2,
            num_self_attention_heads=2,
        ),
        decoder=pit.PerceiverDecoder(
            output_adapter=pit.TextOutputAdapter(
                vocab_size=vocab_size, max_seq_len=max_seq_len,
                num_output_channels=c,
            ),
            latent_shape=(4, c),
            num_cross_attention_heads=2,
        ),
        masking=TextMasking(vocab_size, 1, 2, 3),
    )


def _init_mlm(model, max_seq_len=16):
    ids = np.zeros((1, max_seq_len), np.int32)
    return model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        jnp.asarray(ids), jnp.asarray(ids == 1),
    )["params"]


# -- encode/decode split (model core) ----------------------------------------


def test_encode_decode_split_parity():
    """decode(encode(x)) must equal the fused forward at f32/2e-5 — full
    decode AND the positions= gathered decode (the latent-cache serving
    path is exactly the fused computation, split)."""
    tok = _word_tokenizer()
    model = _tiny_mlm(tok.get_vocab_size())
    ids, pad = encode_masked_texts(
        tok, ["the movie was [MASK]", "a [MASK] plot and a [MASK] ending"], 16
    )
    params = _init_mlm(model)

    fused, _ = model.apply(
        {"params": params}, ids, pad, masking=False, deterministic=True
    )
    latents = model.apply({"params": params}, ids, pad, method="encode")
    split = model.apply({"params": params}, latents, method="decode")
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(split)[:, : ids.shape[1], :], atol=2e-5
    )

    positions = np.asarray([[3, 0], [1, 7]], np.int32)
    fused_pos, _ = model.apply(
        {"params": params}, ids, pad, masking=False, deterministic=True,
        positions=positions,
    )
    split_pos = model.apply(
        {"params": params}, latents, positions=positions, method="decode"
    )
    np.testing.assert_allclose(
        np.asarray(fused_pos), np.asarray(split_pos), atol=2e-5
    )


def test_perceiver_io_encode_decode_split(rng):
    """The generic PerceiverIO core exposes the same split."""
    enc = pit.PerceiverEncoder(
        input_adapter=pit.ImageInputAdapter(
            image_shape=(6, 6, 1), num_frequency_bands=3
        ),
        latent_shape=(4, 16), num_layers=1,
        num_self_attention_layers_per_block=1,
        num_cross_attention_heads=2, num_self_attention_heads=2,
    )
    dec = pit.PerceiverDecoder(
        output_adapter=pit.ClassificationOutputAdapter(
            num_classes=3, num_output_channels=16
        ),
        latent_shape=(4, 16), num_cross_attention_heads=2,
    )
    model = pit.PerceiverIO(encoder=enc, decoder=dec)
    x = jnp.asarray(rng.normal(0, 1, (3, 6, 6, 1)), jnp.float32)
    params = model.init({"params": jax.random.key(0)}, x)["params"]
    fused = model.apply({"params": params}, x)
    latents = model.apply({"params": params}, x, method="encode")
    split = model.apply({"params": params}, latents, method="decode")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(split), atol=2e-5)


# -- ServingEngine core ------------------------------------------------------


def test_engine_bucket_warmup_compiles_once():
    """warmup() compiles one program per power-of-two bucket, and the serving
    stream then NEVER compiles: the traced-call counter (jax traces exactly
    once per compilation) stays at the warmup count across mixed batch
    sizes, padded buckets, and an oversized chunked request.

    The steady phase runs under BOTH runtime sanitizers (analysis/): the
    XLA-level ``no_recompile()`` (the trace counter alone cannot see a
    constant-folding recompile of an unchanged trace) and the armed
    device→host transfer guard (a silent host fetch on the dispatch or
    completion path is a per-batch host sync in
    production)."""
    from perceiver_io_tpu.analysis import no_implicit_transfers, no_recompile

    traces = [0]

    def apply_fn(p, x):
        traces[0] += 1
        return x * p + 1.0

    with ServingEngine(
        apply_fn, jnp.float32(2.0), max_batch=8, name="warm"
    ) as eng:
        warmed = eng.warmup(np.zeros((1, 3), np.float32))
        assert warmed == [1, 2, 4, 8]
        assert traces[0] == 4
        assert eng.num_programs == 4

        sizes = (1, 2, 3, 5, 8, 19)  # 19 chunks into 8+8+4(padded)
        with no_recompile(), no_implicit_transfers():
            futures = [
                eng.submit(np.full((n, 3), float(n), np.float32))
                for n in sizes
            ]
            for n, fut in zip(sizes, futures):
                out = fut.result(timeout=60)
                assert out.shape == (n, 3)
                np.testing.assert_allclose(out, n * 2.0 + 1.0)
        assert traces[0] == 4, "steady-state serving must not compile"


def test_engine_queue_drain_mixed_sizes_and_signatures():
    """Mixed batch sizes, two input signatures (widths), an oversized
    request, and an empty request all drain correctly under one engine —
    every request's rows come back exactly (row i carries value i)."""

    def apply_fn(p, x):
        return x + p

    with ServingEngine(apply_fn, jnp.float32(0.5), max_batch=4) as eng:
        cases = []
        for i, (n, width) in enumerate(
            [(1, 3), (4, 5), (2, 3), (11, 5), (3, 3), (0, 3)]
        ):
            x = np.full((n, width), float(i), np.float32)
            x += np.arange(n, dtype=np.float32)[:, None] if n else 0
            cases.append((x, eng.submit(x)))
        for x, fut in cases:
            out = fut.result(timeout=60)
            assert out.shape == x.shape
            np.testing.assert_allclose(out, x + 0.5)
        stats = eng.stats()
        assert stats["requests"] == len(cases) - 1  # empty skips the queue
        assert stats["rows"] == sum(len(x) for x, _ in cases)


def test_engine_concurrent_submitters():
    """Requests submitted from many threads (the serving situation) coalesce
    into micro-batches and every caller gets its own rows back."""

    def apply_fn(p, x):
        return x * p

    results = {}
    with ServingEngine(apply_fn, jnp.float32(3.0), max_batch=16) as eng:
        eng.warmup(np.zeros((1, 2), np.float32))

        def client(i):
            x = np.full((1 + i % 3, 2), float(i), np.float32)
            results[i] = (x, eng.submit(x).result(timeout=60))

        threads = [threading.Thread(target=client, args=(i,)) for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i, (x, out) in results.items():
        np.testing.assert_allclose(out, x * 3.0, err_msg=str(i))


def test_engine_error_propagates_and_engine_survives():
    """A request whose shapes break the program fails ITS future; the engine
    keeps serving later requests."""

    def apply_fn(p, x):
        return x @ p  # (n, 3) @ (3,) — a (n, 2) input cannot trace

    with ServingEngine(
        apply_fn, jnp.arange(3, dtype=jnp.float32), max_batch=4
    ) as eng:
        bad = eng.submit(np.ones((2, 2), np.float32))
        with pytest.raises(Exception):
            bad.result(timeout=60)
        good = eng.submit(np.ones((2, 3), np.float32))
        np.testing.assert_allclose(good.result(timeout=60), [3.0, 3.0])

    with pytest.raises(EngineClosed):
        eng.submit(np.ones((1, 3), np.float32))


def test_engine_update_params_requantize_queues_not_races():
    """Hot-swapping params on a QUANTIZED engine while submitters hammer it:
    requests that arrive mid-(re)quantization queue and are served with a
    COMPLETE tree — every result is consistent with exactly one installed
    param set (k * row-sum), never a torn mix of old int8 values with new
    scales. (The quantize-at-load error-isolation satellite.)

    Weights are k * ones(3, 3): per-channel symmetric int8 represents them
    EXACTLY (w/scale = ±127 on the grid), so any tearing shows up as a
    result outside the integer-k set, not as quantization noise."""

    def apply_fn(p, x):
        return x @ p["lin"]["kernel"]

    def params_for(k):
        return {"lin": {"kernel": np.full((3, 3), float(k), np.float32)}}

    ks = (1, 2, 3, 4, 5)
    stop = threading.Event()
    errors = []
    completed = [0] * 4  # per-client served-request counters (int writes
    #                      under the GIL; read by the pacing loop below)

    with ServingEngine(
        apply_fn, params_for(ks[0]), max_batch=8, quantize="int8"
    ) as eng:
        eng.warmup(np.zeros((1, 3), np.float32))

        def client(i):
            rng = np.random.default_rng(i)
            while not stop.is_set():
                x = rng.normal(0, 1, (2, 3)).astype(np.float32)
                out = np.asarray(eng.submit(x).result(timeout=60))
                completed[i] += 1
                row_sum = x.sum(axis=1)
                # out[r, c] must equal k * row_sum[r] for ONE k across the
                # whole result (a torn tree would mix ratios). Rows with a
                # small |row_sum| are excluded generously: the division
                # amplifies f32 summation-order noise, and a torn tree is a
                # WHOLE-COLUMN integer-ratio flip, not a 1e-3 wiggle.
                ratios = out / np.where(
                    np.abs(row_sum[:, None]) < 1e-1, np.nan, row_sum[:, None]
                )
                ratios = ratios[np.isfinite(ratios)]
                if ratios.size == 0:
                    continue
                k = np.round(np.median(ratios))
                if k not in ks or not np.allclose(
                    ratios, k, rtol=1e-3, atol=1e-3
                ):
                    errors.append((k, ratios.min(), ratios.max()))

        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()

        def wait_served(min_total, deadline_s=30.0):
            # pace the drill so dispatches GENUINELY overlap the staging/
            # install window — an instantaneous update burst would barely
            # exercise the queue-not-race property
            deadline = time.monotonic() + deadline_s
            while sum(completed) < min_total and time.monotonic() < deadline:
                time.sleep(0.005)

        wait_served(4)  # every client is in its serving loop
        # re-quantize repeatedly while the submitters run: preparation on
        # this (caller) thread, atomic install on the worker thread, with
        # requests flowing between consecutive swaps
        served = sum(completed)
        for _ in range(3):
            for k in ks:
                eng.update_params(params_for(k))
                served += 2
                wait_served(served)
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors[:5]
        assert sum(completed) >= served, "drill ended before overlap happened"

        # the LAST staged tree wins once the queue drains
        x = np.ones((1, 3), np.float32)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            out = np.asarray(eng.submit(x).result(timeout=60))
            if np.allclose(out, 3.0 * ks[-1]):
                break
            time.sleep(0.01)
        np.testing.assert_allclose(out, 3.0 * ks[-1], rtol=1e-5)

    with pytest.raises(EngineClosed):
        eng.update_params(params_for(1))


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_engine_worker_crash_closes_engine_with_cause():
    """A worker crash must leave the engine CLOSED, not half-dead: post-crash
    submits raise EngineClosed immediately (never enqueue into a dead queue
    and hang toward a timeout), with the crash cause chained as __cause__."""

    def apply_fn(p, x):
        return x + p

    eng = ServingEngine(apply_fn, jnp.float32(1.0), max_batch=4, name="crash_t")
    try:
        boom = RuntimeError("worker exploded")

        def bad_next_batch(timeout):
            raise boom

        eng._next_batch = bad_next_batch  # crash OUTSIDE the per-batch guard
        eng._thread.join(timeout=30)
        assert not eng._thread.is_alive()

        t0 = time.monotonic()
        with pytest.raises(EngineClosed, match="crashed") as excinfo:
            eng.submit(np.ones((1, 3), np.float32))
        assert time.monotonic() - t0 < 5, "must fast-fail, not hang"
        assert excinfo.value.__cause__ is boom

        with pytest.raises(EngineClosed, match="crashed"):
            eng.update_params(jnp.float32(2.0))
    finally:
        eng.close()


def test_engine_bf16_compute_dtype():
    """compute_dtype='bfloat16' casts floating params/inputs once (the bf16
    serving path); results track f32 at bf16 tolerance."""

    def apply_fn(p, x):
        return x @ p

    p32 = jnp.asarray(np.linspace(0, 1, 12, dtype=np.float32).reshape(3, 4))
    x = np.linspace(-1, 1, 6, dtype=np.float32).reshape(2, 3)
    want = x @ np.asarray(p32)
    with ServingEngine(
        apply_fn, p32, max_batch=4, compute_dtype="bfloat16"
    ) as eng:
        assert eng.params.dtype == jnp.bfloat16
        out = eng.predict(x, timeout=60)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), want, rtol=2e-2, atol=2e-2
        )


# -- MLMServer: width buckets + latent cache ---------------------------------


@pytest.fixture(scope="module")
def mlm_setup():
    tok = _word_tokenizer()
    model = _tiny_mlm(tok.get_vocab_size())
    params = _init_mlm(model)
    return tok, model, params


TEXTS = [
    "the movie was [MASK]",                                   # short
    "a [MASK] plot and a [MASK] ending",                      # two masks
    "no mask here",                                           # no mask
    "the movie was great the plot felt slow the [MASK] was",  # long
]


def test_mlm_server_width_bucketed_roundtrip(mlm_setup):
    """Variable-length texts round-trip through the tokenizer into width
    buckets, and fill-mask results exactly match the (max-width)
    MLMPredictor path — width bucketing changes the shapes, not the math."""
    tok, model, params = mlm_setup
    want = MLMPredictor(
        model, params, tok, max_seq_len=16, max_batch=4
    ).fill_masks(TEXTS, k=3)

    with MLMServer(
        model, params, tok, max_seq_len=16, bucket_widths=[8], max_batch=4
    ) as server:
        # a constrained family (tier-1 budget, r10): full-default-family
        # warmup cost is exercised by test_engine_bucket_warmup_compiles_once
        # and the r10 warm-cache tests below; here warmup only needs to exist
        # so the steady-state no-new-programs assertion has a baseline
        warmed = server.warmup(batch_buckets=[1], query_buckets=(1, 2))
        assert warmed > 0
        got = server.fill_masks(TEXTS, k=3)
        assert got == want
        # the short texts really were served at the 8-wide bucket: the fused
        # engine saw an 8-wide program signature
        widths_seen = {
            key[0][0][0] for key, _ in server.engine._programs
        }
        assert 8 in widths_seen, widths_seen

        # steady state after warmup: repeat requests add no programs
        programs = server.engine.num_programs
        assert server.fill_masks(TEXTS, k=3) == want
        assert server.engine.num_programs == programs


def test_mlm_server_latent_cache_decode_many(mlm_setup):
    """Encode once, decode many: fill_masks_cached matches the fused path,
    and explicit-position decode matches the model's gathered decode — with
    ZERO additional encoder work after encode()."""
    tok, model, params = mlm_setup
    with MLMServer(
        model, params, tok, max_seq_len=16, bucket_widths=[8], max_batch=4
    ) as server:
        want = server.fill_masks(TEXTS, k=3)
        cached = server.encode(TEXTS)
        assert cached.latents.shape[0] == len(TEXTS)
        encoder_batches = server.encoder.stats()["batches"]

        assert server.fill_masks_cached(cached, k=3) == want
        # decode-many against the same latents: 3 more decode rounds
        positions = np.tile(np.arange(4, dtype=np.int32), (len(TEXTS), 1))
        logits = server.decode(cached, positions)
        assert logits.shape[:2] == (len(TEXTS), 4)
        for shift in (1, 2):
            more = server.decode(cached, (positions + shift) % 8)
            assert more.shape == logits.shape
        assert server.encoder.stats()["batches"] == encoder_batches, (
            "decode-many must not re-run the encoder"
        )

        # the decoded logits are the fused forward's rows (full parity chain:
        # fused == encode+decode at these positions)
        row = 1
        width = len(cached.token_ids[row])
        ids = cached.token_ids[row][None]
        fused, _ = model.apply(
            {"params": params}, ids, ids == tok.token_to_id(PAD_TOKEN),
            masking=False, deterministic=True,
            positions=positions[row: row + 1],
        )
        np.testing.assert_allclose(
            logits[row], np.asarray(fused)[0], atol=2e-5
        )


def test_engine_stats_snapshot_is_locked_and_deep():
    """stats() is a consistent deep copy: mutating the snapshot (or its
    latency lists) never touches live engine state, and concurrent submitters
    hammering the counters while snapshots are taken leave the final tallies
    exact (the r6 thread-safety hole: requests was bumped on caller threads
    while the worker wrote rows/batches, unlocked)."""

    def apply_fn(p, x):
        return x + p

    with ServingEngine(apply_fn, jnp.float32(1.0), max_batch=4) as eng:
        fut = eng.submit(np.zeros((2, 3), np.float32))
        fut.result(timeout=60)
        snap = eng.stats()
        snap["requests"] = 10**9
        snap["latency_s_by_bucket"].setdefault(2, []).append(123.0)
        fresh = eng.stats()
        assert fresh["requests"] == 1
        assert 123.0 not in fresh["latency_s_by_bucket"].get(2, [])

        # hammer: 8 threads x 25 requests, snapshots interleaved throughout
        def client(_):
            for _ in range(25):
                eng.submit(np.zeros((1, 3), np.float32)).result(timeout=60)
                eng.stats()

        threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        final = eng.stats()
        assert final["requests"] == 1 + 8 * 25
        assert final["rows"] == 2 + 8 * 25


def test_mlm_server_stats_shim_shape(mlm_setup):
    """MLMServer.stats() keeps the r6 shape (fused/encode/decode/programs)
    over the registry-backed engines, stays JSON-serializable (the serve CLI
    --stats path), and deep-copies."""
    import json as _json

    tok, model, params = mlm_setup
    with MLMServer(model, params, tok, max_seq_len=16, max_batch=4) as server:
        server.fill_masks(["the movie was [MASK]"], k=2)
        stats = server.stats()
        assert set(stats) == {"fused", "encode", "decode", "programs"}
        assert stats["fused"]["requests"] == 1
        _json.dumps(stats)  # deques would raise here
        for lats in stats["fused"]["latency_s_by_bucket"].values():
            lats.append(999.0)
        assert all(
            999.0 not in v
            for v in server.stats()["fused"]["latency_s_by_bucket"].values()
        )


def test_engine_publishes_registry_instruments():
    """The engine's registry instruments carry the serving telemetry: request
    /row/batch counters, padding waste, occupancy + latency histograms, and
    compile events that stay flat in steady state (the recompile detector)."""
    from perceiver_io_tpu import obs

    reg = obs.MetricsRegistry()

    def apply_fn(p, x):
        return x * p

    with ServingEngine(
        apply_fn, jnp.float32(2.0), max_batch=4, name="obs_t", registry=reg
    ) as eng:
        eng.warmup(np.zeros((1, 2), np.float32))
        compiles_after_warmup = reg.counter(
            "serving_compile_events_total", labels={"engine": "obs_t"}
        ).value
        assert compiles_after_warmup == 3  # buckets 1, 2, 4
        for n in (1, 3, 4):
            eng.submit(np.zeros((n, 2), np.float32)).result(timeout=60)
        snap = reg.snapshot()
        assert snap["counters"]['serving_requests_total{engine="obs_t"}'] == 3
        assert snap["counters"]['serving_rows_total{engine="obs_t"}'] == 8
        # 3 requests → 3 buckets (1, 4, 4): the 3-row one padded by 1
        assert snap["counters"]['serving_padded_rows_total{engine="obs_t"}'] >= 1
        assert reg.counter(
            "serving_compile_events_total", labels={"engine": "obs_t"}
        ).value == compiles_after_warmup, "steady state must not compile"
        lat = reg.histogram(
            "serving_latency_seconds",
            labels={"engine": "obs_t", "bucket": "4"},
        )
        assert lat.count >= 1
        text = reg.prometheus_text()
        assert '# TYPE serving_requests_total counter' in text
        assert 'serving_requests_total{engine="obs_t"} 3' in text


def test_mlm_server_oversized_and_empty(mlm_setup):
    """A request stream larger than max_batch chunks transparently; a
    no-mask text completes without touching the device."""
    tok, model, params = mlm_setup
    texts = ["the movie was [MASK]"] * 9 + ["no mask here"]
    with MLMServer(model, params, tok, max_seq_len=16, max_batch=4) as server:
        got = server.fill_masks(texts, k=2)
    assert got[-1] == []
    assert all(g == got[0] for g in got[:9])


# -- MLMServer: zero-recompile cold start + background warmup (r10) ----------


def test_mlm_server_warm_cache_zero_compiles(mlm_setup, tmp_path):
    """Server-level acceptance: a second MLMServer over a populated compile
    cache warms its ENTIRE (width, batch, K) program family across all three
    engines with ZERO XLA compiles (jax_compilations_total flat), and serves
    fills identical to the freshly-compiled server."""
    from perceiver_io_tpu.obs import install_compile_counter

    tok, model, params = mlm_setup
    cache_dir = str(tmp_path / "cache")
    kwargs = dict(max_seq_len=16, max_batch=1, compile_cache=cache_dir)
    with MLMServer(model, params, tok, **kwargs) as cold:
        n_cold = cold.warmup(query_buckets=(1, 2))
        fresh = cold.fill_masks(TEXTS, k=2)
        cached_lat = cold.encode(TEXTS[:2])
        fresh_cached = cold.fill_masks_cached(cached_lat, k=2)

    counter = install_compile_counter()
    before = counter.value
    with MLMServer(model, params, tok, **kwargs) as warm:
        assert warm.warmup(query_buckets=(1, 2)) == n_cold
        assert counter.value == before, "warm warmup must not compile"
        got = warm.fill_masks(TEXTS, k=2)
        lat = warm.encode(TEXTS[:2])
        got_cached = warm.fill_masks_cached(lat, k=2)
        assert counter.value == before, "warm serving must not compile"
    assert got == fresh
    assert got_cached == fresh_cached


def test_mlm_server_background_warmup_serves_immediately(mlm_setup, tmp_path):
    """warmup(background=True) returns a handle at once; fills submitted
    right away are answered (on-demand builds dedup against the warmup
    threads), and the handle reports the same program count as blocking
    mode. update_params mid-warm composes (r8 semantics preserved)."""
    tok, model, params = mlm_setup
    cache_dir = str(tmp_path / "cache")
    with MLMServer(model, params, tok, max_seq_len=16, max_batch=1,
                   compile_cache=cache_dir) as server:
        handle = server.warmup(query_buckets=(1, 2), background=True)
        got = server.fill_masks(TEXTS, k=2)  # while (possibly) still warming
        server.update_params(params)  # hot-swap composes with warmup
        n = handle.wait(timeout=300)
    # the blocking-mode reference rides the now-warm cache (cheap) — same
    # results, same program count
    with MLMServer(model, params, tok, max_seq_len=16, max_batch=1,
                   compile_cache=cache_dir) as ref:
        expect = ref.fill_masks(TEXTS, k=2)
        n_blocking = ref.warmup(query_buckets=(1, 2))
    assert got == expect
    assert n == n_blocking
