"""Persistent AOT executable cache (perceiver_io_tpu.aot): warm starts
deserialize instead of compiling (bit-identical, zero XLA compiles),
fingerprint drift and corrupt entries fall back to a normal compile, shared
cache directories don't race, and background warmup serves traffic before
the full bucket family is warm."""

import os
import threading

import numpy as np
import pytest
import jax
import flax.linen as nn

import perceiver_io_tpu.obs as obs
from perceiver_io_tpu.aot import (
    ExecutableCache,
    callable_sources,
    fingerprint,
    resolve_cache,
)
from perceiver_io_tpu.inference import ServingEngine
from perceiver_io_tpu.obs import install_compile_counter


class _Net(nn.Module):
    width: int = 32

    @nn.compact
    def __call__(self, x):
        return nn.Dense(8)(nn.tanh(nn.Dense(self.width)(x)))


def _setup(width: int = 32):
    model = _Net(width)
    params = model.init(jax.random.key(0), np.ones((1, 16), np.float32))[
        "params"]
    apply_fn = lambda p, x: model.apply({"params": p}, x)
    return apply_fn, params


def _entries(directory):
    return [n for n in os.listdir(directory) if n.endswith(".pitx")]


def test_warm_start_bit_identical_and_zero_compiles(tmp_path):
    """The acceptance drill: with a warm cache, warmup() performs ZERO XLA
    compiles (pinned via the r7 jax_compilations_total counter) and the
    deserialized executables produce BIT-identical outputs to the freshly
    compiled ones on the f32 parity path."""
    cache_dir = str(tmp_path / "cache")
    apply_fn, params = _setup()
    x = np.random.default_rng(0).normal(size=(3, 16)).astype(np.float32)

    with ServingEngine(apply_fn, params, max_batch=8,
                       compile_cache=cache_dir, name="aot_cold") as cold:
        warmed = cold.warmup(np.ones((1, 16), np.float32))
        out_fresh = cold.predict(x)
    assert warmed == [1, 2, 4, 8]
    assert len(_entries(cache_dir)) == len(warmed)

    counter = install_compile_counter()
    before = counter.value
    with ServingEngine(apply_fn, params, max_batch=8,
                       compile_cache=cache_dir, name="aot_warm") as warm:
        assert warm.warmup(np.ones((1, 16), np.float32)) == warmed
        assert counter.value == before, "warm warmup must not compile"
        out_cached = warm.predict(x)
        assert counter.value == before, "warm serving must not compile"
    assert out_fresh.dtype == np.float32
    assert np.array_equal(np.asarray(out_fresh), np.asarray(out_cached))


def test_fingerprint_change_is_a_miss(tmp_path):
    """Any drift in the fingerprinted identity — here the caller salt, the
    hook model/config changes ride on — lands in a DIFFERENT entry: the old
    executable is never served for a new program."""
    cache_dir = str(tmp_path / "cache")
    apply_fn, params = _setup()
    for salt in ("model-v1", "model-v2"):
        with ServingEngine(apply_fn, params, max_batch=2,
                           compile_cache=cache_dir, cache_salt=salt,
                           name=f"aot_{salt}") as eng:
            eng.warmup(np.ones((1, 16), np.float32), buckets=[1])
    assert len(_entries(cache_dir)) == 2  # one per salt: the change missed

    # input-shape drift misses too (same salt, new signature)
    with ServingEngine(apply_fn, params, max_batch=2,
                       compile_cache=cache_dir, cache_salt="model-v1",
                       name="aot_shape") as eng:
        eng.warmup(np.ones((1, 16), np.float32), buckets=[2])
    assert len(_entries(cache_dir)) == 3


def test_corrupt_entry_warns_and_falls_back(tmp_path):
    """A truncated/garbage cache entry must degrade to a fresh compile with
    a warning — never an outage, never a wrong answer."""
    cache_dir = str(tmp_path / "cache")
    apply_fn, params = _setup()
    x = np.random.default_rng(1).normal(size=(2, 16)).astype(np.float32)
    with ServingEngine(apply_fn, params, max_batch=2,
                       compile_cache=cache_dir, name="aot_pre") as eng:
        eng.warmup(np.ones((1, 16), np.float32))
        expect = eng.predict(x)
    paths = _entries(cache_dir)
    assert paths
    for name in paths:
        with open(os.path.join(cache_dir, name), "wb") as f:
            f.write(b"not a serialized executable")

    with pytest.warns(UserWarning, match="corrupt"):
        with ServingEngine(apply_fn, params, max_batch=2,
                           compile_cache=cache_dir, name="aot_post") as eng:
            eng.warmup(np.ones((1, 16), np.float32))
            got = eng.predict(x)
    assert np.array_equal(np.asarray(expect), np.asarray(got))
    # the corrupt entries were replaced by good ones (fresh compile stored)
    with ServingEngine(apply_fn, params, max_batch=2,
                       compile_cache=cache_dir, name="aot_post2") as eng:
        eng.warmup(np.ones((1, 16), np.float32))
        assert np.array_equal(np.asarray(expect), np.asarray(eng.predict(x)))


def test_concurrent_engines_share_one_cache_dir(tmp_path):
    """Two engines warming the same family against one directory — the
    background-warmup-races-the-worker shape, and the multi-replica shape —
    must both finish and serve correctly (atomic writes, claim dedup)."""
    cache_dir = str(tmp_path / "cache")
    apply_fn, params = _setup()
    x = np.random.default_rng(2).normal(size=(2, 16)).astype(np.float32)
    engines = [
        ServingEngine(apply_fn, params, max_batch=4,
                      compile_cache=cache_dir, name=f"aot_cc{i}")
        for i in range(2)
    ]
    errors = []

    def warm(eng):
        try:
            eng.warmup(np.ones((1, 16), np.float32))
        except BaseException as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=warm, args=(e,)) for e in engines]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    expect = np.asarray(apply_fn(params, x))
    for eng in engines:
        np.testing.assert_allclose(np.asarray(eng.predict(x)), expect,
                                   rtol=0, atol=0)
        eng.close()
    assert len(_entries(cache_dir)) == 3  # 4-buckets: 1, 2, 4 — once each


def test_background_warmup_answers_before_family_is_warm(tmp_path):
    """The serve-before-warm claim: with a deliberately large bucket family,
    a request submitted right after warmup(background=True) starts is
    answered while the family is still warming (priority order puts the
    request's small bucket first), and the handle later reports the full
    family + flips engine_ready."""
    cache_dir = str(tmp_path / "cache")
    apply_fn, params = _setup(width=192)  # heavy enough to compile slowly
    x = np.random.default_rng(3).normal(size=(1, 16)).astype(np.float32)
    with ServingEngine(apply_fn, params, max_batch=64,
                       compile_cache=cache_dir, name="aot_bg") as eng:
        handle = eng.warmup(np.ones((1, 16), np.float32), background=True)
        got = eng.submit(x).result(timeout=300)
        family_was_warm = handle.done()
        assert handle.wait(timeout=300) == [1, 2, 4, 8, 16, 32, 64]
        assert eng._m_ready.value == 1.0
    assert np.array_equal(np.asarray(got), np.asarray(apply_fn(params, x)))
    assert not family_was_warm, (
        "first answer should land before the 7-bucket family finishes "
        "warming; if this is flaky the family is too small/fast"
    )


def test_cache_open_fail_soft(tmp_path):
    """An uncreatable cache path (here: nested under a regular file) warns
    and disables caching instead of raising — serving must never be refused
    over a cache problem."""
    blocker = tmp_path / "a_file"
    blocker.write_text("x")
    with pytest.warns(UserWarning, match="unusable"):
        cache = ExecutableCache.open(str(blocker / "cache"))
    assert cache is None
    # an engine handed the bad path serves uncached
    apply_fn, params = _setup()
    with pytest.warns(UserWarning, match="unusable"):
        eng = ServingEngine(apply_fn, params, max_batch=2,
                            compile_cache=str(blocker / "cache"),
                            name="aot_soft")
    try:
        out = eng.predict(np.ones((1, 16), np.float32))
        assert np.asarray(out).shape == (1, 8)
    finally:
        eng.close()


def test_fingerprint_is_stable_and_sensitive():
    """Same inputs → same digest; any component changing → different."""
    apply_fn, params = _setup()
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    base = {"platform": "cpu", "donate": False}
    srcs = callable_sources(apply_fn)
    a = fingerprint(base, avals=avals, extra=srcs)
    assert a == fingerprint(base, avals=avals, extra=srcs)
    assert a != fingerprint({**base, "donate": True}, avals=avals, extra=srcs)
    assert a != fingerprint(base, avals=avals, extra=srcs + ["more"])
    other = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((7, *s.shape), s.dtype), avals)
    assert a != fingerprint(base, avals=other, extra=srcs)
    # closure walk reaches the model hyperparameters through the apply fn
    assert any("_Net" in s for s in srcs)


def test_resolve_cache_passthrough(tmp_path):
    cache = ExecutableCache.open(str(tmp_path / "c"))
    assert resolve_cache(cache) is cache
    assert resolve_cache(None) is None
    opened = resolve_cache(str(tmp_path / "c2"))
    assert isinstance(opened, ExecutableCache)
    assert os.path.isdir(tmp_path / "c2")


def test_store_refused_while_persistent_cache_active(tmp_path, monkeypatch):
    """The two tiers must never both serialize one compile (an executable
    the persistent cache served, serialized again, reloads broken —
    aot/cache.py): with jax's persistent compilation cache active
    in-process, AOT stores are refused with one warning — loads stay
    enabled, serving stays up."""
    from perceiver_io_tpu.aot import cache as cache_mod

    c = ExecutableCache.open(str(tmp_path / "c"))
    monkeypatch.setattr(cache_mod, "persistent_cache_active", lambda: True)
    monkeypatch.setattr(cache_mod, "_DOUBLE_TIER_WARNED", False)
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x + 1).lower(jnp.ones(2)).compile()
    with pytest.warns(UserWarning, match="persistent compilation cache"):
        assert c.store("deadbeef", compiled) is False
    assert c.entries() == []
    # once-only warning: the second refusal is silent
    assert c.store("deadbeef", compiled) is False
