"""chip_smoke.py's phases at tiny widths on CPU, the compile-cache resolver,
and the by-name kernel refusal.

The script itself takes no size option: the phases are functions whose sizes
are arguments, and the device check is one function this file stubs. What
only the chip can show (``tpu_custom_call`` in a compiled program, the
persistent cache answering a warm start) the phases enforce when jax reports
a TPU and merely report here.
"""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import chip_smoke  # noqa: E402

_TINY_MODEL = [
    "--num_latents", "4", "--num_latent_channels", "16",
    "--num_encoder_layers", "1", "--num_self_attention_layers_per_block", "1",
    "--num_cross_attention_heads", "2", "--num_self_attention_heads", "2",
    "--max_seq_len", "32", "--vocab_size", "120", "--batch_size", "16",
    "--synthetic", "--synthetic_size", "64", "--no_tensorboard",
    "--pad_vocab_multiple", "128", "--log_every_n_steps", "1",
    "--learning_rate", "3e-3",
]


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    """Point the script's work directory at a temp dir and stub its device
    check (the test's business, not an option of the script)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(chip_smoke, "WORK", str(tmp_path_factory.mktemp("smoke")))
    mp.setattr(chip_smoke, "HEAD_WIDTH", 128)
    mp.setattr(chip_smoke, "require_tpu", lambda devices, chips: None)
    mp.setattr(chip_smoke, "SERVE_MAX_BATCH", 2)
    chip_smoke.phase_device(chips=8)
    yield chip_smoke.WORK
    mp.undo()


@pytest.fixture(scope="module")
def trained(work):
    return chip_smoke.phase_train(
        flagship_flags=[*_TINY_MODEL, "--dtype", "bfloat16",
                        "--experiment", "flagship", "--max_steps", "12",
                        "--eval_every_n_steps", "6"],
        reference_flags=[*_TINY_MODEL, "--dtype", "bfloat16",
                         "--experiment", "reference", "--max_steps", "10",
                         "--eval_every_n_steps", "10"],
    )


def test_device_check_refuses_a_backend_that_is_not_a_tpu():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.require_tpu(jax.devices(), 1)


def test_phase_kernels_runs_every_given_case(work):
    import kernel_smoke as ks

    line = chip_smoke.phase_kernels({
        "attn": lambda: ks._attention_case(1, 8, 256, 2, 16),
        "attn-causal": lambda: ks._attention_case(
            1, 8, 256, 2, 16, causal_offset=248),
        "ce": lambda: ks._ce_case(64, 16, 203),
        "qmm-int4-grouped": lambda: ks._qmm_case(
            16, 256, 256, bits=4, group_size=128),
        "quant": ks._quant_case,
    })
    assert line["ok"] and line["cases"] == 5
    # interpret mode compiles no kernel; on the chip the same line names them
    assert line["compiled_kernels"] == []
    bad = ks.Case(lambda x: x + 1.0, lambda x: x, (np.ones(4, np.float32),))
    with pytest.raises(AssertionError, match="kernel case off"):
        chip_smoke.phase_kernels({"off": lambda: bad})


def test_phase_train_checks_loss_compiles_and_checkpoint(trained):
    assert trained["ok"] and trained["steps"] == 12
    assert trained["last_loss"] < trained["first_loss"]
    assert trained["built_after_warmup"] == 0
    assert trained["checkpoints"] and os.path.isdir(trained["checkpoint"])


def test_phase_serve_and_warm_start(trained, work):
    texts = ["a [MASK] movie", "the [MASK] was [MASK]",
             "a [MASK] " + " ".join(["good film"] * 12),
             "[MASK] " + " ".join(["bad plot"] * 12)]
    first = chip_smoke.phase_serve(trained["checkpoint"], texts=texts,
                                   bucket_width=16)
    assert set(first) == {"none", "int8", "int4"}
    for mode, out in first.items():
        assert out["widths"] == [16, 32], mode
        assert out["max_rel_err"] <= out["bound"], mode
    line = chip_smoke.phase_warm_start(
        trained["checkpoint"], first["none"], texts=texts, bucket_width=16)
    assert line["backend_compiles"] == 0 and line["bit_identical"]
    # here the persistent cache is off (conftest), so the executable tier
    # gave the warm start; on the chip the phase line says which tier did
    assert line["aot_executable_hits"] > 0


def test_phase_generate_matches_the_per_session_generator(work):
    from perceiver_io_tpu.models.presets import tiny_ar

    line = chip_smoke.phase_generate(
        build_model=tiny_ar, max_seq_len=64, vocab=503, streams=4,
        new_tokens=6)
    assert line["tokens_match"] and line["admitted"] >= 4


def test_phase_multichip_on_virtual_devices(work):
    line = chip_smoke.phase_multichip(
        base_flags=[*_TINY_MODEL, "--dtype", "float32", "--max_steps", "3",
                    "--eval_every_n_steps", "1000"],
        meshes={"dp8_zero3": ["--dp", "8", "--zero3"],
                "dp2_tp2_sp2": ["--dp", "2", "--tp", "2", "--sp", "2",
                                "--shard_seq"]})
    for name in ("dp8_zero3", "dp2_tp2_sp2"):
        assert line[name]["max_loss_diff"] <= line["loss_atol"]
        assert line[name]["collectives"]
        assert (line[name]["argument_bytes_per_device"]
                < line["one_device_argument_bytes"])


def test_compile_log_pairs_a_cache_hit_with_its_compile_event():
    """jax fires backend_compile_duration around the persistent-cache lookup
    too: a program the cache answered is a hit, not an XLA compile — for the
    script's log and for the repo's jax_compilations_total alike."""
    import perceiver_io_tpu.obs as obs

    log = chip_smoke.CompileLog()
    log._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
    log._on_event("/jax/compilation_cache/cache_hits")
    log._on_duration("/jax/core/compile/backend_compile_duration", 0.1)
    log._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
    assert [k for k, _ in log.events] == ["backend", "hit", "backend"]

    counter = obs.install_compile_counter(obs.MetricsRegistry())
    before = counter.value
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.1)
    assert counter.value == before  # the cache answered
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.1)
    assert counter.value == before + 1


def test_device_line_is_the_contracts():
    import json

    line = json.loads(chip_smoke.device_line())
    assert line == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 8}}


def test_compile_cache_resolver_honours_env_else_fixed_checkout_path(
        monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax's own handling stands, no code
    names another directory. Unset: <checkout>/.cache/jax, whatever the
    working directory."""
    from perceiver_io_tpu.aot import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert configure_compile_cache() == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir(tmp_path)
        want = os.path.join(ROOT, ".cache", "jax")
        assert configure_compile_cache() == want
        assert configure_compile_cache() == want  # idempotent
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_quantized_matmul_by_name_raises_where_the_gate_rejects(monkeypatch):
    """impl='pallas' (or PIT_QMM_IMPL=pallas) that the compiled-tiling gate
    rejects raises; only the backend default may give way to the XLA path."""
    import jax.numpy as jnp

    from perceiver_io_tpu.ops import pallas_matmul
    from perceiver_io_tpu.quant.int8 import QKernel, quantize_array

    w = np.random.default_rng(0).normal(0, 0.02, (64, 128)).astype(np.float32)
    q, scale = quantize_array(w, bits=4, group_size=16)  # bk=16: not % 32
    qk = QKernel(jnp.asarray(q, jnp.int4), jnp.asarray(scale), "float32")
    x = jnp.ones((8, 64), jnp.float32)
    # the gate only applies to COMPILED kernels: make this look like a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="asked for by name"):
        pallas_matmul.quantized_matmul(x, qk, impl="pallas")
    monkeypatch.setenv("PIT_QMM_IMPL", "pallas")
    with pytest.raises(ValueError, match="asked for by name"):
        pallas_matmul.quantized_matmul(x, qk)
    monkeypatch.delenv("PIT_QMM_IMPL")
    got = pallas_matmul.quantized_matmul(x, qk)  # the default may choose
    ref = x @ qk.dequantize()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)
