"""End-to-end CLI tests on tiny synthetic configs (reference train/ entry
points, SURVEY.md §4d integration tier)."""

import json
import os

import numpy as np
import pytest

from perceiver_io_tpu.cli import train_img_clf, train_mlm, train_seq_clf
from perceiver_io_tpu.training import read_metrics

TINY_MODEL = [
    "--num_latents", "8", "--num_latent_channels", "16",
    "--num_encoder_layers", "2", "--num_self_attention_layers_per_block", "1",
    "--num_cross_attention_heads", "2", "--num_self_attention_heads", "2",
    "--dtype", "float32",
]


def _common(tmp_path, name):
    return [
        "--synthetic", "--logdir", str(tmp_path / "logs" / name),
        "--root", str(tmp_path / "cache"),
    ]


@pytest.mark.slow  # tier-1 budget (r10): the image-classifier CLI e2e stays
# tier-1 via test_train_imagenet (imagefolder task); MNIST data/adapters in
# tests/test_data.py and tests/test_adapters.py
def test_train_img_clf(tmp_path):
    run_dir = train_img_clf.main(
        _common(tmp_path, "img") + TINY_MODEL + [
            "--synthetic_size", "128", "--batch_size", "16",
            "--max_epochs", "1", "--log_every_n_steps", "2",
        ]
    )
    rows = read_metrics(run_dir)
    assert any("train_loss" in r for r in rows)
    assert any("val_loss" in r for r in rows)
    assert os.path.isdir(os.path.join(run_dir, "checkpoints"))


@pytest.mark.slow  # tier-1 budget (r19): hybrid ICI×DCN coverage stays
# tier-1 in test_sharding.py (layout, validation, and
# test_hybrid_dcn_mesh_matches_single_device numeric parity) and in the
# 2-real-process granule check of test_multihost.py — this is the 20s
# end-to-end CLI variant
def test_train_mlm_hybrid_dcn_mesh(tmp_path):
    """--dcn_dp 2 --tp 2 trains end to end on the 8-device CPU mesh (the
    hybrid ICI×DCN layout is placement-only — the run must behave exactly
    like the flat mesh)."""
    run_dir = train_mlm.main(
        _common(tmp_path, "mlmdcn") + TINY_MODEL + [
            "--synthetic_size", "64", "--batch_size", "16",
            "--max_seq_len", "32", "--vocab_size", "90",
            "--max_steps", "3", "--log_every_n_steps", "1",
            "--tp", "2", "--dcn_dp", "2",
        ]
    )
    rows = read_metrics(run_dir)
    losses = [r["train_loss"] for r in rows if "train_loss" in r]
    assert losses and np.isfinite(losses).all()


@pytest.mark.slow  # tier-1 budget (r10): fused-head numerics stay tier-1 in
# tests/test_pallas_ce.py::TestMLMFusedHeadPallas::test_train_step_matches_unfused; flag
# parsing in test_all_parsers_build_and_render_help
def test_train_mlm_fused_head_flag(tmp_path):
    """--fused_head pallas trains end to end (interpret mode off-TPU) and
    --fused_head pallas under --tp vocab sharding is rejected with the
    single-device-head explanation."""
    args = _common(tmp_path, "mlmfh") + TINY_MODEL + [
        "--synthetic_size", "64", "--batch_size", "16",
        "--max_seq_len", "32", "--vocab_size", "90",
        "--max_steps", "2", "--log_every_n_steps", "1",
        "--fused_head", "pallas",
    ]
    run_dir = train_mlm.main(args)
    rows = read_metrics(run_dir)
    assert any("train_loss" in r for r in rows)

    with pytest.raises(SystemExit, match="single-device head"):
        train_mlm.main(args + ["--tp", "2"])


@pytest.mark.slow  # encoder-transfer restore semantics stay tier-1 in
# tests/test_checkpoint.py::test_encoder_transfer; this is the CLI ride
def test_train_mlm_then_transfer(tmp_path):
    mlm_args = _common(tmp_path, "mlm") + TINY_MODEL + [
        "--synthetic_size", "96", "--batch_size", "16",
        "--max_seq_len", "64", "--vocab_size", "150",
        "--max_steps", "4", "--log_every_n_steps", "2",
        "--num_predictions", "3",
    ]
    run_dir = train_mlm.main(mlm_args)
    rows = read_metrics(run_dir)
    assert any("train_loss" in r for r in rows)
    # masked-sample predictions were logged as text
    assert any(r.get("tag") == "predictions" for r in rows)
    ckpt_dir = os.path.join(run_dir, "checkpoints")
    with open(os.path.join(ckpt_dir, "hparams.json")) as f:
        hparams = json.load(f)
    assert hparams["num_latents"] == 8

    # transfer: bigger model args on the CLI must be overridden by the
    # checkpoint's hparams so the restored encoder fits
    clf_run = train_seq_clf.main(
        _common(tmp_path, "clf") + [
            "--num_latents", "32",  # overridden from hparams
            "--dtype", "float32",
            "--synthetic_size", "96", "--batch_size", "16",
            "--max_seq_len", "64", "--vocab_size", "150",
            "--max_steps", "3", "--log_every_n_steps", "1",
            "--mlm_checkpoint", ckpt_dir, "--freeze_encoder",
        ]
    )
    rows = read_metrics(clf_run)
    assert any("val_acc" in r for r in rows)

    # resume path
    resumed = train_seq_clf.main(
        _common(tmp_path, "clf") + [
            "--dtype", "float32",
            "--synthetic_size", "96", "--batch_size", "16",
            "--max_seq_len", "64", "--vocab_size", "150",
            "--max_steps", "5", "--log_every_n_steps", "1",
            "--clf_checkpoint", os.path.join(clf_run, "checkpoints"),
        ]
    )
    rows = read_metrics(resumed)
    # resumed at step 3, trained to 5
    assert max(r["step"] for r in rows) == 5


@pytest.mark.slow  # tier-1 budget (r21): the serve CLI pipeline stays
# tier-1 via test_serve_metrics_sidecar_end_to_end (same train+serve path
# plus the sidecar); engine fused==cached parity stays in
# tests/test_engine.py::test_mlm_server_latent_cache_decode_many
def test_serve_cli_end_to_end(tmp_path):
    """Train a tiny MLM, then serve it through the micro-batching engine CLI:
    fused, latent-cache, and bf16 paths all answer, fused == cached, and the
    JSON-line results carry per-[MASK] top-k token lists."""
    import glob

    from perceiver_io_tpu.cli import serve

    run_dir = train_mlm.main(
        _common(tmp_path, "servemlm") + [
            "--num_latents", "4", "--num_latent_channels", "16",
            "--num_encoder_layers", "1",
            "--num_self_attention_layers_per_block", "1",
            "--num_cross_attention_heads", "2",
            "--num_self_attention_heads", "2", "--dtype", "float32",
            "--synthetic_size", "64", "--batch_size", "16",
            "--max_seq_len", "32", "--vocab_size", "120",
            "--max_steps", "2", "--log_every_n_steps", "1",
            "--num_predictions", "2",
        ]
    )
    ckpt = os.path.join(run_dir, "checkpoints")
    tok = glob.glob(str(tmp_path / "cache" / "*tokenizer*.json"))[0]
    base = ["--checkpoint", ckpt, "--tokenizer", tok, "--max_batch", "4",
            "--k", "3"]

    # the resilience AND SLO flags ride the happy path too: generous
    # deadline/queue bound, an armed breaker, and a declared SLO must not
    # perturb results
    fused = serve.main(
        base + ["--bucket_widths", "16",
                "--request_deadline_s", "60", "--queue_limit", "256",
                "--breaker_failures", "3", "--breaker_cooldown_s", "1",
                "--slo_p99_ms", "60000", "--slo_availability", "0.99",
                "--texts", "a [MASK] b", "no mask here"]
    )
    assert len(fused) == 2
    assert len(fused[0]["fills"]) == 1 and len(fused[0]["fills"][0]) == 3
    assert fused[1]["fills"] == []

    cached = serve.main(
        base + ["--cached", "--no_warmup", "--texts", "a [MASK] b"]
    )
    assert cached[0]["fills"] == fused[0]["fills"]

    bf16 = serve.main(
        base + ["--dtype", "bfloat16", "--no_warmup",
                "--texts", "a [MASK] b"]
    )
    assert len(bf16[0]["fills"][0]) == 3  # bf16 rounds: presence, not parity

    # weight-only int8 at f32 compute: on this tiny model the top-k picks
    # match the f32 path (quantization error ≪ the logit gaps)
    int8w = serve.main(
        base + ["--quantize", "int8", "--no_warmup",
                "--texts", "a [MASK] b"]
    )
    assert int8w[0]["fills"] == fused[0]["fills"]

    # zero-recompile cold start: --compile_cache serves identical fills and
    # persists the on-demand programs as .pitx entries (the zero-compile
    # warm-family assertion lives in test_engine.py / test_aot_cache.py;
    # --no_warmup keeps this run inside the tier-1 budget)
    cache_dir = tmp_path / "ccache"
    cached_serve = serve.main(
        base + ["--compile_cache", str(cache_dir), "--no_warmup",
                "--texts", "a [MASK] b"]
    )
    assert cached_serve[0]["fills"] == fused[0]["fills"]
    assert any(f.endswith(".pitx") for f in os.listdir(cache_dir))

    # fail-soft (satellite): a cache path that cannot exist (nested under a
    # regular file) must WARN and serve uncached — never refuse traffic
    blocker = tmp_path / "a_file"
    blocker.write_text("x")
    with pytest.warns(UserWarning, match="unusable"):
        soft = serve.main(
            base + ["--compile_cache", str(blocker / "cache"), "--no_warmup",
                    "--texts", "a [MASK] b"]
        )
    assert soft[0]["fills"] == fused[0]["fills"]

    with pytest.raises(SystemExit, match="nothing to serve"):
        serve.main(base)


@pytest.mark.slow  # tier-1 budget (r21): the one-JSON-line bench-CLI
# contract stays tier-1 via test_coldstart_bench_cpu_emits_one_json_line
# and the load_bench --dry/--cpu contract tests; the engine A/B itself is
# a tools-only path with no serving-side coverage gap
def test_inference_bench_engine_cpu_emits_one_json_line(tmp_path):
    """tools/inference_bench.py --engine --cpu runs the full serving-engine
    A/B offline and emits EXACTLY one JSON line on stdout (the driver's
    inference-trajectory contract)."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "inference_bench.py"),
         "--engine", "--cpu", "--preset", "tiny",
         "--requests", "8", "--rounds", "1"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    result = json.loads(lines[0])
    assert result["mode"] == "engine" and result["backend"] == "cpu"
    for key in ("naive_requests_per_s", "engine_requests_per_s", "speedup",
                "engine_tokens_per_s"):
        assert key in result, result
    assert any(k.startswith("bucket") and k.endswith("p50_ms")
               for k in result), result


@pytest.mark.slow  # tier-1 budget (r22 box drift): the serve CLI
# contract stays tier-1 in test_serve_cli_end_to_end; the metrics
# registry/exporters are unit-covered in tests/test_obs.py. This drill
# adds only the sidecar-process layer.
def test_serve_metrics_sidecar_end_to_end(tmp_path):
    """The observability acceptance drill: a live serve.py process with
    --metrics_port answers /metrics with valid Prometheus text carrying
    nonzero engine counters after one request, /healthz 200, /statz JSON —
    while stdout stays exactly one JSON line per text."""
    import glob
    import re
    import subprocess
    import sys
    import time
    import urllib.request

    run_dir = train_mlm.main(
        _common(tmp_path, "obsmlm") + [
            "--num_latents", "4", "--num_latent_channels", "16",
            "--num_encoder_layers", "1",
            "--num_self_attention_layers_per_block", "1",
            "--num_cross_attention_heads", "2",
            "--num_self_attention_heads", "2", "--dtype", "float32",
            "--synthetic_size", "64", "--batch_size", "16",
            "--max_seq_len", "32", "--vocab_size", "120",
            "--max_steps", "2", "--log_every_n_steps", "1",
            "--num_predictions", "2",
        ]
    )
    ckpt = os.path.join(run_dir, "checkpoints")
    tok = glob.glob(str(tmp_path / "cache" / "*tokenizer*.json"))[0]
    events = str(tmp_path / "events.jsonl")
    series = str(tmp_path / "series.jsonl")
    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps([
        # never fires (healthz must stay ok); its state gauge still exports
        {"name": "queue_hot", "metric": "serving_queue_depth",
         "threshold": 1e6, "window_s": 60, "severity": "page"},
    ]))

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "perceiver_io_tpu.cli.serve", "--cpu",
         "--checkpoint", ckpt, "--tokenizer", tok, "--stdin",
         "--max_batch", "4", "--bucket_widths", "16", "--no_warmup",
         "--metrics_port", "0", "--heartbeat_deadline_s", "60",
         "--events_jsonl", events, "--k", "2",
         "--series_interval_s", "0.1", "--series_jsonl", series,
         "--alert_rules", str(rules)],
        cwd=root, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        # the sidecar address is printed to stderr before the model loads
        port = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stderr.readline()
            m = re.search(r"metrics on http://127\.0\.0\.1:(\d+)", line)
            if m:
                port = int(m.group(1))
                break
            assert line or proc.poll() is None, proc.poll()
        assert port, "serve never announced its metrics port"
        base = f"http://127.0.0.1:{port}"

        proc.stdin.write("a [MASK] b\n")
        proc.stdin.flush()

        def get(path):
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return r.status, r.read().decode()

        # poll until the request flowed through the engine (batches counts
        # at dispatch, after the submit-side requests counter)
        text = ""
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            _, text = get("/metrics")
            m = re.search(
                r'^serving_batches_total\{engine="mlm"\} (\d+)$',
                text, re.M)
            if m and int(m.group(1)) >= 1:
                break
            time.sleep(0.25)
        else:
            raise AssertionError(f"no nonzero engine counters:\n{text}")
        assert "# TYPE serving_requests_total counter" in text
        assert re.search(
            r'^serving_requests_total\{engine="mlm"\} [1-9]', text, re.M)
        assert re.search(
            r'^serving_rows_total\{engine="mlm"\} [1-9]', text, re.M)

        code, body = get("/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        code, body = get("/statz")
        statz = json.loads(body)
        assert code == 200
        assert statz["counters"]['serving_requests_total{engine="mlm"}'] >= 1
        assert statz["health"]["status"] == "ok"
        # the never-firing page rule still exports its state gauge, and the
        # alerting healthz source reports it without degrading the probe
        assert statz["gauges"]['alert_state{rule="queue_hot"}'] == 0.0
        assert statz["health"]["sources"]["alerts:serve"]["paging"] == []
        # /seriesz serves the sampled history live: the engine's request
        # counter has accumulated windowed samples by now
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            code, body = get("/seriesz")
            entry = json.loads(body)["series"].get(
                'serving_requests_total{engine="mlm"}')
            if entry and entry["n"] >= 2 and entry["last"] >= 1:
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"/seriesz never showed the history: {body}")

        # communicate() flushes and closes stdin → serve drains and exits
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err[-2000:]
        lines = [l for l in out.splitlines() if l.strip()]
        assert len(lines) == 1, out  # one JSON line per text, nothing else
        row = json.loads(lines[0])
        assert row["text"] == "a [MASK] b"
        assert len(row["fills"]) == 1 and len(row["fills"][0]) == 2
        # the event log captured the compile events (all off-stdout)
        rows = [json.loads(l) for l in open(events)]
        assert any(r.get("event") == "serving_compile" for r in rows)
        # the series JSONL drained on close: every persisted sweep parses
        # and carries the sampled engine counter
        srows = [json.loads(l) for l in open(series)]
        assert len(srows) >= 2
        assert all(r["event"] == "series_sample" for r in srows)
        assert srows[-1]["series"][
            'serving_requests_total{engine="mlm"}'] >= 1
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


def test_json_emitters_keep_one_line_stdout_contract(tmp_path):
    """CI guard (satellite): the tools/ JSON emitters must keep exactly one
    JSON line on stdout with the telemetry subsystem wired in — all logs ride
    stderr. kernel_smoke --dry covers the report shape without touching any
    device; inference_bench --engine --cpu has its own full test above."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "kernel_smoke.py"),
         "--dry", "--out", str(tmp_path / "ks.json")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    report = json.loads(lines[0])
    assert report["metric"] == "kernel_smoke" and report["dry"] is True
    assert report["total"] > 0 and report["skipped"]
    # the weight-only int8 path is registered in the per-round smoke
    assert "quant-int8w-dequant" in report["skipped"]
    # the generative causal decode geometries are registered too (the
    # in-kernel causal flag at guard boundaries + the q_len=1 step shape)
    assert "attn-causal-prefill-d128" in report["skipped"]
    assert "attn-q1-decode-32k" in report["skipped"]
    # the continuous-batching arena shapes: batched q1 step + batched
    # causal prefill (batch = arena slots) at VMEM-guard boundaries
    assert "attn-arena8-q1-32k" in report["skipped"]
    assert "attn-arena16-prefill-d64" in report["skipped"]
    # the fused dequant-matmul kernel geometries (r24): flagship vocab
    # head, grouped int4, and the all-axes-unaligned pad/slice path
    assert "qmm-int8-vocab-head" in report["skipped"]
    assert "qmm-int4-grouped-mlp" in report["skipped"]
    assert "qmm-int8-awkward-f32" in report["skipped"]
    with open(tmp_path / "ks.json") as f:
        assert json.loads(f.read()) == report


@pytest.mark.slow  # tier-1 budget (r10): the int8w parity bounds stay
# tier-1 in tests/test_quant.py (engine parity vs the f32 oracle) and the
# serve --quantize int8 e2e; the one-JSON-line stdout contract shape is
# asserted tier-1 by the inference_bench/coldstart_bench contract tests
def test_quant_bench_cpu_emits_one_json_line(tmp_path):
    """tools/quant_bench.py --cpu runs the interleaved bf16-vs-int8w engine
    A/B offline and emits EXACTLY one JSON line on stdout (the driver's
    quant-trajectory contract): throughput both arms, parity error vs the
    f32 oracle within the documented tiny-preset bound, and the predicted
    bytes-streamed accounting."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "quant_bench.py"),
         "--cpu", "--preset", "tiny", "--requests", "8", "--rounds", "1"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    result = json.loads(lines[0])
    assert result["mode"] == "quant" and result["backend"] == "cpu"
    for key in ("bf16_requests_per_s", "int8w_requests_per_s",
                "int4w_requests_per_s", "speedup_int8w_vs_bf16",
                "speedup_int4w_vs_bf16", "parity_bf16_rel_err",
                "parity_int8w_rel_err", "parity_int4w_rel_err",
                "param_bytes_int8w", "param_bytes_int4w",
                "predicted_weight_stream_ratio",
                "predicted_weight_stream_ratio_int4w",
                "qmm_pallas_ms", "qmm_xla_ms", "qmm_kernel_rel_err",
                "speedup_qmm_pallas_vs_xla"):
        assert key in result, result
    # the documented tiny-preset parity bounds (PERF.md §Quantization)
    assert result["parity_int8w_rel_err"] <= 0.05, result
    assert result["parity_int4w_rel_err"] <= 0.35, result
    # the kernel A/B consumes identical quantized operands — any gap is
    # purely kernel-vs-XLA, and in bf16 compute it measures exactly 0
    assert result["qmm_kernel_rel_err"] <= 2e-5, result
    assert 0 < result["predicted_weight_stream_ratio"] < 1, result
    assert (result["predicted_weight_stream_ratio_int4w"]
            < result["predicted_weight_stream_ratio"]), result


def test_quant_bench_dry_declares_record_keys(tmp_path):
    """tools/quant_bench.py --dry: one JSON line declaring the record's key
    contract without touching any device — what bench_compare and the
    driver key their floor classes on (tier-1: no model build, <5 s)."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "quant_bench.py"),
         "--dry"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    result = json.loads(lines[0])
    assert result["mode"] == "quant" and result["dry"] is True
    keys = set(result["keys"])
    for key in ("bf16_requests_per_s", "int8w_requests_per_s",
                "int4w_requests_per_s", "parity_int4w_rel_err",
                "param_bytes_int4w", "qmm_pallas_ms",
                "speedup_qmm_pallas_vs_xla"):
        assert key in keys, result
    assert "achieved_hbm_ratio_int8w_vs_bf16" in result["tpu_only_keys"]


@pytest.mark.slow  # tier-1 budget (r19): where the persistent cache is
# PLACED is tier-1 in tests/test_chip_smoke.py (the resolver); this 20s
# subprocess variant shows a train CLI really writing its compiles there
def test_train_cli_compile_cache_persists_step_compiles(tmp_path):
    """A train CLI writes its step compiles to jax's persistent compilation
    cache at JAX_COMPILATION_CACHE_DIR — and nowhere else — and the run
    stays green. Subprocess on purpose: the tier-1 process keeps the
    persistent cache off (conftest)."""
    import subprocess
    import sys

    cache = tmp_path / "tcache"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache)}
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    proc = subprocess.run(
        [sys.executable, "-m", "perceiver_io_tpu.cli.train_mlm",
         "--synthetic", "--synthetic_size", "32", "--batch_size", "16",
         "--max_seq_len", "32", "--vocab_size", "90",
         "--num_latents", "4", "--num_latent_channels", "16",
         "--num_encoder_layers", "1",
         "--num_self_attention_layers_per_block", "1",
         "--num_cross_attention_heads", "2", "--num_self_attention_heads", "2",
         "--dtype", "float32", "--max_steps", "1", "--log_every_n_steps", "1",
         "--logdir", str(tmp_path / "logs"), "--root", str(tmp_path / "cache")],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert any(n.endswith("-cache") for n in os.listdir(cache)), (
        "no compiled entries persisted")


@pytest.mark.slow  # tier-1 budget (r22 box drift): compile-cache
# mechanics stay tier-1 in tests/test_aot_cache.py; the cache
# subprocess drill was slow-marked in r20. This is the bench CLI shell.
def test_coldstart_bench_cpu_emits_one_json_line(tmp_path):
    """tools/coldstart_bench.py --cpu runs the same-process cold-vs-warm
    warmup A/B over the AOT executable cache and emits EXACTLY one JSON line
    on stdout. The acceptance bars ride the record: the warm pass performs
    ZERO XLA compiles and is >= 5x faster than the cold pass, and the
    background arm answers its first request before the family is warm."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "coldstart_bench.py"),
         "--cpu", "--max_batch", "4", "--widths", "32",
         "--cache_dir", str(tmp_path / "cache")],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    result = json.loads(lines[0])
    assert result["metric"] == "coldstart_warmup_speedup"
    assert result["backend"] == "cpu"
    assert result["compiles_warm"] == 0, result
    assert result["compiles_cold"] == result["programs"] > 0, result
    assert result["speedup"] >= 5, result
    assert result["bg_first_result_s"] <= result["bg_family_warm_s"], result


def test_load_bench_dry_emits_schema_json_line():
    """tools/load_bench.py --dry emits EXACTLY one JSON line describing the
    record shape (point + phase keys) without touching any backend."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "load_bench.py"),
         "--dry"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    assert record["metric"] == "load_bench" and record["dry"] is True
    assert record["sweep"] == [] and record["capacity"] is None
    for key in ("offered_rps", "achieved_rps", "p99_ms", "shed_rate",
                "phase_p50_ms", "breaker"):
        assert key in record["point_keys"], record
    assert record["phase_keys"] == [
        "admission", "queue", "assembly", "dispatch", "device", "complete"]
    # the continuous-deployment ride-along (--publish_every_s) declares its
    # block's keys; the block itself is null when the ride-along is off
    assert record["deploy"] is None
    for key in ("publishes", "swaps", "rejects", "rollbacks",
                "p99_steady_ms", "p99_swap_ms", "per_swap_p99_ms"):
        assert key in record["deploy_keys"], record
    # the elastic-autoscaling (--schedule/--autoscale) and admission
    # (--noisy_neighbor) blocks declare their keys the same way
    assert record["autoscale"] is None and record["admission"] is None
    assert record["schedule"] is None
    for key in ("schedule", "peak_replicas", "scale_ups", "scale_downs",
                "spawn_failures", "replica_seconds",
                "static_replica_seconds", "replica_seconds_saved_pct",
                "p99_within_slo", "lost_accepted"):
        assert key in record["autoscale_keys"], record
    for key in ("classes", "abuser_quota_rps", "victim_p99_delta_pct",
                "abuser_shed_drill", "victim_p99_unprotected_ms",
                "sheds_by_reason", "null"):
        assert key in record["admission_keys"], record
    # the generative traffic class (--generate_rps) declares its block's
    # keys the same way — the second, stateful class the r17 policies see
    assert record["generate"] is None
    for key in ("offered_streams", "completed", "failed", "tokens_total",
                "steps_per_s", "stream_p99_ms", "followups", "resumed",
                "reroutes", "spills", "stream"):
        assert key in record["generate_keys"], record
    # the token-level streaming sub-block (r21) declares its keys: caller-
    # clock TTFT/ITL, engine-side goodput, flight-recorder idle attribution
    for key in ("ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms",
                "streams_timed", "tokens_generated", "tokens_delivered",
                "tokens_wasted", "goodput", "idle_slot_rounds",
                "idle_attributed", "idle_attribution_frac", "idle_causes"):
        assert key in record["stream_keys"], record
    # the generate-class trace A/B rides the trace block
    assert "generate_ab" in record["trace_keys"], record


@pytest.mark.slow  # tier-1 budget (r22 box drift): the load_bench
# record schema stays tier-1 in test_load_bench_dry_fleet_schema and
# the full --cpu contract run is the r21 slow-marked drill; the
# saturation/SLO logic is unit-covered in tests/test_obs.py (slo).
def test_load_bench_cpu_sweep_shows_saturation_signature(tmp_path):
    """The SLO-observability acceptance drill: tools/load_bench.py --cpu
    emits ONE JSON line whose open-loop sweep shows the saturation
    signature — achieved throughput plateaus below the top offered rate,
    p99 inflects away from its floor, shed rate becomes nonzero past the
    knee — plus a fitted capacity estimate and per-phase attribution whose
    sum reconciles with the end-to-end latency."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "load_bench.py"),
         "--cpu", "--preset", "tiny", "--duration_s", "1.5", "--calibration_waves", "2",
         "--calibration_wave_size", "16",
         "--rate_factors", "0.3,0.8,1.5,3.0"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    assert record["metric"] == "load_bench" and record["backend"] == "cpu"
    assert record["preset"] == "tiny" and record["dry"] is False
    sweep = record["sweep"]
    assert len(sweep) == 4

    # the saturation signature: shedding appears past the knee ...
    assert sweep[-1]["shed_rate"] > 0, sweep
    # ... p99 inflects away from its light-load floor ...
    p99s = [p["p99_ms"] for p in sweep]
    assert max(p99s) > 1.5 * min(p99s), p99s
    # ... and achieved throughput plateaus below the top offered rate
    assert sweep[-1]["achieved_rps"] < 0.9 * sweep[-1]["offered_rps"], sweep
    # saturation is QUEUEING, attributed: the queue phase grows from the
    # first point to the last far more than the device phase does
    q_growth = (sweep[-1]["phase_p50_ms"]["queue"]
                - sweep[0]["phase_p50_ms"]["queue"])
    d_growth = (sweep[-1]["phase_p50_ms"]["device"]
                - sweep[0]["phase_p50_ms"]["device"])
    assert q_growth > d_growth, (q_growth, d_growth)

    # the fitted capacity model rides the record
    cap = record["capacity"]
    assert cap["capacity_rps"] > 0
    assert cap["service_floor_ms"] > 0
    assert "knee_rps" in cap and "slo_sustainable_rps" in cap
    assert cap["slo"]["availability_target"] == 0.999

    # per-phase attribution present on every point, and the phase sum
    # self-check reconciles with end-to-end latency
    for point in sweep:
        assert set(point["phase_p50_ms"]) == {
            "admission", "queue", "assembly", "dispatch", "device",
            "complete"}
    assert 0.9 <= record["phase_sum_ratio"] <= 1.1, record["phase_sum_ratio"]


@pytest.mark.slow  # tier-1 budget (r21): the TTFT/ITL/goodput/attribution
# semantics this run exercises stay tier-1 at the engine level in
# tests/test_stream_obs.py (reconciliation + flight kill drill) and the
# schema contract stays tier-1 in test_load_bench_dry_emits_schema_json_line;
# this is the full-stack subprocess run (router -> batched replica ->
# flight recorder -> record assembly), ~65 s of warmup-dominated wall
def test_load_bench_cpu_generate_stream_block_populates_finite():
    """A --generate_rps --decode_batching --trace_ab run populates every
    stream key with a FINITE value: caller-clock TTFT/ITL percentiles,
    engine-side goodput accounting, the flight recorder's idle attribution
    (>= 0.95 — the acceptance bar), and the generate-class traced-vs-
    untraced A/B block."""
    import math
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "load_bench.py"),
         "--cpu", "--preset", "tiny", "--duration_s", "1.5", "--calibration_waves", "1",
         "--calibration_wave_size", "8", "--rate_factors", "0.8",
         "--replicas", "1", "--generate_rps", "8", "--decode_batching",
         "--trace_ab", "--trace_ab_waves", "2"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    stream = record["generate"]["stream"]
    for key in ("ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms"):
        assert isinstance(stream[key], float) and stream[key] > 0, stream
        assert math.isfinite(stream[key]), stream
    assert stream["ttft_p95_ms"] >= stream["ttft_p50_ms"]
    assert stream["streams_timed"] > 0
    # goodput ledger: generated >= delivered, wasted accounts the gap
    assert stream["tokens_generated"] >= stream["tokens_delivered"] > 0
    assert stream["tokens_wasted"] == (stream["tokens_generated"]
                                       - stream["tokens_delivered"])
    assert 0.0 < stream["goodput"] <= 1.0
    # the flight recorder attributed the idleness (acceptance: >= 95%)
    assert stream["idle_slot_rounds"] >= 0
    assert stream["idle_attribution_frac"] >= 0.95, stream
    assert set(stream["idle_causes"]) == {
        "no_pending", "width_mismatch", "arena_full", "draining"}
    assert (sum(stream["idle_causes"].values())
            == stream["idle_attributed"])
    # the generate-class A/B populated alongside the request-class one
    gen_ab = record["trace"]["generate_ab"]
    assert gen_ab["untraced_tokens_per_s"] > 0
    assert gen_ab["traced_tokens_per_s"] > 0
    assert gen_ab["decode_events_recorded"] > 0
    assert math.isfinite(gen_ab["overhead_pct"])
    # the built-in null control: same paired waves, log hooked in NEITHER
    # arm — readers judge overhead_pct against this floor, not against 0
    assert math.isfinite(gen_ab["null_overhead_pct"])


def test_decode_flight_dry_emits_schema_json_line():
    """tools/decode_flight.py --dry emits EXACTLY one JSON line declaring
    the attribution-record keys without touching any backend."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "decode_flight.py"),
         "--dry"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    assert record["metric"] == "decode_flight" and record["dry"] is True
    for key in ("rounds", "slot_rounds", "idle_slot_rounds", "attributed",
                "attribution_frac", "causes", "evicts", "grows",
                "pending_max", "dumps", "dump_reasons", "drill"):
        assert key in record["record_keys"], record


def test_deploy_bench_dry_emits_schema_json_line():
    """tools/deploy_bench.py --dry emits EXACTLY one JSON line declaring the
    record + per-swap keys without touching any backend."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "deploy_bench.py"),
         "--dry"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    assert record["metric"] == "deploy_bench" and record["dry"] is True
    for key in ("swaps", "rejects", "rollbacks", "lost_accepted",
                "swap_cadence_s", "p99_steady_ms", "p99_swap_ms",
                "blip_ratio", "per_swap"):
        assert key in record["record_keys"], record
    assert record["per_swap_keys"] == [
        "step", "action", "gate_ms", "swap_ms", "p99_ms", "n_window"]


@pytest.mark.slow  # tier-1 budget (r21): gated-rollout + zero-lost-
# accepted semantics stay tier-1 in tests/test_deploy.py::
# test_fleet_deploy_chaos_e2e (real fleet, chaos injection); this is the
# bench-CLI wrapper over the same loop
def test_deploy_bench_cpu_gated_swaps_zero_loss(tmp_path):
    """The deployment-loop acceptance contract: tools/deploy_bench.py --cpu
    pushes N publications through gate + hot-swap under open-loop traffic
    and emits ONE JSON line with every swap completed, ZERO lost accepted
    requests, and the per-swap latency attribution populated."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "deploy_bench.py"),
         "--cpu", "--swaps", "3", "--publish_every_s", "0.5",
         "--calibration_waves", "1", "--rate_factor", "0.3"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    assert record["metric"] == "deploy_bench" and record["backend"] == "cpu"
    assert record["preset"] == "tiny" and record["mode"] == "engine"
    # every publication passed the gate and swapped; none were lost to it
    assert record["publishes"] == record["swaps"] == 3, record
    assert record["rejects"] == 0 and record["rollbacks"] == 0, record
    assert record["lost_accepted"] == 0 and record["failed"] == 0, record
    assert record["completed"] > 0 and record["shed"] == 0, record
    # attribution populated: a steady p99 plus a window around every swap
    assert record["p99_steady_ms"] is not None, record
    assert len(record["per_swap"]) == 3, record
    for s in record["per_swap"]:
        assert s["action"] == "swapped" and s["swap_ms"] > 0, s
        assert s["n_window"] > 0, s


def test_bench_without_a_tpu_exits_nonzero_with_its_reason():
    """bench.py measures a chip: on a backend that is not a TPU it exits
    non-zero with the reason on stderr and prints NO record on stdout — a
    CPU timing must never look like a result."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "TPU" in proc.stderr and "cpu" in proc.stderr, proc.stderr[-2000:]


def test_encode_masked_samples(tmp_path):
    from perceiver_io_tpu.data.imdb import IMDBDataModule

    data = IMDBDataModule(
        root=str(tmp_path / "cache"), max_seq_len=16, vocab_size=120,
        synthetic=True, synthetic_size=64,
    )
    data.prepare_data()
    data.setup()
    mask_id = data.tokenizer.token_to_id("[MASK]")
    ids, pad = train_mlm.encode_masked_samples(
        data.collator, ["movie was [MASK] and [MASK] acting"]
    )
    assert ids.shape == (1, 16)
    assert (ids[0] == mask_id).sum() == 2
    assert pad.dtype == bool


@pytest.mark.slow  # tier-1 budget (r22 box drift): the shared train
# loop/CLI machinery stays tier-1 via the train_mlm variants above;
# the image model forward/adapters are unit-covered in test_model.py.
def test_train_imagenet(tmp_path):
    from perceiver_io_tpu.cli import train_imagenet

    run_dir = train_imagenet.main(
        _common(tmp_path, "imagenet") + TINY_MODEL + [
            "--synthetic_size", "64", "--synthetic_classes", "4",
            "--image_size", "16", "--batch_size", "8", "--num_workers", "2",
            "--num_frequency_bands", "4",
            "--max_epochs", "1", "--log_every_n_steps", "2",
        ]
    )
    rows = read_metrics(run_dir)
    assert any("train_loss" in r for r in rows)
    assert any("val_loss" in r for r in rows)
    assert os.path.isdir(os.path.join(run_dir, "checkpoints"))


@pytest.mark.slow  # tier-1 budget (r11): multimodal adapter/model/loss
# numerics stay tier-1 in tests/test_multimodal.py (incl. the
# make_multimodal_steps train step), the sharded end-to-end in
# tests/test_sharding.py::test_multimodal_autoencoder_sharded, flag parsing
# in test_all_parsers_build_and_render_help, and the Trainer-CLI plumbing
# via the train_mlm e2es in this file
def test_train_multimodal(tmp_path):
    from perceiver_io_tpu.cli import train_multimodal

    run_dir = train_multimodal.main(
        _common(tmp_path, "multimodal") + TINY_MODEL + [
            "--synthetic_size", "32", "--batch_size", "8",
            "--video_frames", "2", "--video_size", "8", "--video_channels", "1",
            "--video_patch", "1", "4", "4",
            "--audio_samples", "64", "--samples_per_patch", "8",
            "--num_classes", "3", "--num_modality_channels", "4",
            "--video_frequency_bands", "2", "--audio_frequency_bands", "2",
            "--max_epochs", "1", "--log_every_n_steps", "1",
        ]
    )
    rows = read_metrics(run_dir)
    assert any("train_loss" in r for r in rows)
    assert any("val_loss" in r for r in rows)
    assert any("val_acc" in r for r in rows)
    assert os.path.isdir(os.path.join(run_dir, "checkpoints"))


@pytest.mark.slow  # tier-1 budget (r10): near-duplicate of the flow CLI e2e
# in tests/test_flow_data.py::test_train_flow_cli (tier-1)
def test_train_flow(tmp_path):
    from perceiver_io_tpu.cli import train_flow

    run_dir = train_flow.main(
        _common(tmp_path, "flow") + TINY_MODEL + [
            "--synthetic_size", "32", "--batch_size", "8",
            "--image_height", "12", "--image_width", "16",
            "--num_frequency_bands", "4",
            "--max_epochs", "1", "--log_every_n_steps", "1",
        ]
    )
    rows = read_metrics(run_dir)
    assert any("train_loss" in r for r in rows)
    assert any("val_loss" in r for r in rows)
    assert os.path.isdir(os.path.join(run_dir, "checkpoints"))


def test_all_parsers_build_and_render_help():
    """Every entry point's composed parser builds without argparse conflicts
    and renders help (cheap guard for flag collisions across the shared
    argument groups)."""
    from perceiver_io_tpu.cli import (
        train_flow,
        train_imagenet,
        train_img_clf,
        train_mlm,
        train_multimodal,
        train_seq_clf,
    )

    for mod in (train_mlm, train_seq_clf, train_img_clf,
                train_imagenet, train_flow, train_multimodal):
        parser = mod.build_parser()
        help_text = parser.format_help()
        for flag in ("--dp", "--tp", "--sp", "--zero", "--multihost",
                     "--resume", "--attn_impl", "--dtype",
                     "--selfprofile_every_n_steps",
                     "--skip_nonfinite_steps", "--rollback_after_bad_steps",
                     "--dispatch_error_retries", "--fit_attempts"):
            assert flag in help_text, f"{mod.__name__} missing {flag}"

    from perceiver_io_tpu.cli import serve

    help_text = serve.build_parser().format_help()
    for flag in ("--checkpoint", "--tokenizer", "--bucket_widths", "--dtype",
                 "--quantize", "--cached", "--max_delay_ms", "--metrics_port",
                 "--heartbeat_deadline_s", "--selfprofile_every",
                 "--events_jsonl", "--events_max_mb", "--cpu",
                 "--request_deadline_s", "--queue_limit",
                 "--dispatch_retries", "--breaker_failures",
                 "--breaker_cooldown_s", "--slo_p99_ms",
                 "--slo_availability", "--slo_burn_alert", "--span_every"):
        assert flag in help_text, f"serve missing {flag}"


@pytest.mark.parametrize("flag, value", [("--attn_impl", "packed"), ("--fused_head", "xla")])
def test_train_mlm_parser_refuses_a_removed_choice(flag, value, capsys):
    """A path that was deleted is not a choice: argparse exits with the
    accepted list instead of the value reaching the model."""
    from perceiver_io_tpu.cli import train_mlm

    with pytest.raises(SystemExit):
        train_mlm.build_parser().parse_args(["--synthetic", flag, value])
    assert f"invalid choice: '{value}'" in capsys.readouterr().err


def test_mlm_preset_flagship_tpu_defaults():
    """--preset flagship_tpu moves the width/compute DEFAULTS (256 latents x
    512 channels, attn_impl xla — models/presets.py flagship_tpu_mlm) while
    explicit flags still override the preset. Resolution is post-parse
    (apply_preset over None sentinels), so it composes with resume's
    hparams-as-defaults layering and never reads global sys.argv."""
    from perceiver_io_tpu.cli import train_mlm

    def parse(argv):
        return train_mlm.apply_preset(
            train_mlm.build_parser().parse_args(argv))

    ref = parse([])
    assert (ref.num_latents, ref.num_latent_channels) == (64, 64)
    assert ref.attn_impl == "auto"

    args = parse(["--preset", "flagship_tpu"])
    assert (args.num_latents, args.num_latent_channels) == (256, 512)
    assert args.attn_impl == "xla"
    # the recipe shape is untouched: reference batch/seq/layer defaults
    assert (args.batch_size, args.max_seq_len) == (64, 512)
    assert (args.num_encoder_layers,
            args.num_self_attention_layers_per_block) == (3, 6)

    args = parse(["--preset", "flagship_tpu", "--num_latent_channels", "128",
                  "--attn_impl", "auto"])
    assert (args.num_latents, args.num_latent_channels) == (256, 128)
    assert args.attn_impl == "auto"


@pytest.mark.slow  # tier-1 budget (r10): zero3 rule correctness stays
# tier-1 in tests/test_sharding.py::test_zero3_param_sharding and the
# checkpoint path in test_zero3_sharded_state_round_trip
def test_train_mlm_zero3(tmp_path):
    """--zero3 (ZeRO-3/FSDP flavor: params AND opt-state over the data
    axis, GSPMD all-gather-on-use) trains end to end on the 8-device mesh
    with finite losses."""
    run_dir = train_mlm.main(
        _common(tmp_path, "mlmz3") + TINY_MODEL + [
            "--synthetic_size", "64", "--batch_size", "16",
            "--max_seq_len", "32", "--vocab_size", "90",
            "--max_steps", "3", "--log_every_n_steps", "1",
            "--dp", "8", "--zero3",
        ]
    )
    rows = read_metrics(run_dir)
    losses = [r["train_loss"] for r in rows if "train_loss" in r]
    assert losses and np.isfinite(losses).all()


@pytest.mark.slow  # tier-1 budget (r19): resume determinism stays tier-1 in
# test_trainer.py::test_resume_fast_forwards_data_stream +
# test_cli_resume_continues_run, and the bucket×K grouped-emission
# contract in test_data.py's group_widths/group_size units — this is the
# 30s full-CLI composition of both
def test_bucketed_stacked_resume_is_bit_for_bit(tmp_path):
    """Deterministic resume survives the r4 composition: with width buckets
    AND steps_per_dispatch=2 active, a run STOPPED at step 4 (end-of-run
    checkpoint; the SIGTERM last/ path has its own drill) and resumed
    to step 8 reproduces the uninterrupted run's logged losses EXACTLY
    (float-equal) — the loader's grouped emission order is a deterministic
    (seed, epoch) function consumed strictly as a prefix, so the resume
    arithmetic lands on the very same batches."""
    base = [
        "--synthetic", "--synthetic_size", "128", "--batch_size", "8",
        "--max_seq_len", "256", "--vocab_size", "120",
        "--bucket_widths", "128", "--length_sort_window", "2",
        "--steps_per_dispatch", "2",
        "--num_latents", "8", "--num_latent_channels", "16",
        "--num_encoder_layers", "1",
        "--num_self_attention_layers_per_block", "1",
        "--dtype", "float32", "--log_every_n_steps", "1",
        "--root", str(tmp_path / "cache"),
    ]

    def losses(run_dir):
        rows = read_metrics(run_dir)
        return {r["step"]: r["train_loss"] for r in rows if "train_loss" in r}

    full = losses(train_mlm.main(
        base + ["--max_steps", "8",
                "--logdir", str(tmp_path / "full"), "--experiment", "f"]))
    part = train_mlm.main(
        base + ["--max_steps", "4",
                "--logdir", str(tmp_path / "part"), "--experiment", "p"])
    resumed = losses(train_mlm.main(base + ["--max_steps", "8", "--resume", part]))

    tail_full = {k: v for k, v in full.items() if k > 4}
    tail_res = {k: v for k, v in resumed.items() if k > 4}
    assert tail_full and tail_full.keys() == tail_res.keys()
    for k in tail_full:
        assert tail_full[k] == tail_res[k], (k, tail_full[k], tail_res[k])


def test_resume_nothing_to_resume_fails_clearly(tmp_path):
    """--resume on a dir with no usable checkpoint must fail with the clear
    nothing-to-resume message (not a raw traceback) in all three shapes: no
    checkpoints/ at all, a regular file as the path, and the
    killed-after-construction window where hparams.json exists but zero
    checkpoint steps were saved."""
    tiny = _common(tmp_path, "rz") + TINY_MODEL + [
        "--synthetic_size", "32", "--max_seq_len", "32", "--vocab_size", "90",
        "--batch_size", "8", "--max_steps", "1", "--log_every_n_steps", "1",
    ]

    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(SystemExit, match="no usable checkpoint"):
        train_mlm.main(tiny + ["--resume", str(empty)])

    not_a_dir = tmp_path / "file.txt"
    not_a_dir.write_text("x")
    with pytest.raises(SystemExit, match="no usable checkpoint"):
        train_mlm.main(tiny + ["--resume", str(not_a_dir)])

    constructed = tmp_path / "constructed"
    (constructed / "checkpoints").mkdir(parents=True)
    (constructed / "checkpoints" / "hparams.json").write_text(
        json.dumps({"num_latents": 8}))
    with pytest.raises(SystemExit, match="no usable checkpoint"):
        train_mlm.main(tiny + ["--resume", str(constructed)])


def test_spawn_retry_gate_reads_coordination_errors(tmp_path):
    """The spawn_hosts port-race retry fires only on distributed-bring-up
    evidence in a child log — a deterministic fast failure (bad flag,
    import error) must NOT look like a race (cli/common.py)."""
    from perceiver_io_tpu.cli.common import _logs_show_coordination_failure

    logs = iter(range(10))

    def fake_log(text):
        f = (tmp_path / f"rank{next(logs)}.log").open("w+")
        f.write(text)
        f.flush()
        return f

    race = fake_log("jaxlib ... UNAVAILABLE: failed to connect to coordinator")
    bind = fake_log("RuntimeError: [Errno 98] Address already in use")
    plain = fake_log("error: unrecognized arguments: --definitely-not-a-flag")
    assert _logs_show_coordination_failure([None, race])
    assert _logs_show_coordination_failure([None, bind])
    assert not _logs_show_coordination_failure([None, plain])
    assert not _logs_show_coordination_failure([None])  # rank 0 only
