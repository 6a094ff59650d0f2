"""chip_smoke.py — prove the program starts on the chip.

One process, one TPU chip, no options: drives the MLM trainer, the fill-mask
server and the generation arena once through the entry points a user calls
(``perceiver_io_tpu.cli.train_mlm`` / ``cli.serve`` / ``MLMServer`` /
``ContinuousBatcher``) at the full widths of the repo's TPU flagship, and
checks what comes out against the repo's own references. Synthetic data and
random weights from fixed seeds; nothing is downloaded; no child process ever
needs the chip (it belongs to this one).

Phases (each prints one JSON line as it ends; a failing phase raises and
nothing is carried past it):

1. device   jax finds a TPU, or the run ends here with a non-zero exit.
2. kernels  every case of ``tools/kernel_smoke.py`` compiled (shown by
            ``tpu_custom_call`` in the compiled text) against its XLA path.
3. train    ``train_mlm --preset flagship_tpu --synthetic``: >= 10 steps,
            eval, checkpoint; finite falling loss, no program built after
            warm-up. Then ``--preset reference`` with the CLI's own
            ``--fused_head auto``: the step holds the flash-CE kernel.
4. serve    ``cli.serve.main`` on phase 3's checkpoint, bf16 (warm-up on) /
            int8 / int4 (programs on demand): 8 requests at two widths
            answered; logits within the repo's parity bounds of the model's
            plain apply; the quantized programs hold the dequant-matmul
            kernel.
5. warm     the server again, same process: zero backend compiles,
            bit-identical answers.
6. generate ``ContinuousBatcher`` at ``flagship_ar`` widths (f32, true-f32
            matmuls: the parity path), 4 concurrent streams x 16 tokens,
            bit-identical to a plain ``ARGenerator``.

The LAST line of stdout is the device line the builder's contract fixes:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

``python chip_smoke.py --chips 4`` runs ONLY the multi-chip phase and what it
is compared with: the ``train_mlm`` step at ``--preset flagship_tpu`` on the
CLI's own 4-device meshes (``--dp 4 --zero3`` and ``--tp 2 --sp 2
--shard_seq``) against a one-device run of the same batches.

What the synthetic corpus cannot give: its tokenizer ends at a few hundred
word pieces, so the flagship's V=10003 head is reached with the CLI's own
``--pad_vocab_multiple 10003`` — the vocab projection, its CE and the serving
head run at full width; the embedding table does not.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
# run artifacts (logs, checkpoints, tokenizer, AOT entries): under the
# checkout's ignored .cache/, emptied at the start of every run
WORK = os.path.join(REPO, ".cache", "chip_smoke")

HEAD_WIDTH = 10003  # the flagship vocab width (models/presets.py)

# rel-to-peak parity bounds, from the repo's own pins: bf16 and int8w vs the
# f32 oracle (tests/test_quant.py::test_int8w_engine_parity_vs_f32_oracle,
# 0.05); int4w's documented bound (tools/quant_bench.py slow test, 0.35)
PARITY_BOUND = {"none": 0.05, "int8": 0.05, "int4": 0.35}

_COMMON_TRAIN = [
    "--synthetic", "--synthetic_size", "1024", "--no_tensorboard",
    "--pad_vocab_multiple", str(HEAD_WIDTH), "--log_every_n_steps", "1",
]
# C=512, 256 latents, 512 tokens, batch 64, bf16: every width of
# flagship_tpu at the CLI's defaults; 12 steps, evals (and saves) at 6 and 12
FLAGSHIP_TRAIN = _COMMON_TRAIN + [
    "--preset", "flagship_tpu", "--experiment", "flagship",
    "--max_steps", "12", "--eval_every_n_steps", "6",
]
# the reference recipe (C=64); --fused_head stays on the CLI's 'auto'
REFERENCE_TRAIN = _COMMON_TRAIN + [
    "--preset", "reference", "--experiment", "reference",
    "--max_steps", "10", "--eval_every_n_steps", "10",
]
WARM_AFTER_STEP = 7  # the first eval + save (step 6) is part of warm-up

# four requests that fit the 64-token bucket and four that need the full
# 512-token width (word pieces of the synthetic corpus; one has two masks)
_SHORT = "this movie was [MASK] and the acting was great"
_LONG = " ".join(["the plot of this film was good and the cast was fine"] * 8)
SERVE_TEXTS = [
    _SHORT, "a [MASK] film with a [MASK] story", "i [MASK] this movie",
    "the acting was [MASK]",
    f"{_LONG} [MASK]", f"[MASK] {_LONG}", f"{_LONG} and it was [MASK] overall",
    f"the [MASK] {_LONG}",
]
SERVE_BUCKET_WIDTH = 64
SERVE_MAX_BATCH = 2

MESHES = {
    "dp4_zero3": ["--dp", "4", "--zero3"],
    "tp2_sp2": ["--dp", "1", "--tp", "2", "--sp", "2", "--shard_seq"],
}
# tests/test_sharding.py compares sharded and one-device losses at
# atol=2e-5 on the f32 path; the comparison runs that path
MESH_LOSS_ATOL = 2e-5


# -- reporting ----------------------------------------------------------------


def emit(phase: str, t0: float, **checked: Any) -> Dict[str, Any]:
    line = {"phase": phase, "ok": True,
            "seconds": round(time.monotonic() - t0, 1), **checked}
    print(json.dumps(line), flush=True)
    return line


class CompileLog:
    """Every program this process builds, as jax's own monitoring reports it:
    ``backend`` = XLA compiled it, ``hit`` = the persistent cache answered
    instead. (jax 0.9.0 fires ``backend_compile_duration`` around the cache
    lookup too; the ``cache_hits`` event precedes it on the same thread, so
    the two are paired — ``obs.install_compile_counter`` does the same.)
    ``mark`` tags each event (the trainer's logged step), so "nothing was
    built after warm-up" can be checked afterwards."""

    def __init__(self) -> None:
        self.events: List[tuple] = []
        self.mark: Callable[[], Any] = lambda: None
        self._answered = threading.local()
        self._installed = False

    def install(self) -> "CompileLog":
        if not self._installed:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
            self._installed = True
        return self

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self._answered.hit = True
            self.events.append(("hit", self.mark()))

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            if getattr(self._answered, "hit", False):
                self._answered.hit = False
            else:
                self.events.append(("backend", self.mark()))

    def count(self, kind: str, since: int = 0) -> int:
        return sum(1 for k, _ in self.events[since:] if k == kind)


COMPILES = CompileLog()


def on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def _kernel_smoke():
    """``tools/kernel_smoke.py``, imported (never started as a child)."""
    tools = os.path.join(REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import kernel_smoke

    return kernel_smoke


# -- phase 1: device ------------------------------------------------------------


def require_tpu(devices: Sequence[Any], chips: int) -> None:
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: jax found no TPU (platform "
            f"{devices[0].platform!r}, {devices[0].device_kind}); this "
            "script proves the program on the chip and prints no result "
            "elsewhere")
    if len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: this run needs {chips} chip(s), jax reports "
            f"{len(devices)}")


def phase_device(chips: int) -> Dict[str, Any]:
    t0 = time.monotonic()
    import jax
    import jaxlib

    from perceiver_io_tpu.aot import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    require_tpu(devices, chips)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # not installed as a distribution: report, don't fail
        libtpu = "unknown"
    COMPILES.install()
    return emit("device", t0, platform=devices[0].platform,
                kind=devices[0].device_kind, count=len(devices),
                jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
                compile_cache=cache_dir,
                compile_cache_entries=len(glob.glob(
                    os.path.join(cache_dir, "*"))))


# -- phase 2: kernels -----------------------------------------------------------


def phase_kernels(cases: Optional[Dict[str, Callable]] = None) -> Dict[str, Any]:
    t0 = time.monotonic()
    kernel_smoke = _kernel_smoke()
    cases = kernel_smoke.CASES if cases is None else cases
    compiled_kernels = []
    for name, build in cases.items():
        try:
            if kernel_smoke.run_case(build(), require_kernel=on_tpu()):
                compiled_kernels.append(name)
        except Exception as e:
            raise AssertionError(f"kernel case {name}: {e}") from e
    return emit("kernels", t0, cases=len(cases),
                compiled_kernels=compiled_kernels,
                xla_by_design=[n for n in cases if n not in compiled_kernels])


# -- phase 3: train -------------------------------------------------------------


def _train_flags(flags: Sequence[str]) -> List[str]:
    return [*flags, "--logdir", os.path.join(WORK, "logs"),
            "--root", os.path.join(WORK, "data")]


def _trainer_with_compiled_step(flags: Sequence[str], mesh=None):
    """The trainer ``train_mlm.main`` would run for ``flags`` (what main does
    short of fitting) and its step program compiled for the first training
    batch: ``(args, trainer, data, compiled)``."""
    from perceiver_io_tpu.cli import train_mlm

    args = train_mlm.apply_preset(
        train_mlm.build_parser().parse_args(_train_flags(flags)))
    trainer, data = train_mlm.build_trainer(args, mesh=mesh)
    batch = next(iter(data.train_dataloader()))
    compiled = trainer._train_step.jitted.lower(
        trainer.state, {k: batch[k] for k in trainer._keys}).compile()
    return args, trainer, data, compiled


def _check_losses(run_dir: str, min_steps: int) -> List[float]:
    from perceiver_io_tpu.training.metrics import read_metrics

    rows = read_metrics(run_dir)
    if not rows:
        raise AssertionError(f"no metrics.jsonl rows under {run_dir}")
    losses = [r["train_loss"] for r in rows if "train_loss" in r]
    if len(losses) < min_steps:
        raise AssertionError(f"{len(losses)} logged steps < {min_steps}")
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite train loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if not any("val_loss" in r for r in rows):
        raise AssertionError("no eval ran")
    return losses


def phase_train(flagship_flags: Sequence[str] = FLAGSHIP_TRAIN,
                reference_flags: Sequence[str] = REFERENCE_TRAIN,
                warm_after_step: int = WARM_AFTER_STEP) -> Dict[str, Any]:
    import jax

    import perceiver_io_tpu.obs as obs
    from perceiver_io_tpu.cli import common, train_mlm

    # -- the flagship, through main() --
    t0 = time.monotonic()
    logged_step = obs.get_registry().gauge("logged_step")
    logged_step.set(0)
    COMPILES.mark = lambda: logged_step.value
    start = len(COMPILES.events)
    run_dir = train_mlm.main(_train_flags(flagship_flags))
    COMPILES.mark = lambda: None
    losses = _check_losses(run_dir, min_steps=10)
    late = [(k, s) for k, s in COMPILES.events[start:]
            if s is not None and s >= warm_after_step]
    if late:
        raise AssertionError(
            f"programs built after warm-up (step >= {warm_after_step}): {late}")
    ckpt = os.path.join(run_dir, "checkpoints")
    steps_saved = sorted(int(n) for n in os.listdir(ckpt) if n.isdigit())
    if not steps_saved:
        raise AssertionError(f"no checkpoint step under {ckpt}")
    flagship = emit(
        "train_flagship", t0, steps=len(losses), first_loss=losses[0],
        last_loss=losses[-1], checkpoints=steps_saved,
        backend_compiles=COMPILES.count("backend", start),
        cache_hits=COMPILES.count("hit", start),
        built_after_warmup=len(late), run_dir=os.path.relpath(run_dir, REPO))

    # -- the reference recipe: what main() does, with the trainer in hand so
    # the compiled step can be read --
    t0 = time.monotonic()
    args, trainer, data, compiled = _trainer_with_compiled_step(
        reference_flags)
    rows = args.batch_size * train_mlm.mlm_gather_capacity(args.max_seq_len)
    text = compiled.as_text()
    has_kernel = "tpu_custom_call" in text
    # the unfused head materializes (rows, V) logits; the fused one never does
    unfused_logits = f"[{rows},{HEAD_WIDTH}]" in text
    if on_tpu() and (not has_kernel or unfused_logits):
        raise AssertionError(
            f"--fused_head auto did not give the flash-CE step: "
            f"tpu_custom_call={has_kernel}, "
            f"[{rows},{HEAD_WIDTH}] logits={unfused_logits}")
    with trainer:
        common.run_fit(trainer, data.train_dataloader(), data.val_dataloader())
    ref_losses = _check_losses(trainer.run_dir, min_steps=10)
    emit("train_reference", t0, steps=len(ref_losses),
         first_loss=ref_losses[0], last_loss=ref_losses[-1],
         flash_ce_kernel=has_kernel, unfused_logits=unfused_logits,
         devices=jax.device_count())
    return {**flagship, "checkpoint": ckpt}


# -- phases 4 + 5: serve, warm start ---------------------------------------------


def _serve_once(ckpt: str, tokenizer: str, texts: Sequence[str],
                bucket_width: int, quantize: str,
                check_parity: bool = True) -> Dict[str, Any]:
    """One pass of the serving check in one mode: the CLI answers ``texts``;
    then the same server class, opened the same way, gives the logits, which
    ``check_parity`` compares with the model's plain apply (the warm-start
    pass compares them with the first pass's instead)."""
    import jax

    from perceiver_io_tpu.cli import serve
    from perceiver_io_tpu.data.tokenizer import load_tokenizer
    from perceiver_io_tpu.inference import MLMServer, load_mlm_checkpoint

    flags = ["--checkpoint", ckpt, "--tokenizer", tokenizer,
             "--compile_cache", os.path.join(WORK, "aot"),
             "--bucket_widths", str(bucket_width),
             "--max_batch", str(SERVE_MAX_BATCH), "--k", "5",
             "--dtype", "bfloat16"]
    # bf16 warms its whole bucket family ahead of the requests; the
    # quantized passes build the programs their requests need on demand
    # (two more 22-program families would not fit the run's time limit cold)
    flags += (["--blocking_warmup"] if quantize == "none"
              else ["--quantize", quantize, "--no_warmup"])
    results = serve.main([*flags, "--texts", *texts])
    if len(results) != len(texts):
        raise AssertionError(f"{len(results)} answers for {len(texts)} texts")
    for r in results:
        if not r["fills"] or not all(len(f) > 0 for f in r["fills"]):
            raise AssertionError(f"empty top-k for {r['text']!r}")

    tok = load_tokenizer(tokenizer)
    vocab = tok.get_vocab_size()
    model, params, max_seq_len = load_mlm_checkpoint(
        ckpt, tok, dtype="bfloat16")
    if check_parity:
        rel_to_peak = _kernel_smoke().rel_to_peak
        oracle_model, _, _ = load_mlm_checkpoint(ckpt, tok)  # f32 compute

        @jax.jit
        def plain(p, ids, pad, pos):
            return oracle_model.apply({"params": p}, ids, pad, masking=False,
                                      deterministic=True, positions=pos)[0]

    logits, errs, widths, kernel_in_program = [], [0.0], set(), None
    with MLMServer(
        model, params, tok, max_seq_len, bucket_widths=[bucket_width],
        max_batch=SERVE_MAX_BATCH, compute_dtype="bfloat16",
        quantize=None if quantize == "none" else quantize,
        compile_cache=os.path.join(WORK, "aot"),
    ) as server:
        for text in texts:
            ids, pad, mask_pos = server._prepare(text)
            widths.add(int(ids.shape[1]))
            pos = server._positions_row(mask_pos, ids.shape[1])
            got = server.engine.predict(ids, pad, pos, timeout=600)
            got = np.asarray(got, np.float32)[0, :len(mask_pos), :vocab]
            logits.append(got)
            if check_parity:
                want = np.asarray(plain(params, ids, pad, pos),
                                  np.float32)[0, :len(mask_pos), :vocab]
                errs.append(rel_to_peak(got, want))
        if check_parity:
            # the program the engine serves, compiled for the last request's
            # shapes: does it hold a Pallas kernel? (attention is XLA at
            # these widths, so in a quantized program that is the
            # dequant-matmul)
            kernel_in_program = (
                "tpu_custom_call" in server.engine._jitted.lower(
                    server.engine.params, (ids, pad, pos)
                ).compile().as_text())
    bound = PARITY_BOUND[quantize]
    if max(errs) > bound:
        raise AssertionError(
            f"quantize={quantize}: rel-to-peak error {max(errs):.4g} > {bound}")
    if (on_tpu() and check_parity and quantize != "none"
            and not kernel_in_program):
        raise AssertionError(
            f"quantize={quantize}: the served program holds no "
            "tpu_custom_call — the dequant-matmul ran on the XLA path")
    return {"results": results, "logits": logits,
            "max_rel_err": round(max(errs), 5), "bound": bound,
            "widths": sorted(widths),
            "kernel_in_program": kernel_in_program}


def _find_tokenizer() -> str:
    (path,) = glob.glob(os.path.join(WORK, "data", "*tokenizer*.json"))
    return path


def phase_serve(ckpt: str, texts: Sequence[str] = SERVE_TEXTS,
                bucket_width: int = SERVE_BUCKET_WIDTH) -> Dict[str, Any]:
    tokenizer = _find_tokenizer()
    first = {}
    for quantize in ("none", "int8", "int4"):
        t0 = time.monotonic()
        start = len(COMPILES.events)
        out = _serve_once(ckpt, tokenizer, texts, bucket_width, quantize)
        if len(out["widths"]) < 2:
            raise AssertionError(
                f"requests landed in one width bucket: {out['widths']}")
        emit(f"serve_{quantize}", t0, requests=len(out["results"]),
             widths=out["widths"], max_rel_err=out["max_rel_err"],
             bound=out["bound"], kernel_in_program=out["kernel_in_program"],
             backend_compiles=COMPILES.count("backend", start),
             cache_hits=COMPILES.count("hit", start))
        first[quantize] = out
    return first


def phase_warm_start(ckpt: str, first: Dict[str, Any],
                     texts: Sequence[str] = SERVE_TEXTS,
                     bucket_width: int = SERVE_BUCKET_WIDTH) -> Dict[str, Any]:
    t0 = time.monotonic()
    import perceiver_io_tpu.obs as obs

    aot_hits = obs.get_registry().counter("aot_cache_hits_total")
    aot0 = aot_hits.value
    start = len(COMPILES.events)
    again = _serve_once(ckpt, _find_tokenizer(), texts, bucket_width, "none",
                        check_parity=False)
    compiles = COMPILES.count("backend", start)
    if compiles:
        raise AssertionError(f"warm start compiled {compiles} program(s)")
    if again["results"] != first["results"]:
        raise AssertionError("warm-start answers differ from the first pass")
    for a, b in zip(again["logits"], first["logits"]):
        if not np.array_equal(a, b):
            raise AssertionError("warm-start logits are not bit-identical")
    return emit("warm_start", t0, backend_compiles=compiles,
                persistent_cache_hits=COMPILES.count("hit", start),
                aot_executable_hits=int(aot_hits.value - aot0),
                bit_identical=True)


# -- phase 6: generate ------------------------------------------------------------


def phase_generate(build_model: Optional[Callable] = None,
                   max_seq_len: int = 512, vocab: int = HEAD_WIDTH,
                   streams: int = 4, new_tokens: int = 16) -> Dict[str, Any]:
    """Stream identity is a property of the arena's LOGIC (slots, admission,
    cache rings) only under exact arithmetic: in bf16 on the MXU a batch-4
    and a batch-1 matmul round differently, and with random weights (near-
    tied logits) a greedy stream flipped at token 13 of 16 on the v5e (PR 22,
    PERF.md). So the check runs the repo's parity path — f32 compute with
    true-f32 matmuls — at every width of ``flagship_ar``."""
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.inference.batching import ContinuousBatcher
    from perceiver_io_tpu.inference.generate import ARGenerator, SamplingConfig
    from perceiver_io_tpu.models.presets import flagship_ar

    model = (build_model or (lambda: flagship_ar(dtype=jnp.float32)))()
    rng = np.random.default_rng(0)
    cases = []
    for i in range(streams):
        prefix = [int(t) for t in rng.integers(3, vocab, 6 + 3 * i)]
        # two greedy and two sampled streams: both sampling programs
        sampling = SamplingConfig(temperature=0.0 if i % 2 == 0 else 0.8,
                                  top_k=16, seed=i)
        cases.append((prefix, new_tokens, sampling))

    got: List[Any] = [None] * streams
    errors: List[BaseException] = []
    # process-wide, not the context manager: the arena traces its programs on
    # its own dispatcher thread, and jax's config contexts are thread-local
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    arena = None
    try:
        ids = np.zeros((1, max_seq_len), np.int32)
        params = model.init(
            {"params": jax.random.key(0)}, ids, ids == 0)["params"]
        oracle = ARGenerator(model, params, max_seq_len=max_seq_len, chunk=8,
                             name="smoke-oracle")
        want = [oracle.generate(list(p), n, s)[0] for p, n, s in cases]
        arena = ContinuousBatcher(
            model, params, max_seq_len=max_seq_len, chunk=8, slots=streams,
            max_slots=streams, name="smoke-arena")

        def one(i: int) -> None:
            try:
                prefix, n, sampling = cases[i]
                got[i], _ = arena.generate(list(prefix), n, sampling)
            except BaseException as e:  # re-raised on the main thread
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        if errors:
            raise errors[0]
        if any(t.is_alive() for t in threads):
            raise AssertionError("a generation stream did not finish")
        stats = arena.stats()
    finally:
        if arena is not None:
            arena.close()
        jax.config.update("jax_default_matmul_precision", precision)
    for i, (g, w) in enumerate(zip(got, want)):
        if g != w:
            raise AssertionError(
                f"stream {i} diverged from the per-session generator: "
                f"{g} vs {w}")
    return emit("generate", t0, streams=streams, new_tokens=new_tokens,
                dtype="float32", matmul_precision="highest",
                tokens_match=True, dispatches=stats["dispatches"],
                admitted=stats["admitted"])


# -- --chips 4 ------------------------------------------------------------------


def _mesh_run(name: str, mesh_flags: Optional[Sequence[str]],
              base_flags: Sequence[str]) -> Dict[str, Any]:
    """Build the CLI's trainer on ``mesh_flags`` (None = a mesh of the first
    device alone: the CLI's flags always span every device), read its
    compiled step, then fit it; returns losses, collectives and per-device
    bytes."""
    import jax

    from perceiver_io_tpu.cli import common
    from perceiver_io_tpu.parallel import make_mesh

    t0 = time.monotonic()
    _, trainer, data, compiled = _trainer_with_compiled_step(
        [*base_flags, "--experiment", name, *(mesh_flags or [])],
        mesh=None if mesh_flags is not None
        else make_mesh(dp=1, devices=jax.devices()[:1]))
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    with trainer:
        common.run_fit(trainer, data.train_dataloader(), None)
    from perceiver_io_tpu.training.metrics import read_metrics

    losses = [r["train_loss"] for r in read_metrics(trainer.run_dir)
              if "train_loss" in r]
    return {
        "name": name, "mesh": dict(trainer.mesh.shape), "losses": losses,
        "collectives": [c for c in ("all-reduce", "reduce-scatter",
                                    "all-gather", "all-to-all",
                                    "collective-permute") if c in text],
        "argument_bytes_per_device": int(mem.argument_size_in_bytes),
        "temp_bytes_per_device": int(mem.temp_size_in_bytes),
        "seconds": round(time.monotonic() - t0, 1),
    }


def phase_multichip(base_flags: Optional[Sequence[str]] = None,
                    meshes: Dict[str, Sequence[str]] = MESHES) -> Dict[str, Any]:
    t0 = time.monotonic()
    if base_flags is None:
        # the parity path (f32): the tolerance of tests/test_sharding.py is
        # an f32 tolerance. Every WIDTH is flagship_tpu's; depth is cut from
        # 3 layers x 6 self-attention to 2 x 2 (the first layer and one pass
        # of the weight-shared one), which divides three f32 compiles of
        # ~3 min each on four chips by about five
        base_flags = _COMMON_TRAIN + [
            "--preset", "flagship_tpu", "--dtype", "float32",
            "--num_encoder_layers", "2",
            "--num_self_attention_layers_per_block", "2",
            "--max_steps", "3", "--eval_every_n_steps", "1000"]
    # true-f32 matmuls for the whole comparison, as in phase 6: at the TPU's
    # default f32 precision (one bf16 pass) the zero3 losses sat 9.2e-5 from
    # the one-device ones on four v5e chips (PR 22) — rounding that depends
    # on the per-device shapes, not a sharding fault, and outside an f32
    # tolerance. Process-wide: trainers trace on their own threads too.
    import jax

    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        one = _mesh_run("one_device", None, base_flags)
        print(json.dumps({"phase": "multichip_run", **one}), flush=True)
        runs = {}
        for name, flags in meshes.items():
            run = _mesh_run(name, flags, base_flags)
            run["max_loss_diff"] = float(np.max(np.abs(
                np.asarray(run["losses"]) - np.asarray(one["losses"]))))
            print(json.dumps({"phase": "multichip_run", **run}), flush=True)
            runs[name] = run
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    for name, run in runs.items():
        if len(run["losses"]) != len(one["losses"]) or not (
                run["max_loss_diff"] <= MESH_LOSS_ATOL):
            raise AssertionError(
                f"{name}: losses {run['losses']} vs one device "
                f"{one['losses']} (atol {MESH_LOSS_ATOL})")
        if not run["collectives"]:
            raise AssertionError(f"{name}: no collective in the step")
        if not (run["argument_bytes_per_device"]
                < one["argument_bytes_per_device"]):
            raise AssertionError(
                f"{name}: {run['argument_bytes_per_device']} argument bytes "
                f"per device, not below the one-device "
                f"{one['argument_bytes_per_device']}")
    return emit(
        "multichip", t0, loss_atol=MESH_LOSS_ATOL, flags=" ".join(base_flags),
        matmul_precision="highest",
        one_device_argument_bytes=one["argument_bytes_per_device"],
        **{name: {k: run[k] for k in ("max_loss_diff", "collectives",
                                      "argument_bytes_per_device")}
           for name, run in runs.items()})


# -- main -----------------------------------------------------------------------


def device_line() -> str:
    import jax

    d = jax.devices()[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}})


def main(argv: Optional[Sequence[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 = run ONLY the multi-chip training phase "
                             "(needs a four-chip host)")
    args = parser.parse_args(argv)

    phase_device(args.chips)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    if args.chips == 4:
        phase_multichip()
    else:
        phase_kernels()
        trained = phase_train()
        first = phase_serve(trained["checkpoint"])
        phase_warm_start(trained["checkpoint"], first["none"])
        phase_generate()
    print(device_line(), flush=True)


if __name__ == "__main__":
    main()
