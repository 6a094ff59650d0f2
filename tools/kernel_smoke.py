"""Kernel smoke: compile + parity-check EVERY Pallas path at guard-boundary
block geometries on the chip.

The block-size tiers in ``ops/pallas_attention.py`` (``_auto_kv_block``, the
q-block bump), the flash-CE row-block rule and the dequant-matmul blocks
encode scoped-VMEM boundaries. The tests exercise the kernels in interpret
mode on CPU, which can NOT catch a Mosaic/compiler upgrade moving a boundary
— that failure is a compile error only the chip's compiler produces. Each
case here is (kernel path, XLA reference, arguments): :func:`run_case`
compiles the kernel path, requires a ``tpu_custom_call`` in the compiled text
where the case has a kernel (shown, not assumed — an ``interpret=None`` call
site that fell back would otherwise pass), runs both and compares.

``chip_smoke.py`` phase 2 imports ``CASES`` and runs them in ITS process (a
chip belongs to one process; this tool is never started as a child of a
process that holds it). Run directly it prints ONE JSON line and exits
non-zero on any failure: ``python tools/kernel_smoke.py [--out FILE]``.

Covered paths and what each geometry pins:

- attention fwd + BOTH backward kernels (dq and dkv) at: the d<=64
  wide-stream tier (kv 2048) at long S; the d<=128 tier (kv 1024); the
  full-2048-KV flow-self shape; a deep-head d=512 shape sitting exactly ON
  the q-bump s_blk*d guard (must resolve to the safe 512 default); a
  lane-unaligned awkward-S shape (the pad-to-block path).
- flash-CE fwd + both backward kernels (dx and dw/db) at the flagship
  exact-divisor row count and at the 131k-context gathered row count
  39328 = 32*1229 (no aligned divisor above 32 — the row-PADDING rule).
- the sequence-parallel shard_map path compiled on the chip (seq axis = all
  local devices; multi-device equivalence is also CI's job on the 8-device
  CPU mesh).
- the weight-only int8 serving path (`perceiver_io_tpu.quant`): in-program
  dequant (int8 values × f32 per-channel scales → bf16) feeding a matmul,
  parity-checked against the f32 oracle. The one case WITHOUT a Pallas
  kernel (``kernel=False``): XLA fuses the dequant.
- the fused dequant-matmul kernel (``ops/pallas_matmul``) at the flagship
  vocab-head shape (int8), a grouped-int4 MLP shape (bk pinned to the
  group), and an all-axes-unaligned f32 shape (the pad/slice path) — each
  vs the XLA-dequant oracle over identical quantized values.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from typing import Any, Callable, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perceiver_io_tpu.utils.jsonline import emit_json_line
from perceiver_io_tpu.utils.platform import probe_backend

import numpy as np


@dataclasses.dataclass(frozen=True)
class Case:
    """One smoke case: ``kernel(*args)`` is the path under test,
    ``reference(*args)`` the XLA path it must match leaf for leaf."""

    kernel: Callable[..., Any]
    reference: Callable[..., Any]
    args: Tuple[Any, ...]
    rtol: float = 0.05
    has_kernel: bool = True  # False: the path is XLA by design


def run_case(case: Case, require_kernel: bool) -> bool:
    """Compile ``case.kernel``, run it and the reference, compare every
    output leaf. Returns whether the compiled text holds a
    ``tpu_custom_call``; with ``require_kernel`` (a TPU backend) a case that
    has a kernel and compiled without one raises."""
    import jax

    compiled = jax.jit(case.kernel).lower(*case.args).compile()
    compiled_kernel = "tpu_custom_call" in compiled.as_text()
    if require_kernel and case.has_kernel and not compiled_kernel:
        raise AssertionError(
            "compiled without a tpu_custom_call: the Pallas path did not run "
            "compiled (interpret mode or an XLA fallback)")
    got = jax.tree.leaves(compiled(*case.args))
    ref = jax.tree.leaves(jax.jit(case.reference)(*case.args))
    if len(got) != len(ref):
        raise AssertionError(f"{len(got)} outputs vs {len(ref)} reference")
    for i, (g, r) in enumerate(zip(got, ref)):
        _assert_close(f"output[{i}]", g, r, rtol=case.rtol)
    return compiled_kernel


def _sum_sq_with_grads(fn):
    """``(loss, grads)`` of ``sum(fn(*args)**2)`` w.r.t. every argument —
    drives the forward AND both backward kernels of ``fn``."""
    import jax
    import jax.numpy as jnp

    def loss(*args):
        return jnp.sum(fn(*args).astype(jnp.float32) ** 2)

    return lambda *args: jax.value_and_grad(
        loss, argnums=tuple(range(len(args))))(*args)


def _attention_case(b, t, s, h, d, seed=0, causal_offset=None) -> Case:
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.ops.pallas_attention import fused_attention

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (b, s, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (b, s, h, d)), jnp.bfloat16)

    def ref(q, k, v):
        logits = jnp.einsum(
            "bthd,bshd->bhts", q * (d ** -0.5), k,
            preferred_element_type=jnp.float32,
        )
        if causal_offset is not None:
            from perceiver_io_tpu.ops.masking import causal_mask

            logits = jnp.where(
                causal_mask(t, s, causal_offset)[None, None],
                jnp.finfo(jnp.float32).min, logits)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhts,bshd->bthd", probs, v)

    def ker(q, k, v):
        return fused_attention(q, k, v, causal_offset=causal_offset)

    return Case(_sum_sq_with_grads(ker), _sum_sq_with_grads(ref), (q, k, v))


def rel_to_peak(got, ref) -> float:
    """Max abs error over the reference's peak magnitude (the repo's parity
    measure); non-finite values raise."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.max(np.abs(got - ref))) / (float(np.max(np.abs(ref))) or 1.0)


def _assert_close(name, got, ref, rtol=0.05):
    try:
        err = rel_to_peak(got, ref)
    except AssertionError as e:
        raise AssertionError(f"{name}: {e}") from None
    if err > rtol:
        raise AssertionError(f"{name}: max rel-to-peak error {err:.3g} > {rtol}")


def _ce_case(rows, c, vocab, seed=0) -> Case:
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.ops.pallas_ce import pallas_linear_ce_integer

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (rows, c)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(0, 0.02, (c, vocab)), jnp.bfloat16)
    bias = jnp.asarray(rng.normal(0, 0.02, (vocab,)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, vocab, (rows,)).astype(np.int32))

    def ref_loss(x, w, bias):
        logits = (x.astype(jnp.float32) @ w.astype(jnp.float32)) + bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    def ker_loss(x, w, bias):
        return jnp.sum(pallas_linear_ce_integer(x, w, bias, labels))

    return Case(jax.value_and_grad(ker_loss, argnums=(0, 1, 2)),
                jax.value_and_grad(ref_loss, argnums=(0, 1, 2)),
                (x, w, bias))


def _quant_case() -> Case:
    """int8w dequant-inside-jit parity on the chip's compiler: quantize a
    small kernel tree, run the bf16 matmul over the in-program dequant, and
    check against the f32 oracle — pins that the convert*scale lowering
    stays numerically sane as the compiler moves (the serving engines'
    weight-only path, `perceiver_io_tpu.quant`)."""
    import jax.numpy as jnp

    from perceiver_io_tpu.quant import dequantize_tree, quantize_tree

    rng = np.random.default_rng(0)
    params = {
        "dense": {
            "kernel": rng.normal(0, 1, (256, 512)).astype(np.float32),
            "bias": rng.normal(0, 0.02, (512,)).astype(np.float32),
        }
    }
    x = jnp.asarray(rng.normal(0, 1, (64, 256)), jnp.bfloat16)

    def apply_fn(p, x):
        d = p["dense"]
        return x @ d["kernel"].astype(x.dtype) + d["bias"].astype(x.dtype)

    qp = quantize_tree(params, compute_dtype="bfloat16")
    return Case(lambda q, x: apply_fn(dequantize_tree(q), x),
                lambda q, x: apply_fn(params, x), (qp, x), has_kernel=False)


def _qmm_case(m, k, n, bits=8, group_size=None, compute_dtype="bfloat16",
              rtol=0.02, seed=0) -> Case:
    """Fused dequant-matmul kernel (ops/pallas_matmul) vs the XLA-dequant
    oracle over the SAME quantized values — any difference is purely
    kernel-vs-XLA, so the bound is tight. Pins that the int8/int4
    convert×scale-in-VMEM lowering and the block/padding resolution stay
    sane as Mosaic moves (scoped-VMEM and tiling refusals only surface on
    the chip's compiler)."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.ops.pallas_matmul import quantized_matmul
    from perceiver_io_tpu.quant.int8 import QKernel, quantize_array

    rng = np.random.default_rng(seed)
    w = rng.normal(0, 0.02, (k, n)).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.dtype(compute_dtype))
    q, scale = quantize_array(w, bits=bits, group_size=group_size)
    store = jnp.int8 if bits == 8 else jnp.int4
    qk = QKernel(jnp.asarray(q, store), jnp.asarray(scale), compute_dtype)

    # the kernel's f32 path is multi-pass (Precision.HIGHEST); on a TPU XLA's
    # DEFAULT f32 matmul is a single bf16 pass (3e-3 rel-to-peak away,
    # measured on a v5e, PR 22), so the oracle asks for the same precision
    return Case(
        lambda x, qk: quantized_matmul(x, qk, impl="pallas"),
        lambda x, qk: jnp.matmul(
            x.astype(qk.compute_dtype), qk.dequantize(),
            precision=jax.lax.Precision.HIGHEST).astype(x.dtype),
        (x, qk), rtol=rtol)


def _sp_case() -> Case:
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.ops.pallas_attention import (
        fused_attention,
        seq_parallel_fused_attention,
    )
    from perceiver_io_tpu.parallel import make_mesh

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (2, 256, 4, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (2, 4096, 4, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (2, 4096, 4, 16)), jnp.bfloat16)
    mesh = make_mesh(dp=1, tp=1, sp=jax.device_count())

    return Case(
        _sum_sq_with_grads(lambda q, k, v: seq_parallel_fused_attention(
            q, k, v, mesh=mesh, axis="seq")),
        _sum_sq_with_grads(fused_attention), (q, k, v))


CASES = {
    # _auto_kv_block d<=64 tier at long S: kv resolves to 2048
    "attn-32k-d16": lambda: _attention_case(1, 256, 32768, 4, 16),
    # d<=128 tier: kv resolves to 1024 (the in-8h family, shrunk for runtime)
    "attn-8k-d128": lambda: _attention_case(2, 512, 8192, 8, 128),
    # full-2048 KV stream at d=64 (the flow-self win) + q-bump interplay
    "attn-flowself-d64": lambda: _attention_case(2, 2048, 2048, 8, 64),
    # deep head exactly ON the q-bump s_blk*d guard: must resolve to the
    # safe 512 default, NOT the measured-OOM (1024, 512, 512) combo
    "attn-deep-d512": lambda: _attention_case(1, 2048, 2048, 1, 512),
    # lane-unaligned S: the pad-to-block streaming path
    "attn-awkward-s": lambda: _attention_case(1, 256, 2944, 4, 16),
    # flash-CE at the flagship gathered shape (10240 = 512*20, exact blocks)
    "ce-flagship": lambda: _ce_case(10240, 64, 10003),
    # flash-CE at the 131k-context gathered rows: 39328 = 32*1229 forces the
    # row-padding rule (the r3 +48% fix) — dead rows must stay exact
    "ce-padded-rows": lambda: _ce_case(39328, 64, 10003),
    # the shard_map'd sequence-parallel kernel compiled on real hardware
    "sp-shard": _sp_case,
    # weight-only int8: in-program dequant feeding a bf16 matmul stays
    # within parity vs the f32 oracle (the serving engines' int8w path;
    # XLA by design, no Pallas kernel)
    "quant-int8w-dequant": _quant_case,
    # -- fused dequant-matmul (ops/pallas_matmul) guard geometries --
    # the flagship vocab head (C=64 → 10003 padded to 10112): the single
    # biggest weight stream in the serving forward, lane-unaligned only
    # after class padding — the shape the int8w serving path lives on
    "qmm-int8-vocab-head": lambda: _qmm_case(512, 64, 10112, bits=8),
    # grouped int4 at the flagship MLP width: bk pinned to group_size=128
    # (the grouped-scale broadcast path), K a multiple of the group
    "qmm-int4-grouped-mlp": lambda: _qmm_case(2048, 512, 2048, bits=4,
                                              group_size=128),
    # sublane/lane-unaligned M/K/N: the zero-pad + slice path, f32 compute
    # (parity dtype) where kernel-vs-XLA must be near-exact
    "qmm-int8-awkward-f32": lambda: _qmm_case(
        96, 320, 161, bits=8, compute_dtype="float32", rtol=2e-5),
    # -- generative decode geometries (the in-kernel causal flag) --
    # causal prefill at the d<=128 wide-KV tier (kv resolves to 2048 with
    # the q-bump interplay): fwd + BOTH backward kernels recompute the same
    # in-kernel causal bias — parity vs the masked-einsum oracle
    "attn-causal-prefill-d128": lambda: _attention_case(
        2, 512, 8192, 8, 128, causal_offset=7680),
    # square-causal self-attention exactly ON the q-bump s_blk*d guard
    # (must resolve to the safe default like its non-causal twin)
    "attn-causal-deep-d512": lambda: _attention_case(
        1, 2048, 2048, 1, 512, causal_offset=0),
    # the q_len=1 incremental decode cross over a long token ring at the
    # VMEM-guard KV tier — the serving step shape (ring validity rides the
    # causal offset here; the engine uses a pad mask, same masking math)
    "attn-q1-decode-32k": lambda: _attention_case(
        1, 1, 32768, 4, 128, causal_offset=32767),
    # -- continuous-batching arena geometries (batch = arena slots) --
    # the arena's batched q_len=1 step at the d<=128 VMEM-guard KV tier:
    # 8 slots × one decode row each over the long ring — the vmapped-step
    # dispatch shape, which must ride the SAME block resolution as b=1
    # (batch is grid-parallel; the per-block VMEM guard maths must not move)
    "attn-arena8-q1-32k": lambda: _attention_case(
        8, 1, 32768, 4, 128, causal_offset=32767),
    # batched causal prefill across a 16-slot arena at the d<=64 wide-KV
    # tier: admission re-encodes burst-compile this exact family
    "attn-arena16-prefill-d64": lambda: _attention_case(
        16, 256, 2048, 8, 64, causal_offset=1792),
}


def run(out_path: str | None, dry: bool = False) -> int:
    if dry:
        # --dry: the stdout-contract mode — emit the one JSON line without
        # touching ANY device (no jax import; what CI uses to pin the
        # one-JSON-line-on-stdout invariant)
        report = {
            "metric": "kernel_smoke",
            "dry": True,
            "backend": None,
            "device": None,
            "passed": 0,
            "total": len(CASES),
            "cases": [],
            "skipped": sorted(CASES),
            "failures": {},
        }
        line = emit_json_line(report)
        if out_path:
            with open(out_path, "w") as f:
                f.write(line + "\n")
        return 0

    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()
    backend = probe_backend()
    results, compiled_kernels, failures = [], [], {}
    for name, build in CASES.items():
        try:
            if run_case(build(), require_kernel=backend.backend == "tpu"):
                compiled_kernels.append(name)
            results.append(name)
        except Exception as e:  # noqa: BLE001 — every failure belongs in the artifact
            failures[name] = f"{type(e).__name__}: {str(e)[:300]}"
    report = {
        "metric": "kernel_smoke",
        "backend": backend.backend,
        "device": backend.device_kind,
        "passed": len(results),
        "total": len(CASES),
        "cases": results,
        "compiled_kernels": compiled_kernels,
        "failures": failures,
    }
    line = emit_json_line(report)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")
    return 1 if failures else 0


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--out", default=None, help="also write the JSON here")
    p.add_argument("--dry", action="store_true",
                   help="emit the JSON report shape without running any case "
                        "or touching a device (stdout-contract CI mode)")
    args = p.parse_args()
    raise SystemExit(run(args.out, dry=args.dry))


if __name__ == "__main__":
    main()
