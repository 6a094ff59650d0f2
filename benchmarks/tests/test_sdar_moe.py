"""The ``sdar_moe`` family's own benchmark code: its operations count against
a hand count at the cell's sizes, its component table (and that the two
readers it shares with the other decoder families read its step alike), its
kernels' roofline readers, the cell's entries in ``BENCHMARK.json``, and the
whole training loop at tiny sizes on the CPU: ``correct`` with the program
as it is, and not with a mask that lets a noised token read its own clean
block."""

import os
import re
import time

import pytest

from benchmarks import components_decoder_lm, components_lfm2_moe
from benchmarks import components_sdar_moe as components
from benchmarks import flops_sdar_moe as flops
from benchmarks import run as run_mod, trace, traffic
from benchmarks.configs import sdar_moe as builder
from benchmarks.loops import train_fit

CELL = "sdar_30b_a3b_chat_bd4_ep8_train"


def _cell():
    bench = run_mod.load_benchmark()
    cell = run_mod.find_cell(bench, CELL)
    return run_mod.load_config(cell["config"]), traffic.load_mix(cell["traffic"])


def test_train_flops_against_a_hand_count():
    """At the cell's sizes: 67,141,632 visible pairs a layer (L (L + B) for
    L = 8,192, B = 4), attention 54% of the 24.5e12 operations a step."""
    cfg, _ = _cell()
    assert flops.visible_pairs(8192, 4) == 8192 * 8196 == 67141632
    parts = flops.forward_parts(cfg, 8192)
    assert parts["attention_kernels"] == 4 * 2.0 * 67141632 * 32 * 2 * 128
    assert parts["attention_projections"] == 4 * 2.0 * 16384 * 2048 * (64 + 8) * 128
    # 16,384 positions x top 8 x 16 / 128 held: 16,384 rows a layer, three products
    assert parts["routed_experts"] == 4 * 3 * 2.0 * 16384 * 2048 * 768
    assert parts["head"] == 2.0 * 8192 * 2048 * 18992
    total = flops.train_flops_per_sample(cfg, 8192)
    assert total == pytest.approx(24.5e12, rel=0.01)
    assert 3 * parts["attention_kernels"] / total == pytest.approx(0.54, abs=0.01)


SCOPES = {
    "jit(train_step)/bd_noise/random_bits": components.OTHER,
    "jit(train_step)/jvp(DecoderLM)/embed/gather": "embed_head_loss",
    "jit(train_step)/jvp(DecoderLM)/layer_0/attn/gqa_attention/q_proj/dot_general": "gqa_attention",
    ("jit(train_step)/transpose(jvp(DecoderLM))/layer_2/attn/gqa_attention/"
     "jit(_fused_attention_bwd_impl)/fused_attention_dq/pallas_call"): "gqa_attention",
    "jit(train_step)/jvp(DecoderLM)/layer_1/moe/moe/router/dot_general": "moe",
    "jit(train_step)/jvp(DecoderLM)/head_loss/dot_general": "embed_head_loss",
    "jit(train_step)/jvp(DecoderLM)/layer_3/attn_norm/mul": components.OTHER,
    "jit(train_step)/adamw/mul": components.OTHER,
}


def _summary(step_scope_seconds, ops=()):
    return trace.Summary(
        busy_s=1.0, window_s=1.0, step_name="jit_train_step", step_durations_ms=[500.0, 500.0],
        step_gaps_ms=[0.0],
        device_ops=[], idle_gaps=[], op_seconds={op: 0.1 for op in ops},
        scope_seconds=dict(step_scope_seconds), step_scope_seconds=dict(step_scope_seconds),
        whole_steps=2)


def test_components_are_disjoint_add_up_and_agree_with_the_shared_readers(monkeypatch):
    monkeypatch.setattr(builder, "BUILT", {"cfg": _cell()[0], "batch_size": 1, "width": 8192})
    for scope, want in SCOPES.items():
        assert components.component_of(scope) == want
        matches = [name for name, pattern in components.SDAR_STEP if re.search(pattern, scope)]
        assert matches == ([want] if want != components.OTHER else [])  # one pattern a scope
    summary = _summary({scope: 0.25 * (i + 1) for i, scope in enumerate(SCOPES)})
    seconds = components.step_seconds(summary)
    assert sum(seconds.values()) == pytest.approx(sum(summary.step_scope_seconds.values()))
    names = [name for name, _ in components.SDAR_STEP] + [components.OTHER]
    total = sum(components.step_ms(summary, name) for name in names)
    assert total == pytest.approx(1e3 * sum(summary.step_scope_seconds.values()) / 2)
    # the accepted readers this cell is added to read the same scopes here
    for shared in ("moe", "embed_head_loss"):
        assert components_decoder_lm.step_ms(summary, shared) == pytest.approx(
            components.step_ms(summary, shared))
        assert components_lfm2_moe.step_seconds(summary)[shared] == pytest.approx(seconds[shared])
    # a process that built no model of this family (another family's step) reads nothing
    monkeypatch.setattr(builder, "BUILT", None)
    assert components.step_ms(summary, "gqa_attention") is None
    assert components.step_ms(None, "moe") is None


def test_kernel_roofline_readers(monkeypatch):
    cfg, mix = _cell()
    built = {"cfg": cfg, "batch_size": mix["batch_size"], "width": 8192}
    scope = ("jit(train_step)/transpose(jvp(DecoderLM))/layer_2/attn/gqa_attention/"
             "jit(_fused_attention_bwd_impl)/fused_attention_dkv/pallas_call")
    ops = ["%fused_attention_dkv.1 = (bf16[1,4,16384,128]{3,2,1,0}, bf16[1,4,16384,128]) custom-call(",
           "%fusion.7 = bf16[1,16384,4,128]{3,2,1,0} fusion(bf16[1,4,16384,128] %fused_attention_dkv.1)"]
    ctx = {"summary": _summary({scope: 0.08}, ops=ops)}
    required, moved = flops.attention_dkv(cfg, 1, 8192)
    assert required == 2.0 * 32 * 67141632 * 4 * 128
    assert required / 197e12 > moved / 819e9  # bound by operations, not bytes
    got = components.kernel_roofline_pct(ctx, "fused_attention_dkv", built, "TPU v5 lite")
    assert got == pytest.approx(100 * required / 197e12 * 2 / 0.08)
    # a program without the kernel, or a process that built no model of the family
    assert components.kernel_roofline_pct(ctx, "fused_attention_fwd", built, "TPU v5 lite") is None
    monkeypatch.setattr(builder, "BUILT", None)
    assert components.kernel_roofline_pct(ctx, "fused_attention_dkv") is None


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = run_mod.load_benchmark()
    cell = run_mod.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_ids_b1_w8192_v18991"
    names = {m["name"] for m in run_mod.metrics_of(bench, cell, "per_layer")}
    own = {"sdar_attention_device_ms.train", "sdar_other_device_ms.train",
           "sdar_attention_fwd_roofline_pct.train", "sdar_attention_dq_roofline_pct.train",
           "sdar_attention_dkv_roofline_pct.train", "bd_masked_pct.train"}
    shared = {"moe_device_ms.train", "embed_head_loss_device_ms.train", "step_mfu_pct.train",
              "step_device_ms.train", "moe_load_max_over_mean.train", "moe_bounded_path_pct.train",
              "attention_residuals_kept_pct.train", "device_idle_pct.train", "import_s.setup",
              "setup_fit_s.setup", "jit_backend_compile_s.setup"}
    assert own | shared <= names
    # the other families' own readers, which would read the wrong family's sizes
    assert not names & {"gqa_attention_device_ms.train", "nemotron_h_attention_device_ms.train",
                        "fused_attention_fwd_roofline_pct.train", "mla_attention_device_ms.train",
                        "moe_local_assignment_gap_pct.train", "ssd_scan_kernel_pct.train"}
    for name in names:
        assert os.path.exists(os.path.join(run_mod.HERE, "metrics", f"{name}.py")), name
    end_to_end = {m["name"] for m in run_mod.metrics_of(bench, cell, "end_to_end")}
    assert end_to_end == {"train_samples_per_s", "setup_s"}
    cfg, mix = _cell()
    spec = mix["fields"]["token_ids"]
    assert (mix["batch_size"], spec["width"], spec["high"]) == (1, 8192, cfg["mask_token_id"])
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1 == 18991


def _tiny():
    """The cell with every width cut (the tier-1 tests' tiny cell, a quarter
    share of 16 experts): rows of 40 tokens in blocks of 4."""
    cfg, mix = _cell()
    cfg.update(vocab_size=96, hidden_size=32, moe_intermediate_size=16, num_attention_heads=8,
               num_key_value_heads=2, head_dim=8, num_hidden_layers=2, num_experts=4,
               mask_token_id=95, dtype="float32")
    cfg["deployment"] = dict(cfg["deployment"], experts_held=4, num_experts_published=16)
    mix.update(batch_size=2, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=40, high=95, length_low=40, length_high=40)
    return {"name": CELL}, cfg, mix, builder


def _leaking(rows, cols, half, block):
    """The mask with one fault: a noised token also reads the clean copy of
    its OWN block."""
    import jax.numpy as jnp

    q_clean, k_clean = rows >= half, cols >= half
    q_block = jnp.where(q_clean, rows - half, rows) // block
    k_block = jnp.where(k_clean, cols - half, cols) // block
    return (k_clean & (k_block <= q_block)) | (~q_clean & ~k_clean & (k_block == q_block))


# limits for the loop in float32 at tiny sizes: the program reads 1e-6 (round-off)
TINY_LIMITS = {"grad_gap": 1e-3, "delta_gap": 1e-3}


@pytest.mark.parametrize("leak", [False, True], ids=["as_it_is", "leaking_mask"])
def test_the_loop_is_correct_and_sees_a_leaking_mask(leak, monkeypatch):
    """The cell's loop at tiny sizes on the CPU, in float32: the program's
    three steps against the reference's, then the window. A noised token that
    reads the clean copy of its own block sees 4 more of the 4 to 44 keys a
    query of a row of 40 reads: the gradients' norms move by a quarter. (At
    the cell's size and bfloat16 they move less than the rounding does:
    PERF.md section 2.)"""
    import jax

    from perceiver_io_tpu.ops import masking

    if leak:
        monkeypatch.setattr(masking, "block_diffusion_visible", _leaking)
    # jax's tracing caches hold the blocks as they were first traced
    jax.clear_caches()
    cell, cfg, mix, family = _tiny()
    start = time.time()
    probes = run_mod.Probes(clock=lambda: time.time() - start, compiles=lambda: 0)
    result = train_fit.run(cell, cfg, mix, family, 2**31 + 47, 0.5, False, probes,
                           limits=TINY_LIMITS)
    assert result["verdict"]["correct"] is (not leak)
    assert result["failed"] == 0 and result["steps"] > 0
    numbers = result["numbers"]
    if leak:  # 0.26 and 0.083
        assert numbers["grad_gap"] > 0.1 and numbers["delta_gap"] > 0.03
    else:
        assert numbers["grad_gap"] < 1e-4 and numbers["delta_gap"] < 1e-4
    jax.clear_caches()
