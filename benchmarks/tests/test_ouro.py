"""The ``ouro`` family's own benchmark code: its operations count against a
hand count, its component table (the components add up to the step, the
attention is a part of the stack), its kernels' roofline readers (executions
a whole step under the scan), every new reader silent where another family
was built, the configuration's deployment arithmetic, the cell's entries in
``BENCHMARK.json``, and the whole training loop at tiny sizes on the CPU:
``correct`` with the program as it is, and not with a stack that skips the
final norm between loop steps."""

import os
import re
import time

import pytest

from benchmarks import components_ouro as components
from benchmarks import flops_ouro as flops
from benchmarks import run as run_mod, trace, traffic
from benchmarks.configs import ouro as builder
from benchmarks.loops import train_fit

CELL = "ouro_2_6b_ut4_train"
OWN = ("ouro_stack_device_ms.train", "ouro_attention_device_ms.train",
       "ouro_exits_device_ms.train", "ouro_other_device_ms.train",
       "ouro_attention_fwd_roofline_pct.train", "ouro_attention_dq_roofline_pct.train",
       "ouro_attention_dkv_roofline_pct.train", "ut_block_applications.train")


def _cell():
    bench = run_mod.load_benchmark()
    cell = run_mod.find_cell(bench, CELL)
    return run_mod.load_config(cell["config"]), traffic.load_mix(cell["traffic"])


def test_train_flops_by_hand_at_a_small_size():
    """Two layers looped three times over rows of 8 tokens, hidden 4, 2 heads
    of 2, SwiGLU 6, a vocabulary of 10: every part by hand."""
    sz = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 2,
          "intermediate_size": 6, "vocab_size": 10, "num_hidden_layers": 2, "total_ut_steps": 3}
    parts = flops.forward_parts(sz, 8)
    assert flops.applications(sz) == 6
    assert parts["attention_projections"] == 6 * 2 * 8 * 4 * 16       # q, k, v, o: 4 x (4 -> 4)
    assert parts["attention_kernels"] == 6 * 2 * 36 * 2 * 2 * 2       # 36 pairs, 2 heads, 2 products
    assert parts["ffn"] == 6 * 3 * 2 * 8 * 4 * 6
    assert parts["exit_heads"] == 3 * 2 * 7 * 4 * 10                  # 7 targets a row, 3 exits
    assert parts["exit_gates"] == 3 * 2 * 8 * 4
    assert flops.train_flops_per_sample(sz, 8) == 3 * sum(parts.values())


def test_train_flops_at_the_cells_size():
    """24 block applications of 4,096 tokens and four exits: 45.15e12
    operations a step: the stack's matmuls 67%, attention 11%, the exits 22%."""
    cfg, mix = _cell()
    parts = flops.forward_parts(cfg, 4096)
    total = flops.train_flops_per_sample(cfg, 4096)
    assert total == pytest.approx(45.15e12, rel=1e-3)
    share = {k: 3 * v / total for k, v in parts.items()}
    assert share["attention_projections"] + share["ffn"] == pytest.approx(0.671, abs=1e-3)
    assert share["attention_kernels"] == pytest.approx(0.110, abs=1e-3)
    assert share["exit_heads"] + share["exit_gates"] == pytest.approx(0.219, abs=1e-3)
    # one execution of each kernel is one block application
    fwd, _ = flops.attention_fwd(cfg, 1, 4096)
    assert 24 * fwd == parts["attention_kernels"]


def test_deployment_arithmetic_from_the_configurations_keys():
    cfg, _ = _cell()
    d, w, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layer = d * hd * (2 * h + 2 * kv) + 3 * d * w + 4 * d
    count = cfg["num_hidden_layers"] * layer + 2 * v * d + d + (d + 1)
    deployment = cfg["deployment"]
    assert layer == 51388416 and count == deployment["parameters_count"] == 509661185
    assert 16 * count == deployment["bytes_at_16"] == 8154578960
    assert (deployment["pipeline_stages"] * deployment["layers_per_stage"]
            == deployment["num_hidden_layers_published"])
    assert f"{count:,}" in deployment["parameters"] and f"{layer:,}" in deployment["parameters"]


SCOPES = {
    "jit(train_step)/jvp(DecoderLM.loss)/DecoderLM._looped_loss/DecoderLM.hidden_states/embed/"
    "gather": components.OTHER,
    ("jit(train_step)/jvp(DecoderLM.loss)/DecoderLM._looped_loss/DecoderLM.hidden_states/ut_stack/"
     "while/body/closed_call/loop/loop/checkpoint/layer_2/mlp/up/dot_general"): "ut_stack",
    ("jit(train_step)/transpose(jvp(DecoderLM.loss))/DecoderLM._looped_loss/DecoderLM.hidden_states/"
     "ut_stack/while/body/closed_call/loop/loop/checkpoint/layer_5/attn/gqa_attention/"
     "jit(_fused_attention_bwd_impl)/fused_attention_dq/pallas_call"): "ut_stack",
    "jit(train_step)/loop/layer_0/attn/gqa_attention/cos": "ut_stack",  # hoisted out of the loop
    ("jit(train_step)/transpose(jvp(DecoderLM.loss))/DecoderLM._looped_loss/DecoderLM.hidden_states/"
     "ut_stack/while/body/closed_call/loop/final_norm/mul"): "ut_stack",
    ("jit(train_step)/jvp(DecoderLM.loss)/DecoderLM._looped_loss/ut_exits/DecoderLM._exit_log_probs/"
     "exit_gate/dot_general"): "ut_exits",
    "jit(train_step)/transpose(jvp(DecoderLM.loss))/DecoderLM._looped_loss/ut_exits/while/body/"
    "dot_general": "ut_exits",
    "jit(train_step)/adamw/mul": components.OTHER,
    "": components.OTHER,
}


def _summary(step_scope_seconds, ops=()):
    return trace.Summary(
        busy_s=1.0, window_s=1.0, step_name="jit_train_step", step_durations_ms=[500.0, 500.0],
        step_gaps_ms=[0.0], device_ops=[], idle_gaps=[], op_seconds={op: 0.1 for op in ops},
        scope_seconds=dict(step_scope_seconds), step_scope_seconds=dict(step_scope_seconds),
        whole_steps=2)


def test_components_are_disjoint_and_add_up_attention_a_part_of_the_stack(monkeypatch):
    """Over two whole steps of 5 s each: the stack's four scopes 0.5 + 0.75 +
    1.0 + 1.25 s, the exits' two 1.5 + 1.75 s; the rest of a step is what the
    two leave of its device time (the unscoped events, 2.25 + 2.5 s here, are
    not summed: a loop's own event encloses its body's operations)."""
    cfg, mix = _cell()
    monkeypatch.setattr(builder, "BUILT", {"cfg": cfg, "batch_size": 1, "width": 4096})
    for scope, want in SCOPES.items():
        assert components.component_of(scope) == want
    summary = _summary({scope: 0.25 * (i + 1) for i, scope in enumerate(SCOPES)})
    summary.step_durations_ms = [5000.0, 5000.0]
    stack, exits = (components.step_ms(summary, name) for name in ("ut_stack", "ut_exits"))
    assert stack == pytest.approx(1e3 * (0.5 + 0.75 + 1.0 + 1.25) / 2)
    assert exits == pytest.approx(1e3 * (1.5 + 1.75) / 2)
    other = components.step_ms(summary, components.OTHER)
    assert stack + exits + other == pytest.approx(5000.0) and other > 0
    attention = components.attention_ms(summary)
    assert attention == pytest.approx(1e3 * (0.75 + 1.0) / 2)
    assert 0 < attention < stack


def test_kernel_roofline_counts_executions_a_whole_step(monkeypatch):
    """Six instructions of the kernel, one a layer in the scan's body, run four
    times a step each: 24 executions; an unrolled program's 24 instructions
    are not multiplied again."""
    cfg, mix = _cell()
    built = {"cfg": cfg, "batch_size": mix["batch_size"], "width": 4096}
    scope = ("jit(train_step)/transpose(jvp(DecoderLM.loss))/ut_stack/while/body/loop/loop/"
             "checkpoint/layer_1/attn/gqa_attention/jit(_fused_attention_bwd_impl)/"
             "fused_attention_dkv/pallas_call")
    ops = [f"%fused_attention_dkv.{i} = (bf16[1,16,4096,128]{{3,2,1,0}}, bf16[1,16,4096,128]) "
           "custom-call(" for i in range(6)]
    ctx = {"summary": _summary({scope: 0.4}, ops=ops)}
    assert components.executions(ctx["summary"], "fused_attention_dkv", built) == 24
    required, moved = flops.attention_dkv(cfg, 1, 4096)
    assert required == 2.0 * 16 * 8390656 * 4 * 128
    assert required / 197e12 > moved / 819e9  # bound by operations, not bytes
    got = components.kernel_roofline_pct(ctx, "fused_attention_dkv", built, "TPU v5 lite")
    assert got == pytest.approx(100 * required / 197e12 * 24 * 2 / 0.4)
    unrolled = _summary({scope: 0.4}, ops=[op.replace(".", f".{j}", 1) for op in ops
                                           for j in range(4)])
    assert components.executions(unrolled, "fused_attention_dkv", built) == 24
    assert components.kernel_roofline_pct(ctx, "fused_attention_fwd", built, "TPU v5 lite") is None


@pytest.mark.parametrize("name", OWN)
def test_every_new_reader_is_silent_where_another_family_was_built(name, monkeypatch):
    """Another family's step has ``gqa_attention`` and kernel scopes too; with
    no ouro model built in this process, and no gauge of its own, each reader
    gives nothing."""
    from perceiver_io_tpu import obs

    monkeypatch.setattr(builder, "BUILT", None)
    monkeypatch.setattr(obs.get_registry(), "snapshot", lambda: {"gauges": {}})
    scope = ("jit(train_step)/transpose(jvp(DecoderLM))/layer_2/attn/gqa_attention/"
             "jit(_fused_attention_bwd_impl)/fused_attention_dq/pallas_call")
    ops = ["%fused_attention_dq.3 = (bf16[1,32,16384,128]{3,2,1,0}) custom-call("]
    ctx = {"summary": _summary({scope: 0.3, "jit(train_step)/adamw/mul": 0.1}, ops=ops),
           "peak_flops": 197e12}
    assert run_mod.read_metric(name, ctx) is None


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = run_mod.load_benchmark()
    cell = run_mod.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_ids_b1_w4096_v49152"
    names = {m["name"] for m in run_mod.metrics_of(bench, cell, "per_layer")}
    shared = {"step_mfu_pct.train", "step_device_ms.train", "dispatch_gap_ms.train",
              "attention_residuals_kept_pct.train", "device_idle_pct.train", "import_s.setup",
              "setup_fit_s.setup", "jit_backend_compile_s.setup", "warmup_xla_compiles"}
    assert set(OWN) | shared <= names
    # the other families' readers, which would read another family's sizes or scopes
    assert not {n for n in names if re.match(r"(sdar|nemotron|gqa_attention|mla|moe|bd_|lfm2)", n)}
    for name in names:
        assert os.path.exists(os.path.join(run_mod.HERE, "metrics", f"{name}.py")), name
    end_to_end = {m["name"] for m in run_mod.metrics_of(bench, cell, "end_to_end")}
    assert end_to_end == {"train_samples_per_s", "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s"
    cfg, mix = _cell()
    spec = mix["fields"]["token_ids"]
    assert (mix["batch_size"], spec["width"], spec["high"]) == (1, 4096, cfg["vocab_size"])


def _tiny():
    """The cell with every width cut (the tier-1 tests' tiny cell)."""
    cfg, mix = _cell()
    cfg.update(vocab_size=97, hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
               layer_types=["full_attention"] * 2, total_ut_steps=3, dtype="float32")
    mix.update(batch_size=2, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=32, high=97, length_low=32, length_high=32)
    return {"name": CELL}, cfg, mix, builder


# limits for the loop in float32 at tiny sizes: the program reads round-off
TINY_LIMITS = {"grad_gap": 1e-3, "delta_gap": 1e-3}


@pytest.mark.parametrize("fault", [False, True], ids=["as_it_is", "no_norm_between_steps"])
def test_the_loop_is_correct_and_sees_a_missing_norm(fault, monkeypatch):
    """The cell's loop at tiny sizes on the CPU, in float32: the program's
    three steps against the reference's, then the window."""
    import jax
    from flax import linen as nn

    from perceiver_io_tpu.models import decoder_lm
    from perceiver_io_tpu.ops.latent_attention import RMSNorm

    class NoNormBetweenSteps(decoder_lm.LoopStep):
        @nn.compact
        def __call__(self, x, _):
            c = self.config
            block = nn.remat(decoder_lm.DecoderBlock, policy=self.policy)
            for i, (mixer, ffn) in enumerate(c.blocks):
                x, _ = block(c, mixer, ffn, self.attn_impl, self.expert_impl, self.dtype,
                             name=f"layer_{i}")(x)
            return x, RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x)

    if fault:
        monkeypatch.setattr(decoder_lm, "LoopStep", NoNormBetweenSteps)
    jax.clear_caches()
    cell, cfg, mix, family = _tiny()
    start = time.time()
    probes = run_mod.Probes(clock=lambda: time.time() - start, compiles=lambda: 0)
    result = train_fit.run(cell, cfg, mix, family, 2**31 + 45, 0.5, False, probes,
                           limits=TINY_LIMITS)
    assert result["verdict"]["correct"] is (not fault)
    assert result["failed"] == 0 and result["steps"] > 0
    numbers = result["numbers"]
    if fault:
        assert numbers["grad_gap"] > 0.01
    else:
        assert numbers["grad_gap"] < 1e-4 and numbers["delta_gap"] < 1e-4
    jax.clear_caches()
