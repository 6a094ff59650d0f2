"""The ``decoder_lm`` family's own benchmark code: its operations count
against a hand count at the cell's sizes, its component table, its kernels'
roofline reader, and the reference's memory by the README's recipe."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks import components_decoder_lm as components, flops_decoder_lm as flops
from benchmarks import run as run_mod, trace, traffic
from benchmarks.configs import decoder_lm as builder

CELL = "joyai_llm_flash_ep32_train"


def _cell():
    bench = run_mod.load_benchmark()
    cell = run_mod.find_cell(bench, CELL)
    return run_mod.load_config(cell["config"]), traffic.load_mix(cell["traffic"])


def test_train_flops_against_a_hand_count():
    """4,096 tokens a row at the published widths, by hand (2 x multiply-adds,
    the causal triangle 4096 x 4097 / 2 = 8,390,656 pairs, 1,024 expected
    assignments of the 8 held experts a row and layer)."""
    cfg, mix = _cell()
    t, d, tri = 4096, 2048, 8390656
    mla = (2 * t * d * 1536 + 2 * t * 1536 * 32 * 192 + 2 * t * d * 576
           + 2 * t * 512 * 32 * 256 + 2 * tri * 32 * 320 + 2 * t * 4096 * d)
    assert mla == 387662741504  # 25.77 + 77.31 + 9.66 + 34.36 + 171.84 + 68.72 e9
    hand = {
        "mla": 6 * mla,                       # 5 layers + the MTP block
        "dense_ffn": 6 * t * d * 7168,        # three products of 2 t d w
        "shared_experts": 5 * 6 * t * d * 768,
        "routed_experts": 5 * 6 * 1024 * d * 768,
        "router": 5 * 2 * t * d * 256,
        "mtp_projection": 2 * t * 4096 * d,
        "heads": 2 * (4095 + 4094) * d * 16160,
    }
    parts = flops.forward_parts(cfg, t)
    assert parts == pytest.approx(hand, rel=1e-12)
    per_sample = builder.train_flops_per_sample(cfg, mix, [{"token_ids": jnp.zeros((4, t))}])
    assert per_sample == pytest.approx(3 * sum(hand.values()), rel=1e-12)
    # ISSUE 32's count: 42.7e12 a step of four rows, latent attention 65% of it
    assert 4 * per_sample == pytest.approx(42.7e12, rel=5e-3)
    assert parts["mla"] / sum(parts.values()) == pytest.approx(0.65, abs=0.01)


def test_kernel_counts():
    cfg, _ = _cell()
    ops, moved = flops.attention_fwd(cfg, 4, 4096)
    assert ops == 2 * 4 * 32 * 8390656 * 320
    assert moved == 2 * 4 * 32 * 4096 * (192 + 192 + 128 + 128) + 2 * 4 * 128 * 4 * 32 * 4096
    ops, moved = flops.grouped_matmul(cfg, 4, 4096)
    assert ops == 2 * 4096 * 2048 * 768  # 16,384 x 8 x 8 / 256 = 4,096 assignments
    assert moved == 2 * (4096 * (2048 + 768) + 8 * 2048 * 768)
    assert set(flops.KERNELS) == {
        "fused_attention_fwd", "fused_attention_dq", "fused_attention_dkv",
        "grouped_matmul", "grouped_matmul_transposed"}


SCOPES = {
    "jit(train_step)/jvp(DecoderLM)/embed/embed/take": "embed_head_loss",
    "jit(train_step)/transpose(jvp(DecoderLM))/layer_2/checkpoint/rematted_computation/attn/"
    "mla_attention/fused_attention_fwd/pallas_call": "mla_attention",
    "jit(train_step)/jvp(DecoderLM)/layer_1/moe/moe/dispatch/sort": "moe",
    "jit(train_step)/jvp(DecoderLM)/mtp/mtp_block/attn/mla_attention/q_a/dot_general": "mtp",
    "jit(train_step)/jvp(DecoderLM)/mtp_block/moe/moe/experts/grouped_matmul/pallas_call": "mtp",
    "jit(train_step)/jvp(DecoderLM)/mtp/checkpoint/head_loss/dot_general": "mtp",
    "jit(train_step)/transpose(jvp(DecoderLM))/checkpoint/head_loss/dot_general": "embed_head_loss",
    "jit(train_step)/jvp(DecoderLM)/layer_0/mlp/gate/dot_general": "lm_other",
    "jit(train_step)/add": "lm_other",
    "": "lm_other",
}


def _summary(step_scope_seconds, whole_steps=2, ops=()):
    return trace.Summary(
        window_s=1.0, busy_s=1.0, step_name="jit_train_step", step_durations_ms=[1.0] * 4,
        step_gaps_ms=[], device_ops=[], idle_gaps=[], op_seconds={op: 0.1 for op in ops},
        scope_seconds={}, step_scope_seconds=step_scope_seconds, whole_steps=whole_steps)


def test_components_are_disjoint_and_add_up():
    for scope, want in SCOPES.items():
        assert components.component_of(scope) == want
        # disjoint by construction: one answer a scope; and the order matters
        # only for the MTP module, whose scopes also carry the others' names
        matches = [name for name, pattern in components.LM_STEP
                   if re.search(pattern, scope)]
        assert matches[:1] == ([want] if want != "lm_other" else [])
    summary = _summary({scope: 0.25 * (i + 1) for i, scope in enumerate(SCOPES)})
    seconds = components.step_seconds(summary)
    assert sum(seconds.values()) == pytest.approx(sum(summary.step_scope_seconds.values()))
    names = [name for name, _ in components.LM_STEP] + [components.OTHER]
    total = sum(components.step_ms(summary, name) for name in names)
    assert total == pytest.approx(1e3 * sum(summary.step_scope_seconds.values()) / 2)
    # another family's step (no scope of this one's): nothing to read
    assert components.step_ms(_summary({"jit(train_step)/encoder/x": 1.0}), "moe") is None
    assert components.step_ms(None, "moe") is None


def _built():
    cfg, mix = _cell()
    return {"cfg": cfg, "batch_size": mix["batch_size"],
            "width": mix["fields"]["token_ids"]["width"]}


def test_kernel_roofline_reader(monkeypatch):
    """The executions a step are the trace's: the HLO instructions named for
    the kernel, whatever else carries its name as a prefix or an operand."""
    built = _built()
    scope = ("jit(train_step)/transpose(jvp(DecoderLM))/layer_1/attn/mla_attention/"
             "jit(_fused_attention_bwd_impl)/fused_attention_dq/pallas_call")
    other = scope.replace("fused_attention_dq", "fused_attention_dkv")
    ops = [f"%fused_attention_dq.{i} = bf16[4,32,4096,192]{{3,2,1,0}} custom-call(bf16[4,32" for i in (12, 13, 14)]
    ops += ["%fused_attention_dq = bf16[4,32,4096,192]{3,2,1,0} custom-call(bf16[4,32",
            "%fused_attention_dkv.3 = (bf16[4,32,4096,192]{3,2,1,0}, bf16[4,32,4096,128]) custom-call(",
            "%fusion.7 = bf16[4,4096,32,192]{3,2,1,0} fusion(bf16[4,32,4096,192] %fused_attention_dq.12), kind=kLoop"]
    summary = _summary({scope: 0.2, other: 0.4}, ops=ops)
    assert components.executions(summary, "fused_attention_dq") == 4
    assert components.executions(summary, "fused_attention_dkv") == 1
    assert components.executions(summary, "fused_attention_fwd") == 0
    ctx = {"summary": summary}
    required, moved = flops.attention_dq(built["cfg"], 4, 4096)
    least = max(required / 197e12, moved / 819e9) * 4
    got = components.kernel_roofline_pct(ctx, "fused_attention_dq", built, "TPU v5 lite")
    assert got == pytest.approx(100 * least * 2 / 0.2)
    # half the executions in the same time (a step that stopped recomputing
    # the kernel and got no faster) reads half the share, not the same
    fewer = {"summary": _summary({scope: 0.2}, ops=ops[:2])}
    assert components.kernel_roofline_pct(
        fewer, "fused_attention_dq", built, "TPU v5 lite") == pytest.approx(got / 2)
    # a program without the kernel, or a process that built no model of the family
    assert components.kernel_roofline_pct(ctx, "grouped_matmul", built, "TPU v5 lite") is None
    monkeypatch.setattr(builder, "BUILT", None)
    assert components.kernel_roofline_pct(ctx, "fused_attention_dq") is None
    assert components.gauge("no_such_gauge_anywhere") is None


def test_local_assignment_gap_reader(monkeypatch):
    """|share - 100 x 8 / 256|: a router that drifts to the held experts and
    one that drifts away both read above 0."""
    from perceiver_io_tpu import obs

    built = _built()
    gauge = obs.get_registry().gauge("moe_local_assignment_pct")
    for share, gap in ((3.125, 0.0), (9.0, 5.875), (0.0, 3.125)):
        gauge.set(share)
        assert components.local_assignment_gap_pct(built) == pytest.approx(gap)
    monkeypatch.setattr(builder, "BUILT", None)  # a process that built no model of the family
    assert components.local_assignment_gap_pct() is None


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_reference_fits_the_chip_by_the_readmes_recipe(one_chip):
    """20 bytes a parameter (a batch is four blocks of one row) plus the
    temporaries of one row's ``value_and_grad``, lowered for a described v5e,
    stay under the chip's 16.9 GB: 9.83 + 2.27 GB (sandbox compile, PR 32)."""
    from benchmarks.reference import common

    cfg, mix = _cell()
    task = builder.reference_task(cfg)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, builder.param_shapes(cfg))
    width = mix["fields"]["token_ids"]["width"]
    row = jax.ShapeDtypeStruct((task["block_rows"], width), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(task["ce_sum"](common.F32))).lower(
        params, {"token_ids": row, "labels": row}).compile()
    count = sum(x.size for x in jax.tree.leaves(params))
    held = 20 * count + compiled.memory_analysis().temp_size_in_bytes
    assert count == 491697408
    assert held < 0.8 * 16.909e9
