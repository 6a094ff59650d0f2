"""The ``lfm2_moe`` family (LFM2-8B-A1B) on the decoder skeleton, at tiny
widths with the published STRUCTURE on the CPU: both mixer kinds (gated short
convolution, grouped-query attention in groups of 4), one dense and four
routed layers, 8 of 32 experts held and top 4, 3 taps, the head tied to the
embedding. Its ops against dense oracles, and the whole program against the
benchmark's plain float32 reference (``benchmarks/reference/lfm2_moe.py``) on
the benchmark's seeded weights (``benchmarks/weights.py``).

Tolerances as ``test_decoder_lm.py`` has them: float32 round-off (1e-5 of the
largest reference value for logits, 1e-6 relative for the loss), which the
same program in bfloat16 misses at least ten times over. Gradients get 2e-5:
every leaf reads under 2.6e-6 but the queries' per-head norm scale, four
values that are each a sum over every row, token and head of terms that
nearly cancel (a softmax does not see a common factor of its row's scores),
which reads 1.13e-5 of its largest with all experts held; bfloat16 reads 0.48
on that leaf and 0.76 on the worst.
"""

import hashlib
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as run_mod, traffic
from benchmarks.reference import common as ref_common, lfm2_moe as ref
from benchmarks.tests import tiny
from benchmarks.weights import make_weights_fn, seed_words, train_rng
from perceiver_io_tpu.models.decoder_lm import DecoderLMConfig
from perceiver_io_tpu.ops import moe
from perceiver_io_tpu.ops.latent_attention import causal_attention
from perceiver_io_tpu.ops.pallas_attention import fused_attention
from perceiver_io_tpu.ops.rotary import apply_rotary_half, rotary_angles
from perceiver_io_tpu.ops.short_conv import causal_depthwise_conv

import test_decoder_lm  # the other family's tiny cell

LOGIT_TOL, GRAD_TOL = 1e-5, 2e-5  # of the largest reference value: float32 round-off
LOSS_TOL = 1e-6              # relative
SEED = 2**31 + 41
PUBLISHED_EXPERTS = 32


def tiny_cell(dtype="float32", held=8, offset=0):
    """The benchmark's configuration with every width cut and the structure
    kept: the same files, the same builder, so the tests drive the cell's own
    code paths. 8 query heads over 2 key/value heads of 4 channels."""
    cfg = run_mod.load_config("lfm2_8b_a1b_ep4")
    cfg.update(vocab_size=96, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_attention_heads=8, num_key_value_heads=2, num_experts=held, dtype=dtype)
    cfg["deployment"] = dict(cfg["deployment"], experts_held=held, expert_offset=offset)
    mix = traffic.load_mix("train_ids_b2_w8192")
    mix.update(batch_size=2, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=24, high=96, length_low=24, length_high=24)
    return cfg, mix, importlib.import_module(f"benchmarks.configs.{cfg['builder']}")


def seeded(cfg, mix, builder):
    """The benchmark's weights, but for the expert bias: there it is the same
    constant for every expert (``ops/moe.py`` ``ExpertBias``), which decides
    no selection; here it is drawn, so that a program or a reference that
    left it out of the top 4 would be caught."""
    params = make_weights_fn(builder.param_shapes(cfg))(*seed_words(SEED))
    keys = iter(jax.random.split(jax.random.key(SEED % 1000), cfg["num_hidden_layers"]))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: 0.02 * jax.random.normal(next(keys), leaf.shape)
        if "expert_bias" in jax.tree_util.keystr(path) else leaf, params)
    batch = traffic.make_batches(mix, SEED)[0]
    return params, jnp.asarray(batch["token_ids"]), jnp.asarray(batch["pad_mask"])


def worst(got, want):
    """Largest difference over the largest reference magnitude."""
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


def program_and_reference(dtype, held, offset):
    """(logits gap, loss gap, worst leaf gradient gap) of the program against
    the reference given the same share."""
    cfg, mix, builder = tiny_cell(dtype, held, offset)
    params, ids, pad = seeded(cfg, mix, builder)
    model, _ = builder.build_model(cfg)
    main, mtp = model.apply({"params": params}, ids)
    assert mtp is None and "head" not in params  # no MTP module, the head is the embedding
    want_logits = ref.logits(ref_common.F32, params, ids, builder.sizes(cfg))
    task = builder.reference_task(cfg)
    block, count = task["prepare"]({"token_ids": ids}, None, 0)
    want_loss, want = ref_common.blocked_value_and_grad(
        task["ce_sum"](ref_common.F32), task["block_rows"])(params, block, count)
    (loss, metrics), got = jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, pad, method=model.loss), has_aux=True)(params)
    assert float(metrics["loss_main"]) == float(loss) and "loss_mtp" not in metrics
    assert float(metrics["moe_dropped_assignments"]) == 0
    # 48 tokens x top 4 are one tile of 256 and 8 tiles of padding, whatever the
    # share: capacity and worst case are the same 9 tiles, the layers build one path
    assert float(metrics["moe_bounded_path_pct"]) == 100.0
    leaves = jax.tree.map(lambda g, w: worst(g, w) if float(jnp.max(jnp.abs(w))) else
                          float(jnp.max(jnp.abs(g))), got, want)
    return (worst(main, want_logits), abs(float(loss) - float(want_loss)) / float(want_loss),
            max(jax.tree.leaves(leaves)))


@pytest.mark.parametrize("held, offset", [(PUBLISHED_EXPERTS, 0), (8, 0), (8, 16)],
                         ids=["whole", "cells_share", "third_share"])
def test_program_matches_the_plain_reference(held, offset):
    logits_gap, loss_gap, grad_gap = program_and_reference("float32", held, offset)
    assert logits_gap < LOGIT_TOL
    assert loss_gap < LOSS_TOL
    # every leaf: the tied embedding (gather + head), the taps, the per-head
    # norms' scales, the selection bias's exact zero
    assert grad_gap < GRAD_TOL


def test_bfloat16_fails_the_tolerances():
    logits_gap, loss_gap, grad_gap = program_and_reference("bfloat16", 8, 0)
    assert logits_gap > 10 * LOGIT_TOL and loss_gap > 10 * LOSS_TOL and grad_gap > 10 * GRAD_TOL


def test_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that the four shares of 8 experts
    give add up to what the uncut reference gives for the whole 32-expert
    layer. There is no shared expert to count once. In tiles of 8 rows a
    quarter share's bounded buffer is 20 tiles of the worst case's 32: each
    share's routing fits it, and a bias that sends every token's four choices
    to the first share overflows it onto the worst-case buffer; nothing is
    dropped on either path."""
    cfg, mix, builder = tiny_cell(held=PUBLISHED_EXPERTS)
    params, _, _ = seeded(cfg, mix, builder)
    p = params["layer_2"]["moe"]
    assert "shared_expert" not in p
    x = jax.random.normal(jax.random.key(7), (2, 24, cfg["hidden_size"]))
    whole = ref.expert_layer(ref_common.F32, p, x, builder.sizes(cfg))
    assert moe.capacity_tiles(48, 4, 8, PUBLISHED_EXPERTS, 8) == 20
    assert moe.worst_case_tiles(192, 8, 8) == 32

    def share(offset, p=p, bounded_pct=100.0, held=8):
        layer = moe.MoELayer(
            num_experts=PUBLISHED_EXPERTS, top_k=cfg["num_experts_per_tok"],
            width=cfg["moe_intermediate_size"], num_shared=0, gate_eps=ref.GATE_EPS,
            expert_bias_buffer=True,
            routed_scaling_factor=cfg["routed_scaling_factor"], experts_held=held,
            expert_offset=offset, tile_rows=8)
        mine = dict(p, **{k: {"kernel": p[k]["kernel"][offset:offset + held]}
                          for k in ("experts_gate", "experts_up", "experts_down")})
        y, stats = layer.apply({"params": mine}, x)
        assert float(stats["dropped_assignments"]) == 0
        assert float(stats["bounded_path_pct"]) == bounded_pct
        # the same share of the reference
        sz = dict(builder.sizes(cfg), experts_held=held, expert_offset=offset)
        assert worst(y, ref.expert_layer(ref_common.F32, mine, x, sz)) < LOGIT_TOL
        return y, float(stats["local_assignment_pct"])

    parts, shares = zip(*(share(offset) for offset in range(0, PUBLISHED_EXPERTS, 8)))
    assert worst(sum(parts), whole) < LOGIT_TOL
    assert np.isclose(sum(shares), 100.0)
    crowded = dict(p, expert_bias={"scale": jnp.zeros(PUBLISHED_EXPERTS).at[:8].set(10.0)})
    assert share(0, crowded, bounded_pct=0.0)[1] == 100.0  # 192 rows: 24 tiles or more of the 20


def test_gate_epsilon_is_the_familys():
    """1e-6 in the gates' normalisation is visible where the selected scores
    are small: the layer follows its ``gate_eps``, not the other family's."""
    scores = jnp.full((1, 4), 1e-6)
    lfm2 = moe.route(scores, jnp.zeros(4), 2, 1.0, True, 1e-6).gates
    other = moe.route(scores, jnp.zeros(4), 2, 1.0, True).gates
    np.testing.assert_allclose(lfm2, [[1 / 3, 1 / 3]], rtol=1e-6)
    np.testing.assert_allclose(other, [[0.5, 0.5]], rtol=1e-6)


# -- the gated short convolution ------------------------------------------------


def test_short_conv_equals_an_explicit_loop():
    z = jax.random.normal(jax.random.key(0), (2, 9, 5))
    taps = jax.random.normal(jax.random.key(1), (3, 5))
    got = causal_depthwise_conv(z, taps)
    want = np.zeros((2, 9, 5))
    zn, kn = np.asarray(z, np.float64), np.asarray(taps, np.float64)
    for t in range(9):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += kn[j] * zn[:, t - 2 + j]
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_short_conv_layer_is_causal_and_matches_the_reference():
    """A token's output does not change when later tokens do; the module is
    the reference's four lines, forward and every gradient."""
    from perceiver_io_tpu.ops.short_conv import GatedShortConv

    layer = GatedShortConv(taps=3)
    x = jax.random.normal(jax.random.key(2), (2, 12, 16))
    params = layer.init(jax.random.key(3), x)["params"]
    assert params["kernel"].shape == (3, 16)
    y = layer.apply({"params": params}, x)
    later = x.at[:, 7:].set(jax.random.normal(jax.random.key(4), (2, 5, 16)))
    y_later = layer.apply({"params": params}, later)
    assert np.array_equal(np.asarray(y[:, :7]), np.asarray(y_later[:, :7]))
    assert float(jnp.max(jnp.abs(y[:, 7:] - y_later[:, 7:]))) > 1e-3
    weight = jax.random.normal(jax.random.key(5), y.shape)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x) * weight), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(ref.short_conv(ref_common.F32, p, x) * weight),
                    (0, 1))(params, x)
    assert worst(y, ref.short_conv(ref_common.F32, params, x)) < LOGIT_TOL
    assert max(jax.tree.leaves(jax.tree.map(worst, got, want))) < GRAD_TOL


# -- grouped keys and values ------------------------------------------------------


def _grouped_operands(heads=8, kv_heads=2, t=64, d=8):
    keys = jax.random.split(jax.random.key(1), 4)
    q, weight = (jax.random.normal(key, (2, t, heads, d)) for key in keys[:2])
    k, v = (jax.random.normal(key, (2, t, kv_heads, d)) for key in keys[2:])
    return q, k, v, weight


def _on_repeated(q, k, v):
    """The blocked XLA path on K / V repeated to the query heads."""
    group = q.shape[2] // k.shape[2]
    return causal_attention(q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                            "xla", query_block=16)


@pytest.mark.parametrize("path", ["pallas_skip", "pallas_every_tile", "xla_grouped"])
@pytest.mark.parametrize("kv_heads", [2, 1, 8], ids=["groups_of_4", "one_kv_head", "group_1"])
def test_grouped_kv_paths_match_repeated_keys_and_values(path, kv_heads):
    """Forward and the three gradients (dk and dv summed over a group's query
    heads) of the kernel in interpret mode, with the tiles above the diagonal
    skipped and, under a pad mask of no padding, with every tile, and of the
    blocked XLA path on the grouped operands, against the XLA path on K / V
    repeated to 8 heads."""
    q, k, v, weight = _grouped_operands(kv_heads=kv_heads)
    fn = {
        "pallas_skip": lambda q, k, v: fused_attention(
            q, k, v, causal_offset=0, kv_block_size=16, q_block_size=32),
        "pallas_every_tile": lambda q, k, v: fused_attention(
            q, k, v, causal_offset=0, kv_block_size=16, q_block_size=32,
            pad_mask=jnp.zeros(k.shape[:2], bool)),
        "xla_grouped": lambda q, k, v: causal_attention(q, k, v, "xla", query_block=16),
    }[path]
    with jax.default_matmul_precision("highest"):
        assert worst(fn(q, k, v), _on_repeated(q, k, v)) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(_on_repeated(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert max(worst(g, w) for g, w in zip(got, want)) < 1e-5


def _dkv_grid(jaxpr):
    """The grid of the one ``fused_attention_dkv`` call under ``jaxpr``."""
    from perceiver_io_tpu.ops.pallas_attention import KERNEL_DKV

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["name"] == KERNEL_DKV:
                return tuple(eqn.params["grid_mapping"].grid)
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns") and (grid := _dkv_grid(sub)):
                    return grid
    return None


@pytest.mark.parametrize("kv_heads, grid", [(8, (2, 8, 4, 2)), (2, (2, 2, 4, 8))],
                         ids=["group_1", "groups_of_4"])
def test_dkv_kernel_runs_a_groups_query_heads_on_its_sequential_axis(kv_heads, grid):
    """(batch, key/value heads, key blocks, group x query blocks): with as many
    key/value heads as query heads that is the grid the kernel had before it
    took groups (the decoder cell's lowered step, below, holds the whole
    program to that); with groups of 4 the last axis is four times as long and
    there are a quarter of the rows."""
    q, k, v, weight = _grouped_operands(kv_heads=kv_heads)

    def step(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fused_attention(
            *a, causal_offset=0, kv_block_size=16, q_block_size=32) * weight),
            argnums=(0, 1, 2))(q, k, v)

    assert _dkv_grid(jax.make_jaxpr(step)(q, k, v).jaxpr) == grid


def test_query_heads_must_divide_into_the_key_value_heads():
    q, k, v, _ = _grouped_operands(kv_heads=2)
    with pytest.raises(ValueError, match="query heads"):
        fused_attention(q, k[:, :, :1].repeat(3, axis=2), v[:, :, :1].repeat(3, axis=2),
                        causal_offset=0)
    with pytest.raises(ValueError, match="query heads"):
        fused_attention(q, k, v[:, :, :1], causal_offset=0)


def test_half_split_rotary_against_a_complex_rotation():
    t, h, d, theta = 12, 2, 8, 1e6
    x = jax.random.normal(jax.random.key(0), (1, t, h, d))
    got = apply_rotary_half(x, *rotary_angles(jnp.arange(t), d, theta))
    xn = np.asarray(x, np.float64)
    angle = np.arange(t)[:, None] * theta ** (-np.arange(0, d, 2) / d)
    turned = (xn[..., :d // 2] + 1j * xn[..., d // 2:]) * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(ref.rotate_halves(x, theta), want, atol=1e-5)
    # a rotated dot product depends on the distance alone
    q = apply_rotary_half(jnp.broadcast_to(x[:, :1], x.shape), *rotary_angles(jnp.arange(t), d, theta))
    np.testing.assert_allclose(jnp.sum(q[0, 3] * q[0, 1]), jnp.sum(q[0, 9] * q[0, 7]), rtol=1e-4)


# -- the skeleton and its configuration ---------------------------------------------


def test_published_config_gives_the_cells_parameter_count():
    """The configuration as run (all published widths, layer 0 + published
    layers 2-5, 8 of 32 experts, 16,384 vocabulary rows, the head tied) is
    507,820,288 parameters = 8.13 GB at 16 B."""
    cfg = run_mod.load_config("lfm2_8b_a1b_ep4")
    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    shapes = builder.param_shapes(cfg)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 507820288
    per_layer = [sum(x.size for x in jax.tree.leaves(shapes[f"layer_{i}"])) for i in range(5)]
    assert per_layer == [60827648, 98635936, 104933408, 104933408, 104933408]
    model, _ = builder.build_model(cfg)
    c = model.config
    assert c.mixers == ("conv", "full_attention", "conv", "conv", "conv")
    assert (c.n_routed_experts, c.experts_held, c.first_k_dense_replace) == (32, 8, 1)
    assert (c.num_key_value_heads, c.head_dim, c.conv_L_cache) == (8, 64, 3)
    assert c.tie_word_embeddings and c.n_shared_experts == 0 and c.gate_eps == 1e-6
    # twice the expected rows and 8 tiles: a cond between that buffer and the worst case's
    assert moe.capacity_tiles(16384, 4, 8, 32, moe.TILE_ROWS) == 136 < moe.worst_case_tiles(
        65536, 8, moe.TILE_ROWS) == 264


@pytest.mark.parametrize("change, match", [
    ({"conv_bias": True}, "conv_bias"), ({"use_expert_bias": False}, "use_expert_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"layer_types": ["conv", "mamba"]}, "layer_types"),
    ({"layer_types": ["conv"]}, "layer_types"), ({"num_key_value_heads": 3}, "key/value heads")])
def test_lfm2_config_refuses_what_the_module_does_not_compute(change, match):
    from perceiver_io_tpu.cli import train_lm

    published = dict(train_lm.SMALL_LFM2, model_type="lfm2_moe", vocab_size=50,
                     num_hidden_layers=2, layer_types=["conv", "full_attention"])
    assert DecoderLMConfig.from_dict(published).mixers == ("conv", "full_attention")
    with pytest.raises(ValueError, match=match):
        DecoderLMConfig.from_dict({**published, **change})


def test_other_familys_keys_do_not_reach_the_latent_attention_family():
    """A DeepSeek-V3 ``config.json`` carries ``head_dim`` and
    ``num_key_value_heads`` that size nothing under MLA: they stay out of the
    skeleton's fields, and every layer's mixer is 'mla'."""
    cfg, _, builder = test_decoder_lm.tiny_cell()
    model, _ = builder.build_model(cfg)
    c = model.config
    assert (c.head_dim, c.num_key_value_heads, c.layer_types) == (0, 0, ())
    assert c.mixers == ("mla",) * cfg["num_hidden_layers"] and not c.tie_word_embeddings
    assert c.gate_eps == 1e-20 and c.n_shared_experts == 1


@pytest.mark.parametrize("case", ["fits", "xla"])
def test_remat_policy_counts_the_attention_layers(case, monkeypatch, remat_policy_events):
    """One attention layer of five blocks: the policy reckons that layer's
    kept bytes alone (the convolutions keep nothing), and where it engages a
    step runs the forward kernel once."""
    from perceiver_io_tpu.models import perceiver
    from perceiver_io_tpu.ops import pallas_attention as pa
    from test_pallas_attention import _kernel_calls

    cfg, mix, builder = tiny_cell()
    cfg["attn_impl"] = "pallas" if case == "fits" else "xla"
    params, ids, pad = seeded(cfg, mix, builder)
    monkeypatch.setattr(perceiver, "_device_bytes_limit", lambda: 16e9)
    model, _ = builder.build_model(cfg)

    def step(p):
        return jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, pad, method=model.loss), has_aux=True)(p)

    (_, metrics), _ = step(params)
    record = remat_policy_events()[-1]
    b, t = ids.shape
    heads, depth = cfg["num_attention_heads"], cfg["hidden_size"] // cfg["num_attention_heads"]
    assert record["layers"] == 1
    assert record["saved_bytes"] == (b * t * heads * (depth * 4 + 8) if case == "fits" else 0)
    assert record["engaged"] is (case == "fits")
    assert float(metrics["attention_residuals_kept_pct"]) == (100.0 if case == "fits" else 0.0)
    calls = [_kernel_calls(jax.make_jaxpr(step)(params).jaxpr, kernel)
             for kernel in (pa.KERNEL_FWD, pa.KERNEL_DQ, pa.KERNEL_DKV)]
    assert calls == ([1, 1, 1] if case == "fits" else [0, 0, 0])


# The cells' ``train_step`` at tiny widths, lowered: the text's SHA-256. The
# three older cells' as the parent of PR 36 lowered them (commit 9a37da4; the
# decoder once on the blocked XLA path and once on the kernels in interpret
# mode; that tiny decoder holds all of its 16 experts, so these guard
# everything in ``ops/moe.py`` BUT ``capacity_tiles``, whose 72 tiles for the
# real cell ``test_decoder_lm.py`` pins), this family's as PR 37 left it (a
# quarter share: each expert layer lowers a ``cond`` between a buffer of 10
# tiles and the worst case's 11). A PR that MEANS to change one of these steps
# puts the new hash here and says so.
LOWERED = {
    "joyai": "f89fcdb957e2cfde72a6e1c9163034347002db24d77e33757cff8a5c41e4da84",
    "joyai_kernels": "1ed0657c28754918315bc621dd1f1cc9467a7bf867709b77da50609be10db580",
    "mlm": "fba30f389c39c9d1dc3333dfe497a7bb344dd00fbb4203a7d8509315f6e48cd2",
    "images": "2c8254997124a2bb19229df934c63e950fbd52615d9fb1feed432d205a2f7e38",
    "lfm2": "482eb96528611199ebb6e268f3b40cb3295acf9853556c99766482d66fcde5d7",
}


@pytest.mark.parametrize("which", sorted(LOWERED))
def test_cells_lowered_steps_are_unchanged(which, tmp_path):
    if which.startswith("joyai"):
        cfg, mix, builder = test_decoder_lm.tiny_cell()
        if which == "joyai_kernels":
            cfg["attn_impl"] = "pallas"
    elif which == "lfm2":
        cfg, mix, builder = tiny_cell()
    else:
        _, cfg, mix, builder = getattr(tiny, which)()
    mix["batch_size"] = 8  # divides by the 8 virtual devices
    lo, hi = seed_words(SEED)
    params = make_weights_fn(builder.param_shapes(cfg))(lo, hi)
    pool = traffic.make_batches(mix, SEED)
    trainer = builder.build_trainer(cfg, mix, params, train_rng(lo, hi), pool[0], str(tmp_path))
    try:
        batch = {k: pool[0][k] for k in trainer._keys}
        text = jax.jit(trainer._raw_train_step).lower(trainer.state, batch).as_text()
    finally:
        trainer.close()
    if which == "lfm2":  # forward and backward of the four expert layers
        assert text.count("stablehlo.case") == 8
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[which]


def test_train_lm_cli_builds_the_family_from_its_model_type(tmp_path):
    from perceiver_io_tpu import obs
    from perceiver_io_tpu.cli import train_lm
    from perceiver_io_tpu.training import read_metrics

    log = tmp_path / "events.jsonl"
    obs.configure_event_log(str(log))
    try:
        run_dir = train_lm.main([
            "--synthetic", "--synthetic_size", "64", "--max_steps", "3", "--batch_size", "8",
            "--max_seq_len", "32", "--vocab_size", "200", "--dtype", "float32",
            "--log_every_n_steps", "2", "--no_tensorboard", "--logdir", str(tmp_path),
            "--model_type", "lfm2_moe", "--layer_types", "conv,full_attention,conv,conv",
            "--num_hidden_layers", "4", "--experts_held", "2", "--expert_offset", "4"])
    finally:
        obs.configure_event_log(None)  # drains, then closes
    with open(log) as f:
        events = {r["event"]: r for r in map(json.loads, f) if "event" in r}
    # one ``lm.layers`` a build, beside ``moe.share``
    layers = events["lm.layers"]
    assert layers["mixers"] == ["conv", "full_attention", "conv", "conv"]
    assert (layers["dense_layers"], layers["kv_group"], layers["tied_head"]) == (1, 2, True)
    assert (layers["experts_held"], layers["experts_published"]) == (2, 8)
    assert events["moe.share"]["held"] == 2 and events["moe.share"]["offset"] == 4
    rows = [r for r in read_metrics(run_dir) if "train_loss" in r]
    assert [r["step"] for r in rows] == [2]
    row = rows[0]
    assert np.isfinite(row["train_loss"]) and row["moe_dropped_assignments"] == 0
    assert row["train_loss"] == row["loss_main"] and "loss_mtp" not in row
    assert row["moe_local_assignment_pct"] < 100.0
    gauges = obs.get_registry().snapshot()["gauges"]
    # 2 of 8 experts held, 256 tokens x top 2 in tiles of 256: a bounded buffer of
    # 3 tiles of the worst case's 4, which two experts overflow only with 256 rows each
    assert gauges["moe_bounded_path_pct"] == 100.0
    assert gauges["attention_residuals_kept_pct"] == 0.0  # off a TPU: the blocked XLA path
