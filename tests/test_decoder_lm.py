"""The causal decoder (latent attention, routed experts told which experts
they hold, multi-token prediction) at tiny widths on the CPU: its ops against
dense oracles, and the whole program against the benchmark's plain float32
reference (``benchmarks/reference/decoder_lm.py``) on the benchmark's seeded
weights (``benchmarks/weights.py``).

Tolerances. The program in float32 and the reference compute the same
mathematics in another order (fused kernels against einsums at
``Precision.HIGHEST``), so they agree to float32 round-off: 1e-5 of the
largest reference value for logits and gradients, 1e-6 relative for the loss.
The same program in bfloat16 misses each at least ten times over (its
activations carry 8 bits; the loss, a mean, by the least: 38x), which
``test_bfloat16_fails_the_tolerances`` holds.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmarks import run as run_mod, traffic
from benchmarks.reference import common as ref_common, decoder_lm as ref
from benchmarks.weights import make_weights_fn, seed_words
from perceiver_io_tpu.ops import moe
from perceiver_io_tpu.ops.latent_attention import causal_attention
from perceiver_io_tpu.ops.pallas_attention import fused_attention
from perceiver_io_tpu.ops.pallas_grouped_matmul import grouped_matmul, grouped_matmul_xla
from perceiver_io_tpu.ops.rotary import apply_rotary_interleaved, rotary_angles

LOGIT_TOL = GRAD_TOL = 1e-5  # of the largest reference value: float32 round-off
LOSS_TOL = 1e-6              # relative
SEED = 2**31 + 41
PUBLISHED_EXPERTS = 16


def tiny_cell(dtype="float32", held=PUBLISHED_EXPERTS, offset=0):
    """The benchmark's configuration with every size cut: the same files, the
    same builder, so the tests drive the cell's own code paths."""
    cfg = run_mod.load_config("joyai_llm_flash_ep32")
    cfg.update(vocab_size=96, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_hidden_layers=3, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, n_routed_experts=held,
               num_experts_per_tok=4, dtype=dtype)
    cfg["deployment"] = dict(cfg["deployment"], n_routed_experts_published=PUBLISHED_EXPERTS,
                             experts_held=held, expert_offset=offset)
    mix = traffic.load_mix("train_ids_b4_w4096")
    mix.update(batch_size=4, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=24, high=96, length_low=24, length_high=24)
    return cfg, mix, importlib.import_module(f"benchmarks.configs.{cfg['builder']}")


def seeded(cfg, mix, builder):
    params = make_weights_fn(builder.param_shapes(cfg))(*seed_words(SEED))
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
    ref_main, ref_mtp = ref.logits(ref_common.F32, params, ids, builder.sizes(cfg))
    task = builder.reference_task(cfg)
    block, count = task["prepare"]({"token_ids": ids}, None, 0)
    want_loss, want = ref_common.blocked_value_and_grad(
        task["ce_sum"](ref_common.F32), task["block_rows"])(params, block, count)
    (loss, metrics), got = jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, pad, method=model.loss), has_aux=True)(params)
    assert np.isclose(float(metrics["loss_main"] + cfg["mtp_loss_factor"] * metrics["loss_mtp"]),
                      float(loss), rtol=1e-6)
    leaves = jax.tree.map(lambda g, w: worst(g, w) if float(jnp.max(jnp.abs(w))) else
                          float(jnp.max(jnp.abs(g))), got, want)
    return (max(worst(main, ref_main), worst(mtp, ref_mtp)),
            abs(float(loss) - float(want_loss)) / float(want_loss),
            max(jax.tree.leaves(leaves)))


@pytest.mark.parametrize("held, offset", [(PUBLISHED_EXPERTS, 0), (4, 4)], ids=["whole", "share"])
def test_program_matches_the_plain_reference(held, offset):
    logits_gap, loss_gap, grad_gap = program_and_reference("float32", held, offset)
    assert logits_gap < LOGIT_TOL
    assert loss_gap < LOSS_TOL
    assert grad_gap < GRAD_TOL  # every leaf, the selection bias's exact zero included


def test_bfloat16_fails_the_tolerances():
    logits_gap, loss_gap, grad_gap = program_and_reference("bfloat16", 4, 4)
    assert logits_gap > 10 * LOGIT_TOL and loss_gap > 10 * LOSS_TOL and grad_gap > 10 * GRAD_TOL


def test_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that all four shares of an expert
    layer give, the shared expert (which every chip computes alike) counted
    once, add up to what the uncut reference gives for the whole layer."""
    cfg, mix, builder = tiny_cell()
    params, _, _ = seeded(cfg, mix, builder)
    p = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.key(7), (2, 24, cfg["hidden_size"]))
    whole = ref.expert_layer(ref_common.F32, p, x, builder.sizes(cfg))
    shared = ref.swiglu(ref_common.F32, p["shared_expert"], x)

    def share(offset, held=4):
        layer = moe.MoELayer(
            num_experts=PUBLISHED_EXPERTS, top_k=cfg["num_experts_per_tok"],
            width=cfg["moe_intermediate_size"], num_shared=1,
            routed_scaling_factor=cfg["routed_scaling_factor"], experts_held=held,
            expert_offset=offset, tile_rows=8)
        mine = dict(p, **{k: {"kernel": p[k]["kernel"][offset:offset + held]}
                          for k in ("experts_gate", "experts_up", "experts_down")})
        y, stats = layer.apply({"params": mine}, x)
        assert float(stats["dropped_assignments"]) == 0
        return y - shared, float(stats["local_assignment_pct"])

    parts, shares = zip(*(share(offset) for offset in range(0, PUBLISHED_EXPERTS, 4)))
    assert worst(sum(parts) + shared, whole) < LOGIT_TOL
    assert np.isclose(sum(shares), 100.0)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_no_assignment_is_dropped_when_every_token_picks_the_same_experts(impl):
    """The worst imbalance: a selection bias that sends all 48 tokens to the
    same four experts, two of them held here. Every assignment gets a row."""
    cfg, mix, builder = tiny_cell()
    params, _, _ = seeded(cfg, mix, builder)
    p = dict(params["layer_1"]["moe"])
    p["e_score_correction_bias"] = jnp.zeros(PUBLISHED_EXPERTS).at[jnp.array([1, 5, 6, 12])].set(10.0)
    held, offset = 4, 4
    p.update({k: {"kernel": p[k]["kernel"][offset:offset + held]}
              for k in ("experts_gate", "experts_up", "experts_down")})
    x = jax.random.normal(jax.random.key(8), (2, 24, cfg["hidden_size"]))
    layer = moe.MoELayer(
        num_experts=PUBLISHED_EXPERTS, top_k=4, width=cfg["moe_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"], experts_held=held,
        expert_offset=offset, tile_rows=8, expert_impl=impl)
    y, stats = layer.apply({"params": p}, x)
    sz = dict(builder.sizes(cfg), experts_held=held, expert_offset=offset)
    assert worst(y, ref.expert_layer(ref_common.F32, p, x, sz)) < LOGIT_TOL
    assert float(stats["dropped_assignments"]) == 0
    assert float(stats["local_assignment_pct"]) == 50.0  # experts 5 and 6 of the four
    assert float(stats["load_max_over_mean"]) == 2.0     # 48 each, the other two held idle


# Layers whose capacity is smaller than their worst case, 48 tokens in tiles of
# 8. SMALL share: 2 of 16 experts (5 and 6) held, top 2. A router that favours
# no expert sends 12 rows here; four times that is 6 tiles, a third of the way
# from 12 rows to the worst case's 96 is 40 rows = 5 tiles, so the bounded
# buffer is 5 + 2 = 7 tiles of the worst case's 96 / 8 + 2 = 14. QUARTER share
# (the LFM2 cell's): 4 of 16 (4 to 7) held, top 4. 48 rows are expected, a third
# of the way to the worst case's 192 is 96 rows: 12 + 4 = 16 tiles of 24 + 4 = 28.
BOUNDED = {
    "small": dict(num_experts=PUBLISHED_EXPERTS, top_k=2, experts_held=2, expert_offset=5,
                  tile_rows=8),
    "quarter": dict(num_experts=PUBLISHED_EXPERTS, top_k=4, experts_held=4, expert_offset=4,
                    tile_rows=8),
}
CAPACITY = {"small": (7, 14), "quarter": (16, 28)}  # the bounded buffer's tiles, the worst case's


def _bias_that_needs(case, scores, share):
    """A selection bias under which the 48 tokens' routing to the held
    experts needs fewer tiles than the capacity, exactly the capacity, or
    more: the share of the 48 * top_k assignments computed here with it."""
    kw, (capacity, _) = BOUNDED[share], CAPACITY[share]
    first, held, top_k = kw["expert_offset"], kw["experts_held"], kw["top_k"]
    bias = jnp.zeros(PUBLISHED_EXPERTS)
    if case == "under":
        return bias, None
    if case == "over":  # every choice of every token is held here: 6 tiles an expert
        return bias.at[first:first + held].set(10.0), 100.0
    # 'exact': ``full`` experts are every token's choice (6 tiles each), the
    # next is chosen last by as many tokens as fill the tiles left, the rest
    # of the held ones by none
    full, left = divmod(capacity, 6)
    bias = bias.at[first:first + held].set(-10.0).at[first:first + full].set(10.0)
    chosen = 8 * left
    # the next expert's bias lies between the ``chosen``-th and the following
    # smallest lead of the last choice among the experts not held
    others = jnp.sort(scores.at[:, first:first + held].set(0.0), axis=-1)[:, -(top_k - full)]
    lead = jnp.sort(others - scores[:, first + full])
    bias = bias.at[first + full].set((lead[chosen - 1] + lead[chosen]) / 2)
    return bias, 100.0 * (48 * full + chosen) / (48 * top_k)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case, share, bounded_pct", [
    ("under", "small", 100.0), ("exact", "small", 100.0), ("over", "small", 0.0),
    ("under", "quarter", 100.0), ("exact", "quarter", 100.0), ("over", "quarter", 0.0),
    ("all_held", "small", 100.0)])
def test_row_buffer_follows_the_load(case, share, bounded_pct, impl):
    """Output and the gradients of the input, the router and the three expert
    kernels, through ``nn.remat`` and ``value_and_grad`` as the step takes
    them, against the plain reference: on the bounded buffer, at its last
    tile, over it (the worst-case buffer), for a small share and for a
    quarter, and with every expert held, where the layer builds one path and
    no ``cond``."""
    cfg, mix, builder = tiny_cell()
    params, _, _ = seeded(cfg, mix, builder)
    p = dict(params["layer_1"]["moe"])
    x = jax.random.normal(jax.random.key(9), (2, 24, cfg["hidden_size"]))
    weight = jax.random.normal(jax.random.key(10), x.shape)
    kw = dict(BOUNDED[share], width=cfg["moe_intermediate_size"], expert_impl=impl,
              routed_scaling_factor=cfg["routed_scaling_factor"])
    local_pct = 100.0
    if case == "all_held":
        kw.update(experts_held=None, expert_offset=0)
    else:
        scores = jax.nn.sigmoid(jnp.dot(x.reshape(-1, x.shape[-1]), p["router"]["kernel"],
                                        precision=jax.lax.Precision.HIGHEST))
        p["e_score_correction_bias"], local_pct = _bias_that_needs(case, scores, share)
        mine = slice(kw["expert_offset"], kw["expert_offset"] + kw["experts_held"])
        p.update({k: {"kernel": p[k]["kernel"][mine]}
                  for k in ("experts_gate", "experts_up", "experts_down")})
        assert (moe.capacity_tiles(48, kw["top_k"], kw["experts_held"], PUBLISHED_EXPERTS, 8),
                moe.worst_case_tiles(48 * kw["top_k"], kw["experts_held"], 8)) == CAPACITY[share]
    layer = nn.remat(moe.MoELayer)(**kw)
    held = kw["experts_held"] or PUBLISHED_EXPERTS
    sz = dict(builder.sizes(cfg), top_k=kw["top_k"], experts_held=held,
              expert_offset=kw["expert_offset"])

    def program(p, x):
        y, stats = layer.apply({"params": p}, x)
        return jnp.sum(y * weight), (y, stats)

    def reference(p, x):
        y = ref.expert_layer(ref_common.F32, p, x, sz)
        return jnp.sum(y * weight), y

    (_, (y, stats)), got = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(p, x)
    (_, want_y), want = jax.value_and_grad(reference, argnums=(0, 1), has_aux=True)(p, x)
    assert worst(y, want_y) < LOGIT_TOL
    assert worst(got[1], want[1]) < GRAD_TOL
    for leaf in ("router", "experts_gate", "experts_up", "experts_down"):
        assert worst(got[0][leaf]["kernel"], want[0][leaf]["kernel"]) < GRAD_TOL, leaf
    assert float(stats["dropped_assignments"]) == 0
    assert float(stats["bounded_path_pct"]) == bounded_pct
    if local_pct is not None:
        assert np.isclose(float(stats["local_assignment_pct"]), local_pct)
    if impl == "xla":  # the interpreted kernel's ``pl.when``s are conds too
        assert str(jax.make_jaxpr(program)(p, x)).count("cond[") == (case != "all_held")


# (tokens, top_k, held, experts, tile rows) -> tiles: the two decoder cells, a
# share between theirs, and every expert held (the worst case: one path)
@pytest.mark.parametrize("shapes, tiles", [
    ((16384, 8, 8, 256, 256), 72), ((16384, 4, 8, 32, 256), 136),
    ((16384, 8, 32, 256, 256), 246), ((16384, 4, 32, 32, 256), 288),
    ((16384, 8, 256, 256, 256), 768)],
    ids=["joyai_cell", "lfm2_cell", "an_eighth", "lfm2_all_held", "joyai_all_held"])
def test_capacity_follows_the_static_shapes(shapes, tiles):
    """``capacity_tiles`` for the two real cells (the JoyAI cell's is PR 33's
    72: its step does not move; the LFM2 cell's lies well under its worst
    case's 264 and over the 74 tiles a step needs), the worst case exactly where
    every expert is held, under it for every share, and never smaller for
    more experts held."""
    tokens, top_k, held, experts, tile_rows = shapes
    worst_case = moe.worst_case_tiles(tokens * top_k, held, tile_rows)
    assert moe.capacity_tiles(*shapes) == tiles
    assert (tiles == worst_case) == (held == experts)
    by_held = [moe.capacity_tiles(tokens, top_k, h, experts, tile_rows)
               for h in range(1, experts + 1)]
    assert by_held == sorted(by_held) and by_held[-1] == moe.worst_case_tiles(
        tokens * top_k, experts, tile_rows)
    assert all(c < moe.worst_case_tiles(tokens * top_k, h, tile_rows)
               for h, c in enumerate(by_held[:-1], start=1))


def test_grouped_matmul_kernel_matches_masked_matmuls():
    """Forward and both gradients, with an empty group in the middle, an
    empty last group and tiles past the last group."""
    tile, k, n, groups = 8, 16, 24, 4
    tile_group = jnp.array([0, 0, 2, 4, 4], jnp.int32)
    keys = jax.random.split(jax.random.key(3), 3)
    lhs = jax.random.normal(keys[0], (5 * tile, k))
    rhs = jax.random.normal(keys[1], (groups, k, n))
    weight = jax.random.normal(keys[2], (5 * tile, n))

    def loss(fn):
        return lambda a, w: jnp.sum(fn(a, w, tile_group, tile) * weight)

    with jax.default_matmul_precision("highest"):
        out = grouped_matmul(lhs, rhs, tile_group, tile)
        assert float(jnp.max(jnp.abs(out[3 * tile:]))) == 0.0
        assert worst(out, grouped_matmul_xla(lhs, rhs, tile_group, tile)) < 1e-6
        got = jax.grad(loss(grouped_matmul), argnums=(0, 1))(lhs, rhs)
        want = jax.grad(loss(grouped_matmul_xla), argnums=(0, 1))(lhs, rhs)
    assert worst(got[0], want[0]) < 1e-6 and worst(got[1], want[1]) < 1e-6
    assert float(jnp.max(jnp.abs(got[1][jnp.array([1, 3])]))) == 0.0  # groups with no tile


def test_rotary_turns_interleaved_pairs_by_position():
    t, h, d, theta = 12, 2, 8, 32e6
    x = jax.random.normal(jax.random.key(0), (1, t, h, d))
    got = apply_rotary_interleaved(x, *rotary_angles(jnp.arange(t), d, theta))
    pairs = np.asarray(x, np.float64).reshape(1, t, h, d // 2, 2)
    angle = np.arange(t)[:, None] * theta ** (-np.arange(0, d, 2) / d)
    turned = (pairs[..., 0] + 1j * pairs[..., 1]) * np.exp(1j * angle)[None, :, None, :]
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(1, t, h, d)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # a rotated dot product depends on the distance alone
    q = apply_rotary_interleaved(jnp.broadcast_to(x[:, :1], x.shape), *rotary_angles(jnp.arange(t), d, theta))
    np.testing.assert_allclose(jnp.sum(q[0, 3] * q[0, 1]), jnp.sum(q[0, 9] * q[0, 7]), rtol=1e-4)


def _dense_causal(q, k, v):
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
    seen = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


@pytest.mark.parametrize("path", ["xla_blocked", "pallas_skip", "pallas_every_tile"])
def test_causal_paths_match_a_dense_mask(path):
    """Score depth 24 beside value depth 16, forward and gradients: the
    blocked XLA path, and the kernel with the tiles above the diagonal
    skipped and, under a pad mask (here of no padding), with every tile."""
    keys = jax.random.split(jax.random.key(1), 4)
    q, k = (jax.random.normal(key, (2, 64, 2, 24)) for key in keys[:2])
    v, weight = (jax.random.normal(key, (2, 64, 2, 16)) for key in keys[2:])
    fn = {
        "xla_blocked": lambda q, k, v: causal_attention(q, k, v, "xla", query_block=16),
        "pallas_skip": lambda q, k, v: fused_attention(
            q, k, v, causal_offset=0, kv_block_size=16, q_block_size=32),
        "pallas_every_tile": lambda q, k, v: fused_attention(
            q, k, v, causal_offset=0, kv_block_size=16, q_block_size=32,
            pad_mask=jnp.zeros(k.shape[:2], bool)),
    }[path]
    with jax.default_matmul_precision("highest"):
        assert worst(fn(q, k, v), _dense_causal(q, k, v)) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(_dense_causal(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    assert max(worst(g, w) for g, w in zip(got, want)) < 1e-5


@pytest.mark.parametrize("offset", [32, 0], ids=["latent_window", "keys_out_of_reach"])
def test_tile_skipping_off_the_square(offset):
    """32 queries over 64 keys, forward and gradients: the last queries of a
    longer row (offset 32), and keys 32.. that no row reaches (offset 0: their
    KV blocks have no visible query block to fetch and a zero gradient)."""
    keys = jax.random.split(jax.random.key(3), 4)
    q, weight = (jax.random.normal(key, (2, 32, 2, 8)) for key in keys[:2])
    k, v = (jax.random.normal(key, (2, 64, 2, 8)) for key in keys[2:])
    seen = jnp.arange(64)[None, :] <= jnp.arange(32)[:, None] + offset

    def dense(q, k, v):
        logits = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs, v)

    def kernel(q, k, v):
        return fused_attention(q, k, v, causal_offset=offset, kv_block_size=16, q_block_size=8)

    with jax.default_matmul_precision("highest"):
        assert worst(kernel(q, k, v), dense(q, k, v)) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    assert max(float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want)) < 1e-5


@pytest.mark.parametrize("case", ["pad_mask", "negative_offset"])
def test_tiles_are_skipped_only_where_that_is_exact(case):
    """A row that sees padding only (key 0 padded: row 0), or no key at all
    (offset -1: row 0), owes its uniform softmax to the tiles above the
    diagonal too, so the kernel keeps every tile there: skipped, row 0 would
    be its first tile's mean, or 0 over 0."""
    keys = jax.random.split(jax.random.key(2), 2)
    q = jax.random.normal(keys[0], (1, 64, 1, 8))
    v = jax.random.normal(keys[1], (1, 64, 1, 8))
    pad = jnp.zeros((1, 64), bool).at[0, 0].set(case == "pad_mask")
    offset = -1 if case == "negative_offset" else 0
    got = fused_attention(q, q, v, pad_mask=pad if case == "pad_mask" else None,
                          causal_offset=offset, kv_block_size=16, q_block_size=16)
    logits = jnp.einsum("bthd,bshd->bhts", q, q) * q.shape[-1] ** -0.5
    seen = jnp.arange(64)[None, :] <= jnp.arange(64)[:, None] + offset
    logits = logits + jnp.where(pad, -1e30, 0.0)[:, None, None, :] + jnp.where(seen, 0.0, -1e30)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(logits, axis=-1), v)
    assert worst(got, want) < 1e-5
    first_tile = jnp.mean(v[0, :16, 0], axis=0)
    assert worst(got[0, 0, 0], first_tile) > 0.05


@pytest.mark.parametrize("case", ["fits", "over_budget", "no_limit", "xla"])
def test_blocks_keep_the_causal_kernels_residuals(case, monkeypatch, remat_policy_events):
    """The blocks' rematerialisation (``DecoderLM._remat_policy``) on the
    kernel path (interpreter) with the MTP module: where the kept bytes fit
    the allowed share of the device's memory (the CPU reports none: one is put
    in through ``_device_bytes_limit``), a step runs the forward kernel once a
    block and not twice, the loss and every gradient leaf are the bare
    ``nn.remat`` build's, and the metric reads 100; over the budget, without a
    limit and on the ``'xla'`` path the program IS the bare build."""
    from perceiver_io_tpu.models import decoder_lm, perceiver
    from perceiver_io_tpu.ops import pallas_attention as pa
    from test_pallas_attention import _kernel_calls

    cfg, mix, builder = tiny_cell()
    cfg["attn_impl"] = "xla" if case == "xla" else "pallas"
    params, ids, pad = seeded(cfg, mix, builder)
    blocks = cfg["num_hidden_layers"] + 1  # the MTP module's
    b, t = ids.shape
    # float32: the output's v_head_dim x 4 bytes and two float32 statistics a row and head
    formula = blocks * b * t * cfg["num_attention_heads"] * (cfg["v_head_dim"] * 4 + 8)
    limit = {"fits": 16e9, "xla": 16e9, "no_limit": None,
             "over_budget": formula / perceiver.REMAT_KEEP_FRACTION - 8}[case]
    monkeypatch.setattr(perceiver, "_device_bytes_limit", lambda: limit)
    model, _ = builder.build_model(cfg)

    def step(p):
        return jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, pad, method=model.loss), has_aux=True)(p)

    def lowered():  # a function of its own each time: jit keeps a trace by function
        return jax.jit(lambda p: step(p)).lower(params).as_text()

    engaged = case == "fits"
    (loss, metrics), grads = step(params)
    record = remat_policy_events()[-1]
    assert record["engaged"] is engaged and record["layers"] == blocks
    assert record["saved_bytes"] == (0 if case == "xla" else formula)
    assert record["budget_bytes"] == (
        None if limit is None else int(limit * perceiver.REMAT_KEEP_FRACTION))
    assert float(metrics["attention_residuals_kept_pct"]) == (100.0 if engaged else 0.0)
    calls = [_kernel_calls(jax.make_jaxpr(step)(params).jaxpr, kernel)
             for kernel in (pa.KERNEL_FWD, pa.KERNEL_DQ, pa.KERNEL_DKV)]
    per_block = {"fits": [1, 1, 1], "xla": [0, 0, 0]}.get(case, [2, 1, 1])
    assert calls == [blocks * n for n in per_block]
    text = lowered()

    # the bare nn.remat: the program before the blocks kept anything
    monkeypatch.setattr(decoder_lm, "remat_keeps", lambda *a: False)
    (loss_bare, _), grads_bare = step(params)
    assert float(loss) == float(loss_bare)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree.leaves(grads_bare)):
        assert np.array_equal(np.asarray(got), np.asarray(want)), jax.tree_util.keystr(path)
    assert (text == lowered()) is not engaged


def test_published_config_gives_the_cells_parameter_count():
    """The configuration as run (all published widths, 5 layers, 8 of 256
    experts, 16,160 vocabulary rows) is 491.7 M parameters = 7.87 GB at 16 B."""
    cfg = run_mod.load_config("joyai_llm_flash_ep32")
    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    count = sum(x.size for x in jax.tree.leaves(builder.param_shapes(cfg)))
    assert abs(count - 491.7e6) / 491.7e6 < 1e-3
    model, _ = builder.build_model(cfg)
    assert model.config.n_routed_experts == 256 and model.config.experts_held == 8


def test_train_lm_cli_three_synthetic_steps(tmp_path):
    from perceiver_io_tpu import obs
    from perceiver_io_tpu.cli import train_lm
    from perceiver_io_tpu.training import read_metrics

    run_dir = train_lm.main([
        "--synthetic", "--synthetic_size", "64", "--max_steps", "3", "--batch_size", "8",
        "--max_seq_len", "32", "--vocab_size", "200", "--dtype", "float32",
        "--log_every_n_steps", "2", "--no_tensorboard", "--logdir", str(tmp_path),
        "--experts_held", "4", "--expert_offset", "4"])
    rows = [r for r in read_metrics(run_dir) if "train_loss" in r]
    assert [r["step"] for r in rows] == [2]  # the one log boundary of three steps
    row = rows[0]
    assert np.isfinite(row["train_loss"]) and row["moe_dropped_assignments"] == 0
    assert row["moe_local_assignment_pct"] < 100.0
    assert np.isclose(row["train_loss"], row["loss_main"] + 0.3 * row["loss_mtp"], rtol=1e-5)
    # step 3 met no boundary: the end of fit published its metrics as gauges
    gauges = obs.get_registry().snapshot()["gauges"]
    assert np.isfinite(gauges["train_loss"]) and gauges["train_loss"] != row["train_loss"]
    assert np.isclose(gauges["train_loss"], gauges["loss_main"] + 0.3 * gauges["loss_mtp"],
                      rtol=1e-5)
    assert {"moe_load_max_over_mean", "moe_local_assignment_pct"} <= set(gauges)
    # 4 of 8 experts held, 256 tokens x top 2 in tiles of 256: capacity and worst
    # case are the same 6 tiles, one path
    assert gauges["moe_bounded_path_pct"] == 100.0
    assert gauges["attention_residuals_kept_pct"] == 0.0  # off a TPU: the blocked XLA path
