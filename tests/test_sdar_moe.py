"""The ``sdar_moe`` family (SDAR-30B-A3B-Chat) on the decoder skeleton, trained
by block diffusion, at tiny widths with the published STRUCTURE on the CPU:
grouped-query attention in groups of 4 with per-head q/k norms and half-split
rotary, a softmax router over 16 experts with top 8, no shared expert, an
untied head, rows of 40 tokens in blocks of 4 run as the 80 positions ``[x_t ;
x]``. Its ops against dense oracles, the block-diffusion kernels against the
blocked XLA path, and the whole program against the benchmark's plain float32
reference (``benchmarks/reference/sdar_moe.py``, whose mask is an explicit
array over every key and whose experts are dense) on the benchmark's seeded
weights (``benchmarks/weights.py``) and noise drawn from the same key.

Tolerances as ``test_nemotron_h.py`` has them: float32 round-off (1e-5 of the
largest reference value for logits, 1e-6 relative for the loss, 2e-5 for every
leaf's gradient), which the same program in bfloat16 misses at least ten times
over, and which a mask that lets a noised token read its own clean block
misses by orders of magnitude.
"""

import hashlib
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check_train, run as run_mod, traffic
from benchmarks.reference import common as ref_common, sdar_moe as ref
from benchmarks.weights import make_weights_fn, seed_words, train_rng
from perceiver_io_tpu.models.decoder_lm import DecoderLMConfig
from perceiver_io_tpu.ops import masking, moe
from perceiver_io_tpu.ops import pallas_attention as pa
from perceiver_io_tpu.ops.grouped_query_attention import GroupedQueryAttention
from perceiver_io_tpu.ops.latent_attention import causal_attention
from perceiver_io_tpu.training import TrainState, make_block_diffusion_steps
from perceiver_io_tpu.training.steps import block_diffusion_noise

LOGIT_TOL, GRAD_TOL = 1e-5, 2e-5  # of the largest reference value: float32 round-off
LOSS_TOL = 1e-6              # relative
SEED = 2**31 + 45
PUBLISHED = 16  # the tiny router's width; the cell's is 128
CONFIG = "sdar_30b_a3b_chat_bd4_ep8"


def tiny_cell(dtype="float32", held=PUBLISHED, offset=0):
    """The benchmark's configuration with every width cut and the structure
    kept: the same files, the same builder, so the tests drive the cell's own
    code paths. Two layers, a router over 16 experts, rows of 40 tokens (10
    blocks of 4) drawn below the mask id, the vocabulary's last row."""
    cfg = run_mod.load_config(CONFIG)
    cfg.update(vocab_size=96, hidden_size=32, moe_intermediate_size=16, num_attention_heads=8,
               num_key_value_heads=2, head_dim=8, num_hidden_layers=2, num_experts=held,
               mask_token_id=95, dtype=dtype)
    cfg["deployment"] = dict(cfg["deployment"], experts_held=held, expert_offset=offset,
                             num_experts_published=PUBLISHED)
    mix = traffic.load_mix("train_ids_b1_w8192_v18991")
    mix.update(batch_size=2, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=40, high=95, length_low=40, length_high=40)
    return cfg, mix, importlib.import_module(f"benchmarks.configs.{cfg['builder']}")


def seeded(cfg, mix, builder):
    """The benchmark's weights, but for the q/k norms' scales: there they are
    1; here they are drawn, so that a norm applied on the wrong axis, or not
    at all, would be caught."""
    params = make_weights_fn(builder.param_shapes(cfg))(*seed_words(SEED))
    keys = iter(jax.random.split(jax.random.key(SEED % 1000), 2 * cfg["num_hidden_layers"]))
    for i in range(cfg["num_hidden_layers"]):
        for name in ("q_layernorm", "k_layernorm"):
            scale = params[f"layer_{i}"]["attn"][name]
            scale["scale"] = 1.0 + 0.1 * jax.random.normal(next(keys), scale["scale"].shape)
    batch = traffic.make_batches(mix, SEED)[0]
    return params, {k: jnp.asarray(v) for k, v in batch.items()}


def worst(got, want):
    """Largest difference over the largest reference magnitude."""
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


def worst_leaf(got, want):
    leaves = jax.tree.map(lambda g, w: worst(g, w) if float(jnp.max(jnp.abs(w))) else
                          float(jnp.max(jnp.abs(g))), got, want)
    return max(jax.tree.leaves(leaves))


def program_and_reference(dtype="float32", held=PUBLISHED, offset=0):
    """(logits gap, loss gap, worst leaf gradient gap, worst AdamW step gap)
    of the program against the reference given the same share, weights and
    noise (the train state's ``masking`` stream of step 0)."""
    cfg, mix, builder = tiny_cell(dtype, held, offset)
    params, batch = seeded(cfg, mix, builder)
    ids, rng = batch["token_ids"], train_rng(*seed_words(SEED))
    model, args = builder.build_model(cfg, mix["batch_size"], ids.shape[1])
    task = builder.reference_task(cfg)
    block, count = task["prepare"](batch, rng, 0)
    noisy, weights, masked_pct = block_diffusion_noise(
        ref.masking_key(rng, 0), ids, batch["pad_mask"], cfg["block_length"], cfg["mask_token_id"])
    # the two draws are the same bits
    assert np.array_equal(noisy, block["noisy_ids"]) and np.array_equal(weights, block["weights"])
    assert 0.0 < float(masked_pct) < 100.0 and "head" in params
    got_logits = model.apply({"params": params}, noisy, ids, method=model.diffusion_logits)
    want_logits = ref.logits(ref_common.F32, params, noisy, ids, builder.sizes(cfg))
    want_loss, want = ref_common.blocked_value_and_grad(
        task["ce_sum"](ref_common.F32), task["block_rows"])(params, block, count)
    (loss, metrics), got = jax.value_and_grad(
        lambda p: model.apply({"params": p}, noisy, ids, weights, method=model.diffusion_loss),
        has_aux=True)(params)
    assert float(metrics["moe_dropped_assignments"]) == 0
    assert float(metrics["moe_bounded_path_pct"]) == 100.0
    # one AdamW step: the train step (its own draw of the same noise) against
    # the reference's update, leaf by leaf as ``check_train`` reads it
    from perceiver_io_tpu.cli import common as cli_common

    tx, schedule = cli_common.optimizer_from_args(args)
    train_step, _, _ = make_block_diffusion_steps(model, schedule)
    state, step_metrics = jax.jit(train_step)(TrainState.create(params, tx, rng), batch)
    if dtype == "float32":  # the same loss, jitted or not
        assert abs(float(step_metrics["loss"]) - float(loss)) <= LOSS_TOL * abs(float(loss))
    assert float(step_metrics["bd_masked_pct"]) == float(masked_pct)
    opt = ref_common.Adam(params, task["learning_rate"], task["weight_decay"])
    stepped = opt.update(jax.tree.map(jnp.copy, params), want)
    step_gap = check_train._worst_leaf(
        np.asarray(check_train.leaf_diff_norms(state.params, params), np.float64),
        np.asarray(check_train.leaf_diff_norms(stepped, params), np.float64),
        np.ones(len(jax.tree.leaves(params)), bool))
    return (worst(got_logits, want_logits), abs(float(loss) - float(want_loss)) / float(want_loss),
            worst_leaf(got, want), step_gap)


@pytest.mark.parametrize("held, offset", [(PUBLISHED, 0), (4, 0), (4, 8)],
                         ids=["every_expert", "first_quarter", "third_quarter"])
def test_program_matches_the_plain_reference(held, offset):
    logits_gap, loss_gap, grad_gap, step_gap = program_and_reference("float32", held, offset)
    assert logits_gap < LOGIT_TOL
    assert loss_gap < LOSS_TOL
    # every leaf: the q/k norms' scales, the router, the three expert stacks
    assert grad_gap < GRAD_TOL
    # Adam's first step is lr * g / |g| per element: the per-leaf norms of the
    # change agree where the gradients do
    assert step_gap < GRAD_TOL


def test_bfloat16_fails_the_tolerances():
    logits_gap, loss_gap, grad_gap, _ = program_and_reference("bfloat16")
    assert logits_gap > 10 * LOGIT_TOL and loss_gap > 10 * LOSS_TOL and grad_gap > 10 * GRAD_TOL


def _leaking(rows, cols, half, block):
    """The mask with one fault: a noised token also reads the clean copy of
    its OWN block, which holds the very tokens it is asked to predict."""
    shift = block.bit_length() - 1
    q_clean, k_clean = rows >= half, cols >= half
    q_block = jnp.where(q_clean, rows - half, rows) >> shift
    k_block = jnp.where(k_clean, cols - half, cols) >> shift
    return ((q_clean & k_clean & (k_block <= q_block))
            | (~q_clean & k_clean & (k_block <= q_block))
            | (~q_clean & ~k_clean & (k_block == q_block)))


def test_a_leaking_mask_fails_the_reference_comparison(monkeypatch):
    monkeypatch.setattr(masking, "block_diffusion_visible", _leaking)
    jax.clear_caches()  # jax's tracing caches hold the blocks as they were first traced
    logits_gap, loss_gap, grad_gap, _ = program_and_reference()
    jax.clear_caches()
    assert logits_gap > 1e3 * LOGIT_TOL and loss_gap > 1e3 * LOSS_TOL and grad_gap > 1e3 * GRAD_TOL


# -- the mask and its kernels ---------------------------------------------------------


def explicit_mask(half, block):
    """The mask written out from its four rules, position by position."""
    n = 2 * half
    out = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(n):
            qi, kj = i % half, j % half
            if i < half:  # noised query
                out[i, j] = (kj // block == qi // block) if j < half else (kj // block < qi // block)
            else:         # clean query: never a noised key
                out[i, j] = j >= half and kj // block <= qi // block
    return out


@pytest.mark.parametrize("block", [1, 4, 8])
def test_mask_function_is_the_four_rules(block):
    half = 24
    rows, cols = np.arange(2 * half)[:, None], np.arange(2 * half)[None, :]
    got = np.asarray(masking.block_diffusion_visible(jnp.asarray(rows), jnp.asarray(cols),
                                                     half, block))
    want = explicit_mask(half, block)
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(ref.visible(rows, cols, half, block)), want)
    # L (L + B) visible pairs (benchmarks/flops_sdar_moe.py counts them so)
    assert want.sum() == half * (half + block)


# (half, block, query tile, key tile, query heads, key/value heads)
KERNEL_CASES = [
    (24, 4, 16, 16, 4, 2), (24, 8, 16, 8, 2, 1), (24, 1, 16, 16, 4, 4), (32, 4, 16, 32, 4, 2),
    (32, 8, 64, 16, 4, 2), (36, 4, 8, 8, 4, 2), (37, 4, 16, 16, 2, 1)]
KERNEL_CASE_IDS = ["straddling_tiles", "blocks_of_8_keys_by_8", "blocks_of_1", "wide_key_tiles",
                   "one_query_tile_over_both_halves", "every_tile_class", "padded_queries_and_keys"]
TILINGS = [case[:4] for case in KERNEL_CASES]


@pytest.mark.parametrize("half, block, q_blk, kv_blk, heads, kv_heads", KERNEL_CASES,
                         ids=KERNEL_CASE_IDS)
def test_block_diffusion_kernels_match_the_blocked_xla_path(half, block, q_blk, kv_blk, heads,
                                                            kv_heads):
    """Forward, dq and dk / dv (summed over a group in the kernel) in
    interpret mode against the blocked XLA path's explicit mask. Rows of 48
    positions in query tiles of 16 put a tile across the boundary of the two
    halves (rows 16-31 hold noised 16-23 and clean 0-7); tiles of 8 keys
    against tiles of 16 queries, and one query tile of 64 rows over both
    halves, walk the other ways the grids can cut the mask. Tiles of 8 over
    rows of 72 visit tiles of all five classes in one grid; rows of 74 have no
    divisor near 16, so queries and keys are padded to 80."""
    t = 2 * half
    keys = jax.random.split(jax.random.key(0), 4)
    q, weight = (jax.random.normal(key, (2, t, heads, 8)) for key in keys[:2])
    k, v = (jax.random.normal(key, (2, t, kv_heads, 8)) for key in keys[2:])

    def kernel(q, k, v):
        return pa.fused_attention(q, k, v, block_diffusion=pa.BlockDiffusion(half, block),
                                  q_block_size=q_blk, kv_block_size=kv_blk, interpret=True)

    def xla(q, k, v):
        return causal_attention(q, k, v, "xla", query_block=16, block_diffusion=block)

    with jax.default_matmul_precision("highest"):
        assert worst(kernel(q, k, v), xla(q, k, v)) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(xla(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert max(worst(g, w) for g, w in zip(got, want)) < 1e-5


def test_kernel_grids_walk_only_the_tiles_the_mask_shows():
    """At the cell's shape (8,192 tokens, 16,384 positions, query tiles of
    512 and key tiles of 1,024): a query tile reads at most its own noised
    key tile and 8 clean ones (9 steps, not the 16 of every key tile), and the
    steps it walks are the tiles holding a visible pair, each once."""
    half, q_blk, kv_blk, block = 8192, 512, 1024, 4
    bd = pa.BlockDiffusion(half, block)
    assert pa._bd_kv_steps(2 * half, q_blk, kv_blk, bd) == 9
    assert pa._bd_q_steps(2 * half, kv_blk, 2 * half, q_blk, bd) == 32
    # a smaller square, every tile checked against the explicit mask
    half, q_blk, kv_blk = 48, 16, 32
    bd = pa.BlockDiffusion(half, block)
    mask = explicit_mask(half, block)
    shown = {(ti, si) for ti in range(2 * half // q_blk) for si in range(2 * half // kv_blk)
             if mask[ti * q_blk:(ti + 1) * q_blk, si * kv_blk:(si + 1) * kv_blk].any()}
    steps = pa._bd_kv_steps(2 * half, q_blk, kv_blk, bd)
    walked = [(ti, int(s)) for ti in range(2 * half // q_blk) for j in range(steps)
              for s, on in [pa._bd_kv_step(ti, j, q_blk, kv_blk, bd)] if on]
    assert sorted(walked) == sorted(shown) and len(walked) == len(set(walked))
    steps = pa._bd_q_steps(2 * half, kv_blk, 2 * half, q_blk, bd)
    walked = [(int(t), si) for si in range(2 * half // kv_blk) for j in range(steps)
              for t, on in [pa._bd_q_step(si, j, q_blk, kv_blk, 2 * half, bd)] if on]
    assert sorted(walked) == sorted(shown) and len(walked) == len(set(walked))


def test_tile_classes_at_the_cells_shape():
    """A head visits 160 tiles: 112 wholly visible (the clean prefixes), and
    16 cut ones of each kind (the noised diagonal, the noised queries' last
    clean tile, the clean diagonal); no tile lies across the two halves. The
    kernels' case `every_tile_class` (rows of 72 in tiles of 8) visits all
    five classes."""
    classes = pa._bd_tile_classes(16384, 512, 1024, pa.BlockDiffusion(8192, 4))
    assert np.bincount(classes, minlength=5).tolist() == [112, 16, 16, 16, 0]
    assert pa.block_diffusion_unmasked_tiles_pct(
        16384, 128, pa.BlockDiffusion(8192, 4), kv_block_size=1024, q_block_size=512,
        interpret=False) == 70.0
    small = pa._bd_tile_classes(72, 8, 8, pa.BlockDiffusion(36, 4))
    assert np.bincount(small, minlength=5).tolist() == [9, 4, 3, 4, 13]


@pytest.mark.parametrize("half, block, q_blk, kv_blk", TILINGS, ids=KERNEL_CASE_IDS)
def test_tile_classes_follow_the_mask(half, block, q_blk, kv_blk):
    """On every tile a grid visits: MIXED where and only where the tile lies
    across the two halves or over padding (shown whole or not), WHOLE where
    and only where the explicit mask shows every pair of a tile that is not
    MIXED, and the mask a class applies is the mask's own on the tile: none
    for WHOLE, one compare for each cut kind."""
    bd = pa.BlockDiffusion(half, block)
    t_blk, t_pad, s_blk, s_pad = pa._block_sizes(2 * half, 2 * half, 8, kv_blk, q_blk, True)
    t, s = 2 * half + t_pad, 2 * half + s_pad
    explicit = explicit_mask(half, block)
    seen = set()
    for ti in range(t // t_blk):
        for j in range(pa._bd_kv_steps(t, t_blk, s_blk, bd)):
            si, on = pa._bd_kv_step(ti, j, t_blk, s_blk, bd)
            if not on:
                continue
            si = int(si)
            kind = int(pa._bd_tile_class(ti, si, t_blk, s_blk, bd, np))
            seen.add(kind)
            rows = np.arange(ti * t_blk, (ti + 1) * t_blk)
            cols = np.arange(si * s_blk, (si + 1) * s_blk)
            across = any(lo < half <= hi for lo, hi in ((rows[0], rows[-1]), (cols[0], cols[-1])))
            padded = rows[-1] >= 2 * half or cols[-1] >= 2 * half
            assert (kind == pa.MIXED) == (across or padded)
            want = np.asarray(masking.block_diffusion_visible(
                jnp.asarray(rows)[:, None], jnp.asarray(cols)[None, :], half, block))
            if not padded:
                assert np.array_equal(want, explicit[np.ix_(rows, cols)])
            assert (kind == pa.WHOLE) == (not (across or padded) and bool(want.all()))
            got = pa._bd_logits(jnp.zeros((t_blk, s_blk)), jnp.zeros((1, 1, s_blk)), kind,
                                ti, si, bd)
            assert np.array_equal(np.asarray(got) == 0, want)
    assert seen == set(np.unique(pa._bd_tile_classes(t, t_blk, s_blk, bd)).tolist())


@pytest.mark.parametrize("half, block, q_blk, kv_blk", TILINGS[-2:], ids=KERNEL_CASE_IDS[-2:])
def test_tile_classes_agree_with_the_general_mask_to_rounding(half, block, q_blk, kv_blk,
                                                             monkeypatch):
    """The kernels' output and gradients with a branch for each class are the
    numbers they give when every tile takes the general mask, to float32
    rounding (a few units in the last place of the largest value: in
    interpret mode XLA's CPU compiler may round the scaled scores of the two
    programs apart)."""
    keys = jax.random.split(jax.random.key(1), 4)
    q, weight = (jax.random.normal(key, (1, 2 * half, 4, 8)) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, 2 * half, 2, 8)) for key in keys[2:])

    def run():
        out, vjp = jax.vjp(lambda *a: pa.fused_attention(
            *a, block_diffusion=pa.BlockDiffusion(half, block), q_block_size=q_blk,
            kv_block_size=kv_blk, interpret=True), q, k, v)
        return [np.asarray(x) for x in (out, *vjp(weight))]

    by_class = run()
    monkeypatch.setattr(pa, "_bd_tile_class", lambda *a: pa.MIXED)
    monkeypatch.setattr(pa, "_bd_kinds", lambda *a: (pa.MIXED,))
    jax.clear_caches()  # the kernels are jitted on their static arguments
    general = run()
    jax.clear_caches()
    for got, want in zip(by_class, general):
        assert worst(got, want) < 1e-6


def _gauge(cfg, width=40):
    from perceiver_io_tpu import obs

    obs.get_registry().remove("bd_unmasked_tiles_pct")
    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    builder.build_model(cfg, 1, width)
    return obs.get_registry().snapshot()["gauges"]["bd_unmasked_tiles_pct"]


def test_unmasked_tiles_gauge_is_the_built_kernels_share(monkeypatch):
    """Where the model is built for the kernels, the gauge is the WHOLE share
    of the tiles over the grid the forward kernel is then built with (read
    from the kernel's own call); on the XLA path it is 0."""
    from perceiver_io_tpu.ops import latent_attention

    cfg, _, builder = tiny_cell()
    assert _gauge(dict(cfg, attn_impl="xla")) == 0.0
    monkeypatch.setattr(latent_attention, "pallas_blocks", lambda depth: (16, 16))
    gauge = _gauge(dict(cfg, attn_impl="pallas"))
    built, kinds = [], pa._bd_kinds
    monkeypatch.setattr(pa, "_bd_kinds", lambda *a: built.append(a) or kinds(*a))
    jax.clear_caches()
    layer = GroupedQueryAttention(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e6,
                                  block_diffusion=cfg["block_length"], attn_impl="pallas")
    x = jax.random.normal(jax.random.key(0), (1, 80, 16))
    layer.apply(layer.init(jax.random.key(1), x), x)
    jax.clear_caches()
    t, t_blk, s_blk, bd = built[0]
    classes = pa._bd_tile_classes(t, t_blk, s_blk, bd)
    assert (t, t_blk, s_blk, bd) == (80, 16, 16, pa.BlockDiffusion(40, 4))
    assert 0.0 < gauge == 100.0 * np.count_nonzero(classes == pa.WHOLE) / classes.size < 100.0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_what_a_token_reads(impl, monkeypatch):
    """Through the attention layer (rotary, q/k norms, both halves at the
    same positions): a noised token's output changes with a LATER token of its
    own noised block, and with nothing of a later block (noised or clean);
    the clean half never reads the noised half."""
    from perceiver_io_tpu.ops import latent_attention

    if impl == "pallas":  # the kernels in interpret mode, as on a TPU
        monkeypatch.setattr(latent_attention, "pallas_blocks", lambda depth: (16, 16))
    half, block = 24, 4
    layer = GroupedQueryAttention(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e6,
                                  block_diffusion=block, attn_impl=impl)
    x = jax.random.normal(jax.random.key(0), (1, 2 * half, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    y = layer.apply({"params": params}, x)

    def changed(position):
        other = x.at[:, position].set(jax.random.normal(jax.random.key(2), (16,)))
        return np.abs(np.asarray(layer.apply({"params": params}, other) - y)).max(axis=-1)[0] > 1e-6

    i = 9  # block 2 of the noised half: positions 8-11
    assert changed(11)[i]                                # a later token of its own noised block
    assert not any(changed(p)[i] for p in (12, 20, half + 12, half + 9, half + 11))
    assert changed(half + 7)[i] and changed(half)[i]     # the clean blocks before its own
    for p in (0, 9, 23):                                 # no noised token reaches the clean half
        assert not changed(p)[half:].any()
    assert changed(half + 9)[half + 11] and not changed(half + 12)[half + 11]


# -- the router -----------------------------------------------------------------------


def _expert_layer(cfg, held, offset, tile_rows=8, impl="xla"):
    return moe.MoELayer(
        num_experts=PUBLISHED, top_k=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"], num_shared=0, scoring_func="softmax",
        experts_held=held, expert_offset=offset, tile_rows=tile_rows, expert_impl=impl)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_softmax_router_against_dense_arithmetic(impl):
    """A softmax over all 16 experts, the top 8 with no bias, gates over their
    sum; every held expert on every token weighted by its gate; no shared
    expert and no selection-bias leaf; forward and gradients, on the XLA path
    and through the grouped-matmul kernel (interpret mode)."""
    cfg, mix, builder = tiny_cell()
    params, _ = seeded(cfg, mix, builder)
    p = params["layer_1"]["moe"]
    assert set(p) == {"router", "experts_gate", "experts_up", "experts_down"}
    x = jax.random.normal(jax.random.key(7), (2, 40, cfg["hidden_size"]))
    layer, sz = _expert_layer(cfg, PUBLISHED, 0, impl=impl), builder.sizes(cfg)
    weight = jax.random.normal(jax.random.key(8), x.shape)
    y, stats = layer.apply({"params": p}, x)
    assert float(stats["dropped_assignments"]) == 0
    assert worst(y, ref.expert_layer(ref_common.F32, p, x, sz)) < LOGIT_TOL
    # the gates by hand: softmax, top 8, renormalised; they sum to 1 a token
    scores = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    gates = ref.gates(ref_common.F32, p, x, sz)
    assert np.allclose(gates.sum(-1), 1.0, atol=1e-6)
    picked = jnp.sort(scores, axis=-1)[..., -8:]
    assert np.allclose(jnp.sort(gates, axis=-1)[..., -8:], picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0] * weight), (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(ref.expert_layer(ref_common.F32, p, x, sz) * weight),
                    (0, 1))(p, x)
    assert worst_leaf(got, want) < GRAD_TOL


def test_four_shares_of_four_experts_add_up_to_the_uncut_layer():
    """The share test: the parts that the four shares of 4 of the 16
    experts give add up to what the uncut reference gives for the whole layer
    (no shared expert: nothing is computed alike on every chip, so nothing is
    counted twice); the shares of the assignments add up to all of them."""
    cfg, mix, builder = tiny_cell()
    params, _ = seeded(cfg, mix, builder)
    p = params["layer_0"]["moe"]
    x = jax.random.normal(jax.random.key(7), (2, 40, cfg["hidden_size"]))
    whole = ref.expert_layer(ref_common.F32, p, x, builder.sizes(cfg))

    def share(offset):
        mine = dict(p, **{k: {"kernel": p[k]["kernel"][offset:offset + 4]}
                          for k in ("experts_gate", "experts_up", "experts_down")})
        y, stats = _expert_layer(cfg, 4, offset).apply({"params": mine}, x)
        assert float(stats["dropped_assignments"]) == 0
        sz = dict(builder.sizes(cfg), experts_held=4, expert_offset=offset)
        assert worst(y, ref.expert_layer(ref_common.F32, mine, x, sz)) < LOGIT_TOL
        return y, float(stats["local_assignment_pct"])

    parts, shares = zip(*(share(offset) for offset in range(0, PUBLISHED, 4)))
    assert worst(sum(parts), whole) < LOGIT_TOL
    assert np.isclose(sum(shares), 100.0)


# -- the configuration and the entry point --------------------------------------------


def test_published_config_gives_the_cells_parameter_count():
    """The configuration as run (every published width, 4 of 48 layers, 16 of
    128 experts, 18,992 of 151,936 vocabulary rows, embedding and head untied)
    is 456,346,624 parameters = 7.30 GB at 16 B."""
    cfg = run_mod.load_config(CONFIG)
    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    shapes = builder.param_shapes(cfg)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 456346624
    per_layer = [sum(x.size for x in jax.tree.leaves(shapes[f"layer_{i}"])) for i in range(4)]
    assert per_layer == [19140864 + 16 * 4718592] * 4
    assert shapes["embed"]["embedding"].shape == (18992, 2048)
    assert shapes["head"]["kernel"].shape == (2048, 18992)
    assert shapes["layer_0"]["attn"]["q_proj"]["kernel"].shape == (2048, 4096)
    assert shapes["layer_0"]["attn"]["k_proj"]["kernel"].shape == (2048, 512)
    assert shapes["layer_0"]["moe"]["router"]["kernel"].shape == (2048, 128)
    assert shapes["layer_0"]["moe"]["experts_gate"]["kernel"].shape == (16, 2048, 768)
    model, _ = builder.build_model(cfg)
    c = model.config
    assert c.mixers == ("full_attention",) * 4 and c.blocks[0] == ("full_attention", "moe")
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok) == (128, 16, 8)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (32, 4, 128)
    assert c.qk_norm and c.rotary and not (c.tie_word_embeddings or c.n_shared_experts)
    assert (c.scoring_func, c.objective, c.block_length, c.mask_token_id) == (
        "softmax", "block_diffusion", 4, 18991)
    # every number of the published config.json is in the file, but the three reduced
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}


@pytest.mark.parametrize("change, match", [
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"mlp_only_layers": [1]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"block_length": 3}, "power of two"), ({"objective": "autoregressive"}, "objective")])
def test_sdar_config_refuses_what_the_module_does_not_compute(change, match):
    from perceiver_io_tpu.cli import train_lm

    published = dict(train_lm.SMALL_SDAR, model_type="sdar_moe", vocab_size=50, mask_token_id=49,
                     use_sliding_window=False, mlp_only_layers=[], decoder_sparse_step=1,
                     rope_scaling=None, tie_word_embeddings=False, attention_bias=False)
    config = DecoderLMConfig.from_dict(published)
    assert config.mixers == ("full_attention",) * 2 and config.scoring_func == "softmax"
    with pytest.raises(ValueError, match=match):
        DecoderLMConfig.from_dict({**published, **change})


def test_train_lm_cli_trains_the_family_by_block_diffusion(tmp_path):
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
            "--model_type", "sdar_moe", "--experts_held", "4", "--expert_offset", "4"])
    finally:
        obs.configure_event_log(None)  # drains, then closes
    with open(log) as f:
        events = {r["event"]: r for r in map(json.loads, f) if "event" in r}
    layers = events["lm.layers"]
    assert layers["mixers"] == ["full_attention", "full_attention"]
    assert (layers["objective"], layers["block_length"]) == ("block_diffusion", 4)
    # off a TPU the attention is the blocked XLA path: no kernel tile runs unmasked
    assert layers["bd_unmasked_tiles_pct"] == 0.0
    assert (layers["kv_group"], layers["tied_head"], layers["shared_expert_width"]) == (2, False, 0)
    assert (layers["experts_held"], layers["experts_published"]) == (4, 8)
    # a row runs as two: 8 rows of 2 x 32 positions route
    assert events["moe.share"]["worst_case_rows"] == moe.TILE_ROWS * moe.worst_case_tiles(
        8 * 64 * 2, 4, moe.TILE_ROWS)
    rows = [r for r in read_metrics(run_dir) if "train_loss" in r]
    assert [r["step"] for r in rows] == [2]
    row = rows[0]
    assert np.isfinite(row["train_loss"]) and row["moe_dropped_assignments"] == 0
    assert "loss_main" not in row and 0.0 < row["bd_masked_pct"] < 100.0
    gauges = obs.get_registry().snapshot()["gauges"]
    assert 0.0 < gauges["bd_masked_pct"] < 100.0 and gauges["moe_bounded_path_pct"] == 100.0
    assert gauges["bd_unmasked_tiles_pct"] == 0.0


def test_noise_draws_are_exact_and_skip_padding():
    """Levels on the 1,024-step grid, masks where the uniform draw falls under
    the level, padding never masked, weights 1 / level where masked."""
    ids = jnp.arange(64).reshape(2, 32) % 50
    pad = jnp.zeros((2, 32), bool).at[1, 24:].set(True)
    noisy, weights, pct = block_diffusion_noise(jax.random.key(3), ids, pad, 4, 99)
    masked = np.asarray(noisy) == 99
    assert not masked[1, 24:].any() and np.array_equal(masked, np.asarray(weights) > 0)
    steps = 1024.0 / np.asarray(weights)[masked]  # k of the level k / 1024
    assert np.allclose(steps, np.round(steps), rtol=1e-6, atol=0)
    assert 1 <= np.round(steps).min() and np.round(steps).max() <= 1024
    assert np.isclose(float(pct), 100.0 * masked.sum() / 56, rtol=1e-6)


# This family's ``train_step`` at tiny widths, lowered: the text's SHA-256. A
# change that MEANS to change this step puts the new hash here and says so.
LOWERED = "ac78e4c3261a694f07dbca2e52cf82ac60eb941ef5858a5b2b41682679fb1c6b"


def test_cells_lowered_step_is_unchanged(tmp_path):
    cfg, mix, builder = tiny_cell(held=4)
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
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED
