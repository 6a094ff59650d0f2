"""The ``nemotron_h`` family (the Nemotron-H stack that
Nemotron-Labs-TwoTower-30B-A3B's ``config.json`` configures) on the decoder
skeleton, at tiny widths with the published STRUCTURE on the CPU: the three
kinds of one-sublayer block in ``MEMEM*EME``, four heads a group in the Mamba-2
mixers and in the attention block, 4 taps with bias, 8 of 128 experts held and
top 6, a shared expert of another width than the experts', an untied head. Its
ops against dense oracles, and the whole program against the benchmark's plain
float32 reference (``benchmarks/reference/nemotron_h.py``, whose state-space
scan walks the row token by token) on the benchmark's seeded weights
(``benchmarks/weights.py``), in both regimes of the scan's parameters: the
cell's draw (``A`` near -1, a state forgets in a token or two) and the
published initialisation (``Delta`` in [0.001, 0.1], ``A`` in [1, 16]: a state
is carried over hundreds of tokens, across every chunk of the row).

Tolerances as ``test_lfm2_moe.py`` has them: float32 round-off (1e-5 of the
largest reference value for logits, 1e-6 relative for the loss, 2e-5 for every
leaf's gradient), which the same program in bfloat16 misses at least ten times
over.
"""

import hashlib
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as run_mod, traffic
from benchmarks.reference import common as ref_common, nemotron_h as ref
from benchmarks.weights import make_weights_fn, seed_words, train_rng
from perceiver_io_tpu.models.decoder_lm import DecoderLMConfig
from perceiver_io_tpu.ops import mamba2, moe, pallas_ssd
from perceiver_io_tpu.ops.grouped_query_attention import GroupedQueryAttention
from perceiver_io_tpu.ops.latent_attention import RMSNorm, causal_attention
from perceiver_io_tpu.ops.pallas_attention import fused_attention
from perceiver_io_tpu.ops.rotary import apply_rotary_half, rotary_angles

LOGIT_TOL, GRAD_TOL = 1e-5, 2e-5  # of the largest reference value: float32 round-off
LOSS_TOL = 1e-6              # relative
SEED = 2**31 + 43
PUBLISHED_EXPERTS = 128
CONFIG = "nemotron_twotower_30b_a3b_ar_ep16"


def tiny_cell(dtype="float32", held=8, offset=0):
    """The benchmark's configuration with every width cut and the structure
    kept: the same files, the same builder, so the tests drive the cell's own
    code paths. Rows of 40 tokens in chunks of 16: two whole chunks and a
    half, so the scan pads."""
    cfg = run_mod.load_config(CONFIG)
    cfg.update(vocab_size=96, hidden_size=32, moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=24, num_attention_heads=8,
               num_key_value_heads=2, head_dim=8, mamba_num_heads=8, mamba_head_dim=4,
               n_groups=2, ssm_state_size=8, chunk_size=16, n_routed_experts=held, dtype=dtype)
    cfg["deployment"] = dict(cfg["deployment"], experts_held=held, expert_offset=offset)
    mix = traffic.load_mix("train_ids_b1_w8192")
    mix.update(batch_size=2, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=40, high=96, length_low=40, length_high=40)
    return cfg, mix, importlib.import_module(f"benchmarks.configs.{cfg['builder']}")


def published_scan_parameters(key, heads):
    """``A_log``, ``dt_bias``, ``D`` as the family initialises them."""
    k_a, k_dt, k_d = jax.random.split(key, 3)
    delta = jnp.exp(jax.random.uniform(k_dt, (heads,), minval=np.log(0.001), maxval=np.log(0.1)))
    return {"A_log": jnp.log(jax.random.uniform(k_a, (heads,), minval=1.0, maxval=16.0)),
            "dt_bias": delta + jnp.log(-jnp.expm1(-delta)),
            "D": 1.0 + 0.1 * jax.random.normal(k_d, (heads,))}


def seeded(cfg, mix, builder, regime="cell"):
    """The benchmark's weights, but for the selection bias: there it is the
    same constant for every expert (``ops/moe.py`` ``ExpertBias``), which
    decides no selection; here it is drawn, so that a program or a reference
    that left it out of the top 6 would be caught. ``regime`` 'published'
    also redraws every Mamba-2 layer's ``A_log``, ``dt_bias`` and ``D`` as the
    family initialises them (the cell's draw is ``weights.py``'s N(0, 0.02))."""
    params = make_weights_fn(builder.param_shapes(cfg))(*seed_words(SEED))
    keys = iter(jax.random.split(jax.random.key(SEED % 1000), 2 * cfg["num_hidden_layers"]))
    for name, layer in params.items():
        if "moe" in layer:
            bias = layer["moe"]["expert_bias"]
            bias["scale"] = 0.02 * jax.random.normal(next(keys), bias["scale"].shape)
        if "mamba" in layer and regime == "published":
            layer["mamba"].update(published_scan_parameters(next(keys), cfg["mamba_num_heads"]))
    batch = traffic.make_batches(mix, SEED)[0]
    return params, jnp.asarray(batch["token_ids"]), jnp.asarray(batch["pad_mask"])


def worst(got, want):
    """Largest difference over the largest reference magnitude."""
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


def worst_leaf(got, want):
    leaves = jax.tree.map(lambda g, w: worst(g, w) if float(jnp.max(jnp.abs(w))) else
                          float(jnp.max(jnp.abs(g))), got, want)
    return max(jax.tree.leaves(leaves))


def program_and_reference(dtype, held, offset, regime):
    """(logits gap, loss gap, worst leaf gradient gap) of the program against
    the reference given the same share."""
    cfg, mix, builder = tiny_cell(dtype, held, offset)
    params, ids, pad = seeded(cfg, mix, builder, regime)
    model, _ = builder.build_model(cfg)
    main, mtp = model.apply({"params": params}, ids)
    assert mtp is None and "head" in params  # no MTP module, an untied head
    want_logits = ref.logits(ref_common.F32, params, ids, builder.sizes(cfg))
    task = builder.reference_task(cfg)
    block, count = task["prepare"]({"token_ids": ids}, None, 0)
    want_loss, want = ref_common.blocked_value_and_grad(
        task["ce_sum"](ref_common.F32), task["block_rows"])(params, block, count)
    (loss, metrics), got = jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, pad, method=model.loss), has_aux=True)(params)
    assert float(metrics["loss_main"]) == float(loss) and "loss_mtp" not in metrics
    assert float(metrics["moe_dropped_assignments"]) == 0
    # 80 tokens x top 6 are two tiles of 256 at most: the layers build one path
    assert float(metrics["moe_bounded_path_pct"]) == 100.0
    return (worst(main, want_logits), abs(float(loss) - float(want_loss)) / float(want_loss),
            worst_leaf(got, want))


@pytest.mark.parametrize("regime, held, offset", [
    ("cell", 8, 0), ("published", 8, 0), ("published", PUBLISHED_EXPERTS, 0),
    ("published", 8, 16)],
    ids=["cells_draw", "published_init", "published_init_whole", "third_share"])
def test_program_matches_the_plain_reference(regime, held, offset):
    logits_gap, loss_gap, grad_gap = program_and_reference("float32", held, offset, regime)
    assert logits_gap < LOGIT_TOL
    assert loss_gap < LOSS_TOL
    # every leaf: A_log, dt_bias, D, the taps and their bias, the grouped
    # norm's scale, the selection bias's exact zero
    assert grad_gap < GRAD_TOL


def test_program_through_the_scan_kernels_matches_the_plain_reference(monkeypatch):
    """The whole program with its four mixers on the kernel pair (interpret
    mode; the resolver answers as it does on a TPU), under the blocks' remat:
    the kernels' ``custom_vjp`` inside ``nn.remat``, rows of 40 tokens in
    chunks of 16, under the published initialisation."""
    monkeypatch.setattr(mamba2, "scan_impl", lambda *sizes: "pallas")
    logits_gap, loss_gap, grad_gap = program_and_reference("float32", 8, 0, "published")
    assert logits_gap < LOGIT_TOL and loss_gap < LOSS_TOL and grad_gap < GRAD_TOL


def test_bfloat16_fails_the_tolerances():
    logits_gap, loss_gap, grad_gap = program_and_reference("bfloat16", 8, 0, "published")
    assert logits_gap > 10 * LOGIT_TOL and loss_gap > 10 * LOSS_TOL and grad_gap > 10 * GRAD_TOL


# -- the chunked scan -----------------------------------------------------------------


def scan_operands(regime, t=512, heads=4, p=8, groups=2, n=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(11), 8)
    x = jax.random.normal(keys[0], (2, t, heads, p))
    b, c = (jax.random.normal(k, (2, t, groups, n)) for k in keys[1:3])
    if regime == "published":  # Delta in [0.001, 0.1], A in [1, 16]: a long memory
        delta = jnp.exp(jax.random.uniform(keys[3], (2, t, heads), minval=np.log(0.001),
                                           maxval=np.log(0.1)))
        a = -jax.random.uniform(keys[4], (heads,), minval=1.0, maxval=16.0)
    else:  # the cell's draw: A near -1, Delta = softplus of a projection of order 1
        delta = jax.nn.softplus(jax.random.normal(keys[3], (2, t, heads)))
        a = -jnp.exp(0.02 * jax.random.normal(keys[4], (heads,)))
    d = 1.0 + 0.1 * jax.random.normal(keys[5], (heads,))
    return ((x.astype(dtype), delta, a, b.astype(dtype), c.astype(dtype), d),
            jax.random.normal(keys[6], x.shape))


def token_loop(x, delta, a, b, c, d):
    """The reference's recurrence, one token after another, and the skip."""
    rows, t, heads, p = x.shape
    groups = b.shape[2]
    shape = (rows, t, groups, heads // groups)
    y = ref.recurrence(ref_common.F32, x.reshape(*shape, p), delta.reshape(shape),
                       a.reshape(shape[2:]), b, c)
    return y.reshape(x.shape) + d[:, None] * x


@pytest.mark.parametrize("chunk", [16, 128, 512], ids=["chunks_of_16", "chunks_of_128", "whole_row"])
@pytest.mark.parametrize("regime", ["published", "cell"])
def test_chunked_scan_matches_the_token_loop(regime, chunk):
    """Rows of 512 tokens: 32 chunks, 4 chunks, and one; forward and the
    gradient of every operand. Under the published initialisation the state
    that enters the last chunk still holds the first chunk's tokens (a decay
    of exp(-0.05 x 16 x 512) at worst and exp(-0.001 x 1 x 512) = 0.6 at best).
    One chunk of 512 tokens gets three times the room: under the cell's draw its
    running sum reaches -350, of which float32 keeps 2e-5 (the published chunk
    is 128)."""
    operands, weight = scan_operands(regime)
    room = 3 if chunk > 128 else 1
    want = token_loop(*operands)
    got = mamba2.ssd_scan(*operands, chunk)
    assert worst(got, want) < room * LOGIT_TOL
    every = tuple(range(6))
    got_grads = jax.grad(lambda *o: jnp.sum(mamba2.ssd_scan(*o, chunk) * weight), every)(*operands)
    want_grads = jax.grad(lambda *o: jnp.sum(token_loop(*o) * weight), every)(*operands)
    assert max(worst(g, w) for g, w in zip(got_grads, want_grads)) < room * GRAD_TOL


def scan_kernels(x, delta, a, b, c, d, chunk):
    """The kernel pair (interpret mode) behind the einsum form's signature: the
    kernels take the mixer's layouts, heads and groups folded into the channels."""
    rows, t, heads, p = x.shape
    groups, n = b.shape[2:]
    y = pallas_ssd.ssd_scan(x.reshape(rows, t, heads * p), delta, a, b.reshape(rows, t, groups * n),
                            c.reshape(rows, t, groups * n), d, heads, groups, chunk, interpret=True)
    return y.reshape(x.shape)


SCANS = {"einsums": mamba2.ssd_scan, "kernels": scan_kernels}


@pytest.mark.parametrize("sizes", [dict(heads=4, p=8), dict(heads=8, p=64, n=32)],
                         ids=["one_slab_a_group", "two_slabs_a_group"])
@pytest.mark.parametrize("t", [512, 200], ids=["whole_chunks", "padded_row"])
@pytest.mark.parametrize("regime", ["published", "cell"])
def test_scan_kernels_match_the_token_loop(regime, t, sizes):
    """The Pallas kernel pair in interpret mode against the reference's
    recurrence: rows of 512 tokens (4 chunks of 128) and of 200 (the second
    chunk padded with ``Delta = 0`` tokens); two heads a group side by side in
    one slab of lanes, and four heads of 64 a group in two slabs of 128 lanes
    (the cell's shape of slab). Forward, and the gradient of all six operands
    through the backward kernel (the states entering each chunk kept by the
    forward, the state's cotangent carried from the last chunk to the first).
    Heads of 64 channels under the cell's draw get three times the room: ``A``'s
    gradient sums 64 channels' nearly cancelling terms of exponents near -100,
    and the chunked form in float32 keeps 4e-5 of it, kernels (4.6e-5) and
    einsums (3.2e-5) alike."""
    operands, weight = scan_operands(regime, t=t, **sizes)
    room = 3 if regime == "cell" and sizes["p"] == 64 else 1
    want = token_loop(*operands)
    assert worst(scan_kernels(*operands, 128), want) < LOGIT_TOL
    every = tuple(range(6))
    got_grads = jax.grad(lambda *o: jnp.sum(scan_kernels(*o, 128) * weight), every)(*operands)
    want_grads = jax.grad(lambda *o: jnp.sum(token_loop(*o) * weight), every)(*operands)
    assert [g.shape for g in got_grads] == [o.shape for o in operands]
    assert max(worst(g, w) for g, w in zip(got_grads, want_grads)) < room * GRAD_TOL


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, GRAD_TOL), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_scan_kernels_match_the_einsums_on_the_same_operands(dtype, tol):
    """The two forms of one scan: to float32 round-off on float32 operands, and
    on bfloat16 operands to the rounding both share (every contraction's
    operands to bfloat16, float32 sums)."""
    operands, weight = scan_operands("published", t=200, dtype=dtype)
    every = tuple(range(6))

    def value_and_grads(scan):
        return jax.value_and_grad(
            lambda *o: jnp.sum(scan(*o, 128).astype(jnp.float32) * weight), every)(*operands)

    (got, got_grads), (want, want_grads) = (value_and_grads(SCANS[k]) for k in ("kernels", "einsums"))
    assert abs(float(got) - float(want)) < tol * abs(float(want))
    assert [g.dtype for g in got_grads] == [w.dtype for w in want_grads]
    assert max(worst(g, w.astype(jnp.float32)) for g, w in zip(got_grads, want_grads)) < tol


def test_scan_runs_its_kernels_on_a_tpu_alone():
    """The mixer's question, which ``build_model`` asks too: off a TPU the
    einsums, whatever the sizes; the kernels' blocks are legal at the published
    sizes and not at the tests'."""
    assert mamba2.scan_impl(64, 64, 8, 128, 128, 8192) == "xla"
    fits = pallas_ssd.kernel_fits
    assert fits(64, 64, 8, 128, 128, 8192) and fits(128, 64, 8, 128, 128, 4096)
    # the tests' sizes; four heads a group; a row shorter than a chunk and no lane tile
    assert not (fits(8, 4, 2, 8, 16, 40) or fits(64, 64, 16, 128, 128, 8192)
                or fits(64, 64, 8, 128, 128, 40))


@pytest.mark.parametrize("form", sorted(SCANS))
def test_scan_carries_the_state_between_chunks(form):
    """A scan that dropped the carry would pass every test of the short-memory
    regime but for a few tokens a chunk: under the published initialisation
    the carried part is a large share of the output. On the kernel path a
    kernel that zeroed its scratch at every chunk is what fails here."""
    operands, _ = scan_operands("published")
    x, delta, a, b, c, d = operands
    scan = SCANS[form]
    whole = scan(*operands, 128)
    alone = jnp.concatenate([scan(x[:, lo:lo + 128], delta[:, lo:lo + 128], a,
                                  b[:, lo:lo + 128], c[:, lo:lo + 128], d, 128)
                             for lo in range(0, 512, 128)], axis=1)
    assert np.array_equal(np.asarray(whole[:, :128]), np.asarray(alone[:, :128]))
    assert worst(alone[:, 128:], whole[:, 128:]) > 0.1


def test_state_bytes_are_one_state_a_chunk():
    # the published sizes: 64 states of 2 MB a row of 8,192, not 8,192 of them
    assert mamba2.state_bytes(8192, 128, 64, 64, 128) == 64 * 64 * 64 * 128 * 4 == 134217728
    assert mamba2.state_bytes(40, 16, 8, 4, 8) == 3 * 8 * 4 * 8 * 4


def test_mixer_is_causal_and_matches_the_reference():
    """A token's output does not change when later tokens do (across a chunk's
    boundary and inside a chunk); the module is the reference's lines, forward
    and every gradient."""
    layer = mamba2.Mamba2Mixer(num_heads=8, head_dim=4, n_groups=2, state_size=8, chunk_size=16)
    sz = {"mamba_heads": 8, "mamba_head_dim": 4, "groups": 2, "state": 8, "eps": 1e-5}
    x = jax.random.normal(jax.random.key(2), (2, 40, 24))
    params = layer.init(jax.random.key(3), x)["params"]
    assert params["conv1d"]["kernel"].shape == (4, 32 + 2 * 16)
    assert params["conv1d"]["bias"].shape == (64,) and params["norm"]["scale"].shape == (32,)
    # the program's own initialisation is the published one
    delta = jax.nn.softplus(params["dt_bias"])
    assert float(delta.min()) >= 0.001 - 1e-6 and float(delta.max()) <= 0.1 + 1e-6
    assert 1.0 <= float(jnp.exp(params["A_log"]).min()) and float(jnp.exp(params["A_log"]).max()) <= 16.0
    params["conv1d"]["bias"] = 0.1 * jax.random.normal(jax.random.key(6), (64,))
    y = layer.apply({"params": params}, x)
    for first in (21, 32):
        later = x.at[:, first:].set(jax.random.normal(jax.random.key(4), (2, 40 - first, 24)))
        y_later = layer.apply({"params": params}, later)
        assert float(jnp.max(jnp.abs(y[:, :first] - y_later[:, :first]))) < 1e-6
        assert float(jnp.max(jnp.abs(y[:, first:] - y_later[:, first:]))) > 1e-3
    weight = jax.random.normal(jax.random.key(5), y.shape)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x) * weight), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(ref.mamba2_mixer(ref_common.F32, p, x, sz) * weight),
                    (0, 1))(params, x)
    assert worst(y, ref.mamba2_mixer(ref_common.F32, params, x, sz)) < LOGIT_TOL
    assert worst_leaf(got, want) < GRAD_TOL


def test_grouped_gated_norm_against_a_loop_over_groups():
    """The gate BEFORE the norm, each group of channels normalised on its own."""
    y, z = (jax.random.normal(jax.random.key(k), (2, 5, 24)) for k in (0, 1))
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (24,))
    got = mamba2.GatedGroupNorm(3, 1e-5).apply({"params": {"scale": scale}}, y, z)
    gated = np.asarray(y, np.float64) * np.asarray(z, np.float64) / (1 + np.exp(-np.asarray(z, np.float64)))
    want = np.zeros_like(gated)
    for g in range(3):
        part = gated[..., 8 * g:8 * g + 8]
        want[..., 8 * g:8 * g + 8] = part / np.sqrt((part ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want * np.asarray(scale), atol=1e-5)
    # not the norm before the gate, and not one norm over all the channels
    whole = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    assert np.abs(np.asarray(got) - whole * np.asarray(scale)).max() > 1e-2


# -- the expert layer -----------------------------------------------------------------


def _expert_layer(cfg, held, offset, tile_rows=8, impl="xla"):
    return moe.MoELayer(
        num_experts=PUBLISHED_EXPERTS, top_k=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"], num_shared=1,
        shared_width=cfg["moe_shared_expert_intermediate_size"], expert_form="relu2",
        gate_eps=ref.GATE_EPS, expert_bias_buffer=True,
        routed_scaling_factor=cfg["routed_scaling_factor"], experts_held=held,
        expert_offset=offset, tile_rows=tile_rows, expert_impl=impl)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_relu2_experts_against_dense_arithmetic(impl):
    """Two grouped matmuls and a squared ReLU between them, no gate; a shared
    expert of the same form and of another width; forward and gradients, on
    the XLA path and through the grouped-matmul kernel (interpret mode)."""
    cfg, mix, builder = tiny_cell(held=PUBLISHED_EXPERTS)
    params, _, _ = seeded(cfg, mix, builder)
    p = params["layer_1"]["moe"]
    assert set(p) == {"router", "expert_bias", "experts_up", "experts_down", "shared_expert"}
    assert set(p["shared_expert"]) == {"up", "down"}
    assert p["experts_up"]["kernel"].shape == (PUBLISHED_EXPERTS, 32, 16)
    assert p["shared_expert"]["up"]["kernel"].shape == (32, 24)
    x = jax.random.normal(jax.random.key(7), (2, 40, cfg["hidden_size"]))
    layer, sz = _expert_layer(cfg, PUBLISHED_EXPERTS, 0, impl=impl), builder.sizes(cfg)
    weight = jax.random.normal(jax.random.key(8), x.shape)
    y, stats = layer.apply({"params": p}, x)
    assert float(stats["dropped_assignments"]) == 0
    assert worst(y, ref.expert_layer(ref_common.F32, p, x, sz)) < LOGIT_TOL
    got = jax.grad(lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0] * weight), (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(ref.expert_layer(ref_common.F32, p, x, sz) * weight),
                    (0, 1))(p, x)
    assert worst_leaf(got, want) < GRAD_TOL


def test_grouped_matmul_blocks_a_width_that_no_lane_aligned_block_divides(monkeypatch):
    """The experts' width, 1856, is 14.5 x 128: the kernels block it by a
    lane-aligned size whose last block is part outside the array
    (``pallas_grouped_matmul._block``). In interpret mode a dimension is one
    block whole, so the blocks are forced here: 320 columns in blocks of 128
    (forward), 192 x 320 in blocks of 128 (the weight gradient); what the last
    block reads outside its operand reaches no value that is kept."""
    from perceiver_io_tpu.ops import pallas_grouped_matmul as pg

    monkeypatch.setattr(pg, "_block", lambda dim, target, interpret, itemsize=2: min(dim, 128))
    lhs = jax.random.normal(jax.random.key(0), (48, 192))
    rhs = jax.random.normal(jax.random.key(1), (3, 192, 320))
    weight = jax.random.normal(jax.random.key(2), (48, 320))
    tile_group = jnp.array([0, 0, 1, 2, 2, 3], jnp.int32)  # the last tile is no expert's

    def through(product):
        return lambda l, r: jnp.sum(product(l, r) * weight)

    def kernel(l, r):
        return pg.grouped_matmul(l, r, tile_group, 8, interpret=True)

    def xla(l, r):
        return pg.grouped_matmul_xla(l, r, tile_group, 8)

    with jax.default_matmul_precision("highest"):
        assert worst(kernel(lhs, rhs), xla(lhs, rhs)) < 1e-5
        got = jax.grad(through(kernel), (0, 1))(lhs, rhs)
        want = jax.grad(through(xla), (0, 1))(lhs, rhs)
    assert max(worst(g, w) for g, w in zip(got, want)) < 1e-5


def test_sixteen_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that the sixteen shares of 8 experts
    give, with the shared expert (which every chip computes alike) counted
    ONCE, add up to what the uncut reference gives for the whole 128-expert
    layer; the shares of the assignments add up to all of them; nothing is
    dropped on either path."""
    cfg, mix, builder = tiny_cell(held=PUBLISHED_EXPERTS)
    params, _, _ = seeded(cfg, mix, builder)
    p = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.key(7), (2, 40, cfg["hidden_size"]))
    whole = ref.expert_layer(ref_common.F32, p, x, builder.sizes(cfg))
    shared = ref.shared_expert(ref_common.F32, p["shared_expert"], x)
    # 80 tokens x top 6 in tiles of 8: a sixteenth share's bounded buffer is
    # 23 tiles (four times the expected 30 rows, and 8) of the worst case's 68
    assert moe.capacity_tiles(80, 6, 8, PUBLISHED_EXPERTS, 8) == 23
    assert moe.worst_case_tiles(480, 8, 8) == 68

    def share(offset, p=p, bounded_pct=100.0):
        mine = dict(p, **{k: {"kernel": p[k]["kernel"][offset:offset + 8]}
                          for k in ("experts_up", "experts_down")})
        y, stats = _expert_layer(cfg, 8, offset).apply({"params": mine}, x)
        assert float(stats["dropped_assignments"]) == 0
        assert float(stats["bounded_path_pct"]) == bounded_pct
        # the same share of the reference
        sz = dict(builder.sizes(cfg), experts_held=8, expert_offset=offset)
        assert worst(y, ref.expert_layer(ref_common.F32, mine, x, sz)) < LOGIT_TOL
        return y - shared, float(stats["local_assignment_pct"])

    parts, shares = zip(*(share(offset) for offset in range(0, PUBLISHED_EXPERTS, 8)))
    assert worst(sum(parts) + shared, whole) < LOGIT_TOL
    assert np.isclose(sum(shares), 100.0)
    crowded = dict(p, expert_bias={"scale": jnp.zeros(PUBLISHED_EXPERTS).at[:8].set(10.0)})
    # 480 rows: 60 tiles or more of the 23: the worst-case buffer, nothing dropped
    assert share(0, crowded, bounded_pct=0.0)[1] == 100.0


# -- attention without positions, groups of 16 ----------------------------------------


def test_grouped_kv_kernel_at_group_16_and_depth_128():
    """The cell's shape of head: 32 query heads on 2 key/value heads, 128
    deep. The kernel in interpret mode (forward, dq, and dk / dv summed over a
    group's 16 query heads in the kernel) against the blocked XLA path on the
    grouped operands."""
    keys = jax.random.split(jax.random.key(1), 4)
    q, weight = (jax.random.normal(key, (1, 64, 32, 128)) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, 64, 2, 128)) for key in keys[2:])

    def kernel(q, k, v):
        return fused_attention(q, k, v, causal_offset=0, kv_block_size=32, q_block_size=32)

    def xla(q, k, v):
        return causal_attention(q, k, v, "xla", query_block=16)

    with jax.default_matmul_precision("highest"):
        assert worst(kernel(q, k, v), xla(q, k, v)) < 1e-5
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(xla(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert max(worst(g, w) for g, w in zip(got, want)) < 1e-5


def test_attention_without_norm_or_positions_and_with_both():
    """``qk_norm`` and ``rotary`` off: four projections and the causal softmax,
    nothing else (no scale leaves); on (the default): today's LFM2 module, the
    per-head norms before the half-split rotary."""
    x = jax.random.normal(jax.random.key(0), (2, 12, 16))
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e4, rms_norm_eps=1e-5)
    bare = GroupedQueryAttention(qk_norm=False, rotary=False, **kw)
    params = bare.init(jax.random.key(1), x)["params"]
    assert set(params) == {"q_proj", "k_proj", "v_proj", "out_proj"}

    def heads(p, name, count):
        return (x @ p[f"{name}_proj"]["kernel"]).reshape(2, 12, count, 8)

    def attend(p, q, k):
        out = causal_attention(q, k, heads(p, "v", 2), "xla")
        return out.reshape(2, 12, 32) @ p["out_proj"]["kernel"]

    with jax.default_matmul_precision("highest"):
        got = bare.apply({"params": params}, x)
        assert worst(got, attend(params, heads(params, "q", 4), heads(params, "k", 2))) < 1e-6
        # without positions a token's output does not depend on WHERE its past is
        swapped = x.at[:, [0, 1]].set(x[:, [1, 0]])
        np.testing.assert_allclose(bare.apply({"params": params}, swapped)[:, 2:], got[:, 2:],
                                   atol=1e-5)
        full = GroupedQueryAttention(**kw)
        p = full.init(jax.random.key(1), x)["params"]
        assert set(p) == set(params) | {"q_layernorm", "k_layernorm"}
        p["q_layernorm"]["scale"] = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (8,))
        cos, sin = rotary_angles(jnp.arange(12), 8, 1e4)
        q, k = (apply_rotary_half(RMSNorm(1e-5).apply({"params": p[f"{n}_layernorm"]},
                                                      heads(p, n, c)), cos, sin)
                for n, c in (("q", 4), ("k", 2)))
        assert worst(full.apply({"params": p}, x), attend(p, q, k)) < 1e-6
        assert worst(full.apply({"params": p}, swapped)[:, 2:],
                     full.apply({"params": p}, x)[:, 2:]) > 1e-3


# -- the skeleton and its configuration -----------------------------------------------


def test_a_tokens_logits_do_not_change_when_later_tokens_do():
    cfg, mix, builder = tiny_cell()
    params, ids, _ = seeded(cfg, mix, builder, "published")
    model, _ = builder.build_model(cfg)
    later = ids.at[:, 21:].set((ids[:, 21:] + 7) % cfg["vocab_size"])
    main, _ = model.apply({"params": params}, ids)
    main_later, _ = model.apply({"params": params}, later)
    assert float(jnp.max(jnp.abs(main[:, :21] - main_later[:, :21]))) < 1e-5
    assert float(jnp.max(jnp.abs(main[:, 21:] - main_later[:, 21:]))) > 1e-3


def test_published_config_gives_the_cells_parameter_count():
    """The configuration as run (all published widths, published blocks 0-8,
    8 of 128 experts, 16,384 vocabulary rows, embedding and head untied) is
    666,963,456 parameters = 10.67 GB at 16 B."""
    cfg = run_mod.load_config(CONFIG)
    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    shapes = builder.param_shapes(cfg)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 666963456
    per_block = [sum(x.size for x in jax.tree.leaves(shapes[f"layer_{i}"])) for i in range(9)]
    m, e, a = 38744896, 100125440, 23399040
    assert per_block == [m, e, m, e, m, a, e, m, e]
    assert shapes["embed"]["embedding"].shape == (16384, 2688)
    assert shapes["head"]["kernel"].shape == (2688, 16384)
    assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (2688, 4096 + 6144 + 64)
    assert shapes["layer_0"]["mamba"]["conv1d"]["kernel"].shape == (4, 6144)
    assert shapes["layer_1"]["moe"]["shared_expert"]["up"]["kernel"].shape == (2688, 3712)
    model, _ = builder.build_model(cfg)
    c = model.config
    assert c.one_sublayer_blocks and c.mixers == (
        "mamba2", "moe", "mamba2", "moe", "mamba2", "full_attention", "moe", "mamba2", "moe")
    assert c.blocks[1] == ("", "moe") and c.blocks[5] == ("full_attention", "")
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok) == (128, 8, 6)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (32, 2, 128)
    assert not (c.qk_norm or c.rotary or c.tie_word_embeddings or c.num_nextn_predict_layers)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.n_groups, c.ssm_state_size) == (64, 64, 8, 128)
    assert (c.conv_kernel, c.chunk_size, c.rms_norm_eps) == (4, 128, 1e-5)
    assert c.mlp_hidden_act == "relu2" and c.moe_shared_expert_intermediate_size == 3712
    assert c.gate_eps == 1e-20 and c.expert_bias_buffer and c.routed_scaling_factor == 2.5
    # four times the expected 3,072 rows and 8 tiles: a cond between that and the worst case's
    assert moe.capacity_tiles(8192, 6, 8, 128, moe.TILE_ROWS) == 56 < moe.worst_case_tiles(
        49152, 8, moe.TILE_ROWS) == 200
    # every number of the published config.json is in the file, but the four reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cfg["source"])
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"}


@pytest.mark.parametrize("change, match", [
    ({"use_conv_bias": False}, "use_conv_bias"), ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"hybrid_override_pattern": "ME-*"}, "hybrid_override_pattern"),
    ({"time_step_limit": [0.0, 1.0]}, "time_step_limit"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"n_groups": 3}, "groups"), ({"num_key_value_heads": 3}, "key/value heads")])
def test_nemotron_h_config_refuses_what_the_module_does_not_compute(change, match):
    from perceiver_io_tpu.cli import train_lm

    published = dict(train_lm.SMALL_NEMOTRON_H, model_type="nemotron_h", vocab_size=50,
                     time_step_limit=[0.0, None])
    config = DecoderLMConfig.from_dict(published)
    assert config.mixers == ("mamba2", "moe", "mamba2", "full_attention", "moe")
    assert config.num_hidden_layers == 5
    with pytest.raises(ValueError, match=match):
        DecoderLMConfig.from_dict({**published, **change})


@pytest.mark.parametrize("case", ["fits", "xla"])
def test_remat_policy_counts_the_attention_sublayer(case, monkeypatch, remat_policy_events):
    """One attention block of nine: the policy reckons that block's kept bytes
    alone (a Mamba-2 or an expert block keeps nothing named), and where it
    engages a step runs the forward kernel once."""
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
    assert record["layers"] == 1
    assert record["saved_bytes"] == (
        b * t * cfg["num_attention_heads"] * (cfg["head_dim"] * 4 + 8) if case == "fits" else 0)
    assert record["engaged"] is (case == "fits")
    assert float(metrics["attention_residuals_kept_pct"]) == (100.0 if case == "fits" else 0.0)
    calls = [_kernel_calls(jax.make_jaxpr(step)(params).jaxpr, kernel)
             for kernel in (pa.KERNEL_FWD, pa.KERNEL_DQ, pa.KERNEL_DKV)]
    assert calls == ([1, 1, 1] if case == "fits" else [0, 0, 0])


# This family's ``train_step`` at tiny widths, lowered: the text's SHA-256, as
# this PR (38) leaves it. The four older cells' are held in
# ``test_lfm2_moe.py`` (``LOWERED``), as their PRs left them: PR 38 changed
# none. A PR that MEANS to change this step puts the new hash here and says so.
LOWERED = "8deb374c95e7e4890d78008aa8f6047cec4a3a4e8c9e93bba21a4b21ad80e97e"


def test_cells_lowered_step_is_unchanged(tmp_path):
    cfg, mix, builder = tiny_cell()
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
    # 320 tokens x top 6 in tiles of 256: 15 tiles of the worst case's 16: forward
    # and backward of the four expert layers are a ``cond`` each
    assert text.count("stablehlo.case") == 8
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED


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
            "--model_type", "nemotron_h", "--hybrid_override_pattern", "MEM*EME",
            "--experts_held", "2", "--expert_offset", "4"])
    finally:
        obs.configure_event_log(None)  # drains, then closes
    with open(log) as f:
        events = {r["event"]: r for r in map(json.loads, f) if "event" in r}
    # one ``lm.layers`` a build, beside ``moe.share``
    layers = events["lm.layers"]
    assert layers["mixers"] == ["mamba2", "moe", "mamba2", "full_attention", "moe", "mamba2", "moe"]
    assert layers["one_sublayer_blocks"] is True and layers["dense_layers"] == 0
    assert (layers["kv_group"], layers["tied_head"], layers["shared_expert_width"]) == (2, False, 48)
    assert (layers["experts_held"], layers["experts_published"]) == (2, 8)
    assert (layers["ssd_chunk"], layers["ssd_state"]) == (16, [8, 8, 16])
    assert layers["ssd_scan_kernel"] is False  # off a TPU: the einsums
    assert events["moe.share"]["held"] == 2 and events["moe.share"]["offset"] == 4
    rows = [r for r in read_metrics(run_dir) if "train_loss" in r]
    assert [r["step"] for r in rows] == [2]
    row = rows[0]
    assert np.isfinite(row["train_loss"]) and row["moe_dropped_assignments"] == 0
    assert row["train_loss"] == row["loss_main"] and "loss_mtp" not in row
    assert row["moe_local_assignment_pct"] < 100.0
    gauges = obs.get_registry().snapshot()["gauges"]
    # two chunks of 16 tokens a row: two states of 8 x 8 x 16 float32
    assert gauges["ssd_state_bytes"] == 2 * 8 * 8 * 16 * 4
    assert gauges["moe_bounded_path_pct"] == 100.0
    assert gauges["attention_residuals_kept_pct"] == 0.0  # off a TPU: the blocked XLA path
    assert gauges["ssd_scan_kernel_pct"] == 0.0


@pytest.mark.parametrize("impl, share", [("pallas", 100.0), ("xla", 0.0)])
def test_build_model_publishes_which_form_of_the_scan_runs(impl, share, monkeypatch):
    """``ssd_scan_kernel_pct`` is the answer of the mixer's own resolver, asked
    with the configuration's sizes, and only a stack with a Mamba-2 mixer
    publishes it."""
    from perceiver_io_tpu import obs
    from perceiver_io_tpu.cli import train_lm

    asked = []
    monkeypatch.setattr(mamba2, "scan_impl", lambda *sizes: asked.append(sizes) or impl)
    registry = obs.get_registry()
    registry.remove("ssd_scan_kernel_pct")
    cfg, _, builder = tiny_cell()
    builder.build_model(cfg)
    assert asked == [(cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                      cfg["ssm_state_size"], cfg["chunk_size"], 8)]  # the builder's rows of 8
    assert registry.snapshot()["gauges"]["ssd_scan_kernel_pct"] == share
    registry.remove("ssd_scan_kernel_pct")
    args = train_lm.build_parser().parse_args(
        ["--model_type", "lfm2_moe", "--batch_size", "2", "--max_seq_len", "16"])
    train_lm.build_model(args, 50)
    assert len(asked) == 1 and "ssd_scan_kernel_pct" not in registry.snapshot()["gauges"]
