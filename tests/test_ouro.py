"""The ``ouro`` family (Ouro-2.6B as a looped language model) on the decoder
skeleton, at tiny widths with the published STRUCTURE on the CPU: two
sandwich-norm layers of multi-head attention (4 heads, a key/value head each)
and a dense SwiGLU, looped R = 3 times over one weight set with the final
norm between steps, an exit gate and the untied head after every step, the
expected-exit loss less the entropy term. The whole program against the
benchmark's plain float32 reference (``benchmarks/reference/ouro.py``, whose
loop is a Python loop of the same weights and whose mask is an explicit
array) on the benchmark's seeded weights (``benchmarks/weights.py``), its
norms' scales drawn.

Tolerances as ``test_sdar_moe.py`` has them: float32 round-off (1e-5 of the
largest reference value for logits and exit probabilities, 1e-6 relative for
the loss, 2e-5 for every leaf's gradient), which the same program in bfloat16
misses at least ten times over. Each of three planted faults (a weight set of
its own for every loop step, no final norm between steps, the entropy term
left out) fails one of these tests.
"""

import functools
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmarks import check_train, run as run_mod, traffic
from benchmarks.reference import common as ref_common, ouro as ref
from benchmarks.weights import make_weights_fn, seed_words, train_rng
from perceiver_io_tpu.models import decoder_lm
from perceiver_io_tpu.models.decoder_lm import DecoderLMConfig
from perceiver_io_tpu.ops.latent_attention import RMSNorm
from perceiver_io_tpu.training import TrainState, make_lm_steps

LOGIT_TOL, GRAD_TOL = 1e-5, 2e-5  # of the largest reference value: float32 round-off
LOSS_TOL = 1e-6                   # relative
SEED = 2**31 + 53
CONFIG = "ouro_2_6b_ut4"
_SCAN = nn.scan


def tiny_cell(dtype="float32", steps=3, beta=0.1):
    """The benchmark's configuration with every width cut and the structure
    kept: the same files, the same builder, so the tests drive the cell's own
    code paths. Two layers looped ``steps`` times, hidden 64, 4 heads of 16,
    a vocabulary of 97, rows of 32 tokens."""
    cfg = run_mod.load_config(CONFIG)
    cfg.update(vocab_size=97, hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
               layer_types=["full_attention"] * 2, total_ut_steps=steps,
               exit_entropy_beta=beta, dtype=dtype)
    mix = traffic.load_mix("train_ids_b1_w4096_v49152")
    mix.update(batch_size=2, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=32, high=97, length_low=32, length_high=32)
    return cfg, mix, importlib.import_module(f"benchmarks.configs.{cfg['builder']}")


@functools.lru_cache(maxsize=None)
def seeded():
    """The benchmark's weights, but for the norms' scales: there they are 1;
    here they are drawn, so that a norm applied on the wrong axis, in the
    wrong place, or not at all would be caught. The tree does not depend on R
    or the compute dtype (``test_parameter_tree_does_not_depend_on_the_loop_count``),
    so it is drawn once, with the batch."""
    cfg, mix, builder = tiny_cell()
    params = make_weights_fn(builder.param_shapes(cfg))(*seed_words(SEED))
    keys = iter(jax.random.split(jax.random.key(SEED % 1000), 64))

    def draw(path, leaf):
        if path[-1].key == "scale":
            return 1.0 + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(draw, params)
    batch = traffic.make_batches(mix, SEED)[0]
    return params, {k: jnp.asarray(v) for k, v in batch.items()}


def worst(got, want):
    """Largest difference over the largest reference magnitude."""
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


def worst_leaf(got, want):
    return max(jax.tree.leaves(jax.tree.map(worst, got, want)))


@functools.lru_cache(maxsize=None)
def reference(steps):
    """The reference's exits, loss and gradient at ``steps`` loop steps, on
    the seeded weights and batch (the same for every test that asks)."""
    cfg, mix, builder = tiny_cell(steps=steps)
    params, batch = seeded()
    task, sz = builder.reference_task(cfg), builder.sizes(cfg)
    block, count = task["prepare"](batch, None, 0)
    exits = jax.jit(lambda p: ref.logits(ref_common.F32, p, batch["token_ids"], sz))(params)
    loss, grads = ref_common.blocked_value_and_grad(
        task["ce_sum"](ref_common.F32), task["block_rows"])(params, block, count)
    return exits, loss, grads


def program_and_reference(dtype="float32", steps=3, program_beta=0.1, adam_step=False):
    """The gaps of the program against the reference on the same weights and
    batch: every exit's logits, the exit probabilities, the loss, the worst
    leaf's gradient, and with ``adam_step`` the worst leaf of one AdamW step
    through the train step; the program's entropy weight may be set apart."""
    cfg, mix, builder = tiny_cell(dtype, steps, program_beta)
    params, batch = seeded()
    ids, pad = batch["token_ids"], batch["pad_mask"]
    model, args = builder.build_model(cfg, mix["batch_size"], ids.shape[1])
    ref_cfg = dict(cfg, exit_entropy_beta=0.1)
    task = builder.reference_task(ref_cfg)
    (want_logits, want_p), want_loss, want = reference(steps)
    got_logits, got_p = jax.jit(lambda p: model.apply({"params": p}, ids,
                                                      method=model.exit_logits))(params)
    (loss, metrics), got = jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, pad, method=model.loss), has_aux=True))(params)
    gaps = {"logits": worst(got_logits, want_logits), "exits": worst(got_p, want_p),
            "loss": abs(float(loss) - float(want_loss)) / float(want_loss),
            "grad": worst_leaf(got, want), "metrics": metrics, "p": got_p}
    if not adam_step:
        return gaps
    # one AdamW step: the train step against the reference's update, leaf by
    # leaf as ``check_train`` reads it
    from perceiver_io_tpu.cli import common as cli_common

    tx, schedule = cli_common.optimizer_from_args(args)
    train_step, _, _ = make_lm_steps(model, schedule)
    rng = train_rng(*seed_words(SEED))
    state, step_metrics = jax.jit(train_step)(TrainState.create(params, tx, rng), batch)
    if dtype == "float32":  # the same loss, jitted or not
        assert abs(float(step_metrics["loss"]) - float(loss)) <= LOSS_TOL * abs(float(loss))
    opt = ref_common.Adam(params, task["learning_rate"], task["weight_decay"])
    stepped = opt.update(jax.tree.map(jnp.copy, params), want)
    step_gap = check_train._worst_leaf(
        np.asarray(check_train.leaf_diff_norms(state.params, params), np.float64),
        np.asarray(check_train.leaf_diff_norms(stepped, params), np.float64),
        np.ones(len(jax.tree.leaves(params)), bool))
    return dict(gaps, step=step_gap)


def within_tolerances(gaps):
    return (gaps["logits"] < LOGIT_TOL and gaps["exits"] < LOGIT_TOL
            and gaps["loss"] < LOSS_TOL and gaps["grad"] < GRAD_TOL)


def test_program_matches_the_plain_reference():
    gaps = program_and_reference(adam_step=True)
    # every exit's logits and the exit distribution
    assert gaps["logits"] < LOGIT_TOL and gaps["exits"] < LOGIT_TOL
    assert gaps["loss"] < LOSS_TOL
    # every leaf: the eight norms of a layer, the projections, the final norm,
    # the exit gate's kernel and bias, the embedding and the head
    assert gaps["grad"] < GRAD_TOL
    # Adam's first step is lr * g / |g| per element: the per-leaf norms of the
    # change agree where the gradients do
    assert gaps["step"] < GRAD_TOL
    metrics, p = gaps["metrics"], np.asarray(gaps["p"])
    assert np.allclose(p.sum(axis=0), 1.0, atol=1e-6) and (p > 0).all()
    assert sorted(metrics) == ["attention_residuals_kept_pct", "loss_ut_1", "loss_ut_2",
                               "loss_ut_3", "ut_expected_exit"]
    assert 1.0 < float(metrics["ut_expected_exit"]) < 3.0


def test_bfloat16_fails_the_tolerances():
    """Every exit's logits, the exit probabilities and the gradients miss by
    more than ten times (1.6e-2, 3.8e-3, 2.2e-2). The loss does not: at random
    weights the exits' cross-entropies move by some 4e-4 each in bfloat16 and
    their p-weighted sum over these 62 tokens by 1e-7."""
    gaps = program_and_reference("bfloat16")
    assert (gaps["logits"] > 10 * LOGIT_TOL and gaps["exits"] > 10 * LOGIT_TOL
            and gaps["grad"] > 10 * GRAD_TOL)


def test_one_loop_step_is_one_sandwich_norm_stack():
    """R = 1: p_1 = 1 exactly, no entropy term, the loss the one exit's
    mean cross-entropy, and the reference's single pass."""
    gaps = program_and_reference(steps=1)
    assert within_tolerances(gaps)
    assert np.array_equal(np.asarray(gaps["p"]), np.ones_like(gaps["p"]))
    metrics = gaps["metrics"]
    assert float(metrics["ut_expected_exit"]) == 1.0 and "loss_ut_2" not in metrics
    cfg, mix, builder = tiny_cell(steps=1)
    params, batch = seeded()
    model, _ = builder.build_model(cfg)
    loss, metrics = model.apply({"params": params}, batch["token_ids"], batch["pad_mask"],
                                method=model.loss)
    assert float(loss) == float(metrics["loss_ut_1"])


def test_parameter_tree_does_not_depend_on_the_loop_count():
    """One weight set, whatever R: the same leaves, names and shapes."""
    trees = [jax.tree.map(lambda s: (s.shape, s.dtype), tiny_cell(steps=r)[2].param_shapes(
        tiny_cell(steps=r)[0])) for r in (1, 3, 4)]
    assert trees[0] == trees[1] == trees[2]
    loop = trees[0]["loop"]
    assert sorted(loop) == ["final_norm", "layer_0", "layer_1"]
    assert sorted(loop["layer_0"]) == ["attn", "attn_norm", "attn_post_norm", "ffn_norm",
                                       "ffn_post_norm", "mlp"]
    assert trees[0]["exit_gate"] == {"kernel": ((64, 1), jnp.float32),
                                     "bias": ((1,), jnp.float32)}
    assert "final_norm" not in trees[0]  # the loop's own, applied at every step


# -- three planted faults, each of which one of the tests above sees ------------------


def _scan_per_step_weights(target, variable_broadcast=None, split_rngs=None, **kw):
    """``nn.scan`` with a weight set of its own for every loop step."""
    return _SCAN(target, variable_axes={"params": 0}, split_rngs={"params": True}, **kw)


class _NoNormBetweenSteps(decoder_lm.LoopStep):
    """The final norm on the exit's state only: the next step reads the last
    block's output as it is."""

    @nn.compact
    def __call__(self, x, _):
        c = self.config
        block = nn.remat(decoder_lm.DecoderBlock, policy=self.policy)
        for i, (mixer, ffn) in enumerate(c.blocks):
            x, _ = block(c, mixer, ffn, self.attn_impl, self.expert_impl, self.dtype,
                         name=f"layer_{i}")(x)
        return x, RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x)


def test_a_weight_set_per_loop_step_fails_the_tree_test(monkeypatch):
    monkeypatch.setattr(decoder_lm.nn, "scan", _scan_per_step_weights)
    cfg, _, builder = tiny_cell(steps=3)
    layer = builder.param_shapes(cfg)["loop"]["layer_0"]
    assert layer["mlp"]["up"]["kernel"].shape == (3, 64, 96)  # stacked: the tree depends on R
    with pytest.raises(AssertionError):
        test_parameter_tree_does_not_depend_on_the_loop_count()


def test_no_final_norm_between_steps_fails_the_reference_comparison(monkeypatch):
    monkeypatch.setattr(decoder_lm, "LoopStep", _NoNormBetweenSteps)
    gaps = program_and_reference()
    assert gaps["logits"] > 1e3 * LOGIT_TOL and gaps["grad"] > 1e3 * GRAD_TOL


def test_the_entropy_term_left_out_fails_the_reference_comparison():
    gaps = program_and_reference(program_beta=0.0)
    assert gaps["logits"] < LOGIT_TOL  # the forward pass is the same
    assert gaps["loss"] > 1e3 * LOSS_TOL and gaps["grad"] > 1e3 * GRAD_TOL


# -- the configuration and the entry point --------------------------------------------


def test_published_config_gives_the_cells_parameter_count():
    """The configuration as run (every published width, 6 of 48 layers, the
    whole vocabulary, head untied) is 509,661,185 parameters = 8.15 GB at 16 B,
    and its ``deployment`` says so."""
    cfg = run_mod.load_config(CONFIG)
    builder = importlib.import_module(f"benchmarks.configs.{cfg['builder']}")
    shapes = builder.param_shapes(cfg)
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == 509661185 == cfg["deployment"]["parameters_count"]
    assert 16 * count == cfg["deployment"]["bytes_at_16"]
    per_layer = [sum(x.size for x in jax.tree.leaves(shapes["loop"][f"layer_{i}"]))
                 for i in range(6)]
    assert per_layer == [4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048] * 6 == [51388416] * 6
    assert shapes["embed"]["embedding"].shape == (49152, 2048)
    assert shapes["head"]["kernel"].shape == (2048, 49152)
    assert shapes["loop"]["layer_0"]["attn"]["k_proj"]["kernel"].shape == (2048, 2048)
    model, _ = builder.build_model(cfg)
    c = model.config
    assert c.blocks == (("full_attention", "dense"),) * 6 and c.total_ut_steps == 4
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (16, 16, 128)
    assert c.sandwich_norm and c.rotary and not (c.qk_norm or c.tie_word_embeddings)
    assert (c.intermediate_size, c.rope_theta, c.exit_entropy_beta) == (5632, 1000000, 0.1)
    deployment = cfg["deployment"]
    assert (deployment["pipeline_stages"] * deployment["layers_per_stage"]
            == deployment["num_hidden_layers_published"] == 48)
    assert deployment["layers_per_stage"] == cfg["num_hidden_layers"]
    # every key of the published config.json is in the file, but the two reduced
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert cfg["layer_types"] == published["layer_types"][:6]


SMALL = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6,
             total_ut_steps=4, early_exit_threshold=1.0, model_type="ouro", vocab_size=97,
             use_sliding_window=False, rope_scaling=None, tie_word_embeddings=False,
             hidden_act="silu", layer_types=["full_attention"] * 2)


@pytest.mark.parametrize("change, match", [
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"total_ut_steps": 0}, "total_ut_steps"),
    ({"layer_types": ["sliding_attention", "full_attention"]}, "full_attention")])
def test_ouro_config_refuses_what_the_module_does_not_compute(change, match):
    config = DecoderLMConfig.from_dict(SMALL)
    assert config.total_ut_steps == 4 and config.sandwich_norm and not config.qk_norm
    with pytest.raises(ValueError, match=match):
        DecoderLMConfig.from_dict({**SMALL, **change})


def test_train_lm_cli_trains_the_looped_stack(tmp_path):
    from perceiver_io_tpu import obs
    from perceiver_io_tpu.cli import train_lm
    from perceiver_io_tpu.training import read_metrics

    log = tmp_path / "events.jsonl"
    obs.configure_event_log(str(log))
    try:
        run_dir = train_lm.main([
            "--synthetic", "--synthetic_size", "16", "--max_steps", "2", "--batch_size", "8",
            "--max_seq_len", "16", "--vocab_size", "200", "--dtype", "float32",
            "--log_every_n_steps", "2", "--no_tensorboard", "--logdir", str(tmp_path),
            "--model_type", "ouro"])
    finally:
        obs.configure_event_log(None)  # drains, then closes
    with open(log) as f:
        events = {r["event"]: r for r in map(json.loads, f) if "event" in r}
    layers = events["lm.layers"]
    assert layers["mixers"] == ["full_attention", "full_attention"] and layers["kv_group"] == 1
    assert (layers["total_ut_steps"], layers["sandwich_norm"], layers["block_applications"]) == (
        3, True, 6)
    assert "moe.share" not in events  # no expert layer
    rows = [r for r in read_metrics(run_dir) if "train_loss" in r]
    assert [r["step"] for r in rows] == [2]
    row = rows[0]
    assert np.isfinite(row["train_loss"]) and 1.0 <= row["ut_expected_exit"] <= 3.0
    assert all(np.isfinite(row[f"loss_ut_{t}"]) for t in (1, 2, 3))
    assert obs.get_registry().snapshot()["gauges"]["ut_block_applications"] == 6.0
