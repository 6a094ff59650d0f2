"""End-to-end training-step tests: loss decreases on tiny synthetic tasks,
freezing semantics, loss masking."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from perceiver_io_tpu.models.adapters import (
    ClassificationOutputAdapter,
    ImageInputAdapter,
    TextInputAdapter,
    TextOutputAdapter,
)
from perceiver_io_tpu.models.perceiver import (
    PerceiverDecoder,
    PerceiverEncoder,
    PerceiverIO,
    PerceiverMLM,
)
from perceiver_io_tpu.ops.masking import IGNORE_LABEL, TextMasking
from perceiver_io_tpu.training import (
    TrainState,
    OptimizerConfig,
    cross_entropy_with_ignore,
    freeze_subtrees,
    make_ar_steps,
    make_classifier_steps,
    make_flow_steps,
    make_lm_steps,
    make_mlm_steps,
    make_multimodal_steps,
    make_optimizer,
)

VOCAB, L, C = 40, 16, 32


def build_image_classifier(image_shape=(8, 8, 1), num_classes=4):
    enc = PerceiverEncoder(
        input_adapter=ImageInputAdapter(image_shape=image_shape, num_frequency_bands=6),
        latent_shape=(8, C),
        num_layers=2,
    )
    dec = PerceiverDecoder(
        output_adapter=ClassificationOutputAdapter(
            num_classes=num_classes, num_output_channels=C
        ),
        latent_shape=(8, C),
    )
    return PerceiverIO(encoder=enc, decoder=dec)


def build_text_classifier(num_classes=2, dropout=0.0):
    enc = PerceiverEncoder(
        input_adapter=TextInputAdapter(vocab_size=VOCAB, max_seq_len=L, num_channels=C),
        latent_shape=(8, C),
        num_layers=2,
        dropout=dropout,
    )
    dec = PerceiverDecoder(
        output_adapter=ClassificationOutputAdapter(
            num_classes=num_classes, num_output_channels=C
        ),
        latent_shape=(8, C),
        dropout=dropout,
    )
    return PerceiverIO(encoder=enc, decoder=dec)


def build_mlm():
    enc = PerceiverEncoder(
        input_adapter=TextInputAdapter(vocab_size=VOCAB, max_seq_len=L, num_channels=C),
        latent_shape=(8, C),
        num_layers=2,
    )
    dec = PerceiverDecoder(
        output_adapter=TextOutputAdapter(
            vocab_size=VOCAB, max_seq_len=L, num_output_channels=C
        ),
        latent_shape=(8, C),
    )
    masking = TextMasking(
        vocab_size=VOCAB, unk_token_id=1, mask_token_id=2, num_special_tokens=3
    )
    return PerceiverMLM(encoder=enc, decoder=dec, masking=masking)


def _token_batch(rng):
    ids = jnp.asarray(rng.integers(3, VOCAB, (2, L)).astype(np.int32))
    return {"token_ids": ids, "pad_mask": jnp.zeros((2, L), bool)}


def _family_mlm(rng, schedule):
    model = build_mlm()
    batch = _token_batch(rng)
    init = model.init({"params": jax.random.key(0), "masking": jax.random.key(1)},
                      batch["token_ids"], batch["pad_mask"])
    return make_mlm_steps(model, schedule, loss_gather_capacity=8)[:2], init, batch, set()


def _family_ar(rng, schedule):
    from perceiver_io_tpu.models.presets import tiny_ar

    model = tiny_ar(vocab_size=VOCAB, max_seq_len=L, num_latents=8)
    batch = _token_batch(rng)
    init = model.init({"params": jax.random.key(0)}, batch["token_ids"], batch["pad_mask"])
    return make_ar_steps(model, schedule)[:2], init, batch, set()


def _family_lm(rng, schedule):
    from perceiver_io_tpu.cli import train_lm
    from perceiver_io_tpu.models.decoder_lm import DecoderLM, DecoderLMConfig

    config = DecoderLMConfig.from_dict(dict(
        train_lm.SMALL, vocab_size=VOCAB, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2, num_attention_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8))
    model = DecoderLM(config, dtype=jnp.float32)
    batch = _token_batch(rng)
    init = model.init({"params": jax.random.key(0)}, batch["token_ids"][:1])
    return make_lm_steps(model, schedule)[:2], init, batch, {
        "loss_main", "loss_mtp", "moe_load_max_over_mean", "moe_local_assignment_pct",
        "moe_dropped_assignments"}


def _family_classifier(rng, schedule):
    model = build_image_classifier()
    batch = {"image": jnp.asarray(rng.normal(0, 1, (2, 8, 8, 1)), jnp.float32),
             "label": jnp.asarray([0, 3], jnp.int32)}
    init = model.init({"params": jax.random.key(0)}, batch["image"])
    return make_classifier_steps(model, schedule, input_kind="image"), init, batch, {"acc"}


def _family_multimodal(rng, schedule):
    from perceiver_io_tpu.models.multimodal import build_multimodal_autoencoder

    model = build_multimodal_autoencoder(
        video_shape=(2, 8, 8, 1), num_audio_samples=64, samples_per_patch=8, num_classes=3,
        latent_shape=(8, 32), video_patch_shape=(1, 4, 4), num_self_attention_layers_per_block=1,
        num_self_attention_heads=2, num_modality_channels=4, video_frequency_bands=2,
        audio_frequency_bands=2)
    batch = {"video": jnp.asarray(rng.normal(0, 1, (2, 2, 8, 8, 1)), jnp.float32),
             "audio": jnp.asarray(rng.normal(0, 1, (2, 64, 1)), jnp.float32),
             "label": jnp.asarray([0, 2], jnp.int32)}
    init = model.init({"params": jax.random.key(0)},
                      {"video": batch["video"], "audio": batch["audio"]})
    return make_multimodal_steps(model, schedule), init, batch, {
        "video_loss", "audio_loss", "label_loss", "video_psnr", "acc"}


def _family_flow(rng, schedule):
    from perceiver_io_tpu.models.flow import build_optical_flow_model

    model = build_optical_flow_model(
        image_shape=(8, 8, 1), latent_shape=(8, 32), num_self_attention_layers_per_block=1,
        num_self_attention_heads=2, num_frequency_bands=2)
    batch = {"frames": jnp.asarray(rng.normal(0, 1, (2, 2, 8, 8, 1)), jnp.float32),
             "flow": jnp.asarray(rng.normal(0, 1, (2, 8, 8, 2)), jnp.float32)}
    init = model.init({"params": jax.random.key(0)}, batch["frames"])
    return make_flow_steps(model, schedule), init, batch, set()


@pytest.mark.parametrize(
    "family", [_family_mlm, _family_ar, _family_lm, _family_classifier, _family_multimodal,
               _family_flow], ids=lambda f: f.__name__.removeprefix("_family_"))
def test_every_factory_gives_the_same_step_contract(family, rng):
    """The six factories share one skeleton: ``train_step(state, batch)``
    advances the step and publishes a finite ``loss``, the family's own
    metrics and the schedule's ``lr``; ``eval_step`` takes the Trainer's key
    or none, and only a family that samples its masking reads it."""
    tx, schedule = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    (train_step, eval_step), init, batch, aux = family(rng, schedule)
    state = TrainState.create(init["params"], tx, jax.random.key(2))

    new_state, metrics = jax.jit(train_step)(state, batch)
    assert int(new_state.step) == int(state.step) + 1
    assert np.isfinite(float(metrics["loss"]))
    assert metrics.keys() >= {"loss", "lr"} | aux
    np.testing.assert_allclose(float(metrics["lr"]), float(schedule(state.step)))

    keyless, keyed = jax.jit(lambda s, b, k: (eval_step(s, b), eval_step(s, b, k)))(
        state, batch, jax.random.key(9))
    assert keyless.keys() == keyed.keys() >= {"loss"} | aux
    assert all(np.isfinite(float(v)) for v in keyed.values())
    if family is not _family_mlm:  # no masking stream: the key changes nothing
        for name in keyed:
            assert float(keyless[name]) == float(keyed[name]), name


@pytest.mark.slow  # convergence smoke duplicated by the trainer fit
# tests, which train the same tiny classifier to a falling loss
def test_image_classifier_learns(rng):
    model = build_image_classifier()
    # learnable synthetic task: class = brightest quadrant
    n = 64
    images = rng.standard_normal((n, 8, 8, 1)).astype(np.float32) * 0.1
    labels = rng.integers(0, 4, n)
    for i, lab in enumerate(labels):
        r, c = divmod(int(lab), 2)
        images[i, r * 4 : r * 4 + 4, c * 4 : c * 4 + 4, 0] += 1.0
    batch = {"image": jnp.asarray(images), "label": jnp.asarray(labels)}

    variables = model.init(jax.random.key(0), batch["image"])
    tx, schedule = make_optimizer(OptimizerConfig(learning_rate=3e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(1))
    train_step, eval_step = make_classifier_steps(model, schedule, input_kind="image")
    train_step = jax.jit(train_step)

    first = None
    for _ in range(40):
        state, metrics = train_step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first * 0.5, (first, last)
    ev = eval_step(state, batch)
    assert float(ev["acc"]) > 0.5
    np.testing.assert_allclose(float(metrics["lr"]), 3e-3, rtol=1e-6)


@pytest.mark.slow  # tier-1 budget (r10): convergence coverage retained by
# tests/test_inference.py::test_mlm_fill_masks_learns_pattern (end-to-end
# learning) and the trainer fit tests (tests/test_trainer.py)
def test_mlm_learns(rng):
    model = build_mlm()
    # strongly structured data: token depends on position
    ids = np.tile(np.arange(L) % (VOCAB - 3) + 3, (32, 1)).astype(np.int32)
    pad = np.zeros((32, L), dtype=bool)
    batch = {"token_ids": jnp.asarray(ids), "pad_mask": jnp.asarray(pad)}

    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        batch["token_ids"], batch["pad_mask"],
    )
    tx, schedule = make_optimizer(OptimizerConfig(learning_rate=3e-3))
    state = TrainState.create(variables["params"], tx, jax.random.key(2))
    train_step, eval_step, predict_fn = make_mlm_steps(model, schedule)
    train_step = jax.jit(train_step)

    losses = []
    for _ in range(60):
        state, metrics = train_step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])

    ev = eval_step(state, batch, jax.random.key(9))
    assert np.isfinite(ev["loss"])

    # predict path: no masking, logits over full vocab
    logits = predict_fn(state.params, batch["token_ids"], batch["pad_mask"])
    assert logits.shape == (32, L, VOCAB)


def test_frozen_encoder_transfer(rng):
    """Encoder params must not move when frozen; decoder must (reference
    train_seq_clf.py:18-24 + train/utils.py:5-8 semantics)."""
    model = build_text_classifier(dropout=0.1)
    ids = jnp.asarray(rng.integers(3, VOCAB, (16, L)).astype(np.int32))
    pad = jnp.zeros((16, L), dtype=bool)
    labels = jnp.asarray(rng.integers(0, 2, 16))
    batch = {"token_ids": ids, "pad_mask": pad, "label": labels}

    variables = model.init(jax.random.key(0), ids, pad)
    params = variables["params"]
    tx, _ = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    tx = freeze_subtrees(tx, params, ["encoder"])
    state = TrainState.create(params, tx, jax.random.key(1))
    train_step, _ = make_classifier_steps(model, input_kind="text", frozen_encoder=True)
    train_step = jax.jit(train_step)

    for _ in range(3):
        state, metrics = train_step(state, batch)

    enc_before = jax.tree.leaves(params["encoder"])
    enc_after = jax.tree.leaves(state.params["encoder"])
    for a, b in zip(enc_before, enc_after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    dec_before = np.concatenate([np.ravel(x) for x in jax.tree.leaves(params["decoder"])])
    dec_after = np.concatenate([np.ravel(x) for x in jax.tree.leaves(state.params["decoder"])])
    assert not np.allclose(dec_before, dec_after)


def test_cross_entropy_ignore_matches_torch(rng):
    import torch

    logits = rng.standard_normal((4, 10, 7)).astype(np.float32)
    labels = rng.integers(0, 7, (4, 10)).astype(np.int64)
    labels[:, ::3] = IGNORE_LABEL

    ours = float(cross_entropy_with_ignore(jnp.asarray(logits), jnp.asarray(labels)))
    theirs = float(
        torch.nn.functional.cross_entropy(
            torch.tensor(logits).permute(0, 2, 1), torch.tensor(labels)
        )
    )
    np.testing.assert_allclose(ours, theirs, rtol=1e-5)


def test_cross_entropy_all_ignored():
    logits = jnp.zeros((2, 3, 5))
    labels = jnp.full((2, 3), IGNORE_LABEL)
    assert float(cross_entropy_with_ignore(logits, labels)) == 0.0


def test_train_state_rng_streams():
    tx, _ = make_optimizer(OptimizerConfig())
    state = TrainState.create({"w": jnp.zeros(3)}, tx, jax.random.key(0))
    r1 = state.step_rngs("masking", "dropout")
    r2 = state.step_rngs("masking", "dropout")
    # same step → same keys; different streams differ
    assert jnp.array_equal(jax.random.key_data(r1["masking"]), jax.random.key_data(r2["masking"]))
    assert not jnp.array_equal(
        jax.random.key_data(r1["masking"]), jax.random.key_data(r1["dropout"])
    )
    state2 = state.replace(step=state.step + 1)
    r3 = state2.step_rngs("masking", "dropout")
    assert not jnp.array_equal(
        jax.random.key_data(r1["masking"]), jax.random.key_data(r3["masking"])
    )


def test_lean_ce_matches_optax(rng):
    """softmax_ce_integer (custom-VJP, no f32 logits materialization) matches
    optax's value and gradient in f32 and bf16."""
    import optax
    from perceiver_io_tpu.training.losses import softmax_ce_integer

    logits32 = jnp.asarray(rng.standard_normal((4, 7, 50)).astype(np.float32)) * 3
    labels = jnp.asarray(rng.integers(0, 50, (4, 7)))

    for dtype, atol in ((jnp.float32, 1e-6), (jnp.bfloat16, 3e-2)):
        logits = logits32.astype(dtype)
        ours = softmax_ce_integer(logits, labels)
        ref = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        )
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=atol)

        w = jnp.asarray(rng.standard_normal((4, 7)).astype(np.float32))
        g_ours = jax.grad(
            lambda l: jnp.sum(softmax_ce_integer(l, labels) * w)
        )(logits)
        g_ref = jax.grad(
            lambda l: jnp.sum(
                optax.softmax_cross_entropy_with_integer_labels(
                    l.astype(jnp.float32), labels
                ) * w
            )
        )(logits)
        assert g_ours.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(g_ours, np.float32), np.asarray(g_ref, np.float32),
            atol=atol,
        )


@pytest.mark.slow  # 23 s alone on the CPU (two step compiles, the kernel
# interpreted); the padded head's masking is tier-1 in
# tests/test_sharding.py::test_padded_vocab_projection_shards_under_tp
def test_fused_head_with_padded_vocab(rng):
    """pad_classes_to: padded columns must not leak into the fused head's
    logsumexp (the flash-CE kernel, interpreted off-TPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import perceiver_io_tpu as pit
    from perceiver_io_tpu.ops.masking import TextMasking
    from perceiver_io_tpu.training import (
        OptimizerConfig,
        TrainState,
        make_mlm_steps,
        make_optimizer,
    )

    VOCAB, L, C, NLAT = 60, 16, 16, 8
    def build(pad):
        return pit.PerceiverMLM(
            encoder=pit.PerceiverEncoder(
                input_adapter=pit.TextInputAdapter(
                    vocab_size=VOCAB, max_seq_len=L, num_channels=C),
                latent_shape=(NLAT, C), num_layers=1,
            ),
            decoder=pit.PerceiverDecoder(
                output_adapter=pit.TextOutputAdapter(
                    vocab_size=VOCAB, max_seq_len=L, num_output_channels=C,
                    pad_classes_to=pad),
                latent_shape=(NLAT, C),
            ),
            masking=TextMasking(VOCAB, 1, 2, 3),
        )

    padded = build(64)
    ids = jnp.asarray(rng.integers(3, VOCAB, (4, L)).astype(np.int32))
    batch = {"token_ids": ids, "pad_mask": jnp.zeros((4, L), bool)}
    variables = padded.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)}, ids,
        batch["pad_mask"],
    )
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-3))
    out = {}
    for fused in (False, "pallas"):
        state = TrainState.create(
            jax.tree.map(jnp.copy, variables["params"]), tx, jax.random.key(2)
        )
        step, _, _ = make_mlm_steps(padded, sched, fused_head=fused)
        state, m = jax.jit(step)(state, batch)
        out[fused] = float(m["loss"])
    np.testing.assert_allclose(out["pallas"], out[False], rtol=1e-5)
