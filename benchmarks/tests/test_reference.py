"""The plain reference against the model's own float32 apply, at tiny sizes
on the CPU, on weights the benchmark made."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import traffic
from benchmarks.reference import perceiver as ref
from benchmarks.tests import tiny
from benchmarks.weights import make_weights_fn, seed_words


def _weights(builder, cfg, seed=7):
    return make_weights_fn(builder.param_shapes(cfg))(*seed_words(seed))


def test_mlm_logits_match_the_models_f32_apply():
    _, cfg, mix, builder = tiny.mlm()
    params = _weights(builder, cfg)
    batch = traffic.make_batches(mix, 3)[0]
    model, _ = builder.build_model(cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = model.apply({"params": params}, batch["token_ids"], batch["pad_mask"],
                              masking=False)
    got = ref.mlm_logits(ref.F32, params, jnp.asarray(batch["token_ids"]),
                         jnp.asarray(batch["pad_mask"]), builder.sizes(cfg))
    assert got.shape == want.shape == (8, 32, 203)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_classifier_logits_match_the_models_f32_apply():
    _, cfg, mix, builder = tiny.images()
    params = _weights(builder, cfg)
    batch = traffic.make_batches(mix, 3)[0]
    model, _ = builder.build_model(cfg)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, batch["image"])
    got = ref.classifier_logits(ref.F32, params, jnp.asarray(batch["image"]), builder.sizes(cfg))
    assert got.shape == want.shape == (4, 10)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_masking_matches_the_programs_draws():
    from perceiver_io_tpu.ops.masking import apply_text_masking

    _, cfg, mix, _ = tiny.mlm()
    batch = traffic.make_batches(mix, 5)[0]
    key = jax.random.key(11)
    want = apply_text_masking(key, jnp.asarray(batch["token_ids"]), jnp.asarray(batch["pad_mask"]),
                              vocab_size=203, unk_token_id=1, mask_token_id=2,
                              num_special_tokens=3)
    got = ref.mask_tokens(key, jnp.asarray(batch["token_ids"]), jnp.asarray(batch["pad_mask"]), 203)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int((got[1] != ref.IGNORE).sum()) > 0


def test_weights_depend_on_the_seed_and_take_large_seeds():
    _, cfg, _, builder = tiny.mlm()
    fn = make_weights_fn(builder.param_shapes(cfg))
    a, b, c = fn(*seed_words(1)), fn(*seed_words(2**31 + 5)), fn(*seed_words(1))
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lc))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lb))
