"""Structural tests for the Perceiver core: weight sharing, shapes, masking flow."""

import contextlib
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

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

VOCAB, MAX_LEN, C = 60, 24, 32
LATENT_SHAPE = (8, C)


def make_text_encoder(num_layers=3):
    return PerceiverEncoder(
        input_adapter=TextInputAdapter(vocab_size=VOCAB, max_seq_len=MAX_LEN, num_channels=C),
        latent_shape=LATENT_SHAPE,
        num_layers=num_layers,
        num_self_attention_layers_per_block=2,
    )


def test_encoder_output_shape(rng):
    enc = make_text_encoder()
    x = jnp.asarray(rng.integers(0, VOCAB, size=(4, MAX_LEN)).astype(np.int32))
    pad = jnp.zeros((4, MAX_LEN), dtype=bool)
    variables = enc.init(jax.random.key(0), x, pad)
    out = enc.apply(variables, x, pad)
    assert out.shape == (4, *LATENT_SHAPE)


def test_encoder_weight_sharing(rng):
    """Layers 2..N share one weight set: params contain exactly layer_1 and
    layer_n (reference model.py:162-166)."""
    enc = make_text_encoder(num_layers=5)
    x = jnp.zeros((2, MAX_LEN), dtype=jnp.int32)
    variables = enc.init(jax.random.key(0), x, None)
    layer_keys = {k for k in variables["params"] if k.startswith("layer")}
    assert layer_keys == {"layer_1", "layer_n"}


def test_encoder_single_layer_has_no_layer_n():
    enc = make_text_encoder(num_layers=1)
    x = jnp.zeros((2, MAX_LEN), dtype=jnp.int32)
    variables = enc.init(jax.random.key(0), x, None)
    layer_keys = {k for k in variables["params"] if k.startswith("layer")}
    assert layer_keys == {"layer_1"}


def test_encoder_depth_changes_output(rng):
    """Recurrent applications of layer_n must actually run (same params,
    different depth ⇒ different output)."""
    x = jnp.asarray(rng.integers(0, VOCAB, size=(2, MAX_LEN)).astype(np.int32))
    enc3 = make_text_encoder(num_layers=3)
    enc5 = make_text_encoder(num_layers=5)
    v = enc3.init(jax.random.key(0), x, None)
    out3 = enc3.apply(v, x, None)
    out5 = enc5.apply(v, x, None)  # same params, more recurrence
    assert not np.allclose(np.asarray(out3), np.asarray(out5), atol=1e-4)


def test_encoder_gradients_flow_through_shared_layers(rng):
    enc = make_text_encoder(num_layers=3)
    x = jnp.asarray(rng.integers(0, VOCAB, size=(2, MAX_LEN)).astype(np.int32))
    variables = enc.init(jax.random.key(0), x, None)

    def loss(params):
        return jnp.sum(enc.apply({"params": params}, x, None) ** 2)

    grads = jax.grad(loss)(variables["params"])
    flat = jax.tree.leaves(jax.tree.map(lambda g: float(jnp.abs(g).sum()), grads))
    assert all(np.isfinite(flat))
    # shared layer and latent both receive gradient
    g_latent = jnp.abs(grads["latent"]).sum()
    assert float(g_latent) > 0
    g_layer_n = sum(jax.tree.leaves(jax.tree.map(lambda g: float(jnp.abs(g).sum()),
                                                 grads["layer_n"])))
    assert g_layer_n > 0


def test_latent_init_distribution():
    enc = make_text_encoder()
    x = jnp.zeros((1, MAX_LEN), dtype=jnp.int32)
    variables = enc.init(jax.random.key(0), x, None)
    latent = np.asarray(variables["params"]["latent"])
    assert np.abs(latent).max() <= 2.0
    assert 0.005 < latent.std() < 0.05  # ~N(0, 0.02)


def test_decoder_validates_latent_shape(rng):
    dec = PerceiverDecoder(
        output_adapter=ClassificationOutputAdapter(num_classes=10, num_output_channels=C),
        latent_shape=LATENT_SHAPE,
    )
    good = jnp.zeros((2, *LATENT_SHAPE))
    variables = dec.init(jax.random.key(0), good)
    with pytest.raises(ValueError, match="Latent shape"):
        dec.apply(variables, jnp.zeros((2, 4, C)))


def test_perceiver_io_text_classification(rng):
    enc = make_text_encoder()
    dec = PerceiverDecoder(
        output_adapter=ClassificationOutputAdapter(num_classes=2, num_output_channels=C),
        latent_shape=LATENT_SHAPE,
    )
    model = PerceiverIO(encoder=enc, decoder=dec)
    x = jnp.asarray(rng.integers(0, VOCAB, size=(4, MAX_LEN)).astype(np.int32))
    pad = jnp.zeros((4, MAX_LEN), dtype=bool)
    variables = model.init(jax.random.key(0), x, pad)
    logits = model.apply(variables, x, pad)
    assert logits.shape == (4, 2)


def test_perceiver_io_image_classification(rng):
    enc = PerceiverEncoder(
        input_adapter=ImageInputAdapter(image_shape=(14, 14, 1), num_frequency_bands=8),
        latent_shape=(16, 64),
        num_layers=2,
        num_self_attention_layers_per_block=2,
    )
    dec = PerceiverDecoder(
        output_adapter=ClassificationOutputAdapter(num_classes=10, num_output_channels=64),
        latent_shape=(16, 64),
    )
    model = PerceiverIO(encoder=enc, decoder=dec)
    x = jnp.asarray(rng.standard_normal((2, 14, 14, 1)).astype(np.float32))
    variables = model.init(jax.random.key(0), x)
    logits = model.apply(variables, x)
    assert logits.shape == (2, 10)


def make_mlm(num_layers=2):
    enc = make_text_encoder(num_layers)
    dec = PerceiverDecoder(
        output_adapter=TextOutputAdapter(vocab_size=VOCAB, max_seq_len=MAX_LEN,
                                         num_output_channels=C),
        latent_shape=LATENT_SHAPE,
    )
    masking = TextMasking(vocab_size=VOCAB, unk_token_id=1, mask_token_id=2,
                          num_special_tokens=3)
    return PerceiverMLM(encoder=enc, decoder=dec, masking=masking)


def test_mlm_forward_with_masking(rng):
    model = make_mlm()
    x = jnp.asarray(rng.integers(3, VOCAB, size=(4, MAX_LEN)).astype(np.int32))
    pad = jnp.zeros((4, MAX_LEN), dtype=bool)
    variables = model.init({"params": jax.random.key(0), "masking": jax.random.key(1)},
                           x, pad)
    logits, labels = model.apply(variables, x, pad,
                                 rngs={"masking": jax.random.key(2)})
    assert logits.shape == (4, MAX_LEN, VOCAB)
    assert labels.shape == (4, MAX_LEN)
    assert (np.asarray(labels) != IGNORE_LABEL).any()


def test_mlm_truncates_logits_to_input_length(rng):
    model = make_mlm()
    x_full = jnp.asarray(rng.integers(3, VOCAB, size=(2, MAX_LEN)).astype(np.int32))
    variables = model.init({"params": jax.random.key(0), "masking": jax.random.key(1)},
                           x_full, jnp.zeros((2, MAX_LEN), dtype=bool))
    l = MAX_LEN // 2
    x = x_full[:, :l]
    pad = jnp.zeros((2, l), dtype=bool)
    logits, labels = model.apply(variables, x, pad, masking=False)
    assert logits.shape == (2, l, VOCAB)
    assert labels is None


def test_mlm_no_masking_is_deterministic(rng):
    model = make_mlm()
    x = jnp.asarray(rng.integers(3, VOCAB, size=(2, MAX_LEN)).astype(np.int32))
    pad = jnp.zeros((2, MAX_LEN), dtype=bool)
    variables = model.init({"params": jax.random.key(0), "masking": jax.random.key(1)},
                           x, pad)
    l1, _ = model.apply(variables, x, pad, masking=False)
    l2, _ = model.apply(variables, x, pad, masking=False)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2))


def test_pad_mask_affects_output(rng):
    enc = make_text_encoder()
    x = jnp.asarray(rng.integers(0, VOCAB, size=(2, MAX_LEN)).astype(np.int32))
    variables = enc.init(jax.random.key(0), x, None)
    pad_none = jnp.zeros((2, MAX_LEN), dtype=bool)
    pad_half = pad_none.at[:, MAX_LEN // 2 :].set(True)
    o1 = enc.apply(variables, x, pad_none)
    o2 = enc.apply(variables, x, pad_half)
    assert not np.allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_bfloat16_compute(rng):
    enc = PerceiverEncoder(
        input_adapter=TextInputAdapter(vocab_size=VOCAB, max_seq_len=MAX_LEN,
                                       num_channels=C, dtype=jnp.bfloat16),
        latent_shape=LATENT_SHAPE,
        num_layers=2,
        dtype=jnp.bfloat16,
    )
    x = jnp.asarray(rng.integers(0, VOCAB, size=(2, MAX_LEN)).astype(np.int32))
    variables = enc.init(jax.random.key(0), x, None)
    # params stay f32
    assert variables["params"]["latent"].dtype == jnp.float32
    out = enc.apply(variables, x, None)
    assert out.dtype == jnp.bfloat16


def test_decoder_positions_match_full_decode(rng):
    """Decoding a subset of output-query positions equals the corresponding
    rows of the full decode (each query attends to the latents independently)."""
    dec = PerceiverDecoder(
        output_adapter=TextOutputAdapter(vocab_size=VOCAB, max_seq_len=MAX_LEN,
                                         num_output_channels=C),
        latent_shape=LATENT_SHAPE,
    )
    latent = jnp.asarray(rng.standard_normal((3, *LATENT_SHAPE)), jnp.float32)
    variables = dec.init(jax.random.key(0), latent)
    full = np.asarray(dec.apply(variables, latent))
    positions = jnp.asarray(rng.integers(0, MAX_LEN, size=(3, 5)).astype(np.int32))
    subset = np.asarray(dec.apply(variables, latent, positions=positions))
    expected = np.take_along_axis(full, np.asarray(positions)[:, :, None], axis=1)
    np.testing.assert_allclose(subset, expected, rtol=1e-5, atol=1e-5)


def test_mlm_gathered_loss_matches_full(rng):
    """CE over the gathered masked positions equals CE over the full decode
    (label -100 positions contribute nothing), and so do the gradients."""
    from perceiver_io_tpu.training.losses import cross_entropy_with_ignore

    model = make_mlm()
    x = jnp.asarray(rng.integers(3, VOCAB, size=(4, MAX_LEN)).astype(np.int32))
    pad = jnp.zeros((4, MAX_LEN), dtype=bool)
    variables = model.init({"params": jax.random.key(0), "masking": jax.random.key(1)},
                           x, pad)
    mask_key = jax.random.key(7)

    def loss(params, capacity):
        logits, labels = model.apply(
            {"params": params}, x, pad, rngs={"masking": mask_key},
            loss_gather_capacity=capacity,
        )
        return cross_entropy_with_ignore(logits, labels)

    # capacity = MAX_LEN - 1 forces the gather path; every masked position
    # fits (15% of 24 positions), so the result must match the full decode
    full_loss, full_grads = jax.value_and_grad(loss)(variables["params"], None)
    gath_loss, gath_grads = jax.value_and_grad(loss)(variables["params"], MAX_LEN - 1)
    np.testing.assert_allclose(float(full_loss), float(gath_loss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6),
        full_grads, gath_grads,
    )


def test_mlm_gather_capacity_helper():
    from perceiver_io_tpu.training.steps import mlm_gather_capacity

    assert mlm_gather_capacity(512) == 160  # 2·0.15·512 = 153.6 → 160
    assert mlm_gather_capacity(512) % 32 == 0
    assert mlm_gather_capacity(24) == 24  # capped at seq_len... still ≥ 32 rule
    assert mlm_gather_capacity(4096, 0.15) >= int(2 * 0.15 * 4096)


def test_flagship_tpu_preset_shapes():
    """The TPU-widths preset keeps the reference recipe SHAPE (3 encoder
    layers x 6 self-attention layers, shared layer_n, text in/out adapters)
    and only widens: 256 latents x 512 channels, 4 heads => head depth 128
    (models/presets.py flagship_tpu_mlm; the BASELINE.md north-star closed
    at TPU-native widths)."""
    from perceiver_io_tpu.models.presets import flagship_tpu_mlm

    model = flagship_tpu_mlm(vocab_size=97, max_seq_len=32, dtype=jnp.float32)
    tok = jnp.zeros((1, 32), jnp.int32)
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        tok, jnp.zeros((1, 32), bool),
    )
    params = variables["params"]
    assert params["encoder"]["latent"].shape == (256, 512)
    sa = params["encoder"]["layer_n"]["self_attention_block"]
    assert sorted(sa) == [f"layer_{i}" for i in range(6)]
    q = sa["layer_0"]["self_attention"]["attention"]["q_proj"]["kernel"]
    assert q.shape == (512, 512)  # 4 heads x depth 128 (full MXU contraction)
    assert model.encoder.num_cross_attention_heads == 4
    # 3 encoder layers = layer_1 + shared layer_n applied twice
    assert model.encoder.num_layers == 3


class TestSharedLayerKVReuse:
    """reuse_kv=True (the default) caches the shared layer_n cross-attention
    K/V projections across recurrent applications — identical weights on the
    identical input make the repeat pure recompute (models/perceiver.py).
    The cache is the SAME tensor reused, so the forward must be bit-exact
    against recompute; gradients reassociate one near-cancelling reduction
    (dk1+dk2 summed before vs after the dW matmul) and agree to fp noise."""

    def _encoder(self, reuse, remat=False):
        return PerceiverEncoder(
            input_adapter=TextInputAdapter(
                vocab_size=VOCAB, max_seq_len=MAX_LEN, num_channels=C,
                dtype=jnp.float32,
            ),
            latent_shape=(8, C),
            num_layers=3,
            num_self_attention_layers_per_block=2,
            reuse_kv=reuse,
            remat=remat,
        )

    def test_forward_bit_exact_and_grads_close(self):
        x = jnp.asarray(
            np.random.default_rng(3).integers(0, VOCAB, (2, MAX_LEN)), jnp.int32
        )
        enc_a, enc_b = self._encoder(True), self._encoder(False)
        va = enc_a.init({"params": jax.random.key(0)}, x)
        # param trees identical: the cache changes no module structure
        vb = enc_b.init({"params": jax.random.key(0)}, x)
        assert all(
            bool((a == b).all())
            for a, b in zip(
                jax.tree_util.tree_leaves(va), jax.tree_util.tree_leaves(vb)
            )
        )
        out_a = enc_a.apply(va, x)
        out_b = enc_b.apply(va, x)
        assert bool((out_a == out_b).all())

        def loss(params, enc):
            return jnp.sum(enc.apply({"params": params}, x) ** 2)

        ga = jax.grad(loss)(va["params"], enc_a)
        gb = jax.grad(loss)(va["params"], enc_b)
        for a, b in zip(
            jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)
        ):
            # atol floor: leaves whose true grad nearly cancels (k_proj/bias)
            # sit at ~1e-6 magnitude, where the dk1+dk2 reassociation IS the
            # signal — only relative structure above the noise floor matters
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b),
                rtol=2e-5, atol=max(1e-5, 1e-4 * float(jnp.abs(b).max())),
            )

    def test_remat_composes_with_reuse(self):
        """The kv cache crosses the nn.remat boundary as a pytree argument
        (no static bool — PerceiverLayer always returns (latent, kv))."""
        x = jnp.asarray(
            np.random.default_rng(4).integers(0, VOCAB, (2, MAX_LEN)), jnp.int32
        )
        enc, enc_r = self._encoder(True), self._encoder(True, remat=True)
        v = enc.init({"params": jax.random.key(0)}, x)
        assert bool((enc_r.apply(v, x) == enc.apply(v, x)).all())

        def loss(params, e):
            return jnp.sum(e.apply({"params": params}, x) ** 2)

        g, gr = jax.grad(loss)(v["params"], enc), jax.grad(loss)(v["params"], enc_r)
        # atol 2e-4: remat's recompute reassociates f32 reductions on this
        # compiler. Large-|g| leaves (~1e2) agree to rtol; the absolute floor
        # covers small-magnitude elements produced by heavy cancellation,
        # where the run-to-run reassociation noise is ~1e-4 regardless of the
        # element's own size (observed 9e-5 on a 0.05-scale element).
        for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-4)


class TestSelectiveRemat:
    """``remat=True`` keeps the long cross-attention's residuals (logits,
    weighted sum, K/V) where their bytes fit a share of the device's memory,
    and recomputes whole layers everywhere else (models/perceiver.py
    ``_remat_policy``). The device is the CPU here, which reports no memory
    limit: the tests put one in through ``_device_bytes_limit``, the one
    function that reads the device."""

    B, T, HEADS = 2, 8, 4

    @staticmethod
    def _text_encoder(remat):
        return TestSharedLayerKVReuse()._encoder(True, remat=remat)

    def _tokens(self):
        return jnp.asarray(
            np.random.default_rng(4).integers(0, VOCAB, (self.B, MAX_LEN)), jnp.int32)

    @staticmethod
    def _image_encoder():
        # 64 x 64 pixels = 4096 positions: AUTO_PALLAS_MIN_KV, a long stream
        return PerceiverEncoder(
            input_adapter=ImageInputAdapter(image_shape=(64, 64, 3),
                                            num_frequency_bands=4),
            latent_shape=(8, 32), num_layers=3, num_cross_attention_heads=1,
            num_self_attention_heads=4, num_self_attention_layers_per_block=1,
            dtype=jnp.bfloat16, remat=True)

    @pytest.fixture
    def short_streams_count(self, monkeypatch):
        """MAX_LEN tokens pass for a long stream, so that the parity cases
        run at the shapes whose tolerance ``test_remat_composes_with_reuse``
        set."""
        from perceiver_io_tpu.models import perceiver

        monkeypatch.setattr(perceiver, "AUTO_PALLAS_MIN_KV", MAX_LEN)
        return perceiver

    @pytest.mark.parametrize("case", ["no_remat", "bare", "engaged", "fallen_back"])
    def test_output_and_gradients_match_unrematerialised(
            self, case, short_streams_count, monkeypatch, capsys):
        limit = {"no_remat": None, "bare": None, "engaged": 16e9,
                 "fallen_back": 1000}[case]
        monkeypatch.setattr(short_streams_count, "_device_bytes_limit", lambda: limit)
        x = self._tokens()
        enc, enc_r = self._text_encoder(False), self._text_encoder(case != "no_remat")
        v = enc.init({"params": jax.random.key(0)}, x)
        assert bool((enc_r.apply(v, x) == enc.apply(v, x)).all())

        def loss(params, e):
            return jnp.sum(e.apply({"params": params}, x) ** 2)

        g, gr = jax.grad(loss)(v["params"], enc), jax.grad(loss)(v["params"], enc_r)
        # test_remat_composes_with_reuse's tolerance, for its reasons
        for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-4)

        # what the backward pass keeps, by shape: cross logits (B, H, T, S),
        # weighted sum (B, T, H, D), K/V (B, S, E); self logits (B, H, T, T)
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(
            lambda params: loss(params, enc_r), v["params"])
        kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()
                if "from the argument" not in line]
        b, t, h = self.B, self.T, self.HEADS
        cross_logits = f"f32[{b},{h},{t},{MAX_LEN}]"
        context = f"f32[{b},{t},{h},{C // h}]"
        kv = f"f32[{b},{MAX_LEN},{C}]"  # the adapted input's shape too: one more
        self_logits = f"f32[{b},{h},{t},{t}]"
        if case == "engaged":
            # 3 layers; layer_1's K/V and the shared layer's one set
            assert kept.count(cross_logits) == 3 and kept.count(context) == 3
            assert kept.count(kv) == 1 + 4
            assert self_logits not in kept
        elif case != "no_remat":
            assert not {cross_logits, context, self_logits} & set(kept)
            # the shared layer's K/V: an output of its first application
            assert kept.count(kv) == 1 + 2

    def test_engaged_policy_recomputes_less_than_bare_more_than_none(
            self, short_streams_count, monkeypatch):
        x = self._tokens()
        enc = self._text_encoder(False)
        params = enc.init({"params": jax.random.key(0)}, x)["params"]

        def flops(remat, limit):
            monkeypatch.setattr(short_streams_count, "_device_bytes_limit", lambda: limit)
            e = self._text_encoder(remat)
            grad = jax.grad(lambda p: jnp.sum(e.apply({"params": p}, x) ** 2))
            return jax.jit(grad).lower(params).compile().cost_analysis()["flops"]

        none, bare, engaged = flops(False, None), flops(True, None), flops(True, 16e9)
        assert none < engaged < bare

    def test_names_lower_to_nothing_without_remat(self, monkeypatch):
        from perceiver_io_tpu.ops import attention

        x = self._tokens()
        enc = self._text_encoder(False)
        params = enc.init({"params": jax.random.key(0)}, x)["params"]

        def lowered():
            grad = jax.grad(lambda p: jnp.sum(enc.apply({"params": p}, x) ** 2))
            text = jax.jit(grad).lower(params).as_text()
            # the names shift the numbers MLIR's symbol table gives private
            # functions (@_where_257 -> @_where_261), and nothing else
            return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

        named = lowered()
        assert "stablehlo.dot_general" in named
        monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
        assert lowered() == named

    @pytest.mark.parametrize("case", ["engaged", "over_budget", "no_limit", "dp_mesh"])
    def test_remat_policy_event(self, case, remat_policy_events, monkeypatch):
        from perceiver_io_tpu.models import perceiver
        from perceiver_io_tpu.parallel import make_mesh
        from perceiver_io_tpu.parallel.mesh import step_mesh_context

        b, s, t, e, layers = 8, 64 * 64, 8, 32, 3
        # bf16: logits + weighted sum per layer, layer_1's and the shared K/V
        reckoned = (layers * (b * t * s + b * t * e) + 2 * 2 * b * s * e) * 2
        limit = {"engaged": 16e9, "dp_mesh": 16e9, "no_limit": None,
                 "over_budget": reckoned / perceiver.REMAT_KEEP_FRACTION - 8}[case]
        monkeypatch.setattr(perceiver, "_device_bytes_limit", lambda: limit)
        enc = self._image_encoder()
        image = jax.ShapeDtypeStruct((b, 64, 64, 3), jnp.float32)
        params = jax.eval_shape(
            lambda x: enc.init({"params": jax.random.key(0)}, x), image)["params"]
        wrapped = []
        monkeypatch.setattr(
            perceiver.nn, "remat",
            lambda cls, policy=None: wrapped.append(policy) or cls)
        with (step_mesh_context(make_mesh(dp=8)) if case == "dp_mesh"
              else contextlib.nullcontext()):
            jax.eval_shape(lambda p, x: enc.apply({"params": p}, x), params, image)
        record = remat_policy_events()[-1]
        engaged = case in ("engaged", "dp_mesh")
        assert record["engaged"] is engaged and record["layers"] == layers
        assert record["saved_bytes"] == (reckoned // 8 if case == "dp_mesh" else reckoned)
        assert record["budget_bytes"] == (
            None if limit is None else int(limit * perceiver.REMAT_KEEP_FRACTION))
        # layer_1 and layer_n of the traced apply: a policy each, or the bare remat
        assert len(wrapped) == 2 and all((p is not None) == engaged for p in wrapped)


def test_scaled_embed_matches_post_scale_bitwise():
    """_ScaledEmbed pre-scales the (vocab, C) table before the gather —
    bit-identical to gathering then multiplying by sqrt(C) (the reference
    formula, adapter.py:112-133) in both f32 and bf16 compute, while moving
    the multiply off the (B, L, C) stream (PERF.md r5)."""
    from perceiver_io_tpu.models.adapters import _ScaledEmbed

    for dtype in (jnp.float32, jnp.bfloat16):
        adapter = TextInputAdapter(
            vocab_size=VOCAB, max_seq_len=MAX_LEN, num_channels=C, dtype=dtype
        )
        x = jnp.asarray(
            np.random.default_rng(5).integers(0, VOCAB, (3, MAX_LEN)), jnp.int32
        )
        v = adapter.init({"params": jax.random.key(7)}, x)
        out = adapter.apply(v, x)
        table = v["params"]["text_embedding"]["embedding"].astype(dtype)
        pos = v["params"]["pos_encoding"][:MAX_LEN].astype(dtype)
        ref = jnp.take(table, x, axis=0) * jnp.asarray(C**0.5, dtype) + pos
        # same per-element multiply either side of the gather
        assert bool((out == ref).all()) or np.allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=0
        )
