"""``flops.py`` against a hand count at tiny sizes."""

import pytest

from benchmarks import flops


def test_layers_by_hand():
    assert flops.linear(3, 4, 5) == 2 * 3 * 4 * 5
    assert flops.attention_core(2, 7, 4) == 2 * (2 * 2 * 7 * 4)
    n, c = 2, 4
    by_hand = 3 * (2 * n * c * c) + 2 * (2 * n * n * c) + 2 * n * c * c + 2 * (2 * n * c * c)
    assert flops.self_attention_layer(n, c) == by_hand


def test_perceiver_forward_by_hand():
    # M=6 inputs of 3 channels, N=2 latents of 4 channels, 3 encoder layers of
    # 1 self-attention layer, 1 output query, 5 classes
    m, ci, n, c, k, v = 6, 3, 2, 4, 1, 5
    lin = lambda r, i, o: 2 * r * i * o
    cross_q = lin(n, c, c) + 2 * (2 * n * m * c) + lin(n, c, c) + 2 * lin(n, c, c)
    kv = 2 * lin(m, ci, c)
    self_layer = 3 * lin(n, c, c) + 2 * (2 * n * n * c) + lin(n, c, c) + 2 * lin(n, c, c)
    dec = lin(k, c, c) + 2 * (2 * k * n * c) + lin(k, c, c) + 2 * lin(k, c, c) + 2 * lin(n, c, c)
    head = lin(k, c, v)
    # K/V of the input: once for layer 1, once for the shared layers 2..3
    forward = 3 * cross_q + 2 * kv + 3 * self_layer + dec + head
    got = flops.perceiver_io(
        input_positions=m, input_channels=ci, num_latents=n, num_channels=c,
        num_encoder_layers=3, num_self_attention_layers_per_block=1,
        output_queries=k, output_classes=v, input_needs_grad=True, training=False)
    assert got == pytest.approx(forward)
    train = flops.perceiver_io(
        input_positions=m, input_channels=ci, num_latents=n, num_channels=c,
        num_encoder_layers=3, num_self_attention_layers_per_block=1,
        output_queries=k, output_classes=v, input_needs_grad=True, training=True)
    assert train == pytest.approx(3 * forward)
    no_input_grad = flops.perceiver_io(
        input_positions=m, input_channels=ci, num_latents=n, num_channels=c,
        num_encoder_layers=3, num_self_attention_layers_per_block=1,
        output_queries=k, output_classes=v, input_needs_grad=False, training=True)
    assert no_input_grad == pytest.approx(3 * forward - 2 * kv)


def test_full_size_counts_are_plausible():
    from benchmarks import run as run_mod, traffic
    from benchmarks.configs import perceiver_image_classifier, perceiver_mlm

    cfg = run_mod.load_config("mlm_c512")
    mix = traffic.load_mix("train_text_b64_w512")
    pool = traffic.make_batches(mix, 1)
    per_step = perceiver_mlm.train_flops_per_sample(cfg, mix, pool) * mix["batch_size"]
    assert 3.9e12 < per_step < 4.5e12   # XLA's own count of this step: 4.3e12
    cfg = run_mod.load_config("imagenet_perceiver")
    per_sample = perceiver_image_classifier.train_flops_per_sample(cfg, None, None)
    assert 2.5e12 < per_sample < 3.5e12
