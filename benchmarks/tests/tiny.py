"""Tiny stand-ins of the two configurations and their traffic: the real
files with every size cut, so that the tests drive the same code paths."""

from __future__ import annotations

import importlib

from benchmarks import run as run_mod, traffic


def mlm(dtype: str = "float32"):
    cfg = run_mod.load_config("mlm_c512")
    cfg.update(vocab_size=203, max_seq_len=32, num_latents=8, num_latent_channels=32,
               num_encoder_layers=3, num_self_attention_layers_per_block=2, dtype=dtype)
    mix = traffic.load_mix("train_text_b64_w512")
    mix.update(batch_size=8, warmup_steps=1)
    mix["fields"]["token_ids"].update(width=32, high=203, length_low=8, length_high=32)
    return {"name": "mlm_c512_train"}, cfg, mix, importlib.import_module(
        f"benchmarks.configs.{cfg['builder']}")


def images(dtype: str = "float32"):
    cfg = run_mod.load_config("imagenet_perceiver")
    cfg.update(image_shape=[16, 16, 3], num_classes=10, num_frequency_bands=4,
               num_latents=8, num_latent_channels=32, num_encoder_layers=3,
               num_self_attention_layers_per_block=2, num_self_attention_heads=4,
               dtype=dtype)
    mix = traffic.load_mix("train_images_b8")
    mix.update(batch_size=4, warmup_steps=1)
    mix["fields"]["image"]["shape"] = [16, 16, 3]
    mix["fields"]["label"]["classes"] = 10
    return {"name": "imagenet_perceiver_train"}, cfg, mix, importlib.import_module(
        f"benchmarks.configs.{cfg['builder']}")


def fillmask(dtype: str = "float32"):
    cfg = run_mod.load_config("mlm_c512")
    cfg.update(vocab_size=203, max_seq_len=32, num_latents=8, num_latent_channels=32,
               num_encoder_layers=3, num_self_attention_layers_per_block=2, dtype=dtype)
    mix = traffic.load_mix("fillmask_closed_c64")
    mix.update(clients=4, warm_requests_per_client=1)
    mix["requests"].update(pool=64, length_median=14, length_low=6, length_high=32)
    mix["server"].update(bucket_widths=[8, 16, 32], max_batch=4, batch_buckets=[1, 2, 4],
                         compute_dtype=dtype)
    mix["check"].update(sample=12, block_rows=4)
    return {"name": "mlm_c512_fillmask"}, cfg, mix, importlib.import_module(
        f"benchmarks.configs.{cfg['builder']}")
