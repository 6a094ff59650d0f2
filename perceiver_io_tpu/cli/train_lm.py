"""Causal decoder LM pretraining entry point (latent attention, routed
experts, multi-token prediction: ``models/decoder_lm.py``).

The model's sizes are the keys of a published ``config.json`` of the
DeepSeek-V3 family (``--config``), each also a flag of its own name that
overrides the file; without either a size takes a CPU-scale default. The
chip's share of the routed experts is ``--experts_held`` / ``--expert_offset``
(default: all of them); ``--attn_impl auto|pallas|xla`` chooses the causal path. Data is the IMDB text pipeline of the other text
tasks (``--synthetic`` works offline); the vocabulary is the tokenizer's.

Usage:

    python -m perceiver_io_tpu.cli.train_lm --synthetic --max_steps 200 \
        --default_root_dir /tmp/lm_run
    python -m perceiver_io_tpu.cli.train_lm --config config.json \
        --experts_held 8 --expert_offset 0 ...
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Optional, Sequence

import jax

from perceiver_io_tpu import obs
from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.imdb import IMDBDataModule
from perceiver_io_tpu.models.decoder_lm import DecoderLM, DecoderLMConfig
from perceiver_io_tpu.ops import moe
from perceiver_io_tpu.training import TrainState, make_lm_steps
from perceiver_io_tpu.training.trainer import Trainer

# CPU-scale sizes for a run that names none (the shape of the family: one
# dense layer, then expert layers, one MTP module)
SMALL = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=2.5, norm_topk_prob=True, num_nextn_predict_layers=1,
    rope_theta=10000.0, rms_norm_eps=1e-6,
)
# the vocabulary is the tokenizer's (``--vocab_size`` of the data flags)
MODEL_FIELDS = [f for f in dataclasses.fields(DecoderLMConfig) if f.name != "vocab_size"]
FLAG_TYPES = {"int": int, "float": float, "Optional[int]": int,
              "bool": lambda text: text.lower() in ("1", "true")}


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    common.add_trainer_args(parser)
    common.add_mesh_args(parser)
    common.add_compute_args(parser)
    common.add_optimizer_args(parser)
    common.add_imdb_args(parser)
    g = parser.add_argument_group("model (keys of the published config.json)")
    g.add_argument("--config", default=None,
                   help="a published config.json; a size flag below overrides its key")
    for field in MODEL_FIELDS:
        g.add_argument(f"--{field.name}", type=FLAG_TYPES[str(field.type)], default=None)
    parser.set_defaults(experiment="lm", batch_size=8)
    return parser


def model_config(args, vocab_size: int) -> DecoderLMConfig:
    """Flag over file over ``SMALL``, for every size."""
    published = {}
    if args.config:
        with open(args.config) as f:
            published = json.load(f)
    sizes = {**SMALL, **published, "vocab_size": vocab_size}
    for field in MODEL_FIELDS:
        if getattr(args, field.name) is not None:
            sizes[field.name] = getattr(args, field.name)
    return DecoderLMConfig.from_dict(sizes)


@obs.span("lm.build_model")
def build_model(args, vocab_size: int) -> DecoderLM:
    config = model_config(args, vocab_size)
    held = config.n_routed_experts if config.experts_held is None else config.experts_held
    tokens = args.batch_size * args.max_seq_len
    obs.event("moe.share", held=held, total=config.n_routed_experts,
              offset=config.expert_offset,
              capacity_rows=moe.TILE_ROWS * moe.capacity_tiles(
                  tokens, config.num_experts_per_tok, held, config.n_routed_experts, moe.TILE_ROWS),
              worst_case_rows=moe.TILE_ROWS * moe.worst_case_tiles(
                  tokens * config.num_experts_per_tok, held, moe.TILE_ROWS))
    # the model rematerialises every block (``--remat`` is the Perceiver
    # encoders' switch) and the experts' path follows the backend
    return DecoderLM(config, attn_impl=args.attn_impl, dtype=common.DTYPES[args.dtype])


def main(argv: Optional[Sequence[str]] = None):
    args = common.parse_with_resume(build_parser(), argv)
    if common.maybe_spawn_hosts(args, argv):
        return None
    configure_compile_cache()
    common.maybe_initialize_distributed(args)
    common.validate_bucket_args(args)

    data = IMDBDataModule(
        root=args.root,
        max_seq_len=args.max_seq_len,
        vocab_size=args.vocab_size,
        batch_size=args.batch_size,
        synthetic=args.synthetic,
        synthetic_size=args.synthetic_size,
        seed=args.seed,
        shard_id=jax.process_index(),
        num_shards=jax.process_count(),
        download=not args.no_download,
        bucket_widths=args.bucket_widths,
        length_sort_window=args.length_sort_window,
        dispatch_group=args.steps_per_dispatch,
    )
    data.prepare_data()
    data.setup()

    model = build_model(args, data.tokenizer.get_vocab_size())
    example = next(iter(data.val_dataloader()))
    variables = model.init({"params": jax.random.key(args.seed)}, example["token_ids"][:1])
    tx, schedule = common.optimizer_from_args(args)
    state = TrainState.create(variables["params"], tx, jax.random.key(args.seed + 2))
    state, resume_dir = common.resume_state(args, state)

    train_step, eval_step, _ = make_lm_steps(model, schedule)
    trainer = Trainer(
        train_step,
        eval_step,
        state,
        common.trainer_config(args),
        example_batch={k: example[k] for k in ("token_ids", "pad_mask")},
        mesh=common.mesh_from_args(args),
        zero_opt=args.zero_opt,
        hparams=vars(args),
        run_dir=resume_dir,
        tokens_per_example=args.max_seq_len,
    )
    with trainer:
        common.run_fit(trainer, data.train_dataloader(), data.val_dataloader())
    return trainer.run_dir


if __name__ == "__main__":
    main()
