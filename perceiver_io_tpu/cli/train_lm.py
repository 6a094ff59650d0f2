"""Causal decoder LM pretraining entry point (``models/decoder_lm.py``: one
skeleton for the DeepSeek-V3 family, latent attention + routed and shared
experts + multi-token prediction; for ``lfm2_moe``, gated short convolutions
and grouped-query attention mixed by ``layer_types`` + routed experts + a tied
head; for ``nemotron_h``, one sublayer a block by
``hybrid_override_pattern``: Mamba-2 mixers, attention without positions,
squared-ReLU experts beside a shared expert of its own width; and for
``sdar_moe``, grouped-query attention and 128 softmax-routed experts in every
layer, trained by block diffusion when the configuration's ``objective`` says
so: ``training.make_block_diffusion_steps``; and for ``ouro``, a LOOPED stack:
multi-head attention and a dense SwiGLU under sandwich norms, the blocks run
``total_ut_steps`` times over one weight set, an exit gate and a head read
after every step, trained by the expected-exit loss).

The model's sizes are the keys of a published ``config.json`` (``--config``),
each also a flag of its own name that overrides the file; the published
``model_type`` (``--model_type``) chooses the family, and without a file a
size takes the family's CPU-scale default. The chip's share of the routed
experts is ``--experts_held`` / ``--expert_offset`` (default: all of them);
``--attn_impl auto|pallas|xla`` chooses the causal path. Data is the IMDB text
pipeline of the other text tasks (``--synthetic`` works offline); the
vocabulary is the tokenizer's.

Usage:

    python -m perceiver_io_tpu.cli.train_lm --synthetic --max_steps 200 \
        --default_root_dir /tmp/lm_run
    python -m perceiver_io_tpu.cli.train_lm --config config.json \
        --experts_held 8 --expert_offset 0 ...
    python -m perceiver_io_tpu.cli.train_lm --synthetic --model_type lfm2_moe \
        --layer_types conv,full_attention,conv ...
    python -m perceiver_io_tpu.cli.train_lm --synthetic --model_type nemotron_h \
        --hybrid_override_pattern 'MEMEM*EME' ...
    python -m perceiver_io_tpu.cli.train_lm --synthetic --model_type sdar_moe \
        --block_length 4 ...
    python -m perceiver_io_tpu.cli.train_lm --synthetic --model_type ouro \
        --total_ut_steps 4 ...
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
from typing import Optional, Sequence

import jax

from perceiver_io_tpu import obs
from perceiver_io_tpu.aot import configure_compile_cache
from perceiver_io_tpu.cli import common
from perceiver_io_tpu.data.imdb import IMDBDataModule
from perceiver_io_tpu.models.decoder_lm import (
    LFM2_MOE,
    NEMOTRON_H,
    OURO,
    SDAR_MOE,
    DecoderLM,
    DecoderLMConfig,
)
from perceiver_io_tpu.ops import latent_attention, mamba2, moe
from perceiver_io_tpu.training import TrainState, make_block_diffusion_steps, make_lm_steps
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
# the same for ``--model_type lfm2_moe``, under that family's published keys:
# both mixer kinds, one dense layer, groups of two query heads, three taps
SMALL_LFM2 = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, num_dense_layers=1, layer_types=("conv", "full_attention", "conv"),
    num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3, num_experts=8,
    num_experts_per_tok=2, routed_scaling_factor=1.0, norm_topk_prob=True,
    use_expert_bias=True, conv_bias=False, rope_theta=1000000.0, norm_eps=1e-5,
)
# the same for ``--model_type nemotron_h``: the three kinds of block, several
# heads a group in both mixers, 4 taps with bias, a shared expert of another
# width than the experts'
SMALL_NEMOTRON_H = dict(
    hidden_size=64, moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    hybrid_override_pattern="MEM*E", num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
    chunk_size=16, n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=2.5, norm_topk_prob=True, mlp_hidden_act="relu2",
    use_conv_bias=True, layer_norm_epsilon=1e-5,
)
# the same for ``--model_type sdar_moe``: groups of two query heads, a softmax
# router over 8 experts, trained by block diffusion over blocks of 4 (the mask
# id is the vocabulary's last row)
SMALL_SDAR = dict(
    hidden_size=64, moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, rope_theta=1000000.0, rms_norm_eps=1e-6, objective="block_diffusion",
    block_length=4,
)
# the same for ``--model_type ouro``: two sandwich-norm layers of multi-head
# attention looped three times
SMALL_OURO = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, rope_theta=1000000.0, rms_norm_eps=1e-6,
    total_ut_steps=3,
)
SMALL_SIZES = {LFM2_MOE: SMALL_LFM2, NEMOTRON_H: SMALL_NEMOTRON_H, SDAR_MOE: SMALL_SDAR,
               OURO: SMALL_OURO}
Flag = collections.namedtuple("Flag", "name type")
# every size is a flag: the skeleton's fields (the vocabulary is the
# tokenizer's, ``--vocab_size`` of the data flags), and the published keys
# that the ``lfm2_moe`` and ``nemotron_h`` families name otherwise
MODEL_FIELDS = [f for f in dataclasses.fields(DecoderLMConfig) if f.name != "vocab_size"] + [
    Flag("model_type", "str"), Flag("num_dense_layers", "int"), Flag("num_experts", "int"),
    Flag("norm_eps", "float"), Flag("use_expert_bias", "bool"), Flag("conv_bias", "bool"),
    Flag("hybrid_override_pattern", "str"), Flag("layer_norm_epsilon", "float"),
    Flag("use_conv_bias", "bool")]
FLAG_TYPES = {"int": int, "float": float, "Optional[int]": int, "str": str,
              "bool": lambda text: text.lower() in ("1", "true"),
              "Tuple[str, ...]": lambda text: tuple(text.split(","))}


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
    """Flag over file over the family's small sizes, for every size; the
    family is the ``model_type`` of the flag or the file."""
    published = {}
    if args.config:
        with open(args.config) as f:
            published = json.load(f)
    family = args.model_type or published.get("model_type")
    sizes = {**SMALL_SIZES.get(family, SMALL), **published, "vocab_size": vocab_size}
    for field in MODEL_FIELDS:
        if getattr(args, field.name) is not None:
            sizes[field.name] = getattr(args, field.name)
    if family == NEMOTRON_H:  # the depth is the pattern's, whichever of the two was given
        sizes["num_hidden_layers"] = len(sizes["hybrid_override_pattern"])
    if family == SDAR_MOE and "mask_token_id" not in sizes:
        sizes["mask_token_id"] = vocab_size - 1
    return DecoderLMConfig.from_dict(sizes)


def build_model(args, vocab_size: int) -> DecoderLM:
    config = model_config(args, vocab_size)
    held = config.n_routed_experts if config.experts_held is None else config.experts_held
    diffusion = config.objective == "block_diffusion"
    # a row of block diffusion runs as two: its noised and its clean copy
    tokens = args.batch_size * args.max_seq_len * (2 if diffusion else 1)
    if config.n_routed_experts:
        obs.event("moe.share", held=held, total=config.n_routed_experts,
                  offset=config.expert_offset,
                  capacity_rows=moe.TILE_ROWS * moe.capacity_tiles(
                      tokens, config.num_experts_per_tok, held, config.n_routed_experts,
                      moe.TILE_ROWS),
                  worst_case_rows=moe.TILE_ROWS * moe.worst_case_tiles(
                      tokens * config.num_experts_per_tok, held, moe.TILE_ROWS))
    scans = "mamba2" in config.mixers
    # the mixer's own question: the kernel pair or the einsums
    scan_kernel = scans and mamba2.scan_impl(
        config.mamba_num_heads, config.mamba_head_dim, config.n_groups,
        config.ssm_state_size, config.chunk_size, args.max_seq_len) == "pallas"
    # the share of the block-diffusion kernels' tiles that take no mask, by the
    # classes they branch on (0 on the XLA path: no kernel runs there)
    unmasked_tiles = latent_attention.block_diffusion_unmasked_tiles_pct(
        2 * args.max_seq_len, config.head_dim, config.block_length,
        args.attn_impl) if diffusion else 0.0
    # every block runs once a step, or R times in a looped stack
    applications = max(config.total_ut_steps, 1) * config.num_hidden_layers
    obs.event("lm.layers", mixers=list(config.mixers),
              one_sublayer_blocks=config.one_sublayer_blocks,
              dense_layers=config.first_k_dense_replace,
              kv_group=(config.num_attention_heads // config.num_key_value_heads
                        if "full_attention" in config.mixers else 1),
              experts_held=held, experts_published=config.n_routed_experts,
              shared_expert_width=(config.moe_shared_expert_intermediate_size
                                   or config.n_shared_experts * config.moe_intermediate_size),
              ssd_chunk=config.chunk_size if scans else 0,
              ssd_state=[config.mamba_num_heads, config.mamba_head_dim,
                         config.ssm_state_size] if scans else [],
              ssd_scan_kernel=scan_kernel,
              tied_head=config.tie_word_embeddings,
              objective=config.objective, block_length=config.block_length,
              bd_unmasked_tiles_pct=unmasked_tiles, total_ut_steps=config.total_ut_steps,
              sandwich_norm=config.sandwich_norm, block_applications=applications)
    # float32 bytes of the states that a Mamba-2 layer's backward pass holds for
    # one row: one a chunk, not one a token (0 without such a layer)
    obs.get_registry().gauge("ssd_state_bytes").set(mamba2.state_bytes(
        args.max_seq_len, config.chunk_size, config.mamba_num_heads, config.mamba_head_dim,
        config.ssm_state_size) if scans else 0)
    if scans:  # a constant of the traced program: 100 the kernel pair, 0 the einsums
        obs.get_registry().gauge("ssd_scan_kernel_pct").set(100.0 if scan_kernel else 0.0)
    if diffusion:  # a constant of the traced program
        obs.get_registry().gauge("bd_unmasked_tiles_pct").set(unmasked_tiles)
    if config.total_ut_steps:  # a constant of the traced program: R x N
        obs.get_registry().gauge("ut_block_applications").set(applications)
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

    steps = (make_block_diffusion_steps if model.config.objective == "block_diffusion"
             else make_lm_steps)
    train_step, eval_step, _ = steps(model, schedule)
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
