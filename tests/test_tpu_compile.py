"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: a block the TPU lowering's tiling rule rejects, a scoped-VMEM
overflow, an unaligned slice. The TPU compiler is installed here and compiles
for a chip that is described, not attached — so these cases guard every PR at
no chip time. Each asserts a ``tpu_custom_call`` in the compiled text: the
kernel lowered through Mosaic, it did not fall back.

The topology is described INSIDE a module-scoped fixture (never at import, in
a ``skipif`` or in ``parametrize``): only one process may load the TPU
library, and every xdist worker imports every test file. All such tests live
in this one file, so one worker loads the library; they compile in the test's
own process, with the persistent compilation cache off around them (a compile
for a described chip is written to it but cannot be read back).
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sum_sq(fn, grad):
    def loss(*args):
        return jnp.sum(fn(*args).astype(jnp.float32) ** 2)

    if grad:
        return lambda *args: jax.grad(
            loss, argnums=tuple(range(len(args))))(*args)
    return loss


def _attention(b, t, s, h, d, grad=False, causal_offset=None, dv=None,
               blocks=(None, None), kv_heads=None, block_diffusion=None):
    from perceiver_io_tpu.ops.pallas_attention import BlockDiffusion, fused_attention

    fn = functools.partial(
        fused_attention, causal_offset=causal_offset, interpret=False,
        q_block_size=blocks[0], kv_block_size=blocks[1],
        block_diffusion=block_diffusion and BlockDiffusion(t // 2, block_diffusion))
    qkv = [((b, t, h, d), jnp.bfloat16), ((b, s, kv_heads or h, d), jnp.bfloat16),
           ((b, s, kv_heads or h, dv or d), jnp.bfloat16)]
    return _sum_sq(fn, grad), qkv


def _mla_blocks():
    from perceiver_io_tpu.ops import latent_attention

    return latent_attention.pallas_blocks(192)


def _gqa_blocks():
    from perceiver_io_tpu.ops import latent_attention

    return latent_attention.pallas_blocks(64)


def _gqa16_blocks():
    from perceiver_io_tpu.ops import latent_attention

    return latent_attention.pallas_blocks(128)


def _grouped_matmul(tokens, top_k, held, k, n, grad=False, dtype=jnp.bfloat16, of_experts=None):
    """Over the worst-case buffer, or over the bounded one of a layer that
    holds ``held`` ``of_experts``."""
    from perceiver_io_tpu.ops.moe import TILE_ROWS, capacity_tiles, worst_case_tiles
    from perceiver_io_tpu.ops.pallas_grouped_matmul import grouped_matmul

    tiles = (worst_case_tiles(tokens * top_k, held, TILE_ROWS) if of_experts is None else
             capacity_tiles(tokens, top_k, held, of_experts, TILE_ROWS))
    fn = lambda lhs, rhs, tile_group: grouped_matmul(  # noqa: E731
        lhs, rhs, tile_group, TILE_ROWS, interpret=False)
    if grad:
        fn = jax.grad(lambda lhs, rhs, tile_group, inner=fn: jnp.sum(
            inner(lhs, rhs, tile_group).astype(jnp.float32) ** 2), argnums=(0, 1))
    return fn, [((tiles * TILE_ROWS, k), dtype), ((held, k, n), dtype),
                ((tiles,), jnp.int32)]


def _flash_ce(rows, c, vocab, grad=False):
    from perceiver_io_tpu.ops.pallas_ce import pallas_linear_ce_integer

    def loss(x, w, bias, labels):
        return jnp.sum(
            pallas_linear_ce_integer(x, w, bias, labels, interpret=False))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    return fn, [((rows, c), jnp.bfloat16), ((c, vocab), jnp.bfloat16),
                ((vocab,), jnp.float32), ((rows,), jnp.int32)]


def _qmm(m, k, n, bits, group_size=None):
    from perceiver_io_tpu.ops.pallas_matmul import dequant_matmul

    fn = functools.partial(
        dequant_matmul, group_size=group_size, interpret=False)
    scale = (n,) if group_size is None else (k // group_size, n)
    return fn, [((m, k), jnp.bfloat16),
                ((k, n), jnp.int8 if bits == 8 else jnp.int4),
                (scale, jnp.float32)]


# flagship widths: (batch 64, 256 latents, 512 tokens, 4 heads) at the
# reference's head depth 16 and flagship_tpu's 128; 10240 = 64 x 160 gathered
# decode rows; the MLP (512 -> 2048) and vocab-head (512 -> 10003) matmuls
_MLP = (2048, 512, 2048)
_HEAD = (512, 512, 10003)
CASES = {
    "attn-fwd-d16": lambda: _attention(64, 256, 512, 4, 16),
    "attn-grad-d16": lambda: _attention(64, 256, 512, 4, 16, grad=True),
    "attn-fwd-d128": lambda: _attention(64, 256, 512, 4, 128),
    "attn-grad-d128": lambda: _attention(64, 256, 512, 4, 128, grad=True),
    "attn-causal-prefill-d128": lambda: _attention(
        4, 256, 512, 4, 128, causal_offset=256),
    "attn-q1-decode-d128": lambda: _attention(
        4, 1, 512, 4, 128, causal_offset=511),
    # the causal decoder's latent attention (4 rows x 4096, 32 heads, scores
    # 192 deep, values 128 deep, tiles above the diagonal skipped) and its held
    # experts' products (16,384 tokens x top-8, 8 experts, 2048 <-> 768)
    "attn-mla-causal-fwd": lambda: _attention(
        4, 4096, 4096, 32, 192, dv=128, causal_offset=0, blocks=_mla_blocks()),
    "attn-mla-causal-grad": lambda: _attention(
        4, 4096, 4096, 32, 192, dv=128, causal_offset=0, blocks=_mla_blocks(), grad=True),
    "gmm-experts-up-grad": lambda: _grouped_matmul(16384, 8, 8, 2048, 768, grad=True),
    "gmm-experts-down-grad": lambda: _grouped_matmul(16384, 8, 8, 768, 2048, grad=True),
    # the same over the bounded buffer of 8 of 256 experts (72 tiles of the 520)
    "gmm-experts-up-grad-bounded": lambda: _grouped_matmul(
        16384, 8, 8, 2048, 768, grad=True, of_experts=256),
    "gmm-experts-down-grad-bounded": lambda: _grouped_matmul(
        16384, 8, 8, 768, 2048, grad=True, of_experts=256),
    # the lfm2_moe cell's grouped-query attention (2 rows x 8192, 32 query heads
    # over 8 key/value heads of 64: the block index maps send a head to its
    # group, dk / dv are summed over the group in the kernel) and its held
    # experts' products (16,384 tokens x top-4, 8 of 32 experts, 2048 <-> 1792:
    # over the bounded buffer, 136 tiles = 34,816 rows, and over the worst
    # case's 264 = 67,584, the branch a step takes where its routing overflows)
    "attn-gqa-causal-fwd": lambda: _attention(
        2, 8192, 8192, 32, 64, causal_offset=0, blocks=_gqa_blocks(), kv_heads=8),
    "attn-gqa-causal-grad": lambda: _attention(
        2, 8192, 8192, 32, 64, causal_offset=0, blocks=_gqa_blocks(), kv_heads=8, grad=True),
    "gmm-lfm2-experts-up-grad": lambda: _grouped_matmul(
        16384, 4, 8, 2048, 1792, grad=True, of_experts=32),
    "gmm-lfm2-experts-down-grad": lambda: _grouped_matmul(
        16384, 4, 8, 1792, 2048, grad=True, of_experts=32),
    "gmm-lfm2-experts-up-grad-worst-case": lambda: _grouped_matmul(
        16384, 4, 8, 2048, 1792, grad=True),
    "gmm-lfm2-experts-down-grad-worst-case": lambda: _grouped_matmul(
        16384, 4, 8, 1792, 2048, grad=True),
    # the nemotron_h cell's attention block (1 row x 8192, 32 query heads over 2
    # key/value heads of 128: groups of 16) and its held experts' two products
    # (8,192 tokens x top-6, 8 of 128 experts, 2688 <-> 1856: 1856 is 14.5 x 128,
    # so the kernels block it by 512 with a last block part outside the array;
    # one block of the whole width ran out of VMEM in the sandbox's compile, PR
    # 38; over the bounded buffer, 56 tiles, and over the worst case's 200)
    "attn-gqa16-causal-fwd": lambda: _attention(
        1, 8192, 8192, 32, 128, causal_offset=0, blocks=_gqa16_blocks(), kv_heads=2),
    "attn-gqa16-causal-grad": lambda: _attention(
        1, 8192, 8192, 32, 128, causal_offset=0, blocks=_gqa16_blocks(), kv_heads=2, grad=True),
    # the sdar cell's: 8,192 tokens as the 16,384 positions [x_t ; x] under the
    # block-diffusion mask with blocks of 4, 32 query heads over 4 of 128
    "attn-sdar-block-diffusion-fwd": lambda: _attention(
        1, 16384, 16384, 32, 128, blocks=_gqa16_blocks(), kv_heads=4, block_diffusion=4),
    "attn-sdar-block-diffusion-grad": lambda: _attention(
        1, 16384, 16384, 32, 128, blocks=_gqa16_blocks(), kv_heads=4, block_diffusion=4,
        grad=True),
    # the ouro cell's multi-head attention (1 row x 4096, 16 query heads over
    # 16 key/value heads of 128: a group of 1), run 32 times a step by the loop
    "attn-ouro-mha-causal-fwd": lambda: _attention(
        1, 4096, 4096, 16, 128, causal_offset=0, blocks=_gqa16_blocks(), kv_heads=16),
    "attn-ouro-mha-causal-grad": lambda: _attention(
        1, 4096, 4096, 16, 128, causal_offset=0, blocks=_gqa16_blocks(), kv_heads=16,
        grad=True),
    "gmm-sdar-experts-gate-grad": lambda: _grouped_matmul(
        16384, 8, 16, 2048, 768, grad=True, of_experts=128),
    "gmm-sdar-experts-down-grad-worst-case": lambda: _grouped_matmul(
        16384, 8, 16, 768, 2048, grad=True),
    "gmm-nemotron-experts-up-grad": lambda: _grouped_matmul(
        8192, 6, 8, 2688, 1856, grad=True, of_experts=128),
    "gmm-nemotron-experts-down-grad": lambda: _grouped_matmul(
        8192, 6, 8, 1856, 2688, grad=True, of_experts=128),
    "gmm-nemotron-experts-up-grad-worst-case": lambda: _grouped_matmul(
        8192, 6, 8, 2688, 1856, grad=True),
    "gmm-nemotron-experts-down-grad-worst-case": lambda: _grouped_matmul(
        8192, 6, 8, 1856, 2688, grad=True),
    # the float32 (parity) path: blocks of the bfloat16 size ran out of VMEM
    # on the chip (PR 32)
    "gmm-experts-up-grad-f32": lambda: _grouped_matmul(
        16384, 8, 8, 2048, 768, grad=True, dtype=jnp.float32),
    "ce-fwd-c64": lambda: _flash_ce(10240, 64, 10003),
    "ce-grad-c64": lambda: _flash_ce(10240, 64, 10003, grad=True),
    "ce-fwd-c512": lambda: _flash_ce(10240, 512, 10003),
    "ce-grad-c512": lambda: _flash_ce(10240, 512, 10003, grad=True),
    "qmm-int8-mlp": lambda: _qmm(*_MLP, bits=8),
    "qmm-int8-head": lambda: _qmm(*_HEAD, bits=8),
    "qmm-int4-mlp": lambda: _qmm(*_MLP, bits=4),
    "qmm-int4-head": lambda: _qmm(*_HEAD, bits=4),
    "qmm-int4-grouped-mlp": lambda: _qmm(*_MLP, bits=4, group_size=128),
    "qmm-int4-grouped-head": lambda: _qmm(*_HEAD, bits=4, group_size=128),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the nemotron_h cell's state-space scan at its real size: 1 row x 8192 tokens,
# 64 heads of 64 over 8 groups of state 128, in chunks of 128
_SCAN = [((1, 8192, 64, 64), jnp.bfloat16), ((1, 8192, 64), jnp.float32),
         ((64,), jnp.float32), ((1, 8192, 8, 128), jnp.bfloat16),
         ((1, 8192, 8, 128), jnp.bfloat16), ((64,), jnp.float32)]


def _compiled_scan_grad(scan, one_chip):
    def loss(*operands):
        return jnp.sum(scan(*operands).astype(jnp.float32) ** 2)

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in _SCAN]
    return jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(*args).compile()


def test_chunked_scan_compiles_for_v5e_inside_its_memory(one_chip):
    """The scan as the chip runs it (``ops/pallas_ssd.py``: a forward and a
    backward kernel under one ``custom_vjp``), forward and backward: both
    lower through Mosaic inside their VMEM, the temporaries are the states
    entering the 64 chunks (134 MB) and not the einsum form's, and nothing of
    the size of the decay matrix (``rows x chunks x H x 128 x 128``) is in the
    program."""
    import re

    from perceiver_io_tpu.ops import pallas_ssd

    def scan(x, delta, a, b, c, d):
        return pallas_ssd.ssd_scan(x.reshape(1, 8192, 4096), delta, a, b.reshape(1, 8192, 1024),
                                   c.reshape(1, 8192, 1024), d, 64, 8, 128, interpret=False)

    compiled = _compiled_scan_grad(scan, one_chip)
    text = compiled.as_text()
    kernels = [len(re.findall(rf"%{name}(\.\d+)? = ", text))
               for name in (pallas_ssd.KERNEL_FWD, pallas_ssd.KERNEL_BWD)]
    assert kernels == [1, 1] and text.count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    shapes = {tuple(map(int, dims.split(","))) for dims in re.findall(r"\[([\d,]+)\]", text)}
    assert not [dims for dims in shapes if dims[-2:] == (128, 128)
                and math.prod(dims) >= 64 * 64 * 128 * 128]


def test_chunked_scan_einsums_compile_for_v5e_inside_their_memory(one_chip):
    """The einsum form (``ops/mamba2.ssd_scan``: plain XLA, no kernel; the
    CPU's path and the kernels' oracle) at the same size, forward and backward
    under its checkpoint: the temporaries are the chunked form's (the decay
    matrix, the chunks' states), nowhere near one state a token (17 GB)."""
    from perceiver_io_tpu.ops.mamba2 import ssd_scan

    def scan(*operands):
        return jax.checkpoint(ssd_scan, static_argnums=(6,))(*operands, 128)

    compiled = _compiled_scan_grad(scan, one_chip)
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_kept_attention_residuals_are_compact_on_v5e(one_chip):
    """What the decoder's blocks keep of the causal kernel between the passes
    (``DecoderLM._remat_policy``), compiled for the chip at the cell's shape
    and depth: six checkpointed layers run the forward kernel once each
    instead of twice, and the program's temporaries grow by no more than the
    bytes the policy reckons (the output and ONE float a row and head of each
    statistic). That bound is what interpret mode cannot see: kept as (B, H,
    T, 1), the tiled layout pads each statistic back to 128 lanes, 268 MB
    where 2 are meant, and the six layers cost 1.3 GB more, not less."""
    import re

    from perceiver_io_tpu.ops import pallas_attention as pa

    b, t, h, d, dv, layers = 4, 4096, 32, 192, 128, 6
    blocks = _mla_blocks()

    def layer(q, k, v):
        return pa.fused_attention(q, k, v, causal_offset=0, interpret=False,
                                  q_block_size=blocks[0], kv_block_size=blocks[1])

    def compiled(checkpoint):
        def loss(q, k, v):
            for _ in range(layers):
                v = checkpoint(layer)(q, k, v)
            return jnp.sum(v.astype(jnp.float32) ** 2)

        args = [jax.ShapeDtypeStruct((b, t, h, depth), jnp.bfloat16, sharding=one_chip)
                for depth in (d, d, dv)]
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile()

    def forward_kernels(program):
        return len(re.findall(rf"%{pa.KERNEL_FWD}(\.\d+)? = ", program.as_text()))

    policy = jax.checkpoint_policies.save_only_these_names(
        pa.REMAT_FUSED_OUT, pa.REMAT_FUSED_STATS)
    bare = compiled(jax.checkpoint)
    keeping = compiled(functools.partial(jax.checkpoint, policy=policy))
    assert (forward_kernels(bare), forward_kernels(keeping)) == (2 * layers, layers)
    more = (keeping.memory_analysis().temp_size_in_bytes
            - bare.memory_analysis().temp_size_in_bytes)
    assert more <= layers * b * t * h * (dv * 2 + 8)
