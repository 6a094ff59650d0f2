"""Block-size sweep for the flow encoder-cross fused-attention kernel.

PERF.md r2 pinned flow's remaining headroom on the encoder-cross kernel's
14-16 TF/s MXU rate and left block tuning "blocked by infra". Subtlety the
sweep must cover: S = 368·496 = 182528 = 2^8·23·31, whose lane-aligned
divisors are 128, 256, then nothing until 2944 (= 128·23) and 3968
(= 128·31) — so the default kv_block_size=512 silently degrades to 256
(`_kv_block_size` picks the largest aligned divisor ≤ request), mid-range
blocks require the PAD path (S padded up to a block multiple with PAD_BIAS
keys), and the big exact divisors stream with no padding at all. This
script times fwd+bwd at the flow encoder-cross shape across (kv_block,
q_block) grids covering all three regimes.

Usage: ``timeout 1800 python tools/flow_block_sweep.py [--batch 4]``
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# one copy of the kernel timing discipline (fori_loop chaining,
# DCE-proof dep sum, 1-iter subtraction) — shared with the shapes bench
from attn_shapes_bench import grad_of, timeit
from perceiver_io_tpu.ops.pallas_attention import fused_attention

T, S, H, D = 2048, 182528, 1, 512
KV_BLOCKS = [256, 512, 1024, 2048, 2944, 3968]  # 2944/3968: exact divisors
Q_BLOCKS = [256, 512, 1024]


def main() -> None:
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    b = 4
    if "--batch" in sys.argv:
        b = int(sys.argv[sys.argv.index("--batch") + 1])
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, T, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, S, H, D)), jnp.bfloat16)
    flops = 4 * b * H * T * S * D * 3.5  # fwd+bwd

    print(f"flow encoder-cross (B={b}, T={T}, S={S}, H={H}, D={D}), fwd+bwd", file=sys.stderr)
    for kv_blk in KV_BLOCKS:
        for q_blk in Q_BLOCKS:
            attn = functools.partial(
                fused_attention, kv_block_size=kv_blk, q_block_size=q_blk
            )
            fn = grad_of(attn)
            try:
                t = timeit(fn, (q, k, v))
                print(f"  kv {kv_blk:5d} q {q_blk:5d}: {t*1e3:8.2f} ms "
                      f"({flops/t/1e12:5.1f} TF/s)", file=sys.stderr)
            except Exception as e:
                print(f"  kv {kv_blk:5d} q {q_blk:5d}: FAILED "
                      f"{type(e).__name__}: {str(e)[:90]}", file=sys.stderr)


if __name__ == "__main__":
    main()
