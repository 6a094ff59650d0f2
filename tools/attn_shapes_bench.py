"""Compare XLA vs fused-Pallas attention across the framework's hot shapes.

Shapes: (name, B, T, S, H, D) — T queries against S keys/values.
- mlm-cross:   encoder cross-attn at the flagship MLM config
- mlm-self:    latent self-attn at the flagship MLM config
- in-cross:    ImageNet encoder cross-attn (M = 224² = 50176, 1 head × 1024)
- in-small:    ImageNet with 8 cross heads (paper variant)
- flow-cross:  Sintel flow encoder cross-attn (M = 368×496 = 182528)

``--decode`` appends the GENERATIVE (Perceiver-AR) decode family — causal
prefill cross/self at the flagship_ar widths and the q_len=1 incremental
step shapes — with both impls running the causal mask (XLA: masked einsum;
Pallas: the in-kernel ``causal_offset`` flag). These rows are what the
``attn_impl='auto'`` causal dispatch thresholds must be set from; until the
sweep runs on hardware, auto resolves every causal call to XLA (PERF.md
§Generation pending).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from perceiver_io_tpu.ops.masking import causal_mask
from perceiver_io_tpu.ops.pallas_attention import fused_attention

SHAPES = [
    ("mlm-cross", 8, 256, 512, 4, 16),
    ("mlm-self", 8, 256, 256, 4, 16),
    ("in-cross", 2, 512, 50176, 1, 1024),
    ("in-8h", 2, 512, 50176, 8, 128),
    ("flow-cross", 1, 2048, 182528, 1, 512),
    ("flow-self", 2, 2048, 2048, 8, 64),
    # shapes the area-based auto trigger also flips: the flow DECODER cross
    # (many queries, few keys) and ImageNet self-attn at batch >= 16
    ("flow-dec-cross", 2, 182528, 2048, 1, 512),
    ("in-self-b16", 16, 512, 512, 8, 128),
    # long-context MLM encoder cross (auto-kv streams 2048-wide blocks)
    ("mlm-32k", 2, 256, 32768, 4, 16),
    ("mlm-131k", 1, 256, 131072, 4, 16),
]

# Generative decode family: (name, B, T, S, H, D, causal_offset).
# - ar-prefill-cross: the causal latent-window cross at flagship_ar widths
#   (256 window queries over a long prefix; offset = S - T)
# - ar-prefill-self:  the square-causal latent self-attention
# - ar-step-cross:    ONE decode step's q_len=1 cross over the token ring
# - ar-step-latent:   q_len=1 over the latent ring (validity-masked; the
#   causal constraint degenerates to the offset)
DECODE_SHAPES = [
    ("ar-prefill-cross", 8, 256, 512, 4, 128, 256),
    ("ar-prefill-self", 8, 256, 256, 4, 128, 0),
    ("ar-prefill-32k", 1, 256, 32768, 4, 128, 32512),
    ("ar-step-cross", 8, 1, 512, 4, 128, 511),
    ("ar-step-cross-32k", 1, 1, 32768, 4, 128, 32767),
    ("ar-step-latent", 8, 1, 256, 4, 128, 255),
]


def xla_attn(q, k, v, causal_offset=None):
    d = q.shape[-1]
    logits = jnp.einsum("bthd,bshd->bhts", q * (d**-0.5), k,
                        preferred_element_type=jnp.float32)
    if causal_offset is not None:
        mask = causal_mask(q.shape[1], k.shape[1], causal_offset)
        logits = jnp.where(mask[None, None], jnp.finfo(jnp.float32).min,
                           logits)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def timeit(fn, args, steps=20):
    """Kernel step time without per-dispatch host latency (comparable to a
    sub-millisecond kernel) and without dead code: run the whole loop device-side in ONE dispatch
    (fori_loop), chaining each iteration's input on a reduction of EVERY
    output leaf (so no part of the computation is dead code — carrying just
    one element lets XLA DCE the rest of the body), then sync with a host
    scalar fetch. A 1-iteration run is subtracted to remove the fetch
    round-trip and loop overheads."""

    @jax.jit
    def loop(n, q0, *rest):
        def body(_, q):
            out = fn(q, *rest)
            dep = sum(jnp.sum(lf.astype(jnp.float32)) for lf in jax.tree.leaves(out))
            return q0 + (dep * 1e-30).astype(q0.dtype)

        return jnp.sum(jax.lax.fori_loop(0, n, body, q0).astype(jnp.float32))

    float(loop(1, *args))  # compile + warm

    def run(n):
        t0 = time.perf_counter()
        float(loop(n, *args))
        return time.perf_counter() - t0

    # grow the iteration count until the run dwarfs the ~100ms fetch noise
    t1 = run(1)
    n = steps
    while True:
        tn = run(n + 1)
        if tn > 1.0 or n >= 4096:
            return (tn - t1) / n
        n *= 4


def grad_of(attn):
    """fwd+bwd step: value_and_grad keeps the primal live so nothing DCEs."""
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def main():
    from perceiver_io_tpu.aot import configure_compile_cache

    configure_compile_cache()

    import functools

    with_grad = "--grad" in sys.argv
    with_decode = "--decode" in sys.argv
    rng = np.random.default_rng(0)
    shapes = [(*row, None) for row in SHAPES]
    if with_decode:
        shapes += DECODE_SHAPES
    for name, b, t, s, h, d, causal in shapes:
        q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
        xla_fn = functools.partial(xla_attn, causal_offset=causal)
        pal_fn = functools.partial(fused_attention, causal_offset=causal)
        fns = ((grad_of(xla_fn), grad_of(pal_fn)) if with_grad
               else (jax.jit(xla_fn), jax.jit(pal_fn)))
        times = []
        for impl, fn in zip(("xla", "pallas"), fns):
            try:
                times.append(timeit(fn, (q, k, v)))
            except Exception as e:
                times.append(float("nan"))
                print(f"{name}: {impl} failed: {type(e).__name__}: {e}", file=sys.stderr)
        t_xla, t_pal = times
        # fwd: QKᵀ + PV; bwd adds dq/dk/ds/dp/dv tile matmuls (~2.5x more);
        # a causal mask halves the LIVE area, but the dense-equivalent count
        # is reported so impls stay comparable across the flag
        flops = 4 * b * h * t * s * d * (3.5 if with_grad else 1.0)
        print(f"{name:16s} xla {t_xla*1e3:8.3f} ms ({flops/t_xla/1e12:6.1f} TF/s)   "
              f"pallas {t_pal*1e3:8.3f} ms ({flops/t_pal/1e12:6.1f} TF/s)", file=sys.stderr)


if __name__ == "__main__":
    main()
