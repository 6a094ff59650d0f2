"""Plain float32 reference of the Perceiver IO models the benchmark runs.

One module, straightforward ``jax.numpy``: the shared encoder and decoder
(Jaegle et al., arXiv:2107.14795; layer equations as in the PyTorch reference
``perceiver/model.py``), the text-in, text-out, image-in and class-out
adapters, BERT-style MLM masking, the MLM and classification losses with
their gradients, and the Adam / AdamW update. No kernels, no remat, no fused
projections, no K/V reuse, no gathered decode: every output position is
decoded and the loss ignores the unselected ones.

It imports nothing of ``perceiver_io_tpu``. Weights come from the benchmark
(``benchmarks/weights.py``) as a nested dict whose names are the published
module names of the architecture (``encoder/layer_1/cross_attention_layer/
cross_attention/attention/q_proj/kernel`` ...); kernels are ``(in, out)``.

Every contraction goes through :class:`Arith`: float32 operands at
``Precision.HIGHEST`` for the reference proper, or operands rounded to a
narrower type first (``float8_e4m3fn``) for the lower-precision CONTROL that
``correct`` has to tell from the program (benchmarks/README.md).

Departure from the published description, noted: none in the mathematics.
The masking key is derived the way the system under test derives it
(``fold_in(rng, step)`` -> split in two -> flax ``make_rng`` at the top-level
module), because the corrupted input is part of what a step computes from
the seed; the draws themselves are re-implemented here.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
Params = Dict[str, Any]

LN_EPS = 1e-5
IGNORE = -100


class Arith:
    """How contractions are computed: ``operand_dtype=None`` is the float32
    reference; a dtype rounds both operands of every contraction to it first,
    in the forward pass and (the cotangents) in the backward pass: the
    control. ``forward_only=True`` leaves the cotangents unrounded (a gentler
    control, read for the record in PERF.md)."""

    def __init__(self, operand_dtype: Optional[Any] = None, forward_only: bool = False):
        self.operand_dtype = operand_dtype
        self.forward_only = forward_only

    def _round(self, x: Array) -> Array:
        x = x.astype(jnp.float32)
        if self.operand_dtype is None:
            return x
        rounded = x.astype(self.operand_dtype).astype(jnp.float32)
        if self.forward_only:  # straight-through
            return x + jax.lax.stop_gradient(rounded - x)
        return rounded

    def einsum(self, spec: str, a: Array, b: Array) -> Array:
        return jnp.einsum(spec, self._round(a), self._round(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


F32 = Arith()


# -- layers -------------------------------------------------------------------


def linear(ar: Arith, x: Array, p: Params) -> Array:
    return ar.einsum("...i,io->...o", x, p["kernel"]) + p["bias"]


def layer_norm(x: Array, p: Params) -> Array:
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def attention(ar: Arith, x_q: Array, x_kv: Array, p: Params, heads: int,
              pad_mask: Optional[Array]) -> Array:
    """Multi-head attention, embedding width = query channels; ``pad_mask``
    (B, S) is True at padding (masked out)."""
    q = linear(ar, x_q, p["q_proj"])
    k = linear(ar, x_kv, p["k_proj"])
    v = linear(ar, x_kv, p["v_proj"])
    b, t, e = q.shape
    s = k.shape[1]
    d = e // heads
    q = q.reshape(b, t, heads, d) * (d ** -0.5)
    k = k.reshape(b, s, heads, d)
    v = v.reshape(b, s, heads, d)
    logits = ar.einsum("bthd,bshd->bhts", q, k)
    if pad_mask is not None:
        logits = jnp.where(pad_mask[:, None, None, :],
                           jnp.finfo(jnp.float32).min, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    out = ar.einsum("bhts,bshd->bthd", probs, v).reshape(b, t, e)
    return linear(ar, out, p["out_proj"])


def mlp(ar: Arith, x: Array, p: Params) -> Array:
    x = layer_norm(x, p["norm"])
    x = linear(ar, x, p["dense_1"])
    x = jax.nn.gelu(x, approximate=False)
    return linear(ar, x, p["dense_2"])


def cross_attention_layer(ar: Arith, x_q: Array, x_kv: Array, p: Params,
                          heads: int, pad_mask: Optional[Array]) -> Array:
    ca = p["cross_attention"]
    attn = attention(ar, layer_norm(x_q, ca["q_norm"]),
                     layer_norm(x_kv, ca["kv_norm"]), ca["attention"], heads,
                     pad_mask)
    x = attn + x_q
    return mlp(ar, x, p["mlp"]) + x


def self_attention_layer(ar: Arith, x: Array, p: Params, heads: int) -> Array:
    sa = p["self_attention"]
    h = layer_norm(x, sa["norm"])
    x = attention(ar, h, h, sa["attention"], heads, None) + x
    return mlp(ar, x, p["mlp"]) + x


def perceiver_layer(ar: Arith, latent: Array, x: Array, p: Params, sizes,
                    pad_mask: Optional[Array]) -> Array:
    latent = cross_attention_layer(ar, latent, x, p["cross_attention_layer"],
                                   sizes["num_cross_attention_heads"], pad_mask)
    block = p["self_attention_block"]
    layers = [block[f"layer_{i}"]
              for i in range(sizes["num_self_attention_layers_per_block"])]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

    def one(h, layer):
        return self_attention_layer(ar, h, layer, sizes["num_self_attention_heads"]), None

    # a scan, not an unrolled loop: same mathematics, a float32 program small
    # enough to compile quickly and to be kept in the compile cache
    latent, _ = jax.lax.scan(one, latent, stacked)
    return latent


def encoder(ar: Arith, x: Array, p: Params, sizes,
            pad_mask: Optional[Array]) -> Array:
    """Adapted input (B, M, C_in) -> latents (B, N, C). Layer 1 has its own
    weights; layers 2..num_layers apply ONE shared set."""
    b = x.shape[0]
    latent = jnp.broadcast_to(p["latent"], (b, *p["latent"].shape))
    latent = perceiver_layer(ar, latent, x, p["layer_1"], sizes, pad_mask)

    def shared(h, _):
        return perceiver_layer(ar, h, x, p["layer_n"], sizes, pad_mask), None

    latent, _ = jax.lax.scan(shared, latent, None,
                             length=sizes["num_encoder_layers"] - 1)
    return latent


def decoder(ar: Arith, latent: Array, p: Params, sizes) -> Array:
    """Latents -> (B, K, C_out) through the learned output-query array, then
    the linear head of the output adapter."""
    b = latent.shape[0]
    query = jnp.broadcast_to(p["output"], (b, *p["output"].shape))
    x = cross_attention_layer(ar, query, latent, p["cross_attention_layer"],
                              sizes["num_cross_attention_heads"], None)
    return linear(ar, x, p["output_adapter"]["linear"])


# -- adapters -----------------------------------------------------------------


def text_input(ids: Array, p: Params) -> Array:
    table = p["text_embedding"]["embedding"]
    c = table.shape[1]
    return jnp.take(table, ids, axis=0) * math.sqrt(c) + p["pos_encoding"][: ids.shape[1]]


def fourier_encodings(spatial_shape: Tuple[int, ...], bands: int) -> np.ndarray:
    """Perceiver position features: coordinates in [-1, 1], then per dim
    ``bands`` frequencies linearly spaced 1 .. size/2 as sin(pi f p) and
    cos(pi f p); order: positions, all sines, all cosines."""
    coords = [np.linspace(-1.0, 1.0, num=s, dtype=np.float32) for s in spatial_shape]
    pos = np.stack(np.meshgrid(*coords, indexing="ij"), axis=-1)
    grids = []
    for i, size in enumerate(spatial_shape):
        freqs = np.linspace(1.0, size / 2.0, num=bands, dtype=np.float32)
        grids.append(pos[..., i:i + 1] * freqs)
    feats = [pos]
    feats += [np.sin(np.float32(np.pi) * g) for g in grids]
    feats += [np.cos(np.float32(np.pi) * g) for g in grids]
    enc = np.concatenate(feats, axis=-1)
    return enc.reshape(-1, enc.shape[-1]).astype(np.float32)


def image_input(image: Array, bands: int) -> Array:
    b, *spatial, ch = image.shape
    enc = jnp.asarray(fourier_encodings(tuple(spatial), bands))
    x = image.reshape(b, -1, ch).astype(jnp.float32)
    return jnp.concatenate([x, jnp.broadcast_to(enc, (b, *enc.shape))], axis=-1)


# -- MLM masking --------------------------------------------------------------


def masking_key(rng: Array, step: int) -> Array:
    """The ``'masking'`` key of optimizer step ``step`` (0-based) as the
    system under test derives it from the train state's ``rng``."""
    from flax import linen as nn

    class _TopLevel(nn.Module):
        def __call__(self):
            return self.make_rng("masking")

    base = jax.random.fold_in(rng, step)
    key = jax.random.split(base, 2)[0]  # streams: ('masking', 'dropout')
    return _TopLevel().apply({}, rngs={"masking": key})


def mask_tokens(key: Array, ids: Array, pad_mask: Array, vocab_size: int,
                unk_id: int = 1, mask_id: int = 2, num_special: int = 3,
                mask_p: float = 0.15) -> Tuple[Array, Array]:
    """BERT-style corruption with the reference's nested draws: 15% of the
    non-special positions selected; 90% of those -> [MASK]; a ninth of THOSE
    -> a random non-special token. Labels are IGNORE off the selection."""
    k_sel, k_mask, k_rand, k_tok = jax.random.split(key, 4)
    shape = ids.shape
    candidate = ~((ids == unk_id) | pad_mask)
    selected = (jax.random.uniform(k_sel, shape) < mask_p) & candidate
    to_mask = selected & (jax.random.uniform(k_mask, shape) < 0.9)
    to_rand = to_mask & (jax.random.uniform(k_rand, shape) < 1.0 / 9.0)
    tokens = jax.random.randint(k_tok, shape, num_special, vocab_size, dtype=ids.dtype)
    out = jnp.where(to_mask, jnp.asarray(mask_id, ids.dtype), ids)
    out = jnp.where(to_rand, tokens, out)
    return out, jnp.where(selected, ids.astype(jnp.int32), IGNORE)


# -- losses (sums, so that row blocks add up) ----------------------------------


def _ce(logits: Array, labels: Array) -> Array:
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked


def mlm_logits(ar: Arith, params: Params, ids: Array, pad_mask: Array, sizes) -> Array:
    """Token ids as given (no corruption) -> (B, L, vocab) logits."""
    x = text_input(ids, params["encoder"]["input_adapter"])
    latent = encoder(ar, x, params["encoder"], sizes, pad_mask)
    return decoder(ar, latent, params["decoder"], sizes)[:, : ids.shape[1]]


def mlm_ce_sum(ar: Arith, params: Params, batch, sizes) -> Array:
    """Sum of cross-entropies over the selected positions of already
    corrupted rows: ``batch = {'token_ids': masked ids, 'pad_mask', 'labels'}``."""
    logits = mlm_logits(ar, params, batch["token_ids"], batch["pad_mask"], sizes)
    labels = batch["labels"]
    valid = labels != IGNORE
    ce = _ce(logits, jnp.where(valid, labels, 0))
    return jnp.where(valid, ce, 0.0).sum()


def classifier_logits(ar: Arith, params: Params, image: Array, sizes) -> Array:
    x = image_input(image, sizes["num_frequency_bands"])
    latent = encoder(ar, x, params["encoder"], sizes, None)
    return decoder(ar, latent, params["decoder"], sizes)[:, 0]


def classifier_ce_sum(ar: Arith, params: Params, batch, sizes) -> Array:
    return _ce(classifier_logits(ar, params, batch["image"], sizes), batch["label"]).sum()


def blocked_value_and_grad(ce_sum: Callable, block_rows: int) -> Callable:
    """``(params, batch, count) -> (mean loss, gradient)`` over a whole batch,
    computed ``block_rows`` rows at a time so that the float32 activations
    fit: the loss is a sum over rows divided by ``count``, so blocks add."""
    fn = jax.jit(jax.value_and_grad(ce_sum))

    def run(params: Params, batch, count: float) -> Tuple[Array, Params]:
        rows = len(next(iter(batch.values())))
        total, grads = None, None
        for lo in range(0, rows, block_rows):
            block = {k: v[lo:lo + block_rows] for k, v in batch.items()}
            value, g = fn(params, block)
            total = value if total is None else total + value
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        scale = 1.0 / count
        return total * scale, jax.tree.map(lambda g: g * scale, grads)

    return run


# -- optimizer ----------------------------------------------------------------


@jax.jit
def _adam_update(params, grads, mu, nu, t, lr, weight_decay):
    b1, b2, eps = 0.9, 0.999, 1e-8
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def step(p, m, n):
        return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + weight_decay * p)

    return jax.tree.map(step, params, mu, nu), mu, nu


def one_cycle_lr(step: int, total_steps: int, max_lr: float, pct_start: float = 0.1,
                 div_factor: float = 25.0, final_div_factor: float = 1e4) -> float:
    """The 1cycle policy (Smith & Topin) with ``torch.optim.lr_scheduler
    .OneCycleLR``'s defaults: cosine from max/25 up to max over the first
    ``pct_start`` of the steps, then cosine down to max/25/1e4."""
    initial, peak = max_lr / div_factor, max(pct_start * total_steps - 1.0, 1e-8)
    low, down = initial / final_div_factor, max(total_steps - 1.0 - peak, 1e-8)

    def cos(start, end, frac):
        return end + (start - end) * (1.0 + math.cos(math.pi * min(max(frac, 0.0), 1.0))) / 2.0

    if step <= peak:
        return cos(initial, max_lr, step / peak)
    return cos(max_lr, low, (step - peak) / down)


class Adam:
    """Adam (Kingma & Ba) with bias correction; ``weight_decay`` is AdamW's
    decoupled decay (Loshchilov & Hutter), 0 for plain Adam.
    ``learning_rate(step)`` gives the rate of 0-based optimizer step ``step``."""

    def __init__(self, params: Params, learning_rate: Callable[[int], float],
                 weight_decay: float = 0.0):
        self.lr, self.wd, self.t = learning_rate, weight_decay, 0
        self.mu = jax.tree.map(jnp.zeros_like, params)
        self.nu = jax.tree.map(jnp.zeros_like, params)

    def update(self, params: Params, grads: Params) -> Params:
        lr = self.lr(self.t)
        self.t += 1
        params, self.mu, self.nu = _adam_update(
            params, grads, self.mu, self.nu, jnp.float32(self.t),
            jnp.float32(lr), jnp.float32(self.wd))
        return params
