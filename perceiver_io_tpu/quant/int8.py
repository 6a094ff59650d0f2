"""Weight-only int8 quantization for the HBM-bound serving path.

The measured inference roofline (PERF.md, `tools/hbm_roofline.py`) puts the
binding resource of this workload on HBM param/elementwise streams, not MXU
FLOPs — every serving micro-batch re-streams the full weight set from HBM.
Weight-only quantization (LLM.int8(), AWQ) attacks exactly that term: store
the matmul weights as int8 with per-channel f32 scales (~4x fewer weight
bytes than f32, ~2x fewer than bf16) and dequantize at apply time, INSIDE
the jitted program, so XLA fuses the ``convert * scale`` into the matmul
operand read and the f32/bf16 copy never round-trips HBM. Compute stays in
the model's compute dtype — this is storage quantization, not int8 matmuls.

Scheme: **per-channel symmetric int8.** For a kernel ``(in, out)`` each
OUTPUT channel ``j`` gets ``scale[j] = max|w[:, j]| / 127`` (f32) and
``q[:, j] = round(w[:, j] / scale[j])`` clipped to ±127; dequantization is
``q * scale`` — elementwise error is bounded by ``scale/2``. Symmetric (no
zero point) keeps dequant a single fused multiply; per-channel (rather than
per-tensor) scales keep the quantization grid matched to each output
column's dynamic range, which is what holds the end-to-end parity error to
the documented bound (see PERF.md §Quantization).

Policy: quantize the **streamed** weights — 2-D leaves whose path ends in
``kernel`` (every q/k/v/out_proj, MLP dense_1/dense_2, and the vocab-sized
head ``linear/kernel``, the single biggest param tensor). GATHERED tables
(``text_embedding/embedding``, the learned latent/output query arrays,
``pos_encoding``) stay in compute dtype: a gather touches only the rows it
reads, while a tree-level dequant would rebuild the full table every
dispatch — quantizing them would ADD traffic on the HBM-bound path, not
remove it. Biases and LayerNorm params are 1-D noise.

Below int8: **grouped int4** (``bits=4``). Per-channel int4 loses too much
grid resolution on kernels with wide per-column dynamic range, so int4
scales are per ``(group_size x column)`` block — ``scale[g, j]`` covers rows
``[g*group_size, (g+1)*group_size)`` of column ``j`` (AWQ-style grouping;
group_size=128 default). Kernels whose fan-in is not a multiple of
``group_size`` fall back to per-channel scales for that leaf (documented,
deterministic — the parity bound covers both). Storage is ``jnp.int4``
(packed 2/byte on TPU; predicted bytes account it at 0.5 B/elem).

Kernel-path transport: :class:`QKernel` is a registered pytree node that
carries ``(q, scale)`` *through* the model's param tree in place of a
kernel leaf, so the fused dequant-matmul kernel (``ops/pallas_matmul.py``)
can stream the int8/int4 bytes instead of a pre-dequantized tensor.
:func:`kernel_operands` builds that operand tree INSIDE the serving jit;
``linear_apply`` at the ``_LinearParams`` sites dispatches on it. Flax param
retrieval only reads ``.shape`` off the leaf, which QKernel provides.

Tree contract (the invariant everything else leans on): the quantized
``values`` tree has EXACTLY the key paths of the source f32 tree — int8
leaves replace f32 kernels in place, scales ride in a separate flat
``{path: (out,) f32}`` map. Checkpoints stay f32 on disk (quantize at
load); ``parallel/sharding.py`` path-regex rules resolve against
``QuantizedParams.values`` unchanged (same paths, same shapes), and the
torch-parity param names are untouched. The apply-time dequant feeds the
existing ``_LinearParams`` fusion sites in ``ops/attention.py`` and the
adapter projections in ``models/`` exactly the tensors they would have read
from an f32 tree — the model code never sees an int8 array.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The canonical key-path rendering — the SAME one parallel/sharding.py
# matches PARAM_RULES against; the scale map is keyed by it.
from perceiver_io_tpu.utils.treepath import simple_keystr as _simple_keystr

# Path regexes selecting the leaves to quantize (first match wins, like
# parallel/sharding.PARAM_RULES — and deliberately a SUBSET of the paths
# those rules shard: every quantized leaf keeps its sharding rule, because
# the int8 tree re-uses the f32 tree's paths and shapes verbatim).
DEFAULT_QUANT_RULES: Sequence[str] = (r"kernel$",)

_QMAX = 127.0  # symmetric int8: [-127, 127]; -128 unused (no zero point)
_QMAX4 = 7.0   # symmetric int4: [-7, 7]; -8 unused (no zero point)
DEFAULT_GROUP_SIZE = 128  # int4 default: one scale per 128-row column block


def quantize_array(
    w, bits: int = 8, group_size: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric quantization over the LAST axis: ``(q, scale f32)``.

    ``bits=8`` (default): per-channel, ``scale`` shaped like the last
    dimension; ``q`` is int8. ``bits=4``: values live in [-7, 7] (returned
    as int8 on host — callers cast to ``jnp.int4`` for storage). With
    ``group_size`` on a 2-D ``(in, out)`` kernel whose fan-in divides
    evenly, ``scale`` is ``(in // group_size, out)`` — one scale per
    column-block; otherwise per-channel. Runs on host numpy — this is
    one-time load work, not step work."""
    w = np.asarray(w, np.float32)
    if w.ndim < 1:
        raise ValueError("quantize_array needs at least one axis")
    if bits not in (8, 4):
        raise ValueError(f"unsupported bits={bits}; expected 8 or 4")
    qmax = _QMAX if bits == 8 else _QMAX4
    if group_size and w.ndim == 2 and w.shape[0] % group_size == 0:
        g = w.shape[0] // group_size
        wg = w.reshape(g, group_size, w.shape[1])
        amax = np.max(np.abs(wg), axis=1)  # (g, out)
        scale = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
        q = np.clip(np.rint(wg / scale[:, None, :]), -qmax, qmax)
        return q.reshape(w.shape).astype(np.int8), scale
    amax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
    # an all-zero channel quantizes to zeros under any scale; 1.0 avoids /0
    scale = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -qmax, qmax).astype(np.int8)
    return q, scale


def dequantize_array(q, scale, dtype) -> jax.Array:
    """``q * scale`` in f32, cast to the compute dtype. Traced inside the
    serving jit: XLA fuses the convert+multiply into the consuming matmul's
    operand read, so HBM streams the int8 bytes, not a materialized copy.
    A 2-D ``scale`` on a 2-D ``q`` means grouped scales: row block ``g`` of
    column ``j`` dequantizes by ``scale[g, j]``."""
    if getattr(scale, "ndim", 0) == 2 and q.ndim == 2:
        g = scale.shape[0]
        gs = q.shape[0] // g
        wf = q.astype(jnp.float32).reshape(g, gs, q.shape[1])
        return (wf * scale[:, None, :]).reshape(q.shape).astype(dtype)
    return (q.astype(jnp.float32) * scale).astype(dtype)


@jax.tree_util.register_pytree_with_keys_class
class QuantizedParams:
    """A params-shaped pytree of int8 weights + their per-channel scales.

    ``values`` mirrors the source tree's key paths exactly (int8 leaves at
    quantized paths, compute-dtype leaves elsewhere); ``scales`` is a flat
    ``{path: (out,) f32}`` dict keyed by the same ``/``-joined path strings
    the sharding rules match. ``compute_dtype`` (static aux data) names the
    dtype :func:`dequantize_tree` reconstructs.
    """

    __slots__ = ("values", "scales", "compute_dtype", "bits", "group_size")

    def __init__(self, values: Any, scales: Dict[str, Any], compute_dtype: str,
                 bits: int = 8, group_size: Optional[int] = None):
        self.values = values
        self.scales = scales
        self.compute_dtype = compute_dtype
        self.bits = bits
        self.group_size = group_size

    def tree_flatten_with_keys(self):
        return (
            (
                (jax.tree_util.GetAttrKey("values"), self.values),
                (jax.tree_util.GetAttrKey("scales"), self.scales),
            ),
            (self.compute_dtype, self.bits, self.group_size),
        )

    @classmethod
    def tree_unflatten(cls, aux_data, children):
        # pre-r24 aux was the bare compute_dtype string — accept both so
        # trees pickled/flattened under the old layout still unflatten
        if isinstance(aux_data, tuple):
            compute_dtype, bits, group_size = aux_data
        else:
            compute_dtype, bits, group_size = aux_data, 8, None
        return cls(children[0], children[1], compute_dtype, bits, group_size)

    @property
    def mode(self) -> str:
        """The engine-facing quantize mode string: ``'int8'`` or ``'int4'``."""
        return "int8" if self.bits == 8 else "int4"

    def __repr__(self) -> str:
        return (
            f"QuantizedParams({len(self.scales)} {self.mode} leaves, "
            f"compute_dtype={self.compute_dtype!r}, "
            f"group_size={self.group_size})"
        )


@jax.tree_util.register_pytree_with_keys_class
class QKernel:
    """A quantized kernel leaf travelling through a params-shaped tree.

    Carries ``(q, scale)`` to a ``linear_apply`` site so the fused
    dequant-matmul kernel can stream the int8/int4 bytes itself instead of
    receiving a pre-dequantized tensor. Registered as a pytree node (jit
    boundaries flatten it into its arrays); exposes ``.shape/.ndim/.dtype``
    mirroring the dequantized kernel so flax's param retrieval — which only
    inspects the leaf's shape — passes it through untouched. ``x @ qkernel``
    dispatches into the fused kernel via ``__rmatmul__`` (so generic
    apply_fns handed to ``ServingEngine`` keep working on a quantized
    tree); any OTHER array op receiving one fails loudly on the first use —
    deliberate containment, not a supported path.
    """

    __slots__ = ("q", "scale", "compute_dtype")

    def __init__(self, q: Any, scale: Any, compute_dtype: str):
        self.q = q
        self.scale = scale
        self.compute_dtype = compute_dtype

    def tree_flatten_with_keys(self):
        return (
            (
                (jax.tree_util.GetAttrKey("q"), self.q),
                (jax.tree_util.GetAttrKey("scale"), self.scale),
            ),
            self.compute_dtype,
        )

    @classmethod
    def tree_unflatten(cls, aux_data, children):
        return cls(children[0], children[1], aux_data)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def group_size(self) -> Optional[int]:
        """Rows per scale group, or None for per-channel scales — derived
        from the array shapes so it stays correct under tracing."""
        if getattr(self.scale, "ndim", 1) == 2 and self.q.ndim == 2:
            return self.q.shape[0] // self.scale.shape[0]
        return None

    def dequantize(self) -> jax.Array:
        return dequantize_array(self.q, self.scale, jnp.dtype(self.compute_dtype))

    def __rmatmul__(self, x):
        # `x @ qkernel` IS a linear-apply site in the x·W convention — route
        # it through the fused dequant-matmul dispatch (local import: the
        # kernel module imports QKernel at top level)
        from perceiver_io_tpu.ops.pallas_matmul import quantized_matmul

        return quantized_matmul(x, self)

    def __repr__(self) -> str:
        return (
            f"QKernel({getattr(self.q, 'shape', '?')}, "
            f"{getattr(self.q, 'dtype', '?')}, gs={self.group_size})"
        )


def is_quantized(tree: Any) -> bool:
    """True for a tree already prepared by :func:`quantize_tree` (the
    engine's skip-requantization check when one quantized copy is shared by
    several engines, e.g. ``MLMServer``'s three program families)."""
    return isinstance(tree, QuantizedParams)


def quantize_tree(
    params: Any,
    compute_dtype: str = "float32",
    rules: Sequence[str] = DEFAULT_QUANT_RULES,
    bits: int = 8,
    group_size: Optional[int] = None,
) -> QuantizedParams:
    """Quantize a params tree for int8w/int4w serving.

    Leaves matching ``rules`` (2-D floating ``kernel`` tensors by default)
    become int8 (or int4 with ``bits=4``) with f32 scales computed FROM THE
    f32 SOURCE (never from an already-rounded bf16 copy); every other
    floating leaf is cast to ``compute_dtype`` (the same cast the bf16
    serving path applies). Key paths, shapes, and tree structure are
    preserved exactly. ``bits=4`` defaults to grouped scales
    (``group_size=128``); kernels whose fan-in is indivisible fall back to
    per-channel for that leaf.
    """
    compute_dtype = str(jnp.dtype(compute_dtype))
    if bits == 4 and group_size is None:
        group_size = DEFAULT_GROUP_SIZE
    compiled = [re.compile(p) for p in rules]
    scales: Dict[str, Any] = {}
    store_dtype = jnp.int8 if bits == 8 else jnp.int4

    def convert(path, leaf):
        name = _simple_keystr(path)
        # dtype inspection must not touch the device (jnp.asarray would
        # transfer every leaf just to read .dtype)
        if not hasattr(leaf, "dtype"):
            leaf = np.asarray(leaf)
        is_float = jnp.issubdtype(leaf.dtype, jnp.floating)
        if (
            is_float
            and getattr(leaf, "ndim", 0) == 2
            and any(p.search(name) for p in compiled)
        ):
            q, scale = quantize_array(leaf, bits=bits, group_size=group_size)
            scales[name] = jnp.asarray(scale)
            return jnp.asarray(q, dtype=store_dtype)
        # every leaf leaves here host-made and uncommitted, like the
        # quantized kernels above: a tree restored from a checkpoint keeps
        # the sharding it was SAVED with (every device of a multi-device
        # trainer), and a program cannot mix leaves committed there with
        # kernels made here — the engine's device_put places the whole tree
        leaf = np.asarray(leaf)
        return leaf.astype(compute_dtype) if is_float else leaf

    values = jax.tree_util.tree_map_with_path(convert, params)
    if not scales:
        raise ValueError(
            "quantize_tree found no quantizable leaves — expected at least "
            f"one 2-D floating leaf matching {list(rules)}"
        )
    return QuantizedParams(values, scales, compute_dtype, bits, group_size)


def dequantize_tree(qparams: QuantizedParams) -> Any:
    """Reconstruct a compute-dtype params tree from a quantized one.

    Call this INSIDE the jitted serving forward (``jax.jit(lambda qp, *x:
    apply(dequantize_tree(qp), *x))``): dequantized kernels are then
    fusion-local intermediates feeding the ``_LinearParams`` sites, and the
    program's weight HBM traffic is the int8 bytes. Calling it eagerly
    outside jit materializes full-size copies and forfeits the win.
    """
    if not is_quantized(qparams):
        raise TypeError(f"expected QuantizedParams, got {type(qparams).__name__}")
    dtype = jnp.dtype(qparams.compute_dtype)

    def deq(path, leaf):
        scale = qparams.scales.get(_simple_keystr(path))
        if scale is None:
            return leaf
        return dequantize_array(leaf, scale, dtype)

    return jax.tree_util.tree_map_with_path(deq, qparams.values)


def kernel_operands(qparams: QuantizedParams) -> Any:
    """Build the kernel-path operand tree: quantized leaves become
    :class:`QKernel` nodes (int bytes + scale travelling together), every
    other leaf passes through. Call this INSIDE the serving jit in place of
    :func:`dequantize_tree` — ``linear_apply`` at the ``_LinearParams``
    sites then dispatches each QKernel to the fused dequant-matmul, and the
    program's weight HBM traffic is the int8/int4 bytes with the
    convert×scale applied in VMEM per tile."""
    if not is_quantized(qparams):
        raise TypeError(f"expected QuantizedParams, got {type(qparams).__name__}")
    dtype = str(jnp.dtype(qparams.compute_dtype))

    def conv(path, leaf):
        scale = qparams.scales.get(_simple_keystr(path))
        if scale is None:
            return leaf
        return QKernel(leaf, scale, dtype)

    return jax.tree_util.tree_map_with_path(conv, qparams.values)


def apply_operands(params: Any) -> Any:
    """The engines' one-line unwrap: quantized trees become QKernel operand
    trees (kernel path), anything else passes through unchanged. Safe to
    call at the top of every jitted forward."""
    return kernel_operands(params) if is_quantized(params) else params


def _leaf_bytes(leaf) -> int:
    n = int(np.prod(leaf.shape))
    if jnp.dtype(leaf.dtype) == jnp.dtype(jnp.int4):
        # ml_dtypes int4 reports itemsize 1 on host; TPU HBM packs 2/byte —
        # predicted-bytes accounting uses the packed figure (not yet
        # checked against a device trace, PERF.md)
        return (n + 1) // 2
    return n * jnp.dtype(leaf.dtype).itemsize


def tree_bytes(tree: Any) -> int:
    """Total parameter bytes of a pytree (``QuantizedParams`` included —
    its scales count; they are streamed with the weights). int4 leaves
    count at the packed 0.5 B/element."""
    return sum(
        _leaf_bytes(leaf)
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
    )


def bytes_summary(params: Any, qparams: Optional[QuantizedParams] = None,
                  compute_dtype: str = "bfloat16") -> Dict[str, Any]:
    """Predicted per-dispatch weight-stream accounting for the quant bench.

    Every serving dispatch streams the full weight set once, so the
    predicted bytes-per-dispatch ratio IS the byte ratio of the trees:
    ``int8w_bytes / cast_bytes`` (the bf16-vs-int8w A/B's roofline
    prediction, checked against the device trace on TPU).
    """
    if qparams is None:
        qparams = quantize_tree(params, compute_dtype=compute_dtype)
    itemsize = jnp.dtype(compute_dtype).itemsize

    def leaf_cast_bytes(leaf):
        if not hasattr(leaf, "dtype"):  # python scalars — host-only inspect
            leaf = np.asarray(leaf)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return int(np.prod(leaf.shape)) * itemsize
        return int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize

    cast_bytes = sum(
        leaf_cast_bytes(leaf) for leaf in jax.tree_util.tree_leaves(params)
    )
    f32_bytes = tree_bytes(params)
    q_bytes = tree_bytes(qparams)
    return {
        "param_bytes_f32": f32_bytes,
        f"param_bytes_{jnp.dtype(compute_dtype)}": cast_bytes,
        f"param_bytes_{qparams.mode}w": q_bytes,
        "quantized_leaves": len(qparams.scales),
        "predicted_weight_stream_ratio": round(q_bytes / cast_bytes, 4),
    }
