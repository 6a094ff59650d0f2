"""Sharding rules and the pjit-ed train step — the DDP replacement.

Recipe (the scaling-book flow): pick a mesh, annotate the params/opt-state and
batch shardings once, ``jax.jit`` the existing pure step function with those
shardings, and let XLA's SPMD partitioner insert the collectives (grad psum
over ``data``, all-gather/reduce-scatter for ``model``-sharded tensors,
softmax-stat psum over ``seq``-sharded attention).

Parameter rules are path-regex → PartitionSpec, applied to any params-shaped
tree — optimizer states (Adam's mu/nu mirror the param tree paths) pick up the
same specs automatically, which keeps ZeRO-style optimizer-state sharding one
rule-table away.

Tensor-parallel layout (Megatron-style pairing, per attention/MLP block):

- q/k/v projection kernels: output (head) dim over ``model`` → attention runs
  head-parallel; out-projection input dim over ``model`` closes the pair with
  one psum.
- MLP: dense_1 output and dense_2 input over ``model``.
- vocab-sized output projection (``linear/kernel``) over ``model`` — the
  (B, 512, vocab) MLM logits, the memory hot spot (SURVEY.md §3.1), never
  materialize unsharded; the CE softmax reduces over the sharded axis in-place.
"""

from __future__ import annotations

import contextlib
import re
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perceiver_io_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_SEQ,
    sequence_parallel_context,
    step_mesh_context,
)


# The bare-name "/"-joined path rendering the PARAM_RULES regexes match
# against — ONE definition shared with perceiver_io_tpu.quant (its scale
# map is keyed by the same rendering; see utils/treepath.py).
from perceiver_io_tpu.utils.treepath import simple_keystr as _simple_keystr

# (path regex, spec). First match wins; default is fully replicated.
PARAM_RULES: Sequence[Tuple[str, P]] = (
    (r"(q_proj|k_proj|v_proj)/kernel$", P(None, AXIS_MODEL)),
    (r"(q_proj|k_proj|v_proj)/bias$", P(AXIS_MODEL)),
    (r"out_proj/kernel$", P(AXIS_MODEL, None)),
    (r"dense_1/kernel$", P(None, AXIS_MODEL)),
    (r"dense_1/bias$", P(AXIS_MODEL)),
    (r"dense_2/kernel$", P(AXIS_MODEL, None)),
    (r"linear/kernel$", P(None, AXIS_MODEL)),
    (r"linear/bias$", P(AXIS_MODEL)),
)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _spec_fits(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> bool:
    """A spec is usable when every named axis divides its dimension.

    (XLA supports uneven sharding via padding, but for parameters we prefer
    clean replication over padded shards — e.g. a 10003-vocab projection on a
    tp=2 mesh stays replicated rather than padding every optimizer step.)
    """
    if len(spec) > len(shape):
        return False
    for dim, axis in zip(shape, spec):
        if axis is None:
            continue
        if dim % mesh.shape[axis] != 0:
            return False
    return True


def sharding_for_tree(tree: Any, mesh: Mesh, rules: Sequence[Tuple[str, P]] = PARAM_RULES):
    """NamedSharding tree for a params-shaped pytree by path-regex rules.

    Works on concrete arrays or ShapeDtypeStructs (use with ``jax.eval_shape``
    to plan shardings before allocating).
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def assign(path, leaf) -> NamedSharding:
        shape = getattr(leaf, "shape", ())
        name = _simple_keystr(path)
        for pat, spec in compiled:
            if pat.search(name):
                if _spec_fits(spec, shape, mesh):
                    return NamedSharding(mesh, spec)
                return replicated(mesh)
        return replicated(mesh)

    return jax.tree_util.tree_map_with_path(assign, tree)


def batch_pspecs(
    batch: Dict[str, Any], mesh: Mesh, shard_seq: bool = False,
    stacked: bool = False,
) -> Dict[str, P]:
    """PartitionSpecs for a batch dict: leading axis over ``data``, and
    optionally the sequence axis over ``seq`` — axis 1 for text tensors
    (token_ids/pad_mask) and for images/frames ('image': (B, H, W, C),
    'frames': (B, 2, H, W, C) → axis 2), whose first spatial axis maps
    contiguously onto the flattened input axis M = H·W the encoder consumes.

    Sequence sharding is the Perceiver sequence-parallel scheme: the encoder
    cross-attention KV stream (derived from these tensors) is sharded over
    ``seq`` while latents replicate — no ring required (SURVEY.md §5).

    ``stacked=True``: the batch leaves carry a leading scan axis of K
    per-step batches (multi-step dispatch, ``TrainerConfig
    .steps_per_dispatch``) — it stays unsharded and the usual specs apply
    one axis later.
    """
    seq_axis = AXIS_SEQ if shard_seq and mesh.shape[AXIS_SEQ] > 1 else None
    off = 1 if stacked else 0

    specs: Dict[str, P] = {}
    for key, value in batch.items():
        ndim = np.ndim(value) if not hasattr(value, "ndim") else value.ndim
        ndim -= off
        if key in ("token_ids", "pad_mask") and ndim >= 2:
            spec = (AXIS_DATA, seq_axis) + (None,) * (ndim - 2)
        elif key == "image" and ndim >= 3:
            spec = (AXIS_DATA, seq_axis) + (None,) * (ndim - 2)
        elif key == "frames" and ndim >= 4:
            spec = (AXIS_DATA, None, seq_axis) + (None,) * (ndim - 3)
        else:
            spec = (AXIS_DATA,) + (None,) * (ndim - 1)
        specs[key] = P(*(((None,) * off) + spec))
    return specs


def batch_shardings(
    batch: Dict[str, Any], mesh: Mesh, shard_seq: bool = False,
    stacked: bool = False,
):
    return {
        k: NamedSharding(mesh, spec)
        for k, spec in batch_pspecs(batch, mesh, shard_seq, stacked).items()
    }


# width of the coordination bitmask carried by the coord_flags channel
# (bit 0: preemption — training/trainer.py _PREEMPT_BIT; room to grow)
_COORD_FLAG_BITS = 8


def coord_flags_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of the ``(num_devices,)`` int32 coordination-flags vector —
    the multi-host agreement channel (``make_sharded_train_step(coord_flags=
    True)``): one element per device, every element of a host's shard
    holding that host's local flag bitmask. A host builds its slice with
    ``jax.make_array_from_process_local_data`` (all-equal values, so the
    device-order permutation inside the shard is irrelevant), and the step
    reduces the vector on device — the same all-reduce a ``psum`` would
    lower to — so the agreed value comes back replicated and bit-identical
    on every host, riding the training dispatch itself (no extra host
    round-trip, no side channel that could observe a different step)."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def _with_data_axis(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Add ``data`` over the first free, divisible dimension of ``spec``."""
    dp = mesh.shape[AXIS_DATA]
    if dp <= 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (dim, axis) in enumerate(zip(shape, entries)):
        if axis is None and dim % dp == 0:
            entries[i] = AXIS_DATA
            return P(*entries)
    return spec


def zero_state_shardings(state, mesh: Mesh, rules=PARAM_RULES,
                         params_too: bool = False):
    """ZeRO-style sharding plan: params follow the rules; OPTIMIZER-STATE
    leaves additionally shard over ``data``.

    SURVEY.md §2.3's "optimizer-state sharding on the data axis": Adam's
    mu/nu (2x the param bytes in f32) are pure per-parameter state, so each
    data-parallel rank can own a 1/dp slice — the per-chip optimizer
    footprint drops by dp, at the cost of one XLA-inserted all-gather of the
    (sharded) updates per step. With ``params_too=False`` params stay
    replicated (ZeRO-1/2 flavor): the forward/backward are untouched.

    ``params_too=True`` is the ZeRO-3/FSDP flavor: the PARAMS shard over
    ``data`` as well (on top of any ``model``-axis rule sharding). Nothing
    else changes — under ``jit`` GSPMD sees data-sharded parameter inputs
    feeding unsharded compute and inserts the all-gather-on-use in the
    forward/backward and the reduce-scatter on the gradients itself (the
    scaling-book recipe: FSDP is a sharding annotation, not an algorithm).
    Per-chip param+grad+opt residency drops by ~dp; the price is per-step
    gather/scatter collectives over ICI.

    Each leaf keeps any ``model``-axis sharding its param rule implies, and
    ``data`` is added over the first free divisible dimension (leaves with
    no data-divisible free dimension stay as ruled — e.g. tiny biases).
    """
    shardings = sharding_for_tree(state, mesh, rules)

    def add_data(path, leaf, sharding):
        name = _simple_keystr(path)
        shape = getattr(leaf, "shape", ())
        wanted = "opt_state" in name or (params_too and name.startswith("params"))
        if not wanted or len(shape) == 0:
            return sharding
        if hasattr(leaf, "dtype") and jax.dtypes.issubdtype(
            leaf.dtype, jax.dtypes.prng_key
        ):
            return sharding
        return NamedSharding(mesh, _with_data_axis(sharding.spec, shape, mesh))

    return jax.tree_util.tree_map_with_path(add_data, state, shardings)


def _place_tree(tree: Any, shardings: Any):
    """Place host-resident values onto (possibly multi-process) shardings.

    Single-process: plain ``device_put``. Multi-process: ``device_put``
    rejects shardings spanning non-addressable devices, so each process
    materializes only its addressable shards via ``make_array_from_callback``
    — every host holds an identical full copy (the standard replicated-init
    contract), and the callback slices this host's pieces out of it. Typed
    PRNG-key leaves carry an extended dtype the callback path can't build
    directly; they round-trip through their uint32 key data.
    """
    if jax.process_count() == 1:
        return jax.device_put(tree, shardings)

    def place(x, s):
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            data = jax.random.key_data(x)
            placed = jax.make_array_from_callback(
                data.shape, s, lambda idx, d=np.asarray(data): d[idx]
            )
            return jax.random.wrap_key_data(placed, impl=jax.random.key_impl(x))
        arr = np.asarray(x)
        return jax.make_array_from_callback(arr.shape, s, lambda idx: arr[idx])

    return jax.tree.map(place, tree, shardings)


def shard_train_state(state, mesh: Mesh, rules=PARAM_RULES, zero_opt=False):
    """Place an existing TrainState onto the mesh per the rules.

    Params and optimizer state follow the same path rules (mu/nu mirror the
    param paths); scalars and rng keys replicate. ``zero_opt=True`` shards
    the optimizer state over ``data``; ``zero_opt='params'`` additionally
    shards the PARAMS over ``data`` (ZeRO-3/FSDP flavor — see
    :func:`zero_state_shardings`).
    """
    if zero_opt:
        if mesh.shape[AXIS_DATA] <= 1:
            import warnings

            warnings.warn(
                "zero_opt requested but the mesh has data=1 — optimizer-state "
                "sharding divides by the data-parallel size, so this is a "
                "no-op; increase dp to save memory",
                stacklevel=2,
            )
        shardings = zero_state_shardings(
            state, mesh, rules, params_too=zero_opt == "params"
        )
    else:
        shardings = sharding_for_tree(state, mesh, rules)
    return _place_tree(state, shardings), shardings


def reresolve_shardings(tree: Any, old_mesh: Mesh, new_mesh: Mesh,
                        rules=PARAM_RULES):
    """Re-resolve the path-regex rules against a NEW mesh (elastic resize).

    An elastic shrink/grow rebuilds the mesh with a different device count;
    the RULES are mesh-independent, so the plan for the new world is just
    :func:`sharding_for_tree` over the new mesh — but a spec that fit the
    old axis sizes can silently degrade to replication on the new ones
    (``_spec_fits``: e.g. a ``model``-sharded 6-wide head dim on tp=3 after
    a tp=2 generation). Degradation is LEGAL — the state stays correct,
    just bigger per chip — but an operator resizing a memory-tight job must
    hear about it, so this returns ``(shardings, degraded)`` where
    ``degraded`` lists the "/"-joined paths whose rule spec applied on
    ``old_mesh`` but falls back to replicated on ``new_mesh``.
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    degraded = []

    def check(path, leaf):
        shape = getattr(leaf, "shape", ())
        name = _simple_keystr(path)
        for pat, spec in compiled:
            if pat.search(name):
                if (_spec_fits(spec, shape, old_mesh)
                        and not _spec_fits(spec, shape, new_mesh)):
                    degraded.append(name)
                return
        return

    jax.tree_util.tree_map_with_path(check, tree)
    return sharding_for_tree(tree, new_mesh, rules), sorted(degraded)


def sp_gradient_canary(mesh: Mesh, axis: str = AXIS_SEQ) -> None:
    """One tiny known-gradient probe through the sequence-parallel kernel.

    ``_sp_bwd`` (ops/pallas_attention.py) compensates for shard_map's
    check_rep=False transpose convention as observed on the pinned JAX
    version — an UNDOCUMENTED contract: a future JAX upgrade could change it
    silently, leaving the forward exact but every gradient scaled by the
    product of some mesh axis sizes. This probe turns that silent rescale
    into a loud failure at trainer setup: it differentiates a sum-of-squares
    loss through :func:`seq_parallel_fused_attention` on throwaway inputs
    and checks dq/dk/dv against the analytic XLA formula computed locally.
    Cost: one tiny shard_map compile (~seconds), once per
    ``make_sharded_train_step(shard_seq=True)``.
    """
    from perceiver_io_tpu.ops.pallas_attention import (
        seq_parallel_fused_attention,
    )

    if jax.process_count() > 1:
        # the probe runs eagerly with host-local arrays, which cannot feed a
        # shard_map over a non-fully-addressable (multi-host) mesh; the
        # convention it guards is per-JAX-build, not per-topology, so the
        # single-controller probe in CI / single-host runs is the coverage
        return
    cache_key = (tuple(sorted(mesh.shape.items())), axis, jax.default_backend())
    if cache_key in _SP_CANARY_OK:
        return
    n = int(mesh.shape[axis])
    b, t, s, h, d = 1, 8, 16 * n, 1, 8
    keys = jax.random.split(jax.random.key(1234), 3)
    q = jax.random.normal(keys[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, h, d), jnp.float32)

    # the kernel's f32 path is multi-pass; XLA's DEFAULT f32 matmul on a TPU
    # is one bf16 pass, 3e-3 away — enough to trip this probe on real chips
    # (median ratio 1.003 on a 2x2 v5e, PR 22) though the convention it
    # guards shows up as a factor of 2 or more. Same precision both sides.
    exact = jax.lax.Precision.HIGHEST

    def ref_loss(q, k, v):
        logits = jnp.einsum("bthd,bshd->bhts", q * (d ** -0.5), k,
                            precision=exact)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.sum(
            jnp.einsum("bhts,bshd->bthd", probs, v, precision=exact) ** 2)

    def sp_loss(q, k, v):
        out = seq_parallel_fused_attention(q, k, v, mesh=mesh, axis=axis)
        return jnp.sum(out ** 2)

    ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(sp_loss, argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip(("dq", "dk", "dv"), ref, got):
        r, g = np.asarray(r), np.asarray(g)
        if not np.allclose(g, r, atol=1e-3, rtol=1e-3):
            denom = np.abs(r) + 1e-12
            ratio = float(np.median(np.abs(g) / denom))
            raise RuntimeError(
                f"sequence-parallel gradient canary FAILED on {name}: the "
                f"shard_map transpose convention _sp_bwd compensates for "
                f"(ops/pallas_attention.py) no longer matches this JAX "
                f"version — median |got|/|expected| = {ratio:.4g} on mesh "
                f"{dict(mesh.shape)}. Re-derive the psum scaling in _sp_bwd "
                f"before training under --shard_seq."
            )
    _SP_CANARY_OK.add(cache_key)


# meshes (by axis sizes + backend) whose canary already passed this process —
# the convention is a property of the JAX build, not of a particular Mesh
# object, so one probe per topology is enough
_SP_CANARY_OK: set = set()


def make_sharded_train_step(
    train_step,
    mesh: Mesh,
    state,
    example_batch: Dict[str, Any],
    rules=PARAM_RULES,
    shard_seq: bool = False,
    donate_state: bool = True,
    zero_opt=False,  # False | True (opt-state over data) | 'params' (ZeRO-3)
    stacked: bool = False,
    coord_flags: bool = False,
):
    """jit the pure ``(state, batch) → (state, metrics)`` step with explicit
    in/out shardings over the mesh. Returns ``(step_fn, sharded_state,
    batch_shardings)``.

    The example batch's keys define the step's input contract: loader batches
    may carry extra keys (e.g. ``label`` on an MLM batch) — the returned step
    selects only the contracted keys, so loader output feeds in directly.
    Batches can be host numpy (dispatch places them per the shardings) or
    pre-placed via ``jax.device_put(batch, batch_shardings)``.

    ``coord_flags=True`` grows the step a third input — the
    :func:`coord_flags_sharding` ``(num_devices,)`` int32 vector of per-host
    flag bitmasks — and a ``metrics['coord_flags']`` output scalar holding
    the fleet-wide OR (a bitwise-or reduce over the sharded vector, which GSPMD
    lowers to the cross-host all-reduce a psum would use). The trainer's
    multi-host preemption agreement rides this channel; the returned step
    then has signature ``(state, batch, flags)`` and exposes the flags
    sharding as ``step.coord_flags_sharding``.
    """
    keys = tuple(sorted(example_batch))
    sharded_state, state_shardings = shard_train_state(state, mesh, rules, zero_opt=zero_opt)
    b_shardings = batch_shardings(example_batch, mesh, shard_seq, stacked)

    sp = shard_seq and mesh.shape[AXIS_SEQ] > 1
    if sp:
        # Runtime canary (VERDICT r3 item 6): fail loudly AT SETUP if a JAX
        # upgrade changed the shard_map transpose convention _sp_bwd encodes,
        # instead of training with silently rescaled gradients.
        sp_gradient_canary(mesh)
    inner_step = train_step

    def train_step(state, batch):  # noqa: F811 — deliberate rebind
        # Every (re)trace sees the mesh it is traced for (per-device byte
        # reckoning in the model) and, under shard_seq, sequence-parallel
        # kernel routing: the encoder cross-attention (seq_shard_kv) then
        # runs its Pallas path under shard_map with S/n KV per device instead
        # of letting GSPMD all-gather the stream around the pallas_call.
        with step_mesh_context(mesh), (
                sequence_parallel_context(mesh) if sp
                else contextlib.nullcontext()):
            return inner_step(state, batch)

    if coord_flags:
        flags_sharding = coord_flags_sharding(mesh)
        base_step = train_step

        def coordinated(state, batch, flags):
            new_state, metrics = base_step(state, batch)
            metrics = dict(metrics)
            # fleet-wide OR of the per-host bitmasks, replicated everywhere.
            # A plain max would drop bits once two hosts raise DIFFERENT
            # bits, and XLA's cross-device reduce has no integer `or` — so
            # OR = per-bit any = per-bit MAX, recombined (8 flag bits).
            bit_positions = jnp.arange(_COORD_FLAG_BITS, dtype=jnp.int32)
            bits = (flags[:, None] >> bit_positions) & 1
            metrics["coord_flags"] = jnp.sum(
                jnp.max(bits, axis=0) << bit_positions, dtype=jnp.int32)
            return new_state, metrics

        jitted = jax.jit(
            coordinated,
            in_shardings=(state_shardings, b_shardings, flags_sharding),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate_state else (),
        )

        def step(state, batch, flags):
            return jitted(state, {k: batch[k] for k in keys}, flags)

        step.coord_flags_sharding = flags_sharding
    else:
        jitted = jax.jit(
            train_step,
            in_shardings=(state_shardings, b_shardings),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate_state else (),
        )

        def step(state, batch):
            return jitted(state, {k: batch[k] for k in keys})

        step.coord_flags_sharding = None

    # expose the underlying jit wrapper for lowering/cost-analysis reuse
    step.jitted = jitted
    return step, sharded_state, b_shardings
