"""Device-mesh construction — the framework's distributed-communication layer.

The reference distributes exclusively through Lightning's DDP plugin over NCCL
(reference ``train_mlm.py:68``, ``train_seq_clf.py:30``, ``train_img_clf.py:19``);
here distribution is a single SPMD program over one ``jax.sharding.Mesh``:
gradient synchronization, sequence-parallel softmax reductions and
tensor-parallel activation exchanges all become XLA collectives riding ICI
(intra-slice) / DCN (inter-slice) — there is no user-facing communication API,
only mesh + sharding construction.

Axes:

- ``data``  — batch-dim sharding (the DDP replacement; grads psum over this axis),
- ``model`` — tensor parallelism (attention heads / MLP width / vocab dims),
- ``seq``   — sequence/context parallelism for long inputs M: the encoder's
  cross-attention KV stream is sharded over this axis while the small latent
  array stays replicated, so the softmax over M runs as partial reductions +
  psum — Perceiver's architectural alternative to ring attention (SURVEY.md §5).

Multi-host: call ``initialize_distributed()`` once per process before mesh
construction; ``jax.devices()`` then spans all hosts and every host feeds its
own data shard (``data/pipeline.py`` shard_id/num_shards).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"

MESH_AXES = (AXIS_DATA, AXIS_MODEL, AXIS_SEQ)


@dataclasses.dataclass(frozen=True)
class SequenceParallelContext:
    """An active sequence-parallel regime: which mesh axis carries KV shards.

    Attention layers whose KV stream is the seq-sharded input (the encoder
    cross-attention — ``seq_shard_kv=True`` in ``ops.attention``) read this at
    trace time to route the kernel path through
    ``seq_parallel_fused_attention`` instead of letting GSPMD all-gather the
    KV stream around the ``pallas_call`` (the failure mode documented on that
    op: under plain jit the O(S/n) memory benefit of sharding M is lost
    exactly where it matters).
    """

    mesh: Mesh
    axis: str = AXIS_SEQ
    batch_axis: Optional[str] = AXIS_DATA
    # head (tensor-parallel) axis: attention passes it through when the head
    # count divides the axis size, so tp meshes keep heads sharded inside the
    # shard_map instead of all-gathering them
    head_axis: Optional[str] = AXIS_MODEL


_ACTIVE_SP: contextvars.ContextVar[Optional[SequenceParallelContext]] = (
    contextvars.ContextVar("perceiver_io_tpu_sequence_parallel", default=None)
)


@contextlib.contextmanager
def sequence_parallel_context(
    mesh: Mesh, axis: str = AXIS_SEQ, batch_axis: Optional[str] = AXIS_DATA
):
    """Activate sequence-parallel kernel routing while tracing a step.

    ``make_sharded_train_step(shard_seq=True)`` (and the Trainer, for its eval
    step) wrap the step function with this, so any retrace — first call,
    new shapes, scanned multi-step dispatch — sees the regime. A mesh whose
    ``axis`` has size 1 deactivates routing (nothing to shard)."""
    if mesh.shape.get(axis, 1) <= 1:
        yield
        return
    token = _ACTIVE_SP.set(SequenceParallelContext(mesh, axis, batch_axis))
    try:
        yield
    finally:
        _ACTIVE_SP.reset(token)


def active_sequence_parallel() -> Optional[SequenceParallelContext]:
    """The active :class:`SequenceParallelContext`, or None."""
    return _ACTIVE_SP.get()


_STEP_MESH: contextvars.ContextVar[Optional[Mesh]] = (
    contextvars.ContextVar("perceiver_io_tpu_step_mesh", default=None)
)


@contextlib.contextmanager
def step_mesh_context(mesh: Mesh):
    """Name the mesh a step is traced for. ``jax.jit(in_shardings=...)`` shows
    the traced code global shapes only; ``make_sharded_train_step`` wraps the
    step with this so that model code which reckons bytes per device at trace
    time (the encoder's rematerialization policy) can ask how many devices
    share the batch."""
    token = _STEP_MESH.set(mesh)
    try:
        yield
    finally:
        _STEP_MESH.reset(token)


def active_step_mesh() -> Optional[Mesh]:
    """The mesh of :func:`step_mesh_context`, or None (plain ``jax.jit``)."""
    return _STEP_MESH.get()


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up: ``jax.distributed.initialize`` (auto-detected on
    TPU pods; explicit coordinator for manual launches). Safe to skip on a
    single host. CPU-backend multi-process collectives need nothing here:
    jax 0.9.0 selects gloo by default."""
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


@dataclasses.dataclass(frozen=True)
class WorldDescriptor:
    """A re-initializable view of the multi-host world (elastic training).

    ``ranks`` are the COORDINATION node ids of the member processes — the id
    each process registered with on the coordinator, fixed for the process's
    lifetime even as the world shrinks and grows around it. What jax sees is
    the DENSE per-generation view: ``process_id = ranks.index(node_id)`` and
    ``num_processes = len(ranks)``. Keeping the two spaces separate is what
    lets a generation-2 world of survivors ``(0, 1, 3)`` present itself to
    jax as a clean 3-process job while KV-store rendezvous keys, heartbeat
    namespaces and buddy assignments keep using the stable node ids.

    ``generation`` increments on every resize (shrink, grow, or a retried
    resize after a mid-resize death) and namespaces all rendezvous state, so
    a straggler from generation N can never consume generation N+1's keys.
    """

    generation: int
    ranks: Tuple[int, ...]
    node_id: int

    def __post_init__(self):
        object.__setattr__(self, "ranks", tuple(sorted(set(self.ranks))))
        if self.node_id not in self.ranks:
            raise ValueError(
                f"node_id {self.node_id} not a member of ranks {self.ranks}")

    @property
    def process_id(self) -> int:
        return self.ranks.index(self.node_id)

    @property
    def num_processes(self) -> int:
        return len(self.ranks)

    @property
    def leader(self) -> int:
        """The node id that performs leader-only rendezvous work (PJRT key
        cleanup, invite/state publication): the lowest surviving id."""
        return self.ranks[0]

    def buddy_of(self, node_id: int) -> int:
        """The ring buddy that mirrors ``node_id``'s state shard: the next
        member id (wrapping), so every member has exactly one buddy and one
        protégé and a single death never takes a shard AND its mirror."""
        i = self.ranks.index(node_id)
        return self.ranks[(i + 1) % len(self.ranks)]

    def shrink(self, dead) -> "WorldDescriptor":
        """The next generation without ``dead`` (an id or iterable of ids)."""
        gone = {dead} if isinstance(dead, int) else set(dead)
        survivors = tuple(r for r in self.ranks if r not in gone)
        return WorldDescriptor(self.generation + 1, survivors, self.node_id)

    def grow(self, new_ids) -> "WorldDescriptor":
        """The next generation with ``new_ids`` joined (spare/hot join)."""
        joined = {new_ids} if isinstance(new_ids, int) else set(new_ids)
        return WorldDescriptor(
            self.generation + 1, self.ranks + tuple(joined), self.node_id)

    def make_mesh(self, tp: int = 1, sp: int = 1, dcn_dp: int = 1) -> Mesh:
        """The generation's mesh over the CURRENT global device set (call
        after :func:`adopt_world` + backend bring-up)."""
        return make_mesh(tp=tp, sp=sp, dcn_dp=dcn_dp)


def reset_backend() -> None:
    """Demolish the live jax backend so a NEW world can be built in-process.

    The elastic-resize primitive: drops the backend registry, every jit
    cache, and the global mesh cache, so the next ``jax.devices()`` call
    re-runs distributed CPU bring-up against whatever
    ``jax._src.distributed.global_state`` then says (see
    :func:`adopt_world`). The old PJRT client itself is NOT freed — live
    jitted functions and arrays keep it referenced indefinitely — which is
    why the elastic runtime pairs this with socket fencing
    (``resilience/elastic.py``) instead of waiting for a destructor that
    never runs.
    """
    import gc

    from jax._src import mesh as mesh_lib
    from jax._src import xla_bridge

    xla_bridge._clear_backends()
    jax.clear_caches()
    mesh_lib._mesh_object_dict.clear()
    gc.collect()


def adopt_world(descriptor: WorldDescriptor) -> None:
    """Point jax's distributed global state at the descriptor's dense view.

    The next backend bring-up (first ``jax.devices()`` after
    :func:`reset_backend`) then constructs an ``N = num_processes`` world:
    CPU topology exchange and gloo ring re-run over the coordinator KV store
    exactly as at process start, just with fewer (or more) participants.
    """
    from jax._src import distributed

    state = distributed.global_state
    state.process_id = descriptor.process_id
    state.num_processes = descriptor.num_processes


def _inner_device_grid(
    devices: Sequence[jax.Device], dp: int, tp: int, sp: int
) -> np.ndarray:
    """(dp, tp, sp) grid over devices that share one fast (ICI) network."""
    if all(d.platform == "cpu" for d in devices):
        # host-platform (virtual-device) meshes have no physical topology —
        # row-major assignment is exact, and create_device_mesh can reject
        # shapes it cannot factor against fake topologies
        try:
            return mesh_utils.create_device_mesh((dp, tp, sp), devices=devices)
        except Exception:
            return np.asarray(devices).reshape(dp, tp, sp)
    # on real accelerators a failure here is a genuine topology error:
    # surface it rather than silently degrading ICI locality
    return mesh_utils.create_device_mesh((dp, tp, sp), devices=devices)


def _hybrid_device_grid(
    devices: Sequence[jax.Device], dcn_dp: int, inner_dp: int, tp: int, sp: int
) -> np.ndarray:
    """(dcn_dp·inner_dp, tp, sp) grid, DCN-major on the first axis.

    Delegates granule discovery, evenness validation and topology-aware
    placement to ``mesh_utils.create_hybrid_device_mesh`` — slice granules
    first (multi-slice pods), then process granules (multi-host CPU /
    hosts-as-granules deployments). When neither yields ``dcn_dp`` granules,
    a SINGLE-process CPU device set falls back to contiguous chunking (so the
    layout is testable on virtual devices); real accelerators — and CPU
    devices spanning processes, where chunks could straddle host boundaries —
    surface the topology error.
    """
    errors = []
    for kwargs in ({}, {"process_is_granule": True}):
        try:
            return mesh_utils.create_hybrid_device_mesh(
                (inner_dp, tp, sp), (dcn_dp, 1, 1), devices=devices, **kwargs
            )
        except (ValueError, AssertionError) as e:
            errors.append(str(e))
    if (all(d.platform == "cpu" for d in devices)
            and len({d.process_index for d in devices}) == 1):
        per = len(devices) // dcn_dp
        return np.concatenate(
            [
                _inner_device_grid(devices[i * per:(i + 1) * per], inner_dp, tp, sp)
                for i in range(dcn_dp)
            ],
            axis=0,
        )
    raise ValueError(
        f"no slice/process granule split of {len(devices)} devices matches "
        f"dcn_dp={dcn_dp}: {errors}"
    )


def make_mesh(
    dp: Optional[int] = None,
    tp: int = 1,
    sp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    dcn_dp: int = 1,
) -> Mesh:
    """A (data, model, seq) mesh over the given (default: all) devices.

    ``dp`` defaults to ``n_devices // (tp * sp)``. On TPU,
    ``mesh_utils.create_device_mesh`` lays the axes out so that the
    highest-traffic axis rides ICI neighbours.

    ``dcn_dp`` > 1 builds a hybrid ICI×DCN layout for multi-slice / multi-host
    deployments: the ``data`` axis is laid out DCN-major, so its outer
    ``dcn_dp`` factor crosses slice (or host) boundaries while the inner
    ``dp // dcn_dp`` factor and the whole ``model``/``seq`` axes stay inside
    one slice's ICI. The logical mesh is unchanged — same three axis names,
    same shape ``(dp, tp, sp)`` — so every sharding rule, the ZeRO partition
    and the sequence-parallel kernel route apply as-is; only the device
    placement (and therefore which hops each collective rides) differs. This
    is the standard hybrid recipe: gradient psum over ``data`` becomes a
    hierarchical reduce (ICI within the slice, one DCN exchange across), and
    the latency-sensitive tensor/sequence collectives never touch DCN.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if tp < 1 or sp < 1 or dcn_dp < 1:
        raise ValueError(
            f"tp, sp and dcn_dp must be >= 1, got tp={tp} sp={sp} dcn_dp={dcn_dp}"
        )
    if dp is None:
        if n % (tp * sp) != 0:
            raise ValueError(f"{n} devices not divisible by tp*sp = {tp * sp}")
        dp = n // (tp * sp)
    if dp * tp * sp != n:
        raise ValueError(f"dp*tp*sp = {dp * tp * sp} != {n} devices")

    if dcn_dp == 1:
        return Mesh(_inner_device_grid(devices, dp, tp, sp), MESH_AXES)

    if dp % dcn_dp != 0:
        raise ValueError(
            f"dcn_dp={dcn_dp} must divide the data-parallel size dp={dp} "
            f"(the DCN factor is the outer part of the data axis)"
        )
    inner_dp = dp // dcn_dp
    device_grid = _hybrid_device_grid(devices, dcn_dp, inner_dp, tp, sp)
    return Mesh(device_grid, MESH_AXES)
