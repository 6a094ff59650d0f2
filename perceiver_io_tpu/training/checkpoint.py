"""Checkpoint / resume: async, multi-host-safe, best-by-metric retention.

The TPU-native replacement for the reference's Lightning ``ModelCheckpoint``
(reference ``train/utils.py:11-13``: monitor ``val_loss`` min, ``save_top_k=1``,
hyperparameters embedded via ``save_hyperparameters`` at ``lightning.py:46``)
and its ``load_from_checkpoint`` transfer path (reference
``train_seq_clf.py:18-28``: reuse a pretrained MLM encoder inside a fresh
classifier).

Built on Orbax, which writes sharded arrays in parallel from every host and
supports async save — the idiomatic way to checkpoint a pjit-sharded
params/opt-state pytree. The reference's "checkpoint surgery" (moving the
encoder ``nn.Module`` between Lightning models) becomes a pure pytree-subtree
swap: ``restore_encoder_params`` returns the ``encoder`` subtree to graft into
any other model's params.
"""

from __future__ import annotations

import dataclasses
import json
import os
import warnings
from typing import Any, Dict, Optional

import jax
import numpy as np

from perceiver_io_tpu import obs

with obs.span("import", module="orbax.checkpoint"):
    import orbax.checkpoint as ocp

HPARAMS_FILE = "hparams.json"
LAST_SUBDIR = "last"  # unconditional newest-state slot (preemption/crash)
METRICS_FILE = "metrics.json"
# Content-digest sidecar: {step: sha256-over-params} per manager directory,
# written at save() time and VERIFIED by restore_train_state(prefer_latest=
# True) before a step is trusted — extending the truncated-newest fallback
# (a partial save that fails to restore) to SILENT bit corruption (a save
# that restores fine but holds different bytes than were written). Same
# digest definition the deploy publications carry (utils/treepath).
DIGESTS_FILE = "digests.json"


def _record_digest(directory: str, step: int, params) -> None:
    """Append ``{step: digest}`` to the sidecar (atomic tmp+replace).

    Multi-process: process 0 alone writes (every host racing one json would
    corrupt it), and only when every leaf is fully REPLICATED (the standard
    data-parallel layout — note a multi-host global array is never fully
    *addressable*, but a replicated one is device_get-able from any one
    host's replica). A ZeRO-3 tree is sharded across hosts and gets no
    sidecar; its restores fall back to Orbax's atomic-commit guarantee, as
    before r19."""
    leaves = jax.tree.leaves(params)
    if jax.process_count() > 1:
        if jax.process_index() != 0 or not all(
            getattr(leaf, "is_fully_replicated", True) for leaf in leaves
        ):
            return
    from perceiver_io_tpu.utils.treepath import tree_digest

    digest = tree_digest(jax.device_get(params))
    path = os.path.join(directory, DIGESTS_FILE)
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        data = {}
    data[str(int(step))] = digest
    os.makedirs(directory, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _expected_digest(directory: str, step: int) -> Optional[str]:
    try:
        with open(os.path.join(directory, DIGESTS_FILE)) as f:
            return json.load(f).get(str(int(step)))
    except (OSError, ValueError):
        return None  # no sidecar (pre-digest checkpoints): nothing to check


def _to_save_tree(state) -> Dict[str, Any]:
    """TrainState → pure-array pytree Orbax can serialize.

    Typed PRNG key arrays carry an opaque dtype; store the raw key data and
    re-wrap on restore.
    """
    return {
        "step": state.step,
        "params": state.params,
        "opt_state": state.opt_state,
        "rng": jax.random.key_data(state.rng),
    }


def _from_save_tree(tree: Dict[str, Any], like_state):
    rng = jax.random.wrap_key_data(np.asarray(tree["rng"], dtype=np.uint32))
    return like_state.replace(
        step=tree["step"],
        params=tree["params"],
        opt_state=tree["opt_state"],
        rng=rng,
    )


def host_state_snapshot(state) -> Dict[str, Any]:
    """In-memory host-local snapshot of a TrainState (the elastic buddy-
    mirror payload, and the resume point for an in-process world rebuild).

    A pure-numpy tree in the ``_to_save_tree`` layout (PRNG keys as raw key
    data), holding this host's addressable view of every leaf: fully
    replicated leaves — the standard data-parallel layout — come back
    complete and identical on every host, so the snapshot IS the whole
    state; a cross-host-sharded leaf (ZeRO over ``data``) contributes only
    this host's first addressable shard. Elastic resume requires the
    complete flavor — gate on :func:`snapshot_is_complete` before trusting
    a snapshot to seed a resized world.
    """

    def to_host(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return np.asarray(x.addressable_data(0))
        return np.asarray(jax.device_get(x))

    return jax.tree.map(to_host, _to_save_tree(state))


def snapshot_is_complete(state) -> bool:
    """True when every leaf of ``state`` is fully replicated (multi-host)
    or fully addressable (single-process) — i.e. :func:`host_state_snapshot`
    captures the COMPLETE state, not one host's shard of it."""
    return all(
        getattr(leaf, "is_fully_replicated", True)
        or getattr(leaf, "is_fully_addressable", True)
        for leaf in jax.tree.leaves(state)
    )


def restore_from_snapshot(snapshot: Dict[str, Any], like_state):
    """Snapshot → TrainState shaped like ``like_state`` (host-resident
    leaves; place onto a mesh via ``make_sharded_train_step`` /
    ``shard_train_state`` as with any restored state)."""
    return _from_save_tree(snapshot, like_state)


def snapshot_digest(snapshot: Dict[str, Any]) -> str:
    """Content digest of a snapshot — the same ``utils/treepath`` digest the
    checkpoint sidecar and deploy manifests use, so a buddy mirror is
    verifiable with the one digest discipline (``DIGESTS_FILE`` above)."""
    from perceiver_io_tpu.utils.treepath import tree_digest

    return tree_digest(snapshot)


class CheckpointManager:
    """Top-k-by-metric checkpointing of TrainState pytrees + hparams.

    Semantics mirror the reference callback (``train/utils.py:11-13``):
    ``monitor='val_loss'``, ``mode='min'``, ``max_to_keep=1`` by default.
    ``hparams`` (any JSON-serializable dict, e.g. a dataclass config) are
    written once per checkpoint, giving ``save_hyperparameters`` parity —
    a checkpoint is self-describing enough to rebuild its model.
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 1,
        monitor: str = "val_loss",
        mode: str = "min",
        hparams: Optional[Dict[str, Any]] = None,
        async_save: bool = True,
    ):
        if mode not in ("min", "max"):
            raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
        self.directory = os.path.abspath(directory)
        self.monitor = monitor
        self.mode = mode
        self._hparams = _jsonable(hparams) if hparams is not None else None

        def best_fn(metrics: Dict[str, float]) -> float:
            return float(metrics[monitor])

        self._mngr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                best_fn=best_fn,
                best_mode=mode,
                enable_async_checkpointing=async_save,
            ),
        )
        self._last_mngr: Optional[ocp.CheckpointManager] = None
        self._async_save = async_save
        if self._hparams is not None and jax.process_index() == 0:
            os.makedirs(self.directory, exist_ok=True)
            with open(os.path.join(self.directory, HPARAMS_FILE), "w") as f:
                json.dump(self._hparams, f, indent=2, sort_keys=True)

    # -- save ---------------------------------------------------------------

    def save_last(self, step: int, state) -> None:
        """Unconditionally save the CURRENT state to the ``last/`` slot
        (one kept), regardless of metric rank — the preemption/crash
        checkpoint. The best-by-metric policy above would GC a state whose
        monitored metric is worse than the champion's, which is exactly the
        state a preempted run needs to resume from."""
        if self._last_mngr is None:
            self._last_mngr = ocp.CheckpointManager(
                os.path.join(self.directory, LAST_SUBDIR),
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=1,
                    enable_async_checkpointing=self._async_save,
                ),
            )
        self._last_mngr.save(
            int(step),
            args=ocp.args.Composite(
                state=ocp.args.StandardSave(_to_save_tree(state))
            ),
        )
        self._last_mngr.wait_until_finished()
        _record_digest(os.path.join(self.directory, LAST_SUBDIR),
                       step, state.params)

    def save(self, step: int, state, metrics: Dict[str, float]) -> bool:
        """Save if ``metrics[monitor]`` ranks in the top-k. Returns whether a
        save was issued (Orbax applies the best-k policy internally)."""
        metrics = {k: float(v) for k, v in metrics.items()}
        if self.monitor not in metrics:
            raise KeyError(
                f"monitored metric {self.monitor!r} missing from metrics "
                f"{sorted(metrics)}"
            )
        # item name 'val_metrics': orbax reserves 'metrics' for itself on
        # the release this runs under (RESERVED_ITEM_NAMES)
        saved = self._mngr.save(
            int(step),
            args=ocp.args.Composite(
                state=ocp.args.StandardSave(_to_save_tree(state)),
                val_metrics=ocp.args.JsonSave(metrics),
            ),
            metrics=metrics,
        )
        if saved:
            # the digest hashes the IN-MEMORY tree being saved (the intended
            # content), so it needs no wait on the async write — a restore
            # that later hashes differently read corrupted bytes
            _record_digest(self.directory, step, state.params)
        return saved

    def wait(self) -> None:
        """Block until in-flight async saves land (call before reading)."""
        self._mngr.wait_until_finished()

    # -- introspection ------------------------------------------------------

    @property
    def all_steps(self):
        self.wait()
        return sorted(self._mngr.all_steps())

    @property
    def best_step(self) -> Optional[int]:
        self.wait()
        return self._mngr.best_step()

    @property
    def latest_step(self) -> Optional[int]:
        self.wait()
        return self._mngr.latest_step()

    # -- restore ------------------------------------------------------------

    def restore_state(self, like_state, step: Optional[int] = None):
        """Restore a full TrainState (resume). ``like_state`` supplies the
        tree structure, shardings and dtypes; ``step=None`` → best step."""
        step = self._resolve(step)
        restored = self._mngr.restore(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardRestore(_to_save_tree(like_state))
            ),
        )["state"]
        return _from_save_tree(restored, like_state)

    def restore_metrics(self, step: Optional[int] = None) -> Dict[str, float]:
        step = self._resolve(step)
        return dict(
            self._mngr.restore(
                step,
                args=ocp.args.Composite(val_metrics=ocp.args.JsonRestore()),
            )["val_metrics"]
        )

    def _resolve(self, step: Optional[int]) -> int:
        self.wait()
        if step is None:
            step = self._mngr.best_step()
            if step is None:
                step = self._mngr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        return int(step)

    def close(self) -> None:
        self.wait()
        self._mngr.close()
        if self._last_mngr is not None:
            self._last_mngr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# -- module-level restore helpers (no manager required) ---------------------


def resolve_checkpoint_step(directory: str, step: Optional[int] = None,
                            monitor: str = "val_loss",
                            mode: str = "min") -> int:
    """The step a param restore from ``directory`` would use (explicit →
    best → latest) WITHOUT reading any arrays — e.g. the deploy watcher's
    ``min_step`` floor, so a restarted serve process never replays
    publications older than the checkpoint it booted from."""
    if step is not None:
        return int(step)
    with _read_manager(directory, monitor, mode) as mngr:
        return _resolve_step(mngr, None, directory)


def load_hparams(directory: str) -> Dict[str, Any]:
    """Read the hparams embedded in a checkpoint directory
    (``save_hyperparameters`` parity, reference ``lightning.py:46``)."""
    with open(os.path.join(os.path.abspath(directory), HPARAMS_FILE)) as f:
        return json.load(f)


def _read_manager(directory: str, monitor: str, mode: str) -> ocp.CheckpointManager:
    """Read-side manager with ranking configured, so best_step() works on a
    directory written by some other process/session."""
    return ocp.CheckpointManager(
        os.path.abspath(directory),
        options=ocp.CheckpointManagerOptions(
            best_fn=lambda metrics: float(metrics[monitor]),
            best_mode=mode,
            # read-only usage: never garbage-collect existing checkpoints
            max_to_keep=None,
        ),
    )


def _committed_steps(directory: str):
    """Steps with a committed directory under ``directory`` (orbax names a
    finished step by its bare number; in-flight saves carry a tmp suffix)."""
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    return [int(n) for n in names
            if n.isdigit() and os.path.isdir(os.path.join(directory, n))]


def restore_train_state(
    directory: str, like_state, step: Optional[int] = None,
    monitor: str = "val_loss", mode: str = "min",
    prefer_latest: bool = False,
):
    """Restore a TrainState from ``directory`` (best step by default).

    ``prefer_latest=True`` is the crash/preemption-resume mode: it considers
    both the ranked checkpoints and the unconditional ``last/`` slot
    (``CheckpointManager.save_last``) and restores whichever holds the highest
    step — continuing training from the newest state rather than the champion.

    In that mode a candidate that fails to restore — the signature of a run
    killed MID-SAVE, leaving a truncated/partial step dir — is skipped with a
    warning and the next-newest step is tried instead of crashing the resume
    (exactly the moment a corrupted checkpoint must not be fatal). Only when
    every candidate fails does the last error propagate.
    """
    restore_args = ocp.args.Composite(
        state=ocp.args.StandardRestore(_to_save_tree(like_state))
    )
    last_dir = os.path.join(os.path.abspath(directory), LAST_SUBDIR)
    if prefer_latest and step is None:
        # Candidates come from the directory listing and each is restored
        # by a step-level checkpointer: a CheckpointManager scans EVERY step
        # at construction (the installed orbax json-parses each step's
        # metrics even for a rank-free manager), so one truncated step from
        # a killed-mid-save run would crash the scan before the per-step
        # fallback below could skip it.
        with ocp.Checkpointer(
                ocp.CompositeCheckpointHandler()) as checkpointer:
            candidates = [
                (s, source)
                for source, d in (("last", last_dir),
                                  ("main", os.path.abspath(directory)))
                for s in _committed_steps(d)
            ]
            # newest step first; on a tie the last/ slot wins (it is by
            # construction at least as new as the ranked save of that step)
            candidates.sort(key=lambda c: (c[0], c[1] == "last"), reverse=True)
            if not candidates:
                raise FileNotFoundError(f"no checkpoints in {directory}")
            errors = []
            for cand_step, source in candidates:
                cand_dir = last_dir if source == "last" \
                    else os.path.abspath(directory)
                try:
                    restored = checkpointer.restore(
                        os.path.join(cand_dir, str(cand_step)),
                        args=restore_args)["state"]
                except Exception as e:  # corrupt/partial step dir
                    errors.append(e)
                    warnings.warn(
                        f"checkpoint step {cand_step} ({source} slot) failed "
                        f"to restore ({type(e).__name__}: {e}) — likely a "
                        f"partial save from an interrupted run; falling back "
                        f"to the previous checkpoint",
                        stacklevel=2,
                    )
                    continue
                # digest sidecar: a restore can SUCCEED while holding
                # silently corrupted bytes — verify the params content
                # against the digest recorded at save time before trusting
                # the step (no sidecar entry = pre-digest checkpoint: trust).
                # Multi-process: every host verifies whenever the restored
                # tree is fully REPLICATED (each host hashes its own full
                # replica); hosts read the same bytes off the shared
                # checkpoint filesystem, so a mismatch — and the fallback
                # to the previous candidate — is observed identically on
                # every rank and the restore collectives stay in lockstep.
                # (single-process trees are always verifiable — sharded or
                # not, every leaf is host-addressable, as pre-r19)
                verifiable = jax.process_count() == 1 or all(
                    getattr(leaf, "is_fully_replicated", True)
                    for leaf in jax.tree.leaves(restored["params"])
                )
                expected = (_expected_digest(cand_dir, cand_step)
                            if verifiable else None)
                if expected is not None:
                    from perceiver_io_tpu.utils.treepath import tree_digest

                    got = tree_digest(jax.device_get(restored["params"]))
                    if got != expected:
                        err = ValueError(
                            f"checkpoint step {cand_step} ({source} slot) "
                            f"restored but its params digest {got[:12]} does "
                            f"not match the save-time sidecar "
                            f"{expected[:12]} — silent corruption"
                        )
                        errors.append(err)
                        warnings.warn(
                            f"{err}; falling back to the previous checkpoint",
                            stacklevel=2,
                        )
                        continue
                return _from_save_tree(restored, like_state)
            raise errors[-1]
    with _read_manager(directory, monitor, mode) as mngr:
        step = _resolve_step(mngr, step, directory)
        restored = mngr.restore(step, args=restore_args)["state"]
    return _from_save_tree(restored, like_state)


def restore_params(
    directory: str, like_params, step: Optional[int] = None,
    monitor: str = "val_loss", mode: str = "min",
):
    """Restore only the params tree (inference / export)."""
    with _read_manager(directory, monitor, mode) as mngr:
        step = _resolve_step(mngr, step, directory)
        restored = mngr.restore(
            step,
            args=ocp.args.Composite(state=_partial_restore({"params": like_params})),
        )["state"]
    return restored["params"]


def restore_raw_params(directory: str, step: Optional[int] = None,
                       monitor: str = "val_loss", mode: str = "min"):
    """Restore the params tree WITHOUT a caller template, as ``(params,
    step)`` with host numpy/jax arrays in the saved structure — for tools
    that only re-serialize the weights (e.g. the reference-checkpoint
    export) and have no model to build a ``like`` tree from.

    The template comes from the checkpoint's own metadata, restricted to
    the ``params`` subtree — a full TrainState checkpoint also stores the
    optimizer moments (~2x the param bytes), which a templateless restore
    would read and materialize only to discard."""
    with _read_manager(directory, monitor, mode) as mngr:
        step = _resolve_step(mngr, step, directory)
        # reading metadata (vs restoring) needs the handler declared upfront
        with ocp.CheckpointManager(
            os.path.abspath(directory),
            options=ocp.CheckpointManagerOptions(
                best_fn=lambda m: m.get(monitor, 0.0), best_mode=mode
            ),
            item_handlers={"state": ocp.StandardCheckpointHandler()},
        ) as meta_mngr:
            meta = meta_mngr.item_metadata(step)["state"]
        like = jax.tree.map(
            lambda m: np.zeros(m.shape, m.dtype), meta["params"]
        )
        restored = mngr.restore(
            step, args=ocp.args.Composite(state=_partial_restore({"params": like}))
        )["state"]
    return restored["params"], int(step)


def restore_encoder_params(
    directory: str, like_encoder_params, step: Optional[int] = None,
    subtree: str = "encoder", monitor: str = "val_loss", mode: str = "min",
):
    """Restore one params subtree — the transfer-learning path.

    The reference moves a pretrained MLM encoder module into a fresh text
    classifier (``train_seq_clf.py:18-24``); here the same capability is a
    partial pytree restore: read only ``params/<subtree>`` from the checkpoint
    (Orbax restores just the requested leaves) and graft it into the new
    model's params: ``params['encoder'] = restore_encoder_params(...)``.
    """
    with _read_manager(directory, monitor, mode) as mngr:
        step = _resolve_step(mngr, step, directory)
        restored = mngr.restore(
            step,
            args=ocp.args.Composite(
                state=_partial_restore({"params": {subtree: like_encoder_params}})
            ),
        )["state"]
    return restored["params"][subtree]


def _partial_restore(item):
    """Restore only the leaves present in ``item`` (subtree loading).

    ``transforms={}`` is the pre-``partial_restore`` spelling this orbax
    release supports: the output takes ``item``'s structure, every key falls
    through to the stored value, and leaves absent from ``item`` are never
    read."""
    return ocp.args.PyTreeRestore(
        item=item,
        restore_args=ocp.checkpoint_utils.construct_restore_args(item),
        transforms={},
    )


def _resolve_step(mngr, step: Optional[int], directory: str) -> int:
    if step is not None:
        return int(step)
    try:
        step = mngr.best_step()
    except KeyError:  # checkpoints saved without the monitored metric
        step = None
    if step is None:
        step = mngr.latest_step()
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {directory}")
    return int(step)


def _jsonable(obj: Any) -> Any:
    """Best-effort JSON projection for hparams (dataclasses, argparse
    namespaces, numpy scalars)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return _jsonable(dataclasses.asdict(obj))
    if hasattr(obj, "__dict__") and not isinstance(obj, (dict, list, tuple, str)):
        try:
            return _jsonable(vars(obj))
        except TypeError:
            return str(obj)
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    return str(obj)
