"""Persistent executable cache: serialize compiled XLA programs to disk.

Tier 1 (:class:`ExecutableCache`): ``jax.experimental.serialize_executable``
round-trips a ``Compiled`` object through bytes. Entries are keyed by a
content *fingerprint* computed WITHOUT tracing or lowering — a warm start
goes straight from (shapes, config) to a loaded executable, skipping the
trace, the lower, and the backend compile entirely. The fingerprint
folds in everything that could change the compiled program:

- package version + best-effort source of the traced callable (closure
  functions recursed; non-function closure cells contribute their repr when
  it is address-free — a flax module repr carries the full hyperparameter
  tree, which is exactly the model identity we want);
- jax/jaxlib versions, backend platform, device kind and count (the PJRT
  topology a serialized executable is only valid for);
- the abstract shapes/dtypes AND pytree structure of every argument;
- static config: donation, quantization mode, compute dtype, caller salt.

Any fingerprint drift = a different file name = an honest MISS followed by a
normal compile; a corrupt or truncated entry deserializes into an exception,
which is caught, warned about, counted, and the entry deleted — then the
normal compile runs. A cache problem can slow a cold start back to baseline;
it can never refuse traffic or serve a wrong program.

Tier 2 (:func:`configure_compile_cache`): jax's own persistent compilation
cache, for every compile of the process (the trainer step, the kernels, the
tools, and the serving programs too): tracing/lowering still run, but the
backend compile becomes a disk hit. Always on, and placed from outside:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.cache/jax``. Every
entry point calls :func:`configure_compile_cache` before its first compile.

The two tiers do not stack: while tier 2 is active, tier-1 STORES are refused
(see :meth:`ExecutableCache.store`), so a warm start is then a tier-2 hit per
program — zero backend compiles, trace and lower still paid. Tier 1 stores
only in a process that runs with ``JAX_ENABLE_COMPILATION_CACHE=false``.

No jax import at module scope — entry points must stay free to pick their
platform (``ensure_cpu_only``) before anything initializes a backend.
"""

from __future__ import annotations

import hashlib
import inspect
import os
import pickle
import re
import tempfile
import warnings
from typing import Any, Dict, Iterable, List, Optional, Union

import numpy as np

import perceiver_io_tpu.obs as obs

_ENTRY_SUFFIX = ".pitx"
_ENTRY_FORMAT = 2  # bump when the on-disk pickle layout changes


# -- fingerprinting ----------------------------------------------------------


def callable_sources(fn: Any, max_depth: int = 4) -> List[str]:
    """Best-effort stable identity strings for a (possibly nested) callable.

    Walks ``fn`` and the functions captured in its closure cells up to
    ``max_depth``, collecting source text where ``inspect`` can see it and
    qualnames otherwise. Non-function cell contents contribute
    ``type.qualname`` plus their ``repr`` with memory addresses normalized
    out (``repr(flax_module)`` is a full hyperparameter tree — exactly the
    model identity we want — but any embedded default ``<obj at 0x...>``
    repr would poison the fingerprint with a per-process address).
    """
    out: List[str] = []
    seen: set = set()

    def visit(obj: Any, depth: int) -> None:
        if depth > max_depth or id(obj) in seen:
            return
        seen.add(id(obj))
        if callable(obj):
            qualname = getattr(obj, "__qualname__", type(obj).__qualname__)
            out.append(f"callable:{qualname}")
            try:
                out.append(inspect.getsource(obj))
            except (OSError, TypeError):
                pass
            closure = getattr(obj, "__closure__", None) or ()
            for cell in closure:
                try:
                    visit(cell.cell_contents, depth + 1)
                except ValueError:  # empty cell
                    continue
            # functools.partial / bound methods: follow the wrapped callable
            for attr in ("func", "__func__", "__wrapped__"):
                inner = getattr(obj, attr, None)
                if inner is not None:
                    visit(inner, depth + 1)
        else:
            r = re.sub(r"0x[0-9a-fA-F]+", "0xADDR", repr(obj))
            out.append(f"object:{type(obj).__qualname__}:{r[:100_000]}")

    visit(fn, 0)
    return out


def _aval_strings(avals) -> List[str]:
    """Stable strings for a pytree of ShapeDtypeStruct-likes: the treedef
    plus every leaf's dtype/shape."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(avals)
    out = [f"treedef:{treedef}"]
    # sharding is part of a compiled executable's input contract (a
    # Compiled object rejects differently-placed args) — its str form is
    # address-free and process-stable (axis names/sizes, spec, device ids)
    out.extend(
        f"leaf:{getattr(l, 'dtype', '?')}:{getattr(l, 'shape', '?')}:"
        f"{getattr(l, 'sharding', None)}"
        for l in leaves
    )
    return out


def fingerprint(base: Dict[str, Any], avals: Any = None,
                extra: Iterable[str] = ()) -> str:
    """sha256 hex digest over the static config dict, the abstract argument
    tree, and any extra identity strings."""
    h = hashlib.sha256()
    for k in sorted(base):
        h.update(f"{k}={base[k]}\x00".encode("utf-8", "backslashreplace"))
    if avals is not None:
        for s in _aval_strings(avals):
            h.update(s.encode("utf-8", "backslashreplace"))
            h.update(b"\x00")
    for s in extra:
        h.update(str(s).encode("utf-8", "backslashreplace"))
        h.update(b"\x00")
    return h.hexdigest()


def environment_fingerprint() -> Dict[str, Any]:
    """The per-process part of every fingerprint: package + jax/jaxlib
    versions, backend platform, device kind/count. Touches the backend —
    call only after the entry point has picked its platform."""
    import jax
    import jaxlib

    import perceiver_io_tpu

    dev = jax.devices()[0]
    return {
        "pkg": perceiver_io_tpu.__version__,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "num_devices": jax.device_count(),
        "entry_format": _ENTRY_FORMAT,
    }


# -- the executable cache ----------------------------------------------------


class ExecutableCache:
    """A directory of serialized compiled executables, one file per
    fingerprint, with fail-soft reads and atomic writes.

    Construct via :meth:`open` (fail-soft: an unusable directory yields
    ``None`` + a warning instead of an exception) — serving must never be
    refused over a cache problem. Concurrent engines/processes may share one
    directory: writes go through a same-directory temp file + ``os.replace``
    (atomic on POSIX), so a reader sees either a complete entry or none, and
    a torn/corrupt read falls back to a normal compile.
    """

    def __init__(self, directory: str,
                 registry: Optional[obs.MetricsRegistry] = None):
        self.directory = directory
        reg = registry if registry is not None else obs.get_registry()
        self._m_hits = reg.counter(
            "aot_cache_hits_total",
            "compiled executables loaded from the persistent AOT cache")
        self._m_misses = reg.counter(
            "aot_cache_misses_total",
            "AOT cache lookups that fell back to a compile")
        self._m_errors = reg.counter(
            "aot_cache_errors_total",
            "corrupt/unreadable/unwritable AOT cache entries (each one "
            "degraded to a normal compile, never an outage)")
        self._m_stores = reg.counter(
            "aot_cache_stores_total",
            "compiled executables serialized into the AOT cache")

    # -- construction --------------------------------------------------------

    @classmethod
    def open(cls, directory: Optional[str],
             registry: Optional[obs.MetricsRegistry] = None,
             ) -> Optional["ExecutableCache"]:
        """Open (creating if needed) ``directory`` as an executable cache.

        Fail-soft: a missing-and-uncreatable or unwritable directory warns
        and returns ``None`` — the caller serves uncached. Never raises for
        environmental problems.
        """
        if not directory:
            return None
        try:
            os.makedirs(directory, exist_ok=True)
            # write probe: root can chmod past a read-only bit, but a path
            # through a regular file / dead mount / full disk fails here
            probe = tempfile.NamedTemporaryFile(
                dir=directory, prefix=".probe_", delete=True)
            probe.write(b"x")
            probe.close()
        except OSError as e:
            warnings.warn(
                f"compile cache {directory!r} is unusable "
                f"({type(e).__name__}: {e}) — serving UNCACHED (cold starts "
                "pay full compiles; traffic is unaffected)", stacklevel=2)
            return None
        return cls(directory, registry=registry)

    # -- entries -------------------------------------------------------------

    def path(self, fp: str) -> str:
        return os.path.join(self.directory, fp + _ENTRY_SUFFIX)

    def load(self, fp: str):
        """Deserialize the executable stored under fingerprint ``fp``.

        Returns the loaded ``Compiled`` on a hit, ``None`` on a miss.
        A corrupt/truncated entry (or a deserialize failure — e.g. an entry
        written by an incompatible runtime that still hashed to the same
        fingerprint) warns, deletes the entry, counts an error, and returns
        ``None`` so the caller compiles normally.
        """
        path = self.path(fp)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            self._m_misses.inc()
            return None
        except OSError as e:
            self._m_errors.inc()
            self._m_misses.inc()
            warnings.warn(
                f"compile cache entry {path} unreadable "
                f"({type(e).__name__}: {e}) — falling back to a fresh "
                "compile", stacklevel=2)
            return None
        try:
            from jax.experimental import serialize_executable

            entry = pickle.loads(blob)
            if entry["format"] != _ENTRY_FORMAT:
                raise ValueError(f"entry format {entry['format']} != "
                                 f"{_ENTRY_FORMAT}")
            # bind the program to the devices it was compiled for: the
            # default is EVERY device of the backend, and a one-device
            # program loaded that way fails at its first call on any host
            # with more than one device
            import jax

            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=[by_id[i] for i in entry["device_ids"]],
            )
        except Exception as e:
            self._m_errors.inc()
            self._m_misses.inc()
            warnings.warn(
                f"compile cache entry {path} is corrupt or incompatible "
                f"({type(e).__name__}: {str(e)[:200]}) — deleting it and "
                "falling back to a fresh compile", stacklevel=2)
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        self._m_hits.inc()
        obs.event("aot_cache_hit", fingerprint=fp[:16])
        return compiled

    def store(self, fp: str, compiled) -> bool:
        """Serialize ``compiled`` under fingerprint ``fp`` (atomic replace).

        Fail-soft: serialization/write errors warn + count and return False
        (e.g. a backend whose executables don't serialize, or a disk that
        filled up mid-write) — the in-memory executable keeps serving.

        Refuses (once-warned) while jax's persistent compilation cache is
        active in this process. An executable that tier 2 handed back from
        its own disk entry, serialized again here, reloads into a program
        that fails at its first call (``NOT_FOUND: ... Function
        dot_add_fusion.1 not found`` — reproduced on jaxlib 0.9.0's CPU
        runtime, PR 22; earlier jaxlibs corrupted the heap instead). Loads
        stay enabled; with both tiers on, new programs persist through
        tier 2 alone.
        """
        if persistent_cache_active():
            global _DOUBLE_TIER_WARNED
            if not _DOUBLE_TIER_WARNED:
                _DOUBLE_TIER_WARNED = True
                warnings.warn(
                    "AOT executable store skipped: jax's persistent "
                    "compilation cache is active in this process, and an "
                    "executable it served cannot be serialized a second "
                    "time (aot/cache.py). New programs persist through the "
                    "persistent cache alone; run with "
                    "JAX_ENABLE_COMPILATION_CACHE=false to store AOT "
                    "entries.", stacklevel=2)
            return False
        path = self.path(fp)
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = pickle.dumps({
                "format": _ENTRY_FORMAT,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "device_ids": [
                    d.id
                    for d in compiled.runtime_executable().local_devices()],
            })
            fd, tmp = tempfile.mkstemp(
                dir=self.directory, prefix=".tmp_", suffix=_ENTRY_SUFFIX)
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)  # readers see all-or-nothing
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:
            self._m_errors.inc()
            warnings.warn(
                f"could not persist compiled executable to {path} "
                f"({type(e).__name__}: {str(e)[:200]}) — serving from the "
                "in-memory copy; the next cold start recompiles",
                stacklevel=2)
            return False
        self._m_stores.inc()
        obs.event("aot_cache_store", fingerprint=fp[:16],
                  bytes=len(blob))
        return True

    def entries(self) -> List[str]:
        """Fingerprints currently on disk (diagnostics/tests)."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        return sorted(
            n[: -len(_ENTRY_SUFFIX)] for n in names
            if n.endswith(_ENTRY_SUFFIX) and not n.startswith(".")
        )


def compile_via_cache(
    jitted: Any,
    example_args: Any,
    cache: Optional["ExecutableCache"],
    base: Dict[str, Any],
    extra: Iterable[str] = (),
):
    """Compile ``jitted`` at ``example_args``' abstract shapes, round-
    tripping the executable through ``cache`` when one is given.

    The shared lower-once path for engines that manage their OWN program
    tables (the continuous-batching arena, ad-hoc tools): avals are derived
    from the example arguments (shape/dtype/sharding — never values, so
    passing live donated buffers is safe: nothing executes here), the
    fingerprint folds ``base`` + avals + ``extra``, and a hit skips
    trace/lower/compile entirely. ``cache=None`` degrades to a plain
    ``lower().compile()`` so callers need no branching."""
    import jax

    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype if np.isscalar(x)
            else x.dtype, sharding=getattr(x, "sharding", None)),
        tuple(example_args))
    if cache is None:
        return jitted.lower(*avals).compile()
    fp = fingerprint(base, avals=avals, extra=extra)
    compiled = cache.load(fp)
    if compiled is None:
        compiled = jitted.lower(*avals).compile()
        cache.store(fp, compiled)
    return compiled


def resolve_cache(
    spec: Union[None, str, ExecutableCache],
    registry: Optional[obs.MetricsRegistry] = None,
) -> Optional[ExecutableCache]:
    """Normalize a ``compile_cache`` argument: a directory path opens
    (fail-soft), an :class:`ExecutableCache` passes through, None disables."""
    if spec is None or isinstance(spec, ExecutableCache):
        return spec
    return ExecutableCache.open(spec, registry=registry)


# -- tier 2: jax's persistent compilation cache ------------------------------

_DOUBLE_TIER_WARNED = False
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def persistent_cache_active() -> bool:
    """True when jax's persistent compilation cache is on in this process."""
    import jax

    return bool(jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns its directory.

    THE one decision, called by every entry point before its first compile:
    where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own handling of it
    stands and no code names another directory; otherwise the cache is
    ``<checkout>/.cache/jax``, derived from this package's location — never
    the working directory, a temporary name, a pid or the time, because the
    directory is where the next process looks, and one that moves never
    hits. Every compile is kept (no minimum compile time or entry size): a
    warm start counts ZERO backend compiles only if the small programs hit
    too. Idempotent.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = os.path.join(_CHECKOUT, ".cache", "jax")
        if jax.config.jax_compilation_cache_dir != directory:
            jax.config.update("jax_compilation_cache_dir", directory)
            # jax latches whether the cache is in use at its first compile;
            # a process that compiled before this call must look again
            compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory
