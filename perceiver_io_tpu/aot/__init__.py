"""Persistent ahead-of-time compilation: zero-recompile cold starts.

Perceiver IO's serving efficiency comes from a *family* of small specialized
XLA programs — one executable per (signature, batch-bucket) — and every
process start used to re-pay the full compile family before the first
request could be answered. This subsystem makes cold start near-zero:

- :class:`ExecutableCache` — tier 1: compiled executables serialized to disk
  (``jax.experimental.serialize_executable``), keyed by a content fingerprint
  (package/source identity of the traced callable, jax/jaxlib + PJRT
  platform/topology, abstract input shapes/dtypes, donation/static config).
  A warm start deserializes the executable directly — no trace, no lower,
  no compile. Corrupt entries and fingerprint mismatches fall back to a
  normal compile; a cache problem NEVER refuses traffic.
- :func:`configure_compile_cache` — tier 2: jax's own persistent
  compilation cache, always on, placed by ``JAX_COMPILATION_CACHE_DIR`` or at
  ``<checkout>/.cache/jax``: tracing and lowering still run, but the
  expensive backend compile becomes a disk hit. While it is active, tier-1
  stores are refused (``aot/cache.py``), so a warm start is tier 2's.

Tier 1 is fail-soft by construction and exports hit/miss/error counters
through the obs registry.
"""

from perceiver_io_tpu.aot.cache import (
    ExecutableCache,
    callable_sources,
    compile_via_cache,
    configure_compile_cache,
    environment_fingerprint,
    fingerprint,
    resolve_cache,
)

__all__ = [
    "ExecutableCache",
    "callable_sources",
    "compile_via_cache",
    "configure_compile_cache",
    "environment_fingerprint",
    "fingerprint",
    "resolve_cache",
]
