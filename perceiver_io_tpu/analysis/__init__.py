"""pitlint — repo-invariant static analysis + runtime sanitizers.

The port's correctness rests on invariants no compiler checks: torch-parity
param-tree names that sharding regexes key on, jit-purity on the dispatch hot
path (one stray ``.item()`` is a host sync that stalls the pipeline),
registered ``PIT_FAULTS`` sites, the one-JSON-line stdout contract of
``tools/`` and ``bench.py``, and lock discipline across the engine/router/
deployer thread soup. This package enforces them by machine:

- **static rules** (:mod:`core` + the ``rules_*`` modules): small AST
  visitors, each with a rule ID, producing file/line findings. Pre-existing
  debt lives in a checked-in baseline file (:data:`core.DEFAULT_BASELINE`)
  so CI blocks only NEW violations; genuinely-fine-forever sites carry an
  inline ``# pitlint: ignore[RULE-ID]`` pragma with the reason on the line.
- **cross-checks** (:mod:`crosscheck`): CPU-only audits that need the real
  code imported — every ``parallel/sharding.py`` path-regex must match at
  least one param path in every ``models/presets.py`` preset tree, so a
  rename cannot silently strand a sharding rule.
- **runtime sanitizers** (:mod:`sanitizers`): ``no_recompile()`` (zero
  ``jax_compilations_total`` delta over a steady-state block),
  ``no_implicit_transfers()`` (``jax.transfer_guard`` armed around engine
  dispatch), and ``record_lock_order()`` (acquisition-graph recording with
  cycle detection — the deadlock linter tier-1 runs).

Entry points: ``tools/lint.py`` (one JSON line, nonzero exit on
non-baselined findings) and ``tests/test_lint.py`` (the tier-1 pass over
``perceiver_io_tpu/``, ``tools/``, and ``bench.py``).
"""

from perceiver_io_tpu.analysis.core import (
    Baseline,
    FileContext,
    Finding,
    Rule,
    all_rules,
    scan_paths,
)
from perceiver_io_tpu.analysis.sanitizers import (
    LockOrderRecorder,
    LockOrderViolation,
    RecompileDetected,
    no_implicit_transfers,
    no_recompile,
    record_lock_order,
)

__all__ = [
    "Baseline",
    "FileContext",
    "Finding",
    "LockOrderRecorder",
    "LockOrderViolation",
    "RecompileDetected",
    "Rule",
    "all_rules",
    "no_implicit_transfers",
    "no_recompile",
    "record_lock_order",
    "scan_paths",
]
