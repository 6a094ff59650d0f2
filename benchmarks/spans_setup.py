"""The set-up stretch of the program's spans, for the readers that split
``setup_s`` by name (``spans.py`` has the arithmetic and is left as it is).

Set-up, on the program's clock, runs from the start of the process's first
span to the start of the window's ``train.fit``. Since PR 40 the first span is
the package's own ``import`` (``perceiver_io_tpu/__init__.py`` takes the clock
in its first statement), so the stretch holds everything the process does from the
program's first import on; what comes before it (the interpreter, the
harness's own imports, ``jax`` and the runtime's start in ``run.require_chips``)
is the harness's and has no program span. ``import`` spans carry ``module``
and nest (the package's holds ``jax``'s, ``cli.common``'s holds
``orbax.checkpoint``'s), so every reader takes a union.

- ``stretch()``: the spans that had ended when the window's fit began, oldest
  first, and that instant; None where the process holds no ``train.fit``.
- ``named_s(*names)``: seconds of the stretch covered by the spans of these
  names; None too where the stretch holds no such span (a program that keeps
  none, as the parent of the PR that added ``import``).
- ``unnamed_s()``: seconds of the stretch that NO span covers, whatever its
  name; None where the stretch holds no ``import`` span: the first span of
  such a program is wherever its first compile happened, and the number would
  be of another stretch.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks import spans


def stretch() -> Optional[Tuple[List[spans.Span], int]]:
    everything = spans.program_spans()
    fit = spans.window_fit(everything)
    if fit is None:
        return None
    return spans.before(everything, fit["start_ns"]), fit["start_ns"]


def named_s(*names: str) -> Optional[float]:
    found = stretch()
    if found is None:
        return None
    of_names = spans.named(found[0], *names)
    return spans.union_s(of_names) if of_names else None


def unnamed_s() -> Optional[float]:
    found = stretch()
    if found is None or not spans.named(found[0], "import"):
        return None
    early, end_ns = found
    start_ns = min(s["start_ns"] for s in early)
    return (end_ns - start_ns) / 1e9 - spans.union_s(early)
