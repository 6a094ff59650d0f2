"""PIT-JIT: no host side effects inside functions reachable from jitted code.

A clock read, ``np.random`` draw, ``print``, file touch, or ``.item()`` /
``float()`` scalar fetch inside traced code is at best a silent
trace-time-frozen constant and at worst a host sync on every dispatch (the
pipeline stalls until the device catches up). The compiler never complains — the value just goes stale or
the hot path just gets slow.

Root set (per file):

- functions syntactically handed to the jit family: ``@jax.jit`` /
  ``@partial(jax.jit, ...)`` decorators, and names passed to
  ``jax.jit(f)`` / ``pjit(f)`` / ``pl.pallas_call(kernel, ...)`` /
  ``shard_map(f, ...)`` / ``jax.checkpoint(f)``;
- every function/method in the always-traced modules (``ops/``,
  ``models/`` — the compute core; their code exists to run under ``jit``).

Reachability then propagates through same-file calls: ``name(...)`` to a
function defined in the file, ``self.m(...)`` to a method of any class in
the file. Cross-file reachability is deliberately out of scope — the traced
core is module-local by construction here, and a lint that imports nothing
stays fast and safe.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from perceiver_io_tpu.analysis.core import (
    FileContext,
    Finding,
    Rule,
    dotted_name,
)

_JIT_WRAPPERS = {
    "jit", "jax.jit", "pjit", "jax.pjit",
    "shard_map", "jax.experimental.shard_map.shard_map",
    "pallas_call", "pl.pallas_call",
    "checkpoint", "jax.checkpoint", "jax.remat",
}

_CLOCK_CALLS = {
    "time.time", "time.monotonic", "time.perf_counter", "time.process_time",
    "time.sleep", "time.time_ns", "time.monotonic_ns",
    "time.perf_counter_ns",
}

_HOST_RANDOM_PREFIXES = ("np.random.", "numpy.random.", "random.")

_SHAPE_ATTRS = {"shape", "ndim", "size", "dtype"}


def _qualname(stack: List[str]) -> str:
    return ".".join(stack)


class _DefCollector(ast.NodeVisitor):
    """Every function/method (including nested) with its qualname, plus the
    set of class names (for ``self.m()`` resolution)."""

    def __init__(self):
        self.defs: Dict[str, List[Tuple[str, ast.AST]]] = {}  # bare name ->
        self.by_qual: Dict[str, ast.AST] = {}
        self._stack: List[str] = []

    def _add(self, node):
        qual = _qualname(self._stack + [node.name])
        self.defs.setdefault(node.name, []).append((qual, node))
        self.by_qual[qual] = node
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _add
    visit_AsyncFunctionDef = _add

    def visit_ClassDef(self, node):
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()


def _is_jit_wrapper(func_node: ast.AST) -> bool:
    name = dotted_name(func_node)
    if name is None:
        return False
    return name in _JIT_WRAPPERS or name.endswith(".jit") \
        or name.endswith(".pallas_call")


class JitPurityRule(Rule):
    rule_id = "PIT-JIT"

    # modules whose whole surface is traced code (the compute core)
    PURE_MODULE_PREFIXES = (
        "perceiver_io_tpu/ops/",
        "perceiver_io_tpu/models/",
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        collector = _DefCollector()
        collector.visit(ctx.tree)
        roots = self._roots(ctx, collector)
        reachable = self._propagate(collector, roots)
        findings: List[Finding] = []
        for qual in sorted(reachable):
            node = collector.by_qual[qual]
            findings.extend(self._scan_body(ctx, node, qual, reachable,
                                            collector))
        return findings

    # -- root discovery ------------------------------------------------------

    def _roots(self, ctx: FileContext, collector: _DefCollector) -> Set[str]:
        roots: Set[str] = set()
        if any(ctx.relpath.startswith(p) for p in self.PURE_MODULE_PREFIXES):
            roots.update(collector.by_qual)

        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    target = deco.func if isinstance(deco, ast.Call) else deco
                    if _is_jit_wrapper(target) or (
                            isinstance(deco, ast.Call)
                            and dotted_name(deco.func) in
                            ("partial", "functools.partial")
                            and deco.args
                            and _is_jit_wrapper(deco.args[0])):
                        roots.update(q for q, n in
                                     collector.defs.get(node.name, ())
                                     if n is node)
            elif isinstance(node, ast.Call) and _is_jit_wrapper(node.func):
                for arg in node.args[:1]:  # the wrapped fn is positional 0
                    if isinstance(arg, ast.Name):
                        roots.update(
                            q for q, _ in collector.defs.get(arg.id, ()))
        return roots

    # -- reachability --------------------------------------------------------

    def _propagate(self, collector: _DefCollector,
                   roots: Set[str]) -> Set[str]:
        reachable = set(roots)
        frontier = list(roots)
        while frontier:
            qual = frontier.pop()
            node = collector.by_qual[qual]
            for callee in self._local_callees(node, collector):
                if callee not in reachable:
                    reachable.add(callee)
                    frontier.append(callee)
        return reachable

    def _local_callees(self, node: ast.AST,
                       collector: _DefCollector) -> Iterable[str]:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            if isinstance(sub.func, ast.Name):
                for qual, _ in collector.defs.get(sub.func.id, ()):
                    yield qual
            elif (isinstance(sub.func, ast.Attribute)
                  and isinstance(sub.func.value, ast.Name)
                  and sub.func.value.id == "self"):
                for qual, _ in collector.defs.get(sub.func.attr, ()):
                    yield qual

    # -- the banned-construct scan -------------------------------------------

    def _scan_body(self, ctx: FileContext, func: ast.AST, qual: str,
                   reachable: Set[str],
                   collector: _DefCollector) -> Iterable[Finding]:
        findings: List[Finding] = []

        def walk(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                    continue  # scanned on its own iff itself reachable
                if isinstance(child, ast.Call):
                    msg = self._banned(child)
                    if msg:
                        findings.append(self.finding(ctx, child, qual, msg))
                walk(child)

        walk(func)
        return findings

    def _banned(self, call: ast.Call) -> str:
        name = dotted_name(call.func)
        if name in _CLOCK_CALLS:
            return (f"calls {name}() in jit-reachable code (clock reads "
                    f"freeze at trace time)")
        if name and name.startswith(_HOST_RANDOM_PREFIXES):
            return (f"calls {name}() in jit-reachable code (host RNG is "
                    f"trace-time-frozen; use jax.random)")
        if isinstance(call.func, ast.Attribute) and call.func.attr == "item" \
                and not call.args:
            return (".item() in jit-reachable code (host scalar fetch — a "
                    "sync that stalls the dispatch pipeline)")
        if name in ("print", "open", "input"):
            return (f"calls {name}() in jit-reachable code (host I/O runs at "
                    f"trace time, not per step)")
        if name in ("float", "int") and len(call.args) == 1 \
                and self._is_scalar_fetch(call.args[0]):
            return (f"{name}() scalar fetch in jit-reachable code (device "
                    f"sync — keep values traced)")
        return ""

    @staticmethod
    def _is_scalar_fetch(arg: ast.AST) -> bool:
        """``float(metrics["loss"])``-style fetches; static shape/config math
        (``int(x.shape[0])``, ``float(len(xs))``, literals) stays allowed."""
        if isinstance(arg, ast.Constant) or isinstance(arg, ast.BinOp):
            return False
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Attribute) and sub.attr in _SHAPE_ATTRS:
                return False
            if isinstance(sub, ast.Call) and dotted_name(sub.func) in (
                    "len", "ord", "np.prod", "math.prod"):
                return False
        # bare names (config scalars, bools) stay allowed — the fetch shapes
        # are metrics["loss"]-style subscripts and method-call results
        return isinstance(arg, (ast.Subscript, ast.Call))
