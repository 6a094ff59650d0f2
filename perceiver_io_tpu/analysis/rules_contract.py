"""PIT-CONTRACT: the tools/ + bench.py stdout contract.

The driver parses ONE JSON line from the stdout of ``bench.py`` and the
``tools/`` benches (CLAUDE.md); everything human-readable rides stderr.

Flags, in files under ``tools/`` and in ``bench.py``:

- ``print(...)`` without an explicit ``file=`` destination (stdout is
  reserved for :func:`perceiver_io_tpu.utils.jsonline.emit_json_line`);
  ``print(..., file=sys.stderr)`` and prints into open file objects pass.
- ``sys.stdout.write(...)`` / writes through a ``sys.stdout`` alias.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from perceiver_io_tpu.analysis.core import (
    FileContext,
    Finding,
    Rule,
    ScopedVisitor,
    dotted_name,
)

SANCTIONED_EMITTERS = {"emit_json_line"}


def _applies(relpath: str) -> bool:
    return relpath.startswith("tools/") or relpath == "bench.py"


class _Visitor(ScopedVisitor):
    def __init__(self, rule: "ToolContractRule", ctx: FileContext):
        super().__init__()
        self.rule = rule
        self.ctx = ctx
        self.findings: List[Finding] = []

    def visit_Call(self, node: ast.Call) -> None:
        name = dotted_name(node.func)
        if name == "print":
            file_kw = next(
                (kw for kw in node.keywords if kw.arg == "file"), None)
            if file_kw is None or dotted_name(file_kw.value) in (
                    "sys.stdout", "stdout"):
                self.findings.append(self.rule.finding(
                    self.ctx, node, self.scope,
                    "print() to stdout — tools reserve stdout for the one "
                    "JSON line; use utils.jsonline.emit_json_line for the "
                    "record and file=sys.stderr for logs"))
        elif name in ("sys.stdout.write", "stdout.write"):
            self.findings.append(self.rule.finding(
                self.ctx, node, self.scope,
                "writes sys.stdout directly — stdout is reserved for "
                "utils.jsonline.emit_json_line"))
        self.generic_visit(node)


class ToolContractRule(Rule):
    rule_id = "PIT-CONTRACT"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _applies(ctx.relpath):
            return ()
        visitor = _Visitor(self, ctx)
        visitor.visit(ctx.tree)
        return visitor.findings
