"""pitlint CLI: the repo-invariant static pass, one JSON line on stdout.

Usage::

    python tools/lint.py                  # full pass + sharding cross-check
    python tools/lint.py --changed        # only `git diff --name-only` files
    python tools/lint.py path/to/file.py  # explicit paths
    python tools/lint.py --write-baseline # re-absorb current findings

Exit 0 iff zero NON-BASELINED findings (and the cross-check passes); the
single stdout line reports counts by rule. Per-finding detail rides stderr.
CPU-only by construction (``ensure_cpu_only`` runs before jax can
initialize any backend — it never claims a chip).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perceiver_io_tpu.utils.platform import ensure_cpu_only  # noqa: E402

ensure_cpu_only()

from perceiver_io_tpu.analysis import core  # noqa: E402
from perceiver_io_tpu.utils.jsonline import emit_json_line, log  # noqa: E402

# scope lives in analysis/core.py — ONE definition shared with the tier-1
# test so the local loop, CI, and the baseline can never disagree
DEFAULT_TARGETS = core.DEFAULT_TARGETS
TEST_FAULT_TARGETS = core.TEST_FAULT_TARGETS
DOC_TARGETS = core.DOC_TARGETS

# the cross-check matters only when these move; --changed runs skip it
# otherwise so the local loop never pays the jax import
CROSSCHECK_TRIGGERS = ("perceiver_io_tpu/parallel/sharding.py",
                       "perceiver_io_tpu/models/")


def changed_files() -> list:
    """Tracked changes vs HEAD plus untracked files — a brand-new tool with
    violations must not slip past the fast local loop unseen."""
    names: list = []
    for cmd in (["git", "diff", "--name-only", "HEAD"],
                ["git", "ls-files", "--others", "--exclude-standard"]):
        out = subprocess.run(
            cmd, cwd=ROOT, capture_output=True, text=True, check=False,
        ).stdout
        names.extend(l.strip() for l in out.splitlines() if l.strip())
    return sorted(set(names))


def scan_docs(paths) -> list:
    from perceiver_io_tpu.analysis.rules_faults import FaultSiteRule

    rule = FaultSiteRule()
    findings = []
    for rel in paths:
        path = os.path.join(ROOT, rel)
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                findings.extend(rule.check_text(rel, f.read()))
    return findings


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("paths", nargs="*",
                        help=f"files/dirs to lint (default: "
                             f"{' '.join(DEFAULT_TARGETS)})")
    parser.add_argument("--changed", action="store_true",
                        help="lint only files changed vs HEAD plus "
                             "untracked files (fast local loop)")
    parser.add_argument("--baseline", default=core.DEFAULT_BASELINE,
                        help="baseline-suppression file")
    parser.add_argument("--write-baseline", action="store_true",
                        help="absorb every current finding into the baseline "
                             "(then exits 0)")
    parser.add_argument("--no-crosscheck", action="store_true",
                        help="skip the sharding-rules × presets audit")
    args = parser.parse_args()

    fault_only_targets: list = []
    # full_scope: whether this invocation covers everything the baseline
    # covers — stale-entry detection (and --write-baseline pruning) is only
    # meaningful then; a partial scan would misread every entry for an
    # unscanned file as paid-down debt
    full_scope = not args.changed and not args.paths
    if args.changed:
        changed = changed_files()
        rels = [f for f in changed if f.endswith(".py")
                and os.path.exists(os.path.join(ROOT, f))]
        targets = [os.path.join(ROOT, f) for f in rels
                   if f.startswith(("perceiver_io_tpu/", "tools/"))
                   or f == "bench.py"]
        # tests/ carries PIT_FAULTS drill specs but legitimately prints and
        # reads wall clocks: fault-site rule only (same split as CI)
        fault_only_targets = [os.path.join(ROOT, f) for f in rels
                              if f.startswith("tests/")]
        run_crosscheck = not args.no_crosscheck and any(
            f.startswith(CROSSCHECK_TRIGGERS) for f in rels)
        doc_targets = [f for f in changed if f.endswith(".md")
                       and os.path.exists(os.path.join(ROOT, f))]
    elif args.paths:
        targets = [os.path.abspath(p) for p in args.paths]
        run_crosscheck = not args.no_crosscheck
        doc_targets = []
    else:
        targets = [os.path.join(ROOT, t) for t in DEFAULT_TARGETS]
        fault_only_targets = [os.path.join(ROOT, t)
                              for t in TEST_FAULT_TARGETS]
        run_crosscheck = not args.no_crosscheck
        doc_targets = list(DOC_TARGETS)

    # ONE tree walk: materialize the file lists, then feed them to the
    # scanner (iter_py_files passes file paths through unchanged)
    files = list(core.iter_py_files(targets))
    fault_only_files = list(core.iter_py_files(fault_only_targets))
    scanned = len(files) + len(fault_only_files)
    findings = core.scan_paths(files, root=ROOT) if files else []
    if fault_only_files:
        from perceiver_io_tpu.analysis.rules_faults import FaultSiteRule

        findings.extend(core.scan_paths(
            fault_only_files, rules=[FaultSiteRule()], root=ROOT))
    findings.extend(scan_docs(doc_targets))
    # repo hygiene, every invocation (one cheap walk): orphan bytecode must
    # never keep a deleted module importable — it is a property of the TREE,
    # not of any changed file, so --changed runs check it too
    findings.extend(core.scan_orphan_bytecode(
        ROOT, targets=(*DEFAULT_TARGETS, *TEST_FAULT_TARGETS)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    if run_crosscheck:
        from perceiver_io_tpu.analysis.crosscheck import audit_sharding_rules

        findings.extend(audit_sharding_rules())

    baseline = core.Baseline.load(args.baseline)
    if args.write_baseline:
        for f in findings:
            baseline.keys.setdefault(f.key(), "absorbed at baseline write")
        if full_scope:
            # pruning needs the full picture: on a partial scan every entry
            # for an unscanned file would look paid-down and be deleted
            for stale in baseline.stale_keys(findings):
                del baseline.keys[stale]
        else:
            log("lint: partial scan — baseline entries absorbed, none "
                "pruned (run without --changed/paths to prune)")
        baseline.save(args.baseline)
        log(f"lint: baseline rewritten with {len(baseline.keys)} entries "
            f"-> {args.baseline}")

    new, baselined = baseline.split(findings)
    stale = baseline.stale_keys(findings) if full_scope else []

    by_rule: dict = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    for f in new:
        log(f"lint: NEW {f.render()}")
    for key in stale:
        log(f"lint: stale baseline entry (debt paid — prune it): {key}")

    ok = not new and not stale
    emit_json_line({
        "tool": "pitlint",
        "files": scanned,
        "findings_total": len(findings),
        "by_rule": dict(sorted(by_rule.items())),
        "baselined": len(baselined),
        "new": len(new),
        "stale_baseline": len(stale),
        "crosscheck": bool(run_crosscheck),
        "ok": ok,
    })
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
