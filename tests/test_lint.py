"""pitlint (perceiver_io_tpu/analysis): per-rule fixtures, baseline
round-trip, the tier-1 repo-wide static pass, the sharding cross-check, the
``tools/lint.py`` one-JSON-line contract, and the runtime sanitizers.

The repo-wide pass IS the enforcement: it runs the same rules
``tools/lint.py`` runs over ``perceiver_io_tpu/``, ``tools/``, and
``bench.py`` and fails on any non-baselined finding — a new stray
``.item()`` on the dispatch path or a renamed fault site breaks tier-1, not
a reviewer's memory."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from perceiver_io_tpu.analysis import (
    Baseline,
    FileContext,
    Finding,
    LockOrderViolation,
    RecompileDetected,
    no_implicit_transfers,
    no_recompile,
    record_lock_order,
    scan_paths,
)
from perceiver_io_tpu.analysis.core import all_rules
from perceiver_io_tpu.analysis.rules_clock import DurationClockRule
from perceiver_io_tpu.analysis.rules_contract import ToolContractRule
from perceiver_io_tpu.analysis.rules_faults import FaultSiteRule
from perceiver_io_tpu.analysis.rules_locks import LockDisciplineRule
from perceiver_io_tpu.analysis.rules_purity import JitPurityRule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _check(rule, src, relpath="pkg/mod.py"):
    ctx = FileContext(relpath, relpath, textwrap.dedent(src))
    return [f for f in rule.check(ctx) if not ctx.suppressed(f.rule, f.line)]


# -- PIT-JIT ------------------------------------------------------------------


def test_jit_purity_flags_clock_rng_io_and_fetches_in_jitted_code():
    src = """
    import time
    import jax
    import numpy as np

    def helper(x):
        return x.mean().item()

    def traced(x):
        t = time.time()
        noise = np.random.normal()
        print("tracing")
        loss = float(metrics["loss"])
        return helper(x) * t * noise * loss

    step = jax.jit(traced)
    """
    found = _check(JitPurityRule(), src)
    msgs = " | ".join(f.message for f in found)
    assert any(f.scope == "traced" and "time.time" in f.message
               for f in found)
    assert "np.random" in msgs
    assert "print()" in msgs
    assert "float() scalar fetch" in msgs
    # reachability: helper is only reachable THROUGH the jitted root
    assert any(f.scope == "helper" and ".item()" in f.message for f in found)


def test_jit_purity_ignores_host_code_and_decorated_roots_work():
    host_only = """
    import time

    def host_loop(x):
        t0 = time.monotonic()
        print("serving", x)
        return time.monotonic() - t0
    """
    assert _check(JitPurityRule(), host_only) == []

    decorated = """
    import time
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def step(x):
        time.sleep(1.0)
        return x
    """
    found = _check(JitPurityRule(), decorated)
    assert len(found) == 1 and "time.sleep" in found[0].message


def test_jit_purity_treats_ops_models_modules_as_traced():
    src = """
    import time

    def anything(x):
        return time.monotonic()
    """
    assert _check(JitPurityRule(), src, "perceiver_io_tpu/other/m.py") == []
    found = _check(JitPurityRule(), src, "perceiver_io_tpu/ops/m.py")
    assert len(found) == 1 and found[0].rule == "PIT-JIT"


# -- PIT-CONTRACT -------------------------------------------------------------


def test_contract_flags_stdout_in_tools_only():
    src = """
    import sys
    import jax

    def main():
        backend = jax.default_backend()
        print("human table row")
        print("sneaky", file=sys.stdout)
        print("log line", file=sys.stderr)
    """
    found = _check(ToolContractRule(), src, "tools/somebench.py")
    assert sum("print() to stdout" in f.message for f in found) == 2
    # the stderr print passes, and so does the plain backend query: a local
    # chip answers jax.default_backend() at once, no wrapper needed
    assert len(found) == 2
    # identical code outside tools/ is not this rule's business
    assert _check(ToolContractRule(), src, "perceiver_io_tpu/x.py") == []


def test_contract_sanctions_emit_json_line_and_plain_device_queries():
    src = """
    import sys
    import jax
    from perceiver_io_tpu.utils.jsonline import emit_json_line

    def main():
        if jax.devices()[0].platform != "tpu":
            sys.stdout.write("no chip")
        emit_json_line({"metric": "x", "value": jax.device_count()})
    """
    found = _check(ToolContractRule(), src, "tools/somebench.py")
    assert len(found) == 1 and "sys.stdout" in found[0].message


# -- PIT-FAULT ----------------------------------------------------------------


def test_fault_rule_validates_sites_specs_and_fstring_prefixes():
    src = """
    from perceiver_io_tpu.resilience import FaultSpec, faults

    def instrumented(name, env, monkeypatch):
        faults.inject("engine.dispatch")            # registered
        faults.inject(f"engine.dispatch.{name}")    # suffixed site
        faults.fire("deploy.publish", None)         # registered
        faults.inject("engine.dispach")             # typo'd
        faults.inject(f"engine.warmup.{name}")      # unregistered prefix
        FaultSpec(site="trainer.metrics", kind="nan", at=(1,))
        FaultSpec(site="trainer.metricz", kind="nan", at=(1,))
        env["PIT_FAULTS"] = "deploy.publish:nan@2"
        env["PIT_FAULTS"] = "deploy.publsh:nan@2"
        monkeypatch.setenv("PIT_FAULTS", "engine.dispatch:transient@1")
        monkeypatch.setenv("PIT_FAULTS", "engine.dispatch:transientt@1")
    """
    found = _check(FaultSiteRule(), src)
    assert len(found) == 5, [f.message for f in found]
    assert sum("engine.dispach" in f.message for f in found) == 1
    assert sum("prefix" in f.message for f in found) == 1
    assert sum("trainer.metricz" in f.message for f in found) == 1
    assert sum("deploy.publsh" in f.message for f in found) == 1
    assert sum("transientt" in f.message for f in found) == 1


def test_fault_rule_checks_doc_examples():
    rule = FaultSiteRule()
    good = 'drill with PIT_FAULTS="engine.dispatch:transient@2,5" set'
    bad = 'drill with PIT_FAULTS="engine.dispatch:sometimes@2" set'
    meta = 'the grammar is PIT_FAULTS="site:kind@WHEN" per clause'
    assert rule.check_text("DOC.md", good) == []
    assert rule.check_text("DOC.md", meta) == []  # meta-variables: not a drill
    found = rule.check_text("DOC.md", bad)
    assert len(found) == 1 and found[0].line == 1


# -- PIT-LOCK -----------------------------------------------------------------


def test_lock_rule_enforces_guarded_by_declarations():
    src = """
    import threading

    class Engine:
        _guarded_by = {"_stats": "_stats_lock", "_backlog": "_stats_lock"}
        _assumes_locked = ("caller_holds",)

        def __init__(self):
            self._stats_lock = threading.Lock()
            self._stats = {}
            self._backlog = 0  # __init__ is exempt (not shared yet)

        def good(self):
            with self._stats_lock:
                self._stats["x"] = self._backlog

        def bad(self):
            return self._stats["x"]

        def caller_holds(self):
            self._backlog += 1

        def _drain_locked(self):
            self._backlog -= 1

        def fast_path(self):
            return self._backlog  # pitlint: ignore[PIT-LOCK] racy diagnostic
    """
    found = _check(LockDisciplineRule(), src)
    assert len(found) == 1
    assert found[0].scope == "Engine.bad" and "_stats" in found[0].message


def test_lock_rule_with_items_evaluate_outside_the_lock():
    src = """
    class C:
        _guarded_by = {"_table": "_lock"}

        def swap(self):
            with self._locks[self._table]:  # _table read BEFORE acquisition
                pass
    """
    found = _check(LockDisciplineRule(), src)
    assert len(found) == 1 and found[0].scope == "C.swap"


# -- PIT-CLOCK ----------------------------------------------------------------


def test_clock_rule_flags_wall_clock_durations_only():
    src = """
    import time

    def bad_duration():
        t0 = time.time()
        work()
        return time.time() - t0

    def good_duration():
        t0 = time.monotonic()
        work()
        return time.monotonic() - t0

    def good_timestamp():
        return {"published_unix_s": time.time()}

    class T:
        def __init__(self):
            self._t0 = time.time()

        def age(self):
            return now() - self._t0
    """
    found = _check(DurationClockRule(), src)
    scopes = sorted(f.scope for f in found)
    assert scopes == ["T.age", "bad_duration"], found


def test_pragma_suppresses_a_rule_on_its_line():
    src = """
    import time

    def epoch_from_boot(uptime_s):
        return time.time() - uptime_s  # pitlint: ignore[PIT-CLOCK] epoch math
    """
    assert _check(DurationClockRule(), src) == []


# -- PIT-SPAN -----------------------------------------------------------------


def test_span_rule_validates_literal_names_against_the_registry():
    """The PIT-FAULT pattern for tracing: a record_span site naming an
    unregistered span cannot reach HEAD — a typo'd hop would silently
    decouple from the assembler."""
    from perceiver_io_tpu.analysis.rules_spans import SpanNameRule

    src = """
    import perceiver_io_tpu.obs as obs
    from perceiver_io_tpu.obs.reqtrace import record_span

    def good(ctx, t0):
        obs.record_span("router_request", ctx, t0, 0.1)
        record_span("replica_serve", ctx, t0, 0.1, replica="r0")

    def bad(ctx, t0):
        obs.record_span("router_requests_typo", ctx, t0, 0.1)

    def dynamic(ctx, t0, name):
        record_span(name, ctx, t0, 0.1)  # non-literal: runtime's problem
    """
    found = _check(SpanNameRule(), src)
    assert len(found) == 1
    assert found[0].scope == "bad"
    assert "router_requests_typo" in found[0].message
    assert "SPAN_NAMES" in found[0].message

    # the registry module itself and the lint fixtures are excluded
    assert SpanNameRule().check(
        FileContext("x", "perceiver_io_tpu/obs/reqtrace.py",
                    'record_span("not_a_span", None, 0, 0)')) == ()


# -- PIT-METRIC ---------------------------------------------------------------


def test_metric_rule_resolves_literals_against_registered_instruments():
    """The PIT-SPAN pattern for the alerting layer: an AlertRule(metric=)
    or series_key() literal naming an instrument nothing registers would
    build a rule that silently never fires — it must fail lint instead.
    The known set derives from the package's .counter/.gauge/.histogram
    registration literals."""
    from perceiver_io_tpu.analysis.rules_metrics import (
        MetricNameRule,
        known_metric_names,
        strip_series_key,
    )

    known = known_metric_names()
    # spot-check the scan found real registrations across layers
    for name in ("serving_queue_depth", "slo_error_budget_burn_rate",
                 "fleet_replica_slo_burn", "fleet_scrape_age_s",
                 "eventlog_dropped_total", "alert_state",
                 "router_latency_seconds"):
        assert name in known, f"{name} missing from the known-metric scan"
    assert strip_series_key(
        'serving_phase_seconds{engine="e",phase="queue"}:p99') \
        == "serving_phase_seconds"
    assert strip_series_key("reqs_total:count") == "reqs_total"
    assert strip_series_key("ns:custom") == "ns:custom"  # not a field

    src = """
    import perceiver_io_tpu.obs as obs
    from perceiver_io_tpu.obs import AlertRule

    def good(store):
        obs.AlertRule(name="q", metric="serving_queue_depth", threshold=1)
        AlertRule("burn", "slo_error_budget_burn_rate:p99")
        store.last(obs.series_key("router_latency_seconds",
                                  {"router": "r"}, field="p99"))

    def bad():
        obs.AlertRule(name="q", metric="serving_queue_depht", threshold=1)
        obs.series_key("router_latency_secondz")

    def dynamic(name):
        obs.AlertRule(name="d", metric=name)  # runtime's problem
    """
    found = _check(MetricNameRule(), src)
    assert len(found) == 2
    assert all(f.scope == "bad" for f in found)
    assert "serving_queue_depht" in found[0].message
    assert "router_latency_secondz" in found[1].message

    # the lint suite's own fixtures are excluded
    assert MetricNameRule().check(
        FileContext("x", "tests/test_lint.py",
                    'series_key("not_a_metric")')) == ()


# -- baseline -----------------------------------------------------------------


def test_baseline_round_trip_split_and_stale_detection(tmp_path):
    f1 = Finding("PIT-CLOCK", "a.py", 10, "f", "msg one")
    f2 = Finding("PIT-JIT", "b.py", 20, "g", "msg two")
    bl = Baseline()
    bl.keys[f1.key()] = "justified: epoch math"
    bl.keys["PIT-LOCK|gone.py|h|paid down"] = "old debt"
    path = str(tmp_path / "baseline.txt")
    bl.save(path)
    loaded = Baseline.load(path)
    assert loaded.keys == bl.keys  # justifications survive the round trip

    new, old = loaded.split([f1, f2])
    assert old == [f1] and new == [f2]
    # line numbers are NOT part of the key: the entry survives edits above it
    assert Finding("PIT-CLOCK", "a.py", 999, "f", "msg one") in loaded
    assert loaded.stale_keys([f1, f2]) == ["PIT-LOCK|gone.py|h|paid down"]


# -- the tier-1 repo-wide pass ------------------------------------------------


def test_repo_static_pass_is_clean_and_fast():
    """THE enforcement test: the full rule set over the shared lint scope
    (core.DEFAULT_TARGETS — perceiver_io_tpu/, tools/, bench.py; tests/
    under the fault-site rule only; PIT_FAULTS examples in the markdown
    docs) yields zero non-baselined findings, inside the budget (<20 s on
    this container; measured ~2 s). ONE scope definition with
    tools/lint.py, so the fast local loop and CI cannot disagree."""
    from perceiver_io_tpu.analysis.core import (
        DEFAULT_BASELINE,
        DEFAULT_TARGETS,
        DOC_TARGETS,
        TEST_FAULT_TARGETS,
    )

    t0 = time.monotonic()
    findings = scan_paths(
        [os.path.join(ROOT, t) for t in DEFAULT_TARGETS], root=ROOT)
    rule = FaultSiteRule()
    findings.extend(scan_paths(
        [os.path.join(ROOT, t) for t in TEST_FAULT_TARGETS],
        rules=[rule], root=ROOT))
    # doc halves of the fault rule (PIT_FAULTS examples in markdown)
    for doc in DOC_TARGETS:
        p = os.path.join(ROOT, doc)
        if os.path.exists(p):
            with open(p, encoding="utf-8") as fh:
                findings.extend(rule.check_text(doc, fh.read()))
    elapsed = time.monotonic() - t0
    baseline = Baseline.load(DEFAULT_BASELINE)
    new, _ = baseline.split(findings)
    assert new == [], "NEW pitlint findings:\n" + "\n".join(
        f.render() for f in new)
    stale = baseline.stale_keys(findings)
    assert stale == [], f"stale baseline entries (prune them): {stale}"
    assert elapsed < 20.0, f"static pass took {elapsed:.1f}s (budget 20s)"


def test_sharding_rules_cover_every_preset():
    """Satellite: every parallel/sharding.py path-regex matches >=1 param
    path in EACH models/presets.py preset tree (CPU-only shape tracing) —
    a torch-parity param rename cannot silently strand a sharding rule."""
    from perceiver_io_tpu.analysis.crosscheck import audit_sharding_rules

    assert audit_sharding_rules() == []


def test_sharding_crosscheck_catches_a_stranded_rule(monkeypatch):
    from jax.sharding import PartitionSpec as P

    import perceiver_io_tpu.parallel.sharding as sharding
    from perceiver_io_tpu.analysis.crosscheck import audit_sharding_rules

    from perceiver_io_tpu.analysis.crosscheck import _preset_builders

    monkeypatch.setattr(
        sharding, "PARAM_RULES",
        tuple(sharding.PARAM_RULES) + ((r"renamed_proj/kernel$", P()),))
    found = audit_sharding_rules()
    # one finding per audited preset (the MLM family + the r18 AR presets)
    assert len(found) == len(_preset_builders()) >= 5
    assert all("renamed_proj" in f.message for f in found)


# -- tools/lint.py contract ---------------------------------------------------


def _run_lint(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "lint.py"),
         "--no-crosscheck", *args],
        capture_output=True, text=True, timeout=300,
    )


def test_lint_cli_clean_at_head_one_json_line():
    proc = _run_lint()
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    assert record["tool"] == "pitlint" and record["ok"] is True
    assert record["new"] == 0 and record["stale_baseline"] == 0


def test_lint_cli_nonzero_exit_and_one_json_line_on_violation(tmp_path):
    bad = tmp_path / "bad_tool.py"
    bad.write_text(textwrap.dedent("""
        import time

        def measure():
            t0 = time.time()
            work()
            return time.time() - t0
    """))
    proc = _run_lint(str(bad))
    assert proc.returncode == 1, (proc.stdout, proc.stderr[-1000:])
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    record = json.loads(lines[0])
    assert record["ok"] is False and record["new"] >= 1
    assert record["by_rule"].get("PIT-CLOCK", 0) >= 1
    assert "PIT-CLOCK" in proc.stderr  # detail rides stderr


# -- runtime sanitizers -------------------------------------------------------


def test_no_recompile_passes_warm_and_trips_cold():
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    f(jnp.ones(5))  # compile OUTSIDE the guard
    with no_recompile():
        f(jnp.ones(5))  # cache hit: silent
    with pytest.raises(RecompileDetected, match="compilation"):
        with no_recompile():
            jax.jit(lambda x: x * 3.0 - 7.0)(jnp.ones(6))


def test_transfer_guard_is_really_armed():
    """CPU cannot exhibit a device->host transfer (arrays are host-resident)
    so the d2h default is structural here and bites on device backends; the
    'all' direction proves the arming mechanism works in-process."""
    f = jax.jit(lambda x: x + 1)
    f(np.ones(3))  # warm (and an implicit transfer OUTSIDE the guard: fine)
    with no_implicit_transfers():
        jax.device_get(f(jnp.ones(3)))  # explicit fetch stays legal
    with pytest.raises(Exception, match="[Dd]isallowed"):
        with no_implicit_transfers(direction="all"):
            f(np.ones(3))  # numpy arg -> implicit host-to-device
    with pytest.raises(ValueError, match="unknown direction"):
        with no_implicit_transfers(direction="d2h"):  # typo must not
            pass                                      # silently mis-arm


def test_lock_order_recorder_benign_and_cycle():
    with record_lock_order() as rec:
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        with a:  # same order again: consistent
            with b:
                pass
    assert rec.acquisitions == 4 and rec.find_cycle() is None

    with pytest.raises(LockOrderViolation, match="cycle"):
        with record_lock_order():
            a = threading.Lock()
            b = threading.Lock()
            a.site, b.site = "siteA", "siteB"  # stable node names
            with a:
                with b:
                    pass
            with b:
                with a:
                    pass


def test_lock_order_recorder_body_error_wins_over_check():
    with pytest.raises(ValueError, match="body"):
        with record_lock_order():
            a = threading.Lock()
            b = threading.Lock()
            a.site, b.site = "sA", "sB"
            with a:
                with b:
                    pass
            with b:
                with a:
                    raise ValueError("body")


# -- repo hygiene: orphan bytecode (PIT-BYTECODE, r22) ------------------------


def test_orphan_bytecode_scan_flags_residue(tmp_path):
    """Deleted modules must be GONE: a legacy-layout pyc is importable in
    place of (or alongside) its source, and an orphan __pycache__ pyc is
    residue from a deleted module. Live cache entries are not findings."""
    from perceiver_io_tpu.analysis.core import scan_orphan_bytecode

    pkg = tmp_path / "pkg"
    (pkg / "__pycache__").mkdir(parents=True)
    (pkg / "live.py").write_text("x = 1\n")
    (pkg / "__pycache__" / "live.cpython-311.pyc").write_bytes(b"\x00")
    (pkg / "__pycache__" / "deleted.cpython-311.pyc").write_bytes(b"\x00")
    (pkg / "ghost.pyc").write_bytes(b"\x00")
    (pkg / "live.pyc").write_bytes(b"\x00")

    findings = scan_orphan_bytecode(str(tmp_path), targets=("pkg",))
    assert all(f.rule == "PIT-BYTECODE" for f in findings)
    by_path = {f.path: f.message for f in findings}
    assert "in place of deleted" in by_path["pkg/ghost.pyc"]
    assert "alongside" in by_path["pkg/live.pyc"]
    assert "residue" in by_path["pkg/__pycache__/deleted.cpython-311.pyc"]
    assert "pkg/__pycache__/live.cpython-311.pyc" not in by_path  # live


def test_repo_has_no_orphan_bytecode():
    """The r22 satellite pin: the stale serving/__pycache__/transport pycs
    are deleted and nothing like them comes back (lint runs this scan on
    every invocation — same scope as tools/lint.py)."""
    from perceiver_io_tpu.analysis.core import (
        DEFAULT_TARGETS,
        TEST_FAULT_TARGETS,
        scan_orphan_bytecode,
    )

    findings = scan_orphan_bytecode(
        ROOT, targets=(*DEFAULT_TARGETS, *TEST_FAULT_TARGETS))
    # legacy-layout pycs are always findings; __pycache__ pycs only when
    # their source is gone — a live dev tree's caches stay clean either way
    assert findings == [], "\n".join(f.render() for f in findings)
