"""flightcheck static-analysis suite (fraud_detection_tpu/analysis/).

Four layers:

1. each rule catches its injected-violation fixture
   (tests/flightcheck_fixtures/ — modules that are PARSED, never imported),
   including the PR 6 whole-program rules: cross-object FC101
   (fx_cross_object.py), the FC401-403 commit-protocol shapes
   (fx_commit_protocol.py — commit-before-flush, commit-after-failed-
   flush, record-after-flush, unguarded drains), and FC404 lock leaks
   (fx_lock_leak.py);
2. the ``--fix`` pragma engine (scaffold + merge + idempotency pins) and
   SARIF 2.1.0 output (emitter validity + validator rejection cases);
3. the clean-tree pin: the real package yields ZERO findings (with the
   deliberate pragma suppressions recorded, not silent) — this is the CI
   ``flightcheck`` gate as a test — plus the pinned analyzer-runtime
   budget;
4. regression pins for the true positives full runs flagged and fixed
   (PR 5: scheduler prewarm region, hotswap writer locks, vectorized
   annotation conversions; PR 6's process_batch flush-flag guard lives in
   tests/test_stream.py::test_process_batch_refuses_after_failed_flush).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from fraud_detection_tpu.analysis import RULES, run_analysis
from fraud_detection_tpu.analysis import (callgraph, concurrency, health,
                                          jaxlint, protocol, sarif)
from fraud_detection_tpu.analysis import threads as threadmap
from fraud_detection_tpu.analysis.core import (SourceFile, filter_suppressed,
                                               load_package)
from fraud_detection_tpu.analysis.entrypoints import (COMMIT_PROTOCOLS,
                                                      CONCURRENT_CLASSES,
                                                      ClassSpec,
                                                      CommitProtocolSpec,
                                                      THREAD_ENTRY_POINTS)
from fraud_detection_tpu.analysis.fixer import apply_fixes
from fraud_detection_tpu.utils import racecheck

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "fraud_detection_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "flightcheck_fixtures")


def load_fixture(name: str) -> SourceFile:
    sf = SourceFile.load(os.path.join(FIXTURES, name), name)
    assert sf is not None, f"fixture {name} failed to parse"
    return sf


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# 1. every rule catches its fixture
# ---------------------------------------------------------------------------

def test_fc101_lock_inversion_detected():
    sf = load_fixture("fx_lock_inversion.py")
    findings = concurrency.analyze([sf], registry={})
    fc101 = [f for f in findings if f.rule == "FC101"]
    assert fc101, "lock inversion fixture not detected"
    assert any("_a" in f.message and "_b" in f.message for f in fc101)


def test_fc102_unguarded_write_detected_and_scoped():
    sf = load_fixture("fx_unguarded_write.py")
    spec = ClassSpec(any_thread=frozenset(),
                     workers={"w": frozenset({"_worker"})})
    raw = concurrency.analyze(
        [sf], registry={"fx_unguarded_write.py::Box": spec})
    fc102 = [f for f in raw if f.rule == "FC102"]
    # exactly the two unguarded writes: reset() and the pragma'd quiet_reset
    lines = {f.line for f in fc102}
    text = sf.text.splitlines()
    assert all("self.count = 0" in text[line - 1] for line in lines)
    assert len(fc102) == 2, fc102
    # pragma suppression drops quiet_reset's finding
    kept, suppressed = filter_suppressed({sf.relpath: sf}, fc102)
    assert len(kept) == 1 and suppressed == 1
    assert "reset" in kept[0].message
    # guarded/locked/context-guarded/single-role writes are all clean
    assert not any("guarded_reset" in f.message or "_indirect" in f.message
                   or "scratch" in f.message or "_drain_locked" in f.message
                   for f in kept)


def test_fc102_needs_role_map():
    """Without a ClassSpec the class is out of FC102 scope (no role info =
    no shared-attr claim), but FC101 still runs."""
    sf = load_fixture("fx_unguarded_write.py")
    findings = concurrency.analyze([sf], registry={})
    assert not [f for f in findings if f.rule == "FC102"]


def test_fc201_fc202_fixtures_detected():
    sf = load_fixture("fx_jax_violations.py")
    findings = jaxlint.analyze([sf], hot_paths=set())
    fc201 = [f for f in findings if f.rule == "FC201"]
    fc202 = [f for f in findings if f.rule == "FC202"]
    assert len(fc201) == 1, fc201            # rebuilds_jit only
    assert len(fc202) == 2, fc202            # `if x > 0` and `while x < k`
    # static-arg, shape, and `is None` branches stay clean
    text = sf.text.splitlines()
    for f in fc202:
        assert "VIOLATION" in text[f.line - 1]


def test_fc203_fc204_hot_path_scoping():
    sf = load_fixture("fx_jax_violations.py")
    hot = {"fx_jax_violations.py::HotClass.hot_loop"}
    findings = jaxlint.analyze([sf], hot_paths=hot)
    fc203 = [f for f in findings if f.rule == "FC203"]
    fc204 = [f for f in findings if f.rule == "FC204"]
    assert len(fc203) == 2, fc203            # float(rows[i]) + .item()
    assert len(fc204) == 1 and "37" in fc204[0].message
    # cold_loop has the same body and is NOT flagged (registry-scoped)
    assert all("cold_loop" not in f.message for f in fc203 + fc204)


def test_fc301_drift_and_inconsistent_returns():
    sf = load_fixture("fx_health_drift.py")
    contracts = (
        health.Contract("fx_health_drift.py", "Probe.health",
                        "fx_schema_tests.py", "PROBE_HEALTH_SCHEMA"),
        health.Contract("fx_health_drift.py", "Probe.snapshot_ok",
                        "fx_schema_tests.py", "SNAP_OK_SCHEMA"),
        health.Contract("fx_health_drift.py", "Probe.torn",
                        "fx_schema_tests.py", "SNAP_OK_SCHEMA"),
    )
    findings = health.analyze([sf], tests_dir=FIXTURES, contracts=contracts)
    assert len(findings) == 2, findings
    drift = [f for f in findings if "drifted" in f.message]
    torn = [f for f in findings if "DIFFERENT key sets" in f.message]
    assert len(drift) == 1 and "renamed_key" in drift[0].message
    assert "dropped" in drift[0].message
    assert len(torn) == 1


def test_fc103_unregistered_thread_detected():
    sf = load_fixture("fx_thread_spawn.py")
    findings = threadmap.analyze([sf], package_root=PKG,
                                 sites_registry=frozenset(),
                                 entry_points=())
    spawn = [f for f in findings if "spawn site" in f.message]
    assert len(spawn) == 1 and "rogue" in spawn[0].message


def test_fleet_fixture_violations_detected():
    """The fleet drift modes the PR 8 registrations guard against: an
    unregistered fleet worker thread (FC103) and a coordinator tick
    mutating the shared lease state without the lock its worker-facing
    surface uses (FC102)."""
    sf = load_fixture("fx_fleet.py")
    spawn = [f for f in threadmap.analyze([sf], package_root=PKG,
                                          sites_registry=frozenset(),
                                          entry_points=())
             if "spawn site" in f.message]
    assert len(spawn) == 1 and "_fleet_worker_main" in spawn[0].message
    spec = ClassSpec(any_thread=frozenset({"renew"}),
                     workers={"monitor": frozenset({"_tick",
                                                    "_tick_guarded"})})
    fc102 = [f for f in concurrency.analyze(
        [sf], registry={"fx_fleet.py::LeaseBoard": spec})
        if f.rule == "FC102"]
    assert len(fc102) == 1 and "_tick" in fc102[0].message, fc102
    assert "_tick_guarded" not in fc102[0].message


def test_fleet_threads_and_regions_registered():
    """The real fleet tree's concurrency map is registered end to end:
    thread sites, entry points with live racecheck regions, role maps for
    every fleet class, and the manual-assignment consumer's region."""
    from fraud_detection_tpu.analysis.entrypoints import (IMPLEMENTATIONS,
                                                          OBJECT_BINDINGS,
                                                          THREAD_SITES)

    assert ("fleet/fleet.py", "self._worker_main") in THREAD_SITES
    assert ("fleet/fleet.py", "self._monitor_loop") in THREAD_SITES
    eps = {(ep.module, ep.qualname): ep for ep in THREAD_ENTRY_POINTS}
    worker_ep = eps[("fleet/fleet.py", "Fleet._worker_main")]
    assert worker_ep.racecheck == "FleetWorker.run"
    assert worker_ep.racecheck in racecheck.INSTRUMENTED_REGIONS
    assert "InProcessAssignedConsumer" in racecheck.INSTRUMENTED_REGIONS
    for key in ("fleet/bus.py::FleetBus",
                "fleet/coordinator.py::FleetCoordinator",
                "fleet/worker.py::FleetWorker",
                "fleet/fleet.py::Fleet"):
        assert key in CONCURRENT_CLASSES, key
    assert "fleet/worker.py::FleetWorker.coordinator" in OBJECT_BINDINGS
    assert "InProcessAssignedConsumer" in IMPLEMENTATIONS["Consumer"]


# ---------------------------------------------------------------------------
# 1b. whole-program + protocol rules (PR 6) catch their fixtures
# ---------------------------------------------------------------------------

_FX_PROTOCOLS = (
    CommitProtocolSpec("fx_commit_protocol.py::BadEngine",
                       drain_names=frozenset({"_finish"}),
                       failure_flag="_flush_failed"),
    CommitProtocolSpec("fx_commit_protocol.py::GoodEngine",
                       drain_names=frozenset({"_finish"}),
                       failure_flag="_flush_failed"),
)


def test_fc101_cross_object_inversion_detected():
    """The whole-program pass follows self.attr calls across objects:
    Engine holds its lock into Broker, Broker holds its lock back into
    Engine — both inversion edges flagged, the consistently-ordered Quiet
    class clean."""
    sf = load_fixture("fx_cross_object.py")
    findings = callgraph.analyze([sf], bindings={}, implementations={})
    assert rules_of(findings) == ["FC101"]
    assert len(findings) == 2, findings
    assert all("cross-object" in f.message for f in findings)
    assert any("Engine._lock" in f.message and "Broker._lock" in f.message
               for f in findings)
    assert not any("Quiet" in f.message for f in findings)


def test_fc101_cross_object_needs_binding():
    """No receiver binding, no edge: with inference defeated (no annotation,
    no direct instantiation) the analyzer must stay silent rather than
    guess — the under-approximation documented in the module docstring."""
    import textwrap
    src = textwrap.dedent("""
        import threading
        class A:
            def __init__(self, other):
                self._lock = threading.Lock()
                self.other = other
            def go(self):
                with self._lock:
                    self.other.back()
        class B:
            def __init__(self, other):
                self._lock = threading.Lock()
                self.other = other
            def back(self):
                with self._lock:
                    self.other.go()
    """)
    import ast as _ast
    sf = SourceFile(path="fx.py", relpath="fx.py", text=src,
                    tree=_ast.parse(src))
    assert callgraph.analyze([sf], bindings={}, implementations={}) == []
    # ...and the explicit registry closes exactly that gap.
    bound = callgraph.analyze(
        [sf], implementations={},
        bindings={"fx.py::A.other": ("B",), "fx.py::B.other": ("A",)})
    assert bound and all(f.rule == "FC101" for f in bound)


def test_fc401_commit_protocol_shapes():
    sf = load_fixture("fx_commit_protocol.py")
    findings = [f for f in protocol.analyze([sf], protocols=_FX_PROTOCOLS)
                if f.rule == "FC401"]
    text = sf.text.splitlines()
    assert len(findings) == 4, findings
    for f in findings:
        assert "VIOLATION FC401" in text[f.line - 1], f
    msgs = "\n".join(f.message for f in findings)
    assert "NO producer flush" in msgs          # commit_before_flush
    assert "result discarded" in msgs           # commit_dropped_flush
    assert "never checked" in msgs              # unchecked + failure-path
    # the acceptance shape: commit-after-FAILED-flush is demonstrably caught
    assert any("commit_on_failure_path" in f.message for f in findings)
    # GoodEngine (the real engine's shape) stays clean
    assert not any("GoodEngine" in f.message for f in findings)


def test_fc402_record_after_flush():
    sf = load_fixture("fx_commit_protocol.py")
    findings = [f for f in protocol.analyze([sf], protocols=_FX_PROTOCOLS)
                if f.rule == "FC402"]
    assert len(findings) == 1
    assert "late_record" in findings[0].message
    assert "VIOLATION FC402" in sf.text.splitlines()[findings[0].line - 1]


def test_fc403_unguarded_drains():
    sf = load_fixture("fx_commit_protocol.py")
    findings = [f for f in protocol.analyze([sf], protocols=_FX_PROTOCOLS)
                if f.rule == "FC403"]
    assert len(findings) == 2, findings
    msgs = "\n".join(f.message for f in findings)
    assert "_drain_unguarded_finally" in msgs   # finally-drain, no flag
    assert "process_no_flag" in msgs            # public entry, no flag
    assert not any("GoodEngine" in f.message for f in findings)


def test_fc404_lock_leak():
    sf = load_fixture("fx_lock_leak.py")
    findings = protocol.analyze([sf], protocols=())
    assert rules_of(findings) == ["FC404"]
    text = sf.text.splitlines()
    assert len(findings) == 2, findings
    for f in findings:
        assert "VIOLATION FC404" in text[f.line - 1], f
    # manual acquire/try/finally and `with` are both accepted shapes
    assert all(f.line < text.index("    def manual_ok(self):") + 1
               for f in findings)


def test_engine_protocol_registered():
    """The real engine must be in the FC4xx scope — deleting its protocol
    spec would silently turn the commit-protocol rules off."""
    keys = {p.cls_key for p in COMMIT_PROTOCOLS}
    assert "stream/engine.py::StreamingClassifier" in keys
    spec = next(p for p in COMMIT_PROTOCOLS
                if p.cls_key == "stream/engine.py::StreamingClassifier")
    assert spec.failure_flag == "_flush_failed"
    assert "_finish" in spec.drain_names


def test_class_names_unique_package_wide():
    """callgraph keys bindings and lock qualifications on bare class names;
    a duplicate top-level class name would silently degrade the analysis
    (last definition wins), so pin uniqueness here."""
    import ast as _ast
    import collections
    counts = collections.Counter()
    for sf in load_package(PKG):
        for node in sf.tree.body:
            if isinstance(node, _ast.ClassDef):
                counts[node.name] += 1
    dups = sorted(name for name, n in counts.items() if n > 1)
    assert not dups, f"duplicate top-level class names: {dups}"


# ---------------------------------------------------------------------------
# 1c. --fix pragma engine + SARIF output
# ---------------------------------------------------------------------------

def _fix_roundtrip_root(tmp_path):
    import shutil
    shutil.copy(os.path.join(FIXTURES, "fx_lock_leak.py"),
                tmp_path / "fx_lock_leak.py")
    return str(tmp_path)


def _analyze_fixture_root(root):
    sf = SourceFile.load(os.path.join(root, "fx_lock_leak.py"),
                         "fx_lock_leak.py")
    raw = protocol.analyze([sf], protocols=())
    return filter_suppressed({sf.relpath: sf}, raw)


def test_fix_scaffolds_and_is_idempotent(tmp_path):
    root = _fix_roundtrip_root(tmp_path)
    kept, suppressed = _analyze_fixture_root(root)
    assert len(kept) == 2 and suppressed == 0
    edits = apply_fixes(kept, root)
    assert [e.action for e in edits] == ["insert", "insert"]
    scaffolded = open(os.path.join(root, "fx_lock_leak.py")).read()
    assert scaffolded.count("TODO(justify)") == 2
    # pragmas now suppress both findings...
    kept2, suppressed2 = _analyze_fixture_root(root)
    assert kept2 == [] and suppressed2 == 2
    # ...and a second --fix changes NOTHING (the idempotency pin)
    assert apply_fixes(kept2, root) == []
    assert open(os.path.join(root, "fx_lock_leak.py")).read() == scaffolded


def test_fix_dry_run_writes_nothing(tmp_path):
    root = _fix_roundtrip_root(tmp_path)
    before = open(os.path.join(root, "fx_lock_leak.py")).read()
    kept, _ = _analyze_fixture_root(root)
    edits = apply_fixes(kept, root, dry_run=True)
    assert len(edits) == 2
    assert open(os.path.join(root, "fx_lock_leak.py")).read() == before


def test_fix_merges_into_existing_pragma(tmp_path):
    """A line already pragma'd for another rule gains the new id in the
    SAME bracket — no stacked pragma lines."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def leak(self):\n"
           "        # flightcheck: ignore[FC102] — existing reason\n"
           "        self._lock.acquire()\n")
    path = tmp_path / "fx_merge.py"
    path.write_text(src)
    sf = SourceFile.load(str(path), "fx_merge.py")
    kept, _ = filter_suppressed(
        {sf.relpath: sf}, protocol.analyze([sf], protocols=()))
    assert len(kept) == 1
    edits = apply_fixes(kept, str(tmp_path))
    assert [e.action for e in edits] == ["merge"]
    out = path.read_text()
    assert "ignore[FC102,FC404]" in out
    assert out.count("flightcheck:") == 1


def test_sarif_document_valid_and_complete():
    sf = load_fixture("fx_lock_leak.py")
    findings = protocol.analyze([sf], protocols=())
    doc = sarif.build(findings, suppressed=3, n_files=1)
    assert sarif.validate(doc) == []
    assert doc["version"] == "2.1.0"
    assert "2.1.0" in doc["$schema"]
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "flightcheck"
    # full rule catalog shipped, every result resolvable by ruleIndex
    ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert ids == sorted(RULES)
    for res in run["results"]:
        assert ids[res["ruleIndex"]] == res["ruleId"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].startswith(
            "fraud_detection_tpu/")
        assert loc["region"]["startLine"] >= 1
    assert run["properties"]["suppressedByPragma"] == 3


def test_sarif_validator_rejects_broken_documents():
    doc = sarif.build([], suppressed=0, n_files=0)
    assert sarif.validate({"version": "2.0.0", "runs": []})
    bad = json.loads(json.dumps(doc))
    bad["runs"][0]["tool"]["driver"].pop("name")
    assert any("driver.name" in p for p in sarif.validate(bad))
    bad2 = json.loads(json.dumps(doc))
    bad2["runs"][0]["results"] = [{"ruleId": "FC999",
                                   "message": {"text": "x"}}]
    assert any("FC999" in p for p in sarif.validate(bad2))


# ---------------------------------------------------------------------------
# 2. clean tree + registry/runtime sync
# ---------------------------------------------------------------------------

def test_clean_tree_zero_findings():
    """THE acceptance pin: the analyzers exit clean on the real package,
    with the deliberate suppressions recorded as pragmas (not zero — the
    tree documents its exceptions)."""
    findings, suppressed, n_files = run_analysis()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert suppressed >= 5          # engine latch x2, lane counters x3, ...
    assert n_files > 50


def test_instrumented_regions_match_source():
    """utils/racecheck.py INSTRUMENTED_REGIONS == the region names actually
    constructed in the package — parsed statically AND importable."""
    static = threadmap.parse_instrumented_registry(PKG)
    assert static == set(racecheck.INSTRUMENTED_REGIONS)
    from fraud_detection_tpu.analysis.core import load_package

    files = load_package(PKG)
    names = {n for _, n, _ in threadmap.collect_region_names(files)}
    assert names == static


def test_entry_points_cover_all_region_claims():
    claimed = {ep.racecheck for ep in THREAD_ENTRY_POINTS
               if ep.racecheck is not None}
    assert claimed <= set(racecheck.INSTRUMENTED_REGIONS)
    for ep in THREAD_ENTRY_POINTS:
        assert ep.racecheck or ep.why_uncovered, ep


def test_rule_catalog_documented():
    doc = open(os.path.join(REPO, "docs", "static_analysis.md")).read()
    for rule in RULES:
        assert rule in doc, f"{rule} missing from docs/static_analysis.md"


# ---------------------------------------------------------------------------
# CLI e2e
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_exits_zero_on_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "fraud_detection_tpu.analysis", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["suppressed"] >= 5


def test_cli_main_inprocess(tmp_path, capsys):
    """The CLI entry without subprocess cost: clean tree -> 0; --list-rules
    prints the catalog; unknown rule id -> 2."""
    from fraud_detection_tpu.analysis.__main__ import main

    assert main([]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out
    assert main(["--rules", "FC999"]) == 2
    assert main(["--dry-run"]) == 2      # --dry-run requires --fix


def test_cli_sarif_and_fix_dry_run(tmp_path, capsys):
    """--sarif writes a validating 2.1.0 document for the clean tree and
    --fix --dry-run is a no-op with exit 0 (the CI smoke)."""
    from fraud_detection_tpu.analysis.__main__ import main

    out_path = tmp_path / "flightcheck.sarif"
    assert main(["--sarif", str(out_path), "--fix", "--dry-run"]) == 0
    doc = json.loads(out_path.read_text())
    assert sarif.validate(doc) == []
    assert doc["runs"][0]["results"] == []
    assert doc["runs"][0]["properties"]["suppressedByPragma"] >= 5


def test_cli_fix_scaffolds_fixture_tree(tmp_path, capsys):
    """e2e --fix against a dirty root: exit 1 (findings are triaged, not
    absolved), pragmas written, second run exits 0 with them suppressed."""
    import shutil

    from fraud_detection_tpu.analysis.__main__ import main

    shutil.copy(os.path.join(FIXTURES, "fx_lock_leak.py"),
                tmp_path / "fx_lock_leak.py")
    argv = ["--root", str(tmp_path), "--rules", "FC404", "--fix"]
    assert main(argv) == 1
    out = capsys.readouterr().out
    assert "2 edit(s) applied" in out
    assert main(argv) == 0
    out = capsys.readouterr().out
    assert "0 finding(s), 2 suppressed" in out
    assert "0 edit(s) applied" in out


def test_incremental_cache_hits_and_invalidates(tmp_path):
    """The per-file cache (analysis/cache.py): identical findings with and
    without it, full hits on a warm second run, and a single-file edit
    misses exactly that file."""
    import shutil

    from fraud_detection_tpu.analysis.cache import AnalysisCache

    root = tmp_path / "pkg"
    root.mkdir()
    for name in ("fx_lock_leak.py", "fx_commit_protocol.py"):
        shutil.copy(os.path.join(FIXTURES, name), root / name)
    cache_dir = str(tmp_path / "cache")

    def run(stats):
        return run_analysis(package_root=str(root), tests_dir=None,
                            cache_dir=cache_dir, stats=stats)

    plain = run_analysis(package_root=str(root), tests_dir=None)
    s1, s2 = {}, {}
    cold = run(s1)
    warm = run(s2)
    assert cold[0] == warm[0] == plain[0]
    assert s1 == {"hits": 0, "misses": 2}
    assert s2 == {"hits": 2, "misses": 0}
    # an edit misses only the edited file...
    (root / "fx_lock_leak.py").write_text(
        (root / "fx_lock_leak.py").read_text() + "\n# touched\n")
    s3 = {}
    run(s3)
    assert s3 == {"hits": 1, "misses": 1}
    # ...and a cache entry survives as plain JSON keyed on content hash
    cache = AnalysisCache(cache_dir)
    entries = [f for f in os.listdir(cache_dir) if f.endswith(".json")]
    assert len(entries) == 3      # 2 originals + 1 edited variant
    assert cache.stats() == {"hits": 0, "misses": 0}


def test_cache_salt_invalidates_on_registry_change(tmp_path, monkeypatch):
    """Changing a registry the file-local rules read (HOT_PATHS here) must
    change the salt — stale verdicts under a new configuration would be
    silently wrong."""
    from fraud_detection_tpu.analysis import cache as cache_mod
    from fraud_detection_tpu.analysis import entrypoints

    before = cache_mod._registry_salt()
    monkeypatch.setattr(entrypoints, "HOT_PATHS",
                        frozenset({"nowhere.py::Nothing.nothing"}))
    after = cache_mod._registry_salt()
    assert before != after


def test_cache_salt_stable_across_processes():
    """frozenset repr is hash-seed ordered; the salt must not be (a fresh
    process would miss the whole cache every run)."""
    import subprocess
    import sys

    cmd = [sys.executable, "-c",
           "from fraud_detection_tpu.analysis.cache import _registry_salt;"
           "print(_registry_salt())"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    a = subprocess.run(cmd, capture_output=True, text=True,
                       env={**env, "PYTHONHASHSEED": "1"}, timeout=120)
    b = subprocess.run(cmd, capture_output=True, text=True,
                       env={**env, "PYTHONHASHSEED": "2"}, timeout=120)
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    assert a.stdout.strip() == b.stdout.strip()


def test_cache_salt_folds_in_checker_and_spec_sources(tmp_path, monkeypatch):
    """ISSUE 20 cache audit: the salt must cover the model checker, the
    trace-conformance module, and the protocol-spec registry — editing an
    eventually-invariant or a role machine changes what pragma context
    and FC5xx findings mean, so it must invalidate every cache entry."""
    from fraud_detection_tpu.analysis import cache as cache_mod
    from fraud_detection_tpu.analysis import checker, conformance, entrypoints

    before = cache_mod._registry_salt()
    for mod in (checker, conformance, entrypoints):
        short = mod.__name__.rsplit(".", 1)[-1]
        variant = tmp_path / f"{short}.py"
        variant.write_text(open(mod.__file__).read() + "\n# edited\n")
        monkeypatch.setattr(mod, "__file__", str(variant))
        assert cache_mod._registry_salt() != before, (
            f"editing {short}.py did not change the cache salt")
        monkeypatch.undo()
        assert cache_mod._registry_salt() == before
    # the parsed FLEET_PROTOCOLS registry is folded in on its own too
    monkeypatch.setattr(entrypoints, "FLEET_PROTOCOLS", ())
    assert cache_mod._registry_salt() != before


#: pragma audit (ISSUE 20): every suppression in the tree, pinned. A new
#: pragma (or a deleted one) must show up here as a conscious edit, with
#: the docs' census (docs/static_analysis.md "Pragmas") kept in step.
_EXPECTED_PRAGMAS = {
    ("fleet/worker.py", "FC102"): 1,          # lock-free stop latch
    ("stream/engine.py", "FC102"): 2,         # lock-free stop latches
    ("stream/annotations.py", "FC102"): 3,    # worker-only counters
    ("models/pipeline.py", "FC201"): 1,       # one-shot donation probe
    ("models/train_llm.py", "FC201"): 1,      # once-per-run opt-state init
}


def test_pragma_audit_every_suppression_is_pinned_and_justified():
    """Counts the tree's ``# flightcheck: ignore[...]`` pragmas with the
    analyzer's own parser and pins them per (file, rule); every pragma
    line must carry a justification string after the bracket."""
    found: dict = {}
    for sf in load_package(PKG):
        lines = sf.text.splitlines()
        for lineno, rules in sorted(sf.ignores.items()):
            line = lines[lineno - 1]
            tail = line.split("]", 1)[1]
            assert tail.strip(" -—#"), (
                f"{sf.relpath}:{lineno}: pragma without a justification "
                f"string: {line.strip()!r}")
            for rule in rules:
                key = (sf.relpath, rule)
                found[key] = found.get(key, 0) + 1
    assert found == _EXPECTED_PRAGMAS, (
        "pragma census drifted — update _EXPECTED_PRAGMAS AND the count "
        "in docs/static_analysis.md consciously")
    total = sum(_EXPECTED_PRAGMAS.values())
    doc = open(os.path.join(REPO, "docs", "static_analysis.md")).read()
    assert f"currently carries {_spell(total)}" in doc, (
        f"docs/static_analysis.md pragma census out of step with the "
        f"tree's {total}")


def _spell(n: int) -> str:
    words = {7: "seven", 8: "eight", 9: "nine", 10: "ten", 11: "eleven",
             12: "twelve"}
    return words.get(n, str(n))


def test_analyzer_runtime_budget():
    """Pinned analyzer-runtime budget: the whole-program pass must stay a
    sub-minute CI gate, not a soak. 30s is ~10x the measured cost on a
    cold CI runner — a blowup here means an accidental O(n^2) walk, not
    noise."""
    start = time.perf_counter()
    findings, _, n_files = run_analysis()
    elapsed = time.perf_counter() - start
    assert findings == []
    assert n_files > 50
    assert elapsed < 30.0, f"flightcheck took {elapsed:.1f}s (budget 30s)"


# ---------------------------------------------------------------------------
# 3. regression pins for the fixed true positives
# ---------------------------------------------------------------------------

class _FakePipe:
    """Just enough pipeline for measure_rung_costs/prewarm_ladder."""

    batch_size = 8

    def __init__(self):
        self.pad_ladder = None

    def predict(self, texts):
        return object()

    def predict_json_async(self, values):
        return None


def _hold_region(region, entered, release):
    def target():
        with region:
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=target, daemon=True)
    t.start()
    entered.wait(5.0)
    return t


def test_prewarm_enters_driver_region():
    """sched fix: prewarm mutates driver-owned ladder state and must be in
    the single-driver region — a concurrent driver now gets RaceError, not
    a torn snapshot (flightcheck FC102 regression)."""
    from fraud_detection_tpu.sched.batcher import default_ladder
    from fraud_detection_tpu.sched.scheduler import (AdaptiveScheduler,
                                                     SchedulerConfig)

    sched = AdaptiveScheduler(
        SchedulerConfig(buckets=tuple(default_ladder(8)), cost_aware=False),
        batch_size=8)
    entered, release = threading.Event(), threading.Event()
    t = _hold_region(sched._region, entered, release)
    try:
        with pytest.raises(racecheck.RaceError):
            sched.prewarm(_FakePipe())
    finally:
        release.set()
        t.join(5.0)
    racecheck.clear_violations()


class _CountingLock:
    def __init__(self):
        self.acquired = 0
        self._inner = threading.Lock()

    def __enter__(self):
        self.acquired += 1
        self._inner.acquire()
        return self

    def __exit__(self, *exc):
        self._inner.release()


def test_configure_ladder_takes_writer_lock():
    """hotswap fix: configure_ladder/measure_ladder publish the ladder under
    the writer lock (flightcheck FC102 regression)."""
    from fraud_detection_tpu.registry.hotswap import HotSwapPipeline

    hot = HotSwapPipeline(_FakePipe(), version=1)
    counting = _CountingLock()
    hot._lock = counting
    hot.configure_ladder((4, 8), prewarm=False, costs={4: 0.1, 8: 0.2})
    assert counting.acquired == 1
    assert hot.pad_buckets == (4, 8)
    assert hot.ladder_costs == {4: 0.1, 8: 0.2}
    hot.measure_ladder((4, 8), texts=["hi"], repeats=1)
    assert counting.acquired == 2


def test_lifecycle_tick_rollback_share_region():
    """promote fix: tick() and rollback() enter the watch region — a
    rollback racing a watcher tick is a loud RaceError, never a silent
    double transition."""
    from fraud_detection_tpu.registry.promote import LifecycleController

    class _Hot:
        active_version = 1

    ctl = LifecycleController.__new__(LifecycleController)
    ctl._region = racecheck.ExclusiveRegion("LifecycleController.watch")
    entered, release = threading.Event(), threading.Event()
    t = _hold_region(ctl._region, entered, release)
    try:
        with pytest.raises(racecheck.RaceError):
            ctl.tick()
        with pytest.raises(racecheck.RaceError):
            ctl.rollback(1)
    finally:
        release.set()
        t.join(5.0)
    racecheck.clear_violations()


def test_shadow_worker_region_is_exclusive():
    """shadow extension: the scorer's worker region rejects a second
    concurrent scorer thread (satellite: racecheck now covers the
    shadow-scoring worker)."""
    from fraud_detection_tpu.registry.shadow import ShadowScorer

    sh = ShadowScorer(max_queue=2)
    try:
        entered, release = threading.Event(), threading.Event()
        t = _hold_region(sh._region, entered, release)
        try:
            with pytest.raises(racecheck.RaceError):
                with sh._region:
                    pass
        finally:
            release.set()
            t.join(5.0)
        assert any(v.region == "ShadowScorer.worker"
                   for v in racecheck.violations())
    finally:
        sh.close(2.0)
        racecheck.clear_violations()


def test_submit_annotations_vectorized_types():
    """engine fix: annotation items carry batch-converted plain Python
    ints/floats — no per-row numpy scalar conversion on the hot path
    (flightcheck FC203 regression)."""
    from fraud_detection_tpu.stream.engine import _InFlight

    class _Lane:
        def __init__(self):
            self.items = None

        def submit(self, items):
            self.items = items

    class _Msg:
        def __init__(self, key):
            self.key = key

    class _Preds:
        labels = np.array([0, 1, 1, 0], np.int32)
        probabilities = np.array([0.1, 0.9, 0.8, 0.2], np.float32)

    engine = object.__new__(
        __import__("fraud_detection_tpu.stream.engine",
                   fromlist=["StreamingClassifier"]).StreamingClassifier)
    lane = _Lane()
    engine._annotation_lane = lane
    inflight = _InFlight(
        msgs=[_Msg(b"k0"), _Msg(b"k1"), _Msg(b"k2"), _Msg(b"k3")],
        texts=["a", "b", "c", "d"], valid_idx=[0, 1, 2, 3],
        pending=None, offsets={}, dispatch_time=0.0, raw=False)
    engine._submit_annotations(inflight, _Preds())
    assert lane.items is not None and len(lane.items) == 2
    for key, text, label, conf, cid in lane.items:
        assert type(label) is int, type(label)
        assert type(conf) is float, type(conf)
        assert cid is None          # no tracer attached: cids ride as None
    assert [it[0] for it in lane.items] == [b"k1", b"k2"]
