"""Self-tests for the repro.analysis lint pass (RPR001-RPR005).

Each rule gets an intentionally-bad fixture (every violation class is
flagged) and a clean fixture (zero findings across ALL rules — the
false-positive guard).  Fixtures live under ``tests/fixtures/analysis``
which the driver's default discovery skips; tests lint them explicitly
through ``lint_file`` with synthetic repo-relative paths so the
path-scoped rules see the directory layout they expect.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from repro.analysis.baseline import (
    apply_baseline,
    load_baseline,
    save_baseline,
)
from repro.analysis.lint import lint_file, run_analysis

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fixture_source(kind: str, name: str) -> str:
    with open(os.path.join(FIXTURES, kind, name), encoding="utf-8") as f:
        return f.read()


def _lint_fixture(kind: str, name: str, relpath: str, **kw):
    return lint_file(relpath, _fixture_source(kind, name), **kw)


# -- bad fixtures: every violation class fires ------------------------------

BAD_CASES = [
    ("rpr001_bad.py", "src/repro/kernels/fixture_mod.py", "RPR001",
     {"bare-int-literal", "uint32-division", "int32-mix"}),
    ("rpr002_bad.py", "src/repro/serving/fixture_mod.py", "RPR002",
     {"assign:self.count", "call:evict", "mutate:append",
      "call:ingest", "mutate:fill"}),
    # The band-store probe path (PR 10): ``probe_*`` reads on a store
    # class are held to the same purity contract as view probes.
    ("rpr002_store_bad.py", "src/repro/core/fixture_mod.py", "RPR002",
     {"assign:self.hits", "call:compact", "mutate:add",
      "assign:self.seq"}),
    ("rpr003_bad.py", "src/repro/serving/fixture_mod.py", "RPR003",
     {"unbucketed:compute_arrays", "unbucketed:compute_signatures"}),
    ("rpr004_bad.py", "src/repro/core/fixture_mod.py", "RPR004",
     {"off-scheme:run_query", "deprecated-call:ingest_arrays",
      "deprecated-attr:uf"}),
    ("rpr005_bad.py", "src/repro/kernels/fixture_mod.py", "RPR005",
     {"index-map-arity", "unclamped-dim:TL", "vmem-budget",
      "out-rank-mismatch"}),
    # The byte-shingle carry-tiling variant: same violation classes on
    # the revisited rank-1 carry-block idiom of kernels/byte_shingle.py.
    ("rpr005_byte_bad.py", "src/repro/kernels/fixture_mod.py", "RPR005",
     {"index-map-arity", "unclamped-dim:TLB", "vmem-budget",
      "out-rank-mismatch"}),
    # Rank-1 (td_,) blocks tiled along the grid: legal in interpret
    # mode, refused by the TPU lowering (the pre-chip byte carry).
    ("rpr005_rank1_bad.py", "src/repro/kernels/fixture_mod.py", "RPR005",
     {"rank1-block:td_"}),
]


@pytest.mark.parametrize("name,relpath,rule,expected",
                         BAD_CASES, ids=[c[2] for c in BAD_CASES])
def test_bad_fixture_flagged(name, relpath, rule, expected):
    findings = _lint_fixture("bad", name, relpath)
    got = {f.symbol for f in findings if f.rule == rule}
    assert expected <= got, f"missing: {expected - got}"
    assert all(f.status == "new" for f in findings)


# -- good fixtures: zero findings, any rule ---------------------------------

GOOD_CASES = [
    ("rpr001_good.py", "src/repro/kernels/fixture_mod.py"),
    ("rpr002_good.py", "src/repro/serving/fixture_mod.py"),
    ("rpr002_store_good.py", "src/repro/core/fixture_mod.py"),
    ("rpr003_good.py", "src/repro/serving/fixture_mod.py"),
    ("rpr004_good.py", "src/repro/core/fixture_mod.py"),
    ("rpr005_good.py", "src/repro/kernels/fixture_mod.py"),
    ("rpr005_byte_good.py", "src/repro/kernels/fixture_mod.py"),
]


@pytest.mark.parametrize("name,relpath", GOOD_CASES,
                         ids=[c[0].split("_")[0].upper() for c in GOOD_CASES])
def test_good_fixture_clean(name, relpath):
    findings = _lint_fixture("good", name, relpath)
    assert findings == [], [f.render() for f in findings]


# -- suppression comments ---------------------------------------------------

def test_inline_suppression_same_line():
    src = _fixture_source("bad", "rpr001_bad.py").replace(
        "a = h * 31 ", "a = h * 31  # repro-lint: disable=RPR001")
    findings = lint_file("src/repro/kernels/fixture_mod.py", src)
    by_symbol = {f.symbol: f.status for f in findings}
    assert by_symbol["bare-int-literal"] == "suppressed"
    assert by_symbol["uint32-division"] == "new"  # others untouched


def test_inline_suppression_comment_above():
    src = _fixture_source("bad", "rpr001_bad.py").replace(
        "    b = h // 2 ",
        "    # repro-lint: disable=RPR001\n    b = h // 2 ")
    findings = lint_file("src/repro/kernels/fixture_mod.py", src)
    by_symbol = {f.symbol: f.status for f in findings}
    assert by_symbol["uint32-division"] == "suppressed"
    assert by_symbol["bare-int-literal"] == "new"


def test_inline_suppression_wrong_rule_does_not_apply():
    src = _fixture_source("bad", "rpr001_bad.py").replace(
        "a = h * 31 ", "a = h * 31  # repro-lint: disable=RPR002")
    findings = lint_file("src/repro/kernels/fixture_mod.py", src)
    by_symbol = {f.symbol: f.status for f in findings}
    assert by_symbol["bare-int-literal"] == "new"


def test_file_level_disable():
    src = ("# repro-lint: disable-file=RPR001\n"
           + _fixture_source("bad", "rpr001_bad.py"))
    findings = lint_file("src/repro/kernels/fixture_mod.py", src)
    assert [f for f in findings if f.rule == "RPR001"] == []


# -- baseline round-trip ----------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    relpath = "src/repro/serving/fixture_mod.py"
    findings = _lint_fixture("bad", "rpr003_bad.py", relpath)
    assert findings and all(f.status == "new" for f in findings)

    bp = str(tmp_path / "baseline.json")
    save_baseline(bp, findings, {})
    baseline = load_baseline(bp)

    # Same findings, shifted line numbers (fingerprints are
    # line-insensitive): a leading comment moves every line by one.
    shifted = lint_file(
        relpath, "# a new leading comment\n"
        + _fixture_source("bad", "rpr003_bad.py"))
    apply_baseline(shifted, baseline)
    assert shifted and all(f.status == "baselined" for f in shifted)


def test_baseline_count_caps_matches(tmp_path):
    relpath = "src/repro/serving/fixture_mod.py"
    src = _fixture_source("bad", "rpr003_bad.py")
    findings = lint_file(relpath, src)
    bp = str(tmp_path / "baseline.json")
    save_baseline(bp, findings, {})

    # Duplicate one offending call inside the same function: the
    # fingerprint count (1) covers only the grandfathered instance.
    dup = src.replace(
        "    sig, bands = pipe.compute_arrays(token_lists)",
        "    pipe.compute_arrays(token_lists)\n"
        "    sig, bands = pipe.compute_arrays(token_lists)")
    grown = lint_file(relpath, dup)
    apply_baseline(grown, load_baseline(bp))
    arrays = [f for f in grown if f.symbol == "unbucketed:compute_arrays"]
    assert sorted(f.status for f in arrays) == ["baselined", "new"]


def test_baseline_preserves_reasons(tmp_path):
    relpath = "src/repro/serving/fixture_mod.py"
    findings = _lint_fixture("bad", "rpr003_bad.py", relpath)
    bp = str(tmp_path / "baseline.json")
    entries = save_baseline(bp, findings, {})
    fp = next(iter(entries))
    old = load_baseline(bp)
    old[fp]["reason"] = "one-shot driver"
    save_baseline(bp, findings, old)
    assert load_baseline(bp)[fp]["reason"] == "one-shot driver"


# -- the repo itself passes -------------------------------------------------

def test_repo_has_no_new_findings():
    report = run_analysis(root=REPO_ROOT)
    assert report["errors"] == []
    assert report["new"] == [], [f.render() for f in report["new"]]


def test_vmem_limit_is_configurable():
    # The clean RPR005 fixture trips when the ceiling drops below its
    # (tiny) resident-tile estimate: the knob is actually plumbed.
    findings = _lint_fixture(
        "good", "rpr005_good.py", "src/repro/kernels/fixture_mod.py",
        vmem_limit=256)
    assert any(f.symbol == "vmem-budget" for f in findings)


# -- CLI --------------------------------------------------------------------

def test_cli_json_output():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--format", "json"],
        cwd=REPO_ROOT, capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO_ROOT, "src")},
        timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["new"] == []
    assert report["files_checked"] > 0


def test_cli_fails_on_new_findings(tmp_path):
    bad = tmp_path / "kernels"
    bad.mkdir()
    (bad / "mod.py").write_text(_fixture_source("bad", "rpr003_bad.py"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--root",
         str(tmp_path), "kernels"],
        cwd=REPO_ROOT, capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO_ROOT, "src")},
        timeout=120)
    assert proc.returncode == 1
    assert "RPR003" in proc.stdout
