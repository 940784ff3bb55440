"""graftlint (layer-1 static analysis, ISSUE 5): every rule fires on its bad
fixture and stays silent on the good twin; the suppression syntax enforces a
written justification; and the CURRENT TREE lints clean with the committed
suppression baseline — so any PR that re-introduces an ad-hoc thread pool, an
unseeded RNG, a host sync inside jit, a bf16 prefix sum, a bare data-plane
read, raw trainer device placement, a stray stdout print in a contract tool,
or a dispatch-only knob refusal fails tier-1, not review."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.graftlint import engine  # noqa: E402
from tools.graftlint.concurrency import (  # noqa: E402
    R1Staleness, R9LockOrder, R10HandlerSafety)
from tools.graftlint.rules import R8RefusalParity  # noqa: E402

FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")

# rule id -> the virtual repo path the fixture pretends to live at (rules are
# path-scoped: R5 only watches data/, R6 only the trainer, R7 the contract
# tools, the rest all library code)
_VPATH = {
    "R1": "glint_word2vec_tpu/ops/somefile.py",
    "R2": "glint_word2vec_tpu/ops/somefile.py",
    "R3": "glint_word2vec_tpu/ops/somefile.py",
    "R4": "glint_word2vec_tpu/ops/somefile.py",
    "R5": "glint_word2vec_tpu/data/somefile.py",
    "R6": "glint_word2vec_tpu/train/trainer.py",
    "R7": "bench.py",
    "R11": "glint_word2vec_tpu/serve/somefile.py",
}


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name), "r", encoding="utf-8") as f:
        return f.read()


@pytest.mark.parametrize("rule_id", sorted(_VPATH))
def test_rule_fires_on_bad_and_not_on_good(rule_id):
    vpath = _VPATH[rule_id]
    bad = engine.lint_text(_fixture(f"{rule_id.lower()}_bad.py"), vpath)
    assert any(f.rule == rule_id and not f.suppressed for f in bad), (
        f"{rule_id} did not fire on its bad fixture: {bad}")
    good = engine.lint_text(_fixture(f"{rule_id.lower()}_good.py"), vpath)
    assert not [f for f in good if f.rule == rule_id], (
        f"{rule_id} false-positived on its good fixture: {good}")


def test_r3_flags_every_host_sync_kind():
    bad = engine.lint_text(_fixture("r3_bad.py"), _VPATH["R3"])
    msgs = " ".join(f.message for f in bad if f.rule == "R3")
    assert "float" in msgs and "asarray" in msgs and "clock" in msgs


def test_r3_transitive_helper_coverage():
    """ISSUE 8 satellite: host syncs in a same-module HELPER the jitted
    function calls by name (the obs/probe.py `_matrix_stats` shape) are in
    scope; the good twin (device-pure helper, host flattening outside the
    jit boundary) stays silent."""
    vpath = "glint_word2vec_tpu/obs/somefile.py"
    bad = engine.lint_text(_fixture("r3_trans_bad.py"), vpath)
    msgs = " ".join(f.message for f in bad if f.rule == "R3")
    assert "concretizes" in msgs and "clock" in msgs, bad
    good = engine.lint_text(_fixture("r3_trans_good.py"), vpath)
    assert not [f for f in good if f.rule == "R3"], good


def test_r3_reaches_the_real_probe_helpers():
    """The closure genuinely covers obs/probe.py: poisoning `_matrix_stats`
    (called from the jitted fused probe, not itself a jit target) with a
    float() concretization must fire R3."""
    path = os.path.join(REPO, "glint_word2vec_tpu", "obs", "probe.py")
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    poisoned = src.replace(
        "    return MatrixStats(",
        "    bad = float(norms.sum())\n    return MatrixStats(")
    assert poisoned != src, "probe.py refactored — update the poison anchor"
    found = engine.lint_text(poisoned, "glint_word2vec_tpu/obs/probe.py")
    assert [f for f in found if f.rule == "R3"], found
    # and the committed module itself is clean under the wider scan
    clean = engine.lint_text(src, "glint_word2vec_tpu/obs/probe.py")
    assert not [f for f in clean if f.rule == "R3"], clean
    watch_path = os.path.join(REPO, "glint_word2vec_tpu", "obs", "watch.py")
    with open(watch_path, "r", encoding="utf-8") as f:
        watch_src = f.read()
    assert not [f for f in engine.lint_text(
        watch_src, "glint_word2vec_tpu/obs/watch.py") if f.rule == "R3"]


def test_r7_counts_second_json_line():
    bad = engine.lint_text(_fixture("r7_bad.py"), _VPATH["R7"])
    assert any("exactly ONE JSON line" in f.message for f in bad)


def test_r8_fires_on_bad_pair_and_not_on_good_pair():
    rule = R8RefusalParity()
    bad = rule.check_repo(os.path.join(FIXTURES, "r8_bad"))
    msgs = [f.message for f in bad if f.rule == "R8"]
    # combo with no config twin at all
    assert any("cbow" in m and "use_pallas" in m for m in msgs), bad
    # combo "covered" only by a single-knob RANGE check — not coverage:
    # the rule must not be blinded by config range checks on a member knob
    assert any("cbow" in m and "negative_pool" in m for m in msgs), bad
    # a NEW stabilizer-class knob with a dispatch-only refusal (ISSUE 7):
    # the range check on max_row_norm must not count as combo coverage
    assert any("max_row_norm" in m and "use_pallas" in m for m in msgs), bad
    # a refusal living in Trainer.__init__ path selection, not _build_step —
    # the device_pairgen class graftcheck's first run caught in the real
    # tree (ISSUE 8): __init__ is now a scanned dispatch surface
    assert any("device_pairgen" in m and "cbow" in m for m in msgs), bad
    # a step-cadence knob whose window exists for one lowering only
    # (ISSUE 17): the config-side positivity check on sync_every must not
    # count as coverage for the {sync_every, step_lowering} dispatch combo
    assert any("sync_every" in m and "step_lowering" in m for m in msgs), bad
    good = rule.check_repo(os.path.join(FIXTURES, "r8_good"))
    assert not good, good


def test_r8_cross_references_graftcheck_registry():
    """R8's graftcheck cross-reference: every config field needs a knob
    entry in tools/graftcheck/registry.py. Verified both ways — the real
    tree is clean, and a field invented on a copied config must be flagged
    as missing from the registry."""
    import shutil
    import tempfile

    rule = R8RefusalParity()
    assert not [f for f in rule.check_repo(REPO)
                if "registry" in f.message], "real tree should be in sync"
    with tempfile.TemporaryDirectory() as td:
        for rel in ("glint_word2vec_tpu/config.py",
                    "glint_word2vec_tpu/train/trainer.py",
                    "tools/graftcheck/registry.py"):
            dst = os.path.join(td, rel)
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(os.path.join(REPO, rel), dst)
        cfg_path = os.path.join(td, "glint_word2vec_tpu", "config.py")
        with open(cfg_path, "r", encoding="utf-8") as f:
            src = f.read()
        src = src.replace("    vector_size: int = 100",
                          "    brand_new_knob: int = 0\n"
                          "    vector_size: int = 100")
        with open(cfg_path, "w", encoding="utf-8") as f:
            f.write(src)
        found = rule.check_repo(td)
        assert any("brand_new_knob" in f.message and "registry" in f.message
                   for f in found), found


def test_suppression_requires_justification():
    src = _fixture("r4_bad.py")
    # justified suppression on the line above the finding
    justified = src.replace(
        "    prefix = jnp.cumsum(rows, axis=0)",
        "    # graftlint: disable=R4 -- fixture: exactness argued elsewhere\n"
        "    prefix = jnp.cumsum(rows, axis=0)")
    out = engine.lint_text(justified, _VPATH["R4"])
    assert [f for f in out if f.rule == "R4" and f.suppressed]
    assert not [f for f in out if not f.suppressed]
    # a directive WITHOUT justification suppresses nothing and is itself
    # a finding
    silent = src.replace(
        "    prefix = jnp.cumsum(rows, axis=0)",
        "    prefix = jnp.cumsum(rows, axis=0)  # graftlint: disable=R4")
    out = engine.lint_text(silent, _VPATH["R4"])
    assert [f for f in out if f.rule == "R4" and not f.suppressed]
    assert [f for f in out if f.rule == "SUP"]


def test_trailing_suppression_on_flagged_line():
    src = _fixture("r4_bad.py").replace(
        "    prefix = jnp.cumsum(rows, axis=0)",
        "    prefix = jnp.cumsum(rows, axis=0)"
        "  # graftlint: disable=R4 -- fixture")
    out = engine.lint_text(src, _VPATH["R4"])
    assert [f for f in out if f.rule == "R4" and f.suppressed]
    assert not [f for f in out if not f.suppressed]


def test_tree_lints_clean_with_baseline():
    """THE acceptance gate: zero unsuppressed findings on the tree and the
    suppression inventory matches the committed baseline exactly."""
    report = engine.lint_repo(REPO)
    assert not report.unsuppressed, "\n".join(
        f.key() for f in report.unsuppressed)
    drift = engine.check_baseline(
        report, os.path.join(REPO, "tools", "graftlint", "baseline.json"))
    assert not drift, drift
    # every suppression that IS in the tree carries a justification
    assert all(f.justification for f in report.suppressed)


def test_cli_json_contract():
    """`python -m tools.graftlint --json` exits 0 on the tree and emits one
    parseable JSON report on stdout (the CI wiring)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] and payload["tool"] == "graftlint"
    assert payload["files_scanned"] > 40


def test_missing_baseline_fails_closed():
    """A deleted/typo'd baseline path must FAIL the run, not silently skip
    the suppression-inventory gate (explicit --no-baseline is the only
    opt-out)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint",
         "--baseline", "tools/graftlint/no-such-baseline.json"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 1
    assert "baseline file not found" in proc.stderr
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--no-baseline"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stderr


def test_ruff_clean_if_available():
    """The generic-lint layer (pyproject [tool.ruff]): pyflakes/E9 clean.
    Skips when the ruff binary is absent (this container does not vendor it);
    CI installs it and fails the lint job on any finding."""
    import shutil

    if shutil.which("ruff") is None:
        pytest.skip("ruff not installed (CI runs it)")
    proc = subprocess.run(["ruff", "check", "."], capture_output=True,
                          text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_fixtures_are_out_of_lint_scope():
    """The bad fixtures must never be swept into the repo lint (they exist to
    fail)."""
    scanned = {os.path.relpath(p, REPO).replace(os.sep, "/")
               for p in engine.iter_source_files(REPO)}
    assert not any(p.startswith("tests/") for p in scanned)
    assert "tools/graftlint/rules.py" not in scanned  # rules discuss patterns


# ---------------------------------------------------------------------------
# graftrace (layer 4, ISSUE 20): R9/R10 repo-rule fixture pairs + the R1
# staleness gate. R11 rides the parametrized per-file pair above.
# ---------------------------------------------------------------------------

def test_r9_fires_on_bad_pair_and_not_on_good_pair():
    rule = R9LockOrder()
    bad = rule.check_repo(os.path.join(FIXTURES, "r9_bad"))
    msgs = [f.message for f in bad if f.rule == "R9"]
    # the inversion: 'outer' (rank 10) taken while holding 'inner' (rank 20)
    assert any("inversion" in m and "'outer'" in m and "'inner'" in m
               for m in msgs), bad
    # the same pair of edges closes a cycle — reported explicitly so a
    # re-ranking "fix" that leaves a loop is still caught
    assert any("cycle" in m for m in msgs), bad
    # registry drift: raw primitive, unregistered factory name, stale entry
    assert any("raw threading.Lock()" in m for m in msgs), bad
    assert any("'unregistered'" in m and "not registered" in m
               for m in msgs), bad
    assert any("stale registry entry 'ghost'" in m for m in msgs), bad
    good = rule.check_repo(os.path.join(FIXTURES, "r9_good"))
    assert not good, good


def test_r10_fires_on_pr9_shape_and_not_on_pr9_fix():
    """Bad twin is the PR 9 handler-deadlock shape (handler closure reaches
    a non-reentrant lock normal paths hold); good twin is the PR 9 FIX
    (literal include_stats=False prunes the locked branch)."""
    rule = R10HandlerSafety()
    bad = rule.check_repo(os.path.join(FIXTURES, "r10_bad"))
    assert any(f.rule == "R10" and "'ring'" in f.message
               and "deadlock" in f.message for f in bad), bad
    good = rule.check_repo(os.path.join(FIXTURES, "r10_good"))
    assert not good, good


def test_r9_registry_site_must_match_construction_site():
    """Moving a construction without updating the registry's site is drift:
    flag it on a copy of the good pair with a wrong site."""
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        shutil.copytree(os.path.join(FIXTURES, "r9_good"), td,
                        dirs_exist_ok=True)
        lc = os.path.join(td, "glint_word2vec_tpu", "lockcheck.py")
        with open(lc, "r", encoding="utf-8") as f:
            src = f.read()
        moved = src.replace(
            '"site": "glint_word2vec_tpu/pipe.py:Pipe.__init__",\n'
            '              "owner": "fixture pipe"},\n    "inner"',
            '"site": "glint_word2vec_tpu/old.py:Old.__init__",\n'
            '              "owner": "fixture pipe"},\n    "inner"')
        assert moved != src, "fixture registry refactored — update anchor"
        with open(lc, "w", encoding="utf-8") as f:
            f.write(moved)
        out = R9LockOrder().check_repo(td)
        assert any("registered at" in f.message and "constructed at"
                   in f.message for f in out), out


def test_repo_rule_findings_honor_suppressions():
    """R9 is a repo rule — the engine only applies suppression directives to
    per-file rules, so the concurrency rules re-apply them per flagged file.
    A justified directive on the raw-construction line must suppress it."""
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        shutil.copytree(os.path.join(FIXTURES, "r9_good"), td,
                        dirs_exist_ok=True)
        pipe = os.path.join(td, "glint_word2vec_tpu", "pipe.py")
        with open(pipe, "a", encoding="utf-8") as f:
            f.write("\nimport threading\n_x = threading.Lock()"
                    "  # graftlint: disable=R9 -- fixture-sanctioned raw\n")
        out = R9LockOrder().check_repo(td)
        raws = [f for f in out if "raw threading.Lock()" in f.message]
        assert raws and all(f.suppressed and f.justification
                            for f in raws), out


def test_r1_staleness_fires_on_dead_entries_and_real_allowlist_is_live():
    """ISSUE 20 satellite: an allowlist entry whose (path, qualname) no
    longer resolves is a finding — on the REAL tree, with the REAL
    allowlist, there must be none (every blessing points at a live def)."""
    assert not R1Staleness().check_repo(REPO)
    stale = R1Staleness(allowlist=[
        ("glint_word2vec_tpu/serve/batcher.py",
         "BatchingScheduler.no_such_method"),
        ("glint_word2vec_tpu/no/such/file.py", "whatever"),
    ])
    out = stale.check_repo(REPO)
    msgs = " ".join(f.message for f in out)
    assert "no_such_method" in msgs and "cannot be parsed/found" in msgs, out


def test_r11_snapshot_escape_requires_name_and_docstring():
    """The documented-snapshot escape is narrow: 'snapshot' in the METHOD
    NAME plus a docstring exempts its accesses; the same unguarded read in
    a method missing either leg stays flagged."""
    tmpl = """
import collections
import threading


class Ring:
    def __init__(self):
        self._lock = threading.Lock()
        self._ring = collections.deque()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        with self._lock:
            self._ring.append(1)

    def {name}(self):
        {doc}return list(self._ring)
"""
    blessed = tmpl.format(
        name="snapshot_ring",
        doc='\"\"\"Callers tolerate a stale copy; GC owns the old one.'
            '\"\"\"\n        ')
    out = engine.lint_text(blessed, _VPATH["R11"])
    assert not [f for f in out if f.rule == "R11"], out
    for name, doc in [("grab", '\"\"\"Some docstring.\"\"\"\n        '),
                      ("snapshot_ring", "")]:
        out = engine.lint_text(tmpl.format(name=name, doc=doc), _VPATH["R11"])
        assert any(f.rule == "R11" for f in out), (name, out)


def test_r6_covers_the_feeds_too():
    """The fit loop's feeds moved to train/feeds.py (PR 44): a raw device
    placement there is the same finding it is in the trainer."""
    bad = engine.lint_text(_fixture("r6_bad.py"),
                           "glint_word2vec_tpu/train/feeds.py")
    assert any(f.rule == "R6" and not f.suppressed for f in bad), bad
