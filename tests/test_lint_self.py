"""The analyzer gates this repo: lint horovod_tpu/ + examples/ in tier-1.

Any new deadlock-prone collective pattern introduced by a future PR fails
here with the finding's rule ID, location and fix hint.  Known, reviewed
findings go in the inline allowlist below — each entry must carry a reason.
"""

import os
import re

from horovod_tpu.analysis import lint_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (rule, path-suffix, line) -> reason.  Line numbers keep the allowlist
# honest: moving/duplicating an allowlisted pattern re-fails the gate.
ALLOWLIST = {
    # (example)
    # ("HVD101", "horovod_tpu/foo.py", 42): "rank-guard is matched by a "
    #     "process_set covering exactly those ranks",
}


def _key(finding):
    rel = os.path.relpath(finding.path, REPO)
    return (finding.rule, rel.replace(os.sep, "/"), finding.line)


def test_self_lint_errors_gate():
    findings = lint_paths([os.path.join(REPO, "horovod_tpu"),
                           os.path.join(REPO, "examples")])
    errors = [f for f in findings
              if f.is_error and _key(f) not in ALLOWLIST]
    assert not errors, (
        "new collective-correctness errors (fix them or allowlist with a "
        "reason):\n" + "\n".join(f.render() for f in errors))


def test_self_lint_warning_budget():
    """Warnings don't fail the gate, but silent growth does: a PR adding
    warning-severity findings must either fix them or consciously raise
    this budget in the same diff."""
    findings = lint_paths([os.path.join(REPO, "horovod_tpu"),
                           os.path.join(REPO, "examples")])
    warnings = [f for f in findings
                if not f.is_error and _key(f) not in ALLOWLIST]
    budget = 0   # current state: repo lints clean
    assert len(warnings) <= budget, (
        f"warning count {len(warnings)} exceeds budget {budget}:\n"
        + "\n".join(f.render() for f in warnings))


def test_self_lint_covers_monitor_package():
    """The monitor subsystem is linted explicitly (not only via the
    package walk, which a future exclude rule could silently narrow):
    its files must parse and carry zero findings of any severity."""
    mon_dir = os.path.join(REPO, "horovod_tpu", "monitor")
    files = [f for f in os.listdir(mon_dir) if f.endswith(".py")]
    assert len(files) >= 5, files       # registry/aggregator/agent/http/CLI
    findings = lint_paths([mon_dir])
    assert not findings, "\n".join(f.render() for f in findings)


def test_self_lint_covers_trace_package():
    """Same explicit coverage for the tracing subsystem: core/writer/
    merge/analyze/CLI must parse and lint clean."""
    tr_dir = os.path.join(REPO, "horovod_tpu", "trace")
    files = [f for f in os.listdir(tr_dir) if f.endswith(".py")]
    assert len(files) >= 5, files       # core/writer/merge/analyze/CLI
    findings = lint_paths([tr_dir])
    assert not findings, "\n".join(f.render() for f in findings)


def test_self_lint_covers_autoscale_stack():
    """Explicit coverage for the autoscaling + resilient-state subsystem
    (ISSUES 10/14): the policy engine, the driver/registration/worker
    layers it drives, and the state plane must parse and lint clean."""
    el_dir = os.path.join(REPO, "horovod_tpu", "elastic")
    files = {f for f in os.listdir(el_dir) if f.endswith(".py")}
    assert {"autoscale.py", "driver.py", "registration.py",
            "worker.py", "stateplane.py"} <= files, files
    findings = lint_paths([el_dir])
    assert not findings, "\n".join(f.render() for f in findings)


def test_self_lint_covers_slice_topology():
    """Explicit coverage for the two-level data plane's topology module
    (ISSUE 17): ``parallel/topology.py`` is jax-free and feeds the engine
    the (cross, local) mesh structure — it must parse and lint clean."""
    path = os.path.join(REPO, "horovod_tpu", "parallel", "topology.py")
    assert os.path.exists(path), path
    findings = lint_paths([path])
    assert not findings, "\n".join(f.render() for f in findings)


def test_self_lint_covers_fault_harness():
    """Explicit coverage for the fault-injection harness AND the churn
    runner (ISSUE 12): both drive the control plane from the jax-free
    tier and the bench, and must parse and lint clean."""
    t_dir = os.path.join(REPO, "horovod_tpu", "testing")
    files = {f for f in os.listdir(t_dir) if f.endswith(".py")}
    assert {"faults.py", "churn.py"} <= files, files
    findings = lint_paths([t_dir])
    assert not findings, "\n".join(f.render() for f in findings)


def test_self_lint_covers_serving_plane():
    """Explicit coverage for the serving plane (ISSUES 19/20): the
    batcher, replica loop, front door, and circuit breaker carry the
    fault-tolerance invariants and must parse and lint clean."""
    sv_dir = os.path.join(REPO, "horovod_tpu", "serve")
    files = {f for f in os.listdir(sv_dir) if f.endswith(".py")}
    assert {"batcher.py", "replica.py", "frontdoor.py",
            "resilience.py"} <= files, files
    findings = lint_paths([sv_dir])
    assert not findings, "\n".join(f.render() for f in findings)


# ------------------------------------------------- whole-package gate (13)
_GATE_RESULT = []      # memo: the full-repo analysis runs once per session


def _gate_result():
    if not _GATE_RESULT:
        from horovod_tpu.analysis.gate import run_gate
        _GATE_RESULT.append(run_gate(root=REPO, quiet=True))
    return _GATE_RESULT[0]


def test_whole_package_gate_green():
    """The interprocedural self-lint (tools/lint_gate.py semantics): the
    two-pass analyzer over horovod_tpu/ + examples/ + tools/ must produce
    NO findings beyond the reviewed baseline."""
    new, _stale, _baselined = _gate_result()
    assert not new, (
        "new whole-package findings (fix them, pragma them with a reason, "
        "or — warnings only — re-baseline via "
        "`python tools/lint_gate.py --update-baseline`):\n"
        + "\n".join(f.render() for f in new))


def test_whole_package_baseline_not_stale():
    """Baseline honesty: entries whose finding no longer fires must be
    pruned in the same PR that fixes the code."""
    _new, stale, _baselined = _gate_result()
    assert not stale, f"stale baseline entries, prune them: {stale}"


def test_whole_package_baseline_carries_no_errors():
    """Only warning-severity findings may be baselined; error-severity
    ones must be fixed or carry an inline pragma with a reason."""
    from horovod_tpu.analysis.baseline import load_baseline
    from horovod_tpu.analysis.findings import RULES, Severity
    baseline = load_baseline(
        os.path.join(REPO, "tools", "lint_baseline.json"))
    errors = [k for k in baseline
              if RULES[k[0]].severity is Severity.ERROR]
    assert not errors, errors


def test_known_out_of_scope_files_now_lint_clean_via_pragmas():
    """ISSUE 13 satellite: the deliberate divergence in
    tests/data/worker_join.py / worker_sanitizer.py is annotated with
    inline pragmas — the files lint error-free WITHOUT directory scoping."""
    findings = lint_paths([
        os.path.join(REPO, "tests", "data", "worker_join.py"),
        os.path.join(REPO, "tests", "data", "worker_sanitizer.py"),
    ])
    errors = [f for f in findings if f.is_error]
    assert not errors, "\n".join(f.render() for f in errors)


# Pages whose named files must exist: the ones that say where the gate, the
# tools and the benchmark live.  A path in backticks or a link target that
# ends in .py, .md or .json, resolved against the page's directory, the
# repo root or (bare names) the package.
_PAGES = ("README.md", "tools/README.md", "docs/benchmarks.md")
_NAMED_FILE = re.compile(
    r"`([\w./-]+\.(?:py|md|json))`|\]\(([\w./-]+\.(?:py|md|json))[#)]")
# What a run writes, and the driver's file outside the repo.
_NOT_COMMITTED = ("benchmark/out/", "chiprun_out/", "/root/")


def test_pages_and_gate_scope_name_files_that_exist():
    """A page that names a file that is gone sends its reader nowhere:
    every path in the gate's SCOPE exists, and so does every repo-relative
    .py, .md or .json file the three pages name."""
    from horovod_tpu.analysis.gate import SCOPE
    missing = [p for p in SCOPE if not os.path.exists(os.path.join(REPO, p))]
    for page in _PAGES:
        with open(os.path.join(REPO, page)) as f:
            text = f.read()
        named = {a or b for a, b in _NAMED_FILE.findall(text)}
        assert named, page
        roots = (os.path.dirname(os.path.join(REPO, page)), REPO,
                 os.path.join(REPO, "horovod_tpu"))
        missing += [
            f"{page}: {name}" for name in sorted(named)
            if not name.startswith(_NOT_COMMITTED)
            and not any(os.path.exists(os.path.join(r, name)) for r in roots)]
    assert not missing, missing


def test_allowlist_entries_still_fire():
    """Stale allowlist entries (fixed code, moved lines) must be pruned."""
    findings = lint_paths([os.path.join(REPO, "horovod_tpu"),
                           os.path.join(REPO, "examples")])
    live = {_key(f) for f in findings}
    stale = [k for k in ALLOWLIST if k not in live]
    assert not stale, f"allowlist entries no longer fire, remove them: {stale}"
