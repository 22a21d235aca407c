"""policyd-lint gate + unit tests.

The first test IS the CI gate: the whole package must be clean against
the checked-in ``cilium_tpu/analysis/baseline.json``. The rest pin the
analyzer's behavior on fixture snippets (one positive and one negative
case per rule) and the baseline/suppression machinery.
"""

import json
import os
import subprocess
import sys
import time

from cilium_tpu.analysis import analyze_paths, collect_files
from cilium_tpu.analysis.baseline import (
    default_baseline_path,
    load_baseline,
    new_findings,
    write_baseline,
)
from cilium_tpu.analysis.callgraph import build_callgraph
from cilium_tpu.analysis.core import Finding, ModuleSource

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "cilium_tpu")
FIXTURES = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "analysis_fixtures"
)


def fixture(name):
    return os.path.join(FIXTURES, name)


def lines_of(findings, rule):
    return sorted(f.line for f in findings if f.rule == rule)


def run_cli(*args, **popen):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # keep the CLI import-light
    return subprocess.run(
        [sys.executable, "-m", "cilium_tpu.analysis", *args],
        capture_output=True, text=True, cwd=REPO, env=env, **popen
    )


# ---------------------------------------------------------------- CI gate


def test_package_clean_against_baseline():
    """THE gate: no analyzer finding outside the checked-in baseline —
    and the whole-package run stays under the 10s budget that keeps it
    viable as a per-commit preflight."""
    t0 = time.monotonic()
    findings = analyze_paths([PKG])
    elapsed = time.monotonic() - t0
    counts, _ = load_baseline(default_baseline_path())
    fresh = new_findings(findings, counts)
    assert not fresh, (
        "new policyd-lint findings (fix them, suppress with a written "
        "justification, or regenerate the baseline via "
        "`python -m cilium_tpu.analysis --write-baseline`):\n"
        + "\n".join(f.render() for f in fresh)
    )
    assert elapsed < 10.0, (
        f"package-wide analysis took {elapsed:.1f}s — the <10s budget "
        "is part of the policyd-contracts contract (the CI gate runs "
        "it on every commit)"
    )


def test_cli_package_exits_zero():
    res = run_cli("--format", "json", "cilium_tpu/")
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout)
    assert payload["tool"] == "policyd-lint"
    assert payload["new"] == 0


def test_cli_no_paths_analyzes_the_package_alone(monkeypatch):
    """With no paths the CLI lints the cilium_tpu package and no file
    beside it."""
    from cilium_tpu.analysis import __main__ as cli

    seen = []

    def record(paths, **_kw):
        seen.append([os.path.realpath(p) for p in paths])
        return []

    monkeypatch.setattr(cli, "analyze_paths", record)
    assert cli.main(["--format", "json"]) == 0
    assert seen == [[os.path.realpath(PKG)]]


def test_cli_seeded_violation_exits_nonzero(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text(
        "# policyd: hot\n"
        "import jax.numpy as jnp\n"
        "def leak():\n"
        "    x = jnp.ones(4)\n"
        "    return int(x.sum())\n"
    )
    res = run_cli("--format", "json", str(bad))
    assert res.returncode == 1, res.stdout + res.stderr
    payload = json.loads(res.stdout)
    assert payload["new"] == 1
    assert payload["new_findings"][0]["rule"] == "TPU001"


# ---------------------------------------------------------- Family A rules


def test_tpu001_positive_and_negative():
    f = analyze_paths([fixture("hot_tpu001.py")])
    assert lines_of(f, "TPU001") == [9, 14, 20, 25, 40]
    sev = {x.line: x.severity for x in f if x.rule == "TPU001"}
    assert sev[9] == "error"  # int() on device value
    assert sev[25] == "warning"  # reduction on param-derived array
    # the np.asarray *result* is host data: int(host[0]) stays clean,
    # and the same-line suppression at the end is honored
    assert all(x.line not in (41, 47) for x in f)


def test_tpu002_positive_and_negative():
    f = analyze_paths([fixture("hot_tpu002.py")])
    assert lines_of(f, "TPU002") == [10, 17]  # for-loop + while-loop
    assert not any(x.rule == "TPU001" for x in f)


def test_tpu003_fires_without_hot_marker():
    f = analyze_paths([fixture("jit_tpu003.py")])
    assert lines_of(f, "TPU003") == [12]
    assert len(f) == 1  # negatives stay silent


def test_tpu004_dtype_drift():
    f = analyze_paths([fixture("hot_tpu004.py")])
    assert lines_of(f, "TPU004") == [8, 12]
    assert len(f) == 2


def test_tpu005_refresh_path_pulls():
    f = analyze_paths([fixture("hot_tpu005.py")])
    # attr pull / .item() above a decorator / block_until_ready /
    # forward taint through an assign
    assert lines_of(f, "TPU005") == [14, 20, 25, 31]
    assert all(x.severity == "error" for x in f if x.rule == "TPU005")
    # the unmarked twin, host-data asarray, jnp upload, cleared taint,
    # and the suppressed line all stay silent — and none of the
    # positives double-report as TPU001 (the whole point: these pulls
    # never touch a jnp chain, so TPU001's flow taint can't see them)
    assert len(f) == 4


def test_tpu005_engine_markers_stay_clean():
    """The real refresh path (engine.py carries the markers) must be
    pull-free — this is the regression gate policyd-delta bought."""
    f = analyze_paths([os.path.join(PKG, "engine.py")])
    assert lines_of(f, "TPU005") == []


def test_robust002_blocking_waits():
    f = analyze_paths([fixture("hot_robust002.py")])
    # join / wait / acquire / get() / get(True) — negatives (timed,
    # polling, dict get, str.join, with-block, suppressed) stay silent
    assert lines_of(f, "ROBUST002") == [12, 16, 20, 24, 28]
    assert all(x.severity == "warning" for x in f if x.rule == "ROBUST002")
    assert len(f) == 5


def test_robust002_verdict_path_stays_clean():
    """The regression gate policyd-overload bought: every blocking
    wait on the verdict path (pipeline, admission, watchdog) must stay
    timed so a wedged device call can never park a caller forever."""
    f = analyze_paths([
        os.path.join(PKG, "datapath", "pipeline.py"),
        os.path.join(PKG, "datapath", "admission.py"),
        os.path.join(PKG, "datapath", "l7_pipeline.py"),
    ])
    assert [x for x in f if x.rule == "ROBUST002"] == []


def test_robust003_state_writes():
    f = analyze_paths([fixture("hot_robust003.py")])
    # plain "w" / "wb" on a joined path / append / mode= kwarg "r+b" —
    # negatives (tmp sibling, mkstemp path, reads, suppressed) silent
    assert lines_of(f, "ROBUST003") == [14, 19, 24, 29]
    assert all(x.severity == "warning" for x in f if x.rule == "ROBUST003")
    assert len(f) == 4


def test_robust003_hot_modules_stay_clean():
    """The regression gate policyd-survive bought: every state-file
    write reachable from the verdict path must use the atomic
    tmp + fsync + os.replace idiom, or a restart restores a torn
    file."""
    f = analyze_paths([
        os.path.join(PKG, "datapath", "pipeline.py"),
        os.path.join(PKG, "engine.py"),
        os.path.join(PKG, "ops"),
    ])
    assert [x for x in f if x.rule == "ROBUST003"] == []


def test_hot_gating_rules_need_hot_module(tmp_path):
    cold = tmp_path / "cold.py"
    cold.write_text(
        "import jax.numpy as jnp\n"
        "def f():\n"
        "    x = jnp.ones(4)\n"
        "    return int(x.sum())\n"
    )
    assert analyze_paths([str(cold)]) == []


# ---------------------------------------------------------- Family B rules


def test_lock001_cycle_detected_once():
    f = analyze_paths([fixture("lock_cycle.py")])
    cyc = [x for x in f if x.rule == "LOCK001"]
    assert len(cyc) == 1
    assert "_map_lock" in cyc[0].message and "_idx_lock" in cyc[0].message
    # the consistently-ordered class contributes no cycle
    assert "Ordered" not in cyc[0].message


def test_lock002_003_004_blocking_fixture():
    f = analyze_paths([fixture("lock_blocking.py")])
    assert lines_of(f, "LOCK002") == [16, 21, 65]
    assert lines_of(f, "LOCK003") == [27, 32]
    assert lines_of(f, "LOCK004") == [45]


def test_held_context_propagation():
    """_write_out only runs under the lock → its open() is LOCK002;
    *_locked / always-held helpers raise no LOCK004 for their writes."""
    f = analyze_paths([fixture("lock_blocking.py")])
    held = [x for x in f if x.line == 65]
    assert held and held[0].rule == "LOCK002"
    assert "called with lock held" in held[0].message
    assert not any(
        x.rule == "LOCK004" and "data" in x.message for x in f
    )


# ------------------------------------------------------------ OBS001


def test_obs001_fixture_positive_and_negatives():
    """One drifted family flagged; documented / suppressed / scoped /
    computed registrations all stay silent."""
    f = analyze_paths([fixture("obs_metrics.py")])
    obs = [x for x in f if x.rule == "OBS001"]
    assert lines_of(f, "OBS001") == [22]
    assert obs[0].severity == "warning"
    assert "fixture_undocumented_total" in obs[0].message
    for name in ("fixture_documented_total", "fixture_suppressed_bytes",
                 "fixture_scoped_seconds", "fixture_computed_total"):
        assert not any(name in x.message for x in obs)


def test_obs001_missing_readme_flags_everything(tmp_path):
    """A metrics module with NO sibling observe/README.md flags every
    module-level registration (the catalogue must exist to drift)."""
    mod = tmp_path / "naked_metrics.py"
    mod.write_text(
        "registry = object()\n"
        "a = registry.counter('orphan_a_total', 'h')\n"  # type: ignore
        "b = registry.gauge('orphan_b', 'h')\n"
    )
    f = analyze_paths([str(mod)])
    assert lines_of(f, "OBS001") == [2, 3]
    assert "no observe/README.md" in f[0].message


def test_obs002_fixture_positives_and_negatives():
    """f-string / str() / %-format label values in a hot module are
    flagged; the bounded 'device' key, literal values, bare names, and
    the suppressed site stay silent."""
    f = analyze_paths([fixture("obs_labels.py")])
    obs = [x for x in f if x.rule == "OBS002"]
    assert lines_of(f, "OBS002") == [24, 26, 28]
    assert all(x.severity == "warning" for x in obs)
    msgs = "\n".join(x.message for x in obs)
    for key in ("'id'", "'endpoint'", "'peer'"):
        assert key in msgs
    assert "'device'" not in msgs and "'ring'" not in msgs


def test_obs002_cold_module_is_exempt(tmp_path):
    """The same interpolated-label shape outside a hot module is not
    flagged — OBS002 polices the per-batch verdict path, not one-shot
    registration-time plumbing."""
    mod = tmp_path / "cold.py"
    mod.write_text(
        "class _F:\n"
        "    def inc(self, n, labels=None):\n"
        "        pass\n"
        "fam = _F()\n"
        "def tick(identity):\n"
        "    fam.inc(1, {'id': f'{identity}'})\n"
    )
    assert lines_of(analyze_paths([str(mod)]), "OBS002") == []


def test_obs002_allowed_table_resolves_from_fixture_contracts(tmp_path):
    """A fixture package defining METRIC_BOUNDED_LABEL_KEYS in its own
    contracts.py overrides the shipped table (the _Canon resolution
    every Family C rule uses)."""
    (tmp_path / "contracts.py").write_text(
        'METRIC_BOUNDED_LABEL_KEYS = ("peer",)\n'
    )
    hot = tmp_path / "hot.py"
    hot.write_text(
        "# policyd: hot\n"
        "class _F:\n"
        "    def inc(self, n, labels=None):\n"
        "        pass\n"
        "fam = _F()\n"
        "def tick(addr):\n"
        "    fam.inc(1, {'peer': str(addr)})\n"
        "    fam.inc(1, {'device': str(addr)})\n"
    )
    f = analyze_paths([str(tmp_path)])
    # 'peer' is allowed by the local table; 'device' (allowed only in
    # the SHIPPED table) is now flagged — the local canon won
    assert lines_of(f, "OBS002") == [8]


def test_obs003_fixture_positives_and_negatives():
    """Unknown kind= literals at emission-shaped call sites are
    errors; known kinds, variable kinds, foreign callees, and the
    suppressed site stay silent; the vocabulary row nothing emits
    draws the stale-row warning at the fixture contracts.py."""
    f = analyze_paths([fixture("journal")])
    obs = [x for x in f if x.rule == "OBS003"]
    by_path = {}
    for x in obs:
        by_path.setdefault(os.path.basename(x.path), []).append(x)
    emit = sorted(by_path.get("emitters.py", []), key=lambda x: x.line)
    assert [x.line for x in emit] == [21, 23]
    assert all(x.severity == "error" for x in emit)
    assert "'bot'" in emit[0].message
    assert "'quarantin'" in emit[1].message
    stale = by_path.get("contracts.py", [])
    assert len(stale) == 1 and stale[0].severity == "warning"
    assert "'stale_row'" in stale[0].message
    # the emitted rows draw no stale warning
    assert "'boot'" not in stale[0].message


def test_obs003_vocabulary_resolves_from_shipped_table(tmp_path):
    """A module with emission sites but no local contracts.py checks
    against the SHIPPED JOURNAL_KINDS — and without a local table
    definition the stale-row direction stays quiet (the analyzed set
    can't see every emitter of the shipped vocabulary)."""
    mod = tmp_path / "emit.py"
    mod.write_text(
        "def tick(oj):\n"
        "    oj(kind='quarantine')\n"
        "    oj(kind='not-a-kind')\n"
    )
    f = analyze_paths([str(mod)])
    assert lines_of(f, "OBS003") == [3]


def test_obs001_package_metrics_stay_documented():
    """The real catalogue gate: every family registered in metrics.py
    is documented in observe/README.md (beyond-baseline drift is also
    caught by test_package_clean_against_baseline, but this one names
    the contract)."""
    f = analyze_paths([os.path.join(PKG, "metrics.py")])
    assert lines_of(f, "OBS001") == []


# ------------------------------------------------- suppressions + baseline


def test_file_level_suppression():
    f = analyze_paths([fixture("suppressed_file.py")])
    assert not any(x.rule == "TPU001" for x in f)
    assert lines_of(f, "TPU002") == [14]


def test_baseline_round_trip(tmp_path):
    findings = analyze_paths([fixture("hot_tpu001.py")])
    assert findings
    path = str(tmp_path / "baseline.json")
    write_baseline(findings, path)
    counts, _ = load_baseline(path)
    assert new_findings(findings, counts) == []
    # editing the flagged line invalidates its entry (context changed)
    f0 = findings[0]
    edited = Finding(
        rule=f0.rule, severity=f0.severity, path=f0.path,
        line=f0.line, message=f0.message, context="return int(other)",
    )
    assert new_findings([edited], counts) == [edited]
    # a second identical violation exceeds the count budget
    assert new_findings([f0, f0], counts) == [f0]


def test_baseline_preserves_justifications(tmp_path):
    findings = analyze_paths([fixture("hot_tpu001.py")])
    path = str(tmp_path / "baseline.json")
    key = findings[0].key()
    write_baseline(findings, path, justifications={key: "intended pull"})
    _, notes = load_baseline(path)
    assert notes[key] == "intended pull"


def test_cli_write_baseline_then_clean(tmp_path):
    path = str(tmp_path / "b.json")
    res = run_cli("--write-baseline", "--baseline", path,
                  fixture("lock_blocking.py"))
    assert res.returncode == 0, res.stdout + res.stderr
    res = run_cli("--baseline", path, fixture("lock_blocking.py"))
    assert res.returncode == 0, res.stdout + res.stderr


# ------------------------------------------------------------- call graph


XMOD = fixture("xmod")


def _graph(paths):
    return build_callgraph([ModuleSource(p) for p in collect_files(paths)])


def test_callgraph_relative_and_aliased_imports(tmp_path):
    """``from ..util import helper as h`` and ``from .. import util as
    u`` both resolve to the same function through the alias tables."""
    pkg = tmp_path / "pkg"
    (pkg / "sub").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "sub" / "__init__.py").write_text("")
    (pkg / "util.py").write_text("def helper():\n    return 1\n")
    (pkg / "sub" / "deep.py").write_text(
        "from ..util import helper as h\n"
        "from .. import util as u\n"
        "def caller():\n"
        "    return h() + u.helper()\n"
    )
    g = _graph([str(pkg)])
    info = g.functions["pkg.sub.deep:caller"]
    assert info.calls.count("pkg.util:helper") == 2


def test_callgraph_method_binding(tmp_path):
    """Constructor-typed locals and module-level singletons bind method
    calls to the right class, one file away."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "eng.py").write_text(
        "class Engine:\n"
        "    def run(self):\n"
        "        return self._step()\n"
        "    def _step(self):\n"
        "        return 0\n"
    )
    (pkg / "use.py").write_text(
        "from .eng import Engine\n"
        "hub = Engine()\n"
        "def local():\n"
        "    e = Engine()\n"
        "    return e.run()\n"
        "def singleton():\n"
        "    return hub.run()\n"
    )
    g = _graph([str(pkg)])
    assert g.functions["pkg.use:local"].calls == ["pkg.eng:Engine.run"]
    assert g.functions["pkg.use:singleton"].calls == ["pkg.eng:Engine.run"]
    # self-calls bind within the class
    assert g.functions["pkg.eng:Engine.run"].calls == [
        "pkg.eng:Engine._step"
    ]


def test_callgraph_dependents_closure():
    """--changed closure: helpers.py pulls in its direct importers."""
    g = _graph([XMOD])
    closure = g.dependents_of(["xmod/helpers.py"])
    assert "xmod/hotcaller.py" in closure
    assert "xmod/locked.py" in closure
    assert "xmod/option.py" not in closure


# --------------------------------------------- inter-procedural (1 edge)


def test_interproc_tpu001_cross_module():
    """A hot caller handing a device value to a helper that pulls it in
    ANOTHER module — invisible to per-module analysis by design."""
    f = analyze_paths([XMOD])
    hits = [x for x in f if x.rule == "TPU001"]
    assert [(x.path, x.line) for x in hits] == [("xmod/hotcaller.py", 17)]
    m = hits[0].message
    assert "pull_stats" in m and ".item()" in m
    assert "xmod/helpers.py" in m and "one call away" in m
    assert hits[0].severity == "error"


def test_interproc_lock002_cross_module():
    """Holding a lock across a call whose callee blocks (open()) in
    another module."""
    f = analyze_paths([XMOD])
    hits = [x for x in f if x.rule == "LOCK002"]
    assert [(x.path, x.line) for x in hits] == [("xmod/locked.py", 21)]
    m = hits[0].message
    assert "write_out" in m and "open" in m and "one call away" in m


def test_interproc_lock002_repo_all_sites_justified():
    """Baseline-shrink invariant: every LOCK002 in the shipping package
    (direct AND one-edge) is either fixed or carries an inline
    suppression with its invariant written at the site — the baseline
    holds NO LOCK002 entries anymore."""
    f = analyze_paths([PKG])
    assert [x.render() for x in f if x.rule == "LOCK002"] == []


# ----------------------------------------------------------- Family C rules


def test_opt001_fixture_package():
    f = [x for x in analyze_paths([XMOD]) if x.rule == "OPT001"]
    by_path = {}
    for x in f:
        by_path.setdefault(x.path, []).append(x)
    # option.py: GateBeta (no tripwire), GateGamma (dead toggle),
    # GateDelta (no table entry), GateEpsilon (bad field + inert)
    assert sorted(x.line for x in by_path["xmod/option.py"]) == [
        17, 18, 19, 20, 20,
    ]
    text = " ".join(x.message for x in by_path["xmod/option.py"])
    assert "GateBeta has no tripwire test" in text
    assert "GateGamma has no consumption site" in text
    assert "GateDelta has no OPTION_BOOT_FIELDS entry" in text
    assert "'gate_epsilon' but DaemonConfig has no such field" in text
    # healthy options stay silent
    assert "GateAlpha" not in text and "GateZeta" not in text
    # reverse direction: stale table row flagged at the table
    [stale] = by_path["xmod/contracts.py"]
    assert "GateOmega" in stale.message and "stale table row" in stale.message
    # hot modules never read the option map per batch
    [hot] = by_path["xmod/gated.py"]
    assert hot.line == 35 and "option-map read in a hot module" in hot.message


def test_opt002_gated_mutation():
    f = [x for x in analyze_paths([XMOD]) if x.rule == "OPT002"]
    assert [(x.path, x.line) for x in f] == [("xmod/gated.py", 18)]
    assert f[0].severity == "warning"
    assert "attribution" in f[0].message and "explain()" in f[0].message
    # _depth (also mutated outside the gate) and explain_gated (gated
    # reader) must not produce findings
    assert "_depth" not in f[0].message


def test_api001_fixture():
    f = analyze_paths([fixture("api_literals.py")])
    assert lines_of(f, "API001") == [8, 9, 13, 15, 21]
    assert len(f) == 5  # matching constants / string REASON_ stay silent
    by_line = {x.line: x.message for x in f}
    assert "drifts from the canonical value 151" in by_line[8]
    assert "unknown drop-reason constant REASON_FIXTURE_LOCAL" in by_line[9]
    assert "drifts from the canonical value 2" in by_line[13]
    assert "canonical ladder" in by_line[15]
    assert "'warpdrive'" in by_line[21]
    assert all(x.severity == "error" for x in f)


def test_family_c_repo_stays_clean():
    """The shipping package satisfies every Family C contract outright
    (no baseline entries, no suppressions)."""
    f = analyze_paths([PKG])
    for rule in ("OPT001", "OPT002", "API001", "OBS002", "OBS003"):
        offenders = [x.render() for x in f if x.rule == rule]
        assert offenders == [], f"{rule} regressions:\n" + "\n".join(offenders)


# ------------------------------------------------------- incremental mode


def test_changed_mode_restricts_to_closure():
    """--changed keeps findings from the changed files plus their
    direct importers — the caller-side inter-procedural findings a
    changed helper causes still surface, everything else is muted."""
    f = analyze_paths([XMOD], changed=["xmod/helpers.py"])
    assert {(x.rule, x.path) for x in f} == {
        ("TPU001", "xmod/hotcaller.py"),
        ("LOCK002", "xmod/locked.py"),
    }
    # an unrelated change reports nothing from the helpers cluster
    f = analyze_paths([XMOD], changed=["xmod/option.py"])
    assert not any(x.path in ("xmod/hotcaller.py", "xmod/locked.py")
                   for x in f)


def test_cli_changed_mode_runs():
    """--changed derives the file set from git and exits cleanly on a
    tree whose full analysis is baseline-clean (restriction can only
    shrink the finding set)."""
    res = run_cli("--changed", "HEAD")
    assert res.returncode == 0, res.stdout + res.stderr


def test_cli_format_github(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text(
        "# policyd: hot\n"
        "import jax.numpy as jnp\n"
        "def leak():\n"
        "    x = jnp.ones(4)\n"
        "    return int(x.sum())\n"
    )
    res = run_cli("--format", "github", str(bad))
    assert res.returncode == 1, res.stdout + res.stderr
    assert "::error file=seeded.py,line=5::TPU001" in res.stdout
