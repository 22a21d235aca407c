"""policyd-lint: AST-based hot-path & lock-discipline analyzer.

Pure-stdlib static analysis for the two bug classes that kill the
paper's target (≥100M verdicts/s, p99 <50µs) silently:

- Family A (TPU hot path): implicit host↔device syncs, jnp-in-loop
  tracing, jit closures over mutable globals, dtype drift — see
  ``hotpath``.
- Family B (lock discipline): lock-order cycles, blocking ops and
  callbacks under locks, guard inconsistency — see ``locks``.
- Family C (stable-API contracts): option discipline, stable-literal
  drift — see ``contracts``. Family C and
  the one-edge-deep inter-procedural variants of TPU001/LOCK002 run on
  a package-wide call graph (``callgraph``).

Run ``python -m cilium_tpu.analysis`` (CI gate: exits non-zero on any
finding not covered by the checked-in ``baseline.json``). See
``README.md`` in this directory for rule ids, the hot-module
convention, suppression syntax, and baseline maintenance.
"""

from __future__ import annotations

import os
from typing import Iterable, List, Optional, Sequence, Set

from .callgraph import CallGraph, build_callgraph
from .contracts import analyze_contracts
from .core import Finding, ModuleSource
from .hotpath import analyze_hotpath
from .locks import LockIndex, analyze_locks_module, cycle_findings
from .obsdocs import analyze_obsdocs
from .obsjournal import analyze_obsjournal
from .obslabels import analyze_obslabels

__all__ = [
    "CallGraph",
    "Finding",
    "ModuleSource",
    "analyze_paths",
    "build_callgraph",
    "collect_files",
    "default_target",
]


def default_target() -> str:
    """The cilium_tpu package directory (the default analysis root)."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def collect_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                out.append(os.path.abspath(p))
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = sorted(
                d for d in dirs
                if d != "__pycache__" and not d.startswith(".")
            )
            for name in sorted(files):
                if name.endswith(".py"):
                    out.append(os.path.abspath(os.path.join(root, name)))
    return sorted(set(out))


def analyze_paths(
    paths: Sequence[str],
    rules: Optional[Iterable[str]] = None,
    restrict: Optional[Iterable[str]] = None,
    changed: Optional[Iterable[str]] = None,
) -> List[Finding]:
    """Run all three rule families over every .py under ``paths``.

    Suppressions (line/file) are already applied; the baseline is NOT —
    callers diff against it via ``baseline.new_findings``.

    ``restrict`` (relpaths) keeps only findings anchored in those
    files — the whole set is still parsed and graphed (cross-module
    rules need full context), only the reporting is narrowed.
    ``changed`` (relpaths) is the incremental mode: the restriction
    set becomes the changed files plus their direct call-graph
    dependents (modules importing them), so a changed helper still
    surfaces the caller-side inter-procedural findings it causes.
    """
    files = collect_files(paths)
    modules: List[ModuleSource] = []
    findings: List[Finding] = []
    for path in files:
        try:
            modules.append(ModuleSource(path))
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            findings.append(
                Finding(
                    rule="PARSE",
                    severity="error",
                    path=os.path.basename(path),
                    line=getattr(e, "lineno", 0) or 0,
                    message=f"cannot parse: {type(e).__name__}: {e}",
                )
            )

    # pass 1: package-wide lock index (cross-method edges need it)
    index = LockIndex()
    for mod in modules:
        index.add_module(mod)
    index.finalize()

    # pass 2: package-wide call graph (inter-procedural TPU001/LOCK002
    # and Family C consume it)
    graph = build_callgraph(modules, lock_index=index)

    all_edges = []
    for mod in modules:
        findings.extend(analyze_hotpath(mod, graph=graph))
        findings.extend(analyze_obsdocs(mod))
        lock_findings, edges = analyze_locks_module(mod, index, graph=graph)
        findings.extend(lock_findings)
        all_edges.extend(edges)
    findings.extend(cycle_findings(all_edges))
    findings.extend(analyze_contracts(modules, graph))
    findings.extend(analyze_obslabels(modules))
    findings.extend(analyze_obsjournal(modules))

    if changed is not None:
        closure = graph.dependents_of(list(changed))
        restrict = closure if restrict is None else set(restrict) | closure
    if restrict is not None:
        keep_paths = set(restrict)
        findings = [f for f in findings if f.path in keep_paths]

    # apply suppressions (cycle findings self-filter on edge sites,
    # but their anchor line suppression is honored here too)
    by_path = {m.relpath: m for m in modules}
    kept: List[Finding] = []
    for f in findings:
        mod = by_path.get(f.path)
        if mod is not None and mod.is_suppressed(f.rule, f.line):
            continue
        kept.append(f)

    wanted: Optional[Set[str]] = (
        {r.strip().upper() for r in rules} if rules else None
    )
    if wanted:
        kept = [f for f in kept if f.rule in wanted]
    kept.sort(key=lambda f: (f.path, f.line, f.rule))
    return kept
