"""policyd-lint runner CLI.

Usage::

    python -m cilium_tpu.analysis [paths...] [--format text|json|github]
        [--baseline PATH | --no-baseline] [--write-baseline]
        [--rules TPU001,LOCK002] [--all] [--changed [REF]]

Exit codes: 0 = clean against baseline; 1 = new findings; 2 = usage /
internal error. With no paths, analyzes the cilium_tpu package.

``--changed [REF]`` is the incremental mode: the full set is still
parsed and call-graphed (cross-module rules need whole-package
context), but reporting narrows to files changed vs REF (default
HEAD, per ``git diff`` + untracked) plus their direct call-graph
dependents. ``--format github`` emits ::error/::warning workflow
annotations for the new findings.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List, Optional

from . import analyze_paths, default_target
from .baseline import (
    default_baseline_path,
    load_baseline,
    new_findings,
    write_baseline,
)


def _repo_root() -> str:
    """Directory containing the package (where relpaths anchor)."""
    return os.path.dirname(default_target())


def _changed_relpaths(ref: str) -> List[str]:
    """Repo-relative .py paths changed vs ``ref`` (plus untracked)."""
    root = _repo_root()
    out: List[str] = []
    for cmd in (
        ["git", "diff", "--name-only", ref, "--"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        proc = subprocess.run(
            cmd, cwd=root, capture_output=True, text=True, check=False
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"{' '.join(cmd)} failed: {proc.stderr.strip()}"
            )
        out.extend(
            line.strip()
            for line in proc.stdout.splitlines()
            if line.strip().endswith(".py")
        )
    return sorted(set(out))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m cilium_tpu.analysis",
        description="policyd-lint: hot-path & lock-discipline analyzer",
    )
    ap.add_argument("paths", nargs="*", help="files/dirs (default: package)")
    ap.add_argument(
        "--format", choices=("text", "json", "github"), default="text"
    )
    ap.add_argument(
        "--changed",
        nargs="?",
        const="HEAD",
        default=None,
        metavar="REF",
        help="restrict reporting to files changed vs REF (default HEAD) "
        "plus their direct call-graph dependents",
    )
    ap.add_argument(
        "--baseline",
        default=None,
        help="baseline file (default: the checked-in analysis/baseline.json)",
    )
    ap.add_argument(
        "--no-baseline",
        action="store_true",
        help="every finding is 'new' (full inventory mode)",
    )
    ap.add_argument(
        "--write-baseline",
        action="store_true",
        help="regenerate the baseline from this run's findings and exit 0",
    )
    ap.add_argument(
        "--rules", default=None, help="comma-separated rule id filter"
    )
    ap.add_argument(
        "--all",
        action="store_true",
        help="print all findings, not just new ones",
    )
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0

    paths = args.paths or [default_target()]
    rules = args.rules.split(",") if args.rules else None
    changed: Optional[List[str]] = None
    if args.changed is not None:
        try:
            changed = _changed_relpaths(args.changed)
        except (RuntimeError, OSError) as e:
            print(f"policyd-lint: --changed: {e}", file=sys.stderr)
            return 2
        if not changed:
            print(
                f"policyd-lint: no .py changes vs {args.changed}",
                file=sys.stderr,
            )
            return 0
    try:
        findings = analyze_paths(paths, rules=rules, changed=changed)
    except Exception as e:  # pragma: no cover - internal error surface
        print(f"policyd-lint: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    baseline_path = args.baseline or default_baseline_path()
    if args.write_baseline:
        _, notes = (
            load_baseline(baseline_path)
            if not args.no_baseline
            else ({}, {})
        )
        write_baseline(findings, baseline_path, justifications=notes)
        print(
            f"policyd-lint: wrote {len(findings)} finding(s) to "
            f"{baseline_path}",
            file=sys.stderr,
        )
        return 0

    if args.no_baseline:
        fresh = list(findings)
        baseline_used = None
    else:
        counts, _notes = load_baseline(baseline_path)
        fresh = new_findings(findings, counts)
        baseline_used = baseline_path

    if args.format == "json":
        payload = {
            "tool": "policyd-lint",
            "total": len(findings),
            "new": len(fresh),
            "baseline": baseline_used,
            "new_findings": [f.to_dict() for f in fresh],
        }
        if args.all:
            payload["findings"] = [f.to_dict() for f in findings]
        print(json.dumps(payload))
    elif args.format == "github":
        shown = findings if args.all else fresh
        for f in shown:
            level = "error" if f.severity == "error" else "warning"
            # workflow-command message body must stay single-line
            msg = f.message.replace("\n", " ")
            print(
                f"::{level} file={f.path},line={f.line}::"
                f"{f.rule} {msg}"
            )
        print(
            f"policyd-lint: {len(findings)} finding(s), {len(fresh)} new",
            file=sys.stderr,
        )
    else:
        shown = findings if args.all else fresh
        for f in shown:
            print(f.render())
        print(
            f"policyd-lint: {len(findings)} finding(s), "
            f"{len(fresh)} new"
            + (f" (baseline: {baseline_used})" if baseline_used else ""),
            file=sys.stderr,
        )
    return 1 if fresh else 0


if __name__ == "__main__":
    sys.exit(main())
