"""Shared infrastructure for the policyd-lint static analyzers.

Everything here is pure stdlib (``ast`` + ``re``): the analyzers must
run in CI contexts without importing jax or touching a device.

Concepts
--------
hot module
    A module on the verdict hot path. Determined by path convention
    (``*/ops/*.py``, ``*/engine.py``, ``*/datapath/pipeline.py``) or an
    explicit ``# policyd: hot`` marker comment anywhere in the file.
suppression
    ``# policyd-lint: disable=RULE[,RULE...]`` on a finding's line (or
    on a comment-only line directly above it) silences those rules at
    that site. ``# policyd-lint: disable-file=RULE`` silences a rule
    for the whole file. Suppressions are for *justified* findings —
    the comment should say why the pattern is safe.
baseline
    Pre-existing findings checked into ``baseline.json``. CI fails
    only on findings NOT covered by the baseline, so the gate catches
    regressions without demanding a flag-day cleanup.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import os
import re
from typing import Dict, List, Optional, Set, Tuple

SEV_ERROR = "error"
SEV_WARNING = "warning"

# Path conventions marking the verdict hot path (relative to the
# package root, forward slashes).
HOT_PATH_PATTERNS = (
    "*/ops/*.py",
    "*/engine.py",
    "*/datapath/pipeline.py",
)

_HOT_MARKER_RE = re.compile(r"#\s*policyd:\s*hot\b")
_SUPPRESS_RE = re.compile(r"#\s*policyd-lint:\s*disable=([A-Za-z0-9_,\s]+)")
_FILE_SUPPRESS_RE = re.compile(
    r"#\s*policyd-lint:\s*disable-file=([A-Za-z0-9_,\s]+)"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One analyzer finding.

    ``context`` is the stripped source text of the flagged line — the
    baseline matches on (rule, path, context) rather than line numbers
    so unrelated edits above a baselined finding don't break CI.
    """

    rule: str
    severity: str
    path: str  # package-relative, forward slashes
    line: int
    message: str
    context: str = ""

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.context)

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "severity": self.severity,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "context": self.context,
        }

    def render(self) -> str:
        return (
            f"{self.path}:{self.line}: {self.rule} "
            f"{self.severity}: {self.message}"
        )


def package_relpath(path: str) -> str:
    """Path of ``path`` relative to the topmost enclosing package
    (walks up while __init__.py exists). Stable across invocation
    directories, so baseline keys survive being run from anywhere."""
    path = os.path.abspath(path)
    root = os.path.dirname(path)
    while os.path.isfile(os.path.join(root, "__init__.py")):
        parent = os.path.dirname(root)
        if parent == root:
            break
        root = parent
    return os.path.relpath(path, root).replace(os.sep, "/")


class ModuleSource:
    """A parsed module plus its comment-derived metadata (markers and
    suppressions live in comments, which ``ast`` discards)."""

    def __init__(self, path: str, text: Optional[str] = None) -> None:
        self.path = os.path.abspath(path)
        if text is None:
            with open(self.path, "r", encoding="utf-8") as f:
                text = f.read()
        self.text = text
        self.relpath = package_relpath(self.path)
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=self.path)  # may raise
        self.hot_marked = False
        self.file_suppressed: Set[str] = set()
        # line number -> set of suppressed rule ids
        self.suppressed: Dict[int, Set[str]] = {}
        for i, ln in enumerate(self.lines, 1):
            m = _SUPPRESS_RE.search(ln)
            if m:
                rules = {
                    r.strip().upper()
                    for r in m.group(1).split(",")
                    if r.strip()
                }
                self.suppressed.setdefault(i, set()).update(rules)
                if ln.split("#", 1)[0].strip() == "":
                    # comment-only line: applies to the next line too
                    self.suppressed.setdefault(i + 1, set()).update(rules)
            m = _FILE_SUPPRESS_RE.search(ln)
            if m:
                self.file_suppressed.update(
                    r.strip().upper()
                    for r in m.group(1).split(",")
                    if r.strip()
                )
            if _HOT_MARKER_RE.search(ln):
                self.hot_marked = True

    # ------------------------------------------------------------------
    def is_hot(self) -> bool:
        if self.hot_marked:
            return True
        rp = "/" + self.relpath  # anchor so "*/ops/*" can't match root
        return any(fnmatch.fnmatch(rp, "*" + p.lstrip("*")) or
                   fnmatch.fnmatch(rp, p) for p in HOT_PATH_PATTERNS)

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def is_suppressed(self, rule: str, line: int) -> bool:
        rule = rule.upper()
        if rule in self.file_suppressed or "ALL" in self.file_suppressed:
            return True
        at = self.suppressed.get(line, ())
        return rule in at or "ALL" in at

    def finding(
        self, rule: str, severity: str, line: int, message: str
    ) -> Finding:
        return Finding(
            rule=rule,
            severity=severity,
            path=self.relpath,
            line=line,
            message=message,
            context=self.line_text(line),
        )


# ---------------------------------------------------------------------------
# small AST helpers shared by both rule families


def attr_chain(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` → ["a", "b", "c"]; None for non-name-rooted chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return None


def call_name(node: ast.Call) -> Optional[str]:
    """Dotted name of a call target, e.g. "jnp.take" / "int"."""
    chain = attr_chain(node.func)
    return ".".join(chain) if chain else None


def walk_skipping(node: ast.AST, skip: Tuple[type, ...]):
    """ast.walk that does not descend into node types in ``skip``
    (the node itself is always yielded)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, skip):
                continue
            stack.append(child)


def iter_target_names(target: ast.AST):
    """Names bound by an assignment target (tuples unpacked)."""
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            yield from iter_target_names(elt)
    elif isinstance(target, ast.Starred):
        yield from iter_target_names(target.value)
