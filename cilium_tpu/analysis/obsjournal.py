"""OBS003: journal event kinds are a closed vocabulary.

The lifecycle journal (observe/journal.py) is a fleet-wide wire
surface: per-node journals are merged into one timeline across nodes
running DIFFERENT commits, bugtool ``events.json`` archives are diffed
offline, and the tests assert against specific kinds. A kind
literal that drifts from ``cilium_tpu.contracts.JOURNAL_KINDS`` is
therefore worse than a typo — ``EventJournal.emit`` raises on it at
runtime, from INSIDE a lifecycle callback (quarantine, drain, watchdog
sweep), which is the worst possible place to discover a misspelling.

The package's emission convention makes the check static: every
journal emission passes ``kind="..."`` as a keyword argument to a
callable named ``emit`` / ``oj`` / ``on_journal`` / ``_journal_emit``
(the four shapes the hub-style one-attribute-read gate produces).

Rules
-----
OBS003  (error) an emission-shaped call — callee's terminal name in
        the convention set — passing a ``kind=`` string literal that
        is not a JOURNAL_KINDS row.
OBS003  (warning, reverse) a JOURNAL_KINDS row that NO emission site
        in the analyzed set references: a stale vocabulary entry
        consumers will wait on forever; remove the row or wire the
        emitter. Anchored at the table definition.

Suppress a justified exception with ``# policyd-lint:
disable=OBS003``.
"""

from __future__ import annotations

import ast
from typing import List, Sequence, Set, Tuple

from .contracts import _Canon
from .core import SEV_ERROR, SEV_WARNING, Finding, ModuleSource

# terminal callee names the journal emission convention uses: the
# journal method itself, the daemon's OFF-gated wrapper, and the two
# local-alias shapes hot modules read the hook into
_EMIT_NAMES = ("emit", "oj", "on_journal", "_journal_emit")


def _callee_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _kind_literal(call: ast.Call) -> Tuple[bool, str, int]:
    """(has_literal, value, lineno) of the call's ``kind=`` keyword."""
    for kw in call.keywords:
        if kw.arg == "kind" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return True, kw.value.value, kw.value.lineno
    return False, "", 0


def analyze_obsjournal(modules: Sequence[ModuleSource]) -> List[Finding]:
    """Run OBS003 over the analyzed set. Cross-file: the vocabulary
    resolves through the canonical-table machinery (a fixture package
    defining JOURNAL_KINDS in its own contracts.py stays
    self-contained), and the stale-row direction needs every emission
    site before it can call a row unreferenced."""
    canon = _Canon(modules)
    kinds = canon.get("JOURNAL_KINDS") or ()
    known = frozenset(kinds)
    findings: List[Finding] = []
    emitted: Set[str] = set()
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if _callee_name(node) not in _EMIT_NAMES:
                continue
            has, value, line = _kind_literal(node)
            if not has:
                continue
            emitted.add(value)
            if known and value not in known:
                findings.append(mod.finding(
                    "OBS003", SEV_ERROR, line,
                    f"journal kind {value!r} is not in "
                    "contracts.JOURNAL_KINDS — EventJournal.emit "
                    "raises on it at runtime, inside a lifecycle "
                    "callback; fix the literal or add the row to the "
                    "canonical vocabulary",
                ))
    # reverse direction: vocabulary rows no emitter references rot —
    # only when the table is defined inside the analyzed set (same
    # containment rule the OPT001 stale-row check applies)
    if known and "JOURNAL_KINDS" in canon.sources:
        src_mod, src_line = canon.sources["JOURNAL_KINDS"]
        for kind in kinds:
            if kind not in emitted:
                findings.append(src_mod.finding(
                    "OBS003", SEV_WARNING, src_line,
                    f"JOURNAL_KINDS row {kind!r} has no emission site "
                    "(no kind= literal anywhere in the package) — "
                    "stale vocabulary row consumers will wait on "
                    "forever; remove it or wire the emitter",
                ))
    return findings
