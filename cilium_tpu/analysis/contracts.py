"""Family C: stable-API contract rules (policyd-contracts).

These rules machine-check the ROADMAP's standing contracts against the
canonical tables in ``cilium_tpu/contracts.py``. Unlike Families A/B
(per-module pattern rules), every rule here is cross-file by nature:
an option registered in ``option.py`` is judged by what ``daemon.py``
and ``tests/`` do with it.

Rules
-----
OPT001  option discipline (the L7DeviceBatch-class bug): every option
        in ``OPTION_SPECS`` must have an ``OPTION_BOOT_FIELDS`` entry
        (a DaemonConfig boot field, or an annotated None exemption);
        a declared boot field must exist on DaemonConfig and be
        consulted by the daemon; a runtime-mutable option must have a
        consumption site (an ``_on_option_change`` branch or a literal
        ``options.get``/``_opt`` read) — otherwise toggling it changes
        nothing; a non-mutable option must at least be seeded or read;
        a datapath-gated option (non-None boot field) must be named by
        at least one tripwire test under ``tests/``; and hot modules
        must never read options through ``options.get(...)`` per batch
        (the hub pushes option values into one pipeline attribute —
        that attribute is the only hot-path gate). Error.
OPT002  option-gated mutation: state mutated ONLY inside an
        ``if self.<gate>:`` ON branch but read by a method that never
        consults the gate — the OFF path observes ON-path state, the
        exact shape that breaks the OFF-path bit-identical contract
        (jit cache keys, parity tests). Hot modules only. Warning.
API001  stable-literal drift: int-valued ``REASON_*``/``ATTR_*``
        constants, ``.phase("...")`` literals, and ``BUCKET_LADDER``
        definitions anywhere in the package must match the canonical
        tables — these names and numbers are read by the benchmark's
        span readers and stored in flow logs, so drift is an
        incompatible wire/schema change. Error.

Canonical tables resolve from the analyzed set first (a module
literally defining ``WIRE_REASONS``/``OPTION_BOOT_FIELDS``/... wins,
which keeps fixture packages self-contained) and fall back to
importing ``cilium_tpu.contracts``.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import (
    SEV_ERROR,
    SEV_WARNING,
    Finding,
    ModuleSource,
    attr_chain,
    walk_skipping,
)

_REASON_RE = re.compile(r"^REASON_[A-Z0-9_]+$")
_ATTR_RE = re.compile(r"^ATTR_[A-Z0-9_]+$")

_CANON_NAMES = (
    "TRACE_PHASES",
    "WIRE_REASONS",
    "ATTR_CODES",
    "BUCKET_LADDER",
    "OPTION_BOOT_FIELDS",
    "METRIC_BOUNDED_LABEL_KEYS",
    "JOURNAL_KINDS",
)


def _const_assign(node: ast.stmt) -> Optional[Tuple[str, ast.AST]]:
    """(name, value expr) for ``NAME = ...`` / ``NAME: T = ...``."""
    if (
        isinstance(node, ast.Assign)
        and len(node.targets) == 1
        and isinstance(node.targets[0], ast.Name)
    ):
        return (node.targets[0].id, node.value)
    if (
        isinstance(node, ast.AnnAssign)
        and isinstance(node.target, ast.Name)
        and node.value is not None
    ):
        return (node.target.id, node.value)
    return None


class _Canon:
    """Canonical tables: extracted from the analyzed set when a module
    defines them as literals, imported from cilium_tpu.contracts
    otherwise."""

    def __init__(self, modules: Sequence[ModuleSource]) -> None:
        self.tables: Dict[str, object] = {}
        # name -> (module, line) of the extracted definition
        self.sources: Dict[str, Tuple[ModuleSource, int]] = {}
        for mod in modules:
            # only a module NAMED contracts.py may define canon —
            # anything else redefining these names is drift for API001
            # to flag, not a new source of truth
            if os.path.basename(mod.path) != "contracts.py":
                continue
            for node in mod.tree.body:
                hit = _const_assign(node)
                if hit is None or hit[0] not in _CANON_NAMES:
                    continue
                name, value = hit
                if name in self.tables:
                    continue
                try:
                    self.tables[name] = ast.literal_eval(value)
                except (ValueError, TypeError, SyntaxError, MemoryError):
                    continue
                self.sources[name] = (mod, node.lineno)

    def get(self, name: str):
        if name in self.tables:
            return self.tables[name]
        try:
            from .. import contracts as _c
        except ImportError:  # analysis used outside the package tree
            return None
        return getattr(_c, name, None)


# ---------------------------------------------------------------- API001


def _check_api001(
    modules: Sequence[ModuleSource],
    canon: _Canon,
    findings: List[Finding],
) -> None:
    reasons = dict(canon.get("WIRE_REASONS") or {})
    attr_codes = dict(canon.get("ATTR_CODES") or {})
    phases = set(canon.get("TRACE_PHASES") or ())
    ladder = tuple(canon.get("BUCKET_LADDER") or ())
    for mod in modules:
        for node in ast.walk(mod.tree):
            hit = _const_assign(node) if isinstance(node, ast.stmt) else None
            if hit is not None:
                name, value = hit
                if isinstance(value, ast.Constant) and isinstance(
                    value.value, int
                ) and not isinstance(value.value, bool):
                    for regex, table, what in (
                        (_REASON_RE, reasons, "drop-reason"),
                        (_ATTR_RE, attr_codes, "attribution"),
                    ):
                        if not regex.match(name) or not table:
                            continue
                        if name not in table:
                            findings.append(mod.finding(
                                "API001", SEV_ERROR, node.lineno,
                                f"unknown {what} constant {name} = "
                                f"{value.value} — not in the canonical "
                                "taxonomy (cilium_tpu/contracts.py); "
                                "extend the table first, codes there "
                                "are the single source of truth",
                            ))
                        elif table[name] != value.value:
                            findings.append(mod.finding(
                                "API001", SEV_ERROR, node.lineno,
                                f"{what} constant {name} = {value.value} "
                                f"drifts from the canonical value "
                                f"{table[name]} — these codes are "
                                "STABLE wire/API numbers (stored flow "
                                "logs and monitor consumers key on "
                                "them)",
                            ))
                if name == "BUCKET_LADDER" and ladder:
                    try:
                        got = tuple(ast.literal_eval(value))
                    except (ValueError, TypeError, SyntaxError):
                        got = None
                    if got is not None and got != ladder:
                        findings.append(mod.finding(
                            "API001", SEV_ERROR, node.lineno,
                            f"BUCKET_LADDER {got} drifts from the "
                            f"canonical ladder {ladder} — the rungs are "
                            "a compile-count contract (jit program "
                            "budget, benchmark compile_s); import it "
                            "from "
                            "cilium_tpu.contracts instead of redefining",
                        ))
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "phase"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and phases
                and node.args[0].value not in phases
            ):
                findings.append(mod.finding(
                    "API001", SEV_ERROR, node.lineno,
                    f"trace phase literal {node.args[0].value!r} is not "
                    "in the canonical TRACE_PHASES vocabulary — phase "
                    "names are STABLE (benchmark/scopes.py and the "
                    "policyd.<kind>.<phase> annotations key on them); "
                    "add it to cilium_tpu/contracts.py deliberately or "
                    "use an existing phase",
                ))


# ---------------------------------------------------------------- OPT001


def _extract_option_specs(mod: ModuleSource) -> Dict[str, int]:
    """Option name -> registration line, from an ``OPTION_SPECS``
    assignment built of ``OptionSpec("Name", ...)`` calls."""
    for node in mod.tree.body:
        hit = _const_assign(node)
        if hit is None or hit[0] != "OPTION_SPECS":
            continue
        out: Dict[str, int] = {}
        for n in ast.walk(node):
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Name)
                and n.func.id == "OptionSpec"
                and n.args
                and isinstance(n.args[0], ast.Constant)
                and isinstance(n.args[0].value, str)
            ):
                out[n.args[0].value] = n.lineno
        return out
    return {}


def _daemonconfig_fields(mod: ModuleSource) -> Optional[Set[str]]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef) and node.name == "DaemonConfig":
            fields: Set[str] = set()
            for item in node.body:
                if isinstance(item, ast.AnnAssign) and isinstance(
                    item.target, ast.Name
                ):
                    fields.add(item.target.id)
                elif isinstance(item, ast.Assign):
                    for t in item.targets:
                        if isinstance(t, ast.Name):
                            fields.add(t.id)
            return fields
    return None


class _DaemonView:
    """What the daemon module does with options, extracted once."""

    def __init__(self, mod: ModuleSource) -> None:
        self.mod = mod
        self.handler_names: Set[str] = set()
        self.mutable: Set[str] = set()
        self.attr_refs: Set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute):
                self.attr_refs.add(node.attr)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name == "_on_option_change":
                    for n in ast.walk(node):
                        if isinstance(n, ast.Compare):
                            for comp in n.comparators:
                                if isinstance(
                                    comp, ast.Constant
                                ) and isinstance(comp.value, str):
                                    self.handler_names.add(comp.value)
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if (
                        isinstance(t, ast.Name)
                        and t.id == "_MUTABLE_OPTIONS"
                        or isinstance(t, ast.Attribute)
                        and t.attr == "_MUTABLE_OPTIONS"
                    ):
                        self.mutable |= _frozenset_literal(node.value)


def _frozenset_literal(expr: ast.AST) -> Set[str]:
    if (
        isinstance(expr, ast.Call)
        and isinstance(expr.func, ast.Name)
        and expr.func.id == "frozenset"
        and expr.args
    ):
        expr = expr.args[0]
    try:
        value = ast.literal_eval(expr)
    except (ValueError, TypeError, SyntaxError):
        return set()
    if isinstance(value, (set, frozenset, list, tuple)):
        return {v for v in value if isinstance(v, str)}
    return set()


def _collect_option_io(
    modules: Sequence[ModuleSource],
) -> Tuple[Set[str], Set[str]]:
    """(seeded names, read names) from literal ``options.set("X", ..)``
    seeds and ``options.get("X")`` / ``self._opt(ep, "X", ..)`` reads
    anywhere in the analyzed set."""
    seeded: Set[str] = set()
    reads: Set[str] = set()
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
            ):
                continue
            chain = attr_chain(node.func) or []
            if node.func.attr in ("set", "get") and any(
                "options" in part for part in chain[:-1]
            ):
                if node.args and isinstance(
                    node.args[0], ast.Constant
                ) and isinstance(node.args[0].value, str):
                    (seeded if node.func.attr == "set" else reads).add(
                        node.args[0].value
                    )
            elif node.func.attr == "_opt" and len(node.args) >= 2:
                arg = node.args[1]
                if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str
                ):
                    reads.add(arg.value)
    return seeded, reads


def _tests_dir_text(mod: ModuleSource) -> Optional[str]:
    """Concatenated source of every .py under the sibling ``tests/``
    of the option module's top-level package, or None when there is no
    such directory (single-file analyses stay self-contained)."""
    root = os.path.dirname(mod.path)
    while os.path.isfile(os.path.join(root, "__init__.py")):
        parent = os.path.dirname(root)
        if parent == root:
            break
        root = parent
    tests_dir = os.path.join(root, "tests")
    if not os.path.isdir(tests_dir):
        return None
    chunks: List[str] = []
    for dirpath, dirnames, files in os.walk(tests_dir):
        dirnames[:] = sorted(
            d for d in dirnames
            if d != "__pycache__" and not d.startswith(".")
        )
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            try:
                with open(
                    os.path.join(dirpath, name), "r", encoding="utf-8"
                ) as f:
                    chunks.append(f.read())
            except OSError:
                continue
    return "\n".join(chunks)


def _check_opt001(
    modules: Sequence[ModuleSource],
    canon: _Canon,
    findings: List[Finding],
) -> None:
    option_mods = [
        (mod, specs)
        for mod in modules
        for specs in (_extract_option_specs(mod),)
        if specs
    ]
    # hot modules must never pay a per-batch option-map read: the hub
    # pushes option values into one pipeline attribute at change time
    for mod in modules:
        if not mod.is_hot():
            continue
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
            ):
                chain = attr_chain(node.func) or []
                if any("options" in part for part in chain[:-1]):
                    findings.append(mod.finding(
                        "OPT001", SEV_ERROR, node.lineno,
                        "option-map read in a hot module — options are "
                        "read through the hub-pushed pipeline attribute "
                        "(one attribute read per batch), never through "
                        "options.get() on the verdict path",
                    ))
    if not option_mods:
        return
    boot_fields: Dict[str, Optional[str]] = dict(
        canon.get("OPTION_BOOT_FIELDS") or {}
    )
    seeded, reads = _collect_option_io(modules)
    daemons = [
        _DaemonView(mod)
        for mod in modules
        if any(
            isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name == "_on_option_change"
            for n in ast.walk(mod.tree)
        )
    ]
    for mod, specs in option_mods:
        top = mod.relpath.split("/")[0]
        daemon = next(
            (d for d in daemons if d.mod.relpath.split("/")[0] == top),
            None,
        )
        cfg_fields = _daemonconfig_fields(mod)
        tests_text = _tests_dir_text(mod)
        for name, line in sorted(specs.items(), key=lambda kv: kv[1]):
            if boot_fields and name not in boot_fields:
                findings.append(mod.finding(
                    "OPT001", SEV_ERROR, line,
                    f"option {name} has no OPTION_BOOT_FIELDS entry in "
                    "the canonical table (cilium_tpu/contracts.py) — "
                    "declare its DaemonConfig boot field, or record "
                    "None with the reason it is boot-exempt",
                ))
                continue
            field = boot_fields.get(name)
            if field is not None:
                if cfg_fields is not None and field not in cfg_fields:
                    findings.append(mod.finding(
                        "OPT001", SEV_ERROR, line,
                        f"option {name} declares boot field '{field}' "
                        "but DaemonConfig has no such field — the "
                        "option cannot be enabled at boot",
                    ))
                elif daemon is not None and field not in daemon.attr_refs:
                    findings.append(mod.finding(
                        "OPT001", SEV_ERROR, line,
                        f"boot field '{field}' of option {name} is "
                        "never consulted by the daemon — the configured "
                        "boot value is dead; seed the option map from "
                        "it in Daemon.__init__",
                    ))
                if tests_text is not None and (
                    f'"{name}"' not in tests_text
                    and f"'{name}'" not in tests_text
                ):
                    findings.append(mod.finding(
                        "OPT001", SEV_ERROR, line,
                        f"datapath-gated option {name} has no tripwire "
                        "test under tests/ naming it — the OFF-path "
                        "bit-identical contract (ROADMAP) is unenforced "
                        "for this option",
                    ))
            if daemon is not None:
                if name in daemon.mutable:
                    if (
                        name not in daemon.handler_names
                        and name not in reads
                    ):
                        findings.append(mod.finding(
                            "OPT001", SEV_ERROR, line,
                            f"runtime-mutable option {name} has no "
                            "consumption site: no _on_option_change "
                            "branch and no literal option read — "
                            "toggling it changes nothing (the "
                            "L7DeviceBatch-class bug); wire a handler "
                            "or drop it from _MUTABLE_OPTIONS",
                        ))
                elif name not in seeded and name not in reads:
                    findings.append(mod.finding(
                        "OPT001", SEV_ERROR, line,
                        f"option {name} is not runtime-mutable, never "
                        "seeded at boot, and never read — it is "
                        "registered surface that cannot do anything; "
                        "seed it, read it, or make it mutable with a "
                        "handler",
                    ))
        # reverse direction: table entries with no registration rot
        if boot_fields and "OPTION_BOOT_FIELDS" in canon.sources:
            src_mod, src_line = canon.sources["OPTION_BOOT_FIELDS"]
            if src_mod.relpath.split("/")[0] == top:
                for name in sorted(boot_fields):
                    if name not in specs:
                        findings.append(src_mod.finding(
                            "OPT001", SEV_ERROR, src_line,
                            f"OPTION_BOOT_FIELDS entry '{name}' has no "
                            "OPTION_SPECS registration — stale table "
                            "row; remove it or register the option",
                        ))


# ---------------------------------------------------------------- OPT002


class _ClassOptGates:
    """Per-class OPT002 state: gate attrs, assignment sites with their
    gate context, reads and gate mentions per method."""

    def __init__(self, cls: ast.ClassDef) -> None:
        self.cls = cls
        self.methods: Dict[str, ast.AST] = {
            item.name: item
            for item in cls.body
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        self.gates: Set[str] = self._gate_attrs()
        # attr -> [(method, active gates, line)]
        self.assigns: Dict[str, List[Tuple[str, frozenset, int]]] = {}
        # method -> self attrs read / mentioned at all
        self.reads: Dict[str, Set[str]] = {}
        self.mentions: Dict[str, Set[str]] = {}
        if not self.gates:
            return
        for mname, mnode in self.methods.items():
            self.reads[mname] = set()
            self.mentions[mname] = set()
            for n in walk_skipping(
                mnode, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                if (
                    isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self"
                ):
                    self.mentions[mname].add(n.attr)
                    if isinstance(n.ctx, ast.Load):
                        self.reads[mname].add(n.attr)
            for stmt in mnode.body:
                self._walk(mname, stmt, frozenset())

    def _gate_attrs(self) -> Set[str]:
        gates: Set[str] = set()
        for mname, mnode in self.methods.items():
            if not mname.startswith("set_"):
                continue
            args = mnode.args
            params = {
                a.arg
                for a in list(args.posonlyargs) + list(args.args)
                + list(args.kwonlyargs)
                if a.arg not in ("self", "cls")
            }
            for n in ast.walk(mnode):
                if not isinstance(n, ast.Assign):
                    continue
                value = n.value
                if isinstance(value, ast.Call) and isinstance(
                    value.func, ast.Name
                ) and value.func.id == "bool" and value.args:
                    value = value.args[0]
                if not (
                    isinstance(value, ast.Name) and value.id in params
                ):
                    continue
                for t in n.targets:
                    if (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        gates.add(t.attr)
        return gates

    def _gate_of_test(self, test: ast.AST) -> Optional[str]:
        if (
            isinstance(test, ast.Attribute)
            and isinstance(test.value, ast.Name)
            and test.value.id == "self"
            and test.attr in self.gates
        ):
            return test.attr
        return None

    def _record(
        self, method: str, target: ast.AST, gates: frozenset, line: int
    ) -> None:
        node = target
        while isinstance(node, ast.Subscript):
            node = node.value
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        ):
            self.assigns.setdefault(node.attr, []).append(
                (method, gates, line)
            )

    def _walk(
        self, method: str, stmt: ast.stmt, gates: frozenset
    ) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if isinstance(stmt, ast.If):
            g = self._gate_of_test(stmt.test)
            body_gates = gates | {g} if g else gates
            for s in stmt.body:
                self._walk(method, s, body_gates)
            for s in stmt.orelse:
                self._walk(method, s, gates)
            return
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                self._record(method, t, gates, stmt.lineno)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            self._record(method, stmt.target, gates, stmt.lineno)
        for attr in ("body", "orelse", "finalbody"):
            for s in getattr(stmt, attr, []) or []:
                self._walk(method, s, gates)
        for h in getattr(stmt, "handlers", []) or []:
            for s in h.body:
                self._walk(method, s, gates)


def _check_opt002(
    modules: Sequence[ModuleSource], findings: List[Finding]
) -> None:
    for mod in modules:
        if not mod.is_hot():
            continue
        for cls in ast.walk(mod.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            view = _ClassOptGates(cls)
            if not view.gates:
                continue
            for attr, sites in sorted(view.assigns.items()):
                if attr in view.gates:
                    continue
                live = [s for s in sites if s[0] != "__init__"
                        and not s[0].startswith("set_")]
                if not live:
                    continue
                gate_sets = [s[1] for s in live]
                common = frozenset.intersection(*gate_sets)
                if not common:
                    continue  # some mutation happens outside any gate
                gate = sorted(common)[0]
                off_readers = sorted(
                    m for m, attrs in view.reads.items()
                    if attr in attrs
                    and gate not in view.mentions.get(m, ())
                    and m != "__init__"
                    and not m.startswith("set_")
                )
                if not off_readers:
                    continue
                line = min(s[2] for s in live)
                findings.append(mod.finding(
                    "OPT002", SEV_WARNING, line,
                    f"{cls.name}.{attr} is mutated only while option "
                    f"gate '{gate}' is ON, but {off_readers[0]}() reads "
                    "it without consulting the gate — the OFF path "
                    "observes ON-path state (breaks the OFF-path "
                    "bit-identical contract; a jit cache key built "
                    "from it recompiles on toggle); gate the reader or "
                    "reset the state when the option turns off",
                ))


# ---------------------------------------------------------------- entry


def analyze_contracts(
    modules: Sequence[ModuleSource], graph=None
) -> List[Finding]:
    """Run Family C over the whole analyzed set at once (every rule
    here is cross-file; per-module iteration happens inside)."""
    findings: List[Finding] = []
    canon = _Canon(modules)
    _check_api001(modules, canon, findings)
    _check_opt001(modules, canon, findings)
    _check_opt002(modules, findings)
    return findings
