"""The program's own names in a profiler trace: device-idle gaps named
by the program span open at the time, and device time by named scope.

    python3 -m benchmark.scopes <trace.xplane.pb[.gz]>

prints one JSON object:

- ``idle_gaps_by_phase``: the longest device-idle gaps of the window,
  each named by the innermost ``policyd.*`` span open at its midpoint
  on the thread that opened ``bench.window``, else by the innermost
  ``bench.*`` span there, else ``bench.idle``; ``gaps_by_phase`` sums
  every gap by that name;
- ``device_scopes``: device self time (an op's time less the ops
  nested in it, so a ``while`` op does not count its body twice) by
  ``<program>/<named scope>``: ``-`` for an op under no named scope;
  an op with no ``tf_op`` (the trace gives none for ``while`` ops, nor
  for ops the compiler inserted, such as a relayout copy) takes the
  scope the ops inside it share, and is ``(compiler)`` when nothing
  inside it has a ``tf_op`` either;
- ``top_ops``: the longest ops by total time, named as
  ``benchmark.tracefile`` names them, each with its scope.

``jax.profiler.ProfileData`` does not expose an op's ``tf_op`` path (the
``jax.named_scope`` chain), so the XSpace is read here with a minimal
descriptor of its protobuf schema (``tsl/profiler/protobuf/xplane.proto``:
field numbers as there, unread fields left out)."""

from __future__ import annotations

import bisect
import gzip
import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.tracefile import WINDOW, _clip, _union, short_op

Span = Tuple[int, int, str]         # (start_ns, end_ns, name)

# path components of a tf_op that are program structure, not scopes
_STRUCTURE = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
              "scan", "branch"}


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchmark_xplane", syntax="proto3")
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED

    def msg(name, fields, parent=None):
        m = (parent.nested_type if parent is not None else fd.message_type).add(name=name)
        for fname, num, typ, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ, label=label)
            if tname:
                f.type_name = ".benchmark_xplane." + tname
        return m

    msg("XStat", [("metadata_id", 1, F.TYPE_INT64, one, None),
                  ("str_value", 5, F.TYPE_STRING, one, None),
                  ("ref_value", 7, F.TYPE_UINT64, one, None)])
    msg("XEvent", [("metadata_id", 1, F.TYPE_INT64, one, None),
                   ("offset_ps", 2, F.TYPE_INT64, one, None),
                   ("duration_ps", 3, F.TYPE_INT64, one, None)])
    msg("XLine", [("name", 2, F.TYPE_STRING, one, None),
                  ("timestamp_ns", 3, F.TYPE_INT64, one, None),
                  ("events", 4, F.TYPE_MESSAGE, many, "XEvent")])
    msg("XEventMetadata", [("id", 1, F.TYPE_INT64, one, None),
                           ("name", 2, F.TYPE_STRING, one, None),
                           ("stats", 5, F.TYPE_MESSAGE, many, "XStat")])
    msg("XStatMetadata", [("id", 1, F.TYPE_INT64, one, None),
                          ("name", 2, F.TYPE_STRING, one, None)])
    plane = msg("XPlane", [
        ("name", 2, F.TYPE_STRING, one, None),
        ("lines", 3, F.TYPE_MESSAGE, many, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, many, "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = msg(entry, [("key", 1, F.TYPE_INT64, one, None),
                        ("value", 2, F.TYPE_MESSAGE, one, value)], parent=plane)
        e.options.map_entry = True
    msg("XSpace", [("planes", 1, F.TYPE_MESSAGE, many, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xplane.XSpace"))


def load_xspace(path: str):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        xs = _xspace_class()()
        xs.ParseFromString(f.read())
    return xs


def _events(line) -> List[Tuple[float, float, int]]:
    """(start_ns, end_ns, metadata id) of a line's events."""
    base = line.timestamp_ns
    out = []
    for e in line.events:
        s = base + e.offset_ps / 1000
        out.append((s, s + e.duration_ps / 1000, e.metadata_id))
    return out


def _tf_ops(plane) -> Dict[int, str]:
    stat = {k: v.name for k, v in plane.stat_metadata.items()}
    tf = [k for k, n in stat.items() if n == "tf_op"]
    out = {}
    for k, md in plane.event_metadata.items():
        for s in md.stats:
            if tf and s.metadata_id == tf[0]:
                out[k] = s.str_value or stat.get(s.ref_value, "")
    return out


def scope_of(tf_op: str) -> str:
    """``jit(f)/lpm_v4/jit(g)/table_flatten/reshape:`` ->
    ``lpm_v4/table_flatten``: the path's named scopes, without program
    structure and the op itself; ``-`` for none."""
    parts = tf_op.rstrip(":").split("/")[:-1]
    kept = [p for p in parts if p and "(" not in p and p not in _STRUCTURE]
    return "/".join(kept) or "-"


def nest(events: Sequence[Tuple[float, float, object]]) -> List[Tuple[object, float, int]]:
    """(payload, self ns, parent index) of properly nested intervals, in
    start order: each one's length less the lengths of the intervals
    directly inside it; the parent is the innermost interval holding
    it (-1 for none)."""
    out: List[list] = []
    stack: List[int] = []
    for s, e, p in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and out[stack[-1]][3] <= s:
            stack.pop()
        parent = stack[-1] if stack else -1
        if stack:
            out[parent][1] -= e - s
        out.append([p, e - s, parent, e])
        stack.append(len(out) - 1)
    return [(p, max(0.0, d), parent) for p, d, parent, _ in out]


def resolve_scopes(own: Sequence[Optional[str]], parents: Sequence[int]) -> List[str]:
    """Each op's scope: its own, else (no ``tf_op``: a ``while`` op, or
    an op the compiler inserted) the scope the ops inside it share
    (``-`` when they share none), else ``(compiler)``. A loop whose
    body runs under ``dfa_walk`` is then ``dfa_walk``'s time too."""
    kids: Dict[int, List[int]] = {}
    for i, parent in enumerate(parents):
        kids.setdefault(parent, []).append(i)
    out = list(own)
    for i in reversed(range(len(out))):      # children before parents
        if out[i] is None:
            inner = [out[k].split("/") for k in kids.get(i, ())
                     if out[k] != "(compiler)"]
            common = []
            for parts in zip(*inner):
                if len(set(parts)) != 1:
                    break
                common.append(parts[0])
            out[i] = ("/".join(common) or "-") if inner else "(compiler)"
    return out


def innermost(spans: Sequence[Span], points: Sequence[int]) -> List[Optional[str]]:
    """For each point, the name of the innermost span containing it
    (spans nest, as spans on one thread do), else None."""
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    order = sorted(range(len(points)), key=lambda i: points[i])
    out: List[Optional[str]] = [None] * len(points)
    stack: List[Span] = []
    j = 0
    for i in order:
        t = points[i]
        while j < len(spans) and spans[j][0] <= t:
            while stack and stack[-1][1] <= spans[j][0]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out[i] = stack[-1][2] if stack else None
    return out


def name_gaps(host: Sequence[Span], busy: Sequence[Tuple[int, int]], lo: int,
              hi: int) -> List[Tuple[str, int]]:
    """(name, ns) of each device-idle gap in [lo, hi]: ``policyd.*``
    first, then ``bench.*``, then ``bench.idle``."""
    gaps, prev = [], lo
    for s, e in _union(_clip(busy, lo, hi)):
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    mids = [(s + e) // 2 for s, e in gaps]
    prog = innermost([x for x in host if x[2].startswith("policyd.")], mids)
    harness = innermost([x for x in host if x[2].startswith("bench.")
                         and x[2] != WINDOW], mids)
    return [(p or b or "bench.idle", e - s)
            for (s, e), p, b in zip(gaps, prog, harness)]


def reduce(xs, k: int = 10) -> dict:
    host: List[Span] = []
    window: Optional[Tuple[int, int]] = None
    for plane in xs.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {i: md.name for i, md in plane.event_metadata.items()}
        for line in plane.lines:
            evs = [(s, e, names.get(m, "")) for s, e, m in _events(line)]
            win = [(s, e) for s, e, n in evs if n == WINDOW]
            if win:
                window = (min(s for s, _ in win), max(e for _, e in win))
                host = [x for x in evs if x[2].startswith(("policyd.", "bench."))]
    if window is None:
        raise ValueError(f"trace has no {WINDOW} span")
    lo, hi = window
    dev = sorted((p for p in xs.planes if p.name.startswith("/device:")),
                 key=lambda p: p.name)
    scopes: Dict[str, float] = {}
    ops: Dict[str, list] = {}
    busy: List[Tuple[int, int]] = []
    for plane in dev:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        names = {i: md.name for i, md in plane.event_metadata.items()}
        tf = _tf_ops(plane)
        mods = sorted(_events(lines["XLA Modules"])) if "XLA Modules" in lines else []
        mstarts = [m[0] for m in mods]
        evs = [(max(s, lo), min(e, hi), m) for s, e, m in _events(lines["XLA Ops"])
               if e > lo and s < hi]
        if not busy:
            busy = [(s, e) for s, e, _ in evs]

        def program(s, e):
            i = bisect.bisect_right(mstarts, s) - 1
            ok = i >= 0 and mods[i][1] >= e
            return names.get(mods[i][2], "").split("(")[0] if ok else ""

        nested = nest([(s, e, (program(s, e), m, e - s)) for s, e, m in evs])
        own = [scope_of(tf[m]) if m in tf else None for (_, m, _), _, _ in nested]
        resolved = resolve_scopes(own, [parent for _, _, parent in nested])
        for ((prog, m, dur), ns, _), scope in zip(nested, resolved):
            key = f"{prog}/{scope}"
            scopes[key] = scopes.get(key, 0.0) + ns / 1e9
            n = short_op(names.get(m, ""))
            key = f"{prog}/{n}" if prog else n
            if key not in ops:
                ops[key] = [0.0, f"{prog}/{scope}"]
            ops[key][0] += dur / 1e9
    gaps = name_gaps(host, busy, lo, hi)
    by_phase: Dict[str, float] = {}
    for n, ns in gaps:
        by_phase[n] = by_phase.get(n, 0.0) + ns / 1e9
    longest = sorted(gaps, key=lambda g: -g[1])[:k]
    named = sum(ns for n, ns in longest if n.startswith("policyd."))
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_gaps_by_phase": [[n, ns / 1e9] for n, ns in longest],
        "policyd_share_of_longest_gaps": named / max(1, sum(ns for _, ns in longest)),
        "gaps_by_phase": dict(sorted(by_phase.items(), key=lambda kv: -kv[1])),
        "device_scopes": [[n, s] for n, s in
                          sorted(scopes.items(), key=lambda kv: -kv[1])[:2 * k]],
        "top_ops": [[n, s, sc] for n, (s, sc) in
                    sorted(ops.items(), key=lambda kv: -kv[1][0])[:k]],
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    print(json.dumps(reduce(load_xspace(args[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
