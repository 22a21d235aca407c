"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, the traced window, device
time by operation and by program, and the device's idle gaps named by
the benchmark's host span open at the time.

Device planes are ``/device:TPU:<n>``. Busy time is the union of the
intervals of the events on a device's ``XLA Ops`` line (its ``XLA
Modules`` line where it has no ops line), averaged over the devices
used. The window is the benchmark's ``bench.window`` host span. Host
spans are the ``bench.*`` ``TraceAnnotation``s the harness opens around
its own calls (generator, submit, result, check_http)."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                         # mean over devices with events
    devices: int
    ops_s: Dict[str, float]               # "program/op opcode shape" -> seconds
    modules_s: Dict[str, float]           # program (XLA module) name -> seconds
    gaps_by_span: Dict[str, float]        # host span -> idle seconds on device 0
    longest_gaps: List[Tuple[str, float]]  # (host span, seconds), longest first

    def breakdown(self, k: int = 10) -> dict:
        top = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.longest_gaps[:k]]}


def short_op(text: str) -> str:
    """``%reshape.1 = s32[11167488]{...} reshape(...)`` -> ``reshape.1
    reshape s32[11167488]``: an HLO op event's name, its opcode and its
    (first) result shape."""
    if " = " not in text:
        return text[:120]
    lhs, rhs = text.split(" = ", 1)
    op = re.search(r" ([a-z][a-z0-9_-]*)\(", rhs)
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rhs)
    return " ".join(x for x in (lhs.lstrip("%"), op and op.group(1),
                                shape and shape.group(0)) if x)[:120]


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def find_xplane(logdir: str) -> Optional[str]:
    got = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb*"), recursive=True))
    return got[-1] if got else None


def reduce_xplane(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` (or a gzipped one, as the recorded
    fixture is kept)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host_spans: List[Tuple[int, int, str]] = []
    dev_ops: Dict[str, List[Tuple[int, int, str]]] = {}
    dev_mods: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    dst = dev_ops if line.name == "XLA Ops" else dev_mods
                    evs = dst.setdefault(plane.name, [])
                    for ev in line.events:
                        s = int(ev.start_ns)
                        evs.append((s, s + int(ev.duration_ns), ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        host_spans.append((s, s + int(ev.duration_ns), ev.name))
    return summarize(host_spans, dev_ops, dev_mods)


def summarize(host_spans, dev_ops, dev_mods) -> TraceSummary:
    """The reduction proper, on plain (start_ns, end_ns, name) events:
    kept apart from the file reader so the tests can feed it events."""
    win = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW} span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    devices = sorted(set(dev_ops) | set(dev_mods))
    busy, ops_s, mods_s = [], {}, {}
    first_busy: List[Tuple[int, int]] = []
    for i, dev in enumerate(devices):
        evs = dev_ops.get(dev) or dev_mods.get(dev, [])
        u = _union(_clip([(s, e) for s, e, _ in evs], lo, hi))
        if not u:
            continue
        busy.append(sum(e - s for s, e in u))
        if not first_busy:
            first_busy = u
        mods = sorted(dev_mods.get(dev, []))
        mstarts = [m[0] for m in mods]
        # a window holds millions of op events but few distinct names:
        # each (program, op) name is shortened once
        keys: Dict[Tuple[str, str], str] = {}
        for s, e, n in dev_ops.get(dev, []):
            c = min(e, hi) - max(s, lo)
            if c > 0:
                k = bisect.bisect_right(mstarts, s) - 1
                prog = mods[k][2] if k >= 0 and mods[k][1] >= e else ""
                key = keys.get((prog, n))
                if key is None:
                    p = prog.split("(")[0]
                    key = keys[(prog, n)] = f"{p}/{short_op(n)}" if p else short_op(n)
                ops_s[key] = ops_s.get(key, 0.0) + c / 1e9
        for s, e, n in dev_mods.get(dev, []):
            c = min(e, hi) - max(s, lo)
            if c > 0:
                mods_s[n] = mods_s.get(n, 0.0) + c / 1e9
    gaps, prev = [], lo
    for s, e in first_busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if first_busy and hi > prev:
        gaps.append((prev, hi))
    # the harness's inner spans follow one another on one thread: the
    # span open at a gap's midpoint is the last one started before it
    inner = sorted((s, e, n) for s, e, n in host_spans if n != WINDOW)
    starts = [s for s, _, _ in inner]
    by_span: Dict[str, float] = {}
    named = []
    for s, e in gaps:
        mid = (s + e) // 2
        k = bisect.bisect_right(starts, mid) - 1
        name = inner[k][2] if k >= 0 and inner[k][1] > mid else "bench.idle"
        by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9
        named.append((name, (e - s) / 1e9))
    named.sort(key=lambda x: -x[1])
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=(sum(busy) / len(busy) / 1e9) if busy else 0.0,
        devices=len(busy), ops_s=ops_s, modules_s=mods_s,
        gaps_by_span=by_span, longest_gaps=named)
