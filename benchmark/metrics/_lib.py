"""Helpers the per-layer readers share. A reader is
``benchmark/metrics/<metric name>.py`` with ``read(r) -> float | None``,
where ``r`` is ``benchmark.run.Readings``; ``None`` means it found
nothing to read, and the harness leaves the metric out."""

from __future__ import annotations

from typing import Iterable, List, Optional


def flow_traces(r) -> List[dict]:
    """PhaseTracing traces of the window's flow batches."""
    return [t for t in r.traces if t["kind"].startswith(("v4-", "v6-"))]


def l7_traces(r) -> List[dict]:
    return [t for t in r.traces if t["kind"] == "l7"]


def self_times(trace: dict) -> List[tuple]:
    """(phase, self ns): a phase's duration less the phases nested in it."""
    ph = sorted(trace["phases"], key=lambda p: (p[1], -p[2]))
    out = []
    for i, (name, start, dur) in enumerate(ph):
        end = start + dur
        inner = sum(d for n, s, d in ph[i + 1:] if s >= start and s + d <= end
                    and (n, s, d) != (name, start, dur))
        out.append((name, max(0, dur - inner)))
    return out


def mean_phase_ms(traces: Iterable[dict], phases: Optional[set] = None,
                  exclude: Optional[set] = None) -> Optional[float]:
    """Mean per batch of the self time of the named phases (all but
    ``exclude`` when ``phases`` is None), in ms; None with no batch."""
    traces = list(traces)
    if not traces:
        return None
    total = 0
    for t in traces:
        for name, ns in self_times(t):
            if (phases is None or name in phases) and name not in (exclude or ()):
                total += ns
    return total / len(traces) / 1e6


def idle_pct(r) -> Optional[float]:
    if r.trace is None or r.trace.window_s <= 0 or r.trace.devices == 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)


def counter(r, name: str, **labels) -> float:
    want = tuple(sorted(labels.items()))
    return sum(v for k, v in r.counters.get(name, {}).items()
               if all(item in k for item in want))
