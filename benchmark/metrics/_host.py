"""Helpers for the readers of the program's host-side spans and
counters added beside ``_lib``: the collector's pauses, the pipeline's
glue between phases, and the proxy's HTTP traces. Each returns None
where the program has no such counter or span."""

from __future__ import annotations

from typing import Iterable, List, Optional

from benchmark.metrics._lib import counter, self_times

GC_PAUSE = "cilium_tpu_gc_pause_seconds_total"


def gc_pause_pct(r) -> Optional[float]:
    """Collector pause seconds over the window ÷ the window, in %."""
    if GC_PAUSE not in r.counters:
        return None
    window = r.trace.window_s if r.trace is not None else r.window.seconds
    return 100.0 * counter(r, GC_PAUSE) / window if window > 0 else None


def glue_ms(traces: Iterable[dict]) -> Optional[float]:
    """Mean per batch of the wall time of the enqueue and complete
    halves (``enqueue_ns``, ``complete_ns`` notes) less the self time
    of the phases, in ms."""
    total, n = 0, 0
    for t in traces:
        notes = t.get("notes", {})
        if "enqueue_ns" not in notes or "complete_ns" not in notes:
            continue
        total += notes["enqueue_ns"] + notes["complete_ns"]
        total -= sum(ns for _, ns in self_times(t))
        n += 1
    return total / n / 1e6 if n else None


def proxy_traces(r) -> List[dict]:
    """PhaseTracing traces of the proxy's HTTP checks."""
    return [t for t in r.traces if t["kind"] == "proxy-http"]
