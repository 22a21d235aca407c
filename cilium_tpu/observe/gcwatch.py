# policyd: hot
"""Python garbage-collector pauses, as metrics and as profiler spans.

A full collection stops every thread of the process for hundreds of
milliseconds, and the verdict path has no span of its own to show it:
the pause lands inside whatever phase was running. ``install`` adds
one ``gc.callbacks`` hook that feeds, always on,

- ``cilium_tpu_gc_pause_seconds_total{generation}`` and
- ``cilium_tpu_gc_collections_total{generation}``,

and, while the given tracer is active, opens a ``policyd.gc`` profiler
annotation at the collection's start and closes it at its end, so a
pause shows on the profiler's clock across the idle gap it caused.

Cost: two ``perf_counter`` reads and two list additions per
collection (a collection itself walks at least 700 new objects).

The hook takes no lock. It runs at whatever allocation starts a
collection, which may be inside a lock that thread already holds (a
counter's ``series()`` copy, say), so it adds into the two families'
plain per-generation slots instead (``metrics.SlotCounter``). That is
safe because collections never overlap.
"""

from __future__ import annotations

import gc
import time

from .. import metrics as _metrics

_PAUSE = _metrics.gc_pause_seconds_total.slots
_COUNT = _metrics.gc_collections_total.slots

_tracer = None     # the daemon's Tracer, or None
_t0 = 0.0
_ann = None        # the open policyd.gc annotation, or None


def _on_gc(phase: str, info: dict) -> None:
    # collections never overlap (the collector holds the GIL and runs
    # one at a time), so one start/stop pair of globals suffices
    global _t0, _ann
    if phase == "start":
        tr = _tracer
        if tr is not None and tr.active:
            _ann = tr.annotate("policyd.gc")
            _ann.__enter__()
        _t0 = time.perf_counter()
        return
    if not _t0:
        return     # installed mid-collection: no start to time from
    dt = time.perf_counter() - _t0
    _t0 = 0.0
    gen = info["generation"]
    _PAUSE[gen] += dt
    _COUNT[gen] += 1
    if _ann is not None:
        ann, _ann = _ann, None
        ann.__exit__(None, None, None)


def install(tracer=None) -> None:
    """Register the hook once per process; ``tracer`` (the daemon's)
    decides when collections are also profiler spans."""
    global _tracer
    _tracer = tracer
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def release(tracer) -> None:
    """Stop mirroring collections for ``tracer`` (its daemon is shutting
    down). The counters stay on."""
    global _tracer
    if _tracer is tracer:
        _tracer = None
