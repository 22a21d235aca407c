# policyd: hot
"""Span tracer for the verdict path (policyd-trace).

The pipeline's phases (CT pre-pass, LPM, policymap lookup, device
dispatch, host sync) are invisible to /metrics alone — a batch's wall
time is one number with no attribution. This module adds the
attribution layer: monotonic-clock spans grouped into per-batch
traces, a thread-local span stack so helpers (``_dispatch``, the
device-CT path) attach to the enclosing batch without parameter
threading, and a bounded ring buffer of completed traces served by
``GET /traces`` and ``cilium-tpu traces``.

Cost model (the hub's ``active`` pattern, monitor/hub.py): the hot
path reads ONE attribute per batch — ``tracer.active`` — and takes the
no-op branch when tracing is off. The no-op batch/span singletons are
constructed once at import; a disabled batch allocates nothing and
times nothing. When enabled, each completed trace feeds the per-phase
latency histograms in metrics.py and (only while a monitor listener
is attached) publishes one TraceSummary event through the hub.

Phase names are a STABLE API: bench rounds compare waterfalls across
commits, so renaming a phase is a breaking change (observe/README.md).

While a tracer is active every span and batch half is also mirrored as
a profiler annotation named ``policyd.<kind>.<phase>`` (see
``annotation``), so a ``jax.profiler`` trace shows the program's own
spans on the same clock as the device ops. Outside a profiler session
an annotation is a no-op C++ object; with the tracer off none is built.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from .. import metrics as _metrics

# one span stack per thread, shared by every Tracer: a trace records the
# innermost trace open on its thread as its parent whichever tracer
# opened it, and ``current(kind)`` finds it from code that holds no
# tracer (the proxy's HTTP policy)
_TLS = threading.local()
_TRACE_IDS = itertools.count(1)
_Annotation = None

# kinds whose traces wrap a verdict batch that is traced on its own (a
# proxy HTTP batch wraps its l7 walk): their phases are observed, their
# wall time is not, or cilium_tpu_pipeline_batch_seconds (and the fleet
# SLO quantiles read from it) would count the inner batch twice
WRAPPER_KINDS = frozenset({"proxy-http"})


def annotation(name: str, **meta):
    """A ``jax.profiler.TraceAnnotation``: a span on the profiler's
    clock. JAX is imported on first use, so a process that never
    traces never loads it from here."""
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation

        _Annotation = TraceAnnotation
    return _Annotation(name, **meta)


def _stack() -> list:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def _innermost(attr: str, value):
    """The innermost open trace on this thread whose ``attr`` equals
    ``value``, or the no-op singleton."""
    stack = getattr(_TLS, "stack", None)
    if stack:
        for bt in reversed(stack):
            if getattr(bt, attr) == value:
                return bt
    return NOOP_BATCH


def _unstack(bt: "BatchTrace") -> None:
    # identity-based removal, not a top-of-stack pop: with depth>1
    # batches complete FIFO while newer traces sit above them (or were
    # already detach()ed), so ``bt`` may be anywhere or gone
    stack = getattr(_TLS, "stack", None)
    if stack is not None:
        try:
            stack.remove(bt)
        except ValueError:
            pass


def current(kind: str):
    """The innermost open trace of ``kind`` on this thread, of any
    tracer, or the no-op singleton."""
    return _innermost("kind", kind)


class _NoopSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _NoopBatch:
    """Shared do-nothing batch: every method is a constant-time no-op
    so instrumented code never branches on enabled-ness beyond the one
    ``tracer.active`` read that selected this singleton."""

    __slots__ = ()

    def phase(self, name: str):
        return _NOOP_SPAN

    def half(self, name: str):
        return _NOOP_SPAN

    def mark(self, **notes) -> None:
        pass

    def end(self, hub=None):
        return None


_NOOP_SPAN = _NoopSpan()
NOOP_BATCH = _NoopBatch()


class _Span:
    """One timed phase inside a batch trace. Records
    (name, start-offset-ns, duration-ns) into the owning trace on
    exit — offsets make the waterfall renderable without re-deriving
    overlap from wall clocks. The profiler annotation opens before the
    clock starts and closes after it stops, so its own cost stays out
    of the phase's duration."""

    __slots__ = ("_trace", "name", "_t0", "_ann")

    def __init__(self, trace: "BatchTrace", name: str) -> None:
        self._trace = trace
        self.name = name
        self._t0 = 0
        self._ann = None

    def __enter__(self):
        t = self._trace
        self._ann = annotation(
            f"policyd.{t.kind}.{self.name}", id=t.id, parent=t.parent or 0
        )
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        now = time.perf_counter_ns()
        self._record(now)
        self._ann.__exit__(None, None, None)
        return False

    def _record(self, now: int) -> None:
        t = self._trace
        t.phases.append((self.name, self._t0 - t.t0_ns, now - self._t0))


class _Half(_Span):
    """One half of a pipelined batch (``enqueue`` or ``complete``): an
    annotation around it and its wall time in the trace's notes as
    ``<name>_ns``, never a phase. A reader subtracts the phases from
    the halves to get the glue between them."""

    __slots__ = ()

    def _record(self, now: int) -> None:
        self._trace.notes[f"{self.name}_ns"] = now - self._t0


class BatchTrace:
    """All spans of one ``_process`` call. ``phases`` is append-only
    from the owning thread; the trace becomes shared (ring buffer,
    monitor event) only after ``end()``."""

    __slots__ = (
        "tracer", "kind", "batch", "ts", "t0_ns", "total_ns", "phases",
        "notes", "id", "parent",
    )

    def __init__(
        self, tracer: "Tracer", kind: str, batch: int,
        parent: Optional[int] = None,
    ) -> None:
        self.tracer = tracer
        self.kind = kind
        self.batch = int(batch)
        # ``id`` is process-unique; ``parent`` is the id of the trace
        # open on this thread when this one began (None: none was), so
        # every span of one request shares an identifier chain
        self.id = next(_TRACE_IDS)
        self.parent = parent
        self.ts = time.time()
        self.total_ns = 0
        self.phases: List[Tuple[str, int, int]] = []
        self.notes: Dict[str, object] = {}
        # last: the batch wall clock starts when construction is done
        self.t0_ns = time.perf_counter_ns()

    def phase(self, name: str) -> _Span:
        return _Span(self, name)

    def half(self, name: str) -> _Half:
        return _Half(self, name)

    def mark(self, **notes) -> None:
        self.notes.update(notes)

    def end(self, hub=None) -> "BatchTrace":
        self.total_ns = time.perf_counter_ns() - self.t0_ns
        self.tracer._complete(self, hub)
        return self

    def to_dict(self) -> Dict:
        return {
            "kind": self.kind,
            "id": self.id,
            "parent": self.parent,
            "batch": self.batch,
            "ts": self.ts,
            "total_ns": self.total_ns,
            "phases": [list(p) for p in self.phases],
            "notes": dict(self.notes),
        }


class Tracer:
    """Per-pipeline span tracer with a bounded ring of completed
    traces. Disabled by default; the daemon toggles it through the
    ``PhaseTracing`` runtime option."""

    def __init__(self, capacity: int = 256) -> None:
        # plain attribute, not a property: the hot path's entire
        # disabled cost is reading this once per batch
        self.active = False
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()

    # -- lifecycle ------------------------------------------------------
    def enable(self) -> None:
        self.active = True

    def disable(self) -> None:
        self.active = False

    def annotate(self, name: str):
        """A profiler annotation while active, else the shared no-op
        (nothing is built): for work outside any batch trace, such as
        conntrack GC on its timer thread."""
        return annotation(name) if self.active else _NOOP_SPAN

    # -- hot-path API ---------------------------------------------------
    def begin(self, kind: str, batch: int) -> BatchTrace:
        """Open a batch trace and push it on this thread's span stack
        (so nested helpers find it via ``current()``). Callers gate on
        ``tracer.active`` BEFORE calling — begin() itself allocates."""
        stack = _stack()
        bt = BatchTrace(self, kind, batch, stack[-1].id if stack else None)
        stack.append(bt)
        return bt

    def current(self):
        """The enclosing batch trace of this tracer on this thread, or
        the no-op singleton when none is open (e.g. ``_dispatch``
        driven directly by a test)."""
        return _innermost("tracer", self)

    def detach(self, bt: BatchTrace) -> None:
        """Remove ``bt`` from this thread's span stack WITHOUT retiring
        it. The pipelined dispatch path parks a submitted batch's trace
        between its enqueue half and its completion half, so spans keep
        attaching to the batch that COMPLETES while ``current()``
        already serves the next submission being prepared."""
        _unstack(bt)

    def _complete(self, bt: BatchTrace, hub=None) -> None:
        """end() tail: pop the span stack, retire the trace into the
        ring, feed the metrics registry, and (monitor listeners only)
        publish a TraceSummary event."""
        _unstack(bt)
        with self._lock:
            self._ring.append(bt)
        for name, _rel, dur in bt.phases:
            _metrics.pipeline_phase_seconds.observe(
                dur / 1e9, {"phase": name}
            )
        if bt.kind not in WRAPPER_KINDS:
            _metrics.batch_total_seconds.observe(bt.total_ns / 1e9)
        if hub is not None and hub.active:
            from ..monitor.events import TraceSummary

            hub.publish(TraceSummary(
                kind=bt.kind, batch=bt.batch, total_ns=bt.total_ns,
                phases=tuple(bt.phases), timestamp=bt.ts,
            ))

    # -- cold-path API --------------------------------------------------
    def traces(self, limit: Optional[int] = None) -> List[Dict]:
        """Completed traces, oldest→newest, bounded by ``limit``."""
        with self._lock:
            items = list(self._ring)
        if limit is not None and limit >= 0:
            items = items[-limit:]
        return [bt.to_dict() for bt in items]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
