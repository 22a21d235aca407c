# policyd: hot
"""Deterministic fault injection for the verdict path (policyd-failsafe).

The pipeline is deep and stateful — bounded in-flight FIFO, CT epochs,
pinned staging free-lists, a verdict mesh — and none of that state is
exercised by tests unless something actually fails mid-batch. This
module is the failure source: a process-wide registry of NAMED
injection sites wired into the hot path (h2d staging, XLA dispatch,
completion pull, CT-epoch advance, kvstore pump, TPU attach, the
admission gate's queue-full probe, the watchdog's stall sweep, the
state-dir CT-snapshot write) that raises classified faults on demand,
deterministically.

Cost model (the hub's ``active`` pattern, observe/tracer.py): the hot
path reads ONE attribute per site visit — ``hub.active`` — and skips
the call entirely when injection is off. The OFF path must stay
byte-identical to pre-faults behavior; tests/test_failsafe.py pins the
compiled program set and verdict outputs with the hub disabled.

Determinism: every site owns its own ``random.Random`` seeded with
``crc32(site) ^ seed`` — NOT ``hash(site)``, which is salted per
process — so a chaos round at a fixed seed injects the same faults at
the same sites in the same order, independent of dict order, thread
interleaving, or which other sites were probed in between.

Taxonomy (mirrors how the pipeline classifies REAL errors):

- ``transient``  — worth a bounded retry (a flaky interconnect, a
  kvstore partition, a wedged attach that recovers on reconnect).
- ``poisoned``   — retry cannot help (device state corrupted, program
  miscompiled); the batch is quarantined and the circuit breaker
  counts toward a degradation-ladder descent.
- ``error``      — NOT a fault: programmer/control errors (TypeError,
  KeyError, assertion) classified out so self-healing never swallows
  a bug; callers re-raise these raw.

Stdlib-only by design: the registry must be importable (and armable)
before jax and inside the proxy."""

from __future__ import annotations

import random
import threading
import zlib
from typing import Dict, Iterable, List, Optional, Tuple

# Stable site names (wired into the hot path; the failsafe, overload
# and survive tests key on these)
SITE_H2D = "h2d"            # staging write + host→device upload
SITE_DISPATCH = "dispatch"  # async XLA enqueue of the fused program
SITE_COMPLETE = "complete"  # host pull of un-pulled device results
SITE_CT_EPOCH = "ct_epoch"  # conntrack basis advance in rebuild()
SITE_KVSTORE = "kvstore"    # SharedStore.pump event drain
SITE_QUEUE_FULL = "queue_full"  # admission gate: forces over-budget
SITE_STALL = "stall"        # watchdog sweep: synthesizes a stuck batch
SITE_STATE_WRITE = "state_write"  # state-dir persistence (CT snapshot)

SITES: Tuple[str, ...] = (
    SITE_H2D, SITE_DISPATCH, SITE_COMPLETE,
    SITE_CT_EPOCH, SITE_KVSTORE,
    SITE_QUEUE_FULL, SITE_STALL, SITE_STATE_WRITE,
)

KIND_TRANSIENT = "transient"
KIND_POISONED = "poisoned"
KIND_ERROR = "error"  # classification-only: never injected


class FaultError(RuntimeError):
    """Base of injected faults. Carries ``site``/``kind`` so the
    pipeline's classification is exact (no string matching)."""

    kind = KIND_TRANSIENT

    def __init__(self, site: str, msg: Optional[str] = None) -> None:
        super().__init__(msg or f"injected {self.kind} fault at {site!r}")
        self.site = site


class TransientFault(FaultError):
    kind = KIND_TRANSIENT


class PoisonedFault(FaultError):
    kind = KIND_POISONED


# Native exception classes treated as transient: environmental errors
# a reconnect/retry can plausibly clear (timeouts and socket errors).
_TRANSIENT_NATIVE = (TimeoutError, ConnectionError, InterruptedError, OSError)
# Programmer/control errors: never "faults" — self-healing must not
# swallow a bug or a shutdown signal.
_ERROR_NATIVE = (
    TypeError, ValueError, KeyError, IndexError, AttributeError,
    AssertionError, NameError, NotImplementedError, StopIteration,
    KeyboardInterrupt, SystemExit, GeneratorExit, MemoryError,
)


def classify(exc: BaseException) -> str:
    """→ ``transient`` | ``poisoned`` | ``error``.

    Injected faults carry their kind; native environmental errors are
    transient; programmer/control errors are surfaced raw (``error``);
    everything else (XLA runtime errors, unknown RuntimeErrors) is
    poisoned — retrying an unknown device failure risks repeating it
    against corrupted state, so the safe default is quarantine."""
    if isinstance(exc, FaultError):
        return exc.kind
    if isinstance(exc, _ERROR_NATIVE):
        return KIND_ERROR
    if isinstance(exc, _TRANSIENT_NATIVE):
        return KIND_TRANSIENT
    return KIND_POISONED


class _Rule:
    """One explicit injection rule: skip ``after`` visits, then fire
    ``times`` faults of ``kind``."""

    __slots__ = ("kind", "times", "after")

    def __init__(self, kind: str, times: int, after: int) -> None:
        self.kind = kind
        self.times = int(times)
        self.after = int(after)


class FaultHub:
    """Process-wide injection registry.

    Disabled cost is one ``hub.active`` attribute read per site visit.
    Enabled, each visit takes the hub lock, consumes explicit rules
    (``fail()``) first, then rolls the site's seeded RNG against the
    armed probability (``arm()``). Counts per (site, kind) accumulate
    in ``injected`` and in ``pipeline_faults_total{site,kind}``."""

    def __init__(self) -> None:
        self.active = False
        self._lock = threading.Lock()
        self._rules: Dict[str, List[_Rule]] = {}
        self._rngs: Dict[str, random.Random] = {}
        self._seed = 0
        self._rate = 0.0
        self._armed_sites: frozenset = frozenset()
        self._poison_every = 0  # every Nth probabilistic fault poisons
        self._prob_fired = 0
        self.injected: Dict[Tuple[str, str], int] = {}

    # -- configuration -------------------------------------------------
    # `active` writes take the hub lock so every mutation is ordered
    # with the guarded state; hot-path READS stay bare by design (a
    # GIL-atomic bool read — the whole point of the hub pattern)
    def enable(self) -> None:
        with self._lock:
            self.active = True

    def disable(self) -> None:
        """Stop injecting. Rules/arming are kept (re-enable resumes);
        use reset() to drop them."""
        with self._lock:
            self.active = False

    def reset(self) -> None:
        with self._lock:
            self.active = False
            self._rules.clear()
            self._rngs.clear()
            self._rate = 0.0
            self._armed_sites = frozenset()
            self._poison_every = 0
            self._prob_fired = 0
            self.injected = {}

    def fail(
        self, site: str, kind: str = KIND_TRANSIENT,
        times: int = 1, after: int = 0,
    ) -> None:
        """Queue an explicit fault: the next visit to ``site`` (after
        skipping ``after`` visits) raises ``times`` faults of ``kind``.
        Enables the hub — an explicit rule always means "inject"."""
        if kind not in (KIND_TRANSIENT, KIND_POISONED):
            raise ValueError(f"kind must be transient|poisoned, got {kind!r}")
        with self._lock:
            self._rules.setdefault(site, []).append(_Rule(kind, times, after))
            self.active = True

    def arm(
        self, seed: int, rate: float,
        sites: Optional[Iterable[str]] = None,
        poison_every: int = 0,
    ) -> None:
        """Probabilistic chaos mode: each visit to an armed site fires
        a fault with probability ``rate``, from a per-site RNG seeded
        ``crc32(site) ^ seed``. ``poison_every=N`` makes every Nth
        probabilistic fault poisoned (0 = all transient)."""
        with self._lock:
            self._seed = int(seed)
            self._rate = float(rate)
            self._armed_sites = frozenset(sites if sites is not None else SITES)
            self._poison_every = int(poison_every)
            self._prob_fired = 0
            self._rngs = {
                s: random.Random(zlib.crc32(s.encode("utf-8")) ^ int(seed))
                for s in self._armed_sites
            }
            self.active = True

    # -- hot-path probe ------------------------------------------------
    def check(self, site: str) -> None:
        """Visit ``site``: raise the due fault, if any. Callers gate on
        ``hub.active`` so the disabled path never reaches here."""
        kind = None
        with self._lock:
            rules = self._rules.get(site)
            if rules:
                r = rules[0]
                if r.after > 0:
                    r.after -= 1
                else:
                    kind = r.kind
                    r.times -= 1
                    if r.times <= 0:
                        rules.pop(0)
            if kind is None and site in self._armed_sites and self._rate > 0.0:
                if self._rngs[site].random() < self._rate:
                    self._prob_fired += 1
                    kind = (
                        KIND_POISONED
                        if self._poison_every
                        and self._prob_fired % self._poison_every == 0
                        else KIND_TRANSIENT
                    )
            if kind is not None:
                k = (site, kind)
                self.injected[k] = self.injected.get(k, 0) + 1
        if kind is None:
            return
        # metric outside the hub lock; imported lazily so the registry
        # stays importable before the package (proxy)
        from . import metrics as _metrics

        _metrics.pipeline_faults_total.inc({"site": site, "kind": kind})
        raise (PoisonedFault if kind == KIND_POISONED else TransientFault)(site)

    def snapshot(self) -> Dict:
        """Introspection for /healthz, traces, and the tests."""
        with self._lock:
            return {
                "active": self.active,
                "injected": {
                    f"{s}:{k}": n for (s, k), n in sorted(self.injected.items())
                },
                "pending_rules": {
                    s: len(rs) for s, rs in self._rules.items() if rs
                },
                "armed_sites": sorted(self._armed_sites),
                "rate": self._rate,
                "seed": self._seed,
            }


# The process-wide hub (the tracer-singleton pattern): sites import
# this module once and read ``hub.active`` per visit.
hub = FaultHub()
