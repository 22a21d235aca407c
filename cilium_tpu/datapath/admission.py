# policyd: hot
"""Deadline-aware admission control + stuck-dispatch watchdog
(policyd-overload).

PR 6's failsafe heals *faults*; this module handles *overload* and
*hangs* — the two failure classes a policy plane serving millions of
users meets long before a poisoned device program:

- ``AdmissionController``: an AIMD limit on the submit queue, keyed on
  queue wait + an EWMA of completion latency. Every submitted batch
  can carry a deadline (``DaemonConfig.verdict_deadline_ms``); over
  budget, the pipeline routes flows through the prefilter shed stage
  (``compile_shed_table`` + the ``shed_flows*`` kernels in
  pipeline.py) or defers them bounded — never an unbounded queue,
  never a silent drop.

- ``compile_shed_table``: the host compile of the coarse
  ``[identity, proto/port-class]`` drop table (PAPER.md layer 1's XDP
  prefilter role, drop reason 144). Sound by construction: a cell is
  markable only when NO realized policymap column of ANY local
  endpoint could allow ANY flow in it, so a shed verdict is always a
  verdict the full path would also have denied.

- ``Watchdog``: a monitor thread that bounds how long the daemon can
  block on a wedged dispatch.
  A batch whose completion pull exceeds ``dispatch_stall_ms`` is
  abandoned THROUGH the PR 6 quarantine — degraded result, CT-epoch
  bump, breaker accounting — and ``result()`` unblocks with a verdict
  per flow. Registered external waits (compile) ride the same sweep
  via ``watching()``.

Both halves are deterministically injectable: ``SITE_QUEUE_FULL``
forces the gate over budget, ``SITE_STALL`` fires a synthetic stall
through the same classify → breaker path a real one takes.

Stdlib + numpy only: the controller and watchdog must be importable
(and testable) without jax; the device kernels live in pipeline.py.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import faults as _faults
from .. import metrics as _metrics

# -- the proto/port class law ----------------------------------------------
# 3 proto rows (tcp / udp / other) × 3 dport buckets (well-known <1024,
# registered <32768, ephemeral) = 9 classes. Coarse on purpose: the
# table must stay a single cheap gather, and DoS mixes concentrate in
# few cells (a SYN flood is one (tcp, bucket) column).
PROTO_TCP = 6
PROTO_UDP = 17
N_PROTO_CLASSES = 3
N_PORT_BUCKETS = 3
N_SHED_CLASSES = N_PROTO_CLASSES * N_PORT_BUCKETS

REASON_SHED_PREFILTER = "prefilter"  # drop reason 144
REASON_SHED_DEADLINE = "deadline"    # resolved via 155 / FailOpen


def flow_class(dport, proto):
    """[B] proto, [B] dport → [B] class index in [0, 9). Operator-only
    math so the SAME law runs on host numpy (table compile, tests) and
    inside the jitted shed walk (jnp arrays)."""
    pi = 2 - 2 * (proto == PROTO_TCP) - 1 * (proto == PROTO_UDP)
    bucket = (dport >= 1024) * 1 + (dport >= 32768) * 1
    return pi * N_PORT_BUCKETS + bucket


def _port_bucket(port: int) -> int:
    return (1 if port >= 1024 else 0) + (1 if port >= 32768 else 0)


def compile_shed_table(
    allow_nc: np.ndarray,  # [N, C_pad] bool host policymap mirror
    ep_slots: Sequence[Sequence[Tuple[int, int]]],
) -> np.ndarray:
    """Realized policymap → ``[N, 9]`` uint8 drop table (1 = every flow
    in this (identity row, class) cell is deny-for-sure).

    A cell stays 0 ("don't shed") whenever any column of any local
    endpooint could cover it: the L3-only column covers every class,
    a (0, proto) slot covers the proto's three buckets, a (port, proto)
    slot covers its exact (proto, bucket) cell. Unknown protos map to
    the "other" row (coverage within the class is a superset of the
    column's true match set, which only ever clears shed bits — the
    sound direction). Merged over endpoints: shed only when NO endpoint
    allows, so the table is valid for any ep_idx in the batch."""
    n = allow_nc.shape[0]
    if not len(ep_slots):
        # no endpoints → nothing can be proven deny-heavy; shed nothing
        return np.zeros((n, N_SHED_CLASSES), np.uint8)
    covered = np.zeros((n, N_SHED_CLASSES), bool)
    col = 0
    for slots in ep_slots:
        l3 = allow_nc[:, col]
        col += 1
        covered |= l3[:, None]
        for port, proto in slots:
            a = allow_nc[:, col]
            col += 1
            if proto == PROTO_TCP:
                pis = (0,)
            elif proto == PROTO_UDP:
                pis = (1,)
            elif proto == 0:  # wildcard proto covers every row
                pis = (0, 1, 2)
            else:
                pis = (2,)
            buckets = (
                range(N_PORT_BUCKETS) if port == 0 else (_port_bucket(port),)
            )
            for pi in pis:
                for bk in buckets:
                    covered[:, pi * N_PORT_BUCKETS + bk] |= a
    return (~covered).astype(np.uint8)


class AdmissionController:
    """AIMD submit-queue limit, keyed on EWMA completion latency.

    The limit moves in ``[1, max_depth]``: additive increase on every
    in-deadline completion, multiplicative (halving) decrease on a
    deadline overrun or an injected queue-full. ``over_budget`` is the
    gate decision: depth at the limit, OR — with a deadline configured
    — the Little's-law projection ``(depth + 1) × ewma`` past the
    budget (admitting one more batch behind ``depth`` waiters can't
    finish in time, so shed it NOW instead of queueing it to die).

    ``shedding()`` is the tuner armistice: while the gate shed
    recently, the depth controller must not probe the queue UP — two
    controllers pushing the same knob in opposite directions is a
    classic oscillation."""

    SHED_HOLD_S = 1.0
    EWMA_ALPHA = 0.2

    def __init__(self, max_depth: int, deadline_ms: float = 0.0) -> None:
        self.max_depth = max(1, int(max_depth))
        self.deadline_s = max(0.0, float(deadline_ms)) / 1000.0
        self._lock = threading.Lock()
        self._limit = float(self.max_depth)
        self._ewma_s = 0.0
        self._last_shed = 0.0  # time.monotonic of the last shed
        self.shed = {REASON_SHED_PREFILTER: 0, REASON_SHED_DEADLINE: 0}
        self.admitted = 0  # flows that entered the full verdict path
        # lifecycle-journal hook (policyd-journal): daemon sets this to
        # EventJournal.emit while LifecycleJournal is on; None keeps
        # the hot path at one attribute read. Shed episodes are EDGE
        # TRIGGERED — one shed_start when shedding begins, one shed_end
        # after SHED_HOLD_S of quiet — never one event per shed batch.
        self.on_journal = None
        self._episode = None  # {"t0": monotonic, "shed0": counts}

    @property
    def limit(self) -> float:
        return self._limit

    def over_budget(self, depth: int) -> bool:
        with self._lock:
            if depth + 1 > self._limit:
                return True
            if self.deadline_s and self._ewma_s:
                return (depth + 1) * self._ewma_s > self.deadline_s
            return False

    def observe_completion(self, latency_s: float) -> None:
        """One batch finished ``latency_s`` after submit: fold into the
        EWMA and move the AIMD limit."""
        in_deadline = (
            not self.deadline_s or latency_s <= self.deadline_s
        )
        with self._lock:
            self._ewma_s = (
                latency_s
                if self._ewma_s == 0.0
                else (1 - self.EWMA_ALPHA) * self._ewma_s
                + self.EWMA_ALPHA * latency_s
            )
            if in_deadline:
                # additive increase, slower near the ceiling (the
                # classic 1/w growth keeps the probe gentle)
                self._limit = min(
                    float(self.max_depth), self._limit + 1.0 / self._limit
                )
            else:
                self._limit = max(1.0, self._limit / 2.0)

    def note_queue_full(self) -> None:
        """Injected (or observed) queue-full: multiplicative decrease
        without waiting for a completion to prove the overrun."""
        with self._lock:
            self._limit = max(1.0, self._limit / 2.0)

    def note_shed(self, reason: str, n: int) -> None:
        end_attrs = start_attrs = None
        with self._lock:
            now = time.monotonic()
            # a burst arriving after the hold window first closes the
            # PREVIOUS episode (its deltas must not include this burst)
            if (
                self._episode is not None
                and now - self._last_shed >= self.SHED_HOLD_S
            ):
                end_attrs = self._close_episode_locked(now)
            if self._episode is None:
                self._episode = {"t0": now, "shed0": dict(self.shed)}
                start_attrs = {"reason": reason}
            self.shed[reason] = self.shed.get(reason, 0) + int(n)
            self._last_shed = now
        _metrics.admission_shed_total.inc({"reason": reason}, float(n))
        oj = self.on_journal
        if oj is not None:
            if end_attrs is not None:
                oj(kind="shed_end", attrs=end_attrs)
            if start_attrs is not None:
                oj(kind="shed_start", severity="warning", attrs=start_attrs)

    def _close_episode_locked(self, now: float) -> Dict:
        """Retire the open episode; returns the shed_end attrs (the
        caller emits OUTSIDE the lock). Deltas are per-reason counts
        shed since the episode opened — the journal carries episode
        totals, never per-flow records."""
        ep = self._episode
        self._episode = None
        deltas = {
            r: self.shed.get(r, 0) - ep["shed0"].get(r, 0)
            for r in self.shed
            if self.shed.get(r, 0) - ep["shed0"].get(r, 0)
        }
        return {
            "shed": deltas,
            "duration_s": round(self._last_shed - ep["t0"], 6),
        }

    def episode_poll(self) -> None:
        """Close an episode that went quiet (called on the daemon's
        journal-shed-poll controller): without this, the FINAL shed_end
        of a load spike would wait for the next overload to surface."""
        end_attrs = None
        with self._lock:
            now = time.monotonic()
            if (
                self._episode is not None
                and now - self._last_shed >= self.SHED_HOLD_S
            ):
                end_attrs = self._close_episode_locked(now)
        oj = self.on_journal
        if oj is not None and end_attrs is not None:
            oj(kind="shed_end", attrs=end_attrs)

    def note_admitted(self, n: int) -> None:
        with self._lock:
            self.admitted += int(n)

    def shedding(self) -> bool:
        return time.monotonic() - self._last_shed < self.SHED_HOLD_S

    def snapshot(self) -> Dict:
        with self._lock:
            shed_n = sum(self.shed.values())
            total = shed_n + self.admitted
            return {
                "limit": round(self._limit, 3),
                "max_depth": self.max_depth,
                "deadline_ms": self.deadline_s * 1000.0,
                "ewma_completion_ms": round(self._ewma_s * 1000.0, 3),
                "shed": dict(self.shed),
                "admitted_flows": self.admitted,
                "shed_ratio": round(shed_n / total, 6) if total else 0.0,
                "shedding": time.monotonic() - self._last_shed
                < self.SHED_HOLD_S,
            }


class Watchdog:
    """Stuck-operation monitor (the bound on how long the daemon can
    hang). Three watch sources per sweep:

    - the pipeline's ACTIVELY COMPLETING batch (``pipe._completing``,
      set around the finish closure): a completion pull older than the
      stall budget is abandoned through ``pipe._quarantine`` — the
      waiter's ``result()`` unblocks with a degraded verdict per flow
      while the wedged XLA pull is left to die on its own thread.
      In-flight batches nobody is pulling are NOT stalls — lazy
      completion is the pipeline's normal shape.
    - registered external waits (``watching(site)``): compile
      stalls ride the same sweep; one metric + breaker note
      per stalled op.
    - ``SITE_STALL`` injection: with the hub armed, every sweep probes
      the site, so a chaos round drives the whole detect → classify →
      quarantine path without a real wedge.

    The sweep interval is stall/4 (clamped to [1ms, 250ms]), so a
    stall is detected at most 1.25× the budget after it began —
    comfortably under the 2× acceptance bound."""

    def __init__(self, pipe, stall_ms: float) -> None:
        self._pipe = pipe
        self.stall_s = float(stall_ms) / 1000.0
        self._poll_s = min(0.25, max(0.001, self.stall_s / 4.0))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._external: Dict[int, List] = {}  # token → [site, t0, fired]
        self._next_token = 0
        self.last_stall: Optional[Dict] = None
        self.stalls = 0

    # -- lifecycle -----------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="policyd-watchdog", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        t = self._thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout=max(1.0, 8 * self._poll_s))
        self._thread = None

    # -- external waits ------------------------------------------------
    @contextmanager
    def watching(self, site: str):
        """Register an external operation (policy compile) for the
        sweep: if it outlives the stall budget it is
        counted and classified like a stuck dispatch. The operation
        itself is not interrupted — the point is that the stall becomes
        VISIBLE (metric + breaker) instead of a silent hang."""
        with self._lock:
            token = self._next_token
            self._next_token += 1
            self._external[token] = [site, time.monotonic(), False]
        try:
            yield
        finally:
            with self._lock:
                self._external.pop(token, None)

    # -- the sweep -----------------------------------------------------
    def _note_stall(self, site: str, age_s: float, exc: BaseException) -> None:
        self.stalls += 1
        self.last_stall = {
            "site": site,
            "age_ms": round(age_s * 1000.0, 3),
            "at": time.time(),
        }
        _metrics.watchdog_stalls_total.inc({"site": site})
        oj = getattr(self._pipe, "on_journal", None)
        if oj is not None:
            oj(
                kind="watchdog_stall",
                severity="error",
                attrs={"site": site, "age_ms": round(age_s * 1000.0, 3)},
            )
        kind = _faults.classify(exc)
        # a stall is never a programmer error; classify() maps the
        # TimeoutError we synthesize (and injected FaultErrors) to
        # transient/poisoned — both feed the breaker
        if kind == _faults.KIND_ERROR:
            kind = _faults.KIND_TRANSIENT
        self._pipe._note_fault(exc, kind)

    def _sweep(self) -> None:
        now = time.monotonic()
        pipe = self._pipe
        # injected stalls: deterministic chaos without a real wedge
        if _faults.hub.active:
            try:
                _faults.hub.check(_faults.SITE_STALL)
            except _faults.FaultError as e:
                self._note_stall(_faults.SITE_STALL, 0.0, e)
        # the actively-completing batch
        completing = pipe._completing
        if completing is not None:
            inf, t0 = completing
            if now - t0 > self.stall_s:
                abandoned = False
                with pipe._queue_lock:
                    if not inf.abandoned and not inf.pending.done:
                        inf.abandoned = True
                        abandoned = True
                if abandoned:
                    exc = TimeoutError(
                        f"dispatch completion stalled > "
                        f"{self.stall_s * 1000.0:.0f}ms"
                    )
                    self._note_stall(_faults.SITE_DISPATCH, now - t0, exc)
                    # quarantine THROUGH the failsafe path: CT epoch
                    # bump + degraded result, then unblock the waiter
                    value = pipe._quarantine(inf)
                    inf.pending._value = value
                    inf.pending._event.set()
        # registered external waits (compile)
        with self._lock:
            stuck = [
                e for e in self._external.values()
                if not e[2] and now - e[1] > self.stall_s
            ]
            for e in stuck:
                e[2] = True  # one note per op
        for site, t0, _f in stuck:
            self._note_stall(
                site, now - t0,
                TimeoutError(
                    f"{site} stalled > {self.stall_s * 1000.0:.0f}ms"
                ),
            )

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            try:
                self._sweep()
            # the watchdog must never die to a racing teardown (the
            # pipe it probes is being mutated by shutdown); a broken
            # sweep carries no pipeline state to corrupt — it simply
            # retries next tick, so classification has nothing to do
            except Exception:  # policyd-lint: disable=ROBUST001
                continue

    def snapshot(self) -> Dict:
        with self._lock:
            watching = [e[0] for e in self._external.values()]
        return {
            "stall_ms": self.stall_s * 1000.0,
            "stalls": self.stalls,
            "last_stall": self.last_stall,
            "watching": watching,
            "alive": self._thread is not None,
        }
