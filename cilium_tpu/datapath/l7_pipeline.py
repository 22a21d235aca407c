"""Overlapped L7 batch classification (policyd-l7batch).

The L7 analogue of DatapathPipeline's submit()/PendingBatch shape: one
``submit()`` packs a request batch's field strings (host work), pushes
the fused DFA walk onto the device asynchronously, and returns a
handle; ``result()`` completes in FIFO order. Host prep of batch N+1
therefore overlaps device execution of batch N — the same overlap
discipline PR 3 gave the verdict path.

Packing follows the PR 5 ladder rules: the walk length is bucketed to
a FIXED rung set (ops.dfa.L7_LEN_LADDER) and the lane (row) dimension
to L7_LANE_RUNGS, so jit keys only on rung shapes — a live batch never
compiles a new program once the rungs are warm. Pad rows are marked
length -1 (the kernels mask them to an empty accept mask) and counted
in ``l7_pad_lanes_total``. Each lane chunk travels as one packed buffer
up (ops.dfa.pack_walk_rows: bytes, lengths and start states) and one
uint32 [2, lanes] result down, counted in
``l7_device_transfers_total``.

The module also owns the ``L7DeviceBatch`` runtime gate: policies read
``device_batch_enabled()`` per batch and fall back to their exact
pre-option code path when it is off (the FlowAttribution /
DispatchAutoTune pinning contract).
"""
# policyd: hot

from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import metrics
from ..observe.tracer import NOOP_BATCH, Tracer
from ..ops.dfa import (
    DeviceDFATable,
    L7_LEN_LADDER,
    dfa_match_packed_fused,
    dfa_match_packed_pair,
    len_rung,
    pack_walk_rows,
    strings_to_batch_u8,
)

# Lane (row-count) rungs: a submit of F fields × B requests dispatches
# ceil(F*B / top) full-rung chunks plus one tail rung. Fixed set —
# same contract as the verdict path's BUCKET_LADDER.
L7_LANE_RUNGS: Tuple[int, ...] = (512, 4096, 16384)


def lane_rung(needed: int) -> int:
    for rung in L7_LANE_RUNGS:
        if needed <= rung:
            return rung
    return L7_LANE_RUNGS[-1]


class PendingL7Batch:
    """Handle for one submitted L7 classification batch. ``result()``
    blocks until this batch (and every earlier one — FIFO) is pulled,
    and returns per-field ``[B] uint64`` accept masks."""

    __slots__ = ("_pipe", "_done", "_value", "_exc")

    def __init__(self, pipe: "L7Pipeline") -> None:
        self._pipe = pipe
        self._done = False
        self._value: Optional[List[np.ndarray]] = None
        self._exc: Optional[BaseException] = None

    def result(self) -> List[np.ndarray]:
        if not self._done:
            self._pipe._complete_until(self)
        if self._exc is not None:
            raise self._exc
        assert self._value is not None
        return self._value


class _InFlight:
    __slots__ = ("pending", "chunks", "n_req", "n_fields", "bt", "t0",
                 "parser", "ps")

    def __init__(self, pending, chunks, n_req, n_fields, bt, t0, parser,
                 ps=None) -> None:
        self.pending = pending
        # [(masks_dev uint32 [2, lanes], rows_live)] — device handles;
        # pulled at completion time, not submit time (that's the overlap)
        self.chunks = chunks
        self.n_req = n_req
        self.n_fields = n_fields
        self.bt = bt
        self.t0 = t0
        self.parser = parser
        # policyd-prof: live _DispatchSample on the profiler's Nth
        # batch (None otherwise); _finish times the mask pull into it
        self.ps = ps


class L7Pipeline:
    """Bounded in-flight queue of fused-DFA dispatches.

    Depth semantics mirror DatapathPipeline: ``submit()`` retires the
    oldest batch first once ``depth`` batches are on device, so at
    most ``depth`` device programs are outstanding while the host
    packs the next batch.
    """

    def __init__(self, depth: int = 2, tracer: Optional[Tracer] = None) -> None:
        self.depth = max(1, int(depth))
        self.tracer = tracer
        # policyd-prof: None (off) keeps submit()/_finish() at one
        # attribute read per batch; the daemon installs the shared
        # DeviceProfiler through set_profiler() below
        self.profiler = None
        self._lock = threading.Lock()
        self._inflight: "deque[_InFlight]" = deque()
        # jit program identity for the walk is (kernel, Q, lanes, rung):
        # tracked so first-use compiles are visible in /metrics and the
        # prewarm pass can claim its rungs
        self._seen_shapes: set = set()

    # -- shape accounting ------------------------------------------------
    def _note_shape(self, kind: str, n_states: int, lanes: int, rung: int,
                    warm: bool = False) -> None:
        key = (kind, n_states, lanes, rung)
        with self._lock:
            fresh = key not in self._seen_shapes
            if fresh:
                self._seen_shapes.add(key)
        if fresh:
            result = "warm" if warm else "miss"
        else:
            result = "hit"
        metrics.jit_shape_buckets_total.inc({"site": "l7", "result": result})

    def prewarm(self, table: DeviceDFATable, caps: Sequence[int]) -> int:
        """Compile the walk for every (lane, length) rung this table
        can be dispatched at — at policy compile() time, so no request
        batch ever eats a first-use jit compile mid-request. → number
        of programs warmed (counted under
        ``jit_shape_buckets_total{site="l7",result="warm"}``)."""
        cap_max = max(caps)
        rungs = [r for r in L7_LEN_LADDER if r <= cap_max]
        if cap_max not in rungs:
            rungs.append(cap_max)
        warmed = 0
        for rung in rungs:
            for lanes in L7_LANE_RUNGS:
                key_kind = "pair" if table.has_pair else "fused"
                key = (key_kind, table.n_states, lanes, rung)
                with self._lock:
                    if key in self._seen_shapes:
                        continue
                none = np.zeros(0, np.int32)
                packed = pack_walk_rows(np.zeros((0, rung), np.uint8), none, none, lanes)
                self._walk(table, packed, rung).block_until_ready()
                self._note_shape(key_kind, table.n_states, lanes, rung, warm=True)
                warmed += 1
        return warmed

    # -- dispatch --------------------------------------------------------
    def _walk(self, table: DeviceDFATable, packed, rung: int):
        """One packed chunk (host or device array) → its device masks,
        uint32 [2, lanes]: one upload, one output buffer."""
        if table.has_pair:
            return dfa_match_packed_pair(
                table.pair, table.accept_lo, table.accept_hi, packed, rung,
            )
        return dfa_match_packed_fused(
            table.trans, table.accept_lo, table.accept_hi, packed, rung,
        )

    def submit(
        self,
        table: DeviceDFATable,
        fields: Sequence[Tuple[Sequence[bytes], int]],
        parser: str = "http",
    ) -> PendingL7Batch:
        """Classify one request batch against ``table``.

        ``fields`` pairs each fused field slot (in table order) with
        (encoded values, field length cap). Values longer than their
        field cap come back with mask 0 — the CALLER host-walks those
        rows, exactly as the unfused path does. → PendingL7Batch whose
        ``result()`` is per-field ``[B] uint64`` masks."""
        if len(fields) != table.n_fields:
            raise ValueError(
                f"table fuses {table.n_fields} fields, got {len(fields)}"
            )
        t0 = time.perf_counter()
        tr = self.tracer
        bt = tr.begin("l7", len(fields[0][0])) if (tr is not None and tr.active) else NOOP_BATCH

        # the enqueue half ends at queue admission: finishing older
        # batches past the depth bound belongs to THEIR complete halves
        with bt.half("enqueue"):
            with bt.phase("prepare"):
                n_req = len(fields[0][0])
                caps = [cap for _, cap in fields]
                flat: List[bytes] = []
                for values, _cap in fields:
                    if len(values) != n_req:
                        raise ValueError("field batches must be the same length")
                    flat.extend(values)
                # one rung covers every field; per-field caps re-mark
                # overlong rows below
                needed = 1
                for s in flat:
                    if len(s) > needed:
                        needed = len(s)
                cap_max = max(caps)
                rung = len_rung(min(needed, cap_max), cap_max)
                sb, lens = strings_to_batch_u8(flat, rung)
                for f, cap in enumerate(caps):
                    if cap < rung:
                        seg = lens[f * n_req : (f + 1) * n_req]
                        seg[seg > cap] = -1
                starts = np.repeat(table.starts_host, n_req)
                live = int(lens.size)
                live_bytes = int(np.maximum(lens, 0).sum())
                # full top-rung chunks, then one tail chunk padded to
                # its lane rung: the pad rows all sit at the end
                top = L7_LANE_RUNGS[-1]
                tail = live % top
                rows = live - tail + (lane_rung(tail) if tail else 0)
                packed = pack_walk_rows(sb, lens, starts, rows)

            # policyd-prof: one attribute read while off; the sampled
            # batch pays the explicit-upload / ready sandwiches below
            prof = self.profiler
            ps = prof.begin_dispatch("l7", n_req) if prof is not None else None

            with bt.phase("dispatch"):
                chunks = []
                pad_rows = rows - live
                off = 0
                _pl_t0 = time.perf_counter() if ps is not None else 0.0
                kind = "pair" if table.has_pair else "fused"
                while off < live:
                    take = min(top, live - off)
                    lanes = lane_rung(take)
                    chunk = packed[off : off + lanes]
                    if ps is not None:
                        # sampled h2d edge: upload explicitly and wait so
                        # the walk below starts from a device-resident
                        # buffer (same aval, same compiled program). The
                        # per-chunk sync IS the measurement, 1-in-N
                        # batches only:
                        _t0 = time.perf_counter()
                        chunk = jax.block_until_ready(  # policyd-lint: disable=TPU002
                            jax.device_put(chunk)
                        )
                        ps.add_h2d(time.perf_counter() - _t0)
                    self._note_shape(kind, table.n_states, lanes, rung)
                    chunks.append((self._walk(table, chunk, rung), take))
                    off += take
                n_chunks = len(chunks)
                metrics.l7_device_transfers_total.inc(
                    {"direction": "h2d", "parser": parser}, n_chunks
                )
                if ps is not None:
                    # sampled compute edge: h2d already completed above, so
                    # the rest of the chunk loop (per-rung jit dispatch)
                    # plus the residual wait here is the fused DFA walk
                    # side of the split
                    jax.block_until_ready([c[0] for c in chunks])
                    ps.add_compute(
                        time.perf_counter() - _pl_t0 - ps.h2d_s
                    )
                    ps.mark(
                        rungs=[lane_rung(c[1]) for c in chunks],
                        len_rung=int(rung),
                        lanes=int(live),
                        pad_lanes=int(pad_rows),
                        chunks=n_chunks,
                        parser=parser,
                    )
                metrics.l7_pad_lanes_total.inc({"kind": "lane"}, pad_rows)
                metrics.l7_pad_lanes_total.inc({"kind": "lane_live"}, live)
                metrics.l7_pad_lanes_total.inc(
                    {"kind": "len_bytes"}, live * rung - live_bytes
                )
                metrics.l7_pad_lanes_total.inc({"kind": "len_bytes_live"}, live_bytes)
                metrics.l7_batches_total.inc({"parser": parser})

            pending = PendingL7Batch(self)
            entry = _InFlight(pending, chunks, n_req, table.n_fields, bt, t0,
                              parser, ps)
            if bt is not NOOP_BATCH:
                tr.detach(bt)
            overflow: List[_InFlight] = []
            with self._lock:
                self._inflight.append(entry)
                while len(self._inflight) > self.depth:
                    overflow.append(self._inflight.popleft())
        for e in overflow:
            self._finish(e)
        return pending

    # -- completion ------------------------------------------------------
    def _complete_until(self, pending: PendingL7Batch) -> None:
        while not pending._done:
            with self._lock:
                if not self._inflight:
                    break
                entry = self._inflight.popleft()
            self._finish(entry)

    def _finish(self, entry: _InFlight) -> None:
        bt = entry.bt
        with bt.half("complete"):
            self._pull(entry)
        bt.end()

    def _pull(self, entry: _InFlight) -> None:
        ps = entry.ps
        _pt0 = time.perf_counter() if ps is not None else 0.0
        try:
            with entry.bt.phase("host_sync"):
                parts = []
                for dev, take in entry.chunks:
                    words = np.asarray(dev)[:, :take].astype(np.uint64)
                    parts.append(words[0] | (words[1] << np.uint64(32)))
                metrics.l7_device_transfers_total.inc(
                    {"direction": "d2h", "parser": entry.parser}, len(parts)
                )
                if not parts:
                    masks = np.zeros(0, np.uint64)
                elif len(parts) == 1:
                    masks = parts[0]
                else:
                    masks = np.concatenate(parts)
            b = entry.n_req
            entry.pending._value = [
                masks[f * b : (f + 1) * b] for f in range(entry.n_fields)
            ]
        # not swallowed: the error is stored and re-raised by the
        # caller's result() — completion must still mark the batch done
        # or FIFO draining would deadlock behind it
        except Exception as exc:  # policyd-lint: disable=ROBUST001
            entry.pending._exc = exc
        if ps is not None:
            ps.add_d2h(time.perf_counter() - _pt0)
            prof = self.profiler
            if prof is not None:
                prof.complete(ps)
            entry.ps = None
        entry.pending._done = True
        metrics.l7_batch_seconds.observe(time.perf_counter() - entry.t0)

    def drain(self) -> None:
        while True:
            with self._lock:
                if not self._inflight:
                    return
                entry = self._inflight.popleft()
            self._finish(entry)


# ---------------------------------------------------------------------------
# L7DeviceBatch runtime gate
# ---------------------------------------------------------------------------

_rt_lock = threading.Lock()
_enabled = False
_pipeline: Optional[L7Pipeline] = None
# shared DeviceProfiler (policyd-prof): installed by the daemon while
# DeviceProfiling is on; carried onto any pipeline set_device_batch
# creates later so toggle order doesn't matter
_profiler = None


def set_device_batch(on: bool, tracer: Optional[Tracer] = None,
                     depth: int = 2) -> None:
    """Flip the L7DeviceBatch runtime option. Turning it OFF drains
    outstanding batches and drops the shared pipeline — the next check
    runs the pre-option code path with the pre-option programs."""
    global _enabled, _pipeline
    with _rt_lock:
        if on:
            if _pipeline is None or (tracer is not None and _pipeline.tracer is not tracer):
                _pipeline = L7Pipeline(depth=depth, tracer=tracer)
            _pipeline.profiler = _profiler
            _enabled = True
            return
        _enabled = False
        pipe, _pipeline = _pipeline, None
    if pipe is not None:
        pipe.drain()


def set_profiler(prof) -> None:
    """Install (or clear, with None) the shared DeviceProfiler on the
    L7 pipeline — the DeviceProfiling half of the L7DeviceBatch gate."""
    global _profiler
    with _rt_lock:
        _profiler = prof
        if _pipeline is not None:
            _pipeline.profiler = prof


def device_batch_enabled() -> bool:
    # one unlocked read on the request path (same cost model as
    # tracer.active)
    return _enabled


def shared_pipeline() -> Optional[L7Pipeline]:
    with _rt_lock:
        return _pipeline


def _reset_for_tests() -> None:
    set_device_batch(False)
    set_profiler(None)
