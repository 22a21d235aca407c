"""Traffic: what every kind of traffic shares. A traffic mix is a data
file, ``benchmark/traffic/<name>.json``; its ``kind`` names the module
``benchmark/kinds/<kind>.py`` that makes its batches, sends them down
the program's entry, and compares the answers. This module holds the
schedule and the flow drawing those kinds share.

Steadiness: the work in a window must not depend on the seed, only its
order. So batch sizes, gaps between arrivals and the family/direction
of each batch come from fixed quantile grids of their distributions,
one cycle of ``cycle`` batches at a time, permuted by the seed. Two
seeds then offer the same sizes at the same mean rate, in another
order, with other flows in them.

Flow content follows ``chip_smoke.make_batch`` (PR 21): a share aimed
at pairs some rule allows (some of them at the L7 endpoint's port),
random pods drawn Zipf over identities, local endpoints, and world
addresses of which half lie inside a prefilter prefix."""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import os
from contextlib import nullcontext
from typing import List, Optional

import numpy as np

from . import world as W

HERE = os.path.dirname(os.path.abspath(__file__))
TCP, UDP = 6, 17
DROP_DEGRADED = 5        # the program's verdict for a batch its failsafe resolved
# (family, ingress) of a batch, in cycle order
KINDS = ((4, True), (4, False), (6, True), (6, False))


def load_kind(name: str):
    """``benchmark/kinds/<name>.py``: the code behind a traffic kind."""
    path = os.path.join(HERE, "kinds", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"no traffic kind {name!r} (benchmark/kinds/{name}.py)")
    spec = importlib.util.spec_from_file_location(f"benchmark_kind_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(on: bool, name: str):
    """A host span in the profiler trace (``bench.*``), when tracing."""
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Done:
    """An answer that is there already: a synchronous entry's handle."""

    done = True

    def __init__(self, value) -> None:
        self._value = value

    def result(self):
        return self._value


@dataclasses.dataclass
class FlowBatch:
    family: int
    ingress: bool
    peer: np.ndarray      # [B] uint32 (v4) or [B, 16] int32 (v6)
    ep: np.ndarray        # [B] int32 local endpoint index
    dport: np.ndarray     # [B] int32
    proto: np.ndarray     # [B] int32
    sport: np.ndarray     # [B] int32
    peer_app: np.ndarray  # [B] int32 app index at that address (-1 = world)

    def __len__(self) -> int:
        return len(self.ep)


@dataclasses.dataclass
class Schedule:
    due: np.ndarray            # [N] seconds after the window opens
    batches: list              # the kind's batches, in due order
    rate: float                # offered items per second


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def cycle_sizes(t: dict) -> np.ndarray:
    """Batch sizes of one cycle: the quantile grid of a log-uniform
    distribution over [batch_min, batch_max]."""
    lo, hi = math.log(t["batch_min"]), math.log(t["batch_max"])
    return np.round(np.exp(lo + (hi - lo) * _grid(int(t["cycle"])))).astype(np.int64)


def cycle_kinds(t: dict) -> np.ndarray:
    """Index into KINDS for each batch of a cycle, in the mix's exact
    proportions (v6 share x egress share)."""
    n = int(t["cycle"])
    v6, eg = float(t.get("v6_share", 0.0)), float(t.get("egress_share", 0.0))
    shares = [(1 - v6) * (1 - eg), (1 - v6) * eg, v6 * (1 - eg), v6 * eg]
    counts = np.floor(np.array(shares) * n + 1e-9).astype(int)
    counts[0] += n - counts.sum()
    return np.repeat(np.arange(4), counts)


def zipf_cdf(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** s
    return np.cumsum(p / p.sum())


def schedule(kind, seconds: float, rate: Optional[float] = None) -> Schedule:
    """Batches due in [0, seconds): Poisson arrivals whose gaps and
    sizes are a cycle's quantile grids, permuted by the seed; the kind
    makes each batch from its size and its (family, direction) index."""
    t, rng = kind.t, kind.rng
    rate = float(t["rate"] if rate is None else rate)
    sizes = cycle_sizes(t)
    mean_gap = float(sizes.mean()) / rate
    gaps = -np.log(1.0 - _grid(len(sizes))) * mean_gap
    kinds = cycle_kinds(t)
    due, batches, now = [], [], 0.0
    while now < seconds:
        cs, cg, ck = (rng.permutation(x) for x in (sizes, gaps, kinds))
        for size, gap, k in zip(cs, cg, ck):
            now += gap
            if now >= seconds:
                break
            due.append(now)
            batches.append(kind.batch(int(size), int(k)))
    return Schedule(np.array(due), batches, rate)


class FlowSource:
    """Draws flows of a mix from its world and the seed."""

    def __init__(self, w: W.World, t: dict, rng) -> None:
        self.w, self.t, self.rng = w, t, rng
        # peers are drawn over every identity pods run under: the
        # services, or in a ``clusters`` world each (cluster, service)
        n_ident = w.n_idents
        self._svc_cdf = zipf_cdf(n_ident, float(t.get("peer_zipf_s", 1.1)))
        self._svc_perm = self.rng.permutation(n_ident)
        # per-endpoint allowed (peer app, port, proto) options, flattened
        self._opts = {}
        for ingress, table in ((True, w.allow_in), (False, w.allow_eg)):
            off, peer, port, proto = [0], [], [], []
            for e in range(len(w.ep_app)):
                o = table.get(int(w.ep_app[e]), [])
                for (p, pt, pr) in o:
                    peer.append(p), port.append(pt), proto.append(pr)
                off.append(len(peer))
            self._opts[ingress] = (np.array(off), np.array(peer, np.int64),
                                   np.array(port, np.int64), np.array(proto, np.int64))
        order = np.argsort(w.pod_app, kind="stable")
        self._pod_order = order
        self._pod_bounds = np.searchsorted(w.pod_app[order], np.arange(w.n_apps + 1))
        flat = [(int(n), plen) for plen, nets in w.prefixes.items() for n in nets]
        self._pf_nets = np.array([n for n, _ in flat], np.int64)
        self._pf_lens = np.array([p for _, p in flat], np.int64)
        self._l7_ep = (len(w.ep_app) - 1) if w.cfg.get("l7_endpoint") else None

    def _pods_of(self, apps: np.ndarray) -> np.ndarray:
        lo, hi = self._pod_bounds[apps], self._pod_bounds[apps + 1]
        return self._pod_order[lo + (self.rng.random(len(apps)) * (hi - lo)).astype(np.int64)]

    def _zipf_apps(self, n: int) -> np.ndarray:
        r = np.searchsorted(self._svc_cdf, self.rng.random(n), side="right")
        return self._svc_perm[np.minimum(r, len(self._svc_perm) - 1)]

    def flows(self, n: int, family: int, ingress: bool, *, allowed_share=None,
              l7_share=None, ep=None) -> FlowBatch:
        """n flows of the mix: ``allowed_share`` aimed at pairs some
        rule allows (``l7_share`` of those at the L7 endpoint's port),
        ``local_share`` from local endpoints, ``world_share`` from world
        addresses (half prefilter-listed, v4), the rest from pods drawn
        Zipf over identities."""
        t, w, rng = self.t, self.w, self.rng
        a_share = float(t["allowed_share"] if allowed_share is None else allowed_share)
        l7 = float(t.get("l7_share_of_allowed", 0.0) if l7_share is None else l7_share)
        ws, ls = float(t.get("world_share", 0.0)), float(t.get("local_share", 0.0))
        if ep is None:
            eps = np.arange(len(w.ep_app)) if ingress else w.egress_eps
            ep = eps[rng.integers(0, len(eps), n)].astype(np.int64)
        else:
            ep = np.full(n, int(ep), np.int64)
        u = rng.random(n)
        allowed = u < a_share
        local = ~allowed & (u >= 1 - ws - ls) & (u < 1 - ws)
        world = ~allowed & (u >= 1 - ws)
        dport = np.asarray(W.PORTS, np.int64)[rng.integers(0, len(W.PORTS), n)]
        peer_app = self._zipf_apps(n).astype(np.int64)

        if ingress and self._l7_ep is not None and l7 > 0:
            ep[allowed & (rng.random(n) < l7)] = self._l7_ep
        off, o_peer, o_port, _o_proto = self._opts[ingress]
        cnt = off[ep + 1] - off[ep]
        has = allowed & (cnt > 0)
        if len(o_peer):
            pick = off[ep] + (rng.random(n) * np.maximum(cnt, 1)).astype(np.int64)
            pick = np.where(has, pick, 0)
            peer_app = np.where(has, o_peer[pick], peer_app)
            dport = np.where(has & (o_port[pick] >= 0), o_port[pick], dport)
        proto = np.where(dport == 53, UDP, TCP)
        pod = self._pods_of(np.maximum(peer_app, 0))

        lidx = rng.integers(0, len(w.ep_app), n)
        peer_app = np.where(local, w.ep_app[lidx], peer_app)
        peer_app = np.where(world, W.WORLD_APP, peer_app)
        sport = rng.integers(32768, 61000, n)
        if family == 4:
            peer = w.pod_ip4[pod].astype(np.int64)
            peer = np.where(local, w.ep_ip4[lidx].astype(np.int64), peer)
            listed = world & (rng.random(n) < float(t.get("prefilter_share_of_world", 0.5)))
            if len(self._pf_nets):
                k = rng.integers(0, len(self._pf_nets), n)
                plen = self._pf_lens[k]
                host = (rng.random(n) * (2.0 ** (32 - plen))).astype(np.int64)
                peer = np.where(listed, self._pf_nets[k] | host, peer)
            else:
                listed[:] = False
            peer = np.where(world & ~listed, rng.integers(64 << 24, 224 << 24, n), peer)
            peer = peer.astype(np.uint32)
        else:
            peer = W.v6_bytes(W.POD_V6, pod + 1).astype(np.int32)
            peer[local] = W.v6_bytes(W.EP_V6, lidx[local] + 2)
            peer[world] = W.v6_bytes(W.WORLD_V6, rng.integers(1, 1 << 31, int(world.sum())))
        return FlowBatch(family, ingress, peer, ep.astype(np.int32), dport.astype(np.int32),
                         proto.astype(np.int32), sport.astype(np.int32),
                         peer_app.astype(np.int32))


def submit(pipe, fb: FlowBatch):
    """A flow batch down ``pipeline.submit()`` / ``submit_v6()`` with its
    source ports, so the conntrack pre-pass runs: a ``PendingBatch``."""
    if fb.family == 4:
        return pipe.submit(fb.peer, fb.ep, fb.dport, fb.proto, ingress=fb.ingress,
                           sports=fb.sport)
    return pipe.submit_v6(fb.peer, fb.ep, fb.dport, fb.proto, ingress=fb.ingress,
                          sports=fb.sport)


_FIELDS = ("peer", "ep", "dport", "proto", "sport", "peer_app")


def copy_rows(dst: FlowBatch, rows: np.ndarray, src: FlowBatch, idx: np.ndarray) -> None:
    for f in _FIELDS:
        getattr(dst, f)[rows] = getattr(src, f)[idx]


def wrong(got: np.ndarray, want: np.ndarray) -> int:
    """Answers that differ; every one of them when the shapes do."""
    if got.shape != want.shape:
        return len(want)
    return int(np.sum(got != want))


def flow_mismatches(got, want) -> int:
    """Flows whose verdict or redirect bit differs from the reference's."""
    return max(wrong(np.asarray(got[0]), want[0]), wrong(np.asarray(got[1]), want[1]))


def kinds_of(t: dict) -> List[int]:
    return sorted(set(int(k) for k in cycle_kinds(t)))
