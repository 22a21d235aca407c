"""HTTP request batches through the proxy redirect:
``daemon.proxy.check_http(redirect, requests)`` with ``L7DeviceBatch``
on, so requests go through ``HTTPPolicy.check_batch``, ``l7_pipeline``
and the fused device DFA. Each batch's new connections first go
through ``pipeline.submit()`` and must come back as redirects."""

from __future__ import annotations

import re
from typing import List

import numpy as np

from benchmark import traffic as T


class HttpBatch:
    def __init__(self, ep: int, conns: T.FlowBatch, rule_set: int, idx: np.ndarray,
                 caller_app: np.ndarray) -> None:
        self.ep = ep                  # local endpoint index the redirect belongs to
        self.conns = conns            # first flow of each new connection
        self.rule_set = rule_set      # the endpoint's HTTP rule set
        self.idx = idx                # [R] rows of that set's request bank
        self.caller_app = caller_app  # [R] app index of each request's caller

    def __len__(self) -> int:
        return len(self.idx)


class Kind:
    rate_metric = "http_requests_per_s"
    tail_metric = "http_batch_p95_ms"
    numbers = ("mismatched_verdicts", "mismatched_http")
    daemon = {"l7_device_batch": True}
    tracing = False

    def __init__(self, w, t: dict, seed: int) -> None:
        self.w, self.t = w, t
        self.rng = np.random.default_rng([seed, 7])
        self.src = T.FlowSource(w, t, self.rng)
        self._banks = {}
        self._want = {}
        self._ep_cdf = T.zipf_cdf(len(w.ep_app), float(t.get("endpoint_zipf_s", 1.1)))
        self._ep_perm = self.rng.permutation(len(w.ep_app))

    # -- drawn before the daemon boots --------------------------------------
    def bank(self, rule_set: int):
        """(methods, paths) of ``bank_size`` requests aimed at one rule
        set, drawn once from the seed; batches draw rows from it."""
        if rule_set not in self._banks:
            t, rng = self.t, self.rng
            n = int(t["bank_size"])
            names = list(t["methods"])
            mi = rng.choice(len(names), n, p=np.array([t["methods"][m] for m in names]))
            sig = float(t["path_len_sigma"])
            lens = np.clip(np.round(t["path_len_median"] * np.exp(sig * rng.standard_normal(n))),
                           t["path_len_min"], t["path_len_max"]).astype(int)
            allow = rng.random(n) < float(t["allowed_share"])
            rules = self.w.http_sets[rule_set]
            methods = [names[i] for i in mi]
            paths = [_path(rules, m, int(L), bool(a), rng)
                     for m, L, a in zip(methods, lens, allow)]
            self._banks[rule_set] = (methods, paths)
        return self._banks[rule_set]

    def batch(self, n: int, k: int) -> HttpBatch:
        """n requests to one local endpoint (Zipf over endpoints), on
        ceil(n / requests_per_connection) new connections from callers
        the endpoint's L3/L4 rule admits."""
        w, rng = self.w, self.rng
        ep = int(self._ep_perm[min(np.searchsorted(self._ep_cdf, rng.random(), side="right"),
                                   len(self._ep_perm) - 1)])
        per = int(self.t["requests_per_connection"])
        conns = self.src.flows(-(-n // per), 4, True, allowed_share=1.0, l7_share=0.0, ep=ep)
        caller = conns.peer_app[np.arange(n) // per]
        rule_set = w.ingress[(int(w.ep_app[ep]), int(caller[0]), w.l7_port, T.TCP)]
        self.bank(rule_set)
        idx = rng.integers(0, int(self.t["bank_size"]), n)
        return HttpBatch(ep, conns, rule_set, idx, caller.astype(np.int32))

    def warm_batches(self) -> list:
        """None: the L7 walk's shapes are compiled by the program at
        ``policy_add`` (``L7Pipeline.prewarm``), and the connections'
        one shape by the warm-up stretch of traffic."""
        return []

    # -- the program ----------------------------------------------------------
    def attach(self, d) -> None:
        from cilium_tpu.labels import parse_label_array

        self.d, self.pipe = d, d.pipeline
        self.caller_ids = {}
        for a in range(self.w.n_apps):
            ident = d.registry.lookup_by_labels(parse_label_array(list(self.w.app_labels[a])))
            if ident:
                self.caller_ids[a] = ident.id
        self.redirects = {e: d.proxy.lookup(e + 1, self.w.l7_port, ingress=True)
                          for e in range(len(self.w.ep_app))}
        if any(r is None or r.http_policy is None for r in self.redirects.values()):
            raise RuntimeError("an L7 endpoint has no HTTP redirect")

    def send(self, hb: HttpBatch):
        """Synchronous: the connections' verdicts, then the requests'."""
        from cilium_tpu import metrics as M
        from cilium_tpu.l7.http_policy import HTTPRequest

        on = self.tracing
        before = M.l7_batches_total.get({"parser": "http"})
        with T.span(on, "bench.submit"):
            p = T.submit(self.pipe, hb.conns)
        with T.span(on, "bench.result"):
            conn = p.result()
        with T.span(on, "bench.generator"):
            ids = self.caller_ids
            methods, paths = self.bank(hb.rule_set)
            reqs = [HTTPRequest(method=methods[j], path=paths[j], host="svc.local",
                                src_identity=ids[int(c)])
                    for j, c in zip(hb.idx.tolist(), hb.caller_app)]
        with T.span(on, "bench.check_http"):
            allows = self.d.proxy.check_http(self.redirects[hb.ep], reqs)
        walked = M.l7_batches_total.get({"parser": "http"}) != before
        return T.Done((conn, np.asarray(allows, bool), walked))

    def degraded(self, out) -> bool:
        """A connection resolved degraded, or requests the L7 pipeline
        never saw (answered by a host path around it)."""
        return bool((out[0][0] == T.DROP_DEGRADED).any()) or not out[2]

    # -- the check -------------------------------------------------------------
    def answer(self, ref, hb: HttpBatch):
        """The connections come from callers the rule admits on the L7
        port: the reference forwards and redirects every one. The
        reference answers each row of a rule set's bank once."""
        key = (id(ref), hb.rule_set)
        if key not in self._want:
            self._want[key] = ref.http_allows(hb.rule_set, *self.bank(hb.rule_set))
        return ref.verdicts(hb.conns), self._want[key][hb.idx], True

    def compare(self, ref, hb: HttpBatch, out) -> dict:
        want_conn, want_allows, _ = self.answer(ref, hb)
        return {"mismatched_verdicts": T.flow_mismatches(out[0], want_conn),
                "mismatched_http": T.wrong(np.asarray(out[1], bool), want_allows),
                "_flows_checked": len(hb.conns), "_http_checked": len(hb)}


_ALNUM = "abcdefghijklmnopqrstuvwxyz0123456789"


def _alphabet(pattern: str) -> str:
    """Characters the pattern's last bracket class admits (``.*``:
    letters and digits)."""
    m = re.findall(r"\[([^\]]+)\]", pattern)
    if not m or pattern.endswith(".*"):
        return _ALNUM
    cls, out, i = m[-1], [], 0
    while i < len(cls):
        if i + 2 < len(cls) and cls[i + 1] == "-":
            out.extend(chr(c) for c in range(ord(cls[i]), ord(cls[i + 2]) + 1))
            i += 3
        else:
            out.append(cls[i])
            i += 1
    return "".join(out)


def _fill(prefix: str, length: int, chars: str, rng) -> str:
    k = max(1, length - len(prefix))
    return prefix + "".join(np.array(list(chars))[rng.integers(0, len(chars), k)])


def _path(rules: List[dict], method: str, length: int, allow: bool, rng) -> str:
    """A path of about ``length`` bytes. Aimed at a rule whose method
    admits ``method`` when ``allow``; otherwise one that a rule's
    prefix starts but its pattern does not admit (a prefix match, not a
    full match, would allow it), or an unlisted path. The reference
    decides what it is; the aim only sets the mix."""
    fits = [r for r in rules if re.fullmatch(r["method"], method)]
    if allow and fits:
        longer = [r for r in fits if r["gen"].endswith("/") and len(r["gen"]) < length]
        pool = longer or fits
        r = pool[int(rng.integers(len(pool)))]
        if not r["gen"].endswith("/"):
            return r["gen"]                   # a fixed path: /healthz
        return _fill(r["gen"], length, _alphabet(r["path"]), rng)
    r = rules[int(rng.integers(len(rules)))]
    if rng.random() < 0.5:
        return _fill(r["gen"], length - 2, _alphabet(r["path"]), rng) + "/!"
    return _fill("/admin/", length, _ALNUM, rng)
