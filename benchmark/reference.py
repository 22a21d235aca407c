"""The plain reference: what the configuration's policy says, computed
from the generator's own tables and nothing the program made.

It shares no code with the program: the rules are the (subject app,
peer app, port, protocol) tuples the world wrote as Cilium JSON, the
addresses are the ones the generator put in each flow, the prefilter
is plain prefix-set membership, and HTTP is host ``re.fullmatch``.

The semantics are Cilium's for the rule shapes the worlds use
(``chip_smoke.Reference`` agreed with the device on them, PR 21):

- ingress: an endpoint some rule selects admits a peer whose app a
  selecting rule names, on the rule's port and protocol or, for a rule
  without ``toPorts``, on any; every local endpoint is selected by an
  ingress rule, so all other ingress is dropped;
- egress: the same, for the endpoints an egress rule selects (the only
  ones the traffic sends egress flows from);
- the prefilter drops v4 ingress from a listed prefix before policy;
- a forwarded flow is redirected to the proxy when the rule that
  admits it on that port carries HTTP rules;
- conntrack never changes a verdict here: an entry is made only for a
  flow the policy forwards without a redirect, and the policy does not
  change during a run, so an established flow's verdict is the one the
  policy gives it.

``CONTROLS`` are this reference with one guarantee broken, put in the
program's place to show the comparison fails them (benchmark/tests).
"""

from __future__ import annotations

import re
from typing import Dict, Sequence, Tuple

import numpy as np

FORWARD, DROP_POLICY, DROP_PREFILTER = 1, 2, 3
_PORT_BITS = 24


class Reference:
    def __init__(self, w, *, port_blind: bool = False, prefix_match: bool = False) -> None:
        self.w = w
        self._stride = w.n_apps + 1
        any_port, with_port, redirect = [], [], []
        for table, direction in ((w.ingress, 0), (w.egress, 1)):
            for (s, p, port, proto), l7_set in table.items():
                key = self._pair(np.int64(s), np.int64(p), direction)
                if port < 0 or port_blind:
                    any_port.append(key)
                else:
                    k2 = (key << _PORT_BITS) | (port << 8) | proto
                    with_port.append(k2)
                    if l7_set >= 0:
                        redirect.append(k2)
        self._any = np.unique(np.array(any_port, np.int64))
        self._port = np.unique(np.array(with_port, np.int64))
        self._redirect = np.unique(np.array(redirect, np.int64))
        self._pf = {plen: np.asarray(nets, np.int64) for plen, nets in w.prefixes.items()}
        self._match = "match" if prefix_match else "fullmatch"
        self._http: Dict[int, list] = {}

    def _pair(self, subj, peer, direction):
        return ((subj * self._stride + (peer + 1)) << 1) | direction

    def in_prefilter(self, addrs: np.ndarray) -> np.ndarray:
        a = np.asarray(addrs).astype(np.int64)
        hit = np.zeros(a.shape[0], bool)
        for plen, nets in self._pf.items():
            mask = (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF
            hit |= np.isin(a & mask, nets)
        return hit

    def verdicts(self, fb) -> Tuple[np.ndarray, np.ndarray]:
        """(verdict [B] int8, redirect [B] bool) for a FlowBatch."""
        subj = self.w.ep_app[fb.ep].astype(np.int64)
        key = self._pair(subj, fb.peer_app.astype(np.int64), 0 if fb.ingress else 1)
        k2 = (key << _PORT_BITS) | (fb.dport.astype(np.int64) << 8) | fb.proto.astype(np.int64)
        world = fb.peer_app < 0
        allow = (np.isin(key, self._any) | np.isin(k2, self._port)) & ~world
        v = np.where(allow, FORWARD, DROP_POLICY).astype(np.int8)
        red = allow & np.isin(k2, self._redirect)
        if fb.family == 4 and fb.ingress and self._pf:
            pf = self.in_prefilter(fb.peer)
            v[pf] = DROP_PREFILTER
            red &= ~pf
        return v, red

    def http_allows(self, rule_set: int, methods: Sequence[str],
                    paths: Sequence[str]) -> np.ndarray:
        """Allow per request for a caller the L3/L4 rule admits."""
        if rule_set not in self._http:
            self._http[rule_set] = [(re.compile(r["method"]), re.compile(r["path"]))
                                    for r in self.w.http_sets[rule_set]]
        rules = self._http[rule_set]
        m = self._match
        return np.array([any(getattr(mr, m)(meth) and getattr(pr, m)(path)
                             for mr, pr in rules)
                         for meth, path in zip(methods, paths)], bool)


# The guarantee each control breaks. Each is the step a later PR could
# be tempted by: a policymap keyed on identity alone, and a path regex
# matched at its start only.
CONTROLS = {
    "port_blind": {"port_blind": True},
    "prefix_match": {"prefix_match": True},
}
