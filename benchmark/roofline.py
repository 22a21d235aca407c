"""The least bytes the verdict kernels must move for a flow, and so the
least time the chip could take for the flows dispatched in a window.

Per flow that reaches the device (a conntrack miss), the algorithm
must at least read its inputs once (peer address, endpoint, port,
protocol), write its outputs once (verdict, redirect), read one table
word per LPM level it walks, and read one policymap row (the peer
identity's row of allow and redirect bits). Table sizes never count:
a flow touches one path through a trie and one row, not the table.
Padded lanes are not flows and do not count either."""

from __future__ import annotations

from typing import Dict, Optional

WORD = 4


def _levels_v6(shapes: Dict[str, list]) -> int:
    """LPM levels an IPv6 walk takes: 16 less the shared prefix the
    trie elides (compared, not walked)."""
    ks = [v[0] for k, v in shapes.items() if "v6" in k and k.endswith("ip_common")]
    return 16 - (min(ks) if ks else 0)


def row_bytes(shapes: Dict[str, list], family: int) -> int:
    rows = [v[1] * WORD for k, v in shapes.items()
            if f"v{family}" in k and k.endswith("id_bits") and len(v) == 2]
    return min(rows) if rows else WORD


def bytes_per_flow(family: int, shapes: Dict[str, list]) -> int:
    if family == 4:
        inputs = 4 + 4 + 2 + 1          # address, endpoint, port, protocol
        lpm = 2 * WORD                  # the wide trie: a 16-bit root, then one byte level
    else:
        inputs = 16 + 4 + 2 + 1
        lpm = _levels_v6(shapes) * WORD
    outputs = 1 + 1
    return inputs + outputs + lpm + row_bytes(shapes, family)


def floor_seconds(flows_by_family: Dict[int, float], shapes: Dict[str, list],
                  hbm_bytes_per_s: float) -> Optional[float]:
    total = sum(n * bytes_per_flow(f, shapes) for f, n in flows_by_family.items())
    return total / hbm_bytes_per_s if total > 0 else None
