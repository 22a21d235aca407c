"""L3/L4 flow batches through ``daemon.pipeline.submit()`` /
``submit_v6()`` with source ports, so the conntrack pre-pass runs,
at the pipeline's default depth; answers are read with
``PendingBatch.result()``.

A kind module defines ``Kind``: the harness (``benchmark/run.py``)
draws its batches before the daemon boots, hands it the daemon, sends
each batch down the program's entry with ``send``, and compares each
answer with the plain reference by ``compare``."""

from __future__ import annotations

import numpy as np

from benchmark import traffic as T


class Kind:
    rate_metric = "flow_verdicts_per_s"      # items whose answers came back, per second
    tail_metric = "flow_batch_p95_ms"        # printed, not judged
    numbers = ("mismatched_verdicts",)       # compared, each with the limit 0
    daemon = {"l7_device_batch": False}
    tracing = False

    def __init__(self, w, t: dict, seed: int) -> None:
        self.w, self.t = w, t
        self.rng = np.random.default_rng([seed, 7])
        self.src = T.FlowSource(w, t, self.rng)
        self._last = {}

    # -- drawn before the daemon boots --------------------------------------
    def batch(self, size: int, k: int) -> T.FlowBatch:
        """``size`` flows of (family, direction) ``KINDS[k]``; a
        ``repeat_share`` of them repeat flows of the last such batch."""
        fam, ing = T.KINDS[k]
        fb = self.src.flows(size, fam, ing)
        rep = float(self.t.get("repeat_share", 0.0))
        last = self._last.get(k)
        if rep > 0 and last is not None:
            m = int(round(size * rep))
            rows = self.rng.choice(size, m, replace=False)
            T.copy_rows(fb, rows, last, self.rng.integers(0, len(last), m))
        self._last[k] = fb
        return fb

    def warm_batches(self) -> list:
        """One batch at each rung of the dispatch ladder for each
        (family, direction) the mix sends: every shape the window uses."""
        from cilium_tpu.contracts import BUCKET_LADDER

        return [self.src.flows(rung, *T.KINDS[k])
                for k in T.kinds_of(self.t) for rung in BUCKET_LADDER]

    # -- the program ----------------------------------------------------------
    def attach(self, d) -> None:
        self.pipe = d.pipeline

    def send(self, fb: T.FlowBatch):
        with T.span(self.tracing, "bench.submit"):
            return T.submit(self.pipe, fb)

    def degraded(self, out) -> bool:
        return bool((out[0] == T.DROP_DEGRADED).any())

    # -- the check -------------------------------------------------------------
    def answer(self, ref, fb: T.FlowBatch):
        return ref.verdicts(fb)

    def compare(self, ref, fb: T.FlowBatch, out) -> dict:
        return {"mismatched_verdicts": T.flow_mismatches(out, self.answer(ref, fb)),
                "_flows_checked": len(fb)}
