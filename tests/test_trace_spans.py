"""Program spans on the profiler's clock, and the always-on conntrack and
collector counters.

- every tracer span is mirrored as a ``policyd.<kind>.<phase>``
  profiler annotation, each pipelined batch gets ``.enqueue`` and
  ``.complete`` halves, and nothing is built while tracing is off;
- traces carry ``id``/``parent``: a proxy HTTP batch is the parent of
  the L7 walk it submits;
- the ``proxy-http`` phases, and the verdict and L7 traces' phase sets
  left as they were;
- conntrack probe/insert counters exact against a reference walk on a
  small table with a full neighbourhood planted, and the entries gauge;
- the collector hook;
- the device programs' named scopes;
- policyd-lint on the touched modules.
"""

from __future__ import annotations

import gc
import glob
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cilium_tpu import contracts, metrics
from cilium_tpu.analysis import analyze_paths
from cilium_tpu.datapath import l7_pipeline as l7rt
from cilium_tpu.datapath.conntrack import (
    _EMPTY,
    FlowConntrack,
    flip_kc,
    pack_keys,
)
from cilium_tpu.datapath.pipeline import DatapathPipeline, process_flows_wide
from cilium_tpu.engine import PolicyEngine
from cilium_tpu.identity import IdentityRegistry
from cilium_tpu.ipcache.ipcache import IPCache
from cilium_tpu.ipcache.prefilter import PreFilter
from cilium_tpu.l7 import HTTPPolicy, HTTPRequest
from cilium_tpu.labels import parse_label_array
from cilium_tpu.observe import Tracer, gcwatch
from cilium_tpu.observe import tracer as tracer_mod
from cilium_tpu.ops import dfa as dfa_mod
from cilium_tpu.ops.dfa import dfa_match_batch_pair
from cilium_tpu.ops.lpm import ip_strings_to_u32
from cilium_tpu.policy.api import (
    EndpointSelector,
    HTTPRule,
    IngressRule,
    PortProtocol,
    PortRule,
    rule,
)
from cilium_tpu.policy.repository import Repository
from cilium_tpu.proxy.proxy import Proxy

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "cilium_tpu")

# the phase sets the verdict and L7 traces had before proxy-http
VERDICT_PHASES = {"rebuild", "prepare", "lb_translate", "ct_prepass",
                  "dispatch", "host_sync", "ct_create", "counters",
                  "emit_events"}
L7_PHASES = {"prepare", "dispatch", "host_sync"}
PROXY_PHASES = ["encode", "overlong", "rule_match", "access_log"]


def _pipeline(conntrack=None):
    repo = Repository()
    repo.add_list([
        rule(
            ["k8s:app=web"],
            ingress=[IngressRule(
                from_endpoints=(EndpointSelector.make(["k8s:app=lb"]),),
                to_ports=(PortRule(ports=(PortProtocol(80, "TCP"),)),),
            )],
        ),
    ])
    reg = IdentityRegistry()
    web = reg.allocate(parse_label_array(["k8s:app=web"]))
    lb = reg.allocate(parse_label_array(["k8s:app=lb"]))
    cache = IPCache()
    cache.upsert("10.0.0.2/32", lb.id, source="k8s")
    pipe = DatapathPipeline(
        PolicyEngine(repo, reg), cache, PreFilter(), conntrack=conntrack
    )
    pipe.set_endpoints([(7, web.id)])
    return pipe


def _batch(n=8, sport0=40000):
    return (
        ip_strings_to_u32(["10.0.0.2"] * n),
        np.zeros(n, np.int32),
        np.full(n, 80),
        np.full(n, 6),
    ), np.arange(sport0, sport0 + n)


class _Recorder:
    """Stands in for ``tracer.annotation``: records enter/exit."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **meta):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.events.append(("enter", name, meta))
                return self

            def __exit__(self, *exc):
                rec.events.append(("exit", name, meta))
                return False

        return _Ann()

    def names(self):
        return [n for kind, n, _ in self.events if kind == "enter"]


@pytest.fixture
def recorder(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(tracer_mod, "annotation", rec)
    return rec


@pytest.fixture
def l7_runtime():
    """The L7 runtime gate and the DFA intern cache are process-global."""
    l7rt._reset_for_tests()
    dfa_mod._reset_intern_for_tests()
    yield
    l7rt._reset_for_tests()
    dfa_mod._reset_intern_for_tests()


_HTTP_RULES = [
    (HTTPRule(method="GET", path="/api/v[0-9]+/.*"), None),
    (HTTPRule(method="POST", path="/upload"), {17}),
]


def _requests(n=40):
    return [HTTPRequest("GET" if i % 3 else "POST",
                        f"/api/v{i % 4}/x{i}" if i % 2 else "/upload",
                        src_identity=17 if i % 5 else 99)
            for i in range(n)]


def _proxy(tr):
    l7rt.set_device_batch(True, tracer=tr)
    pol = HTTPPolicy(_HTTP_RULES)
    p = Proxy(tracer=tr)
    return p, p.create_or_update_redirect(1, 80, "http", http_policy=pol)


# ---------------------------------------------------------- annotations


class TestAnnotationMirror:
    def test_off_builds_no_annotation(self, monkeypatch, l7_runtime):
        def _boom(*a, **k):
            raise AssertionError("annotation built while tracing is off")

        monkeypatch.setattr(tracer_mod, "annotation", _boom)
        pipe = _pipeline(FlowConntrack(capacity_bits=8))
        args, sports = _batch()
        v, _ = pipe.process(*args, sports=sports)
        assert (v == 1).all()
        p, r = _proxy(pipe.tracer)
        assert len(p.check_http(r, _requests())) == 40
        pipe.conntrack.tracer = pipe.tracer
        pipe.conntrack.gc()
        assert pipe.tracer.traces() == []

    def test_on_emits_phases_and_halves(self, recorder):
        pipe = _pipeline(FlowConntrack(capacity_bits=8))
        pipe.tracer.enable()
        args, sports = _batch()
        pipe.process(*args, sports=sports)
        (t,) = pipe.tracer.traces()
        names = recorder.names()
        assert names[0] == "policyd.v4-ingress.enqueue"
        assert "policyd.v4-ingress.complete" in names
        for phase, _off, _dur in t["phases"]:
            assert f"policyd.v4-ingress.{phase}" in names
        # every annotation of the batch names the trace it belongs to
        assert {m["id"] for _, _, m in recorder.events} == {t["id"]}
        # balanced, and each half closes after the phases inside it
        assert len(recorder.events) == 2 * len(names)
        assert recorder.events[-1][:2] == ("exit", "policyd.v4-ingress.complete")

    def test_halves_hold_their_phases(self):
        pipe = _pipeline(FlowConntrack(capacity_bits=8))
        pipe.tracer.enable()
        args, sports = _batch(64)
        pipe.submit(*args, sports=sports).result()
        (t,) = pipe.tracer.traces()
        notes = t["notes"]
        dur = {n: d for n, _, d in t["phases"]}
        enq = sum(dur[p] for p in ("rebuild", "prepare", "ct_prepass", "dispatch"))
        done = sum(d for n, d in dur.items()
                   if n in ("host_sync", "ct_create", "counters", "emit_events"))
        assert notes["enqueue_ns"] >= enq > 0
        assert notes["complete_ns"] >= done > 0
        assert notes["enqueue_ns"] + notes["complete_ns"] <= t["total_ns"]

    def test_annotations_reach_the_profiler_trace(self, tmp_path):
        from jax.profiler import ProfileData

        pipe = _pipeline()
        pipe.tracer.enable()
        args, _ = _batch()
        pipe.process(*args)   # compile outside the recorded window
        jax.profiler.start_trace(str(tmp_path))
        try:
            pipe.process(*args)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        names = {ev.name for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for ev in line.events}
        for want in ("policyd.v4-ingress.enqueue", "policyd.v4-ingress.dispatch",
                     "policyd.v4-ingress.host_sync", "policyd.v4-ingress.complete"):
            assert want in names

    def test_ct_gc_span_only_while_tracing(self, recorder):
        tr = Tracer()
        ct = FlowConntrack(capacity_bits=4)
        ct.tracer = tr
        ct.gc()
        assert recorder.names() == []
        tr.enable()
        ct.gc()
        assert recorder.names() == ["policyd.ct.gc"]


# ------------------------------------------------------- ids and parents


class TestTraceIds:
    def test_proxy_trace_is_parent_of_its_l7_walk(self, l7_runtime):
        tr = Tracer()
        p, r = _proxy(tr)
        tr.enable()
        p.check_http(r, _requests())
        by_kind = {t["kind"]: t for t in tr.traces()}
        proxy, l7 = by_kind["proxy-http"], by_kind["l7"]
        assert proxy["parent"] is None
        assert l7["parent"] == proxy["id"] != l7["id"]
        # nothing left open on the thread
        assert tracer_mod.current("proxy-http") is tracer_mod.NOOP_BATCH

    def test_batch_histogram_counts_the_l7_walk_once(self, l7_runtime):
        tr = Tracer()
        p, r = _proxy(tr)
        tr.enable()
        n0 = metrics.batch_total_seconds.get_count()
        p.check_http(r, _requests())
        assert {t["kind"] for t in tr.traces()} == {"proxy-http", "l7"}
        assert metrics.batch_total_seconds.get_count() == n0 + 1

    def test_ids_unique_and_no_parent_across_batches(self):
        pipe = _pipeline()
        pipe.tracer.enable()
        args, _ = _batch()
        for _ in range(3):
            pipe.process(*args)
        ts = pipe.tracer.traces()
        assert len({t["id"] for t in ts}) == 3
        assert all(t["parent"] is None for t in ts)

    def test_current_is_per_tracer(self):
        a, b = Tracer(), Tracer()
        bt = a.begin("proxy-http", 1)
        try:
            assert a.current() is bt
            assert b.current() is tracer_mod.NOOP_BATCH
            assert tracer_mod.current("proxy-http") is bt
            assert tracer_mod.current("l7") is tracer_mod.NOOP_BATCH
            child = b.begin("l7", 1)
            assert child.parent == bt.id
            child.end()
        finally:
            bt.end()


# ------------------------------------------------------------ phase sets


class TestPhaseSets:
    def test_proxy_http_phases_in_order(self, l7_runtime):
        tr = Tracer()
        p, r = _proxy(tr)
        tr.enable()
        p.check_http(r, _requests())
        (proxy,) = [t for t in tr.traces() if t["kind"] == "proxy-http"]
        ph = sorted(proxy["phases"], key=lambda x: x[1])
        assert [n for n, _, _ in ph] == PROXY_PHASES
        assert proxy["batch"] == 40

    def test_verdict_and_l7_traces_keep_their_phases(self, l7_runtime):
        pipe = _pipeline(FlowConntrack(capacity_bits=8))
        pipe.tracer.enable()
        args, sports = _batch()
        pipe.process(*args, sports=sports)
        pipe.process(*args, sports=sports)      # CT hits
        pipe.process_v6(np.tile(np.arange(16, dtype=np.int32), (4, 1)),
                        np.zeros(4, np.int32), np.full(4, 80), np.full(4, 6),
                        sports=np.arange(4))
        p, r = _proxy(pipe.tracer)
        p.check_http(r, _requests())
        traces = pipe.tracer.traces()
        kinds = {t["kind"] for t in traces}
        assert {"v4-ingress", "v6-ingress", "l7", "proxy-http"} <= kinds
        for t in traces:
            names = {n for n, _, _ in t["phases"]}
            if t["kind"].startswith(("v4-", "v6-")):
                assert names <= VERDICT_PHASES, names
            elif t["kind"] == "l7":
                assert names == L7_PHASES
            else:
                assert names == set(PROXY_PHASES)

    def test_no_proxy_trace_without_a_tracer(self, l7_runtime):
        """A proxy built without the daemon's tracer opens no trace: the
        L7 walk's own trace stays a root."""
        tr = Tracer()
        l7rt.set_device_batch(True, tracer=tr)
        p = Proxy()
        r = p.create_or_update_redirect(1, 80, "http",
                                        http_policy=HTTPPolicy(_HTTP_RULES))
        tr.enable()
        allows = p.check_http(r, _requests())
        assert list(allows[:4]) == [False, True, False, False]
        (l7,) = tr.traces()
        assert l7["kind"] == "l7" and l7["parent"] is None


# ------------------------------------------------------------- conntrack


def _key(kb: int, sport: int = 1000, proto: int = 17):
    return pack_keys(
        np.zeros(1, np.uint64), np.array([kb], np.uint64),
        np.zeros(1, np.uint64), np.array([sport], np.uint64),
        np.array([53], np.uint64), np.array([proto], np.uint64),
        np.zeros(1, np.uint64),
    )


def _cat(keys):
    return tuple(np.concatenate([k[i] for k in keys]) for i in range(3))


def _home(ct, key) -> int:
    return int(ct._hash(*key)[0] & ct.mask)


def _ref_probes(ct, key) -> int:
    """Slots a search for ``key`` probes: its chain ends at an empty
    slot, a live match, or the probe cap."""
    ka, kb, kc = (int(x[0]) for x in key)
    h, now = _home(ct, key), time.monotonic()
    for p in range(ct.probes):
        s = (h + p) & int(ct.mask)
        if ct.ka[s] == _EMPTY:
            return p + 1
        if (int(ct.ka[s]), int(ct.kb[s]), int(ct.kc[s])) == (ka, kb, kc) \
                and ct.valid[s] and ct.expires[s] > now:
            return p + 1
    return ct.probes


def _delta(before, family, **labels):
    return getattr(metrics, family).get(labels) - before[(family, tuple(labels.items()))]


def _snap(*series):
    return {(f, tuple(l.items())): getattr(metrics, f).get(l) for f, l in series}


CT_SERIES = [("ct_lookups_total", {"op": "lookup"}),
             ("ct_probe_rounds_total", {"op": "lookup"}),
             ("ct_lookups_total", {"op": "create"}),
             ("ct_probe_rounds_total", {"op": "create"}),
             ("ct_inserts_total", {"result": "inserted"}),
             ("ct_inserts_total", {"result": "dropped"})]


@pytest.fixture
def full_neighbourhood():
    """A 16-slot table with 4 probes; the 4 slots of key K's probe
    chain hold 4 other live keys (inserted through the API)."""
    ct = FlowConntrack(capacity_bits=4, probes=4)
    k = _key(1)
    h = _home(ct, k)
    planted = [_key(kb) for kb in range(2, 4000) if _home(ct, _key(kb)) == h][:4]
    assert len(planted) == 4
    for key in planted:
        assert ct.create_batch(*key) == 1
    chain = {(h + p) & int(ct.mask) for p in range(4)}
    assert all(ct.valid[s] for s in chain)
    free = next(_key(kb) for kb in range(4000, 8000)
                if _home(ct, _key(kb)) not in chain
                and (_home(ct, _key(kb)) + 1) & int(ct.mask) not in chain)
    return ct, k, free


class TestConntrackCounters:
    def test_probe_rounds_exact(self, full_neighbourhood):
        ct, k, free = full_neighbourhood
        keys = _cat([k, free])
        reply = (keys[0], keys[1], flip_kc(keys[2]))
        want = _ref_probes(ct, k) + _ref_probes(ct, free)
        want_reply = sum(_ref_probes(ct, tuple(x[i:i + 1] for x in reply))
                         for i in range(2))
        assert _ref_probes(ct, k) == 4     # the whole chain, no empty slot
        before = _snap(*CT_SERIES)
        state, _ = ct.lookup_batch(*keys)
        assert (state == 0).all()          # both new: forward and reply searched
        assert _delta(before, "ct_lookups_total", op="lookup") == 4
        assert _delta(before, "ct_probe_rounds_total", op="lookup") == want + want_reply

    def test_insert_drop_exact(self, full_neighbourhood):
        ct, k, free = full_neighbourhood
        probes = _ref_probes(ct, k) + _ref_probes(ct, free)
        before = _snap(*CT_SERIES)
        # duplicates are deduped before counting; K has no free slot
        assert ct.create_batch(*_cat([k, free, free])) == 1
        assert _delta(before, "ct_inserts_total", result="inserted") == 1
        assert _delta(before, "ct_inserts_total", result="dropped") == 1
        assert _delta(before, "ct_lookups_total", op="create") == 2
        assert _delta(before, "ct_probe_rounds_total", op="create") == probes
        # an established key is neither inserted nor dropped
        before = _snap(*CT_SERIES)
        assert ct.create_batch(*free) == 0
        assert _delta(before, "ct_inserts_total", result="inserted") == 0
        assert _delta(before, "ct_inserts_total", result="dropped") == 0

    def test_entries_gauge_follows_inserts_gc_and_flush(self):
        ct = FlowConntrack(capacity_bits=6, other_lifetime=0.05,
                           tcp_lifetime=3600.0)
        udp = _cat([_key(kb) for kb in range(10)])
        tcp = _cat([_key(kb, proto=6) for kb in range(10, 13)])
        ct.create_batch(*udp)
        ct.create_batch(*tcp)
        assert metrics.ct_entries.get() == 13 == len(ct)
        time.sleep(0.06)
        # expired entries stay counted until GC reaps them
        assert metrics.ct_entries.get() == 13 and len(ct) == 3
        assert ct.gc() == 10
        assert metrics.ct_entries.get() == 3
        # reusing a reaped slot adds it back; reusing an expired one
        # in place does not double-count
        ct.create_batch(*_cat([_key(kb) for kb in range(20, 25)]))
        assert metrics.ct_entries.get() == 8 == len(ct)
        ct.flush()
        assert metrics.ct_entries.get() == 0


# ------------------------------------------------------------ collector


class TestGCHook:
    def test_hook_counts_a_collection(self):
        gcwatch.install(None)
        g2 = {"generation": "2"}
        c0 = metrics.gc_collections_total.get(g2)
        p0 = metrics.gc_pause_seconds_total.get(g2)
        gc.collect()
        assert metrics.gc_collections_total.get(g2) >= c0 + 1
        assert metrics.gc_pause_seconds_total.get(g2) > p0
        assert gcwatch._on_gc in gc.callbacks
        gcwatch.install(None)               # idempotent
        assert gc.callbacks.count(gcwatch._on_gc) == 1

    def test_reads_under_constant_collection_do_not_deadlock(self):
        # A collection can start at any allocation, including one inside
        # a counter's locked copy; the hook must take no lock there. Run
        # in a child so a deadlock fails the test instead of hanging it.
        script = (
            "import gc\n"
            "from cilium_tpu import metrics as M\n"
            "from cilium_tpu.observe import gcwatch\n"
            "gcwatch.install(None)\n"
            "gc.set_threshold(1)\n"
            "for _ in range(300):\n"
            "    M.registry.expose()\n"
            "    for obj in vars(M).values():\n"
            "        if isinstance(obj, M.Counter):\n"
            "            obj.series()\n"
            "gc.set_threshold(700)\n"
            "print(int(sum(M.gc_collections_total.series().values())))\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", script], cwd=os.path.dirname(PKG),
            env=env, capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert int(out.stdout.split()[-1]) > 1000

    def test_span_only_while_tracing(self, recorder):
        tr = Tracer()
        gcwatch.install(tr)
        try:
            gc.collect()
            assert "policyd.gc" not in recorder.names()
            tr.enable()
            gc.collect()
            gcs = [e for e in recorder.events if e[1] == "policyd.gc"]
            assert gcs and [k for k, _, _ in gcs[:2]] == ["enter", "exit"]
            assert len(gcs) % 2 == 0
        finally:
            gcwatch.release(tr)
        assert gcwatch._tracer is None


# ---------------------------------------------------------- named scopes


class TestNamedScopes:
    """Scope names ride the programs' debug locations into each op's
    ``op_name`` metadata, which the profiler reports as ``tf_op``."""

    def test_verdict_program_stages(self):
        pipe = _pipeline()
        args, _ = _batch()
        pipe.process(*args)
        t = pipe._dp_state[0][(0, 4)]
        n = 1024
        hlo = process_flows_wide.lower(
            t, jnp.zeros(n, jnp.uint32), jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int32),
            ep_count=1, prefilter=True,
        ).as_text(debug_info=True)
        for scope in ("lpm_v4/", "prefilter/", "policymap/", "counters/",
                      "table_flatten/reshape"):
            assert scope in hlo, scope

    def test_dfa_walk_stages(self):
        q = 4
        pair = jnp.zeros((q, dfa_mod.PAIR_ALPHA * dfa_mod.PAIR_ALPHA), jnp.int32)
        acc = jnp.zeros(q, jnp.uint32)
        hlo = dfa_match_batch_pair.lower(
            pair, acc, acc, jnp.zeros(8, jnp.int32),
            jnp.zeros((8, 16), jnp.uint8), jnp.zeros(8, jnp.int32), 16,
        ).as_text(debug_info=True)
        assert "dfa_walk/while" in hlo and "dfa_step/" in hlo
        assert "table_flatten/reshape" in hlo


# ------------------------------------------------------------------ lint


def test_lint_knows_the_new_phases_and_labels():
    assert set(PROXY_PHASES) <= set(contracts.TRACE_PHASES)
    files = [os.path.join(PKG, *p) for p in (
        ("proxy", "proxy.py"), ("l7", "http_policy.py"),
        ("datapath", "conntrack.py"), ("datapath", "l7_pipeline.py"),
        ("observe", "gcwatch.py"), ("observe", "tracer.py"), ("metrics.py",),
    )]
    found = [f for f in analyze_paths(files)
             if f.rule in ("API001", "OBS001", "OBS002")]
    assert found == [], [f.render() for f in found]
