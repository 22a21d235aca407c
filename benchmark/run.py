"""Run one benchmark cell once.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run boots a ``Daemon`` and imports the cell's world through the
daemon's API, warms the shapes its traffic uses, drives the traffic
open-loop for ``--seconds``, checks every answer against the plain
reference (``benchmark/reference.py``), and prints one JSON line last
on stdout. With ``--trace 1`` it records a profiler trace of the
window and reports the cell's per-layer metrics instead of its
end-to-end ones.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmark/configs/``, its
traffic mix in ``benchmark/traffic/`` (a data file, whose ``kind``
names the code that sends it: ``benchmark/kinds/<kind>.py``) and each
per-layer metric's reader in ``benchmark/metrics/<metric>.py``.

``setup_s`` is the node's set-up: process start, daemon boot, the
world's import through the daemon's API, warm-up and compiles. The
harness's own work (the world's data, the reference, drawing every
batch) runs before the daemon boots and is left out; each run prints
it as ``harness_*`` on its ``phase=setup`` line.

Without a TPU (or with fewer chips than the cell asks for) the run
exits 2 and prints no result; ``--cpu-rehearsal`` is the one explicit
exception, and such a result names the CPU as its device. A cell on
more than one chip prints the daemon's placement plan once set-up is
done, and exits 3 with no result unless the plan spans exactly the
cell's chips on the mesh axes its configuration asks for.

Each step of set-up prints a ``setup-step`` line on stderr as it ends
(seconds, seconds since start, compile seconds so far, the process's
peak RSS, each chip's bytes in use), so a run stopped in set-up names
the step it was in.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference as R  # noqa: E402
from benchmark import traffic as T  # noqa: E402
from benchmark import world as W  # noqa: E402
from benchmark.tracefile import WINDOW, find_xplane, reduce_xplane  # noqa: E402

WARM_SECONDS = 2.0       # traffic run before the window, at the cell's rate


class NoResult(SystemExit):
    """The run ends with this code and prints no result line."""


def say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, section: str, cell: str) -> List[dict]:
    return [m for m in bench[section] if "workloads" not in m or cell in m["workloads"]]


# -- device -----------------------------------------------------------------

def device_or_exit(chips: int, rehearsal: bool) -> dict:
    import jax

    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        print(f"benchmark: no TPU (JAX found {devs[0].platform}); no result", file=sys.stderr)
        raise NoResult(2)
    if not rehearsal and len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX sees {len(devs)}; no result",
              file=sys.stderr)
        raise NoResult(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def memory_peaks(ids: List[int]) -> Dict[int, int]:
    """``peak_bytes_in_use`` of each device in ``ids`` (0 where the
    backend keeps no statistics)."""
    import jax

    by_id = {d.id: d for d in jax.devices()}
    return {i: int((by_id[i].memory_stats() or {}).get("peak_bytes_in_use", 0)) for i in ids}


def plan_devices(d, chips: int) -> List[int]:
    """The devices the cell runs on: device 0 for one chip; for more,
    the daemon's placement plan, printed, which has to span exactly
    ``chips`` devices on the axes the daemon's configuration asks for
    (``flows`` x ``ident`` with ``mesh_sharding_2d``, else ``flows``),
    or the run ends with no result."""
    import jax

    from cilium_tpu.option import get_config

    if chips == 1:
        return [jax.devices()[0].id]
    st = d.pipeline.placement_state()
    say(phase="placement", axes=json.dumps(st["axes"], separators=(",", ":")),
        devices=json.dumps(st["devices"], separators=(",", ":")),
        ident_sharded=st["ident_sharded"], generation=st["generation"])
    dcfg = get_config()
    ident = dcfg.mesh_ident_axis if dcfg.mesh_sharding_2d else 1
    want = {"flows": chips // ident, "ident": ident} if ident > 1 else {"flows": chips}
    if len(st["devices"]) != chips or st["axes"] != want:
        print(f"benchmark: the cell asks for {chips} chips on axes {want}; the daemon's plan "
              f"spans devices {st['devices']} on axes {st['axes']}; no result", file=sys.stderr)
        raise NoResult(3)
    return list(st["devices"])


class CompileLog:
    """Backend compile seconds, from JAX's own monitoring events
    (``chip_smoke.CompileLog``)."""

    def __init__(self) -> None:
        import jax

        self.secs = 0.0
        self.count = 0

        def on_event(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def counter_snapshot() -> Dict[str, Dict[tuple, float]]:
    """Every counter and gauge series of the program's registry."""
    from cilium_tpu import metrics as M

    out = {}
    for obj in vars(M).values():
        if isinstance(obj, M.Counter):
            out[obj.name] = obj.series()
    return out


def counter_delta(before, after) -> Dict[str, Dict[tuple, float]]:
    out = {}
    for name, series in after.items():
        b = before.get(name, {})
        out[name] = {k: v - b.get(k, 0.0) for k, v in series.items()}
    return out


# -- the window -------------------------------------------------------------

@dataclasses.dataclass
class Window:
    due: np.ndarray
    sent: np.ndarray          # seconds after the open; nan = never sent
    done: np.ndarray          # seconds after the open; nan = never answered
    sizes: np.ndarray
    results: list             # per batch: answer or None
    failed: np.ndarray        # per batch: degraded / fallback / quarantined
    seconds: float
    traces: list

    def in_window(self) -> np.ndarray:
        return np.isfinite(self.done) & (self.done < self.seconds)


class Driver:
    """Open loop, one thread: a batch is sent once it is due, whether or
    not earlier ones have been answered; while nothing is due the oldest
    batch in flight is completed. What a batch is and how it is sent is
    the traffic kind's (``benchmark/kinds/``)."""

    def __init__(self, d, kind) -> None:
        self.kind = kind
        self.pipe = d.pipeline
        self._q0 = self.pipe.failsafe_state()["quarantined_batches"]

    def _healthy(self) -> bool:
        st = self.pipe.failsafe_state()
        return st["level"] == 0 and st["quarantined_batches"] == self._q0

    def run(self, sched: T.Schedule, seconds: float) -> Window:
        n = len(sched.batches)
        sent = np.full(n, np.nan)
        done = np.full(n, np.nan)
        results: list = [None] * n
        failed = np.zeros(n, bool)
        traces: list = []
        inflight: collections.deque = collections.deque()
        kind, tr = self.kind, self.pipe.tracer
        clock = time.perf_counter
        i = 0
        t0 = clock()

        def harvest(j, h):
            with T.span(kind.tracing, "bench.result"):
                out = h.result()
            done[j] = clock() - t0
            results[j] = out
            failed[j] = kind.degraded(out) or not self._healthy()

        with T.span(kind.tracing, WINDOW):
            while True:
                now = clock() - t0
                # nothing is sent once the window has closed: what was
                # due and not yet sent is the backlog, never attempted
                due = i < n and sched.due[i] < seconds and now < seconds
                if due and (sched.due[i] <= now or not inflight):
                    if sched.due[i] > now:
                        time.sleep(sched.due[i] - now)
                    sent[i] = clock() - t0
                    inflight.append((i, kind.send(sched.batches[i])))
                    while inflight and inflight[0][1].done:
                        harvest(*inflight.popleft())
                    i += 1
                    if tr.active and len(traces) < 1_000_000:
                        traces.extend(tr.traces())
                        tr.clear()
                    continue
                if not inflight:
                    break
                harvest(*inflight.popleft())
        if tr.active:
            traces.extend(tr.traces())
            tr.clear()
        return Window(sched.due, sent, done,
                      np.array([len(b) for b in sched.batches]), results, failed,
                      seconds, traces)


# -- checks ------------------------------------------------------------------

def check(kind, ref: R.Reference, sched: T.Schedule, win: Window) -> dict:
    """Every answer against the reference (the kind's ``compare``), and
    the batches that failed or never came back. Returns the numbers
    compared, and the counts checked under ``_`` names."""
    out = {k: 0 for k in kind.numbers}
    for b, got in zip(sched.batches, win.results):
        if got is None:
            continue
        for k, v in kind.compare(ref, b, got).items():
            out[k] = out.get(k, 0) + v
    out["failed_batches"] = int(win.failed.sum())
    out["unanswered"] = int(np.sum(np.isfinite(win.sent) & ~np.isfinite(win.done)))
    return out


def limits(kind) -> Dict[str, int]:
    """Every number compared is exact: its limit is 0."""
    return {k: 0 for k in (*kind.numbers, "failed_batches", "unanswered")}


# -- metrics -----------------------------------------------------------------

def _p95(x) -> float:
    return float(np.percentile(np.asarray(x, float), 95)) if len(x) else float("nan")


def end_to_end(win: Window, kind, setup_s: float) -> Dict[str, float]:
    ok = win.in_window()
    lat_ms = (win.done[ok] - win.due[ok]) * 1e3
    return {"setup_s": setup_s,
            kind.rate_metric: float(win.sizes[ok].sum()) / win.seconds,
            kind.tail_metric: _p95(lat_ms)}


def lateness(win: Window) -> dict:
    s = np.isfinite(win.sent)
    late = (win.sent[s] - win.due[s]) * 1e3
    slope = float(np.polyfit(win.due[s], late, 1)[0]) if s.sum() > 2 else float("nan")
    return {"batches_sent": int(s.sum()), "batches_due": int((win.due < win.seconds).sum()),
            "late_p50_ms": float(np.median(late)) if len(late) else float("nan"),
            "late_p95_ms": _p95(late), "late_max_ms": float(late.max()) if len(late) else 0.0,
            "late_growth_ms_per_s": slope}


@dataclasses.dataclass
class Readings:
    """What a per-layer metric's reader may read."""
    cell: dict
    config: dict
    traffic: dict
    setup_steps: Dict[str, float]
    world_build_s: float
    compile_s: float
    window: Window
    traces: list
    counters: Dict[str, Dict[tuple, float]]
    trace: object              # tracefile.TraceSummary or None
    peaks: dict
    table_shapes: Dict[str, list]


def read_metric(name: str, r: Readings) -> Optional[float]:
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    v = mod.read(r)
    return None if v is None else float(v)


def peaks_for(kind: str, rehearsal: bool) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind in table:
        return table[kind]
    if rehearsal:
        return {}
    raise SystemExit(f"benchmark/peaks.json has no entry for device kind {kind!r}")


def table_shapes(d) -> Dict[str, list]:
    import jax

    out: Dict[str, list] = {}
    for (direction, family), t in sorted(d.pipeline._tables.items()):
        for path, leaf in jax.tree_util.tree_leaves_with_path(t):
            if hasattr(leaf, "shape"):
                out[f"dp{direction}v{family}{jax.tree_util.keystr(path)}"] = list(leaf.shape)
    return out


# -- set-up ------------------------------------------------------------------

@dataclasses.dataclass
class Prepared:
    """The harness's own work, done before the node boots and left out
    of ``setup_s``: the world's data, the reference, and every batch
    the run will send."""
    w: W.World
    ref: R.Reference
    kind: object
    warm_batches: list
    warm_sched: T.Schedule
    scheds: List[T.Schedule]
    steps: Dict[str, float]
    stores: Dict[str, object]     # remote clusters' kvstores (clusters worlds)


def prepare(cfg: dict, traffic: dict, seed: int, seconds: float,
            rates: Optional[List[float]], on_step=None) -> Prepared:
    steps: Dict[str, float] = {}
    t = time.perf_counter()

    def lap(name):
        nonlocal t
        steps[name] = time.perf_counter() - t
        t = time.perf_counter()
        if on_step is not None:
            on_step(f"harness_{name}", steps[name])

    w = W.build_world(cfg, seed)
    ref = R.Reference(w)
    kind = T.load_kind(traffic["kind"]).Kind(w, traffic, seed)
    lap("world_data")
    warm_batches = kind.warm_batches()
    warm_sched = T.schedule(kind, WARM_SECONDS)
    scheds = [T.schedule(kind, seconds, rate=r) for r in (rates or [None])]
    lap("schedule")
    stores = W.remote_stores(w)
    if stores:
        lap("remote_stores")
    return Prepared(w, ref, kind, warm_batches, warm_sched, scheds, steps, stores)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, sweep: Optional[List[float]] = None,
             overrides: Optional[dict] = None, t_start: float = T_START) -> dict:
    bench = load_benchmark()
    cell = cell_of(bench, workload)
    cfg = W.load_json("configs", cell["config"])
    traffic = W.load_json("traffic", cell["traffic"])
    for k, v in (overrides or {}).get("config", {}).items():
        cfg[k] = v
    for k, v in (overrides or {}).get("traffic", {}).items():
        traffic[k] = v
    W.daemon_config(cfg)     # an unknown daemon key ends the run here
    chips = int(cell["chips"])
    device = device_or_exit(chips, rehearsal)

    import jax

    from cilium_tpu import compile_cache

    compile_cache.enable()
    # every program, however quick to compile, comes from the cache on
    # every run after the first: set-up stays the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileLog()
    peaks = peaks_for(device["kind"], rehearsal)

    def step_line(name, secs):
        hbm = [round((dev.memory_stats() or {}).get("bytes_in_use", 0) / 2**30, 3)
               for dev in jax.devices()[:chips]]
        print(f"setup-step {name} s={secs:.3f} at_s={time.perf_counter() - t_start:.1f} "
              f"compile_s={compiles.secs:.1f} "
              f"rss_peak_gb={resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f} "
              f"hbm_gb={json.dumps(hbm)}", file=sys.stderr, flush=True)

    prep = prepare(cfg, traffic, seed, seconds, sweep, on_step=step_line)
    harness_s = sum(prep.steps.values())
    w, ref, kind = prep.w, prep.ref, prep.kind
    d, steps = W.boot_daemon(w, phase_tracing=trace, stores=prep.stores, on_step=step_line,
                             **kind.daemon)
    world_build_s = sum(steps.values())
    try:
        kind.attach(d)
        driver = Driver(d, kind)
        t = time.perf_counter()
        for b in prep.warm_batches:
            kind.send(b).result()
        driver.run(prep.warm_sched, WARM_SECONDS)
        steps["warm"] = time.perf_counter() - t
        step_line("warm", steps["warm"])
        devices = plan_devices(d, chips)
        if sweep:
            return run_sweep(d, driver, kind, ref, prep.scheds, seconds)
        sched = prep.scheds[0]
        compile_s, compiles_setup = compiles.secs, compiles.count
        if trace:
            d.pipeline.tracer.clear()
        logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        c0 = counter_snapshot()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the host side is the bench.* spans
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(logdir, profiler_options=opts)
        kind.tracing = trace
        setup_s = time.perf_counter() - t_start - harness_s
        win = driver.run(sched, seconds)
        if trace:
            jax.profiler.stop_trace()
        c1 = counter_snapshot()
        compiles_in_window = compiles.count - compiles_setup
        mem = memory_peaks(devices)
        device["memory_peak_bytes"] = max(mem.values())
        ct_live = len(d.conntrack) if d.conntrack is not None else 0
        ct_cap = d.conntrack.capacity if d.conntrack is not None else 0
        shapes = table_shapes(d) if trace else {}
    finally:
        d.shutdown()

    t = time.perf_counter()
    checks = check(kind, ref, sched, win)
    check_s = time.perf_counter() - t
    late = lateness(win)
    ok = win.in_window()
    lat_ms = (win.done[ok] - win.due[ok]) * 1e3
    say(phase="setup", **{f"harness_{k}": round(v, 3) for k, v in prep.steps.items()},
        **{k: round(v, 3) for k, v in steps.items()},
        world_build_s=round(world_build_s, 3), compile_s=round(compile_s, 3),
        compiles=compiles_setup, setup_s=round(setup_s, 3))
    say(phase="window", offered_per_s=round(sched.rate, 1), seconds=seconds,
        samples=int(ok.sum()), **{k: (round(v, 3) if isinstance(v, float) else v)
                                  for k, v in late.items()},
        batch_p50_ms=round(float(np.median(lat_ms)), 3) if len(lat_ms) else "nan",
        batch_p95_ms=round(_p95(lat_ms), 3), compiles_in_window=compiles_in_window,
        ct_live_entries=ct_live, ct_capacity=ct_cap, check_s=round(check_s, 3),
        memory_peak_bytes=json.dumps(mem, separators=(",", ":")),
        **{k.lstrip("_"): v for k, v in checks.items() if k.startswith("_")})

    e2e = end_to_end(win, kind, setup_s)
    metrics: Dict[str, dict] = {}
    result = {"correct": None, "attempted": 0, "failed": 0, "metrics": metrics,
              "device": device}
    trace_sum = None
    if trace:
        path = find_xplane(logdir)
        trace_sum = reduce_xplane(path) if path else None
        shutil.rmtree(logdir, ignore_errors=True)
        say(phase="tables", shapes=json.dumps(shapes, separators=(",", ":")))
        r = Readings(cell, cfg, traffic, steps, world_build_s, compile_s, win, win.traces,
                     counter_delta(c0, c1), trace_sum, peaks, shapes)
        for m in metrics_for(bench, "per_layer", workload):
            v = read_metric(m["name"], r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace_sum is not None:
            device["busy_s"] = trace_sum.busy_s
            device["window_s"] = trace_sum.window_s
            result["breakdown"] = trace_sum.breakdown()
    else:
        for m in metrics_for(bench, "end_to_end", workload):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        for k, v in e2e.items():
            if k not in metrics:
                say(phase="not-judged", metric=k, value=v)

    sent = np.isfinite(win.sent)
    result["attempted"] = int(win.sizes[sent].sum())
    bad = win.failed | (np.array([o is None for o in win.results], bool) & sent)
    result["failed"] = int(win.sizes[bad].sum()) + sum(checks[k] for k in kind.numbers)
    lim = limits(kind)
    numbers = {k: [checks[k], lim[k]] for k in lim}
    result["correct"] = all(v <= m for v, m in numbers.values()) and (
        not trace or trace_sum is not None)
    result["checks"] = numbers
    for k, (v, m) in numbers.items():
        print(f"check {k}={v} limit={m}", file=sys.stderr)
    return result


def run_sweep(d, driver, kind, ref, scheds, seconds) -> dict:
    """Steps of ``seconds`` at rising offered rates, in this process."""
    out = []
    for sched in scheds:
        win = driver.run(sched, seconds)
        ok = win.in_window()
        lat_ms = (win.done[ok] - win.due[ok]) * 1e3
        checks = check(kind, ref, sched, win)
        row = {"offered_per_s": sched.rate,
               "completed_per_s": float(win.sizes[ok].sum()) / seconds,
               "batch_p50_ms": float(np.median(lat_ms)) if len(lat_ms) else None,
               "batch_p95_ms": _p95(lat_ms), **lateness(win),
               "mismatched": sum(checks[k] for k in kind.numbers),
               "failed_batches": checks["failed_batches"],
               "ct_live_entries": len(d.conntrack) if d.conntrack is not None else 0}
        print("sweep " + json.dumps(row), flush=True)
        out.append(row)
    return {"sweep": out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU (rehearsal only: the result names the CPU)")
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates: one window each, no result line")
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       rehearsal=args.cpu_rehearsal,
                       sweep=[float(x) for x in args.sweep.split(",") if x])
    except NoResult as e:
        return int(e.code)
    if "sweep" in res:
        return 0
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
