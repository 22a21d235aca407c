"""policyd-survive: connection continuity across restart, drain, and
quarantine.

The reference keeps its conntrack maps PINNED in the kernel — the agent
can restart (or be drained) without dropping established flows. Our
host table dies with the process, so the survive contract is:

- a kill -9 restart restores ct.npz (basis-verified) and established
  flows stay allowed through the first post-boot batch;
- a rule change racing the restart voids the restore (flush, not stale
  bypass);
- SIGTERM drains: shed new work, complete in-flight, persist, exit 0;
- quarantine rescues the live device-CT into the host table and
  re-uploads it on ladder re-promotion.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from cilium_tpu import faults
from cilium_tpu.daemon import Daemon
from cilium_tpu.datapath.ct_snapshot import load_ct_state, save_ct_state
from cilium_tpu.ops.lpm import ip_strings_to_u32

ALLOW = json.dumps([{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "ingress": [{"fromEndpoints": [{"matchLabels": {"app": "client"}}]}],
}])
EXTRA = json.dumps([{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "ingress": [{"fromEndpoints": [{"matchLabels": {"app": "extra"}}]}],
}])


def _seed(dm):
    dm.policy_add(ALLOW)
    dm.endpoint_add(1, ["unspec:app=web"], ipv4="10.0.0.1")
    dm.endpoint_add(2, ["unspec:app=client"], ipv4="10.0.0.2")


def _flows(dm, n=8, sport0=10000):
    peers = ip_strings_to_u32(["10.0.0.2"] * n)
    v, _ = dm.pipeline.process(
        peers, np.zeros(n, np.int32), np.full(n, 80, np.int32),
        np.full(n, 6, np.int32),
        sports=(sport0 + np.arange(n)).astype(np.int32),
    )
    return v


def _stop(dm):
    """Tear down a daemon WITHOUT the drain-side persistence (the
    kill -9 stand-in for in-process tests)."""
    dm.controllers.remove_all()
    dm.health.stop()
    dm.fqdn.stop()
    dm.endpoint_manager.shutdown()


@pytest.fixture(autouse=True)
def _clean_hub():
    faults.hub.reset()
    yield
    faults.hub.reset()


class TestRestartContinuity:
    def test_established_flows_survive_restart(self, tmp_path):
        from cilium_tpu import metrics
        from cilium_tpu.datapath.pipeline import FORWARD

        d = str(tmp_path)
        dm = Daemon(state_dir=d)
        _seed(dm)
        assert (_flows(dm) == FORWARD).all()
        assert len(dm.conntrack) == 8
        dm.shutdown()  # graceful: persists CT + compiled + state.json

        dm2 = Daemon(state_dir=d)
        try:
            info = dm2.ct_restore_info()
            assert info["basis_match"] is True
            assert info["kept"] == 8
            assert info["flushed"] == 0
            # the SAME established tuples still forward, and the first
            # batch's rebuild does NOT flush them (revision-pinned
            # restore hold)
            assert (_flows(dm2) == FORWARD).all()
            assert len(dm2.conntrack) == 8
            # first post-boot verdict closed the downtime window
            assert metrics.restart_downtime_seconds.get() > 0.0
        finally:
            _stop(dm2)

    def test_journal_tells_the_same_restart_story(self, tmp_path):
        """With LifecycleJournal on from boot, the restarted daemon's
        journal agrees with the restore it records: ct_restore carries
        ct_restore_info()'s basis verdict, and the boot -> restore_done
        span covers the downtime window and lies inside the wall time
        from Daemon() to the first verdict (1 ms for the two clocks)."""
        from cilium_tpu.option import DaemonConfig, get_config, set_config

        saved = get_config()
        d = str(tmp_path)
        dm2 = None
        try:
            set_config(DaemonConfig(lifecycle_journal=True))
            dm = Daemon(state_dir=d)
            _seed(dm)
            _flows(dm)
            dm.shutdown()

            t0 = time.perf_counter()
            dm2 = Daemon(state_dir=d)
            _flows(dm2)
            measured_ms = (time.perf_counter() - t0) * 1e3
            first = {}
            for e in dm2.events(limit=128)["events"]:
                first.setdefault(e["kind"], e)
            info = dm2.ct_restore_info()
            assert info["basis_match"] is True
            assert first["ct_restore"]["attrs"]["basis_match"] is True
            boot, done = first["boot"], first["restore_done"]
            journal_ms = (done["wall_ts"] - boot["wall_ts"]) * 1e3
            assert 0.0 < done["attrs"]["downtime_ms"] <= journal_ms + 1.0
            assert journal_ms <= measured_ms + 1.0
        finally:
            set_config(saved)
            if dm2 is not None:
                dm2.shutdown()

    def test_rule_change_before_first_batch_voids_hold(self, tmp_path):
        """A policy mutation landing after restore but before the first
        batch bumps the revision and voids the restore hold — the
        restored entries flush instead of bypassing the new rules."""
        d = str(tmp_path)
        dm = Daemon(state_dir=d)
        _seed(dm)
        _flows(dm)
        dm.shutdown()

        dm2 = Daemon(state_dir=d)
        try:
            assert dm2.ct_restore_info()["kept"] == 8
            dm2.policy_add(EXTRA)  # races in before any batch
            _flows(dm2, sport0=30000)  # rebuild: hold voided -> flush
            # only the fresh batch's entries remain (16 if the restored
            # 8 had survived the mutation)
            assert len(dm2.conntrack) == 8
        finally:
            _stop(dm2)

    def test_basis_mismatch_restores_cold(self, tmp_path):
        """ct.npz stamped under a basis the compiled snapshot does not
        carry (restart raced a rule change) flushes instead of
        restoring stale bypass entries."""
        d = str(tmp_path)
        dm = Daemon(state_dir=d)
        _seed(dm)
        _flows(dm)
        dm.shutdown()
        # re-stamp the CT snapshot with a foreign basis
        save_ct_state(
            os.path.join(d, "ct.npz"), dm.conntrack,
            basis=(99999, 1, 1), ct_epoch=0,
        )
        dm2 = Daemon(state_dir=d)
        try:
            info = dm2.ct_restore_info()
            assert info["basis_match"] is False
            assert info["flushed"] == 8
            assert info["kept"] == 0
            assert len(dm2.conntrack) == 0
        finally:
            _stop(dm2)

    def test_torn_ct_write_boots_cold_never_crashes(self, tmp_path):
        from cilium_tpu.datapath.pipeline import FORWARD

        d = str(tmp_path)
        dm = Daemon(state_dir=d)
        _seed(dm)
        _flows(dm)
        dm.controllers.remove_all()  # no background resave heals it
        dm._save_compiled_snapshot(force=True)
        faults.hub.fail(
            faults.SITE_STATE_WRITE, faults.KIND_TRANSIENT, times=1
        )
        dm._save_ct_snapshot(force=True)  # logged, not raised
        assert load_ct_state(os.path.join(d, "ct.npz")) is None  # torn
        dm2 = Daemon(state_dir=d)
        try:
            info = dm2.ct_restore_info()
            assert info["kept"] == 0 and info["flushed"] == 0
            assert info["basis_match"] is False
            # cold but alive: rules re-imported, verdicts flow
            assert (_flows(dm2) == FORWARD).all()
        finally:
            _stop(dm2)
            _stop(dm)

    def test_restore_never_clobbers_disk_snapshot(self, tmp_path):
        """The boot-crash window: a daemon that restores and then dies
        before its first CT sync must leave ct.npz exactly as the dead
        process wrote it — the restore path's own save_state calls may
        not overwrite the only copy with an empty mid-re-add table."""
        d = str(tmp_path)
        dm = Daemon(state_dir=d)
        _seed(dm)
        _flows(dm)
        dm.shutdown()
        before = load_ct_state(os.path.join(d, "ct.npz"))
        assert before["entries"] == 8

        dm2 = Daemon(state_dir=d)  # boots, restores...
        _stop(dm2)  # ...and "crashes" before any batch or CT sync
        after = load_ct_state(os.path.join(d, "ct.npz"))
        assert after is not None
        assert after["entries"] == 8
        assert after["basis"] == before["basis"]
        # and a third boot still keeps the flows
        dm3 = Daemon(state_dir=d)
        try:
            assert dm3.ct_restore_info()["kept"] == 8
        finally:
            _stop(dm3)

    def test_v2_state_json_migrates_forward(self, tmp_path):
        """Schema chain: a v2 state.json (pre-CT) boots through
        state_migrate and restores endpoints; the absent ct.npz is a
        cold start, not an error."""
        d = str(tmp_path)
        dm = Daemon(state_dir=d)
        _seed(dm)
        dm.shutdown()
        path = os.path.join(d, "state.json")
        with open(path) as f:
            body = json.load(f)
        body["schema"] = 2
        body.pop("ct", None)
        with open(path, "w") as f:
            json.dump(body, f)
        os.unlink(os.path.join(d, "ct.npz"))
        dm2 = Daemon(state_dir=d)
        try:
            assert len(dm2.endpoint_list()) == 2
            info = dm2.ct_restore_info()
            assert info["kept"] == 0 and info["basis_match"] is False
        finally:
            _stop(dm2)

    def test_bugtool_carries_ct_provenance(self, tmp_path):
        from cilium_tpu.bugtool import collect_debuginfo

        dm = Daemon(state_dir=str(tmp_path))
        _seed(dm)
        _flows(dm)
        try:
            info = collect_debuginfo(dm)
            assert info["ct"]["entries"] == 8
            assert info["ct"]["capacity"] > 0
            assert len(info["ct"]["sample"]) == 8
            assert "restore" in info["ct"]
        finally:
            _stop(dm)


class TestDrain:
    def test_drain_sheds_completes_and_persists(self, tmp_path):
        from cilium_tpu.datapath.pipeline import DROP_DEGRADED, FORWARD

        d = str(tmp_path)
        dm = Daemon(state_dir=d)
        _seed(dm)
        assert (_flows(dm) == FORWARD).all()
        rep = dm.drain(deadline_s=2.0)
        try:
            assert rep["verdicts_lost"] == 0
            assert rep["abandoned"] == 0
            assert rep["drain_s"] < 2.5
            # tail persistence landed while quiescent
            for name in ("ct.npz", "compiled.npz", "state.json"):
                assert os.path.exists(os.path.join(d, name)), name
            assert load_ct_state(os.path.join(d, "ct.npz"))["entries"] == 8
            # admission is shed: post-drain submits resolve immediately
            # with the degraded shape (still a verdict per flow)
            v = _flows(dm, sport0=40000)
            assert (v == DROP_DEGRADED).all()
        finally:
            dm.pipeline.end_drain()
            _stop(dm)

    def test_signal_handlers_raise_keyboard_interrupt(self):
        from cilium_tpu.cli import _install_signal_handlers

        old_term = signal.getsignal(signal.SIGTERM)
        old_int = signal.getsignal(signal.SIGINT)
        try:
            _install_signal_handlers()
            for sig in (signal.SIGTERM, signal.SIGINT):
                with pytest.raises(KeyboardInterrupt):
                    os.kill(os.getpid(), sig)
        finally:
            signal.signal(signal.SIGTERM, old_term)
            signal.signal(signal.SIGINT, old_int)

    def test_handlers_tolerate_non_main_thread(self):
        from cilium_tpu.cli import _install_signal_handlers

        errs = []

        def run():
            try:
                _install_signal_handlers()  # ValueError swallowed
            except BaseException as e:  # pragma: no cover
                errs.append(e)

        t = threading.Thread(target=run)
        t.start()
        t.join(10)
        assert errs == []

    def test_sigterm_subprocess_drains_and_exits_zero(self, tmp_path):
        """The full production teardown in a REAL process: SIGTERM ->
        KeyboardInterrupt -> drain -> persisted state -> exit 0."""
        d = str(tmp_path)
        src = (
            "import json, os, signal, sys\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "import numpy as np\n"
            "from cilium_tpu.cli import _install_signal_handlers\n"
            "from cilium_tpu.daemon import Daemon\n"
            "from cilium_tpu.ops.lpm import ip_strings_to_u32\n"
            f"dm = Daemon(state_dir={d!r})\n"
            f"dm.policy_add({ALLOW!r})\n"
            "dm.endpoint_add(1, ['unspec:app=web'], ipv4='10.0.0.1')\n"
            "dm.endpoint_add(2, ['unspec:app=client'], ipv4='10.0.0.2')\n"
            "dm.pipeline.process(ip_strings_to_u32(['10.0.0.2'] * 4),\n"
            "    np.zeros(4, np.int32), np.full(4, 80, np.int32),\n"
            "    np.full(4, 6, np.int32),\n"
            "    sports=np.arange(4).astype(np.int32) + 1000)\n"
            "_install_signal_handlers()\n"
            "try:\n"
            "    import time\n"
            "    print('READY', flush=True)\n"
            "    while True:\n"
            "        time.sleep(0.1)\n"
            "except KeyboardInterrupt:\n"
            "    rep = dm.drain(deadline_s=5.0)\n"
            "    dm.shutdown(deadline_s=1.0)\n"
            "    print('DRAIN', json.dumps(rep['verdicts_lost']),\n"
            "          flush=True)\n"
            "    sys.exit(0)\n"
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.Popen(
            [sys.executable, "-u", "-c", src],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env,
        )
        try:
            deadline = time.time() + 180
            while time.time() < deadline:
                line = proc.stdout.readline()
                if line.startswith("READY"):
                    break
                assert proc.poll() is None, "daemon died before READY"
            else:
                pytest.fail("daemon never became READY")
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert proc.returncode == 0
        assert "DRAIN 0" in out
        # the drained state restores warm
        snap = load_ct_state(os.path.join(d, "ct.npz"))
        assert snap is not None and snap["entries"] == 4


class TestQuarantineRescue:
    def _host_keys(self, n=32):
        from cilium_tpu.datapath.conntrack import pack_keys

        rng = np.random.default_rng(5)
        return pack_keys(
            np.zeros(n, np.uint64),
            rng.integers(1, 1 << 32, n, dtype=np.uint64),
            (np.arange(n) % 8).astype(np.uint64),
            (2000 + np.arange(n)).astype(np.uint64),
            np.full(n, 443, np.uint64),
            np.full(n, 6, np.uint64),
            np.zeros(n, np.uint64),
        )

    def test_device_words_roundtrip_host_keys(self):
        """seed_state_from_host -> pull_live_entries reconstructs the
        exact host uint64 key words (the 32-bit word split is
        lossless)."""
        from cilium_tpu.datapath.device_ct import (
            pull_live_entries,
            seed_state_from_host,
        )

        ka, kb, kc = self._host_keys()
        ttl = np.full(len(ka), 30.0)
        state = seed_state_from_host(ka, kb, kc, ttl, 10, now_s=1000)
        pulled = pull_live_entries(state, now_s=1000)
        got = set(zip(
            pulled["ka"].tolist(), pulled["kb"].tolist(),
            pulled["kc"].tolist(),
        ))
        want = set(zip(ka.tolist(), kb.tolist(), kc.tolist()))
        assert got == want
        assert (pulled["ttl"] > 0).all()

    def _pipe_shell(self):
        from cilium_tpu.datapath.conntrack import FlowConntrack

        return SimpleNamespace(
            conntrack=FlowConntrack(capacity_bits=10),
            device_ct_rescue_limit=1 << 16,
            _lock=threading.Lock(),
            _device_ct_seed=False,
        )

    def test_rescue_pulls_device_entries_into_host(self):
        from cilium_tpu.datapath.device_ct import seed_state_from_host
        from cilium_tpu.datapath.pipeline import DatapathPipeline

        ka, kb, kc = self._host_keys()
        state = seed_state_from_host(
            ka, kb, kc, np.full(len(ka), 30.0), 10,
            now_s=int(time.monotonic()),
        )
        shell = self._pipe_shell()
        DatapathPipeline._rescue_device_ct(shell, state)
        assert len(shell.conntrack) == len(ka)
        # re-upload half armed: the next fresh device table seeds from
        # the host CT instead of zeros
        assert shell._device_ct_seed is True

    def test_rescue_fault_skips_cold_never_escalates(self):
        """The device being quarantined may fail the pull itself — an
        injected fault at the completion site means rescue skipped
        (cold), never a raise or a second escalation."""
        from cilium_tpu.datapath.device_ct import seed_state_from_host
        from cilium_tpu.datapath.pipeline import DatapathPipeline

        ka, kb, kc = self._host_keys()
        state = seed_state_from_host(
            ka, kb, kc, np.full(len(ka), 30.0), 10,
            now_s=int(time.monotonic()),
        )
        shell = self._pipe_shell()
        faults.hub.fail(
            faults.SITE_COMPLETE, faults.KIND_TRANSIENT, times=1
        )
        DatapathPipeline._rescue_device_ct(shell, state)  # no raise
        assert len(shell.conntrack) == 0
        assert shell._device_ct_seed is False
