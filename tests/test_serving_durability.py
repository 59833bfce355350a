"""Durable serving: WAL + crash recovery (PR 10 tentpole, part 1).

The contract under test: with ``EDMServer(state_dir=...)``, every
registration and every *accepted* append is durable before its future
resolves, and ``EDMServer.recover(state_dir)`` rebuilds every panel
**bit-identically** at its pre-crash library version — including after
kill -9 mid-append-stream, a torn WAL tail, compaction, master
eviction, and masked-invalid panels. Oracles are cold sessions /
uninterrupted servers on the same data; equality is bitwise
(``np.float32`` compare), never approximate.
"""

import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import telemetry
from repro.data import timeseries as ts
from repro.edm import EDM, EDMConfig
from repro.serving import (EDMServer, FaultInjector, PanelQuarantined,
                           WalError)

CFG = dict(E_max=3, cache=True)
E_REQ = 3
PAIRS = [(0, 1), (1, 2), (2, 3), (3, 4), (4, 0)]


@pytest.fixture(scope="module")
def panel():
    x, _ = ts.forced_network_panel(5, 240, seed=11)
    return np.asarray(x, np.float32)


@pytest.fixture(scope="module")
def deltas():
    rng = np.random.default_rng(23)
    return [rng.standard_normal((5, 4)).astype(np.float32)
            for _ in range(6)]


def _drain_all(srv):
    while srv.scheduler.drain_once():
        pass




def _served_ccm(srv, name, pairs):
    futs = srv.submit_many(
        "ccm", name, [{"lib": l, "target": t, "E": E_REQ}
                      for l, t in pairs])
    _drain_all(srv)
    return [np.float32(f.result()) for f in futs]


def _oracle_ccm(panel, applied, pairs):
    """The quiesced answers of a cold session on ``panel`` grown by the
    ``applied`` deltas, appended in turn before its master exists — so
    it holds the capacity the server's panel holds."""
    sess = EDM(panel, EDMConfig(**CFG))
    for d in applied:
        sess.append(d)
    return [np.float32(v) for v in sess.ccm_batch(pairs, E=E_REQ)]


# ------------------------------------------------- basic WAL round trip


def test_recover_bit_identical_after_appends(tmp_path, panel, deltas):
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False) as srv:
        srv.register_panel("p", panel, **CFG)
        _served_ccm(srv, "p", PAIRS)  # warm master: appends then merge
        for d in deltas[:3]:
            f = srv.submit("append", "p", delta=d)
            _drain_all(srv)
            assert f.result()["version"] >= 1

    rec = EDMServer.recover(sd, autostart=False)
    try:
        info = rec.recovery_report["p"]
        assert info["version"] == 3 and info["torn_tail_bytes"] == 0
        entry = rec.registry.get("p")
        assert entry.version == 3
        got = _served_ccm(rec, "p", PAIRS)
        want = _oracle_ccm(panel, deltas[:3], PAIRS)
        assert got == want  # bitwise: float32 equality
    finally:
        rec.close()


def test_recovered_panel_keeps_appending_bit_identically(
        tmp_path, panel, deltas):
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False) as srv:
        srv.register_panel("p", panel, **CFG)
        srv.submit("append", "p", delta=deltas[0])
        _drain_all(srv)

    rec = EDMServer.recover(sd, autostart=False)
    try:
        f = rec.submit("append", "p", delta=deltas[1])
        _drain_all(rec)
        assert f.result()["version"] == 2
        got = _served_ccm(rec, "p", PAIRS)
        want = _oracle_ccm(panel, deltas[:2], PAIRS)
        assert got == want
    finally:
        rec.close()


def test_compaction_bounds_replay_and_gcs_segments(
        tmp_path, panel, deltas):
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False,
                   compact_every=2) as srv:
        srv.register_panel("p", panel, **CFG)
        for d in deltas[:5]:
            srv.submit("append", "p", delta=d)
            _drain_all(srv)
        pdir = srv.registry.get("p").wal.pdir
        names = sorted(os.listdir(pdir))
    # compactions at v2 and v4 ran; older snapshots/WALs are GC'd.
    assert "snap-0000000004" in names
    assert "wal-0000000004.log" in names
    assert not any(n.startswith(("snap-0000000002", "wal-0000000000",
                                 "wal-0000000002")) for n in names)

    rec = EDMServer.recover(sd, autostart=False)
    try:
        info = rec.recovery_report["p"]
        assert info["snapshot"] == 4 and info["replayed"] == 1
        assert info["version"] == 5
        got = _served_ccm(rec, "p", PAIRS)
        assert got == _oracle_ccm(panel, deltas[:5], PAIRS)
    finally:
        rec.close()


# -------------------------------------------------- recovery edge cases


def test_truncated_wal_tail_recovers_to_last_record_and_warns(
        tmp_path, panel, deltas):
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False,
                   compact_every=100) as srv:
        srv.register_panel("p", panel, **CFG)
        for d in deltas[:3]:
            srv.submit("append", "p", delta=d)
            _drain_all(srv)
        pdir = srv.registry.get("p").wal.pdir

    wal = Path(pdir) / "wal-0000000000.log"
    blob = wal.read_bytes()
    wal.write_bytes(blob[:-7])  # tear the final record mid-payload

    with pytest.warns(UserWarning, match="torn tail"):
        rec = EDMServer.recover(sd, autostart=False)
    try:
        info = rec.recovery_report["p"]
        assert info["version"] == 2 and info["torn_tail_bytes"] > 0
        got = _served_ccm(rec, "p", PAIRS)
        assert got == _oracle_ccm(panel, deltas[:2], PAIRS)
        # The post-recovery rotation truncated the torn tail for good:
        # a second recovery is clean.
        rec.close()
        rec2 = EDMServer.recover(sd, autostart=False)
        assert rec2.recovery_report["p"]["version"] == 2
        assert rec2.recovery_report["p"]["torn_tail_bytes"] == 0
        rec2.close()
    finally:
        rec.close()


def test_fingerprint_mismatch_is_refused(tmp_path, panel):
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False) as srv:
        srv.register_panel("p", panel, **CFG)
        pdir = srv.registry.get("p").wal.pdir
    tampered = np.array(np.load(os.path.join(pdir, "base.npy")))
    tampered[0, 0] += 1.0
    np.save(os.path.join(pdir, "base.npy"), tampered)
    with pytest.raises(WalError, match="fingerprint"):
        EDMServer.recover(sd, autostart=False)


def test_recover_evicted_master_panel(tmp_path, panel, deltas):
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False) as srv:
        srv.register_panel("p", panel, **CFG)
        _served_ccm(srv, "p", PAIRS)      # builds the master
        srv.submit("append", "p", delta=deltas[0])
        _drain_all(srv)
        assert srv.evict_panel("p") > 0   # cold on disk AND in memory

    rec = EDMServer.recover(sd, autostart=False)
    try:
        assert rec.recovery_report["p"]["version"] == 1
        got = _served_ccm(rec, "p", PAIRS)
        assert got == _oracle_ccm(panel, deltas[:1], PAIRS)
    finally:
        rec.close()


def test_subscription_reregistered_post_restart(tmp_path, panel, deltas):
    sd = str(tmp_path / "state")
    watch = PAIRS[:3]
    with EDMServer(state_dir=sd, autostart=False) as srv:
        srv.register_panel("p", panel, **CFG)
        f = srv.submit("subscribe", "p",
                       pairs=[list(p) for p in watch], E=E_REQ)
        _drain_all(srv)
        f.result()
        srv.submit("append", "p", delta=deltas[0])
        _drain_all(srv)

    # Subscriptions are NOT durable state: recovery starts with none,
    # and a re-registered watch list linearizes with the new stream.
    rec = EDMServer.recover(sd, autostart=False)
    try:
        assert rec.subscriptions.count() == 0
        f = rec.submit("subscribe", "p",
                       pairs=[list(p) for p in watch], E=E_REQ)
        _drain_all(rec)
        sub = f.result()
        assert sub["version"] == 1
        assert [np.float32(v) for v in sub["rho"]] == _oracle_ccm(
            panel, deltas[:1], watch)
        rec.submit("append", "p", delta=deltas[1])
        _drain_all(rec)
        ticks = rec.subscription(sub["id"]).poll(timeout=1.0)
        assert ticks and ticks[-1]["version"] == 2
        assert [np.float32(v) for v in ticks[-1]["rho"]] == _oracle_ccm(
            panel, deltas[:2], watch)
    finally:
        rec.close()


def test_mask_policy_panel_recovers_bit_identically(tmp_path):
    rng = np.random.default_rng(3)
    dirty = rng.standard_normal((4, 120)).astype(np.float32)
    dirty[1, 10] = np.nan                       # masked at registration
    d0 = rng.standard_normal((4, 5)).astype(np.float32)
    d1 = rng.standard_normal((4, 5)).astype(np.float32)
    d1[2, 3] = np.inf                           # masked at append time

    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False, compact_every=1) as srv, \
            EDMServer(autostart=False) as live:
        for s in (srv, live):
            s.register_panel("p", dirty, on_invalid="mask", **CFG)
            for d in (d0, d1):
                s.submit("append", "p", delta=d)
                _drain_all(s)
        live_ds = live.registry.get("p").sess.data

        rec = EDMServer.recover(sd, autostart=False)
        try:
            ds = rec.registry.get("p").sess.data
            assert np.asarray(ds.panel).tobytes() == \
                np.asarray(live_ds.panel).tobytes()
            assert np.array_equal(ds.valid, live_ds.valid)
            for k in ("cnt", "lo", "hi"):
                assert np.array_equal(ds._stats[k], live_ds._stats[k])
            assert ds.invalid_report == live_ds.invalid_report
        finally:
            rec.close()


def test_reregister_into_existing_state_dir_is_refused(tmp_path, panel):
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False) as srv:
        srv.register_panel("p", panel, **CFG)
    with EDMServer(state_dir=sd, autostart=False) as srv2:
        with pytest.raises(ValueError, match="recover"):
            srv2.register_panel("p", panel, **CFG)
        # the failed durable publish rolled the registry claim back
        with pytest.raises(KeyError):
            srv2.registry.get("p")
        srv2.register_panel("other", panel, **CFG)  # new names still fine


def test_config_mesh_refused_for_durable_registration(tmp_path, panel):
    import types
    mesh = types.SimpleNamespace(axis_names=("data", "model"))
    sd = str(tmp_path / "state")
    with EDMServer(state_dir=sd, autostart=False) as srv:
        with pytest.raises(ValueError, match="mesh"):
            srv.register_panel("p", panel,
                               config=EDMConfig(mesh=mesh, **CFG))
        with pytest.raises(KeyError):
            srv.registry.get("p")


# ------------------------------------------- WAL failure == quarantine


def test_wal_write_failure_quarantines_panel(tmp_path, panel, deltas):
    fi = FaultInjector(seed=0, rates={"wal_write": 1.0})
    sd = str(tmp_path / "state")
    with telemetry.record() as rec:
        with EDMServer(state_dir=sd, autostart=False, faults=fi) as srv:
            srv.register_panel("p", panel, **CFG)
            f = srv.submit("append", "p", delta=deltas[0])
            _drain_all(srv)
            with pytest.raises(Exception, match="injected WAL"):
                f.result(timeout=5)
            # memory is ahead of the log: the panel fails fast now
            with pytest.raises(PanelQuarantined):
                srv.submit("ccm", "p", lib=0, target=1, E=E_REQ)
            assert "p" in srv.scheduler.quarantined_panels()
    assert rec.counter_delta("serve_quarantined") == 1

    # recovery serves the last DURABLE version (0), bit-identically
    rec2 = EDMServer.recover(sd, autostart=False)
    try:
        assert rec2.recovery_report["p"]["version"] == 0
        got = _served_ccm(rec2, "p", PAIRS)
        assert got == _oracle_ccm(panel, [], PAIRS)
    finally:
        rec2.close()


# ------------------------------------------------ kill -9 (the big one)

_CHILD = r"""
import os, sys, time
import numpy as np
from repro.serving import EDMServer

state_dir, n_appends = sys.argv[1], int(sys.argv[2])
panel = np.load(os.path.join(state_dir, "panel.npy"))
delta = np.load(os.path.join(state_dir, "delta.npy"))
srv = EDMServer(state_dir=state_dir, workers=1)
srv.register_panel("kp", panel, E_max=3, cache=True)
srv.call("ccm", "kp", lib=0, target=1, E=3)   # warm master: appends merge
print("READY", flush=True)
for k in range(n_appends):
    r = srv.call("append", "kp", delta=delta)
    print(f"ACK {r['version']}", flush=True)
print("DONE", flush=True)
time.sleep(120)
"""


@pytest.mark.slow
def test_kill9_mid_append_stream_recovers_bit_identically(
        tmp_path, panel, deltas):
    """kill -9 between append ticks; recovery must restore the panel at
    its last durable version with answers bit-identical to an
    uninterrupted session at that version (the acceptance assert)."""
    sd = str(tmp_path / "state")
    os.makedirs(sd)
    delta = deltas[0]
    n_appends = 6
    np.save(os.path.join(sd, "panel.npy"), panel)
    np.save(os.path.join(sd, "delta.npy"), delta)

    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen([sys.executable, "-c", _CHILD, sd,
                             str(n_appends)],
                            stdout=subprocess.PIPE, text=True, env=env)
    acked = 0
    try:
        deadline = time.monotonic() + 180
        for line in proc.stdout:
            if line.startswith("ACK"):
                acked = int(line.split()[1])
                if acked >= 2:
                    break  # kill -9 mid-stream, between ticks
            if time.monotonic() > deadline:
                raise TimeoutError("child never reached 2 acks")
        assert acked >= 2, "child exited before acking"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    rec = EDMServer.recover(sd, autostart=False)
    try:
        v = rec.recovery_report["kp"]["version"]
        # every ACKed append is durable; later un-ACKed ticks may or may
        # not have hit the log before the kill
        assert acked <= v <= n_appends
        assert rec.registry.get("kp").version == v
        grown = np.concatenate([panel] + [delta] * v, axis=1)
        assert rec.registry.get("kp").sess.data.L == grown.shape[1]
        got = _served_ccm(rec, "kp", PAIRS)
        assert got == _oracle_ccm(panel, [delta] * v, PAIRS)
    finally:
        rec.close()
