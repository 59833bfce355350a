"""Chaos suite for the serving stack (PR 10 tentpole, part 2).

Seeded fault-injection scenarios drive randomized kill → recover →
append cycles through a live ``EDMServer`` and check the two contracts
the overload/failure design promises:

* **Liveness** — every submitted request resolves within bound: a
  result, ``Overloaded``, ``DeadlineExceeded``, ``PanelQuarantined``,
  an injected fault, or a named worker-death error. Never a hung
  future.
* **Linearizability** — every *successful* CCM answer is bit-identical
  to a singleton oracle at some consistent library version: exactly the
  number of successful appends submitted before it (per-panel FIFO +
  version barrier). Every successful append's version is its 1-based
  rank among successful appends. After ``close`` → ``recover``, the
  panel is at version == #successful appends and serves oracle bits.

The oracle trick: every append in a scenario carries the IDENTICAL
delta, so library state after k commits depends only on k — one cold
session per commit count answers for every interleaving the thread
pool can produce (asserts stay schedule-independent even though the
fault draws land on different requests per run).
"""

import bisect
import threading
import time
from concurrent.futures import TimeoutError as _FutureTimeout

import numpy as np
import pytest

from repro import telemetry
from repro.data import timeseries as ts
from repro.edm import EDM, EDMConfig
from repro.serving import (DeadlineExceeded, Draining, EDMServer,
                           FaultInjector, Overloaded, PanelQuarantined,
                           WalError)
from repro.serving.faultinject import (POINTS, InjectedFault,
                                       InjectedWalError,
                                       InjectedWorkerDeath)

N, L0, DL = 4, 120, 3
MAX_APPENDS = 8
WATCH = [(0, 1), (1, 2), (2, 3), (3, 0)]
ES = (2, 3)

_PANEL = None
_DELTA = None
_ORACLE: dict[int, dict] = {}


def _panel():
    global _PANEL, _DELTA
    if _PANEL is None:
        x, _ = ts.forced_network_panel(N, L0, seed=5)
        _PANEL = np.asarray(x, np.float32)
        _DELTA = np.random.default_rng(7).standard_normal(
            (N, DL)).astype(np.float32)
    return _PANEL, _DELTA


def oracle(k: int) -> dict:
    """Singleton answers at commit count ``k`` (cold session grown by the
    same ``k`` appends before its master exists, so at the capacity the
    served panel holds)."""
    if k not in _ORACLE:
        panel, delta = _panel()
        sess = EDM(panel, EDMConfig(E_max=3, cache=True))
        for _ in range(k):
            sess.append(delta)
        _ORACLE[k] = {E: [np.float32(v)
                          for v in sess.ccm_batch(WATCH, E=E)]
                      for E in ES}
    return _ORACLE[k]


# --------------------------------------------------- injector unit tests


def test_fault_injector_is_seed_deterministic():
    rates = {p: 0.5 for p in POINTS}
    a = FaultInjector(seed=3, rates=rates)
    b = FaultInjector(seed=3, rates=rates)
    c = FaultInjector(seed=4, rates=rates)
    seq = {fi: {p: [fi.fire(p) for _ in range(50)] for p in POINTS}
           for fi in (a, b, c)}
    assert seq[a] == seq[b]              # same seed → same draws
    assert seq[a] != seq[c]              # different seed → different
    # streams are independent per point: firing one point does not
    # perturb another's sequence
    d = FaultInjector(seed=3, rates=rates)
    only_wal = [d.fire("wal_write") for _ in range(50)]
    assert only_wal == seq[a]["wal_write"]


def test_fault_injector_max_fires_and_counters():
    fi = FaultInjector(seed=0, rates={"launch_error": 1.0}, max_fires=2)
    hits = [fi.fire("launch_error") for _ in range(10)]
    assert sum(hits) == 2 and hits[:2] == [True, True]
    assert fi.calls["launch_error"] == 10
    assert fi.fired["launch_error"] == 2
    with pytest.raises(InjectedFault, match="RESOURCE_EXHAUSTED"):
        FaultInjector(rates={"launch_oom": 1.0}).check("launch_oom")
    with pytest.raises(InjectedWalError, match="injected WAL"):
        FaultInjector(rates={"wal_write": 1.0}).check("wal_write")
    with pytest.raises(ValueError, match="unknown fault points"):
        FaultInjector(rates={"nope": 1.0})


# -------------------------------------------------------- chaos scenarios


def _allowed(exc: BaseException) -> bool:
    if isinstance(exc, (Overloaded, DeadlineExceeded, PanelQuarantined,
                        Draining, InjectedFault, OSError, WalError)):
        return True
    return (isinstance(exc, RuntimeError)
            and str(exc).startswith(("serve worker died",
                                     "scheduler closed")))


RATES = {"worker_death": 0.08, "launch_error": 0.08,
         "launch_oom": 0.05, "slow_launch": 0.10, "wal_write": 0.03}


@pytest.mark.parametrize("seed", range(20))
def test_chaos_scenario_liveness_and_linearizability(seed, tmp_path):
    panel, delta = _panel()
    rng = np.random.default_rng((20260808, seed))
    sd = str(tmp_path / "state")
    fi = FaultInjector(seed=seed, rates=RATES, slow_s=0.005)
    srv = EDMServer(state_dir=sd, compact_every=4, workers=2,
                    supervise=True, max_queue_depth=64,
                    quarantine_after=3, faults=fi,
                    revive_backoff_s=(0.01, 0.1))
    srv.scheduler.supervise_interval = 0.02
    submitted = []      # (kind, fut, ticket, j, E)
    n_appends = 0
    try:
        srv.register_panel("cp", panel, E_max=3, cache=True)
        for _ in range(28):
            do_append = n_appends < MAX_APPENDS and rng.random() < 0.3
            try:
                if do_append:
                    n_appends += 1
                    f = srv.submit("append", "cp", delta=delta)
                    submitted.append(("append", f, f.ticket, None, None))
                else:
                    j = int(rng.integers(len(WATCH)))
                    E = int(rng.choice(ES))
                    kw = {}
                    if rng.random() < 0.1:
                        kw["deadline_s"] = 0.0   # guaranteed to expire
                    f = srv.submit("ccm", "cp", lib=WATCH[j][0],
                                   target=WATCH[j][1], E=E, **kw)
                    submitted.append(("ccm", f, f.ticket, j, E))
            except Exception as exc:  # refused at admission
                assert _allowed(exc), f"submit raised {exc!r}"

        # ---- liveness: EVERY accepted future resolves within bound
        outcomes = []
        for kind, fut, ticket, j, E in submitted:
            try:
                res = fut.result(timeout=120)
            except DeadlineExceeded:
                # Caught first: it subclasses TimeoutError, which is also
                # concurrent.futures.TimeoutError on Python ≥ 3.11.
                outcomes.append((kind, ticket, j, E, None))
                continue
            except _FutureTimeout:
                pytest.fail(f"hung future: ticket {ticket} ({kind})")
            except Exception as exc:
                assert _allowed(exc), \
                    f"ticket {ticket} ({kind}) failed with {exc!r}"
                outcomes.append((kind, ticket, j, E, None))
            else:
                outcomes.append((kind, ticket, j, E, res))

        # ---- linearizability against the commit-count oracle
        ok_appends = sorted(t for k, t, _, _, r in outcomes
                            if k == "append" and r is not None)
        for rank, t in enumerate(ok_appends):
            _, _, _, _, res = next(o for o in outcomes if o[1] == t)
            assert res["version"] == rank + 1
        for kind, ticket, j, E, res in outcomes:
            if kind != "ccm" or res is None:
                continue
            k = bisect.bisect_left(ok_appends, ticket)
            assert np.float32(res) == oracle(k)[E][j], \
                f"ticket {ticket}: served bits diverge from oracle[{k}]"
    finally:
        srv.close()

    # ---- crash recovery: durable state == the successful appends
    n_committed = len(ok_appends)
    rec = EDMServer.recover(sd, autostart=False)
    try:
        assert rec.recovery_report["cp"]["version"] == n_committed
        futs = rec.submit_many(
            "ccm", "cp", [{"lib": l, "target": t, "E": 3}
                          for l, t in WATCH])
        while rec.scheduler.drain_once():
            pass
        got = [np.float32(f.result()) for f in futs]
        assert got == oracle(n_committed)[3]
    finally:
        rec.close()


# ------------------------------------------------- supervisor + drain


def test_supervisor_revives_dead_worker_and_service_resumes():
    panel, _ = _panel()
    fi = FaultInjector(seed=1, rates={"worker_death": 1.0}, max_fires=1)
    with telemetry.record() as rec:
        srv = EDMServer(workers=1, supervise=True, faults=fi,
                        revive_backoff_s=(0.01, 0.05))
        srv.scheduler.supervise_interval = 0.01
        try:
            srv.register_panel("sp", panel, E_max=3, cache=True)
            f = srv.submit("ccm", "sp", lib=0, target=1, E=3)
            with pytest.raises(RuntimeError, match="serve worker died"):
                f.result(timeout=30)
            deadline = time.monotonic() + 10
            while not srv.health()["ok"]:
                assert time.monotonic() < deadline, "supervisor never " \
                    "revived the worker"
                time.sleep(0.01)
            # exactly one injected death; the revived worker serves
            got = srv.call("ccm", "sp", lib=0, target=1, E=3, timeout=30)
            assert np.float32(got) == oracle(0)[3][0]
            assert fi.fired["worker_death"] == 1
        finally:
            srv.close()
    assert rec.counter_delta("serve_worker_revives") >= 1
    assert rec.counter_delta("serve_worker_deaths") == 1


def test_drain_stops_admission_and_empties_queues():
    panel, delta = _panel()
    srv = EDMServer(autostart=False, workers=1)
    try:
        srv.register_panel("dp", panel, E_max=3, cache=True)
        futs = [srv.submit("append", "dp", delta=delta)
                for _ in range(3)]
        done = {}
        t = threading.Thread(
            target=lambda: done.setdefault("ok", srv.drain(timeout=30)))
        t.start()
        deadline = time.monotonic() + 5
        while not srv.scheduler._draining:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        with pytest.raises(Draining):
            srv.submit("ccm", "dp", lib=0, target=1, E=3)
        assert srv.health()["ok"] is False      # draining reads not-ok
        while srv.scheduler.drain_once():       # queued work still runs
            pass
        t.join(timeout=30)
        assert done.get("ok") is True
        assert [f.result()["version"] for f in futs] == [1, 2, 3]
    finally:
        srv.close()


def test_quarantine_after_repeated_launch_failures():
    panel, _ = _panel()
    fi = FaultInjector(seed=0, rates={"worker_death": 1.0}, max_fires=3)
    with telemetry.record() as rec:
        srv = EDMServer(workers=1, supervise=True, quarantine_after=3,
                        faults=fi, revive_backoff_s=(0.01, 0.05))
        srv.scheduler.supervise_interval = 0.01
        try:
            srv.register_panel("qp", panel, E_max=3, cache=True)
            failures = 0
            deadline = time.monotonic() + 30
            while "qp" not in srv.scheduler.quarantined_panels():
                assert time.monotonic() < deadline, \
                    "panel never quarantined"
                try:
                    srv.call("ccm", "qp", lib=0, target=1, E=3,
                             timeout=30)
                except (RuntimeError, PanelQuarantined):
                    failures += 1
                time.sleep(0.02)
            assert failures >= 3
            with pytest.raises(PanelQuarantined):
                srv.submit("ccm", "qp", lib=0, target=1, E=3)
            # operator reset: injector is exhausted, service resumes
            assert srv.clear_quarantine("qp") is True
            got = srv.call("ccm", "qp", lib=0, target=1, E=3, timeout=30)
            assert np.float32(got) == oracle(0)[3][0]
        finally:
            srv.close()
    assert rec.counter_delta("serve_quarantined") == 1


def test_injected_worker_death_is_base_exception():
    # the point rides the real worker-death path, which a plain
    # ``except Exception`` must NOT catch
    assert issubclass(InjectedWorkerDeath, BaseException)
    assert not issubclass(InjectedWorkerDeath, Exception)
