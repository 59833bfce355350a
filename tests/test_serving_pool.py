"""Serving phase 2: worker pool, LRU eviction, subscriptions (ISSUE 9).

The concurrency & parity battery. The single-worker PR-8 design passes
most of these trivially (everything serializes); the pooled design must
earn them:

* **Per-panel linearization under randomized interleavings** — ≥3
  panels, ≥8 client threads mixing submit/submit_many/append/evict.
  Within a panel, requests execute in ticket (submit) order, so a CCM
  answer must bit-match the singleton ``ccm_batch`` oracle at exactly
  version = #appends on that panel with a smaller ticket. Derandomized
  hypothesis drives the schedules.
* **Eviction parity** — evict → rebuild and evict → re-append bit-match
  a never-evicted session across E/τ/Δt grids including duplicate-tie
  panels; the LRU honors the byte budget under interleaved multi-panel
  load.
* **Worker liveness** — a dead drain worker turns ``/healthz`` degraded
  (it used to answer healthy) and ``revive_workers`` restores service.
* **Error paths** — an op raising mid-batch fails only the affected
  futures; a failed append neither wedges the panel queue nor leaks the
  version barrier.
* **Subscriptions** — every append tick pushes re-scored ρ that
  bit-matches a never-evicted direct session at that version.
"""

import threading

import numpy as np
import pytest

from repro import telemetry
from repro.edm.session import EDM
from repro.serving import EDMServer, serve_http  # noqa: F401 (HTTP below)


def _panel(n, length, seed, tie=False):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, length)).astype(np.float32)
    if tie:  # heavy value collisions → exercises master tie ordering
        x = np.round(x * 2) / 2
    return x


def _grown(full, L0, L, **cfg):
    """The direct session a server's panel is after its appends:
    registered at L0, the rest appended (the first append sizes its
    capacity), its master then built at that capacity."""
    sess = EDM(full[:, :L0], **cfg)
    if L > L0:
        sess.append(full[:, L0:L])
    sess.optimal_E()
    return sess


def _drain_all(srv):
    sizes = []
    while True:
        n = srv.scheduler.drain_once()
        if not n:
            return sizes
        sizes.append(n)


# ---------------------------------------------------- pool structure


def test_round_robin_across_panels_keeps_per_panel_fifo():
    """Ready-list rotation: a busy panel's remainder goes behind other
    panels, but never reorders within the panel."""
    pa, pb = _panel(4, 200, 0), _panel(4, 200, 1)
    with telemetry.record() as rec, EDMServer(autostart=False) as srv:
        srv.register_panel("a", pa, E_max=3, cache=True)
        srv.register_panel("b", pb, E_max=3, cache=True)
        srv.submit("ccm", "a", lib=0, target=1, E=3)
        srv.submit("ccm", "a", lib=1, target=2, E=2)   # incompatible tail
        srv.submit("ccm", "b", lib=0, target=1, E=3)
        assert _drain_all(srv) == [1, 1, 1]
    batches = rec.spans("serve.batch")
    assert [b["attrs"]["panel"] for b in batches] == ["a", "b", "a"]


def test_distinct_panels_drain_concurrently_under_pool():
    """Two panels, two workers: a slow op on panel a must not block
    panel b's requests (the PR-8 single drain serialized them)."""
    pa, pb = _panel(4, 200, 2), _panel(4, 200, 3)
    gate = threading.Event()
    with EDMServer(autostart=False, workers=2) as srv:
        srv.register_panel("a", pa, E_max=3, cache=True)
        srv.register_panel("b", pb, E_max=3, cache=True)
        sched = srv.scheduler
        orig = sched._exec_one

        def slow(entry, r):
            if r.params.get("block"):
                assert gate.wait(30), "panel b never unblocked panel a"
            return orig(entry, r)

        sched._exec_one = slow
        sched.start()
        fa = srv.submit("simplex", "a", E=3, block=True)
        fb = srv.submit("simplex", "b", E=3)
        # b completes while a is still parked on the gate — impossible
        # with one drain worker.
        np.asarray(fb.result(timeout=30))
        assert not fa.done()
        gate.set()
        np.asarray(fa.result(timeout=30))


# -------------------------------------------- randomized linearization


try:  # optional dep: fall back to fixed seeds (≡ derandomize=True)
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

N_SER, L0 = 5, 140
DELTAS = 2          # appends available per panel
DT = 8              # columns per append tick
E_REQ = 3
PANELS = ("pan0", "pan1", "pan2")
PAIRS = [(0, 2), (1, 3), (0, 4), (2, 1), (3, 0), (4, 2)]


@pytest.fixture(scope="module")
def stress_world():
    """Panels, their append deltas, and singleton-ccm_batch oracles at
    every library version (the quiesced pre/post oracles)."""
    data = {p: _panel(N_SER, L0 + DELTAS * DT, seed=10 + i)
            for i, p in enumerate(PANELS)}
    oracles = {}
    for p, full in data.items():
        per_version = []
        for v in range(DELTAS + 1):
            sess = _grown(full, L0, L0 + v * DT, E_max=4, cache=True)
            per_version.append({pair: sess.ccm_batch([pair], E=E_REQ)[0]
                                for pair in PAIRS})
        oracles[p] = per_version
    deltas = {p: [full[:, L0 + v * DT: L0 + (v + 1) * DT]
                  for v in range(DELTAS)] for p, full in data.items()}
    return data, deltas, oracles


def _hyp_or_seeds(fn):
    """Drive by derandomized hypothesis when available, else the same
    deterministic schedule space via fixed-seed parametrize."""
    if _HAVE_HYPOTHESIS:
        return settings(max_examples=3, deadline=None, derandomize=True)(
            given(seed=st.integers(0, 2**16 - 1))(fn))
    return pytest.mark.parametrize("seed", [7, 1234, 40961])(fn)


@_hyp_or_seeds
def test_randomized_interleavings_linearize_per_panel(stress_world, seed):
    """8 client threads × random submit/submit_many/append/evict across
    3 panels: every served CCM answer must bit-match the singleton
    oracle at version = #appends on its panel with a smaller ticket
    (per-panel FIFO + version barrier = the full linearization)."""
    data, deltas, oracles = stress_world
    rng = np.random.default_rng(seed)
    with EDMServer(workers=3) as srv:
        for p in PANELS:
            srv.register_panel(p, data[p][:, :L0], E_max=4, cache=True)
            srv.call("optimal_E", p)
        remaining = {p: list(deltas[p]) for p in PANELS}
        alloc_lock = threading.Lock()
        ccm_log: list = []    # (panel, future)  — fut.ticket carries order
        app_log: list = []    # (panel, future)
        log_lock = threading.Lock()
        errs: list = []

        def worker(tid):
            try:
                trng = np.random.default_rng(seed * 1000 + tid)
                for _ in range(5):
                    p = PANELS[trng.integers(len(PANELS))]
                    roll = trng.random()
                    if roll < 0.15:
                        with alloc_lock:
                            delta = (remaining[p].pop(0)
                                     if remaining[p] else None)
                        if delta is not None:
                            f = srv.submit("append", p, delta=delta)
                            with log_lock:
                                app_log.append((p, f))
                            continue
                        roll = 0.5  # fall through to a query
                    if roll < 0.25:
                        srv.evict_panel(p)  # memory event, never answers
                    elif roll < 0.6:
                        pair = PAIRS[trng.integers(len(PAIRS))]
                        f = srv.submit("ccm", p, lib=pair[0],
                                       target=pair[1], E=E_REQ)
                        with log_lock:
                            ccm_log.append((p, pair, f))
                    else:
                        burst = [dict(lib=l, target=t, E=E_REQ)
                                 for l, t in PAIRS[:3]]
                        futs = srv.submit_many("ccm", p, burst)
                        with log_lock:
                            ccm_log.extend(
                                (p, pr, f)
                                for pr, f in zip(PAIRS[:3], futs))
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errs
        # Quiesce: every future resolved before we read tickets/answers.
        for _, f in app_log:
            f.result(timeout=60)
        append_tickets = {p: sorted(f.ticket for q, f in app_log
                                    if q == p) for p in PANELS}
        for p, pair, f in ccm_log:
            rho = np.asarray(f.result(timeout=60))
            v = sum(t < f.ticket for t in append_tickets[p])
            np.testing.assert_array_equal(
                rho, oracles[p][v][pair],
                err_msg=f"{p} ticket {f.ticket} pair {pair}: answer is "
                        f"not the version-{v} singleton oracle")
        # Appends themselves linearize: versions 1..n in ticket order.
        for p in PANELS:
            got = [f.result(timeout=60)["version"]
                   for q, f in sorted(app_log, key=lambda it: it[1].ticket)
                   if q == p]
            assert got == list(range(1, len(got) + 1)), (p, got)
        # Post-quiesce, every panel answers at its final version exactly.
        for p in PANELS:
            v = len(append_tickets[p])
            for pair in PAIRS[:2]:
                np.testing.assert_array_equal(
                    np.asarray(srv.call("ccm", p, lib=pair[0],
                                        target=pair[1], E=E_REQ)),
                    oracles[p][v][pair])


# ------------------------------------------------------ eviction parity


@pytest.mark.parametrize("E,tau,dt", [(3, 1, 4), (4, 2, 7), (2, 1, 1)])
@pytest.mark.parametrize("tie", [False, True])
def test_evict_rebuild_and_reappend_bit_match_never_evicted(E, tau, dt, tie):
    full = _panel(5, 220 + dt, seed=100 * E + 10 * tau + dt, tie=tie)
    old, delta = full[:, :220], full[:, 220:]
    pairs = PAIRS[:4]
    never = EDM(old, E_max=4, tau=tau, cache=True)
    never.optimal_E()
    pre = {p: never.ccm_batch([p], E=E)[0] for p in pairs}
    never.append(delta)  # master grown incrementally, never dropped
    post = {p: never.ccm_batch([p], E=E)[0] for p in pairs}

    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, E_max=4, tau=tau, cache=True)
        srv.submit("optimal_E", "p")
        _drain_all(srv)
        entry = srv.registry.get("p")
        assert entry.master_nbytes() > 0
        # evict → rebuild: cold queries bit-match the warm session
        assert srv.evict_panel("p") > 0
        assert entry.master_nbytes() == 0
        futs = [srv.submit("ccm", "p", lib=l, target=t, E=E)
                for l, t in pairs]
        _drain_all(srv)
        for p, f in zip(pairs, futs):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=30)), pre[p],
                err_msg=f"evict->rebuild pair {p} (E={E} tau={tau})")
        # evict → re-append: the appended-after-eviction panel still
        # bit-matches the never-evicted incremental session
        assert srv.evict_panel("p") > 0
        fa = srv.submit("append", "p", delta=delta)
        futs = [srv.submit("ccm", "p", lib=l, target=t, E=E)
                for l, t in pairs]
        _drain_all(srv)
        assert fa.result(timeout=30)["L"] == full.shape[1]
        for p, f in zip(pairs, futs):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=30)), post[p],
                err_msg=f"evict->reappend pair {p} (E={E} tau={tau} "
                        f"dt={dt} tie={tie})")


def test_lru_honors_byte_budget_under_interleaved_load():
    """3 panels, budget ≈ 1.5 masters: totals stay within budget (the
    MRU master is exempt by design), evictions hit the COLDEST panel,
    and every answer stays bit-identical."""
    panels = {f"p{i}": _panel(6, 260, seed=40 + i) for i in range(3)}
    oracle = {}
    for name, data in panels.items():
        s = EDM(data, E_max=4, cache=True)
        s.optimal_E()
        oracle[name] = s.ccm_batch(PAIRS[:3], E=3)
    with telemetry.record() as rec, EDMServer(autostart=False) as srv:
        for name, data in panels.items():
            srv.register_panel(name, data, E_max=4, cache=True)
            srv.submit("optimal_E", name)
        _drain_all(srv)
        one = srv.registry.get("p0").master_nbytes()
        assert one > 0
        assert srv.registry.master_bytes_total() == 3 * one
        srv.registry.set_budget(int(1.5 * one))
        rounds = ["p0", "p1", "p2", "p0", "p2", "p1", "p0"]
        for name in rounds:
            futs = [srv.submit("ccm", name, lib=l, target=t, E=3)
                    for l, t in PAIRS[:3]]
            _drain_all(srv)
            got = np.asarray([f.result(timeout=30) for f in futs])
            np.testing.assert_array_equal(
                got, oracle[name], err_msg=f"post-eviction answers {name}")
            # ≤ budget once eviction can help (MRU exemption: a single
            # master fits the 1.5× budget, so totals must comply).
            assert srv.registry.master_bytes_total() <= int(1.5 * one), \
                f"budget violated after {name}"
    assert rec.counter_delta("serve_evictions") >= 3
    infos = {i["name"]: i for i in srv.registry.infos()}
    assert sum(i["evictions"] for i in infos.values()) >= 3


# ----------------------------------------------------- worker liveness


def test_healthz_degrades_on_dead_worker_and_recovers():
    """A dead drain worker must flip /healthz to degraded (it used to
    stay green) and revive_workers() must restore service."""
    with EDMServer(workers=2) as srv:
        srv.register_panel("p", _panel(4, 200, 7), E_max=3, cache=True)
        srv.call("optimal_E", "p")
        assert srv.health()["ok"]
        sched = srv.scheduler
        orig = sched._exec_one

        def boom(entry, r):
            if r.params.get("poison"):
                raise SystemExit("injected worker death")
            return orig(entry, r)

        sched._exec_one = boom
        f = srv.submit("simplex", "p", E=3, poison=True)
        with pytest.raises(RuntimeError, match="worker died"):
            f.result(timeout=30)
        for _ in range(100):  # the dying thread's epilogue races us
            h = srv.health()
            if not h["ok"]:
                break
            threading.Event().wait(0.05)
        assert not h["ok"], "healthz stayed green with a dead worker"
        assert sum(not w["alive"] for w in h["workers"]) == 1
        assert srv.health()["queues"] == {"p": 0}
        # Recovery: respawn, then the pool serves again (poison cleared).
        sched._exec_one = orig
        assert sched.revive_workers() == 1
        assert srv.health()["ok"]
        np.asarray(srv.call("simplex", "p", E=3))


def test_healthz_http_reports_503_when_degraded():
    with EDMServer(workers=1) as srv:
        srv.register_panel("p", _panel(4, 200, 8), E_max=3)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        import json
        import urllib.error
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            body = json.loads(r.read())
            assert r.status == 200 and body["ok"]
            assert body["workers"][0]["alive"]
            assert body["queues"] == {}
        sched = srv.scheduler
        sched._exec_one = lambda entry, r: (_ for _ in ()).throw(
            SystemExit("die"))
        try:
            srv.submit("simplex", "p", E=3).exception(timeout=30)
            deadline = 100
            while deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/healthz",
                            timeout=30) as r:
                        pass
                except urllib.error.HTTPError as e:
                    assert e.code == 503
                    degraded = json.loads(e.read())
                    assert not degraded["ok"]
                    break
                deadline -= 1
                threading.Event().wait(0.05)
            assert deadline, "healthz never degraded over HTTP"
        finally:
            httpd.shutdown()


# --------------------------------------------------------- error paths


def test_mid_batch_failure_hits_only_affected_futures():
    """In a loop-executed (dedup) batch, one request raising must fail
    that future alone — its batch peers still get results, and the
    panel queue keeps draining."""
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", _panel(4, 200, 9), E_max=3, cache=True)
        sched = srv.scheduler
        orig = sched._exec_one
        doomed = set()

        def picky(entry, r):
            if r.ticket in doomed:
                raise RuntimeError(f"injected failure #{r.ticket}")
            return orig(entry, r)

        sched._exec_one = picky
        futs = [srv.submit("simplex", "p", E=3) for _ in range(3)]
        doomed.add(futs[1].ticket)
        assert sched.drain_once() == 3  # one dedup batch of 3
        ok0 = np.asarray(futs[0].result(timeout=30))
        with pytest.raises(RuntimeError, match="injected failure"):
            futs[1].result(timeout=30)
        np.testing.assert_array_equal(
            np.asarray(futs[2].result(timeout=30)), ok0)
        # queue not wedged: a follow-up request drains normally
        f = srv.submit("optimal_E", "p")
        assert sched.drain_once() == 1
        f.result(timeout=30)


def test_shared_launch_failure_fails_batch_but_not_queue():
    """A coalesced CCM batch shares ONE launch: if it raises, all its
    futures fail together — but later batches still execute."""
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", _panel(5, 200, 11), E_max=3, cache=True)
        srv.submit("optimal_E", "p")
        _drain_all(srv)
        sess = srv.registry.get("p").sess
        orig = sess.ccm_batch
        calls = {"n": 0}

        def flaky(pairs, *, E):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient engine failure")
            return orig(pairs, E=E)

        sess.ccm_batch = flaky
        futs = [srv.submit("ccm", "p", lib=l, target=t, E=3)
                for l, t in PAIRS[:3]]
        assert srv.scheduler.drain_once() == 3
        for f in futs:
            with pytest.raises(RuntimeError, match="transient"):
                f.result(timeout=30)
        retry = [srv.submit("ccm", "p", lib=l, target=t, E=3)
                 for l, t in PAIRS[:3]]
        assert srv.scheduler.drain_once() == 3
        got = [np.asarray(f.result(timeout=30)) for f in retry]
        del sess.ccm_batch  # restore the bound method
        want = sess.ccm_batch([(l, t) for l, t in PAIRS[:3]], E=3)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_failed_append_neither_wedges_queue_nor_leaks_barrier():
    """A rejected append (NaN delta) fails only its own future; the
    requests queued BEHIND its barrier still execute and answer at the
    UN-appended version, and a later valid append works normally."""
    full = _panel(5, 160, seed=12)
    old, bad, good = full[:, :140], full[:, 140:150].copy(), full[:, 140:150]
    bad[1, 3] = np.nan
    d_old = EDM(old, E_max=3, cache=True)
    d_old.optimal_E()
    d_new = _grown(full, 140, 150, E_max=3, cache=True)
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, names=[f"s{i}" for i in range(5)],
                           E_max=3, cache=True)
        srv.submit("optimal_E", "p")
        _drain_all(srv)
        fa = srv.submit("append", "p", delta=bad)
        behind = [srv.submit("ccm", "p", lib=l, target=t, E=2)
                  for l, t in PAIRS[:3]]
        sizes = _drain_all(srv)
        assert sizes == [1, 3]  # failed append solo, queries still batch
        with pytest.raises(ValueError, match="series s1"):
            fa.result(timeout=30)
        entry = srv.registry.get("p")
        assert entry.version == 0 and entry.queued_version == 1
        for p, f in zip(PAIRS[:3], behind):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=30)),
                d_old.ccm_batch([p], E=2)[0],
                err_msg=f"behind-failed-append pair {p}")
        # barrier not leaked: a valid append still versions cleanly
        fa2 = srv.submit("append", "p", delta=good)
        after = [srv.submit("ccm", "p", lib=l, target=t, E=2)
                 for l, t in PAIRS[:3]]
        _drain_all(srv)
        assert fa2.result(timeout=30)["version"] == 1
        assert entry.queued_version == 2
        for p, f in zip(PAIRS[:3], after):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=30)),
                d_new.ccm_batch([p], E=2)[0],
                err_msg=f"post-valid-append pair {p}")


# -------------------------------------------------------- subscriptions


def test_subscription_ticks_bit_match_direct_sessions():
    full = _panel(5, 156, seed=13)
    old = full[:, :140]
    ticks = [full[:, 140:148], full[:, 148:156]]
    watch = PAIRS[:3]
    sessions = []
    for v in range(3):
        sessions.append(_grown(full, 140, 140 + v * 8, E_max=3, cache=True))
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, E_max=3, cache=True)
        srv.submit("optimal_E", "p")
        fs = srv.submit("subscribe", "p", pairs=watch, E=2)
        _drain_all(srv)
        info = fs.result(timeout=30)
        sub = srv.subscription(info["id"])
        np.testing.assert_array_equal(
            np.asarray(info["rho"]), sessions[0].ccm_batch(watch, E=2))
        base = sub.poll()
        assert len(base) == 1 and base[0]["version"] == 0
        assert base[0]["d_rho"] is None
        for v, delta in enumerate(ticks, start=1):
            srv.submit("append", "p", delta=delta)
            _drain_all(srv)
            got = sub.poll()
            assert len(got) == 1
            t = got[0]
            assert t["version"] == v and t["L"] == 140 + v * 8
            np.testing.assert_array_equal(
                t["rho"], sessions[v].ccm_batch(watch, E=2),
                err_msg=f"tick {v} not bit-identical to direct session")
            np.testing.assert_array_equal(
                t["d_rho"],
                sessions[v].ccm_batch(watch, E=2)
                - sessions[v - 1].ccm_batch(watch, E=2))
        assert sub.poll(timeout=0.01) == []
        srv.unsubscribe(info["id"])
        with pytest.raises(KeyError):
            srv.subscription(info["id"])


def test_subscription_survives_eviction_bitwise():
    """Evicting the panel between ticks must not change a single pushed
    bit — the append path re-grows from the rebuilt master."""
    full = _panel(5, 152, seed=14)
    old, d1, d2 = full[:, :140], full[:, 140:146], full[:, 146:152]
    watch = PAIRS[:2]
    g1 = _grown(full, 140, 146, E_max=3, cache=True)
    g2 = _grown(full, 140, 152, E_max=3, cache=True)
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, E_max=3, cache=True)
        fs = srv.submit("subscribe", "p", pairs=watch, E=2)
        srv.submit("append", "p", delta=d1)
        _drain_all(srv)
        sub = srv.subscription(fs.result(timeout=30)["id"])
        srv.evict_panel("p")
        srv.submit("append", "p", delta=d2)
        _drain_all(srv)
        got = sub.poll()
        assert [t["version"] for t in got] == [0, 1, 2]
        np.testing.assert_array_equal(got[1]["rho"],
                                      g1.ccm_batch(watch, E=2))
        np.testing.assert_array_equal(got[2]["rho"],
                                      g2.ccm_batch(watch, E=2))


def test_subscription_http_roundtrip_long_poll():
    import json
    import urllib.request
    full = _panel(4, 148, seed=15)
    old, delta = full[:, :140], full[:, 140:]
    grown = _grown(full, 140, 148, E_max=3, cache=True)
    with EDMServer() as srv:
        httpd = serve_http(srv)
        port = httpd.server_address[1]

        def post(path, body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                json.dumps(body).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        try:
            post("/v1/register", {"panel": "p", "data": old.tolist(),
                                  "E_max": 3, "cache": True})
            sid = post("/v1/subscribe",
                       {"panel": "p", "pairs": [[0, 2], [1, 3]],
                        "E": 2})["result"]["id"]
            post("/v1/append", {"panel": "p", "delta": delta.tolist()})
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v1/subscriptions/{sid}"
                    f"?timeout=10", timeout=60) as r:
                ticks = json.loads(r.read())["ticks"]
            assert [t["version"] for t in ticks] == [0, 1]
            want = grown.ccm_batch([(0, 2), (1, 3)], E=2)
            got = np.asarray([np.nan if v is None else v
                              for v in ticks[1]["rho"]], np.float32)
            np.testing.assert_array_equal(got, want)
            assert post("/v1/unsubscribe", {"id": sid})["result"]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v1/subscriptions/{sid}"
                    "?timeout=0") as r:
                raise AssertionError("poll of closed sub should 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
        finally:
            httpd.shutdown()
