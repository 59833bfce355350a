"""EDM server: scheduler coalescing, append barriers, HTTP front end.

The serving contracts (ISSUE 8):

* FIFO across signatures — a batch never executes before an earlier
  incompatible request.
* Compatible CCM requests coalesce into ONE launch whose per-request
  answers are bit-identical to direct ``EDM`` session calls (telemetry
  counter-delta assertions, PR-7 style — no monkeypatching).
* An append is a version barrier: requests behind it see the grown
  library, requests ahead of it the old one, and every answer is
  bit-identical to the quiesced ordering.
* The submit API is thread-safe under concurrent clients.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from repro import telemetry
from repro.edm.session import EDM
from repro.serving import EDMServer, serve_http


@pytest.fixture(scope="module")
def panel():
    from repro.data.timeseries import forced_network_panel
    p, _ = forced_network_panel(6, 300, seed=9)
    return np.asarray(p)


PAIRS = [(0, 2), (1, 3), (0, 4), (2, 5), (1, 2), (3, 0)]


def _direct(panel, grown_from=None):
    """A direct session on ``panel``; with ``grown_from`` L0, the one a
    server's appends give it — registered at L0, the rest appended —
    which holds it at the capacity the first append sizes."""
    if grown_from is None:
        sess = EDM(panel, E_max=4, cache=True)
    else:
        sess = EDM(panel[:, :grown_from], E_max=4, cache=True)
        sess.append(panel[:, grown_from:])
    sess.optimal_E()
    return sess


# ------------------------------------------------------------ coalescing


def test_compatible_ccm_requests_coalesce_into_one_launch(panel):
    old = panel[:, :280]
    with telemetry.record() as rec, EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, E_max=4, cache=True)
        srv.submit("optimal_E", "p")
        srv.scheduler.drain_once()
        futs = [srv.submit("ccm", "p", lib=l, target=t, E=3)
                for l, t in PAIRS]
        assert srv.scheduler.drain_once() == len(PAIRS)  # one batch
        got = [f.result(timeout=5) for f in futs]
    # ONE coalesced launch, n−1 launches saved — counter-delta style.
    assert rec.counter_delta("serve_ccm_group_launches") == 1
    assert rec.counter_delta("serve_batches") == 2  # optimal_E + ccm batch
    assert rec.counter_delta("serve_launches_saved") == len(PAIRS) - 1
    assert rec.counter_delta("serve_requests") == len(PAIRS) + 1
    direct = _direct(old)
    for (l, t), rho in zip(PAIRS, got):
        # bit-identical to the direct session call (singleton ccm_batch
        # is the quiesced oracle — batch composition must not matter)...
        np.testing.assert_array_equal(
            np.asarray(rho), direct.ccm_batch([(l, t)], E=3)[0],
            err_msg=f"pair ({l},{t}) not bit-identical to direct call")
        # ...and numerically the classic single-pair engine's answer.
        np.testing.assert_allclose(
            np.asarray(rho), np.asarray(direct.ccm(l, t, E=3)),
            rtol=1e-6, atol=1e-6)


def test_queue_wait_exec_and_dispatch_counters(panel):
    """The always-on clocks behind ``/metrics``: one drained batch of n
    ccm requests claims n requests, and adds queue-wait, execution and
    engine-dispatch seconds."""
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", panel, E_max=4, cache=True)
        srv.submit("optimal_E", "p")
        srv.scheduler.drain_once()
        with telemetry.record() as rec:
            futs = [srv.submit("ccm", "p", lib=l, target=t, E=3)
                    for l, t in PAIRS]
            assert srv.scheduler.drain_once() == len(PAIRS)
            for f in futs:
                f.result(timeout=5)
    assert rec.counter_delta("serve_claimed") == len(PAIRS)
    assert rec.counter_delta("serve_batches") == 1
    for name in ("serve_queue_wait_seconds", "serve_exec_seconds",
                 "edm_dispatch_seconds"):
        assert rec.counter_delta(name) > 0, name
        assert f"# TYPE {name} counter" in telemetry.render_prom()


def test_fifo_across_mixed_signatures(panel):
    """A later-arriving compatible request must not leapfrog an earlier
    incompatible one: batches run in head-of-queue arrival order."""
    old = panel[:, :280]
    with telemetry.record() as rec, EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, E_max=4, cache=True)
        srv.submit("ccm", "p", lib=0, target=2, E=3)
        srv.submit("ccm", "p", lib=1, target=3, E=2)   # different E
        srv.submit("simplex", "p", E=3)
        srv.submit("ccm", "p", lib=0, target=4, E=3)   # compatible w/ head
        sizes = []
        while True:
            n = srv.scheduler.drain_once()
            if not n:
                break
            sizes.append(n)
    # E=3 head coalesces with the 4th request; E=2 and simplex stay solo
    # and execute in arrival order between them.
    assert sizes == [2, 1, 1]
    batches = [e for e in rec.spans("serve.batch")]
    assert [b["attrs"]["op"] for b in batches] == ["ccm", "ccm", "simplex"]
    assert [b["attrs"]["size"] for b in batches] == [2, 1, 1]


def test_duplicate_panel_ops_dedup_to_one_execution(panel):
    old = panel[:, :280]
    with telemetry.record() as rec, EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, E_max=4, cache=True)
        futs = [srv.submit("optimal_E", "p") for _ in range(4)]
        assert srv.scheduler.drain_once() == 4
        results = [f.result(timeout=5) for f in futs]
    assert rec.counter_delta("serve_batches") == 1
    assert rec.counter_delta("edm_knn_master_builds") == 1  # ONE compute
    for E_opt, rho in results[1:]:
        np.testing.assert_array_equal(E_opt, results[0][0])
        np.testing.assert_array_equal(rho, results[0][1])


def test_ccm_batch_is_batch_invariant(panel):
    """The serving bit contract: a pair's ρ is independent of which
    other pairs share its launch (singleton == any batch)."""
    sess = _direct(panel[:, :280])
    full = sess.ccm_batch(PAIRS, E=3)
    for j, pair in enumerate(PAIRS):
        np.testing.assert_array_equal(
            sess.ccm_batch([pair], E=3)[0], full[j],
            err_msg=f"pair {pair} depends on batch composition")
    np.testing.assert_array_equal(
        sess.ccm_batch(PAIRS[2:5], E=3), full[2:5])
    # and numerically equivalent to the classic engine
    for j, (l, t) in enumerate(PAIRS):
        np.testing.assert_allclose(full[j], np.asarray(sess.ccm(l, t, E=3)),
                                   rtol=1e-6, atol=1e-6)


# --------------------------------------------------------- append barrier


def test_append_sequences_against_inflight_compatible_batch(panel):
    """Requests queued before/after an append resolve against the
    pre-/post-append library — bit-identical to the quiesced order."""
    old, delta = panel[:, :280], panel[:, 280:]
    with telemetry.record() as rec, EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, E_max=4, cache=True)
        srv.submit("optimal_E", "p")
        srv.scheduler.drain_once()
        pre = [srv.submit("ccm", "p", lib=l, target=t, E=3)
               for l, t in PAIRS[:3]]
        fa = srv.submit("append", "p", delta=delta)
        post = [srv.submit("ccm", "p", lib=l, target=t, E=3)
                for l, t in PAIRS[:3]]
        sizes = []
        while True:
            n = srv.scheduler.drain_once()
            if not n:
                break
            sizes.append(n)
        # pre-batch coalesced, append solo (barrier), post-batch coalesced
        assert sizes == [3, 1, 3]
        assert fa.result(timeout=5)["L"] == panel.shape[1]
        assert rec.counter_delta("serve_appends") == 1
        assert rec.counter_delta("edm_knn_master_appends") == 1  # no rebuild
        d_old = _direct(old)
        d_new = _direct(panel, grown_from=280)
        for (l, t), f in zip(PAIRS[:3], pre):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=5)),
                d_old.ccm_batch([(l, t)], E=3)[0],
                err_msg=f"pre-append pair ({l},{t})")
        for (l, t), f in zip(PAIRS[:3], post):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=5)),
                d_new.ccm_batch([(l, t)], E=3)[0],
                err_msg=f"post-append pair ({l},{t})")


def test_append_rejects_nan_delta_and_names_series(panel):
    old, delta = panel[:, :280], panel[:, 280:].copy()
    delta[2, 1] = np.nan
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", old, names=[f"s{i}" for i in range(6)],
                           E_max=4)
        fut = srv.submit("append", "p", delta=delta)
        srv.scheduler.drain_once()
        with pytest.raises(ValueError, match="series s2"):
            fut.result(timeout=5)
        # server state untouched: panel length unchanged, next op fine
        assert srv.registry.get("p").sess.data.L == 280


# ------------------------------------------------------- capacity panels
#
# A panel registers exact; its first append sizes a capacity C with room
# to grow (``dataset.grown_capacity``), and the append and ccm programs
# then read the valid length as an operand until L passes C.


class _Compiles:
    """Backend compiles (XLA), counted from JAX's compile events while
    ``on`` — the harness's ``CompileClock`` count."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.n += 1


def _drain(srv, futs):
    while srv.scheduler.drain_once():
        pass
    return [f.result(timeout=5) for f in futs]


def _ccm_round(srv):
    return _drain(srv, [srv.submit("ccm", "p", lib=l, target=t, E=3)
                        for l, t in PAIRS]
                  + [srv.submit("ccm", "p", lib=0, target=5, E=2)])


def _session_at(grown, L0, C):
    """A session on ``grown`` whose master is built cold at capacity C
    (``_direct`` with the rest appended before the master exists)."""
    sess = _direct(grown, grown_from=L0 if grown.shape[1] > L0 else None)
    assert sess.data.capacity == C
    return sess


def test_appends_within_capacity_compile_nothing(panel):
    compiles = _Compiles()
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", panel[:, :268], E_max=4, cache=True)
        _drain(srv, [srv.submit("optimal_E", "p")])
        _drain(srv, [srv.submit("append", "p", delta=panel[:, 268:272])])
        _ccm_round(srv)                       # warms every (E, batch) shape
        C = srv.registry.get("p").sess.data.capacity
        compiles.on = True
        with telemetry.record() as rec:
            for a in range(272, 300, 4):      # 7 appends within capacity
                _drain(srv, [srv.submit("append", "p",
                                        delta=panel[:, a:a + 4])])
                got = _ccm_round(srv)
        compiles.on = False
        assert srv.registry.get("p").sess.data.L == 300
    assert C == 384
    assert compiles.n == 0, f"{compiles.n} compiles after warm-up"
    assert rec.counter_delta("serve_appends") == 7
    assert rec.counter_delta("edm_capacity_regrows") == 0
    assert rec.counter_delta("serve_append_seconds") > 0
    cold = _session_at(panel, 268, C)
    want = [cold.ccm_batch([p], E=3)[0] for p in PAIRS] + [
        cold.ccm_batch([(0, 5)], E=2)[0]]
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)


def test_served_ccm_after_appends_matches_ref_on_grown_panel(panel):
    """Each answer equals the cold session at the same capacity on the
    panel as of its version, bit for bit, and the plain jnp engine
    (``impl="ref"``, exact shapes, no master) to float32 rounding."""
    import jax.numpy as jnp

    from repro.core.ccm import ccm_group_batched

    L0, dt = 260, 10
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", panel[:, :L0], E_max=4, cache=True)
        _drain(srv, [srv.submit("optimal_E", "p")])
        answers = {}
        for v in range(5):
            if v:
                _drain(srv, [srv.submit(
                    "append", "p", delta=panel[:, L0 + (v - 1) * dt:
                                                L0 + v * dt])])
            answers[v] = _ccm_round(srv)
    for v, got in answers.items():
        grown = panel[:, :L0 + v * dt]
        cold = _session_at(grown, L0, 384 if v else L0)
        want = [cold.ccm_batch([p], E=3)[0] for p in PAIRS] + [
            cold.ccm_batch([(0, 5)], E=2)[0]]
        np.testing.assert_array_equal(np.asarray(got, np.float32), want,
                                      err_msg=f"version {v}")
        X = jnp.asarray(grown)
        plain = [float(np.asarray(ccm_group_batched(
            X[l:l + 1], X[t:t + 1], E=E, tau=1, Tp=0, impl="ref"))[0, 0])
            for (l, t), E in zip(PAIRS + [(0, 5)], [3] * len(PAIRS) + [2])]
        np.testing.assert_allclose(got, plain, rtol=0, atol=1e-5,
                                   err_msg=f"version {v}")


@pytest.mark.parametrize("compact_every", [64, 2])
def test_recover_restores_capacity_and_panel(panel, tmp_path,
                                             compact_every):
    """Replayed from the WAL alone, or from a snapshot plus its tail."""
    with EDMServer(autostart=False, state_dir=str(tmp_path),
                   compact_every=compact_every) as srv:
        srv.register_panel("p", panel[:, :100], E_max=4, cache=True)
        with telemetry.record() as rec:
            for a in range(100, 300, 40):     # sized at 140, regrown at 260
                _drain(srv, [srv.submit("append", "p",
                                        delta=panel[:, a:a + 40])])
        sess = srv.registry.get("p").sess
        want_C = sess.data.capacity
        before = _ccm_round(srv)
    assert want_C == 384 and rec.counter_delta("edm_capacity_regrows") == 2
    rec = EDMServer.recover(str(tmp_path), autostart=False)
    try:
        data = rec.registry.get("p").sess.data
        assert (data.capacity, data.L) == (want_C, 300)
        np.testing.assert_array_equal(np.asarray(data.panel), panel)
        np.testing.assert_array_equal(
            np.asarray(_ccm_round(rec), np.float32),
            np.asarray(before, np.float32))
    finally:
        rec.close()


def test_freeze_heap_holds_the_warm_heap_until_close(panel):
    """A warm server's objects leave the cyclic collector; serving goes
    on bit for bit, and closing the server thaws them."""
    import gc

    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", panel, E_max=4, cache=True)
        before = _ccm_round(srv)
        assert srv.freeze_heap() > 0 and gc.get_freeze_count() > 0
        assert _ccm_round(srv) == before
    assert gc.get_freeze_count() == 0


# --------------------------------------------------------- threaded mode


def test_concurrent_clients_threaded_worker(panel):
    old = panel[:, :280]
    direct = _direct(old)
    want = {(l, t): direct.ccm_batch([(l, t)], E=3)[0] for l, t in PAIRS}
    with EDMServer() as srv:  # live worker thread
        srv.register_panel("p", old, E_max=4, cache=True)
        srv.call("optimal_E", "p")
        results: dict = {}
        errs: list = []

        def client(pair):
            try:
                results[pair] = np.asarray(
                    srv.call("ccm", "p", lib=pair[0], target=pair[1], E=3))
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [threading.Thread(target=client, args=(p,))
                   for p in PAIRS * 3]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs
    for pair, rho in results.items():
        np.testing.assert_array_equal(rho, want[pair],
                                      err_msg=f"pair {pair}")


def test_append_during_inflight_traffic_is_linearized(panel):
    """Concurrent clients + one append tick: every answer matches the
    pre- or post-append direct value, and anything submitted after the
    append resolves matches post-append exactly."""
    old, delta = panel[:, :280], panel[:, 280:]
    d_old = _direct(old)
    d_new = _direct(panel, grown_from=280)
    pre = {p: d_old.ccm_batch([p], E=3)[0] for p in PAIRS}
    post = {p: d_new.ccm_batch([p], E=3)[0] for p in PAIRS}
    with EDMServer() as srv:
        srv.register_panel("p", old, E_max=4, cache=True)
        srv.call("optimal_E", "p")
        answers: list = []
        errs: list = []

        def client(pair):
            try:
                answers.append(
                    (pair, np.asarray(srv.call("ccm", "p", lib=pair[0],
                                               target=pair[1], E=3))))
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [threading.Thread(target=client, args=(p,))
                   for p in PAIRS * 2]
        for t in threads[:6]:
            t.start()
        fa = srv.submit("append", "p", delta=delta)
        for t in threads[6:]:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs and fa.result(timeout=60)["L"] == panel.shape[1]
        for pair, rho in answers:
            assert (np.array_equal(rho, pre[pair])
                    or np.array_equal(rho, post[pair])), \
                f"pair {pair}: answer matches neither library version"
        # quiesced: everything from here on is post-append, exactly
        for pair in PAIRS:
            np.testing.assert_array_equal(
                np.asarray(srv.call("ccm", "p", lib=pair[0],
                                    target=pair[1], E=3)), post[pair])


# ---------------------------------------------------------------- errors


def test_unknown_panel_and_op_rejected(panel):
    with EDMServer(autostart=False) as srv:
        with pytest.raises(KeyError, match="ghost"):
            srv.submit("ccm", "ghost", lib=0, target=1)
        srv.register_panel("p", panel[:, :280])
        with pytest.raises(ValueError, match="unknown op"):
            srv.submit("smap_all_the_things", "p")
        with pytest.raises(ValueError, match="already registered"):
            srv.register_panel("p", panel[:, :280])


# ------------------------------------------------------------------ HTTP


def test_http_front_end_roundtrip(panel):
    old, delta = panel[:, :280], panel[:, 280:]
    with EDMServer() as srv:
        httpd = serve_http(srv)
        port = httpd.server_address[1]

        def post(path, body, code=200):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                json.dumps(body).encode(),
                {"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    assert r.status == code
                    return json.loads(r.read())
            except urllib.error.HTTPError as e:
                assert e.code == code
                return json.loads(e.read())

        info = post("/v1/register",
                    {"panel": "p", "data": old.tolist(), "E_max": 4})
        assert info["result"]["L"] == 280
        rho = post("/v1/ccm",
                   {"panel": "p", "lib": 0, "target": 2, "E": 3})["result"]
        direct = _direct(old)
        assert rho == pytest.approx(float(direct.ccm(0, 2, E=3)))
        grown = post("/v1/append",
                     {"panel": "p", "delta": delta.tolist()})["result"]
        assert grown["L"] == panel.shape[1] and grown["version"] == 1
        assert post("/v1/ccm", {"panel": "ghost", "lib": 0, "target": 1},
                    code=400)["error"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            prom = r.read().decode()
        assert "serve_requests" in prom and "serve_queue_depth" in prom
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/panels", timeout=30) as r:
            panels = json.loads(r.read())["panels"]
        assert panels[0]["name"] == "p" and panels[0]["version"] == 1
        httpd.shutdown()


def test_http_malformed_bodies_all_get_400(panel):
    """Every malformed-body shape gets a named 400, never a 500."""
    with EDMServer(autostart=False) as srv:
        srv.register_panel("p", panel[:, :280], E_max=4, cache=True)
        httpd = serve_http(srv)
        port = httpd.server_address[1]

        def post_raw(path, payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}", payload,
                {"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        def post(path, body):
            return post_raw(path, json.dumps(body).encode())

        cases = [
            (post("/v1/ccm", {"lib": 0, "target": 1}), "missing 'panel'"),
            (post("/v1/register", {"panel": "q"}), "missing 'data'"),
            (post("/v1/append", {"panel": "p"}), "missing 'delta'"),
            (post("/v1/unsubscribe", {}), "missing 'id'"),
            (post("/v1/ccm", [1, 2, 3]), "JSON object"),
        ]
        for (code, body), needle in cases:
            assert code == 400, f"expected 400 for {needle!r}, got {code}"
            assert needle in body["error"]
        # undecodable JSON is a 400 too (ValueError path), not a 500
        code, body = post_raw("/v1/ccm", b"{not json")
        assert code == 400 and body["error"]
        # and op-level validation errors surface as 400 with the message
        code, body = post("/v1/ccm", {"panel": "ghost", "lib": 0,
                                      "target": 1})
        assert code == 400 and "ghost" in body["error"]
        httpd.shutdown()


def test_subscription_poll_survives_spurious_wakeup(panel):
    """A notify_all with no tick queued must NOT end the long-poll
    early: poll re-waits on the remaining deadline (regression for the
    spurious-wakeup early return)."""
    import time as _time

    from repro.serving import Subscription
    sub = Subscription("s-spur", "p", [(0, 1)], {3: [0]})

    def spurious():
        for _ in range(3):
            _time.sleep(0.05)
            with sub._cv:
                sub._cv.notify_all()     # deliberate: no tick, no close

    t = threading.Thread(target=spurious)
    t0 = _time.monotonic()
    t.start()
    got = sub.poll(timeout=0.5)
    elapsed = _time.monotonic() - t0
    t.join()
    assert got == []                     # nothing was ever queued
    assert elapsed >= 0.45, \
        f"poll returned after {elapsed:.3f}s — spurious wakeup ended it"
    # ...while a REAL tick still ends the wait early
    def push_soon():
        _time.sleep(0.05)
        sub.push(1, 300, np.zeros(1, np.float32))

    t = threading.Thread(target=push_soon)
    t0 = _time.monotonic()
    t.start()
    got = sub.poll(timeout=5.0)
    elapsed = _time.monotonic() - t0
    t.join()
    assert len(got) == 1 and elapsed < 4.0
    # close() also ends the wait promptly with []
    def close_soon():
        _time.sleep(0.05)
        sub.close()

    t = threading.Thread(target=close_soon)
    t.start()
    assert sub.poll(timeout=5.0) == []
    t.join()


def test_http_client_disconnect_is_counted_not_crashed(panel):
    """A client that RSTs mid-long-poll is counted; the server keeps
    answering on other connections."""
    import socket
    import time as _time
    with telemetry.record() as rec, EDMServer() as srv:
        srv.register_panel("p", panel[:, :280], E_max=4, cache=True)
        sub = srv.subscribe("p", [(0, 2)], E=3)
        srv.subscription(sub["id"]).poll(timeout=5)   # eat baseline tick
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        s.sendall((f"GET /v1/subscriptions/{sub['id']}?timeout=1 "
                   f"HTTP/1.1\r\nHost: x\r\n\r\n").encode())
        _time.sleep(0.5)   # let the handler read the request + block
        # RST the connection while the handler is still in poll()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     __import__("struct").pack("ii", 1, 0))
        s.close()
        deadline = _time.monotonic() + 10
        while rec.counter_delta("serve_client_disconnects") < 1:
            assert _time.monotonic() < deadline, \
                "disconnect never counted"
            _time.sleep(0.05)
        # the server still serves post-disconnect
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/panels", timeout=30) as r:
            assert json.loads(r.read())["panels"][0]["name"] == "p"
        httpd.shutdown()


def _post_expect(port, path, body, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", json.dumps(body).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def test_http_429_overloaded_with_retry_after(panel):
    with EDMServer(autostart=False, max_queue_depth=1) as srv:
        srv.register_panel("p", panel[:, :280], E_max=4, cache=True)
        fill = srv.submit("ccm", "p", lib=0, target=2, E=3)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        code, headers, body = _post_expect(
            port, "/v1/ccm", {"panel": "p", "lib": 1, "target": 3,
                              "E": 3})
        assert code == 429
        assert int(headers["Retry-After"]) >= 1
        assert body["retry_after_s"] > 0
        assert "max_queue_depth" in body["error"]
        while srv.scheduler.drain_once():
            pass
        fill.result(timeout=5)
        # capacity is back: go live and the same request succeeds
        srv.scheduler.start()
        code, _, body = _post_expect(
            port, "/v1/ccm", {"panel": "p", "lib": 1, "target": 3,
                              "E": 3})
        assert code == 200
        httpd.shutdown()


def test_http_504_deadline_and_503_wedged_and_draining(panel):
    # 504: a live server claims the request after its 0-second deadline
    with telemetry.record() as rec, EDMServer() as srv:
        srv.register_panel("p", panel[:, :280], E_max=4, cache=True)
        httpd = serve_http(srv)
        port = httpd.server_address[1]
        code, _, body = _post_expect(
            port, "/v1/ccm", {"panel": "p", "lib": 0, "target": 2,
                              "E": 3, "deadline_s": 0.0})
        assert code == 504 and "deadline" in body["error"]
        httpd.shutdown()
    assert rec.counter_delta("serve_deadline_exceeded") == 1

    # 503: nothing drains an autostart=False server — the HTTP thread's
    # bounded wait fires instead of wedging the connection forever
    with telemetry.record() as rec, EDMServer(autostart=False) as srv:
        srv.register_panel("p", panel[:, :280], E_max=4, cache=True)
        httpd = serve_http(srv, request_timeout_s=0.3)
        port = httpd.server_address[1]
        code, _, body = _post_expect(
            port, "/v1/ccm", {"panel": "p", "lib": 0, "target": 2,
                              "E": 3})
        assert code == 503 and "timed out" in body["error"]

        # 503 while draining: admission is closed, healthz degrades
        while srv.scheduler.drain_once():   # retire the wedged request
            pass
        assert srv.drain(timeout=10) is True
        code, _, body = _post_expect(
            port, "/v1/ccm", {"panel": "p", "lib": 1, "target": 3,
                              "E": 3})
        assert code == 503 and "draining" in body["error"]
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
                code = r.status
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 503
        httpd.shutdown()
    assert rec.counter_delta("serve_request_timeouts") == 1
