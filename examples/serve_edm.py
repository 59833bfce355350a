"""Serving smoke + phase-2 soak: HTTP clients ≡ direct sessions, always.

The CI job for the serving subsystem (docs/ARCHITECTURE.md "Serving").
Part 1, the single-panel smoke:

* start an ``EDMServer`` behind the stdlib HTTP front end on an
  ephemeral port and register a panel over the wire;
* drive N concurrent client threads issuing compatible CCM requests
  (the scheduler coalesces them into group launches) plus ``optimal_E``
  and ``xmap`` panel ops, and assert every response **bit-matches** a
  direct in-process ``EDM`` session on the same panel — the served-
  answer contract: batching and transport never change bits
  (``EDM.ccm_batch`` on a singleton pair is the quiesced CCM oracle);
* submit one **append tick** through the server and assert post-append
  answers bit-match a COLD session built on the grown panel — the
  incremental kNN-master merge is indistinguishable from a rebuild;
* record the whole run to a telemetry JSONL sink and assert it is
  schema-valid and contains the serve spans/metrics CI expects.

Part 2, the multi-panel soak (~1 min wall budget): three panels behind
the worker pool with an LRU master byte budget sized to ~1.5 masters,
so round-robin load keeps evicting cold masters while concurrent HTTP
clients query all panels and per-panel append ticks stream through a
subscription. Every answer and every subscription tick must bit-match
the per-version direct-session oracle, ``/healthz`` must stay OK with
all workers alive, the registry must respect the byte budget, and at
least one eviction must actually have happened (else the soak proved
nothing).

Part 3, the durability smoke, runs FIRST: a CHILD process runs a
durable server (``state_dir=``) behind HTTP; the parent registers a
panel and streams append ticks over the wire, then **kill -9**'s the
child mid-stream. A restarted child recovers from the WAL and must serve
answers **bit-identical** to a cold session at the last acked version;
one more append then lands on the recovered log, SIGTERM drains the
child gracefully (exit 0), and a final in-process ``EDMServer.recover``
proves the whole history — pre-kill appends + post-recovery append —
replays to the same bits. A device belongs to one process: the parent
starts no JAX backend until the last child has exited, so it only
collects the served answers over HTTP and checks them afterwards.

Run: ``PYTHONPATH=src python examples/serve_edm.py [out_dir]``

With ``out_dir``, the event log lands at
``<out_dir>/serve/telemetry/events.jsonl`` so CI can schema-validate and
upload it; without, a tempdir is used. (``--child <state_dir>`` is the
internal durability-smoke entry point.)
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import urllib.request

import numpy as np

from repro import compile_cache, telemetry
from repro.data import timeseries as ts
from repro.edm import EDM, EDMConfig
from repro.serving import EDMServer, serve_http
from repro.telemetry import schema

N_CLIENTS = 6
E_REQ = 3
CFG = dict(E_max=4, cache=True)


def _post(port: int, op: str, **body) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/{op}",
        data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def _get(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as resp:
        return resp.read().decode()


def _bit_match(served, oracle: np.float32, what: str) -> None:
    got = np.float32(np.nan if served is None else served)
    ok = (got == oracle) or (np.isnan(got) and np.isnan(oracle))
    assert ok, f"{what}: served {got!r} != direct {oracle!r}"


def main() -> None:
    out = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp()
    log = os.path.join(out, "serve", "telemetry", "events.jsonl")
    sink = telemetry.JsonlSink(log)
    telemetry.add_sink(sink)

    panel, _ = ts.forced_network_panel(8, 300, seed=33)
    panel = np.asarray(panel, np.float32)
    rng = np.random.default_rng(5)
    delta = rng.standard_normal((panel.shape[0], 6)).astype(np.float32)

    # Direct oracles: the same answers with no server in the loop.
    direct = EDM(panel, EDMConfig(**CFG))
    direct_grown = EDM(np.concatenate([panel, delta], axis=1),
                       EDMConfig(**CFG))
    pairs = [(i, (i + 1) % panel.shape[0]) for i in range(panel.shape[0])]
    oracle = {p: direct.ccm_batch([p], E=E_REQ)[0] for p in pairs}
    oracle_grown = {p: direct_grown.ccm_batch([p], E=E_REQ)[0] for p in pairs}

    srv = EDMServer()
    httpd = serve_http(srv)
    port = httpd.server_address[1]
    try:
        _post(port, "register", panel="smoke", data=panel.tolist(), **CFG)

        # --- N concurrent clients, compatible CCM requests -> coalesced
        errors: list[BaseException] = []

        def client(cid: int) -> None:
            try:
                for lib, tgt in pairs[cid::2]:
                    r = _post(port, "ccm", panel="smoke",
                              lib=lib, target=tgt, E=E_REQ)["result"]
                    _bit_match(r, oracle[(lib, tgt)],
                               f"client {cid} ccm{(lib, tgt)}")
            except BaseException as exc:  # surface in the parent
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(N_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

        # --- panel ops over the wire match the direct session
        e_direct, rho_direct = direct.optimal_E()
        e_srv, rho_srv = _post(port, "optimal_E", panel="smoke")["result"]
        assert np.array_equal(np.asarray(e_srv, np.int32), e_direct)
        # JSON None -> NaN; float32 -> float64 repr -> float32 is exact,
        # so equality below is still bitwise.
        assert np.array_equal(np.asarray(rho_srv, np.float32),
                              np.asarray(rho_direct, np.float32),
                              equal_nan=True)
        x_srv = _post(port, "xmap", panel="smoke")["result"]
        assert np.array_equal(np.asarray(x_srv, np.float32),
                              np.asarray(direct.xmap(), np.float32),
                              equal_nan=True)

        # --- one append tick: server == COLD session on the grown panel
        info = _post(port, "append", panel="smoke",
                     delta=delta.tolist())["result"]
        assert info["L"] == panel.shape[1] + delta.shape[1], info
        for p in pairs:
            r = _post(port, "ccm", panel="smoke",
                      lib=p[0], target=p[1], E=E_REQ)["result"]
            _bit_match(r, oracle_grown[p], f"post-append ccm{p}")

        # --- observability surfaces
        prom = _get(port, "/metrics")
        for needle in ("serve_requests", "serve_batches",
                       "serve_latency_ms_ccm", "edm_knn_master_appends"):
            assert needle in prom, f"{needle} missing from /metrics"
        panels = json.loads(_get(port, "/panels"))["panels"]
        assert panels[0]["name"] == "smoke" and panels[0]["version"] == 1
    finally:
        httpd.shutdown()
        srv.close()
        telemetry.remove_sink(sink)
        sink.close()

    errs = schema.validate_events_file(log)
    assert not errs, f"telemetry schema violations: {errs[:5]}"
    names = {json.loads(line)["name"]
             for line in open(log) if line.strip()}
    for needle in ("serve.register", "serve.batch", "serve.request",
                   "session.append", "session.master_append"):
        assert needle in names, f"{needle} missing from {log}"
    print(f"telemetry log: {log}")
    print("SERVE SMOKE OK")


# ---------------------------------------------------------------- soak

SOAK_PANELS = 3
SOAK_TICKS = 2
SOAK_SERIES, SOAK_L, SOAK_DT = 8, 240, 6


def soak() -> None:
    """Multi-panel worker pool + LRU eviction + subscriptions, ~60 s."""
    rng = np.random.default_rng(77)
    full = {f"soak{i}": rng.standard_normal(
        (SOAK_SERIES, SOAK_L + SOAK_TICKS * SOAK_DT)).astype(np.float32)
        for i in range(SOAK_PANELS)}
    pairs = [(i, (i + 3) % SOAK_SERIES) for i in range(SOAK_SERIES)]
    watch = pairs[:4]

    # Per-version direct oracles (and the size of one warm master, which
    # calibrates the byte budget to ~1.5 masters so LRU churn is forced).
    oracle: dict[str, list[dict]] = {}
    one_master = 0
    for name, x in full.items():
        per_v = []
        for v in range(SOAK_TICKS + 1):
            sess = EDM(x[:, : SOAK_L + v * SOAK_DT], EDMConfig(**CFG))
            per_v.append({p: sess.ccm_batch([p], E=E_REQ)[0]
                          for p in pairs})
            one_master = max(one_master, sess.master_nbytes())
        oracle[name] = per_v
    budget_mb = 1.5 * one_master / 2**20

    srv = EDMServer(workers=SOAK_PANELS, master_budget_mb=budget_mb)
    httpd = serve_http(srv)
    port = httpd.server_address[1]
    evictions0 = telemetry.counter("serve_evictions").value
    try:
        for name, x in full.items():
            _post(port, "register", panel=name,
                  data=x[:, :SOAK_L].tolist(), **CFG)
        subs = {name: _post(port, "subscribe", panel=name,
                            pairs=[list(p) for p in watch],
                            E=E_REQ)["result"] for name in full}
        for name, sub in subs.items():  # baseline tick = version 0
            _bit_match_vec(sub["rho"], [oracle[name][0][p] for p in watch],
                           f"{name} subscribe baseline")

        for tick in range(SOAK_TICKS + 1):
            # Concurrent clients sweep every panel at the current version.
            errors: list[BaseException] = []

            def client(cid: int, v=tick) -> None:
                try:
                    for name in full:
                        for lib, tgt in pairs[cid::2]:
                            r = _post(port, "ccm", panel=name, lib=lib,
                                      target=tgt, E=E_REQ)["result"]
                            _bit_match(r, oracle[name][v][(lib, tgt)],
                                       f"soak v{v} {name} ccm{(lib, tgt)}")
                except BaseException as exc:
                    errors.append(exc)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(N_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]

            h = json.loads(_get(port, "/healthz"))
            assert h["ok"] and all(w["alive"] for w in h["workers"]), h
            assert h["master_bytes"] <= h["master_budget_bytes"], h

            if tick == SOAK_TICKS:
                break
            for name, x in full.items():  # one append tick per panel
                lo = SOAK_L + tick * SOAK_DT
                _post(port, "append", panel=name,
                      delta=x[:, lo:lo + SOAK_DT].tolist())
            for name, sub in subs.items():  # the tick streams out
                got = _post_poll(port, sub["id"])
                assert got and got[-1]["version"] == tick + 1, got
                _bit_match_vec(got[-1]["rho"],
                               [oracle[name][tick + 1][p] for p in watch],
                               f"{name} tick v{tick + 1}")

        # One explicit evict: the rebuilt master answers identically.
        _post(port, "ccm", panel="soak0", lib=pairs[1][0],
              target=pairs[1][1], E=E_REQ)  # warm (LRU may have evicted)
        freed = srv.evict_panel("soak0")
        assert freed > 0, "explicit evict freed nothing"
        r = _post(port, "ccm", panel="soak0", lib=pairs[0][0],
                  target=pairs[0][1], E=E_REQ)["result"]
        _bit_match(r, oracle["soak0"][SOAK_TICKS][pairs[0]],
                   "post-explicit-evict ccm")

        churn = telemetry.counter("serve_evictions").value - evictions0
        assert churn >= 1, "LRU budget never evicted - soak proved nothing"
        print(f"soak: {churn} evictions under "
              f"{budget_mb:.2f} MiB budget, "
              f"{SOAK_PANELS} panels x {SOAK_TICKS} ticks")
    finally:
        httpd.shutdown()
        srv.close()
    print("SERVE SOAK OK")


def _post_poll(port: int, sid: str) -> list:
    body = _get(port, f"/v1/subscriptions/{sid}?timeout=10")
    return json.loads(body)["ticks"]


def _bit_match_vec(served, oracles, what: str) -> None:
    for j, (s, o) in enumerate(zip(served, oracles)):
        _bit_match(s, np.float32(o), f"{what}[{j}]")


# ---------------------------------------------------- durability smoke

DUR_PAIRS = [(0, 1), (2, 3), (4, 5)]


def child(state_dir: str) -> None:
    """The durable server process: recover-or-create, serve until
    terminated (SIGTERM → drain → exit 0; SIGKILL → the WAL's job)."""
    from repro.serving import run_until_terminated
    compile_cache.enable()
    panels = os.path.join(state_dir, "panels")
    if os.path.isdir(panels) and os.listdir(panels):
        srv = EDMServer.recover(state_dir)
    else:
        srv = EDMServer(state_dir=state_dir)
    httpd = serve_http(srv)
    print(f"PORT {httpd.server_address[1]}", flush=True)
    sys.exit(run_until_terminated(srv, httpd, poll_s=0.05))


def durability_smoke() -> None:
    """kill -9 → recover → bit-match → append → graceful drain."""
    state_dir = tempfile.mkdtemp(prefix="edm-dur-")
    panel, _ = ts.forced_network_panel(6, 260, seed=21)
    panel = np.asarray(panel, np.float32)
    rng = np.random.default_rng(9)
    deltas = [rng.standard_normal((6, 5)).astype(np.float32)
              for _ in range(4)]

    def spawn():
        p = subprocess.Popen([sys.executable, __file__, "--child",
                              state_dir], stdout=subprocess.PIPE,
                             text=True)
        line = p.stdout.readline()
        assert line.startswith("PORT"), f"child never came up: {line!r}"
        return p, int(line.split()[1])

    def oracle_at(k: int):
        return EDM(np.concatenate([panel] + deltas[:k], axis=1),
                   EDMConfig(**CFG))

    p1, port = spawn()
    try:
        _post(port, "register", panel="dur", data=panel.tolist(), **CFG)
        acked = 0
        for d in deltas[:3]:  # acked == durably logged (WAL-then-ack)
            acked = _post(port, "append", panel="dur",
                          delta=d.tolist())["result"]["version"]
        assert acked == 3, acked
    finally:
        os.kill(p1.pid, signal.SIGKILL)  # mid-stream, no goodbye
        p1.wait(timeout=30)

    # Restart: the child recovers from the WAL; its answers are checked
    # below against a never-crashed session at version 3.
    p2, port = spawn()
    try:
        served3 = [_post(port, "ccm", panel="dur", lib=pr[0], target=pr[1],
                         E=E_REQ)["result"] for pr in DUR_PAIRS]
        # the recovered WAL keeps accepting appends...
        info = _post(port, "append", panel="dur",
                     delta=deltas[3].tolist())["result"]
        assert info["version"] == 4, info
    finally:
        # ...and SIGTERM drains gracefully: admission stops, queues
        # empty, WALs fsync, exit code 0.
        p2.send_signal(signal.SIGTERM)
        rc = p2.wait(timeout=60)
    assert rc == 0, f"graceful drain exited {rc}, want 0"

    # No child is left: from here on the parent computes.
    o3 = oracle_at(3)
    for pr, r in zip(DUR_PAIRS, served3):
        _bit_match(r, o3.ccm_batch([pr], E=E_REQ)[0], f"post-kill9 ccm{pr}")
    rec = EDMServer.recover(state_dir, autostart=False)
    try:
        assert rec.recovery_report["dur"]["version"] == 4, \
            rec.recovery_report
        o4 = oracle_at(4)
        futs = rec.submit_many(
            "ccm", "dur", [{"lib": l, "target": t, "E": E_REQ}
                           for l, t in DUR_PAIRS])
        while rec.scheduler.drain_once():
            pass
        for pr, f in zip(DUR_PAIRS, futs):
            _bit_match(float(f.result()),
                       o4.ccm_batch([pr], E=E_REQ)[0],
                       f"final recover ccm{pr}")
    finally:
        rec.close()
    print("SERVE DURABILITY OK "
          "(kill -9 -> recover bit-match -> drain exit 0)")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2])
    else:
        compile_cache.enable()
        durability_smoke()
        main()
        soak()
