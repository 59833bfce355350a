"""Serve-and-append job: open-loop ccm requests against an EDMServer whose
panel grows by a fixed-size append on a fixed clock.

A lab streams a recording into a warm panel while it keeps asking
cross-map questions. Mix parameters (``mixes/<traffic>.json`` with
``"job": "serve_append"``), beside those of ``job_serve``
(``panel_seed``, ``op``, ``rate_per_s``, ``warmup_s``, ``max_batch``,
``check_requests``, ``limits``):

* ``append_dt``: samples per append (a camera's frames per delivery);
* ``append_every_s``, ``append_first_s``: the append clock — one append
  every ``append_every_s`` seconds from ``append_first_s`` on, in the
  warm-up and in the window alike;
* ``drain_s``: how long the job waits, after the last submission, for
  open requests; one still open then counts as failed, so a server
  that falls behind ends the run instead of hanging it.

The configuration gives the panel (``N``, ``L``), its ``capacity``
(the room the program gives a live panel at its first append, checked
against the session, so the statement stays true) and ``serving``: the
server's durability settings (``state_dir`` "temp": a temporary
directory; ``wal_fsync``; ``compact_every``).

Each append is the next ``append_dt`` columns of one seeded forced
logistic panel, generated at L plus every sample the run appends; the
server starts from its first L columns. Set-up registers the panel,
computes every series' optimal E (each request's E is its target's),
makes one append, which sizes the panel's capacity and compiles the
append program, compiles every (E, libraries-per-batch) program at
that capacity (``job_serve``), runs ``warmup_s`` of the mix, appends
included, on a seed stream of its own, and freezes the warm server's
heap (``EDMServer.freeze_heap``), as a deployment does once warm.

End to end: ``ccm_p95_ms`` over the window's ccm requests, each timed
from when it was due (a failed or unfinished one is infinite). Appends
and ccm requests both count as attempted operations.

The check: ``rho_max_abs_diff`` over ``check_requests`` answers drawn
from the seed from three library versions — before the window's first
append, the middle one, after its last — each against the reference
(``reference.skill``) on the exact panel prefix of its version, the
appends submitted before it; and ``append_readback_mismatch``, the
samples that differ between the generated prefix and (a) the live
session's panel, (b) the panel ``PanelLog.recover`` rebuilds from the
state dir after the server closed. A length mismatch counts every
missing sample.

Run as a script, it finds the cell's knee with its appends on, as
``sweep.py`` does for ``job_serve`` (one JSON line per rate):

    python3 benchmarks/chip/job_serve_append.py \
        --workload fish1_serve_append --seed 11 --rates 384,480
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

import datagen
import harness
import job_serve

PANEL = job_serve.PANEL


def append_times(first: float, every: float, seconds: float) -> np.ndarray:
    """Due times of the appends in a phase of ``seconds``."""
    n = max(0, int(np.ceil((seconds - first) / every)))
    return first + every * np.arange(n)


def appended_samples(mix: dict, seconds: float) -> int:
    """Samples a whole run appends: set-up's, the warm-up's, the window's."""
    n = 1 + sum(len(append_times(mix["append_first_s"],
                                 mix["append_every_s"], s))
                for s in (mix["warmup_s"], seconds))
    return n * mix["append_dt"]


@dataclasses.dataclass
class Live:
    """The server and the recording it is being fed."""

    srv: object
    full: np.ndarray   # (N, L + every appended sample)
    L0: int            # length registered
    appended: int      # appends applied so far (the library version)
    dt: int
    E_opt: np.ndarray
    state_dir: str

    def next_delta(self) -> np.ndarray:
        a = self.L0 + self.appended * self.dt
        return self.full[:, a:a + self.dt]

    def length(self, version: int) -> int:
        return self.L0 + version * self.dt


def drive(live: Live, op, due, libs, tgts, Es, t_app, drain_s):
    """Submit ccm requests and appends in due order on one clock.

    Returns (sub, done, results, versions, app_done): per ccm request
    its submission time, completion time (NaN: failed or still open at
    the deadline), answer and library version (appends submitted before
    it); per append its completion time. An append due at the same
    moment as a request goes first.
    """
    n, m = len(due), len(t_app)
    done = np.full(n, np.nan)
    sub = np.zeros(n)
    results: list = [None] * n
    versions = np.zeros(n, np.int64)
    app_done = np.full(m, np.nan)
    left = threading.Semaphore(0)
    events = sorted([(t, 0, j) for j, t in enumerate(t_app)]
                    + [(t, 1, i) for i, t in enumerate(due)])
    t0 = time.perf_counter()

    def finished(out, i, fut):
        if fut.exception() is None:
            out[i] = time.perf_counter() - t0
            if out is done:
                results[i] = fut.result()
        elif out is done:
            results[i] = fut.exception()
        left.release()

    for t, kind, i in events:
        wait = t0 + t - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if kind == 0:
            fut = live.srv.submit("append", PANEL, delta=live.next_delta())
            live.appended += 1
            fut.add_done_callback(lambda f, i=i: finished(app_done, i, f))
            continue
        sub[i] = time.perf_counter() - t0
        versions[i] = live.appended
        fut = live.srv.submit(op, PANEL, lib=int(libs[i]),
                              target=int(tgts[i]), E=int(Es[i]))
        fut.add_done_callback(lambda f, i=i: finished(done, i, f))
    deadline = time.perf_counter() + drain_s
    for _ in range(n + m):
        if not left.acquire(timeout=max(0.0, deadline - time.perf_counter())):
            break
    return sub, done, results, versions, app_done


def phase(live: Live, rng, mix, seconds):
    """One open-loop phase of the mix; as ``drive``, plus the plan."""
    N = live.full.shape[0]
    plan = job_serve.schedule(rng, mix["rate_per_s"], seconds, N,
                              live.E_opt)
    t_app = append_times(mix["append_first_s"], mix["append_every_s"],
                         seconds)
    return plan, t_app, drive(live, mix["op"], *plan, t_app,
                              mix["drain_s"])


def start(ctx) -> Live:
    """Server with the panel live at its capacity, every ccm shape and
    the append program compiled, and the warm-up run."""
    from repro.edm.dataset import Dataset
    from repro.serving import EDMServer

    config, mix = ctx.config, ctx.mix
    if not hasattr(Dataset, "capacity"):  # fail at once, not after set-up
        raise RuntimeError(
            "this program holds no panel at a capacity: each append "
            "would recompile every serving program")
    N, L = config["N"], config["L"]
    extra = appended_samples(mix, ctx.seconds)
    full = datagen.forced_network_panels(1, N, L + extra,
                                         seed=mix["panel_seed"])[0]
    serving = config["serving"]
    state_dir = tempfile.mkdtemp(prefix="chipbench-wal-")
    srv = EDMServer(max_batch=mix["max_batch"], state_dir=state_dir,
                    wal_fsync=serving["wal_fsync"],
                    compact_every=serving["compact_every"])
    try:
        s = config["edm"]
        srv.register_panel(PANEL, full[:, :L], E_max=s["E_max"],
                           tau=s["tau"], Tp=s["Tp"], Tp_cross=s["Tp_cross"])
        E_opt, _ = srv.call("optimal_E", PANEL, timeout=600)
        live = Live(srv, full, L, 0, mix["append_dt"], np.asarray(E_opt),
                    state_dir)
        srv.call("append", PANEL, timeout=600, delta=live.next_delta())
        live.appended += 1
        capacity = srv.registry.get(PANEL).sess.data.capacity
        if capacity != config["capacity"]:
            raise RuntimeError(f"the panel went live at capacity {capacity}"
                               f", the configuration states "
                               f"{config['capacity']}")
        job_serve.warm_shapes(srv, N, E_opt, mix["max_batch"])
        rng_warm = np.random.default_rng([ctx.seed % 2**64, 2])
        phase(live, rng_warm, mix, mix["warmup_s"])
        srv.freeze_heap()  # warm: its long-lived heap leaves the GC
    except BaseException:
        srv.close()
        shutil.rmtree(state_dir, ignore_errors=True)
        raise
    return live


def pick_checked(rng, ok, versions, v_first, v_last, n_check):
    """Indices of the checked answers: ``n_check`` drawn from the seed,
    split evenly over the versions before the window's first append, the
    middle one and after its last (fewer where a version has fewer)."""
    chosen = sorted({v_first, (v_first + v_last) // 2, v_last})
    per = -(-n_check // len(chosen))
    picks = []
    for v in chosen:
        pool = ok[versions[ok] == v]
        picks.append(rng.choice(pool, min(len(pool), per), replace=False))
    return np.sort(np.concatenate(picks)).astype(np.int64), chosen


def readback_mismatch(full, length, panel) -> int:
    """Samples of ``panel`` that differ from ``full[:, :length]``; a
    missing or extra column counts every one of its samples."""
    panel = np.asarray(panel, np.float32)
    want = full[:, :length]
    common = min(length, panel.shape[1])
    diff = int(np.sum(panel[:, :common] != want[:, :common]))
    return diff + abs(length - panel.shape[1]) * full.shape[0]


def recovered_panel(state_dir: str):
    """(panel, version) the state dir's WAL recovers."""
    from repro.serving.durability import Durability

    d = Durability(state_dir)
    try:
        (log,) = d.scan()
        sess, version, _ = log.recover()
        return np.asarray(sess.data.panel), version
    finally:
        d.close()


def run(ctx) -> harness.Outcome:
    config, mix = ctx.config, ctx.mix
    live = start(ctx)
    srv = live.srv
    try:
        ctx.setup_done()
        rng = np.random.default_rng([ctx.seed % 2**64, 3])
        v_first = live.appended
        with ctx.window.measure() as reading:
            plan, t_app, (sub, done, results, versions, app_done) = phase(
                live, rng, mix, ctx.seconds)
        mem = ctx.memory_peak()
        sess = srv.registry.get(PANEL).sess
        live_panel = np.asarray(sess.data.panel)
        capacity = sess.data.capacity
    finally:
        srv.close()
    try:
        rec_panel, rec_version = recovered_panel(live.state_dir)
    finally:
        shutil.rmtree(live.state_dir, ignore_errors=True)

    due, libs, tgts, Es = plan
    v_last = live.appended
    lat_ms = (done - due) * 1e3
    ok = np.flatnonzero(~np.isnan(done))
    app_ok = int(np.sum(~np.isnan(app_done)))
    failed = (len(due) - len(ok)) + (len(t_app) - app_ok)
    pick, checked = pick_checked(rng, ok, versions, v_first, v_last,
                                 mix["check_requests"])
    got = np.asarray([results[i] for i in pick], np.float32)
    want = np.zeros(len(pick), np.float32)
    for v in checked:
        sel = versions[pick] == v
        if sel.any():
            want[sel] = job_serve.reference_answers(
                live.full[:, :live.length(v)], libs[pick][sel],
                tgts[pick][sel], Es[pick][sel], config)
    L_end = live.length(v_last)
    mismatch = (readback_mismatch(live.full, L_end, live_panel)
                + readback_mismatch(live.full, L_end, rec_panel)
                + (0 if rec_version == v_last else 1))
    nums = dict(job_serve.numbers(got, want),
                append_readback_mismatch=float(mismatch))
    compared = [harness.Compared(k, v, float(mix["limits"][k]))
                for k, v in nums.items()]
    s = config["edm"]
    k_master = s["E_max"] + 1 + max(1, s["Tp"], s["Tp_cross"])
    work = [{"op": "knn_append", "series": config["N"],
             "E_max": s["E_max"], "tau": s["tau"], "dt": live.dt,
             "L_old": live.length(v), "k": k_master}
            for v in range(v_first, v_first + app_ok)]
    app_ms = (app_done - t_app) * 1e3
    late = sub - due
    return harness.Outcome(
        attempted=len(due) + len(t_app), failed=failed,
        end_to_end={"ccm_p95_ms": job_serve.p95(lat_ms)},
        compared=compared, work=work, window=reading,
        memory_peak_bytes=mem,
        extra={"checked_requests": len(pick),
               "checked_versions": checked,
               "ccm_p50_ms": float(np.nanmedian(lat_ms)) if len(ok) else
               float("inf"),
               "appends_in_window": len(t_app),
               "append_max_ms": float(np.nanmax(app_ms)) if app_ok else
               float("inf"),
               "L_window": [live.length(v_first), L_end],
               "capacity": capacity,
               "capacity_regrows_in_window": reading.counters.get(
                   "edm_capacity_regrows", 0),
               "generator_late_max_ms": float(late.max() * 1e3),
               "E_hist": np.bincount(live.E_opt).tolist()})


def sweep(argv=None) -> int:
    """The knee with appends on: set up once, then ``--seconds`` of the
    mix at each rate in turn; p50/p95, failures, requests still open at
    the schedule's end, and the p95 of the last fifth against the first
    (a growing backlog shows as a last fifth far slower)."""
    ap = argparse.ArgumentParser(description=sweep.__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = Path.cwd()
    sys.path.insert(0, str(root / "src"))
    spec = harness.Spec(root)
    cell = spec.cell(args.workload)
    config, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    rates = [float(r) for r in args.rates.split(",")]
    total = args.seconds * len(rates)
    from repro import compile_cache

    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        harness.find_chips(cell["chips"],
                           harness.load_json(harness.HERE / "peaks.json")
                           ["devices"])
    except harness.NoChip as e:
        print(f"job_serve_append.py: {e}", file=sys.stderr)
        return 2
    ctx = argparse.Namespace(config=config, mix=mix, seed=args.seed,
                             seconds=total)
    t0 = time.perf_counter()
    live = start(ctx)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    try:
        for k, rate in enumerate(rates):
            rng = np.random.default_rng([args.seed, 100 + k])
            c0 = harness.counters()
            (due, *_), _, (sub, done, _, _, app_done) = phase(
                live, rng, dict(mix, rate_per_s=rate), args.seconds)
            c1 = harness.counters()
            lat = (done - due) * 1e3
            fifth = max(1, len(due) // 5)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(due),
                "p50_ms": float(np.nanmedian(lat)),
                "p95_ms": job_serve.p95(lat),
                "failed": int(np.isnan(done).sum()
                              + np.isnan(app_done).sum()),
                "open_at_schedule_end": int(np.sum(done > due[-1])),
                "p95_first_fifth_ms": job_serve.p95(lat[:fifth]),
                "p95_last_fifth_ms": job_serve.p95(lat[-fifth:]),
                "appends": len(app_done),
                "regrows": c1.get("edm_capacity_regrows", 0)
                - c0.get("edm_capacity_regrows", 0),
                "generator_late_max_ms": float((sub - due).max() * 1e3)}),
                flush=True)
    finally:
        live.srv.close()
        shutil.rmtree(live.state_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(sweep())
