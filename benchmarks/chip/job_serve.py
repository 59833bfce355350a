"""Serve job: an open loop of requests against an in-process EDMServer.

Mix parameters (``mixes/<traffic>.json`` with ``"job": "serve"``):

* ``panel_seed``: the seed of the served panel. It is fixed in the mix,
  not taken from ``--seed``: the server compiles one program per
  (E, libraries per batch), and the set of E's is the panel's, so a
  panel drawn per run would change the work (and what set-up compiles)
  from seed to seed. ``--seed`` draws the request stream and the check;
* ``op``: the served request (``"ccm"``: full-library cross-map skill
  of one (lib, target) pair at the target's optimal E);
* ``rate_per_s``: the offered load, fixed in the mix;
* ``warmup_s``: seconds of the same generator, on its own seed stream,
  after every (E, libraries-per-batch) shape has been compiled;
* ``max_batch``: the server's largest coalesced batch (``EDMServer``'s
  ``max_batch``); set-up warms every batch size up to it. The server
  compiles one program per (E, libraries per batch), 1.5–8.4 s each on
  a v5e, so a batch size left cold would compile inside the window, and
  under load one such stall grows the queue into larger, colder batches;

* ``check_requests``: requests drawn from the seed, among those the
  window finished, that the reference recomputes;
* ``limits``: the limit of each compared number.

Arrivals are Poisson: exponential gaps drawn from the seed, scaled so
that ``rate_per_s · seconds`` requests fall in the window (the same
count for every seed). (lib, target) is uniform over the panel. Each
request is timed from when it was due until its future resolved; a
failed request counts as a miss (infinite latency).

End to end: ``ccm_p95_ms``, the 95th percentile (nearest rank) of all
the window's requests.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

import datagen
import harness
import reference

PANEL = "panel"


def schedule(rng, rate: float, seconds: float, N: int, E_opt):
    """(due times, libs, targets, Es) of one open-loop window."""
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0, n)
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    libs = rng.integers(0, N, n)
    tgts = rng.integers(0, N, n)
    return due - due[0], libs, tgts, np.asarray(E_opt)[tgts]


def drive(srv, op, due, libs, tgts, Es):
    """Submit on schedule; returns (due, submitted, done, results).

    ``done[i]`` is NaN for a request that failed; ``results[i]`` is its
    answer or its exception.
    """
    n = len(due)
    done = np.full(n, np.nan)
    sub = np.zeros(n)
    results: list = [None] * n
    left = threading.Semaphore(0)
    t0 = time.perf_counter()

    def finished(i, fut):
        exc = fut.exception()
        if exc is None:
            done[i] = time.perf_counter() - t0
            results[i] = fut.result()
        else:
            results[i] = exc
        left.release()

    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sub[i] = time.perf_counter() - t0
        fut = srv.submit(op, PANEL, lib=int(libs[i]), target=int(tgts[i]),
                         E=int(Es[i]))
        fut.add_done_callback(lambda f, i=i: finished(i, f))
    deadline = time.perf_counter() + 60.0
    for _ in range(n):
        if not left.acquire(timeout=max(0.0, deadline - time.perf_counter())):
            break
    return due, sub, done, results


def warm_shapes(srv, N, Es, batch_max):
    """Compile every (E, libraries-per-batch) program the window can use.

    The server answers a coalesced batch of b distinct libraries at one
    E with one ``EDM.ccm_batch`` call whose programs are specialised to
    b, so each (E, b) up to the server's ``max_batch`` is called once
    here, on the served session itself.
    """
    sess = srv.registry.get(PANEL).sess
    for E in sorted({int(e) for e in Es}):
        for b in range(1, min(batch_max, N) + 1):
            sess.ccm_batch([(i, 0) for i in range(b)], E=E)


def start(ctx):
    """Server with the seeded panel registered, its master built and
    every shape warmed; returns (server, panel, E_opt)."""
    from repro.serving import EDMServer

    config, mix = ctx.config, ctx.mix
    N, L = config["N"], config["L"]
    panel = datagen.forced_network_panels(1, N, L,
                                          seed=mix["panel_seed"])[0]
    srv = EDMServer(max_batch=mix["max_batch"])
    try:
        srv.register_panel(PANEL, panel)
        E_opt, _ = srv.call("optimal_E", PANEL, timeout=600)
        warm_shapes(srv, N, E_opt, mix["max_batch"])
        rng_warm = np.random.default_rng([ctx.seed % 2**64, 2])
        drive(srv, mix["op"], *schedule(rng_warm, mix["rate_per_s"],
                                        mix["warmup_s"], N, E_opt))
    except BaseException:
        srv.close()
        raise
    return srv, panel, E_opt


def p95(lat_ms) -> float:
    """Nearest-rank 95th percentile; NaN (failed) counts as infinite."""
    v = np.sort(np.where(np.isnan(lat_ms), np.inf, lat_ms))
    return float(v[max(0, math.ceil(0.95 * len(v)) - 1)])


def reference_answers(panel, libs, tgts, Es, config, dtype=None):
    """The reference's ρ for each (lib, target, E)."""
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    s = config["edm"]
    X = jnp.asarray(panel)
    out = np.zeros(len(libs), np.float32)
    chunk = 32  # libraries per reference launch (one program per E)
    for E in sorted({int(e) for e in Es}):
        sel = np.flatnonzero(Es == E)
        uniq = np.unique(libs[sel])
        for a in range(0, len(uniq), chunk):
            part = uniq[a:a + chunk]
            padded = np.concatenate(
                [part, np.full(chunk - len(part), part[-1])])
            r = np.asarray(reference.skill(X[padded], X, E=E, tau=s["tau"],
                                           Tp=s["Tp_cross"], dtype=dtype))
            pos = {int(l): j for j, l in enumerate(part)}
            for i in sel:
                if int(libs[i]) in pos:
                    out[i] = r[pos[int(libs[i])], tgts[i]]
    return out


def numbers(got, want) -> dict:
    d = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    v = float(np.max(d)) if len(d) else 0.0
    return {"rho_max_abs_diff": v if np.isfinite(v) else float("inf")}


def run(ctx) -> harness.Outcome:
    config, mix = ctx.config, ctx.mix
    srv, panel, E_opt = start(ctx)
    try:
        ctx.setup_done()
        rng = np.random.default_rng([ctx.seed % 2**64, 3])
        due, libs, tgts, Es = schedule(rng, mix["rate_per_s"], ctx.seconds,
                                       config["N"], E_opt)
        with ctx.window.measure() as reading:
            due, sub, done, results = drive(srv, mix["op"], due, libs, tgts,
                                            Es)
        mem = ctx.memory_peak()
    finally:
        srv.close()

    lat_ms = (done - due) * 1e3
    ok = np.flatnonzero(~np.isnan(done))
    failed = len(due) - len(ok)
    pick = np.sort(rng.choice(ok, min(len(ok), mix["check_requests"]),
                              replace=False))
    got = np.asarray([results[i] for i in pick], np.float32)
    want = reference_answers(panel, libs[pick], tgts[pick], Es[pick], config)
    compared = [harness.Compared(k, v, float(mix["limits"][k]))
                for k, v in numbers(got, want).items()]
    late = sub - due
    return harness.Outcome(
        attempted=len(due), failed=failed,
        end_to_end={"ccm_p95_ms": p95(lat_ms)},
        compared=compared, work=[], window=reading,
        memory_peak_bytes=mem,
        extra={"checked_requests": len(pick),
               "ccm_p50_ms": float(np.nanmedian(lat_ms)),
               "generator_late_max_ms": float(late.max() * 1e3),
               "E_hist": np.bincount(np.asarray(E_opt)).tolist()})
