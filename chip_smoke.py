"""Drive the EDM main path once on a TPU and check every answer.

Run from the repository root:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded engine on 4 chips

One process, data made from ``--seed`` (``repro.data.timeseries``),
everything through the user entry points (``repro.edm.EDM`` and
``repro.serving.EDMServer``) at kEDM Table 1 series lengths
(arXiv 2105.12301). One line per phase: shape, resolved kernel impl,
compile seconds, run seconds and the phase's check.

One chip:
  a  Fish1_Normo, 154 × 1600, full published scale: ``optimal_E`` (the
     ``knn_multi_e`` master), ``xmap`` derived from the master
     (``lookup_rho``), and a one-shot ``xmap`` on a fresh session (the
     ``knn_batch`` engine).
  b  F1 at its published L = 29484 with N cut to ``F1_N`` (the full
     8520 series would take hours on one chip): one-shot ``xmap`` at
     ``F1_E``, then the share of k-best merge passes ``knn_batch``'s
     gate lets one library run.
  c  an in-process ``EDMServer`` on the phase-a panel: ``ccm`` requests,
     then ``APPENDS`` appends of 8 samples with ``ccm`` between them;
     the first append sizes the panel's capacity, nothing compiles
     after the requests that follow it, and every answer bit-matches a
     direct session at the same capacity on the same chip.

Phases a and b are checked against ``kernels/ref.py`` run on the same
chip, on a sample of library rows (see ``RHO_TOL``).

Four chips (``--four-chips``, only these phases): a sharded ``xmap`` on a
2×2 ``make_ccm_mesh`` against the single-device session on the same
panel, and a check that each device holds one shard of the matrix.

Exits nonzero, and prints no result line, without a TPU or on any
failed check. The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import compile_cache  # noqa: E402  (needs the checkout's src/)

# Kernel vs ref tolerances.
#
# Neighbour indices. Both sides sum each squared distance over the same
# E lag terms, each rounded to f32, so a distance is good to a relative
# E·2⁻²⁴ (≤ 6e-7 at the largest E checked here, 10). Two neighbours can
# swap only where their distances lie closer than that. Near the k-th
# of Lp neighbours in an attractor of dimension d ≤ E, the relative gap
# between consecutive neighbour distances is ≈ 1/(d·k) ≥ 1/(10·11) ≈
# 9e-3, so a swap has probability ≈ 6e-7 / 9e-3 ≈ 7e-5 per adjacent pair,
# and ≲ 1.5e-4 of the table's entries may differ (a swap moves two).
# IDX_AGREE_MIN allows 1e-3, about 7× that.
#
# ρ. A swapped near-tie neighbour carries almost the same weight, so ρ
# differs by the rounding of the Pearson sums over Lp ≤ 29484 terms,
# which the kernel merges tile by tile (Schubert–Gertz) and the ref sums
# in two passes: each is good to ≈ log2(Lp)·2⁻²⁴ ≈ 1e-6 relative.
# RHO_TOL is 10× that, and far below any ρ difference that would change
# a result.
RHO_TOL = 1e-5
IDX_AGREE_MIN = 0.999

FISH = (154, 1600)  # Fish1_Normo (N, L), kEDM Table 1
F1_L = 29484  # F1 series length, kEDM Table 1
F1_N = 256  # cut from the published 8520 so phase b ends in about a minute
F1_E = 10  # fixed embedding dimension of phase b
APPENDS = 4  # phase c: appends of 8 samples within the panel's capacity


class CompileClock:
    """Seconds of backend (XLA + Mosaic) compilation, from JAX's
    monitoring events, so a phase's wall time splits into compile and
    run. Tracing and lowering are left in run time: their events nest
    (an outer jit's trace spans its inner ones) and would double count."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.s = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.s += duration
            self.n += 1


def run_phase(clock, name, shape, impl, fn):
    c0, t0 = clock.s, time.perf_counter()
    check = fn()
    wall = time.perf_counter() - t0
    comp = clock.s - c0
    print(f"[{name}] shape={shape[0]}x{shape[1]} impl={impl} "
          f"compile_s={comp:.3f} run_s={wall - comp:.3f} {check}",
          flush=True)


def require(ok, what):
    if not ok:
        raise AssertionError(what)


def ref_check(panel, rho, E_of_target, sample, *, tau, Tp, impl):
    """Max |Δρ| and neighbour-index agreement against ``kernels/ref.py``.

    For the sampled library rows, each E-group of targets is recomputed
    by the same engine with ``impl="ref"`` (jnp oracles, same chip), and
    the sampled rows' neighbour tables from the ``impl`` (kernel) and ref
    kNN are compared entry by entry. One library row per launch: the ref
    kNN materializes the (Lp, Lp) distance matrix, ≈ 9 GB of one chip's
    16 GB at L = 29484.
    """
    import jax.numpy as jnp

    from repro.core import ccm
    from repro.core.embedding import num_embedded
    from repro.kernels import ops

    X = jnp.asarray(panel)
    libs = X[np.asarray(sample)]
    max_d, agree, total = 0.0, 0, 0
    for E in sorted({int(e) for e in E_of_target}):
        members = np.flatnonzero(E_of_target == E)
        want = ccm.ccm_group_batched(libs, X[members], E=E, tau=tau, Tp=Tp,
                                     impl="ref", batch_libs=1)
        got = rho[np.ix_(sample, members)]
        max_d = max(max_d, float(np.max(np.abs(got - want))))
        cap = num_embedded(panel.shape[1], E, tau) - 1 - max(Tp, 0)
        for r in range(len(sample)):
            tables = [np.asarray(ops.all_knn_batch(
                libs[r:r + 1], E=E, tau=tau, k=E + 1, max_idx=cap,
                impl=name)[1]) for name in (impl, "ref")]
            agree += int(np.sum(tables[0] == tables[1]))
            total += tables[0].size
    return max_d, agree / total


def checked(max_d, agree):
    require(max_d <= RHO_TOL, f"max |Δρ| vs ref {max_d:.3g} > {RHO_TOL}")
    require(agree >= IDX_AGREE_MIN,
            f"neighbour-index agreement {agree:.6f} < {IDX_AGREE_MIN}")
    return f"vs_ref max|drho|={max_d:.3g} idx_agree={agree:.6f}"


def sample_rows(N, n):
    return np.unique(np.linspace(0, N - 1, n).astype(int))


def phase_a(clock, panel, impl_want):
    """Fish1_Normo: master optimal-E, master xmap, one-shot xmap."""
    from repro.edm import EDM

    sess = EDM(panel)
    impl = sess.plan("xmap").impl
    require(impl == impl_want, f"session resolved impl {impl!r}")
    N = panel.shape[0]
    c = sess.config
    out = {}

    def a1():
        E_opt, rho = sess.optimal_E()
        require(sess.stats["knn_master_builds"] == 1, "no master built")
        require(np.isfinite(rho).all(), "non-finite rho(E)")
        out["E_opt"] = E_opt
        hist = np.bincount(E_opt, minlength=c.E_max + 1)[1:]
        return f"E_opt_hist={hist.tolist()}"

    def a2():
        rho = sess.xmap()
        require(sess.stats["xmap_direct_runs"] == 0, "master not used")
        require(rho.shape == (N, N) and np.isfinite(rho).all(),
                "bad master xmap")
        out["rho_master"] = rho
        return checked(*ref_check(panel, rho, out["E_opt"],
                                  sample_rows(N, 8), tau=c.tau,
                                  Tp=c.Tp_cross, impl=impl))

    def a3():
        fresh = EDM(panel)
        rho = fresh.xmap(E_opt=out["E_opt"])
        require(fresh.stats["xmap_direct_runs"] == 1
                and fresh.stats["knn_master_builds"] == 0,
                "one-shot xmap did not take the knn_batch engine")
        require(np.isfinite(rho).all(), "bad one-shot xmap")
        vs_master = float(np.max(np.abs(rho - out["rho_master"])))
        return (checked(*ref_check(panel, rho, out["E_opt"],
                                   sample_rows(N, 8), tau=c.tau,
                                   Tp=c.Tp_cross, impl=impl))
                + f" vs_master max|drho|={vs_master:.3g}")

    run_phase(clock, "a1 optimal_E (knn_multi_e master)", panel.shape,
              impl, a1)
    run_phase(clock, "a2 xmap from master (lookup_rho)", panel.shape, impl,
              a2)
    run_phase(clock, "a3 one-shot xmap (knn_batch)", panel.shape, impl, a3)
    return out["E_opt"]


def phase_b(clock, panel, E, impl_want):
    """F1 length: one-shot xmap at fixed E."""
    from repro.edm import EDM, EDMConfig

    sess = EDM(panel, EDMConfig(E=E))
    impl = sess.plan("xmap").impl
    require(impl == impl_want, f"session resolved impl {impl!r}")
    N = panel.shape[0]

    def b1():
        rho = sess.xmap()
        require(sess.stats["xmap_direct_runs"] == 1, "not the direct engine")
        require(rho.shape == (N, N) and np.isfinite(rho).all(),
                "bad xmap")
        c = sess.config
        return f"E={E} " + checked(*ref_check(
            panel, rho, np.full(N, E), sample_rows(N, 2), tau=c.tau,
            Tp=c.Tp_cross, impl=impl))

    run_phase(clock, "b1 one-shot xmap (knn_batch)", panel.shape, impl, b1)

    def b2():
        from repro.kernels.knn_batch import knn_batch_merge_share

        share = knn_batch_merge_share(panel[:1], E=E)
        require(0.0 < share <= 1.0, f"merge share {share}")
        return f"E={E} merge passes run / k per cell = {share:.4f}"

    run_phase(clock, "b2 knn_batch merge share", panel[:1].shape, impl, b2)


def phase_c(clock, grown, L, E_opt, impl_want):
    """EDMServer: ccm requests, APPENDS appends with ccm between them —
    no compile once the first append has sized the capacity, and every
    answer bit-matches a direct session at the same capacity."""
    from repro.edm import EDM
    from repro.serving import EDMServer

    N = grown.shape[0]
    dt = (grown.shape[1] - L) // APPENDS
    pairs = [(int(l), int(t)) for l, t in
             zip(sample_rows(N, 6), sample_rows(N, 6)[::-1])]

    def serve(srv):
        futs = [srv.submit("ccm", "fish", lib=l, target=t,
                           E=int(E_opt[t])) for l, t in pairs]
        while srv.scheduler.drain_once():
            pass
        return [np.float32(f.result(timeout=600)) for f in futs]

    def append(srv, n):
        fut = srv.submit("append", "fish",
                         delta=grown[:, L + (n - 1) * dt:L + n * dt])
        srv.scheduler.drain_once()
        info = fut.result(timeout=600)
        require(info["L"] == L + n * dt, f"append info {info}")

    def oracle(data):
        sess = EDM(data[:, :L])
        if data.shape[1] > L:  # appended before the master: built at C
            sess.append(data[:, L:])
        require(sess.plan("ccm").impl == impl_want, "oracle impl")
        return [sess.ccm_batch([p], E=int(E_opt[p[1]]))[0] for p in pairs]

    def bit_equal(got, want, what):
        for p, g, w in zip(pairs, got, want):
            require(g.tobytes() == np.float32(w).tobytes(),
                    f"{what} ccm{p}: served {g!r} != direct {w!r}")

    def c1():
        srv = EDMServer(autostart=False)
        try:
            srv.register_panel("fish", grown[:, :L])
            srv.submit("optimal_E", "fish")  # the master at every level
            srv.scheduler.drain_once()
            before = serve(srv)
            append(srv, 1)  # sizes C, compiles the (C, dt) append program
            serve(srv)  # and the (C, E, batch) ccm programs
            capacity = srv.registry.get("fish").sess.data.capacity
            n0 = clock.n
            for n in range(2, APPENDS + 1):
                append(srv, n)
                after = serve(srv)
            compiles = clock.n - n0
        finally:
            srv.close()
        require(compiles == 0, f"{compiles} compiles after the first append")
        bit_equal(before, oracle(grown[:, :L]), "pre-append")
        bit_equal(after, oracle(grown), "post-append")
        return (f"capacity {capacity}: {APPENDS} appends of {dt} with "
                f"{len(pairs)} ccm between, 0 compiles after the first; "
                f"bit-equal to direct sessions")

    run_phase(clock, "c1 EDMServer ccm/append/ccm", grown.shape,
              impl_want, c1)


def four_chip_phases(clock, panel, E, impl_want):
    """Sharded xmap on a 2×2 mesh vs one device; one shard per device."""
    import jax
    import jax.numpy as jnp

    from repro.distributed import make_ccm_mesh
    from repro.distributed.sharded_ccm import sharded_ccm_matrix
    from repro.edm import EDM, EDMConfig

    devices = jax.devices()
    mesh = make_ccm_mesh((2, 2), ("data", "model"))
    N = panel.shape[0]

    def f1():
        single = EDM(panel)
        E_opt, _ = single.optimal_E()
        want = EDM(panel).xmap(E_opt=E_opt)
        sharded = EDM(panel, EDMConfig(mesh=mesh))
        require(sharded.plan("xmap").placement == "sharded"
                and sharded.plan("xmap").impl == impl_want,
                f"plan {sharded.plan('xmap').describe()}")
        got = sharded.xmap(E_opt=E_opt)
        d = float(np.max(np.abs(got - want)))
        require(d <= RHO_TOL, f"sharded vs single max|drho| {d:.3g}")
        return (f"sharded vs single-device max|drho|={d:.3g} "
                f"bit_equal={bool(np.array_equal(got, want))}")

    def f2():
        X = jnp.asarray(panel)
        out = sharded_ccm_matrix(X, X, E=E, mesh=mesh, impl=impl_want)
        shards = out.addressable_shards
        held = sorted(s.device.id for s in shards)
        require(held == sorted(d.id for d in devices),
                f"shards on devices {held}")
        require(all(s.data.shape == (N // 2, N // 2) for s in shards),
                "uneven shards")
        want = EDM(panel, EDMConfig(E=E)).xmap()
        d = float(np.max(np.abs(np.asarray(out) - want)))
        require(d <= RHO_TOL, f"fixed-E sharded vs single {d:.3g}")
        return (f"E={E} one {N // 2}x{N // 2} shard on each of devices "
                f"{held}; vs single-device max|drho|={d:.3g}")

    run_phase(clock, "4a sharded xmap vs single device", panel.shape,
              impl_want, f1)
    run_phase(clock, "4b one shard per device", panel.shape, impl_want, f2)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phases, on 4 chips")
    args = ap.parse_args()

    cache_dir = compile_cache.enable()
    import jax

    from repro.data.timeseries import forced_network_panel

    devices = jax.devices()
    dev = devices[0]
    want = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU device(s), JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache_dir}", flush=True)
    clock = CompileClock()
    N, L = FISH
    if args.four_chips:
        panel = forced_network_panel(N, L, seed=args.seed)[0]
        four_chip_phases(clock, panel, 4, "pallas")
    else:
        grown = forced_network_panel(N, L + 8 * APPENDS, seed=args.seed)[0]
        E_opt = phase_a(clock, grown[:, :L], "pallas")
        f1 = forced_network_panel(F1_N, F1_L, seed=args.seed)[0]
        phase_b(clock, f1, F1_E, "pallas")
        phase_c(clock, grown, L, E_opt, "pallas")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
