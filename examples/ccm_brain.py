"""All-pairs causal mapping, zebrafish-brain style (paper's headline use).

Run:  PYTHONPATH=src python examples/ccm_brain.py [--series 24] [--steps 600]
      PYTHONPATH=src python examples/ccm_brain.py --sharded

``--sharded`` runs on every device JAX finds (an even count: the mesh is
devices/2 × 2). On a CPU host, emulate them before starting, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Builds a panel of coupled "neurons" where a few driver units force the
rest, then runs the whole workload through ONE ``repro.edm.EDM`` session:
per-series optimal embedding dimension, and the full N×N cross-map skill
matrix (grouped by E, exactly kEDM §3.4) reusing the optimal-E pass's
kNN master tables. ``--sharded`` hands the SAME session a device mesh —
the plan layer then routes the matrix through the E-grouped
zero-collective shard_map engine instead, no other code change.
"""

import argparse
import sys
import time

import jax
import numpy as np

from repro import compile_cache
from repro.data import timeseries as ts
from repro.edm import EDM, EDMConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--series", type=int, default=24)
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--drivers", type=int, default=2)
    ap.add_argument("--sharded", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    panel_np, adj = ts.forced_network_panel(
        args.series, args.steps, n_drivers=args.drivers, coupling=0.1,
        seed=11)
    N = args.series

    print(f"panel: {N} series × {args.steps} steps, "
          f"{args.drivers} hidden drivers")

    cfg = EDMConfig(E_max=5)
    n_dev = jax.device_count()
    if args.sharded:
        from repro.distributed import make_ccm_mesh
        if n_dev < 2 or n_dev % 2:
            sys.exit(f"--sharded needs an even number of devices, "
                     f"JAX found {n_dev}")
        cfg = cfg.replace(mesh=make_ccm_mesh((n_dev // 2, 2),
                                             ("data", "model")))
    sess = EDM(panel_np, cfg)

    t0 = time.time()
    E_opt, _ = sess.optimal_E()
    # CCM needs E ≥ 2: an E=1 'manifold' is a line and cross-map skill
    # from it is degenerate (biases the asymmetry statistic)
    E_opt = np.maximum(E_opt, 2)
    print(f"optimal-E search [{sess.plan('optimal_E').placement}]: "
          f"{time.time() - t0:.1f}s, E histogram: {np.bincount(E_opt)[1:]}")

    t0 = time.time()
    print(f"xmap plan: {sess.plan('xmap').describe()}")
    rho = sess.xmap(E_opt=E_opt)
    where = (f"sharded, {n_dev} devices, E-grouped"
             if args.sharded else "local, cached-kNN E-groups")
    print(f"CCM matrix ({where}): {time.time() - t0:.1f}s")

    # driver detection: evidence that unit d forces unit j is rho[j, d]
    # (cross-map the driver from the follower's manifold). The standard
    # CCM statistic is the ASYMMETRY rho[j, d] − rho[d, j]: common-drive
    # synchrony among followers is symmetric and cancels.
    drive_score = (rho - rho.T).mean(axis=0)
    ranked = np.argsort(-drive_score)
    print("units ranked by outgoing causal influence "
          f"(true drivers: {list(range(args.drivers))}):")
    for r, u in enumerate(ranked[: args.drivers + 3]):
        mark = " ← true driver" if u < args.drivers else ""
        print(f"  #{r + 1}: unit {u:3d} score {drive_score[u]:.3f}{mark}")
    top = args.drivers + 2  # common-drive confounds cost a rank or two
    hits = sum(1 for u in ranked[:top] if u < args.drivers)
    print(f"drivers recovered in top-{top}: {hits}/{args.drivers} "
          "(follower-follower links from shared forcing are a known CCM "
          "confound; the asymmetry statistic bounds, not eliminates, them)")

    # The whole-brain study gates every score on convergence + surrogate
    # significance; do the same for the strongest detected link. The 40
    # shuffled nulls run as ONE batched curve-grid program per test.
    d = int(ranked[0])
    follower = int(np.argmax(rho[:, d] - np.eye(N)[d] * 2))
    t0 = time.time()
    sig = sess.surrogate_test(follower, d, num_surrogates=40,
                              lib_sizes=(args.steps // 8, args.steps // 2,
                                         args.steps - 10), seed=0)
    print(f"link unit{d}→unit{follower}: convergence curve "
          f"{np.round(sig.rho, 3).tolist()}, surrogate p per size "
          f"{np.round(sig.pvalue, 3).tolist()} "
          f"({time.time() - t0:.1f}s, 40 shuffle nulls)")
    return 0 if hits == args.drivers else 1


if __name__ == "__main__":
    sys.exit(main())
