"""Quickstart: the paper's EDM toolkit in five minutes — session API.

Run:  PYTHONPATH=src python examples/quickstart.py

Covers the full kEDM surface on synthetic chaotic systems through ONE
``repro.edm.EDM`` session per dataset: simplex forecasting, optimal
embedding dimension, the S-Map nonlinearity test, and convergent cross
mapping with its convergence-in-library-size causality criterion. Note
what never happens below: no E/tau/Tp re-threading between calls, and no
neighbor table is ever computed twice — the session's plan layer caches
the multi-E kNN state and every method reuses it.
"""

import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.data import timeseries as ts
from repro.edm import EDM, EDMConfig


def main():
    compile_cache.enable()
    print("=" * 64)
    print("1. Simplex projection: forecasting deterministic chaos")
    x = jnp.asarray(ts.logistic_map(500))
    for tp in (1, 2, 5, 10):
        rho = float(EDM(x, EDMConfig(E=2, Tp=tp)).simplex()[0])
        print(f"   horizon Tp={tp:2d}: forecast skill ρ = {rho:.4f}")
    print("   (skill decays with horizon — the signature of chaos)")

    print("=" * 64)
    print("2. Optimal embedding dimension (Lorenz-63, true dim ≈ 3)")
    lz = EDM(ts.lorenz63(800)[0], EDMConfig(E_max=8, tau=2))
    E_opt, rhos = lz.optimal_E()
    for E, r in enumerate(rhos[0], start=1):
        marker = " ← chosen" if E == int(E_opt[0]) else ""
        print(f"   E={E}: ρ={float(r):.4f}{marker}")

    print("=" * 64)
    print("3. S-Map nonlinearity test (ρ rising with θ ⇒ nonlinear)")
    thetas = (0.0, 0.5, 2.0, 8.0)
    sess = EDM(x, EDMConfig(E=2, thetas=thetas))
    for t, r in zip(thetas, sess.smap()[0]):
        print(f"   θ={t:4.1f}: ρ={r:.4f}")

    print("=" * 64)
    print("4. CCM: who causes whom? (X forces Y, not vice versa)")
    xs, ys = ts.coupled_logistic(900, b_xy=0.0, b_yx=0.32, seed=3)
    from repro.edm import Dataset
    pair = EDM(Dataset(np.stack([xs, ys]), names=["X", "Y"]),
               EDMConfig(E=2, Tp_cross=0))
    sizes = (60, 200, 500, 880)
    x_from_y = pair.ccm("Y", "X", lib_sizes=sizes)
    y_from_x = pair.ccm("X", "Y", lib_sizes=sizes)
    print("   lib size | X̂|M_Y (X→Y evidence) | Ŷ|M_X (Y→X evidence)")
    for s, a, b in zip(sizes, x_from_y, y_from_x):
        print(f"   {s:8d} | {a:20.4f} | {b:19.4f}")
    print("   (left column converges high: X causes Y; right stays low)")
    # a score alone is not evidence — gate it on a surrogate ensemble
    # (50 shuffled nulls, cross-mapped as ONE batched program)
    sig = pair.surrogate_test("Y", "X", num_surrogates=50, seed=0)
    rev = pair.surrogate_test("X", "Y", num_surrogates=50, seed=0)
    print(f"   vs 50 shuffle nulls: X→Y p = {sig.pvalue:.3f}, "
          f"Y→X p = {rev.pvalue:.3f}")
    print("   (a shuffle null rejects 'no dependence at all'; the "
          "direction verdict is the convergence asymmetry above)")

    print("=" * 64)
    print("5. One session, every method — state shared, plans visible")
    panel, _ = ts.forced_network_panel(6, 400, n_drivers=1, seed=7)
    sess = EDM(panel, EDMConfig(E_max=5))
    print("   plan:", sess.plan("optimal_E").describe())
    E_opt, _ = sess.optimal_E()
    print(f"   optimal E per series: {E_opt.tolist()}")
    print("   plan:", sess.plan("xmap").describe())
    rho = sess.xmap()  # reuses the kNN master built by optimal_E
    print(f"   cross-map matrix mean skill: {rho.mean():.3f}  "
          f"(stats: {dict(sess.stats)})")


if __name__ == "__main__":
    main()
