"""Panels made from the seed: forced logistic networks.

A copy of ``repro.data.timeseries.forced_network_panel`` (the first
``n_drivers`` series force all others, star topology), kept here so that
no change to the program can change the benchmark's data. One call makes
every panel of a cell at once: the map is stepped for all panels
together, each panel with its own random stream.
"""

from __future__ import annotations

import numpy as np


def panel_rng(seed: int, index: int) -> np.random.Generator:
    """The random stream of panel ``index`` under ``--seed`` (any int)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**64, int(index)]))


def forced_network_panels(n_panels: int, n_series: int, n_steps: int, *,
                          seed: int, n_drivers: int = 2,
                          coupling: float = 0.08,
                          discard: int = 100) -> np.ndarray:
    """(n_panels, n_series, n_steps) float32 forced logistic panels.

    Per panel: growth rates r ~ U(3.6, 3.9), initial states ~ U(0.2,
    0.8), per-(driver, follower) coupling weights ~ U(0.5, 1.5); then

        x'[d] = x[d]·(r[d] − r[d]·x[d])                  (drivers)
        x'[f] = x[f]·(r[f] − r[f]·x[f] − F[f])           (followers)
        F[f]  = coupling · Σ_d w[d, f]·x[d]

    clipped to [1e-6, 1 − 1e-6]; the first ``discard`` steps are dropped.
    """
    P, N = n_panels, n_series
    r = np.empty((P, N))
    x = np.empty((P, N))
    w = np.empty((P, n_drivers, N))
    for p in range(P):
        rng = panel_rng(seed, p)
        r[p] = rng.uniform(3.6, 3.9, size=N)
        x[p] = rng.uniform(0.2, 0.8, size=N)
        w[p] = rng.uniform(0.5, 1.5, size=(n_drivers, N))
    n = n_steps + discard
    out = np.empty((n, P, N), np.float32)
    rd, rf = r[:, :n_drivers], r[:, n_drivers:]
    wf = w[:, :, n_drivers:]
    for t in range(n):
        out[t] = x
        xd, xf = x[:, :n_drivers], x[:, n_drivers:]
        force = coupling * np.einsum("pd,pdf->pf", xd, wf)
        x_new = np.empty_like(x)
        x_new[:, :n_drivers] = xd * (rd - rd * xd)
        x_new[:, n_drivers:] = xf * (rf - rf * xf - force)
        x = np.clip(x_new, 1e-6, 1.0 - 1e-6)
    return np.ascontiguousarray(out[discard:].transpose(1, 2, 0))
