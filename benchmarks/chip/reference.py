"""Plain reference of the EDM answers the benchmark checks.

Written from the method's definition (Sugihara et al. 2012; kEDM,
arXiv 2105.12301), in straightforward ``jax.numpy``, importing nothing
of the program under test:

* Delay embedding: point i of a series x is (x[i], x[i+τ], …,
  x[i+(E−1)τ]), i in [0, Lp) with Lp = L − (E−1)τ.
* Neighbours: for each query point i < rows = Lp − Tp, the k = E + 1
  library points j ≠ i, j ≤ Lp − 1 − Tp, nearest in squared Euclidean
  distance; ties go to the smaller j.
* Weights: w = exp(−d / d_min) over the k Euclidean distances,
  normalised to sum 1 (d_min guarded at 1e-30).
* Prediction of a target y at query i: Σ_k w·y[j_k + (E−1)τ + Tp],
  against the truth y[i + (E−1)τ + Tp]; skill ρ is their Pearson
  correlation over the rows (two-pass).

Cross-map skill (CCM, ``xmap``, served ``ccm``) embeds the library and
predicts other targets with Tp = 0; simplex skill (the optimal-E sweep)
predicts the library series itself at Tp = 1.

``dtype`` is the precision of the embedding distances and the
neighbour search. The benchmark's answers use float32; the control
reruns this reference with ``jnp.bfloat16`` there, the lower precision
a faster kernel would be tempted by, and the comparison must reject it.
Everything after the search (weights, lookup, ρ) is float32 either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024  # query rows per distance block: (block, Lp) at a time


def _neighbours(x, *, E, tau, Tp, k, dtype):
    """(rows, k) Euclidean distances (f32) and indices of one library."""
    L = x.shape[-1]
    Lp = L - (E - 1) * tau
    rows, cap = Lp - Tp, Lp - 1 - Tp
    xd = x.astype(dtype)
    lags = jnp.stack([xd[e * tau: e * tau + Lp] for e in range(E)])  # (E, Lp)
    nb = -(-rows // QUERY_BLOCK)
    q_all = jnp.arange(nb * QUERY_BLOCK, dtype=jnp.int32)
    cols = jnp.arange(Lp, dtype=jnp.int32)

    def block(q):  # q: (QUERY_BLOCK,) query indices
        qc = jnp.minimum(q, Lp - 1)
        d2 = jnp.zeros((QUERY_BLOCK, Lp), dtype)
        for e in range(E):
            diff = lags[e][qc][:, None] - lags[e][None, :]
            d2 = d2 + diff * diff
        bad = (cols[None, :] == q[:, None]) | (cols[None, :] > cap)
        d2 = jnp.where(bad, jnp.asarray(jnp.inf, dtype), d2)
        neg, idx = jax.lax.top_k(-d2, k)
        return jnp.sqrt(-neg.astype(jnp.float32)), idx

    d, i = jax.lax.map(block, q_all.reshape(nb, QUERY_BLOCK))
    return (d.reshape(-1, k)[:rows], i.reshape(-1, k)[:rows])


def _weights(d):
    d_min = jnp.maximum(d[:, :1], 1e-30)
    w = jnp.exp(-d / d_min)
    return w / jnp.sum(w, axis=-1, keepdims=True)


def _pearson(a, b):
    am = a - jnp.mean(a, axis=-1, keepdims=True)
    bm = b - jnp.mean(b, axis=-1, keepdims=True)
    return (jnp.sum(am * bm, axis=-1)
            / jnp.sqrt(jnp.sum(am * am, axis=-1) * jnp.sum(bm * bm, axis=-1)))


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "dtype"))
def skill(libs, targets, *, E, tau, Tp, dtype=jnp.float32):
    """(B, L) libraries × (Nt, L) targets → (B, Nt) ρ at one E."""
    libs = libs.astype(jnp.float32)
    targets = targets.astype(jnp.float32)
    off = (E - 1) * tau + Tp

    def one(x):
        d, i = _neighbours(x, E=E, tau=tau, Tp=Tp, k=E + 1, dtype=dtype)
        rows = d.shape[0]
        w = _weights(d)
        yhat = jnp.einsum("nrk,rk->nr", targets[:, i + off], w)
        truth = jax.lax.dynamic_slice_in_dim(targets, off, rows, axis=-1)
        return _pearson(yhat, truth)

    return jax.lax.map(one, libs)


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "dtype"))
def self_skill(series, *, E, tau, Tp, dtype=jnp.float32):
    """(S, L) series → (S,) simplex skill of each series on itself."""
    return jax.lax.map(
        lambda x: skill(x[None], x[None], E=E, tau=tau, Tp=Tp,
                        dtype=dtype)[0, 0], series.astype(jnp.float32))


def rho_curves(series, *, E_max, tau, Tp, dtype=jnp.float32) -> np.ndarray:
    """(S, L) series → (S, E_max) simplex skill for E = 1..E_max."""
    x = jnp.asarray(series)
    return np.stack([np.asarray(self_skill(x, E=E, tau=tau, Tp=Tp,
                                           dtype=dtype))
                     for E in range(1, E_max + 1)], axis=1)
