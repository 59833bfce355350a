"""Master-derived neighbour tables: the sort-free compaction in
``edm.plan`` against its stable-argsort oracle, and the optimal-E curves
it feeds against the legacy per-series sweep — both bit for bit."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.simplex import rho_curve
from repro.edm import plan
from repro.kernels.ref import PAD_IDX


def _derive_sorted(dE, iE, *, k, max_idx):
    """The stable-argsort derivation the compaction replaced: the test
    oracle. Sorting the invalid flags stably keeps the valid slots in
    master order; a gather along the slot axis reads them."""
    valid = (iE >= 0) & (iE <= max_idx)
    order = jnp.argsort(jnp.where(valid, 0, 1).astype(jnp.int32), axis=-1,
                        stable=True)[..., :k]
    ok = jnp.take_along_axis(valid, order, axis=-1)
    d = jnp.where(ok, jnp.take_along_axis(dE, order, axis=-1), jnp.inf)
    i = jnp.where(ok, jnp.take_along_axis(iE, order, axis=-1), -1)
    return d, i, ok


K_MASTER = 10  # E_max = 8: k_master = E_max + 1 + slack 1


def _tables(case, seed=0, shape=(3, 40)):
    """(dE, iE, max_idx) master-shaped rows for one validity case."""
    rng = np.random.default_rng(seed)
    rows = shape + (K_MASTER,)
    d = np.sort(rng.random(rows).astype(np.float32) * 4, axis=-1)
    i = rng.integers(0, 60, rows).astype(np.int32)
    max_idx = 30
    if case == "fewer_than_k":  # most slots past the cap
        i = np.where(rng.random(rows) < 0.8, 45, i).astype(np.int32)
    elif case == "all_invalid":
        i[:] = 50
    elif case == "pad_idx":  # a level's padding: inf / PAD_IDX slots
        pad = rng.random(rows) < 0.4
        i = np.where(pad, PAD_IDX, i).astype(np.int32)
        d = np.where(pad, np.inf, d).astype(np.float32)
    elif case == "ties":  # equal distances, inf among the kept ones
        d = np.round(d).astype(np.float32)
        d[..., -3:] = np.inf
    return jnp.asarray(d), jnp.asarray(i), max_idx


CASES = ("random", "fewer_than_k", "all_invalid", "pad_idx", "ties")


@pytest.mark.parametrize("k", [2, 9])  # 9 = E_max + 1
@pytest.mark.parametrize("case", CASES)
def test_compaction_equals_stable_argsort(case, k):
    dE, iE, mx = _tables(case, seed=CASES.index(case))
    want_d, want_i, want_ok = _derive_sorted(dE, iE, k=k, max_idx=mx)
    d, i = plan._derive(dE, iE, k=k, max_idx=mx)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    # the index-only form, batched and per series on a traced cap
    i2, ok = plan._derive_idx(iE, k=k, max_idx=mx)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(want_ok))
    i3, ok3 = plan._derive_idx(iE[0], k=k, max_idx=jnp.int32(mx))
    np.testing.assert_array_equal(np.asarray(i3), np.asarray(want_i[0]))
    np.testing.assert_array_equal(np.asarray(ok3), np.asarray(want_ok[0]))
    if case == "all_invalid":
        assert not np.asarray(ok).any()
        assert np.isinf(np.asarray(d)).all() and (np.asarray(i) == -1).all()


def _panel(n, L, seed):
    rng = np.random.default_rng(seed)
    t = np.arange(L)
    X = np.sin(0.3 * t * (1 + rng.random((n, 1))))
    return (X + 0.3 * rng.standard_normal((n, L))).astype(np.float32)


def _curves_case(X, *, E_max, tau, Tp, capacity=None):
    k_m = E_max + 1 + max(1, Tp)  # the session's master width
    dM, iM = plan.panel_master(jnp.asarray(X), E_max=E_max, tau=tau, k=k_m,
                               impl="ref")
    if capacity is not None:
        dM, iM = plan.pad_master(dM, iM, capacity)
    got = plan.rho_curves_from_master(jnp.asarray(X), dM, iM, E_max=E_max,
                                      tau=tau, Tp=Tp, impl="ref")
    want = np.stack([np.asarray(rho_curve(jnp.asarray(x), E_max=E_max,
                                          tau=tau, Tp=Tp, impl="ref"))
                     for x in X])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("E_max", [3, 8])
@pytest.mark.parametrize("Tp", [0, 1, 2])
@pytest.mark.parametrize("tau", [1, 2])
def test_rho_curves_from_master_equal_rho_curve(tau, Tp, E_max):
    _curves_case(_panel(3, 90, seed=10 * tau + Tp), E_max=E_max, tau=tau,
                 Tp=Tp)


@pytest.mark.parametrize("kind", ["capacity", "masked"])
def test_rho_curves_from_master_capacity_and_masked(kind):
    """A capacity master (rows past L read inf / PAD_IDX) and a masked-
    invalid series (its non-finite entries zeroed: a run of tied
    distances) give the legacy sweep's curves, bit for bit."""
    X = _panel(3, 90, seed=7)
    if kind == "capacity":
        _curves_case(X, E_max=4, tau=1, Tp=1, capacity=128)
    else:
        X[1, 20:50] = 0.0
        X[2] = 0.0
        _curves_case(X, E_max=4, tau=1, Tp=1)
