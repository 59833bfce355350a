"""Incremental master append ≡ cold rebuild, bit for bit.

The serving contract (ISSUE 8): ``ops.master_append`` grows a cached
multi-E kNN master by Δt points in O(Lp·(k+Δt)) per level and the result
must be indistinguishable — every distance bit, every index, every tie,
every garbage slot — from throwing the table away and rebuilding with
``ops.all_knn_multi_e`` on the full series. Anything weaker would make a
warm serving session's answers depend on its append history.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.edm.dataset import Dataset, screen_panel, series_stats
from repro.edm.plan import pad_master, panel_master_append
from repro.edm.session import EDM
from repro.kernels import ops, ref


def _series(rng, L, kind):
    x = rng.normal(size=L).astype(np.float32)
    if kind == "tie":  # heavy value collisions → exercises tie ordering
        x = np.round(x * 2) / 2
    return jnp.asarray(x)


def _cold_and_grown(x, *, L_old, E_max, tau, k, impl="ref"):
    d0, i0 = ref.all_knn_multi_e(x[:L_old], E_max=E_max, tau=tau, k=k)
    grown = ops.master_append(x, d0, i0, tau=tau, impl=impl)
    cold = ref.all_knn_multi_e(x, E_max=E_max, tau=tau, k=k)
    return grown, cold


def _assert_bit_equal(grown, cold, msg=""):
    np.testing.assert_array_equal(np.asarray(grown[0]), np.asarray(cold[0]),
                                  err_msg=f"distances {msg}")
    np.testing.assert_array_equal(np.asarray(grown[1]), np.asarray(cold[1]),
                                  err_msg=f"indices {msg}")


@pytest.mark.parametrize("L_new,E_max,tau,dt", [
    (100, 3, 1, 1),
    (100, 3, 1, 17),
    (154, 4, 2, 7),     # Lp not a multiple of anything convenient
    (211, 6, 1, 64),    # deep levels, big tick
    (40, 3, 2, 7),      # thin levels after the slice
    (400, 1, 1, 32),    # E_max=1: no delay structure at all
])
@pytest.mark.parametrize("kind", ["rand", "tie"])
def test_append_bit_identical_to_cold_rebuild(rng, L_new, E_max, tau, dt,
                                              kind):
    x = _series(rng, L_new, kind)
    L_old = L_new - dt
    Lp1 = L_old - (E_max - 1) * tau
    k = min(Lp1 + 3, 20, L_old - 1)
    grown, cold = _cold_and_grown(x, L_old=L_old, E_max=E_max, tau=tau, k=k)
    _assert_bit_equal(grown, cold, f"(L={L_new}, E={E_max}, tau={tau}, "
                                   f"dt={dt}, {kind})")


@pytest.mark.parametrize("L_new,E_max,tau,dt,k", [
    (30, 4, 2, 2, 25),   # k_m exceeds deep levels' candidate count:
    (24, 6, 1, 3, 20),   # garbage (inf) slots present before AND after
    (20, 3, 2, 4, 16),   # the append, pattern must match cold exactly
])
def test_append_garbage_slots_match_cold(rng, L_new, E_max, tau, dt, k):
    x = _series(rng, L_new, "rand")
    grown, cold = _cold_and_grown(x, L_old=L_new - dt, E_max=E_max,
                                  tau=tau, k=k)
    assert not bool(np.isfinite(np.asarray(cold[0])).all()), \
        "regime check: this grid is meant to produce garbage slots"
    _assert_bit_equal(grown, cold, "(garbage regime)")


@pytest.mark.parametrize("impl", ["interpret"])
@pytest.mark.parametrize("L_new,E_max,tau,dt", [
    (100, 3, 1, 7),
    (154, 4, 2, 17),
    (30, 4, 2, 2),       # garbage regime via the kernel path too
])
def test_kernel_path_matches_cold(rng, impl, L_new, E_max, tau, dt):
    """The Pallas selection kernel inherits the same bit contract."""
    x = _series(rng, L_new, "tie")
    L_old = L_new - dt
    Lp1 = L_old - (E_max - 1) * tau
    k = 25 if L_new == 30 else min(Lp1 + 3, 20, L_old - 1)
    grown, cold = _cold_and_grown(x, L_old=L_old, E_max=E_max, tau=tau,
                                  k=k, impl=impl)
    _assert_bit_equal(grown, cold, f"(kernel, L={L_new})")


def test_multi_tick_append_equals_one_cold_build(rng):
    """Append history must not leak into the table: many small ticks
    land bit-identically on the single cold build of the final series."""
    L = 163
    x = _series(rng, L, "rand")
    d, i = ref.all_knn_multi_e(x[:100], E_max=3, tau=1, k=8)
    for stop in (101, 108, 131, 163):
        d, i = ops.master_append(x[:stop], d, i, tau=1)
    cold = ref.all_knn_multi_e(x, E_max=3, tau=1, k=8)
    _assert_bit_equal((d, i), cold, "(4 ticks)")


def test_panel_append_matches_panel_master(rng):
    X = jnp.asarray(rng.normal(size=(6, 120)).astype(np.float32))
    from repro.edm.plan import panel_master
    dM, iM = panel_master(X[:, :100], E_max=4, tau=1, k=7, impl="ref")
    grown = panel_master_append(X, dM, iM, tau=1, impl="ref")
    cold = panel_master(X, E_max=4, tau=1, k=7, impl="ref")
    _assert_bit_equal(grown, cold, "(panel)")


def test_panel_append_kernel_path_matches_panel_master(rng):
    """The kernel path over a panel whose series fill no whole block of
    the fold (11 = 8 + 3), τ = 2: the grown master equals the cold one."""
    X = jnp.asarray(np.round(rng.normal(size=(11, 130)) * 2) / 2,
                    jnp.float32)              # ties
    from repro.edm.plan import panel_master
    dM, iM = panel_master(X[:, :112], E_max=4, tau=2, k=9, impl="ref")
    grown = panel_master_append(X, dM, iM, tau=2, impl="interpret")
    cold = panel_master(X, E_max=4, tau=2, k=9, impl="ref")
    _assert_bit_equal(grown, cold, "(panel, kernel path)")


def test_append_args_validated(rng):
    x = _series(rng, 50, "rand")
    d, i = ref.all_knn_multi_e(x, E_max=3, tau=1, k=5)
    with pytest.raises(ValueError):  # dt < 1: nothing appended
        ops.master_append(x, d, i, tau=1)
    with pytest.raises(ValueError):  # shrunk series
        ops.master_append(x[:40], d, i, tau=1)
    with pytest.raises(ValueError):  # dists/idx shape mismatch
        ops.master_append(jnp.concatenate([x, x[:4]]), d, i[:, :-1], tau=1)


# ---------------------------------------------------------------- sessions


def test_session_append_master_bit_matches_cold_session(rng):
    full = rng.normal(size=(5, 130)).astype(np.float32)
    warm = EDM(full[:, :100], E_max=4, cache=True)
    warm.optimal_E()                       # builds + caches the master
    warm.append(full[:, 100:])
    cold = EDM(full, E_max=4, cache=True)
    cold._master(warm._cache["master"][3])
    # The grown master is held at the capacity the append sized: the
    # cold tables in its first L rows, inf / PAD_IDX below.
    _assert_bit_equal(warm._cache["master"][:2],
                      pad_master(*cold._cache["master"][:2],
                                 warm.data.capacity), "(session master)")
    # ...and every consumer downstream of the master agrees too.
    np.testing.assert_array_equal(warm.optimal_E()[1], cold.optimal_E()[1])
    np.testing.assert_array_equal(np.asarray(warm.ccm(0, 2)),
                                  np.asarray(cold.ccm(0, 2)))
    assert warm.stats["knn_master_appends"] == 1
    assert warm.stats["knn_master_builds"] == 1


def test_session_append_without_master_stays_lazy(rng):
    sess = EDM(rng.normal(size=(4, 90)).astype(np.float32), E_max=3,
               cache=True)
    sess.append(rng.normal(size=(4, 5)).astype(np.float32))
    assert "master" not in sess._cache
    assert sess.stats.get("knn_master_appends", 0) == 0
    assert sess.data.L == 95


# ------------------------------------------------------- delta screening


def test_screen_panel_delta_mode_matches_full_screen(rng):
    full = rng.normal(size=(6, 80)).astype(np.float32)
    full[1, 70] = np.nan            # fault arrives in the delta
    full[3, :] = 2.5                # constant throughout
    prior = series_stats(full[:, :64])
    delta_recs = screen_panel(full[:, 64:], prior=prior)
    full_recs = screen_panel(full)
    assert ([r["index"] for r in delta_recs]
            == [r["index"] for r in full_recs] == [1, 3])
    assert "appended delta" in delta_recs[0]["reason"]
    assert delta_recs[1]["reason"] == "constant series"


def test_dataset_append_raise_names_series_and_mutates_nothing(rng):
    panel = rng.normal(size=(3, 60)).astype(np.float32)
    ds = Dataset(panel, names=["a", "b", "c"])
    bad = rng.normal(size=(3, 4)).astype(np.float32)
    bad[1, 2] = np.inf
    with pytest.raises(ValueError, match="series b"):
        ds.append(bad)
    assert ds.L == 60 and ds.valid.all() and not ds.invalid_report


def test_dataset_append_mask_and_drop_policies(rng):
    panel = rng.normal(size=(4, 60)).astype(np.float32)
    bad = rng.normal(size=(4, 4)).astype(np.float32)
    bad[2, 0] = np.nan
    dm = Dataset(panel, on_invalid="mask")
    recs = dm.append(bad)
    assert [r["index"] for r in recs] == [2]
    assert list(dm.valid) == [True, True, False, True]
    assert bool(np.isfinite(np.asarray(dm.panel)).all())

    dd = Dataset(panel, on_invalid="drop", names=list("wxyz"))
    recs = dd.append(bad)
    assert recs[0]["index"] == 2 and recs[0]["name"] == "y"
    assert dd.N == 3 and dd.names == ["w", "x", "z"] and dd.valid.all()


def test_dataset_append_constant_series_can_become_valid(rng):
    panel = rng.normal(size=(2, 50)).astype(np.float32)
    panel[1, :] = 7.0
    ds = Dataset(panel, on_invalid="mask")
    assert not ds.is_valid(1)
    delta = rng.normal(size=(2, 6)).astype(np.float32)
    assert ds.append(delta) == []          # nothing NEW became invalid
    assert ds.is_valid(1)                  # variation arrived: now usable


def test_session_append_drop_compacts_master_rows(rng):
    full = rng.normal(size=(5, 110)).astype(np.float32)
    bad = full[:, 100:].copy()
    bad[2, 3] = np.nan
    sess = EDM(Dataset(full[:, :100], on_invalid="drop"), E_max=3,
               cache=True)
    sess._master(3)
    sess.append(bad)
    keep = [0, 1, 3, 4]
    ref_full = full[keep].copy()
    ref_full[:, 100:] = np.asarray(bad)[keep]
    cold = EDM(ref_full, E_max=3, cache=True)
    cold._master(3)
    _assert_bit_equal(sess._cache["master"][:2],
                      pad_master(*cold._cache["master"][:2],
                                 sess.data.capacity), "(drop compaction)")


# ------------------------------------------------------------ capacity
#
# A live panel is held at a capacity C ≥ L: the master's rows past a
# level's valid rows are inf / PAD_IDX, and the append program reads the
# valid length as an operand. Append at C must equal the cold build of
# the grown series at the same C, bit for bit — which is the exact cold
# build padded to C rows.


def _at_capacity(tables, C):
    d, i = tables
    pad = ((0, 0), (0, C - d.shape[1]), (0, 0))
    return (jnp.pad(d, pad, constant_values=jnp.inf),
            jnp.pad(i, pad, constant_values=ref.PAD_IDX))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("L_old,dt,E_max,tau,C", [
    (90, 8, 3, 1, 128),
    (97, 5, 4, 2, 110),
    (60, 1, 6, 1, 64),
    (24, 3, 6, 1, 40),    # k_m beyond deep levels' candidates: garbage
])
@pytest.mark.parametrize("kind", ["rand", "tie"])
def test_append_at_capacity_bit_identical_to_cold_build_at_capacity(
        rng, impl, L_old, dt, E_max, tau, C, kind):
    L_new = L_old + dt
    x = _series(rng, L_new, kind)
    k = 22 if L_old == 24 else min(L_old - (E_max - 1) * tau + 3, 20)
    buf = jnp.zeros(C, jnp.float32).at[:L_new].set(x)
    sq, it = ref.append_state(buf, *_at_capacity(
        ref.all_knn_multi_e(x[:L_old], E_max=E_max, tau=tau, k=k), C),
        tau=tau)
    sq, it = ops.master_append_sq(buf[None], sq[:, :, None], it[:, :, None],
                                  length=jnp.int32(L_old), dt=dt, tau=tau,
                                  impl=impl)
    grown = (jnp.sqrt(jnp.maximum(sq[:, :, 0], 0.0)).swapaxes(1, 2),
             it[:, :, 0].swapaxes(1, 2))
    cold = _at_capacity(ref.all_knn_multi_e(x, E_max=E_max, tau=tau, k=k),
                        C)
    _assert_bit_equal(grown, cold, f"(capacity {C}, L {L_old}+{dt}, "
                                   f"E_max={E_max}, tau={tau}, {kind})")


def test_session_appends_within_capacity_match_cold_session_at_capacity(
        rng):
    full = rng.normal(size=(5, 124)).astype(np.float32)
    warm = EDM(full[:, :100], E_max=4, cache=True)
    warm.optimal_E()
    for a in (100, 108, 116):
        warm.append(full[:, a:a + 8])
    cold = EDM(full[:, :100], E_max=4, cache=True)
    cold.append(full[:, 100:])             # no master yet: built at C
    cold._master(4)
    assert warm.data.capacity == cold.data.capacity == 256
    assert warm.data.L == 124
    assert warm.stats["capacity_regrows"] == 1     # the first append's
    _assert_bit_equal(warm._cache["master"][:2], cold._cache["master"][:2],
                      "(session master at capacity)")
    pairs = [(0, 2), (3, 1), (4, 4)]
    got = warm.ccm_batch(pairs, E=3)
    np.testing.assert_array_equal(got, cold.ccm_batch(pairs, E=3))
    # Against the exact-L program the masked sums round in another order.
    exact = EDM(full, E_max=4, cache=True).ccm_batch(pairs, E=3)
    np.testing.assert_allclose(got, exact, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(warm.data.panel), full)


def test_append_past_capacity_regrows_once(rng):
    full = rng.normal(size=(4, 300)).astype(np.float32)
    sess = EDM(full[:, :100], E_max=3, cache=True)
    sess.optimal_E()
    sess.append(full[:, 100:108])              # sizes C = 256
    assert sess.data.capacity == 256
    with telemetry.record() as rec:
        sess.append(full[:, 108:250])          # 250 ≤ 256: no regrow
        sess.append(full[:, 250:258])          # 258 > 256: regrow
        sess.append(full[:, 258:300])          # 300 ≤ 384: no regrow
    assert rec.counter_delta("edm_capacity_regrows") == 1
    assert sess.data.capacity == 384           # 1.25 · 258, to 128s
    assert sess._cache["master"][0].shape[2] == 384
    cold = EDM(full[:, :100], E_max=3, cache=True)
    cold.append(full[:, 100:])
    cold._master(3)
    _assert_bit_equal(sess._cache["master"][:2], cold._cache["master"][:2],
                      "(regrown master)")
    pairs = [(0, 1), (2, 3), (3, 0)]
    got = sess.ccm_batch(pairs, E=2)
    np.testing.assert_array_equal(got, cold.ccm_batch(pairs, E=2))
    np.testing.assert_allclose(
        got, EDM(full, E_max=3, cache=True).ccm_batch(pairs, E=2),
        rtol=0, atol=1e-5)
