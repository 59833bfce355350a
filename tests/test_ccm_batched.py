"""Library-batched CCM matrix engine: batch-axis bit-parity, ragged
batches, launch counting, the auto B memory-budget rule, and the session
routing of ISSUE 5."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import core, telemetry
from repro.core import ccm
from repro.data import timeseries as ts
from repro.edm import EDM, EDMConfig
from repro.edm import plan as edm_plan


def _panel(n=7, steps=240, seed=5):
    panel, _ = ts.forced_network_panel(n, steps, seed=seed)
    return jnp.asarray(panel)


# --------------------------------------------------- batch-axis parity


def test_batched_bit_invariant_in_B_including_ragged():
    """The layout contract: results never depend on the batch size —
    B = 1 (the per-series oracle), a ragged split (Nl % B != 0), and a
    one-launch run are bit-identical."""
    X = _panel(7)
    runs = [core.ccm_group_batched(X, X, E=3, impl="ref", batch_libs=B)
            for B in (1, 2, 3, 7)]  # 7 % 2 and 7 % 3 != 0: ragged finals
    for got in runs[1:]:
        np.testing.assert_array_equal(runs[0], got)


def test_batched_matches_legacy_ccm_group():
    """Index/tie order is exact vs the legacy per-series ``lax.map``
    path by construction; ρ is bit-equal on these shapes (the ~1 ULP
    lax.map drift documented in kernels/ref.py shows up only at some
    shapes, e.g. Lp = 94 — see the bench's allclose guard there)."""
    X = _panel(6)
    for E, tau, Tp in ((2, 1, 0), (3, 2, 1), (5, 1, 2)):
        got = core.ccm_group_batched(X, X, E=E, tau=tau, Tp=Tp, impl="ref",
                                     batch_libs=4)
        want = np.asarray(core.ccm_group(X, X, E=E, tau=tau, Tp=Tp,
                                         impl="ref"))
        np.testing.assert_array_equal(got, want, err_msg=f"E={E}")


def test_batched_duplicate_manifold_tie_order():
    """Exact-duplicate library series get identical matrix rows — ties
    are broken by global neighbor index, not by batch position."""
    X = _panel(5)
    Xd = jnp.concatenate([X, X[:1]], axis=0)  # series 5 duplicates 0
    rho = core.ccm_group_batched(Xd, Xd, E=3, impl="ref", batch_libs=4)
    np.testing.assert_array_equal(rho[0], rho[5])


def test_batched_empty_library_axis():
    """Review follow-up: zero libraries → empty matrix, like ccm_group."""
    X = _panel(4)
    rho = core.ccm_group_batched(X[:0], X, E=2, impl="ref")
    assert rho.shape == (0, 4)
    sess = EDM(X, EDMConfig(E_max=4))
    sess.optimal_E()
    iM = sess._cache["master"][1]
    rho_m = edm_plan.ccm_group_from_master(
        X[:0], iM[:0, 1], X, E=2, tau=1, Tp=0, k=3, impl="ref")
    assert rho_m.shape == (0, 4)


def test_batched_single_target_and_custom_k():
    X = _panel(4)
    rho = core.ccm_group_batched(X, X[0], E=2, impl="ref", batch_libs=3)
    assert rho.shape == (4, 1)
    rho_k = core.ccm_group_batched(X, X, E=2, k=5, impl="ref", batch_libs=2)
    np.testing.assert_array_equal(
        rho_k, core.ccm_group_batched(X, X, E=2, k=5, impl="ref",
                                      batch_libs=4))


def test_master_batched_bit_invariant_and_matches_per_series():
    """The cached-master twin obeys the same layout contract and equals
    the legacy per-series derivation."""
    X = _panel(6)
    sess = EDM(X, EDMConfig(E_max=4))
    sess.optimal_E()
    dM, iM, k_m, lv = sess._cache["master"]
    E = 3
    launch = edm_plan.make_master_group_launch(
        X, iM[:, E - 1], X, E=E, tau=1, Tp=0, k=E + 1, impl="ref")
    runs = [ccm.drive_batched(6, B, launch) for B in (1, 4, 6)]
    for got in runs[1:]:
        np.testing.assert_array_equal(runs[0], got)
    legacy = np.asarray(edm_plan.ccm_group_from_master(
        X, iM[:, E - 1], X, E=E, tau=1, Tp=0, k=E + 1, impl="ref"))
    np.testing.assert_array_equal(runs[0], legacy)


# ------------------------------------------------------ launch counting


def test_engine_launch_count_ceil_nl_over_b():
    """ceil(Nl/B) engine launches, exactly — the padded ragged final
    batch rides in the last launch, never a retrace or an extra step.
    Counted via the ``edm_group_launches`` telemetry counter the launch
    closure increments at runtime (no cache clear needed — launches are
    per call, not per trace)."""
    X = _panel(7)
    launches = telemetry.counter("edm_group_launches")
    base = launches.value
    core.ccm_group_batched(X, X, E=3, impl="ref", batch_libs=3)
    assert launches.value - base == 3  # ceil(7/3)
    base = launches.value
    core.ccm_group_batched(X, X, E=3, impl="ref", batch_libs=7)
    assert launches.value - base == 1
    base = launches.value
    core.ccm_group_batched(X, X, E=3, impl="ref", batch_libs=100)  # clamped
    assert launches.value - base == 1


def test_session_xmap_launch_count():
    """The session's xmap drives each E-group with ceil(N/B) launches of
    the right engine: master-derived when the cached master covers the
    group, direct otherwise. Asserted via Recorder counter deltas on the
    two launch counters."""
    X = _panel(6)
    with telemetry.record() as rec:
        EDM(X, EDMConfig(E=3, batch_libs=2)).xmap()  # fixed E: one group
    assert rec.counter_delta("edm_group_launches") == 3  # ceil(6/2)
    assert rec.counter_delta("edm_master_launches") == 0  # no master built

    sess2 = EDM(X, EDMConfig(E_max=4, batch_libs=2))
    sess2.optimal_E()  # builds the master the xmap then derives from
    groups = len(set(sess2.optimal_E()[0].tolist()))
    with telemetry.record() as rec2:
        sess2.xmap()
    assert rec2.counter_delta("edm_group_launches") == 0
    assert rec2.counter_delta("edm_master_launches") == 3 * groups


def test_repeat_xmap_amortizes_via_master_on_second_call():
    """Review follow-up: a one-shot matrix skips the master build, but a
    REPEATING xmap workload on a caching session must recover the
    amortization — the second call builds the master once, later calls
    derive from it, and every call agrees bit-for-bit."""
    X = _panel(5)
    sess = EDM(X, EDMConfig(E=3))
    p0 = sess.plan("xmap")
    assert "direct engine" in p0.detail and p0.builds == ()
    first = sess.xmap()
    assert "master" not in sess._cache
    assert sess.stats["xmap_direct_runs"] == 1
    p1 = sess.plan("xmap")
    assert "cached kNN master" in p1.detail and p1.builds == ("master",)
    second = sess.xmap()
    assert sess.stats["knn_master_builds"] == 1
    third = sess.xmap()
    assert sess.stats["knn_master_builds"] == 1  # built once, reused
    np.testing.assert_array_equal(first, second)
    np.testing.assert_array_equal(first, third)


# ------------------------------------------------------- auto B sizing


def test_auto_batch_libs_budget_rule():
    # B·Lp²·4 bytes under the budget, clamped to [1, Nl]
    assert core.auto_batch_libs(1024, 500, budget_mb=64) == 16
    assert core.auto_batch_libs(4096, 64, budget_mb=64) == 1  # budget < Lp²
    assert core.auto_batch_libs(64, 100, budget_mb=64) == 100  # whole panel
    assert core.auto_batch_libs(1024, 8, budget_mb=1 << 20) == 8  # Nl clamp
    # launches are equalized under the cap: a 949-cap against Nl=1024
    # must not schedule a full launch plus a 75→949 padded one
    per_mb = 4 * 94 * 94 / 2**20
    B = core.auto_batch_libs(94, 1024, budget_mb=949 * per_mb)
    assert B == 512  # two even launches, both under the cap
    B_default = core.auto_batch_libs(1024, 500)  # backend-aware default
    assert B_default == core.auto_batch_libs(
        1024, 500, budget_mb=ccm._default_budget_mb())


def test_config_batch_knobs_validated():
    with pytest.raises(ValueError, match="batch_libs"):
        EDMConfig(batch_libs=0)
    with pytest.raises(ValueError, match="batch_budget_mb"):
        EDMConfig(batch_budget_mb=0)
    X = _panel(4)
    a = EDM(X, EDMConfig(E=2, batch_libs=3)).xmap()
    b = EDM(X, EDMConfig(E=2, batch_budget_mb=0.5)).xmap()  # tiny budget
    np.testing.assert_array_equal(a, b)  # knobs never change results


# ------------------------------------------------------ session parity


def test_session_xmap_equals_batched_composition_per_E_group():
    X = _panel(6)
    sess = EDM(X, EDMConfig(E_max=5))
    E_opt, _ = sess.optimal_E()
    got = sess.xmap()
    want = np.zeros((6, 6), np.float32)
    for E in sorted(set(E_opt.tolist())):
        m = np.nonzero(E_opt == E)[0]
        want[:, m] = core.ccm_group_batched(X, X[m], E=int(E), impl="ref")
    np.testing.assert_array_equal(got, want)


def test_sharded_local_block_batching_matches_unbatched():
    """The per-shard batched inner engine gives the same matrix for any
    B (1×1 mesh exercises the real shard_map path in-process)."""
    from repro.distributed import make_ccm_mesh, sharded_ccm_matrix
    X = _panel(5, 220)
    mesh = make_ccm_mesh((1, 1), ("data", "model"))
    runs = [np.asarray(sharded_ccm_matrix(X, X, E=2, mesh=mesh, impl="ref",
                                          batch_libs=B))
            for B in (1, 2, 5)]
    for got in runs[1:]:
        np.testing.assert_array_equal(runs[0], got)
    E_opt = np.array([2, 3, 2, 4, 3], np.int32)
    got_e = sharded_ccm_matrix(X, X, E_opt=E_opt, mesh=mesh, impl="ref",
                               batch_libs=2)
    np.testing.assert_allclose(got_e, core.ccm_matrix(X, E_opt),
                               rtol=1e-5, atol=1e-6)


def test_sharded_ccm_program_is_named_and_compiled_once():
    """The sharded matrix program is one named, cached jit: a repeat call
    on the same shapes compiles nothing in either E mode, and the trace
    sees it as ``jit_sharded_ccm_program``."""
    import jax

    from repro.distributed import make_ccm_mesh, sharded_ccm_matrix
    from repro.distributed.sharded_ccm import _ccm_program
    X = _panel(6, 220)
    mesh = make_ccm_mesh((1, 1), ("data", "model"))
    E_opt = np.array([2, 3, 2, 3, 3, 2], np.int32)

    def both():
        return (np.asarray(sharded_ccm_matrix(X, X, E=2, mesh=mesh,
                                              impl="ref")),
                sharded_ccm_matrix(X, X, E_opt=E_opt, mesh=mesh, impl="ref"))

    first = both()
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        again = both()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    prog = _ccm_program(((2, 6),), tau=1, Tp=0, impl="ref", batch_libs=None,
                        budget_mb=None, mesh=mesh, lib_axes=("data",),
                        tgt_axes=("model",))
    assert "jit_sharded_ccm_program" in prog.lower(X, X).as_text()


def test_egroup_layout_device_side_matches_host_reference():
    """The device-built permutation equals the old host-side layout:
    groups ascending by E, members in index order, each padded to a
    multiple of S by repeating its last member, interleaved per shard."""
    from repro.distributed.sharded_ccm import _egroup_layout, pad_members
    E_opt = np.array([3, 2, 5, 2, 2, 3, 5, 5, 5], np.int32)
    for S in (1, 2, 4):
        perm, keep, segs = _egroup_layout(jnp.asarray(E_opt), S)
        # host reference (the pre-PR-5 implementation)
        seg_perm, seg_keep, ref_segs = [], [], []
        for E in sorted(set(E_opt.tolist())):
            members = np.nonzero(E_opt == E)[0]
            padded = pad_members(members, S)
            kp = np.arange(len(padded)) < len(members)
            w = len(padded) // S
            ref_segs.append((int(E), w))
            seg_perm.append(padded.reshape(S, w))
            seg_keep.append(kp.reshape(S, w))
        np.testing.assert_array_equal(
            np.asarray(perm), np.concatenate(seg_perm, axis=1).reshape(-1))
        np.testing.assert_array_equal(
            keep, np.concatenate(seg_keep, axis=1).reshape(-1))
        assert segs == tuple(ref_segs)
