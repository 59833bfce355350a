"""Library-batched all-kNN ≡ the per-series pipeline, for every B/tiling."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.kernels import ops, ref
from repro.kernels.knn_batch import knn_batch_merge_share


def _per_series_oracle(x, *, E, tau, k, exclude_self, max_idx):
    """The fused standalone per-series pipeline (one jitted program)."""

    @jax.jit
    def one(x):
        D = ref.pairwise_distances(x, E=E, tau=tau)
        return ref.topk_select(D, k=k, exclude_self=exclude_self,
                               max_idx=max_idx)

    return one(x)


@pytest.mark.parametrize("L,B,E,tau,k", [
    (137, 5, 3, 2, None),
    (96, 9, 3, 1, None),     # short series (the shape where lax.map wobbles)
    (200, 3, 1, 1, None),
    (150, 4, 4, 1, 6),       # custom-k override
])
def test_ref_batch_matches_per_series_pipeline(rng, L, B, E, tau, k):
    X = jnp.asarray(rng.normal(size=(B, L)).astype(np.float32))
    Lp = L - (E - 1) * tau
    kk = E + 1 if k is None else k
    d, i = ref.all_knn_batch(X, E=E, tau=tau, k=k)
    assert d.shape == i.shape == (B, Lp, kk)
    for b in range(B):
        want_d, want_i = _per_series_oracle(
            X[b], E=E, tau=tau, k=kk, exclude_self=True, max_idx=Lp - 1)
        np.testing.assert_array_equal(np.asarray(i[b]), np.asarray(want_i),
                                      err_msg=f"series {b}")
        # Distances: ~1 ULP, not bit-equal — the oracle is a DIFFERENT
        # XLA program (2-D accumulation) and XLA CPU may contract it
        # differently from the batched (B, Lp, Lp) stream at some
        # shapes. Bit-equality is only contracted in B (next test).
        np.testing.assert_allclose(np.asarray(d[b]), np.asarray(want_d),
                                   rtol=2e-7, atol=2e-7,
                                   err_msg=f"series {b}")


def test_ref_batch_is_bit_invariant_in_B(rng):
    """The layout contract: any batch decomposition gives identical
    tables — the per-series oracle is the B = 1 launch."""
    X = jnp.asarray(rng.normal(size=(11, 233)).astype(np.float32))
    d_all, i_all = ref.all_knn_batch(X, E=4, tau=1)
    for sl in (slice(0, 1), slice(3, 10), slice(10, 11)):
        d_s, i_s = ref.all_knn_batch(X[sl], E=4, tau=1)
        np.testing.assert_array_equal(np.asarray(d_all[sl]), np.asarray(d_s))
        np.testing.assert_array_equal(np.asarray(i_all[sl]), np.asarray(i_s))


def test_ref_batch_max_idx_and_no_self(rng):
    X = jnp.asarray(rng.normal(size=(4, 150)).astype(np.float32))
    for excl in (True, False):
        for cap in (0, 40):
            d, i = ref.all_knn_batch(X, E=3, tau=1, max_idx=cap,
                                     exclude_self=excl)
            if cap >= 4:  # slots below k valid candidates carry arbitrary
                assert int(np.asarray(i).max()) <= cap  # zero-weight idx
            for b in range(4):
                want_d, want_i = _per_series_oracle(
                    X[b], E=3, tau=1, k=4, exclude_self=excl, max_idx=cap)
                np.testing.assert_array_equal(np.asarray(i[b]),
                                              np.asarray(want_i))
                np.testing.assert_array_equal(np.asarray(d[b]),
                                              np.asarray(want_d))


def test_ref_batch_duplicate_series_tie_order(rng):
    """Exact-duplicate manifolds must produce identical tables (ties
    broken by global index, independent of batch position)."""
    X = jnp.asarray(rng.normal(size=(3, 180)).astype(np.float32))
    Xd = jnp.concatenate([X, X[:1]], axis=0)
    d, i = ref.all_knn_batch(Xd, E=3, tau=1)
    np.testing.assert_array_equal(np.asarray(d[0]), np.asarray(d[3]))
    np.testing.assert_array_equal(np.asarray(i[0]), np.asarray(i[3]))


@pytest.mark.parametrize("L,B,E,tau,k,block,max_idx,levels", [
    (137, 4, 3, 2, None, (16, 128), None, 0),  # gj > 1: streaming merge
    (200, 3, 1, 1, None, (32, 128), None, 0),
    (96, 5, 3, 1, 4, (8, 128), None, 0),
    (300, 2, 4, 1, None, (64, 128), None, 0),  # 3 column tiles, partial last
    # Gated merge: Lp ≥ 4·bc, so most column blocks run only the passes
    # a candidate of theirs can win.
    (600, 2, 1, 1, None, (8, 128), None, 0),
    (700, 2, 10, 1, None, (16, 128), None, 0),
    (700, 1, 20, 1, None, (8, 128), None, 0),  # k = 21
    (600, 2, 1, 1, None, (16, 128), None, 3),  # ties across column blocks
    (700, 2, 10, 1, None, (8, 128), None, 4),
    (700, 1, 20, 2, None, (16, 128), None, 2),
    (700, 2, 10, 1, None, (16, 128), 6, 0),    # rows with < k valid
    (600, 2, 20, 1, None, (8, 128), 300, 3),
])
def test_interpret_kernel_matches_ref(rng, L, B, E, tau, k, block, max_idx,
                                      levels):
    """Bit-identical tables: the same accumulation order, and the same
    (distance, index) selection, for any tiling of the merge."""
    if levels:  # a few levels: exact distance ties everywhere
        X = np.floor(rng.uniform(size=(B, L)) * levels)
    else:
        X = rng.normal(size=(B, L))
    X = jnp.asarray(X.astype(np.float32))
    want_d, want_i = ref.all_knn_batch(X, E=E, tau=tau, k=k,
                                       max_idx=max_idx)
    got_d, got_i = ops.all_knn_batch(X, E=E, tau=tau, k=k, max_idx=max_idx,
                                     impl="interpret", block=block)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def _merge_passes_model(X, *, E, tau, k, block):
    """Merge passes of the gated kernel, from the reference's distances:
    k for column block 0 of each (series, row tile); for a later block,
    min(k, the most candidates of any row of the tile that precede the
    row's k-th best so far in (distance, index) order)."""
    br, bc = block
    total = 0
    for x in X:
        D = np.array(ref.pairwise_distances(x, E=E, tau=tau))
        Lp = D.shape[0]
        np.fill_diagonal(D, np.inf)
        gj = -(-Lp // bc)
        D = np.pad(D, ((0, 0), (0, gj * bc - Lp)), constant_values=np.inf)
        cols = np.arange(gj * bc)
        # Running k best per row, sorted by (distance, index): a stable
        # sort by distance keeps index order, since the list's indices
        # are sorted within ties and all precede the block's.
        best_d, best_i = D[:, :0], cols[:0][None].repeat(Lp, 0)
        passes = np.zeros(-(-Lp // br), np.int64)
        for j in range(gj):
            blk_d, blk_i = D[:, j * bc:(j + 1) * bc], cols[j * bc:(j + 1) * bc]
            if j == 0:
                passes += k
            else:
                d_k, i_k = best_d[:, -1:], best_i[:, -1:]
                q = (blk_d < d_k) | ((blk_d == d_k) & (blk_i[None] < i_k))
                count = q.sum(axis=1)
                for t in range(len(passes)):
                    passes[t] += min(k, count[t * br:(t + 1) * br].max())
            cand_d = np.concatenate([best_d, blk_d], axis=1)
            cand_i = np.concatenate(
                [best_i, np.broadcast_to(blk_i, blk_d.shape)], axis=1)
            order = np.argsort(cand_d, axis=1, kind="stable")[:, :k]
            best_d = np.take_along_axis(cand_d, order, axis=1)
            best_i = np.take_along_axis(cand_i, order, axis=1)
        total += int(passes.sum())
    return total


def _logistic(L, r=3.9, x0=0.4):
    x = np.empty(L)
    x[0] = x0
    for t in range(1, L):
        x[t] = r * x[t - 1] * (1 - x[t - 1])
    return x


@pytest.mark.parametrize("L,B,E,block,data", [
    (140, 2, 3, (8, 128), "normal"),      # Lp ≤ bc: one block, share 1.0
    (700, 2, 10, (16, 128), "normal"),
    (600, 1, 20, (8, 128), "quantised"),
    (4096, 1, 3, (8, 128), "logistic"),
])
def test_merge_share_counts_the_gated_passes(rng, L, B, E, block, data):
    """The kernel's pass output equals a numpy model of the gate exactly;
    the share is 1 in one column block and well under it on a long
    chaotic series."""
    if data == "logistic":
        X = _logistic(L)[None]
    elif data == "quantised":
        X = np.floor(rng.uniform(size=(B, L)) * 3)
    else:
        X = rng.normal(size=(B, L))
    X = X.astype(np.float32)
    k = E + 1
    ran0 = telemetry.counter("knn_merge_passes").value
    slots0 = telemetry.counter("knn_merge_slots").value
    share = knn_batch_merge_share(jnp.asarray(X), E=E, block=block,
                                  interpret=True)
    ran = telemetry.counter("knn_merge_passes").value - ran0
    slots = telemetry.counter("knn_merge_slots").value - slots0
    Lp = L - (E - 1)
    br, bc = block[0], max(128, min(block[1], Lp))  # the kernel's clamp
    assert slots == k * B * -(-Lp // br) * -(-Lp // bc)
    assert share == ran / slots
    assert ran == _merge_passes_model(X, E=E, tau=1, k=k, block=(br, bc))
    if Lp <= bc:
        assert share == 1.0
    if data == "logistic":
        assert share < 0.7


def test_interpret_kernel_b_invariance(rng):
    """Kernel-path layout contract: the per-series tiling is independent
    of B, so a batch launch equals its own B = 1 launches bit-for-bit."""
    X = jnp.asarray(rng.normal(size=(5, 120)).astype(np.float32))
    d_all, i_all = ops.all_knn_batch(X, E=3, tau=1, impl="interpret",
                                     block=(16, 128))
    for b in range(5):
        d1, i1 = ops.all_knn_batch(X[b:b + 1], E=3, tau=1,
                                   impl="interpret", block=(16, 128))
        np.testing.assert_array_equal(np.asarray(d_all[b]),
                                      np.asarray(d1[0]))
        np.testing.assert_array_equal(np.asarray(i_all[b]),
                                      np.asarray(i1[0]))


def test_interpret_kernel_caps_and_fewer_valid_than_k(rng):
    """Rows with < k valid candidates emit distinct lowest-index fill
    entries (retire-by-index in the streaming merge), matching the ref."""
    X = jnp.asarray(rng.normal(size=(3, 100)).astype(np.float32))
    for cap in (0, 1, 30):
        want_d, want_i = ref.all_knn_batch(X, E=3, tau=1, max_idx=cap)
        got_d, got_i = ops.all_knn_batch(X, E=3, tau=1, max_idx=cap,
                                         impl="interpret", block=(16, 128))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
        np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                                   rtol=1e-6, atol=1e-6)


def test_batch_rejects_bad_rank():
    with pytest.raises(ValueError, match=r"\(B, L\)"):
        ref.all_knn_batch(jnp.zeros(32), E=2)
    with pytest.raises(ValueError, match=r"\(B, L\)"):
        from repro.kernels.knn_batch import all_knn_batch
        all_knn_batch(jnp.zeros(32), E=2)
