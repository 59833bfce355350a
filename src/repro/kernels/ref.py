"""Pure-jnp reference oracles for the EDM kernels.

These are the ground truth the Pallas kernels are validated against
(``tests/test_kernels_*``) and the path that multi-pod dry-runs lower
(the container's CPU backend cannot compile Mosaic/TPU kernels).

Index conventions (0-based, matching DESIGN.md §2):
  - delay embedding of a series ``x`` of length L with dimension E and lag tau:
        z_i[k] = x[i + k*tau],   k in [0, E),  i in [0, Lp),
    where ``Lp = L - (E-1)*tau`` is the number of embedded points.
  - embedded point i corresponds to *time* index ``t = i + (E-1)*tau``
    (its most recent component).
  - a lookup with horizon Tp reads target values at
    ``I[j, k] + (E-1)*tau + Tp`` — callers pass that combined ``offset``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_INF = np.float32(np.inf)  # numpy, not jnp: importing must not start a backend


def strict_sq(d: jax.Array) -> jax.Array:
    """The rounded square fl(d·d), pinned to strict IEEE at every shape.

    Every distance chain in the repo accumulates ``acc ± d·d``. Left
    bare, XLA CPU's backend (LLVM) may contract the multiply and the
    accumulate into one FMA — one rounding instead of two — and whether
    it does depends on how the surrounding program fused and vectorized,
    i.e. on *buffer shapes*. That makes accumulator bits a function of
    program shape, which breaks every bit-parity contract in the repo:
    ``master_append``'s gathered/slab recomputes vs the cold (L, L)
    build, derived-table recomputes vs engine outputs, and multi-E vs
    per-E cross-checks. (``lax.optimization_barrier`` does NOT help: the
    contraction happens below HLO, inside a fused loop body — measured.)

    The guard select breaks the mul→add edge the contraction pattern
    needs: ``d·d > −1`` is always true for real data, but neither XLA's
    simplifier nor LLVM can prove it (without ``nnan``, ``d·d`` may be
    NaN and the select must keep the 0.0 arm), so the select survives to
    codegen and the product is materialized with its own rounding —
    strict two-rounding semantics at any shape, matching a scalar numpy
    ``fl(acc ± fl(d·d))`` chain exactly. NaN products select 0.0; inputs
    are screened finite, so that arm is dead in practice.
    """
    d2 = d * d
    return jnp.where(d2 > -1.0, d2, jnp.zeros_like(d2))


def num_embedded(L: int, E: int, tau: int) -> int:
    """Number of valid delay-embedding vectors."""
    n = L - (E - 1) * tau
    if n <= 0:
        raise ValueError(f"series too short: L={L}, E={E}, tau={tau}")
    return n


def delay_embed(x: jax.Array, E: int, tau: int) -> jax.Array:
    """Materialized time-delay embedding, shape (Lp, E).

    Only used by tests and the S-Map solver; the distance kernels fuse
    this step (the paper's core optimization).
    """
    L = x.shape[-1]
    Lp = num_embedded(L, E, tau)
    cols = [jax.lax.dynamic_slice_in_dim(x, k * tau, Lp, axis=-1) for k in range(E)]
    return jnp.stack(cols, axis=-1)


def lag_rows(x: jax.Array, *, E: int, tau: int, width: int) -> jax.Array:
    """Lag-shifted copies ``out[…, e, j] = x[…, j + e·τ]`` → (…, E, width).

    The layout the Pallas kernels read lag terms from. A tile starting at
    column j0 finds its e-th lag at ``[e, j0:j0+b]``, so every in-kernel
    slice starts on a tile boundary: Mosaic refuses the unaligned lane
    offset ``j0 + e·τ`` that slicing the raw series would need. ``x``
    must be at least ``width + (E−1)·τ`` long. Kernels that read lags
    on sublanes (row tiles) take the (…, width, E) transpose.
    """
    return jnp.stack([jax.lax.slice_in_dim(x, e * tau, e * tau + width,
                                           axis=-1) for e in range(E)],
                     axis=-2)


@functools.partial(jax.jit, static_argnames=("E", "tau"))
def pairwise_distances(x: jax.Array, *, E: int, tau: int) -> jax.Array:
    """Squared-Euclidean pairwise distance matrix of the delay embedding.

    Fused formulation (no (Lp, E) matrix is materialized): accumulates
    ``(x[i+k*tau] - x[j+k*tau])**2`` over k. Returns (Lp, Lp) float32.
    """
    x = x.astype(jnp.float32)
    Lp = num_embedded(x.shape[-1], E, tau)
    acc = jnp.zeros((Lp, Lp), jnp.float32)
    for k in range(E):
        xk = jax.lax.dynamic_slice_in_dim(x, k * tau, Lp, axis=-1)
        d = xk[:, None] - xk[None, :]
        acc = acc + strict_sq(d)
    return acc


@functools.partial(jax.jit, static_argnames=("k", "exclude_self"))
def topk_select(
    D: jax.Array,
    *,
    k: int,
    exclude_self: bool = True,
    max_idx: jax.Array | int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Partial sort: k smallest entries per row of a squared-distance matrix.

    Returns (dists, idx): ``dists`` are *Euclidean* (sqrt applied — the
    "normalize" step of the paper's Algorithm 2), sorted ascending, shape
    (Lp, k); ``idx`` int32 embedded indices.

    ``exclude_self`` masks the diagonal (CCM/simplex leave-one-out).
    ``max_idx`` (inclusive) restricts neighbor candidates — used for
    Tp-horizon validity and library-size convergence sweeps.
    """
    Lp = D.shape[0]
    cols = jnp.arange(Lp, dtype=jnp.int32)
    mask = jnp.zeros((Lp, Lp), bool)
    if exclude_self:
        mask = mask | jnp.eye(Lp, dtype=bool)
    if max_idx is not None:
        mask = mask | (cols[None, :] > jnp.asarray(max_idx, jnp.int32))
    Dm = jnp.where(mask, _INF, D)
    # Two-stage chunk-max top-k (exact incl. ties — see _chunked_topk):
    # ~W/k× fewer elements through XLA-CPU's sequential TopK scan than the
    # plain full-row jax.lax.top_k the seed used.
    neg_d, idx = _chunked_topk(-Dm, k)
    return jnp.sqrt(jnp.maximum(-neg_d, 0.0)), idx.astype(jnp.int32)


def check_sizes_caps(max_idxs) -> tuple[int, ...]:
    """Validate a multi-cap tuple (non-empty, >= 0, ascending) → ints.

    The one contract both ``topk_select_sizes`` implementations (this
    oracle and the Pallas kernel) enforce; ``ops`` dispatches to them
    unchecked.
    """
    caps = tuple(int(m) for m in max_idxs)
    if not caps:
        raise ValueError("max_idxs must not be empty")
    if any(m < 0 for m in caps):
        raise ValueError(f"max_idxs must be >= 0, got {caps}")
    if any(b < a for a, b in zip(caps, caps[1:])):
        raise ValueError(f"max_idxs must be ascending, got {caps}")
    return caps


@functools.partial(jax.jit, static_argnames=("k", "max_idxs", "exclude_self"))
def topk_select_sizes(
    D: jax.Array,
    *,
    k: int,
    max_idxs: tuple[int, ...],
    exclude_self: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """k smallest per row under EVERY prefix cap in one pass → (S, Lp, k).

    The multi-cap primitive behind CCM convergence sweeps: ``max_idxs``
    is an ascending tuple of inclusive column caps (one per library
    size), and level s of the output equals ``topk_select(D, k=k,
    max_idx=max_idxs[s])`` — same Euclidean distances, same
    ``lax.top_k`` (value, index) tie order for every valid slot. Slots
    with no valid candidate under a cap are dist=inf / idx=``PAD_IDX``
    (the per-cap calls emit arbitrary masked-column indices there;
    both carry zero simplex weight, so downstream ρ is bit-identical).

    One pass instead of S: columns are consumed in ascending segments
    between consecutive caps, each segment's k-best merged into a
    running table. The merge concatenates the running k-best (all
    indices below the segment) before the segment's candidates, so
    ``lax.top_k``'s positional tie-breaking remains global
    (value, index) order — the invariant that makes the running table
    reusable across caps.
    """
    Lp = D.shape[0]
    caps = check_sizes_caps(max_idxs)
    neg = -D.astype(jnp.float32)
    rows = jnp.arange(Lp, dtype=jnp.int32)[:, None]
    run_nd = jnp.full((Lp, k), -_INF, jnp.float32)
    run_i = jnp.full((Lp, k), PAD_IDX, jnp.int32)
    outs_d, outs_i, prev = [], [], 0
    for m in caps:
        hi = min(m + 1, Lp)
        if hi > prev:
            w = hi - prev
            seg = jax.lax.slice_in_dim(neg, prev, hi, axis=1)
            seg_cols = prev + jnp.arange(w, dtype=jnp.int32)[None, :]
            if exclude_self:
                seg = jnp.where(seg_cols == rows, -_INF, seg)
            if w > k:
                snd, pos = _chunked_topk(seg, k)
                si = pos + prev
            else:
                snd, si = seg, jnp.broadcast_to(seg_cols, (Lp, w))
            cand_nd = jnp.concatenate([run_nd, snd], axis=1)
            cand_i = jnp.concatenate([run_i, si], axis=1)
            run_nd, pos = jax.lax.top_k(cand_nd, k)
            run_i = jnp.take_along_axis(cand_i, pos, axis=1)
            prev = hi
        ok = run_nd > -_INF
        outs_d.append(jnp.where(ok, jnp.sqrt(jnp.maximum(-run_nd, 0.0)),
                                _INF))
        outs_i.append(jnp.where(ok, run_i, jnp.int32(PAD_IDX)))
    return jnp.stack(outs_d), jnp.stack(outs_i)


def make_weights(dists: jax.Array, eps: float = 1e-30) -> jax.Array:
    """Simplex weights from sorted neighbor distances, paper step (3).

    w_i = exp(-d_i / d_min) normalized to sum 1; d_min is the nearest
    distance, guarded so exact-duplicate neighbors dominate (cppEDM
    semantics).

    Rows with *no* valid neighbor (all-inf distances, e.g. from an
    aggressive ``max_idx`` cap) get all-zero weights instead of NaN:
    inf/inf ratios are forced to inf (→ zero weight) and the normalizer
    is clamped away from zero.
    """
    d_min = jnp.maximum(dists[..., :1], eps)
    ratio = jnp.where(jnp.isfinite(d_min), dists / d_min, jnp.inf)
    w = jnp.exp(-ratio)
    s = jnp.sum(w, axis=-1, keepdims=True)
    return jnp.where(s > 0, w / jnp.maximum(s, eps), 0.0)


@functools.partial(jax.jit, static_argnames=("offset",))
def lookup(
    Y: jax.Array, idx: jax.Array, w: jax.Array, *, offset: int = 0
) -> jax.Array:
    """Batched simplex lookup, paper Algorithm 3.

    Y:   (N, L) target series sharing the library's neighbor tables.
    idx: (Lp, k) int32 embedded neighbor indices.
    w:   (Lp, k) normalized weights.
    Returns (N, Lp): Yhat[n, j] = sum_k w[j, k] * Y[n, idx[j, k] + offset].
    """
    g = jnp.take(Y, idx + offset, axis=-1)  # (N, Lp, k)
    return jnp.einsum("njk,jk->nj", g, w.astype(Y.dtype))


@functools.partial(jax.jit, static_argnames=("offset",))
def lookup_rho(
    Y: jax.Array, idx: jax.Array, w: jax.Array, *, offset: int = 0
) -> jax.Array:
    """Fused lookup + Pearson ρ (paper §3.4 "on-the-fly" path).

    Compares Yhat[n, j] against the aligned truth Y[n, j + offset] and
    returns ρ per target, shape (N,). Never materializes Yhat in HBM on
    the kernel path; this oracle just composes the two refs.
    """
    yhat = lookup(Y, idx, w, offset=offset)
    Lp = idx.shape[0]
    yt = jax.lax.dynamic_slice_in_dim(Y, offset, Lp, axis=-1)
    return pearson_rows(yhat, yt)


# --------------------------------------------------------------------------
# Library-batched all-kNN (the CCM matrix engine primitive).
#
# One launch computes the neighbor tables of B library series at one E —
# the batch axis is embarrassingly independent, so this is a *layout*
# contract, not a numerics change: the result is bit-invariant in B (any
# batch decomposition of this program gives identical tables — the
# contract journaled resume and OOM backoff re-tiling rely on). What is
# NOT contracted is bit-equality against *other programs* computing the
# same tables: XLA CPU contracts the distance accumulation differently
# at some shapes (~1 ULP) both inside ``lax.map`` bodies (e.g. Lp = 94,
# the legacy ``core.ccm.ccm_group`` route) and in the standalone 2-D
# per-series pipeline (e.g. L = 150, E = 4) — selection indices still
# agree (ties at 1 ULP don't arise in practice), distances wobble in
# the last bit. One more entry in the XLA-CPU contraction pathology
# file alongside the TopK slowdown in ROADMAP.
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("E", "tau", "k", "exclude_self",
                                             "max_idx"))
def _all_knn_batch(X, *, E, tau, k, exclude_self, max_idx):
    B, L = X.shape
    Lp = num_embedded(L, E, tau)
    Xf = X.astype(jnp.float32)
    acc = jnp.zeros((B, Lp, Lp), jnp.float32)
    for lag in range(E):  # same accumulation order as pairwise_distances
        xk = jax.lax.dynamic_slice_in_dim(Xf, lag * tau, Lp, axis=-1)
        d = xk[:, :, None] - xk[:, None, :]
        acc = acc + strict_sq(d)
    cols = jnp.arange(Lp, dtype=jnp.int32)
    mask = jnp.zeros((Lp, Lp), bool)
    if exclude_self:
        mask = mask | jnp.eye(Lp, dtype=bool)
    if max_idx is not None:
        mask = mask | (cols[None, :] > max_idx)
    # One batched top-k over the whole (B, Lp, Lp) stack: selection is
    # row-independent and rounding-free, so batching it is exact — and it
    # hoists the TopK out of any lax.map body (where XLA CPU degenerates).
    # No (B·Lp, Lp) reshape: it would cut the mask/negate fusion into the
    # chunk-max prefilter and re-materialize the stack (2× at Lp=4094).
    neg_d, idx = _chunked_topk(-jnp.where(mask[None], _INF, acc), k)
    return (jnp.sqrt(jnp.maximum(-neg_d, 0.0)),
            idx.astype(jnp.int32))


def all_knn_batch(
    X: jax.Array,
    *,
    E: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
) -> tuple[jax.Array, jax.Array]:
    """All-kNN tables for B library series in ONE launch → (B, Lp, k).

    ``X`` is a (B, L) stack of series; slice b of the output matches the
    fused per-series pipeline (``pairwise_distances`` + ``topk_select``)
    on ``X[b]`` — indices exactly, with ``lax.top_k``'s (value, index)
    tie order; distances to ~1 ULP (the per-series pipeline is a
    different XLA program, see the section comment). Results are
    **bit-invariant in B**: any batch decomposition of this program
    yields identical tables — that is the resume/backoff contract.
    """
    X = jnp.asarray(X)
    if X.ndim != 2:
        raise ValueError(f"X must be (B, L), got shape {X.shape}")
    num_embedded(X.shape[-1], E, tau)  # raises on too-short series
    k = E + 1 if k is None else int(k)
    max_idx = None if max_idx is None else int(max_idx)
    return _all_knn_batch(X, E=E, tau=tau, k=k, exclude_self=exclude_self,
                          max_idx=max_idx)


# --------------------------------------------------------------------------
# Incremental multi-E all-kNN (the one-pass optimal-E sweep engine).
#
# D_E = D_{E-1} + the rank-1 lag term (x[i+(E-1)τ] − x[j+(E-1)τ])², so the
# full stack of per-E neighbor tables costs one O(E_max·Lp²) accumulation
# instead of the O(ΣE·Lp²) of re-running the pairwise kernel per E.
# Outputs are padded to the E=1 shape: (E_max, Lp_1, k_max) with Lp_1 = L,
# k_max = max-per-E k; padding is dist=inf / idx=PAD_IDX.
# --------------------------------------------------------------------------

PAD_IDX = -1  # idx padding outside the valid (Lp_E, k_E) block per level

_CHUNK_W = 32  # column-chunk width of the two-stage top-k; power of two


def _chunked_topk(neg: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-k (largest) per row via a chunk-max prefilter.

    Two-stage selection: (1) reduce each row to per-chunk maxima and pick
    the k best chunks, (2) run the real top_k over only those chunks'
    k·W candidates — ~W/k× fewer elements through the (single-threaded,
    ~2ns/elem) XLA-CPU TopK scan. The chunk maxima are computed with a
    pairwise elementwise max tree, NOT ``jnp.max(axis=-1)``: the XLA CPU
    reduce emitter goes scalar on this shape when its input is an
    in-graph accumulator (~15× slower than the tree; measured).

    EXACT, ties included: if a chunk holding a true top-k element v were
    not selected, each of the k selected chunks contributes a maximum
    outranking v (greater value, or equal value in an earlier chunk —
    stage-1 top_k is stable), giving v ≥ k predecessors — contradiction.
    Sorting the selected chunk ids keeps candidates in global column
    order, so stage-2 tie-breaking equals full-row stability; the ragged
    last chunk's out-of-range candidate slots are masked to -inf (same
    semantics as padding the row, without the full-matrix pad copy that
    used to dominate the cost on materialized inputs — ~70ms of the
    ~200ms total at Lp=4096).

    ``neg`` may carry leading batch dims ((…, Lc)); every stage is
    row-independent, so the result per row is identical to the 2-D call
    — the batched kNN engine passes its (B, Lp, Lp) stack directly
    instead of reshaping to (B·Lp, Lp), which would cut the fusion of
    the mask/negate producers into stage 1 and re-materialize the whole
    stack (measured 2× end-to-end at Lp=4094).
    """
    Lc = neg.shape[-1]
    lead = neg.shape[:-1]
    C = -(-Lc // _CHUNK_W)
    if k >= C or Lc <= 4 * _CHUNK_W:  # prefilter can't shrink the scan
        nd, ik = jax.lax.top_k(neg, k)
        return nd, ik.astype(jnp.int32)
    C0 = Lc // _CHUNK_W
    body = neg[..., :C0 * _CHUNK_W].reshape(*lead, C0, _CHUNK_W)
    m, w = body, _CHUNK_W
    while w > 1:  # vectorized pairwise max tree → (…, C0) chunk maxima
        m = jnp.maximum(m[..., :w // 2], m[..., w // 2:w])
        w //= 2
    m = m[..., 0]
    if C0 != C:  # ragged last chunk: tiny (…, Lc−C0·W) reduce
        m = jnp.concatenate(
            [m, jnp.max(neg[..., C0 * _CHUNK_W:], axis=-1, keepdims=True)],
            axis=-1)
    _, cid = jax.lax.top_k(m, k)
    cid = jnp.sort(cid, axis=-1)  # global column order → stable ties
    gidx = (cid[..., :, None] * _CHUNK_W
            + jnp.arange(_CHUNK_W, dtype=cid.dtype)
            ).reshape(*lead, k * _CHUNK_W)
    cand = jnp.take_along_axis(neg, jnp.minimum(gidx, Lc - 1), axis=-1)
    cand = jnp.where(gidx < Lc, cand, -_INF)
    nd, pos = jax.lax.top_k(cand, k)
    ik = jnp.take_along_axis(gidx, pos, axis=-1)
    return nd, ik.astype(jnp.int32)


def multi_e_ks(E_max: int, k: int | None) -> tuple[int, ...]:
    """Per-level neighbor counts: k_E = E+1 (simplex default) or uniform k."""
    if E_max < 1:
        raise ValueError(f"E_max must be >= 1, got {E_max}")
    if k is None:
        return tuple(e + 2 for e in range(E_max))  # E = e+1 → k = E+1
    return (int(k),) * E_max


def multi_e_max_idx(L: int, E_max: int, tau: int, max_idx) -> tuple[int, ...]:
    """Per-level candidate caps, clamped to the level's last valid index.

    ``max_idx`` may be None (no user cap), a python int, or a static
    (E_max,) sequence of ints (e.g. ``Lp_E − 1 − Tp`` for optimal-E's
    horizon-validity constraint). Static on purpose: the caps bake into
    the accumulation stream as constants (see ``_all_knn_multi_e``), and
    every caller derives them from already-static (L, E_max, tau, Tp).
    """
    base = [L - e * tau - 1 for e in range(E_max)]
    if max_idx is None:
        return tuple(base)
    mx = np.broadcast_to(np.asarray(max_idx, np.int64), (E_max,))
    return tuple(int(min(m, b)) for m, b in zip(mx, base))


def pad_multi_e_tables(
    dists: jax.Array, idx: jax.Array, *, E_max: int, tau: int,
    ks: tuple[int, ...],
) -> tuple[jax.Array, jax.Array]:
    """Force dist=inf / idx=PAD_IDX outside each level's (Lp_E, k_E) block."""
    L = dists.shape[1]
    lev = jnp.arange(E_max, dtype=jnp.int32)[:, None, None]
    rows = jnp.arange(L, dtype=jnp.int32)[None, :, None]
    kcol = jnp.arange(dists.shape[2], dtype=jnp.int32)[None, None, :]
    ks_a = jnp.asarray(ks, jnp.int32)[:, None, None]
    valid = (rows < L - lev * tau) & (kcol < ks_a)
    return (jnp.where(valid, dists, _INF),
            jnp.where(valid, idx, jnp.int32(PAD_IDX)))


@functools.partial(jax.jit, static_argnames=("E_max", "tau", "ks", "mxs",
                                             "exclude_self"))
def _all_knn_multi_e(x, *, E_max, tau, ks, mxs, exclude_self):
    # Invalidity is monotone when the caps are non-increasing (always true
    # for the defaults and for optimal-E's Lp_E−1−Tp caps): a column masked
    # at level e stays masked at every later level. Then masking FUSES into
    # the accumulation stream — the accumulator holds *negated* distances
    # with invalid entries stuck at −inf (−inf − d² = −inf), and the level
    # extraction runs directly on it: one read-modify-write of the matrix
    # per level, no separate masked copy. (Negating the accumulator
    # instead of the top_k input is bit-exact: f32 rounding commutes with
    # negation.)
    L = x.shape[-1]
    k_max = max(ks)
    xpad = jnp.pad(x.astype(jnp.float32), (0, (E_max - 1) * tau))
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    sticky = all(b <= a for a, b in zip(mxs, mxs[1:]))
    acc = jnp.zeros((L, L), jnp.float32)
    outs_d, outs_i = [], []
    for e in range(E_max):  # level e ↔ embedding dim E = e+1
        xk = jax.lax.dynamic_slice_in_dim(xpad, e * tau, L, axis=-1)
        d = xk[:, None] - xk[None, :]
        d2 = strict_sq(d)  # shape-independent bits — the append contract
        invalid = cols > mxs[e]
        if exclude_self and (e == 0 or not sticky):
            invalid = invalid | (cols == rows)
        if sticky:
            acc = jnp.where(invalid, -_INF, acc - d2)
            neg = acc
        else:  # non-monotone caps: mask a per-level copy instead
            acc = acc - d2
            neg = jnp.where(invalid, -_INF, acc)
        # Rows ≥ Lp_E are garbage (x-padding) but cheap — the extraction
        # scans them and the final pad mask discards them; this avoids a
        # strided slice copy per level.
        nd, ik = _chunked_topk(neg, ks[e])
        pad = k_max - ks[e]
        outs_d.append(jnp.pad(jnp.sqrt(jnp.maximum(-nd, 0.0)),
                              ((0, 0), (0, pad)), constant_values=jnp.inf))
        outs_i.append(jnp.pad(ik, ((0, 0), (0, pad)),
                              constant_values=PAD_IDX))
    return jnp.stack(outs_d), jnp.stack(outs_i)


def all_knn_multi_e(
    x: jax.Array,
    *,
    E_max: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
) -> tuple[jax.Array, jax.Array]:
    """Neighbor tables for *every* E in 1..E_max in one incremental pass.

    Returns (dists, idx), both (E_max, L, k_max): slice ``[E-1, :Lp_E, :k_E]``
    for the table at dimension E — identical to running ``pairwise_distances``
    + ``topk_select`` at that E. Padding is dist=inf / idx=PAD_IDX.
    """
    L = x.shape[-1]
    num_embedded(L, E_max, tau)  # raises on too-short series
    ks = multi_e_ks(E_max, k)
    mxs = multi_e_max_idx(L, E_max, tau, max_idx)
    d, i = _all_knn_multi_e(x, E_max=E_max, tau=tau, ks=ks, mxs=mxs,
                            exclude_self=exclude_self)
    return pad_multi_e_tables(d, i, E_max=E_max, tau=tau, ks=ks)


# --------------------------------------------------------------------------
# S-Map weighted normal equations (the batched S-Map engine substrate).
#
# For query row j and locality θ, S-Map fits ŷ = [1, z_j]·b with
# b = argmin Σ_i w_i (y_i − [1, z_i]·b)²,  w_i = exp(−θ d_ij / d̄_j).
# Instead of one lstsq per (j, θ) on √w-scaled copies of the design matrix
# (the seed path), the engine accumulates the (E+1, E+1) weighted Gram
# matrix G = AᵀWA and moment vector m = AᵀWy for EVERY (j, θ, target) at
# once and batch-solves the ridge-regularized normal equations downstream
# (core/smap_engine.py has the conditioning discussion).
# --------------------------------------------------------------------------

_DBAR_TINY = 1e-30  # d̄ below this ⇒ degenerate (constant) row: use ratio 0


def smap_ratio(x: jax.Array, *, E: int, tau: int, rows: int) -> jax.Array:
    """(rows, rows) S-Map distance ratios d_ij / d̄_j over the library.

    d̄_j is the mean Euclidean distance from query j to ALL library points
    (self included — its zero distance is part of the mean, matching
    cppEDM). Degenerate rows (d̄ ≈ 0, e.g. a constant series) would make
    the exp(−θ·d/d̄) weights NaN/inf; they get ratio 0 (⇒ weight 1), the
    only consistent limit since d̄ = 0 forces every d_ij = 0 too.
    """
    d = jnp.sqrt(jnp.maximum(
        pairwise_distances(x, E=E, tau=tau)[:rows, :rows], 0.0))
    dbar = jnp.mean(d, axis=1, keepdims=True)
    return d / jnp.where(dbar > _DBAR_TINY, dbar, 1.0)


@functools.partial(
    jax.jit, static_argnames=("E", "tau", "Tp", "thetas", "exclude_self"))
def smap_gram(
    x: jax.Array,
    Y: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 1,
    thetas: tuple[float, ...],
    exclude_self: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Weighted Gram/moment accumulation for every (query row, θ, target).

    x: (L,) library series; Y: (N, L) target panel (self-prediction is
    Y = x[None]). With rows = Lp − max(Tp, 0) library points (those whose
    Tp-ahead truth exists) and A = [1 | delay_embed(x)[:rows]] of shape
    (rows, E+1):

      G[j, t]    = Aᵀ W_{j,θ_t} A            (rows, T, E+1, E+1)
      M[j, t, n] = Aᵀ W_{j,θ_t} y_n          (rows, T, N,   E+1)

    where W_{j,θ} = diag(exp(−θ d_ij / d̄_j)) with the self weight zeroed
    when ``exclude_self`` (leave-one-out) and y_n[i] = Y[n, i + off],
    off = (E−1)τ + Tp. Each θ is one (rows, rows) @ (rows, (E+1)²) matmul
    — no per-query solve loop, no (T, rows, rows) weight tensor. Tp ≥ 0.
    """
    x = x.astype(jnp.float32)
    Y = Y.astype(jnp.float32)
    L = x.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = Lp - max(Tp, 0)
    off = (E - 1) * tau + Tp
    E1 = E + 1
    A = jnp.concatenate(
        [jnp.ones((rows, 1), jnp.float32), delay_embed(x, E, tau)[:rows]],
        axis=1)
    ratio = smap_ratio(x, E=E, tau=tau, rows=rows)
    yv = jax.lax.dynamic_slice_in_dim(Y, off, rows, axis=-1)  # (N, rows)
    N = yv.shape[0]
    AA = (A[:, :, None] * A[:, None, :]).reshape(rows, E1 * E1)
    yA = (yv.T[:, :, None] * A[:, None, :]).reshape(rows, N * E1)
    self_mask = jnp.eye(rows, dtype=bool)
    Gs, Ms = [], []
    for t in thetas:  # |θ| ≤ ~16: unrolled, two matmuls per θ
        W = jnp.exp(jnp.float32(-t) * ratio)
        if exclude_self:
            W = jnp.where(self_mask, 0.0, W)
        Gs.append((W @ AA).reshape(rows, E1, E1))
        Ms.append((W @ yA).reshape(rows, N, E1))
    return jnp.stack(Gs, axis=1), jnp.stack(Ms, axis=1)


@jax.jit
def pearson_rows(a: jax.Array, b: jax.Array) -> jax.Array:
    """Row-wise Pearson correlation, two-pass (numerically stable).

    Jitted so that a standalone call (``core.simplex_skill``) compiles
    the same fused reductions as a call inlined into a larger program
    (``lookup_rho``): dispatched op by op, XLA's CPU reductions round
    the sums differently, ≈1 ULP of ρ.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    am = a - jnp.mean(a, axis=-1, keepdims=True)
    bm = b - jnp.mean(b, axis=-1, keepdims=True)
    cov = jnp.sum(am * bm, axis=-1)
    va = jnp.sum(am * am, axis=-1)
    vb = jnp.sum(bm * bm, axis=-1)
    denom = jnp.sqrt(va * vb)
    return jnp.where(denom > 0, cov / jnp.maximum(denom, 1e-30), 0.0)

# --------------------------------------------------------------------------
# Incremental master append (the serving-path stream-in/merge primitive).
#
# A session's multi-E master is the top-k_m table of ``all_knn_multi_e``
# over the library axis. When the monitored series grows by dt points the
# level-e library grows by exactly dt columns (Lp_e = L − e·τ), and the
# table can be updated without the O(Lp²) rebuild:
#
#   - OLD rows (i < Lp_old_e): their coordinates are unchanged, so any
#     old column surviving into the new top-k_m must already sit in the
#     stored top-k_m. Merge the stored k_m candidates against only the
#     dt new columns — O(Lp·(k_m+dt)) per level.
#   - NEW rows (Lp_old_e ≤ i < Lp_new_e): no stored state; one full
#     (dt, L_new) scan per level.
#
# Bit-parity with a cold rebuild is the contract (tests/test_master_
# append.py property-tests it over Δt/E/τ grids, ties included). Three
# rules make it hold:
#
#   1. Every distance chain is STRICT two-rounding IEEE — ``strict_sq``
#      in ``_all_knn_multi_e`` and in the recompute chains below. Strict
#      per-element chains are deterministic regardless of buffer shape
#      or vectorization, so the (Lp, k) gathered recompute of a stored
#      candidate, the (dt, L) slab, and the cold (L, L) accumulator all
#      produce the same bits. (Left bare, XLA CPU FMA-contracts
#      acc − d·d at some shapes and the three programs disagree by
#      1 ULP — measured; see ``strict_sq``.)
#   2. The merge orders candidates in the *pre-sqrt* negated-squared
#      domain (sqrt is many-to-one after f32 rounding — merging on sqrt
#      values can invert 1-ULP ties), with candidates laid out
#      [stored slots ascending, new columns ascending]: stored indices
#      are < Lp_old_e ≤ new indices and ``lax.top_k`` is positionally
#      stable, so equal-value ties resolve in global column order —
#      exactly the cold extraction's tie rule.
#   3. Stored garbage slots (dist=inf from k_m > Lp_old_e − 1) carry the
#      OLD deterministic pattern [i, Lp_old_e, …]; those indices collide
#      with now-valid columns. They enter the merge as −inf candidates
#      and every surviving garbage slot is re-normalized afterwards to
#      the cold pattern [i, Lp_new_e, …] — which, because garbage
#      survives only when the finite count f equals Lp_new_e − 1, is
#      exactly ``idx = i`` at slot f and ``idx = slot`` beyond it.
#
# The merge itself is then pure selection over carried bits, so the
# Pallas variant (kernels/knn_append.py) shares these guarantees.
# --------------------------------------------------------------------------


def append_new_row_slab(x, *, dt, E_max, tau):
    """Negated-squared distances of the dt newest rows vs all columns.

    Returns (E_max, dt, L_new) UNMASKED accumulator levels: entry
    [e, r, j] equals the cold accumulator value at
    (row Lp_old_e + r, col j) wherever the cold entry is valid (strict
    chains are shape-independent). Row r of level e also supplies the
    dt new COLUMNS of every old row by symmetry: negation and squaring
    are exact and the per-lag chain order is identical, so
    acc(i, j) == acc(j, i) bitwise. Shared by the ref and Pallas paths.
    """
    L_new = x.shape[-1]
    xpad = jnp.pad(x.astype(jnp.float32), (0, (E_max - 1) * tau))
    xls = [jax.lax.dynamic_slice_in_dim(xpad, l * tau, L_new, axis=-1)
           for l in range(E_max)]
    outs = []
    for e in range(E_max):
        Lp_old = L_new - dt - e * tau
        Lp_new = L_new - e * tau
        acc = jnp.zeros((dt, L_new), jnp.float32)
        for l in range(e + 1):
            xl = xls[l]
            df = xl[Lp_old:Lp_new, None] - xl[None, :]
            acc = acc - strict_sq(df)
        outs.append(acc)
    return jnp.stack(outs)


def normalize_garbage(nd, ik, rows):
    """Rewrite non-finite slots to the cold build's garbage pattern.

    ``nd`` (rows, k) negated-squared merge output, ``ik`` its indices,
    ``rows`` (rows,) the row ids. Garbage survives the merge only when
    the finite count equals the row's full valid-neighbor count, so the
    cold pattern is self at the first garbage slot, then the slot id.
    """
    finite = nd > -_INF
    nfin = jnp.sum(finite.astype(jnp.int32), axis=1)[:, None]
    slot = jnp.arange(nd.shape[1], dtype=jnp.int32)[None, :]
    garb = jnp.where(slot == nfin, rows[:, None], slot)
    return jnp.where(finite, ik, garb)


@functools.partial(jax.jit, static_argnames=("dt", "E_max", "tau"))
def _master_append(x, dM, iM, *, dt, E_max, tau):
    L_new = x.shape[-1]
    L_old = L_new - dt
    k_m = dM.shape[-1]
    xpad = jnp.pad(x.astype(jnp.float32), (0, (E_max - 1) * tau))
    xls = [jax.lax.dynamic_slice_in_dim(xpad, l * tau, L_new, axis=-1)
           for l in range(E_max)]
    slab = append_new_row_slab(x, dt=dt, E_max=E_max, tau=tau)
    outs_d, outs_i = [], []
    for e in range(E_max):  # level e ↔ embedding dim E = e+1
        Lp_old = L_old - e * tau
        Lp_new = L_new - e * tau
        rows_o = jnp.arange(Lp_old, dtype=jnp.int32)
        new_cols = Lp_old + jnp.arange(dt, dtype=jnp.int32)
        # -- old rows: recompute stored candidates (strict chain) --------
        i_o = iM[e, :Lp_old]
        ok = jnp.isfinite(dM[e, :Lp_old])
        jj = jnp.maximum(i_o, 0)  # clamp garbage/PAD for a safe gather
        acc_s = jnp.zeros((Lp_old, k_m), jnp.float32)
        for l in range(e + 1):
            xl = xls[l]
            ds = xl[:Lp_old, None] - xl[jj]
            acc_s = acc_s - strict_sq(ds)
        # dt new columns of every old row — slab transpose, by symmetry
        nd_new = slab[e, :, :Lp_old].T
        cand_nd = jnp.concatenate([jnp.where(ok, acc_s, -_INF), nd_new],
                                  axis=1)
        cand_i = jnp.concatenate(
            [i_o, jnp.broadcast_to(new_cols, (Lp_old, dt))], axis=1)
        nd_o, pos = jax.lax.top_k(cand_nd, k_m)
        ik_o = normalize_garbage(
            nd_o, jnp.take_along_axis(cand_i, pos, axis=1), rows_o)
        # -- new rows: full slab rows, masked like the cold accumulator --
        rows_n = Lp_old + jnp.arange(dt, dtype=jnp.int32)
        colsL = jnp.arange(L_new, dtype=jnp.int32)[None, :]
        inval = (colsL > Lp_new - 1) | (colsL == rows_n[:, None])
        nd_n, ik_n = _chunked_topk(jnp.where(inval, -_INF, slab[e]), k_m)
        # -- assemble the level ------------------------------------------
        nd = jnp.concatenate([nd_o, nd_n], axis=0)
        ik = jnp.concatenate([ik_o, ik_n], axis=0)
        d_lvl = jnp.sqrt(jnp.maximum(-nd, 0.0))
        outs_d.append(jnp.pad(d_lvl, ((0, L_new - Lp_new), (0, 0)),
                              constant_values=jnp.inf))
        outs_i.append(jnp.pad(ik, ((0, L_new - Lp_new), (0, 0)),
                              constant_values=PAD_IDX))
    return jnp.stack(outs_d), jnp.stack(outs_i)


def check_append_args(x, dists, idx, tau: int) -> int:
    """Validate master_append inputs; returns dt (the appended width)."""
    E_max, L_old, _ = dists.shape
    L_new = int(x.shape[-1])
    dt = L_new - L_old
    if dt < 1:
        raise ValueError(f"append needs at least one new point, got dt={dt}")
    if idx.shape != dists.shape:
        raise ValueError(
            f"dists/idx shape mismatch: {dists.shape} vs {idx.shape}")
    num_embedded(L_old, E_max, tau)  # stored master must already be valid
    return dt


def master_append(
    x: jax.Array,
    dists: jax.Array,
    idx: jax.Array,
    *,
    tau: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """Grow a multi-E master table to cover ``dt`` appended points.

    ``x`` is the FULL appended series (length L_new); ``dists``/``idx``
    are the stored ``all_knn_multi_e`` tables of its length-L_old
    prefix, both (E_max, L_old, k_m) with uniform k (``panel_master``
    masters). Returns the (E_max, L_new, k_m) tables, bit-identical to
    ``all_knn_multi_e(x, E_max=E_max, tau=tau, k=k_m)`` at
    O(Lp·(k_m+dt)) per level instead of O(Lp²).
    """
    dt = check_append_args(x, dists, idx, tau)
    E_max = dists.shape[0]
    return _master_append(x, dists, idx, dt=dt, E_max=E_max, tau=tau)
