"""Pallas TPU kernels: small-k partial sort of a distance matrix.

The paper's Algorithm 2 (kEDM §3.3.2) uses per-thread priority queues in
GPU shared memory, merged by a team leader — and reports the queues' scratch
footprint degrading occupancy as E (hence k = E+1) grows.

Priority queues are branch-hostile on the TPU VPU, so the TPU-idiomatic
equivalent (DESIGN.md §2) is **k-pass vectorized extraction**: each grid
cell holds a (br, Lp) row block in VMEM and performs k passes of
(min, first-argmin, mask) — every pass is a full-width lane reduction, no
data-dependent control flow. k ≤ 32 in EDM (k = E+1, E ≤ 20), so the
k·Lp read traffic stays within a small constant of the queue approach
while vectorizing perfectly.

Emits Euclidean distances (sqrt — the "normalize D_k" step of Alg. 2) and
int32 indices, both sorted ascending. Self-exclusion (leave-one-out) and a
dynamic ``max_idx`` candidate cap (library-size sweeps, Tp validity) are
fused into the masking pass.

``topk_select_sizes`` is the multi-cap variant behind CCM convergence
sweeps: ONE column-tiled pass over the distance matrix emits the k-best
table under every prefix library cap, instead of S full-matrix re-scans.

The column-tiled kernels fold each block into a running k-best with
``merge_kbest`` (k passes over the block concatenated with the list).
``merge_kbest_gated`` is its threshold-gated form, used by
``knn_batch`` once a row's list is full: it admits only candidates
lexicographically below the list's slot k − 1, runs only as many
passes as the most-admitting row needs, and shift-inserts each
extracted entry. Its result is bit-identical to ``merge_kbest`` over
the concatenation. In a later column block every candidate index
exceeds every index in the list, so an inf-distance tie goes to the
held fill index, as in the ungated merge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import PAD_IDX, check_sizes_caps

_BIG_I = 2**30  # python int: jnp constants must not be captured by kernels


def _kernel(mx_ref, d_ref, dk_ref, ik_ref, *, k: int, br: int, Lp: int,
            exclude_self: bool):
    i0 = pl.program_id(0) * br
    d = d_ref[...]  # (br, Lcols)
    cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    max_idx = mx_ref[0, 0]
    invalid = (cols >= Lp) | (cols > max_idx)
    if exclude_self:
        rows = i0 + jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
        invalid = invalid | (cols == rows)
    d = jnp.where(invalid, jnp.inf, d)
    dists, idxs = [], []
    for _ in range(k):
        m = jnp.min(d, axis=1, keepdims=True)  # (br, 1)
        cand = jnp.where(d == m, cols, _BIG_I)
        idx = jnp.min(cand, axis=1, keepdims=True)  # first argmin: stable ties
        dists.append(m)
        idxs.append(idx)
        d = jnp.where(cols == idx, jnp.inf, d)
    dk_ref[...] = jnp.sqrt(jnp.maximum(jnp.concatenate(dists, axis=1), 0.0))
    ik_ref[...] = jnp.concatenate(idxs, axis=1)


@functools.partial(
    jax.jit, static_argnames=("k", "exclude_self", "block_rows", "interpret")
)
def topk_select(
    D: jax.Array,
    *,
    k: int,
    exclude_self: bool = True,
    max_idx: jax.Array | int | None = None,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """k smallest per row of a squared-distance matrix → (dists, idx).

    dists: (Lp, k) f32 Euclidean, ascending. idx: (Lp, k) int32.
    ``max_idx`` is dynamic (no re-lowering across library-size sweeps).
    """
    Lp = D.shape[0]
    br = max(1, min(block_rows, Lp))
    mx = jnp.full((1, 1), Lp - 1 if max_idx is None else max_idx, jnp.int32)
    dk, ik = pl.pallas_call(
        functools.partial(_kernel, k=k, br=br, Lp=Lp, exclude_self=exclude_self),
        grid=(pl.cdiv(Lp, br),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # dynamic candidate cap
            pl.BlockSpec((br, Lp), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Lp, k), jnp.float32),
            jax.ShapeDtypeStruct((Lp, k), jnp.int32),
        ],
        interpret=interpret,
    )(mx, D)
    return dk, ik


def merge_kbest(cand_d, cand_i, k, *, width=None, big=_BIG_I):
    """k passes of (min, min-global-index-on-ties, retire-by-index).

    The streaming k-best merge every column-tiled kernel shares
    (``knn_batch``, ``knn_multi_e``, ``knn_append``, the multi-cap
    sweep below). Selecting the minimum *global index* among distance
    ties makes the streamed result bit-identical to a stable full-row
    partial sort for any column tiling. The winner is retired by index,
    clearing BOTH arrays: inf-distance entries can't be retired via
    distance alone (they're already inf), and an un-cleared index would
    win every later inf-tie — re-emitting the same index on rows with
    < k valid candidates. Indices are unique per row across the
    candidates (``big`` only fills interchangeable padding).

    Returns (br, width) tables (width defaults to k) whose slots ≥ k are
    inf / ``big``. The passes run as a ``fori_loop``, not unrolled: the
    body is compiled once, which keeps Mosaic's compile time and VMEM
    stack flat in k (unrolled at k = 22 across 20 multi-E levels it
    took minutes and overflowed the scoped VMEM limit).
    """
    width = k if width is None else width
    br = cand_d.shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (br, width), 1)

    def one_pass(p, carry):
        cand_d, cand_i, best_d, best_i = carry
        m = jnp.min(cand_d, axis=1, keepdims=True)
        sel = jnp.where(cand_d == m, cand_i, big)
        bi = jnp.min(sel, axis=1, keepdims=True)
        removed = cand_i == bi
        return (jnp.where(removed, jnp.inf, cand_d),
                jnp.where(removed, big, cand_i),
                jnp.where(slot == p, m, best_d),
                jnp.where(slot == p, bi, best_i))

    init = (cand_d, cand_i, jnp.full((br, width), jnp.inf, jnp.float32),
            jnp.full((br, width), big, jnp.int32))
    _, _, best_d, best_i = jax.lax.fori_loop(0, k, one_pass, init)
    return best_d, best_i


def _shift_right(x, fill):
    """x[:, s − 1] in slot s, ``fill`` in slot 0."""
    return jnp.concatenate([jnp.full_like(x[:, :1], fill), x[:, :-1]],
                           axis=1)


def merge_kbest_gated(cand_d, cand_i, best_d, best_i, live):
    """Fold a candidate block into a full sorted k-best, passes on demand.

    ``best_d``/``best_i`` (br, k) hold each row's running k best sorted
    by (distance, index); ``cand_*`` (br, bc) is the next column block.
    The gate: only a candidate lexicographically below the row's slot
    k − 1, ``(d < d_k) | (d == d_k & i < i_k)``, can reach the merged
    top k; every other one becomes (inf, ``_BIG_I``). The block then runs
    P = min(k, most qualifying candidates in any ``live`` row) passes of
    ``merge_kbest``'s (min, min-index-on-ties, retire-by-index), each
    over the block alone, and shift-inserts the extracted (m, bi) at its
    lexicographic rank in the running list, so slot k − 1 drops off.
    Passes extract in ascending (d, index) order, so after P of them
    the list is the k smallest of (running ∪ block): bit-identical to
    ``merge_kbest`` over their concatenation. A row with fewer
    qualifying candidates than P extracts (inf, ``_BIG_I``), which
    precedes no slot and inserts nothing. Rows where the (br, 1) mask
    ``live`` is false (tile padding) still merge but do not raise P.

    Returns (best_d, best_i, P), P an int32 scalar.
    """
    br, k = best_d.shape
    d_k, i_k = best_d[:, k - 1:k], best_i[:, k - 1:k]
    q = (cand_d < d_k) | ((cand_d == d_k) & (cand_i < i_k))
    count = jnp.sum(q.astype(jnp.int32), axis=1, keepdims=True)
    count = jnp.where(live, count, 0)
    passes = jnp.minimum(jnp.max(count), k)
    slot = jax.lax.broadcasted_iota(jnp.int32, (br, k), 1)

    def one_pass(_, carry):
        cand_d, cand_i, best_d, best_i = carry
        m = jnp.min(cand_d, axis=1, keepdims=True)
        sel = jnp.where(cand_d == m, cand_i, _BIG_I)
        bi = jnp.min(sel, axis=1, keepdims=True)
        removed = cand_i == bi
        # Slots the new entry precedes: a suffix, since the list is sorted.
        after = (m < best_d) | ((m == best_d) & (bi < best_i))
        pos = k - jnp.sum(after.astype(jnp.int32), axis=1, keepdims=True)
        return (jnp.where(removed, jnp.inf, cand_d),
                jnp.where(removed, _BIG_I, cand_i),
                jnp.where(slot < pos, best_d,
                          jnp.where(slot == pos, m,
                                    _shift_right(best_d, jnp.inf))),
                jnp.where(slot < pos, best_i,
                          jnp.where(slot == pos, bi,
                                    _shift_right(best_i, _BIG_I))))

    init = (jnp.where(q, cand_d, jnp.inf), jnp.where(q, cand_i, _BIG_I),
            best_d, best_i)
    _, _, best_d, best_i = jax.lax.fori_loop(0, passes, one_pass, init)
    return best_d, best_i, passes


def _sizes_kernel(d_ref, dk_ref, ik_ref, run_d, run_i, *, k, caps, br, bc,
                  Lp, exclude_self):
    i0 = pl.program_id(0) * br
    j = pl.program_id(1)
    j0 = j * bc

    @pl.when(j == 0)
    def _init():  # running k-best scratch + snapshot outputs
        run_d[...] = jnp.full((br, k), jnp.inf, jnp.float32)
        run_i[...] = jnp.full((br, k), _BIG_I, jnp.int32)
        dk_ref[...] = jnp.full((len(caps), br, k), jnp.inf, jnp.float32)
        ik_ref[...] = jnp.full((len(caps), br, k), _BIG_I, jnp.int32)

    d = d_ref[...]  # (br, bc)
    rows = i0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 0)
    cols = j0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
    invalid = cols >= Lp
    if exclude_self:
        invalid = invalid | (cols == rows)
    # Snapshots BEFORE the main merge: level s's table is the running
    # k-best over columns [0, caps[s]], so it merges the pre-block state
    # with only this block's columns ≤ caps[s]. Caps are static — each
    # level's snapshot column block is known at trace time, so each cap
    # costs one extra merge at exactly one column step.
    for s, m in enumerate(caps):
        sb = min(m, Lp - 1) // bc  # the column block holding cap s

        @pl.when(j == sb)
        def _snapshot(s=s, m=m):
            snap = jnp.where(invalid | (cols > m), jnp.inf, d)
            cand_d = jnp.concatenate([snap, run_d[...]], axis=1)
            cand_i = jnp.concatenate([cols, run_i[...]], axis=1)
            bd, bi = merge_kbest(cand_d, cand_i, k)
            dk_ref[s] = jnp.sqrt(jnp.maximum(bd, 0.0))
            ik_ref[s] = bi
    # Main stream: fold the full block (masked to the global cap) into
    # the running k-best reused by every later snapshot.
    cand_d = jnp.concatenate(
        [jnp.where(invalid | (cols > caps[-1]), jnp.inf, d), run_d[...]],
        axis=1)
    cand_i = jnp.concatenate([cols, run_i[...]], axis=1)
    run_d[...], run_i[...] = merge_kbest(cand_d, cand_i, k)


@functools.partial(
    jax.jit,
    static_argnames=("k", "max_idxs", "exclude_self", "block", "interpret"))
def topk_select_sizes(
    D: jax.Array,
    *,
    k: int,
    max_idxs: tuple[int, ...],
    exclude_self: bool = True,
    block: tuple[int, int] = (8, 512),
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """k smallest per row under every prefix cap in one pass → (S, Lp, k).

    Column-tiled streaming variant of ``ref.topk_select_sizes`` (same
    semantics: ascending inclusive caps, dist=inf / idx=PAD_IDX in slots
    with no valid candidate). The grid is (row blocks, column blocks)
    with the column axis minor (sequential on TPU); the running k-best
    lives in VMEM scratch and is reused incrementally across caps — one
    merge per column block plus one snapshot merge per cap, never a
    re-scan of earlier columns. Columns past the largest cap are not
    even loaded (the column grid stops at it).
    """
    Lp = D.shape[0]
    caps = check_sizes_caps(max_idxs)
    S = len(caps)
    br = max(1, min(block[0], Lp))
    bc = max(k, min(block[1], Lp))
    gi = pl.cdiv(Lp, br)
    gj = pl.cdiv(min(Lp, caps[-1] + 1), bc)
    dk, ik = pl.pallas_call(
        functools.partial(_sizes_kernel, k=k, caps=caps, br=br, bc=bc,
                          Lp=Lp, exclude_self=exclude_self),
        grid=(gi, gj),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((S, br, k), lambda i, j: (0, i, 0)),
            pl.BlockSpec((S, br, k), lambda i, j: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, Lp, k), jnp.float32),
            jax.ShapeDtypeStruct((S, Lp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, k), jnp.float32),
            pltpu.VMEM((br, k), jnp.int32),
        ],
        interpret=interpret,
    )(D)
    ok = jnp.isfinite(dk)
    return (jnp.where(ok, dk, jnp.inf),
            jnp.where(ok, ik, jnp.int32(PAD_IDX)))
