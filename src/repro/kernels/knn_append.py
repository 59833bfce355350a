"""Pallas kernels of the incremental master append.

The serving-path companion to ``ref.master_append_sq``: the same
O(Lp·(k+dt)) per-level stream-in/merge (see the append section of
kernels/ref.py for the contract and the strict-chain/tie-order/garbage
rules) on the same state — (E_max, k, N, C): the k slots lead, the
rows run along the vector lanes — and every series and level of a
panel in one launch of each kernel per tick.

The split of labor is deliberate: candidate *values* are the carried
squared distances of the stored candidates and the strict-``jnp`` slab
of the new rows (``ref.strict_sq`` keeps them bit-identical to the cold
build at any shape), and both kernels are PURE SELECTION — no float
arithmetic, only compares and selects — so the kernel path inherits the
reference's bit-parity guarantee:

* new rows (``knn_append``): ``knn_batch.py``'s retire-by-index
  min-merge over every column ((value asc, index asc), distinct fills
  for rows with fewer than k candidates), which equals ``lax.top_k``
  over the row;
* old rows (``knn_append_fold``): the stored k-best is already sorted
  by (value, position), so the dt new columns are folded in by rank:
  each new candidate's place in the union is counted, and each output
  slot takes the new candidate ranked there or the stored entry moved
  down by the new ones ranked above it — equal to ``lax.top_k`` over
  stored ∪ new, ties included. The same kernel writes the new rows'
  k-best at their columns and fills the columns past the level's end,
  so each level is assembled in one pass over the state.

Garbage slots (dist=inf from k_m exceeding a level's candidate count)
sort after every finite candidate in the fold and are re-normalized to
the cold build's pattern afterwards (``ref.normalize_garbage``, here
with the slots leading).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.ref import PAD_IDX
from repro.kernels.topk import _BIG_I, merge_kbest


def _select_kernel(cd_ref, cc_ref, dk_ref, ik_ref, *, k):
    """Per-row k smallest (value asc, index asc) of a candidate block.

    Inputs are positive squared distances (inf = masked) and the (1, w)
    candidate indices every row shares (the columns). Pure selection —
    the output value bits are copies of input bits.
    """
    cd = cd_ref[...]
    ci = jnp.broadcast_to(cc_ref[...], cd.shape)
    dk_ref[...], ik_ref[...] = merge_kbest(cd, ci, k, big=_BIG_I + 2**20)


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def knn_append(cand_d, cand_cols, *, k, block, interpret):
    """k smallest (value, index) of each row of (R, w) candidates.

    ``cand_cols`` (w,) are the candidates' indices, shared by every row.
    Named for the kernel: the HLO instruction of the ``pallas_call``
    takes this function's name, so a device trace keys it
    ``kernel:knn_append``.
    """
    R, w = cand_d.shape
    # Row blocks of at most ``block`` rows, fewer on long rows: the merge
    # holds ~12 bytes a candidate in scoped VMEM (16 MiB on a v5e). A
    # block that divides R needs no padding copy.
    br = max(8, min(block, R, (1 << 20) // w) // 8 * 8)
    br = next((b for b in range(br, 7, -8) if R % b == 0), br)
    g = pl.cdiv(R, br)
    # Whole row blocks; the padding rows are selected, then discarded.
    cand_d = jnp.pad(cand_d, ((0, g * br - R), (0, 0)),
                     constant_values=jnp.inf)
    dk, ik = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(g,),
        in_specs=[pl.BlockSpec((br, w), lambda i: (i, 0)),
                  pl.BlockSpec((1, w), lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g * br, k), jnp.float32),
            jax.ShapeDtypeStruct((g * br, k), jnp.int32),
        ],
        interpret=interpret,
    )(cand_d, cand_cols.reshape(1, w))
    return dk[:R], ik[:R]


def _fold_kernel(len_ref, s_ref, i_ref, v_ref, dn_ref, in_ref, so_ref,
                 io_ref, *, k, dt, tau, bc):
    """One level's block of stored lists, grown by an append.

    Blocks are (1, k, bn, bc): the k slots lead, so every slot is a
    dense (bn series, bc rows) tile and moving an entry between slots is
    picking another tile. Folds the dt new columns in by rank (module
    docstring), re-normalizes garbage slots, writes the new rows' k-best
    (``dn``/``in`` blocks (1, k, bn, dt)) at columns Lp_old … Lp_old +
    dt − 1 and fills the columns past Lp_new. Pure selection: every
    value is a copy.
    """
    Lp_old = len_ref[0] - pl.program_id(0) * tau
    bn = s_ref.shape[2]
    col = pl.program_id(2) * bc + jax.lax.broadcasted_iota(
        jnp.int32, (bn, bc), 1)
    s = [s_ref[0, q] for q in range(k)]
    i = [i_ref[0, q] for q in range(k)]
    v = [v_ref[0, j] for j in range(dt)]
    # A new candidate ranks after the stored entries ≤ it and after the
    # new ones below it, or equal and earlier.
    rank = []
    for j in range(dt):
        r = sum((s[q] <= v[j]).astype(jnp.int32) for q in range(k))
        for o in range(dt):
            if o != j:
                r = r + (v[o] <= v[j] if o < j else v[o] < v[j]).astype(
                    jnp.int32)
        rank.append(r)
    # Slot q: the new candidate ranked q, else stored entry q − t, t the
    # new ones ranked above q.
    out_s, out_i = [], []
    for q in range(k):
        above = sum((r < q).astype(jnp.int32) for r in rank)
        os_, oi = s[q], i[q]
        for t in range(1, min(dt, q) + 1):
            os_ = jnp.where(above == t, s[q - t], os_)
            oi = jnp.where(above == t, i[q - t], oi)
        for j in range(dt):
            os_ = jnp.where(rank[j] == q, v[j], os_)
            oi = jnp.where(rank[j] == q, Lp_old + j, oi)
        out_s.append(os_)
        out_i.append(oi)
    # Garbage slots: self at the first, then the slot id (cold pattern).
    nfin = sum((o < jnp.inf).astype(jnp.int32) for o in out_s)
    live = col < Lp_old + dt
    lane = jax.lax.broadcasted_iota(jnp.int32, (bn, dt), 1)
    for q in range(k):
        os_ = out_s[q]
        oi = jnp.where(os_ < jnp.inf, out_i[q],
                       jnp.where(nfin == q, col, q))
        # The new rows, column j of the (bn, dt) tile, at their columns.
        dq, iq = dn_ref[0, q], in_ref[0, q]
        for j in range(dt):
            at = col == Lp_old + j
            os_ = jnp.where(at, jnp.max(jnp.where(lane == j, dq, -jnp.inf),
                                        axis=1, keepdims=True), os_)
            oi = jnp.where(at, jnp.max(jnp.where(lane == j, iq, -_BIG_I),
                                       axis=1, keepdims=True), oi)
        so_ref[0, q] = jnp.where(live, os_, jnp.inf)
        io_ref[0, q] = jnp.where(live, oi, PAD_IDX)


@functools.partial(jax.jit, static_argnames=("tau", "interpret"))
def knn_append_fold(length, sq, idx, new_d, new_dk, new_ik, *, tau,
                    interpret):
    """The append's old-row fold and level assembly: ``sq``/``idx``
    (E_max, k, N, C) stored lists; ``new_d`` (E_max, dt, N, C) the new
    columns' squared distances to every row; ``new_dk``/``new_ik``
    (E_max, k, N, dt) the new rows' k-best → the grown (E_max, k, N, C)
    lists. One block per (level, 8 series, column block).
    """
    E_max, k, N, C = sq.shape
    dt = new_d.shape[1]
    bn, bc = min(8, N), min(512, C)  # ragged edge blocks are masked
    tile = lambda w, c: pl.BlockSpec((1, w, bn, c),
                                     lambda e, n, j: (e, 0, n, j))
    new = pl.BlockSpec((1, k, bn, dt), lambda e, n, j: (e, 0, n, 0))
    return pl.pallas_call(
        functools.partial(_fold_kernel, k=k, dt=dt, tau=tau, bc=bc),
        grid=(E_max, pl.cdiv(N, bn), pl.cdiv(C, bc)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile(k, bc),
                  tile(k, bc), tile(dt, bc), new, new],
        out_specs=[tile(k, bc), tile(k, bc)],
        out_shape=[jax.ShapeDtypeStruct(sq.shape, jnp.float32),
                   jax.ShapeDtypeStruct(idx.shape, jnp.int32)],
        interpret=interpret,
    )(jnp.reshape(length, (1,)).astype(jnp.int32), sq, idx, new_d, new_dk,
      new_ik)


@functools.partial(jax.jit, static_argnames=("dt", "tau", "block",
                                             "interpret"))
def master_append_sq(X, sq, idx, *, length, dt, tau, block=128,
                     interpret=False):
    """Kernel-path ``ref.master_append_sq`` — bit-identical, same contract.

    ``sq``/``idx`` (E_max, k, B, C) are the append states. All levels at
    once: the new rows' selection (E_max·dt·B rows) is one
    ``knn_append`` launch, and the old rows' fold with the assembly of
    every level is one ``knn_append_fold`` launch.
    """
    E_max, k_m, B, C = sq.shape
    slab = jax.vmap(lambda x: _ref.append_new_row_slab(
        x, length, dt=dt, E_max=E_max, tau=tau), out_axes=2)(X)
    rows = jnp.arange(C, dtype=jnp.int32)  # slab: (E_max, dt, B, C)
    # level e ↔ embedding dim E = e+1: Lp_old_e = length − e·τ rows
    Lp_old = (length - tau * jnp.arange(E_max, dtype=jnp.int32))[:, None,
                                                                  None]
    new_cols = Lp_old + jnp.arange(dt, dtype=jnp.int32)[:, None]  # (E,dt,1)
    # -- new rows: full slab rows, masked like the cold accumulator ------
    inval = (rows > Lp_old + dt - 1) | (rows == new_cols)
    dk, ik = knn_append(
        jnp.where(inval[:, :, None], jnp.inf, -slab).reshape(-1, C), rows,
        k=k_m, block=block, interpret=interpret)  # rows in (E, dt, B) order
    ik = _ref.normalize_garbage(
        -dk, ik, jnp.broadcast_to(new_cols, (E_max, dt, B)).reshape(-1))
    dk, ik = (a.reshape(E_max, dt, B, k_m).transpose(0, 3, 2, 1)
              for a in (dk, ik))  # (E_max, k, B, dt)
    # -- old rows: the dt new columns folded in; the levels assembled ----
    return knn_append_fold(length, sq, idx, -slab, dk, ik, tau=tau,
                           interpret=interpret)
