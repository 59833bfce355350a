"""Pallas TPU kernel: library-batched all-kNN with streaming k-best merge.

The CCM matrix engine primitive (ISSUE 5). kEDM's all-pairs CCM drives
one all-kNN pass per library series, N times; this kernel adds a
*leading series-grid axis* to ``knn_multi_e.py``'s streaming k-best
tiling so ONE launch emits the neighbor tables of B library series at a
fixed E: the grid is (series, row blocks, column blocks) with the column
axis minor/sequential, each cell accumulates its series' (br, bc)
fused-embedding distance block in VMEM (E unrolled lag terms, the
(Lp, E) embedding never materialized) and merges it into the running
per-row k-best that lives in the revisited output block.

The batch axis is embarrassingly independent — series b's tiling,
accumulation order, and min-global-index tie-breaking are *identical*
for every B, so a B-series launch is bit-identical to B separate B = 1
launches (the layout contract the ref oracle also guarantees). Merge
semantics match ``knn_multi_e.py`` exactly (squared running bests,
retire-by-index so rows with < k valid candidates emit distinct fill
entries, sqrt once after the last column step); see its docstring for
the tie-order proof.

The merge is threshold-gated (``topk.merge_kbest_gated``). Column
block 0 extracts its own k best (k passes, no empty running list to
concatenate). A later block admits only candidates lexicographically
below the row's running slot k − 1, ``(d < d_k) | (d == d_k & i <
i_k)``, and runs P = min(k, most admitted in any row of the tile)
passes over the block alone, shift-inserting each extracted entry into
the sorted running list. A candidate not admitted cannot reach the
merged top k, and passes extract in ascending (distance, index) order,
so the tables are bit-identical to the ungated k-pass merge. Indices of
a later column block exceed every index already held, so an inf tie
with a held fill entry goes to the fill index, as before. The kernel
also writes the passes it ran per (series, row tile), which only the
diagnostic ``knn_batch_merge_share`` reads.

VMEM per cell is O(E·(br + bc) + br·bc + br·k): the row and column
tiles' lag-shifted slices of the one series being processed (the
tile-aligned ``ref.lag_rows`` layout, as in ``knn_multi_e.py``), the
distance block, and the running k-best — per-cell footprint grows with
neither L nor B, which is what lets B scale to the host-side memory
budget (``core.ccm.auto_batch_libs``) instead of a VMEM ceiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import telemetry
from repro.kernels.ref import lag_rows, num_embedded, strict_sq
from repro.kernels.topk import merge_kbest, merge_kbest_gated


def _kernel(xc_ref, xr_ref, dk_ref, ik_ref, np_ref, *, E, k, mx, Lp, br,
            bc, gj, exclude_self):
    i0 = pl.program_id(1) * br
    j = pl.program_id(2)
    j0 = j * bc

    rows = i0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 0)
    cols = j0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
    acc = jnp.zeros((br, bc), jnp.float32)
    for e in range(E):  # E ≤ ~20: unrolled, as in knn_multi_e.py
        xi = xc_ref[0, :, e:e + 1]  # (br, 1) sublanes: lag e of the row tile
        xj = xr_ref[0, e:e + 1, :]  # (1, bc) lanes: lag e of the column tile
        d = xi - xj
        acc = acc + strict_sq(d)
    invalid = cols > mx  # static cap, pre-clamped to Lp − 1
    if exclude_self:
        invalid = invalid | (cols == rows)
    cand_d = jnp.where(invalid, jnp.inf, acc)

    @pl.when(j == 0)
    def _first():  # nothing merged yet: the block's own k best
        dk_ref[0], ik_ref[0] = merge_kbest(cand_d, cols, k)
        np_ref[...] = jnp.full(np_ref.shape, k, jnp.int32)

    @pl.when(j > 0)
    def _gated():  # only the passes a candidate of this block can win
        dk_ref[0], ik_ref[0], passes = merge_kbest_gated(
            cand_d, cols, dk_ref[0], ik_ref[0], rows[:, :1] < Lp)
        np_ref[...] += passes

    @pl.when(j == gj - 1)
    def _finalize():  # squared → Euclidean, once all columns are merged
        dk_ref[...] = jnp.sqrt(jnp.maximum(dk_ref[...], 0.0))


def _tiles(Lp, block):
    """(br, bc, gi, gj): the tile shape clamped to Lp, and the grid."""
    br = max(8, min(block[0], Lp))
    bc = max(128, min(block[1], Lp))
    return br, bc, pl.cdiv(Lp, br), pl.cdiv(Lp, bc)


@functools.partial(
    jax.jit,
    static_argnames=("E", "tau", "k", "mx", "exclude_self", "block",
                     "interpret"))
def knn_batch(X, *, E, tau, k, mx, exclude_self, block, interpret):
    """→ (dists, idx) (B, Lp, k), and the merge passes run, (B, gi, 1, 128)
    int32: every lane of [b, i] holds the sum over row tile i's column
    blocks of series b."""
    B, L = X.shape
    Lp = num_embedded(L, E, tau)
    br, bc, gi, gj = _tiles(Lp, block)
    # Lag-shifted copies padded to whole tiles (row/col + lag reach).
    need = max(gi * br, gj * bc) + (E - 1) * tau
    Xp = jnp.pad(X.astype(jnp.float32), ((0, 0), (0, need - L)))
    xr = lag_rows(Xp, E=E, tau=tau, width=gj * bc)  # (B, E, cols)
    xc = jnp.swapaxes(lag_rows(Xp, E=E, tau=tau, width=gi * br), 1, 2)
    return pl.pallas_call(
        functools.partial(_kernel, E=E, k=k, mx=mx, Lp=Lp, br=br, bc=bc,
                          gj=gj, exclude_self=exclude_self),
        grid=(B, gi, gj),
        in_specs=[
            pl.BlockSpec((1, br, E), lambda b, i, j: (b, i, 0)),  # row tile
            pl.BlockSpec((1, E, bc), lambda b, i, j: (b, 0, j)),  # col tile
        ],
        out_specs=[
            pl.BlockSpec((1, br, k), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, br, k), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, 128), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Lp, k), jnp.float32),
            jax.ShapeDtypeStruct((B, Lp, k), jnp.int32),
            jax.ShapeDtypeStruct((B, gi, 1, 128), jnp.int32),
        ],
        interpret=interpret,
        name="knn_batch",
    )(xc, xr)


def _launch(X, *, E, tau, k, exclude_self, max_idx, block, interpret):
    """Validate, resolve k and the clamped cap, run ``knn_batch``."""
    X = jnp.asarray(X)
    if X.ndim != 2:
        raise ValueError(f"X must be (B, L), got shape {X.shape}")
    Lp = num_embedded(X.shape[-1], E, tau)  # raises on too-short series
    k = E + 1 if k is None else int(k)
    mx = Lp - 1 if max_idx is None else min(int(max_idx), Lp - 1)
    return knn_batch(X, E=E, tau=tau, k=k, mx=mx, exclude_self=exclude_self,
                     block=block, interpret=interpret)


def all_knn_batch(
    X: jax.Array,
    *,
    E: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
    block: tuple[int, int] = (128, 1024),
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Neighbor tables for B series in one launch → (dists, idx), (B, Lp, k).

    Slice b equals the per-series two-kernel pipeline on ``X[b]`` (same
    ``lax.top_k`` tie order), for any B and any (br, bc) tiling.
    """
    d, i, _ = _launch(X, E=E, tau=tau, k=k, exclude_self=exclude_self,
                      max_idx=max_idx, block=block, interpret=interpret)
    return d, i


def knn_batch_merge_share(
    X: jax.Array,
    *,
    E: int,
    tau: int = 1,
    k: int | None = None,
    max_idx=None,
    block: tuple[int, int] = (128, 1024),
    interpret: bool = False,
) -> float:
    """Run ``knn_batch`` on (B, L) ``X`` and read back its merge passes.

    Adds the passes run to the counter ``knn_merge_passes`` and the
    ungated ceiling, k per grid cell, to ``knn_merge_slots``; returns
    their ratio (1.0 when every column block fits in one). A diagnostic:
    the hot path never reads the pass output.
    """
    d, _, passes = _launch(X, E=E, tau=tau, k=k, exclude_self=True,
                           max_idx=max_idx, block=block, interpret=interpret)
    B, Lp, k = d.shape
    _, _, gi, gj = _tiles(Lp, block)
    ran = int(passes[:, :, 0, 0].sum())
    slots = k * B * gi * gj
    telemetry.counter("knn_merge_passes").inc(ran)
    telemetry.counter("knn_merge_slots").inc(slots)
    return ran / slots
