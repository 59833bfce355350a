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

from repro.kernels.ref import lag_rows, num_embedded, strict_sq
from repro.kernels.topk import _BIG_I, merge_kbest


def _kernel(xc_ref, xr_ref, dk_ref, ik_ref, *, E, k, mx, br, bc, gj,
            exclude_self):
    i0 = pl.program_id(1) * br
    j = pl.program_id(2)
    j0 = j * bc

    @pl.when(j == 0)
    def _init():  # running k-best state lives in the revisited out block
        dk_ref[...] = jnp.full((1, br, k), jnp.inf, jnp.float32)
        ik_ref[...] = jnp.full((1, br, k), _BIG_I, jnp.int32)

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
    cand_d = jnp.concatenate(
        [jnp.where(invalid, jnp.inf, acc), dk_ref[0]], axis=1)
    cand_i = jnp.concatenate([cols, ik_ref[0]], axis=1)
    dk_ref[0], ik_ref[0] = merge_kbest(cand_d, cand_i, k)

    @pl.when(j == gj - 1)
    def _finalize():  # squared → Euclidean, once all columns are merged
        dk_ref[...] = jnp.sqrt(jnp.maximum(dk_ref[...], 0.0))


@functools.partial(
    jax.jit,
    static_argnames=("E", "tau", "k", "mx", "exclude_self", "block",
                     "interpret"))
def _call(X, *, E, tau, k, mx, exclude_self, block, interpret):
    B, L = X.shape
    Lp = num_embedded(L, E, tau)
    br = max(8, min(block[0], Lp))
    bc = max(128, min(block[1], Lp))
    gi = pl.cdiv(Lp, br)
    gj = pl.cdiv(Lp, bc)
    # Lag-shifted copies padded to whole tiles (row/col + lag reach).
    need = max(gi * br, gj * bc) + (E - 1) * tau
    Xp = jnp.pad(X.astype(jnp.float32), ((0, 0), (0, need - L)))
    xr = lag_rows(Xp, E=E, tau=tau, width=gj * bc)  # (B, E, cols)
    xc = jnp.swapaxes(lag_rows(Xp, E=E, tau=tau, width=gi * br), 1, 2)
    return pl.pallas_call(
        functools.partial(_kernel, E=E, k=k, mx=mx, br=br, bc=bc,
                          gj=gj, exclude_self=exclude_self),
        grid=(B, gi, gj),
        in_specs=[
            pl.BlockSpec((1, br, E), lambda b, i, j: (b, i, 0)),  # row tile
            pl.BlockSpec((1, E, bc), lambda b, i, j: (b, 0, j)),  # col tile
        ],
        out_specs=[
            pl.BlockSpec((1, br, k), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, br, k), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Lp, k), jnp.float32),
            jax.ShapeDtypeStruct((B, Lp, k), jnp.int32),
        ],
        interpret=interpret,
    )(xc, xr)


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
    X = jnp.asarray(X)
    if X.ndim != 2:
        raise ValueError(f"X must be (B, L), got shape {X.shape}")
    L = X.shape[-1]
    Lp = num_embedded(L, E, tau)  # raises on too-short series
    k = E + 1 if k is None else int(k)
    mx = Lp - 1 if max_idx is None else min(int(max_idx), Lp - 1)
    return _call(X, E=E, tau=tau, k=k, mx=mx, exclude_self=exclude_self,
                 block=block, interpret=interpret)
