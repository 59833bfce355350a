"""Pallas TPU kernel: incremental multi-E all-kNN with streaming k-best merge.

Beyond-paper optimization. kEDM's ``edim`` (optimal embedding dimension,
§3.4) re-runs the full pairwise+top-k pipeline once per E, paying
O(ΣE·Lp²) = O(E_max²·Lp²/2) FLOPs and E_max round trips of the distance
matrix through global memory. But the squared delay-embedding distance
obeys a first-order recurrence in E:

    D_E[i, j] = D_{E-1}[i, j] + (x[i+(E-1)τ] − x[j+(E-1)τ])²,

so one accumulation sweep of the E_max lag terms visits every D_E on the
way to D_{E_max}. This kernel exploits that: each grid cell holds a
(br, bc) block of the distance matrix in VMEM, adds the lag terms one E
at a time, and *at every level E* extracts that block's top-k before
adding the next term — emitting the complete stack of per-E neighbor
tables (E_max, Lp_1, k_max) in a single O(E_max·Lp²) pass with the
distance matrix never touching HBM.

Streaming k-best merge (the column-tiling that removes ``knn_fused.py``'s
one-VMEM-row-block ceiling on Lp): the grid is (row blocks, column
blocks) with the column axis minor, i.e. sequential on TPU. The output
block for a row block is revisited across all column steps and doubles as
the running k-best state: at level E the cell concatenates its masked
(br, bc) distance block (with global column indices) against the running
(br, k_max) best-so-far (with their indices) and runs k_E passes of
(min, first-argmin-by-*global*-index, mask) over the combined candidates.
Min-global-index tie-breaking makes the streaming result bit-identical to
a stable full-row partial sort (``jax.lax.top_k`` on the masked row), for
any column tiling. After the last column step the squared running bests
are rooted (sqrt) in place.

VMEM per cell is O(E_max·(br + bc) + br·bc + E_max·br·k_max),
independent of L: each cell receives only its row tile's and column
tile's lag-shifted slices of the series (``ref.lag_rows``, built in the
wrapper so every in-kernel slice is tile-aligned, as Mosaic requires),
the distance block is a fixed (br, bc) tile, and the quadratic (br, Lp)
row block of ``knn_fused.py`` is gone.

Per-level semantics match ``ref.all_knn_multi_e``: level e (E = e+1) has
Lp_E = L − e·τ valid rows/cols, k_E neighbors (E+1 by default), a static
per-level candidate cap ``mxs[e]`` (pre-clamped to Lp_E − 1), and optional
self-exclusion. Output padding outside each level's (Lp_E, k_E) block is
dist=inf / idx=PAD_IDX, applied by the host-side wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import (
    lag_rows,
    multi_e_ks,
    multi_e_max_idx,
    num_embedded,
    pad_multi_e_tables,
    strict_sq,
)
from repro.kernels.topk import _BIG_I, merge_kbest


def _kernel(xc_ref, xr_ref, dk_ref, ik_ref, *, E_max, ks, mxs,
            br, bc, gj, exclude_self):
    i0 = pl.program_id(0) * br
    j = pl.program_id(1)
    j0 = j * bc
    k_max = max(ks)

    @pl.when(j == 0)
    def _init():  # running k-best state lives in the revisited out block
        dk_ref[...] = jnp.full((E_max, br, k_max), jnp.inf, jnp.float32)
        ik_ref[...] = jnp.full((E_max, br, k_max), _BIG_I, jnp.int32)

    rows = i0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 0)
    cols = j0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
    acc = jnp.zeros((br, bc), jnp.float32)
    for e in range(E_max):  # E_max ≤ ~20: unrolled, as in pairwise_dist.py
        xi = xc_ref[:, e:e + 1]  # (br, 1) sublanes: lag e of the row tile
        xj = xr_ref[e:e + 1, :]  # (1, bc) lanes: lag e of the column tile
        d = xi - xj
        acc = acc + strict_sq(d)
        # ---- level-E extraction: merge this block into the running k-best
        invalid = cols > mxs[e]  # static cap, pre-clamped to Lp_E − 1
        if exclude_self:
            invalid = invalid | (cols == rows)
        cand_d = jnp.concatenate(
            [jnp.where(invalid, jnp.inf, acc), dk_ref[e]], axis=1)
        cand_i = jnp.concatenate([cols, ik_ref[e]], axis=1)
        dk_ref[e], ik_ref[e] = merge_kbest(cand_d, cand_i, ks[e],
                                           width=k_max)

    @pl.when(j == gj - 1)
    def _finalize():  # squared → Euclidean, once all columns are merged
        dk_ref[...] = jnp.sqrt(jnp.maximum(dk_ref[...], 0.0))


@functools.partial(
    jax.jit,
    static_argnames=("E_max", "tau", "ks", "mxs", "exclude_self", "block",
                     "interpret"))
def knn_multi_e(x, *, E_max, tau, ks, mxs, exclude_self, block, interpret):
    L = x.shape[-1]
    k_max = max(ks)
    br = max(8, min(block[0], L))
    bc = max(128, min(block[1], L))
    gi = pl.cdiv(L, br)
    gj = pl.cdiv(L, bc)
    # Lag-shifted copies padded to whole tiles (row/col + lag reach).
    need = max(gi * br, gj * bc) + (E_max - 1) * tau
    xpad = jnp.pad(x.astype(jnp.float32), (0, need - L))
    xr = lag_rows(xpad, E=E_max, tau=tau, width=gj * bc)  # (E_max, cols)
    xc = lag_rows(xpad, E=E_max, tau=tau, width=gi * br).T  # (rows, E_max)
    dk, ik = pl.pallas_call(
        functools.partial(_kernel, E_max=E_max, ks=ks, mxs=mxs,
                          br=br, bc=bc, gj=gj, exclude_self=exclude_self),
        grid=(gi, gj),
        in_specs=[
            pl.BlockSpec((br, E_max), lambda i, j: (i, 0)),  # row-tile lags
            pl.BlockSpec((E_max, bc), lambda i, j: (0, j)),  # col-tile lags
        ],
        out_specs=[
            pl.BlockSpec((E_max, br, k_max), lambda i, j: (0, i, 0)),
            pl.BlockSpec((E_max, br, k_max), lambda i, j: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E_max, L, k_max), jnp.float32),
            jax.ShapeDtypeStruct((E_max, L, k_max), jnp.int32),
        ],
        interpret=interpret,
        name="knn_multi_e",
    )(xc, xr)
    return pad_multi_e_tables(dk, ik, E_max=E_max, tau=tau, ks=ks)


def all_knn_multi_e(
    x: jax.Array,
    *,
    E_max: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
    block: tuple[int, int] = (128, 1024),
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One-pass neighbor tables for every E in 1..E_max → (dists, idx).

    Both outputs are (E_max, Lp_1, k_max); ``[E-1, :Lp_E, :k_E]`` is the
    table at dimension E, identical to the per-E two-kernel pipeline.
    """
    L = x.shape[-1]
    num_embedded(L, E_max, tau)  # raises on too-short series
    ks = multi_e_ks(E_max, k)
    mxs = multi_e_max_idx(L, E_max, tau, max_idx)
    return knn_multi_e(x, E_max=E_max, tau=tau, ks=ks, mxs=mxs,
                       exclude_self=exclude_self, block=block,
                       interpret=interpret)
