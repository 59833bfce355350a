"""Pallas TPU kernel: batched simplex lookup (+ fused Pearson ρ).

The paper's Algorithm 3 (kEDM §3.4): predictions for N target series that
share one library's neighbor tables,

    yhat[n, j] = sum_k W[j, k] * Y[n, I[j, k] + offset].

Kokkos caches the target series in team scratch and unrolls the k-loop;
the TPU adaptation (DESIGN.md §2) puts **targets on the 128-lane axis**:
the target block is held in VMEM transposed, (L, bn), so each neighbor
gather ``Y_T[I[j,k]+offset, :]`` is a single sublane dynamic-slice of a
(1, bn) vector — the lane-major analog of kEDM's coalesced reads. The
k-loop (k ≤ 32) is unrolled; the j-loop is a fori with direct stores.

``lookup_rho`` is the paper's "on-the-fly correlation" path: predicted
values never reach HBM; per-target covariance statistics are accumulated
across j-tiles in a revisited output block using the numerically stable
pairwise-merge scheme of Schubert & Gertz (the paper's ref. [15]).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_rows(yT_ref, i_ref, w_ref, o_ref, *, k, bj):
    """Store one (bj, bn) tile of predictions into ``o_ref``, row by row.

    ``i_ref``/``w_ref`` are the tile's flattened (bj·kp,) SMEM index and
    weight blocks (row stride kp = k rounded up to 8, see ``_tiles``):
    indices already carry the lookup offset and are clamped into the
    series, so every scalar read drives one dynamic sublane load of a
    (1, bn) target row.
    """
    bn = o_ref.shape[1]
    kp = _row_stride(k)

    def body(j, carry):
        row = jnp.zeros((1, bn), jnp.float32)
        for kk in range(k):  # unrolled: k is small and static
            idx = i_ref[j * kp + kk]
            row = row + w_ref[j * kp + kk] * yT_ref[pl.ds(idx, 1), :]
        o_ref[pl.ds(j, 1), :] = row
        return carry

    jax.lax.fori_loop(0, bj, body, 0)


def _row_stride(k):
    """SMEM row stride of the tables: 1-D SMEM blocks must be a multiple
    of 1024 words, so k is padded to a multiple of 8 (bj = 128 rows)."""
    return -(-k // 8) * 8


def _tiles(Y, idx, w, *, offset, block):
    """Shared wrapper layout of both kernels.

    Returns the tile sizes, the grid, the (L, N) transposed targets, and
    the (gj·bj·kp,) flattened SMEM tables: the index table clamped into
    the series (padded or masked slots may hold any index) and shifted
    by ``offset``, both zero-padded to whole row tiles and to the row
    stride kp (a padded slot gathers row 0 with weight 0).
    """
    N, L = Y.shape
    Lp, k = idx.shape
    bj = max(8, min(block[0], Lp))
    bn = N if N <= block[1] else block[1]
    gj, gn = pl.cdiv(Lp, bj), pl.cdiv(N, bn)
    pad = ((0, gj * bj - Lp), (0, _row_stride(k) - k))
    it = jnp.pad(jnp.clip(idx.astype(jnp.int32), 0, max(L - 1 - offset, 0))
                 + offset, pad)
    wt = jnp.pad(w.astype(jnp.float32), pad)
    yT = Y.astype(jnp.float32).T
    return bj, bn, gj, gn, yT, it.reshape(-1), wt.reshape(-1)


def _target_specs(L, bn, bj, k):
    """BlockSpecs of (yT, idx, w): the whole target history of one lane
    block stays resident across the j sweep (single-buffered: its block
    index changes only with n), the tables stream per row tile in SMEM."""
    return [
        pl.BlockSpec((L, bn), lambda n, j: (0, n),
                     pipeline_mode=pl.Buffered(1)),
        pl.BlockSpec((bj * _row_stride(k),), lambda n, j: (j,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((bj * _row_stride(k),), lambda n, j: (j,),
                     memory_space=pltpu.SMEM),
    ]


def _vmem_params(L, bn, bj):
    """Scoped-VMEM limit that fits the resident (L, bn) target block.

    At kEDM's longest published series (L = 29484) that block alone is
    ≈15 MB, at the default scoped limit; the limit is raised to the
    block plus room for the streamed tiles instead of capping L.
    """
    lanes = -(-bn // 128) * 128
    need = 4 * lanes * (L + 8 * bj) + (4 << 20)
    return pltpu.CompilerParams(vmem_limit_bytes=max(need, 32 << 20))


@functools.partial(
    jax.jit, static_argnames=("offset", "block", "interpret")
)
def lookup(
    Y: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    *,
    offset: int = 0,
    block: tuple[int, int] = (128, 128),
    interpret: bool = False,
) -> jax.Array:
    """Batched lookup via Pallas. Returns (N, Lp) float32."""
    N, L = Y.shape
    Lp, k = idx.shape
    bj, bn, gj, gn, yT, it, wt = _tiles(Y, idx, w, offset=offset,
                                        block=block)
    out = pl.pallas_call(
        functools.partial(_gather_rows, k=k, bj=bj),
        grid=(gn, gj),
        in_specs=_target_specs(L, bn, bj, k),
        out_specs=pl.BlockSpec((bj, bn), lambda n, j: (j, n)),
        out_shape=jax.ShapeDtypeStruct((gj * bj, N), jnp.float32),
        compiler_params=_vmem_params(L, bn, bj),
        interpret=interpret,
    )(yT, it, wt)
    return out[:Lp].T


# ---------------------------------------------------------------- fused rho


def _kernel_rho(yT_ref, i_ref, w_ref, yt_ref, s_ref, yh_ref, *, k, bj, Lp):
    j = pl.program_id(1)
    j0 = j * bj

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    _gather_rows(yT_ref, i_ref, w_ref, yh_ref, k=k, bj=bj)
    yhat = yh_ref[...]
    ytrue = yt_ref[...]  # the tile's truth rows, pre-shifted by offset
    # Mask ragged-edge rows with selects, not multiplies: the interpreter
    # (and Mosaic) pad ragged input blocks with undefined values, which may
    # be NaN — and NaN * 0 == NaN would poison the reduction.
    valid_b = j0 + jax.lax.broadcasted_iota(jnp.int32, (bj, 1), 0) < Lp
    valid = valid_b.astype(jnp.float32)
    yhat = jnp.where(valid_b, yhat, 0.0)
    ytrue = jnp.where(valid_b, ytrue, 0.0)

    # Tile-local two-pass stats (masked), then Schubert–Gertz pairwise merge
    # with the running stats held in the revisited output block.
    nt = jnp.sum(valid)  # scalar
    nt_safe = jnp.maximum(nt, 1.0)
    ma_t = jnp.sum(yhat, axis=0, keepdims=True) / nt_safe  # (1, bn)
    mb_t = jnp.sum(ytrue, axis=0, keepdims=True) / nt_safe
    da = (yhat - ma_t) * valid
    db = (ytrue - mb_t) * valid
    M2a_t = jnp.sum(da * da, axis=0, keepdims=True)
    M2b_t = jnp.sum(db * db, axis=0, keepdims=True)
    C_t = jnp.sum(da * db, axis=0, keepdims=True)

    n0 = s_ref[0:1, :]
    ma0, mb0 = s_ref[1:2, :], s_ref[2:3, :]
    M2a0, M2b0, C0 = s_ref[3:4, :], s_ref[4:5, :], s_ref[5:6, :]
    n1 = n0 + nt
    n1_safe = jnp.maximum(n1, 1.0)
    dA = ma_t - ma0
    dB = mb_t - mb0
    f = n0 * nt / n1_safe
    s_ref[0:1, :] = n1
    s_ref[1:2, :] = ma0 + dA * nt / n1_safe
    s_ref[2:3, :] = mb0 + dB * nt / n1_safe
    s_ref[3:4, :] = M2a0 + M2a_t + dA * dA * f
    s_ref[4:5, :] = M2b0 + M2b_t + dB * dB * f
    s_ref[5:6, :] = C0 + C_t + dA * dB * f


@functools.partial(
    jax.jit, static_argnames=("offset", "block", "interpret")
)
def lookup_rho(
    Y: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    *,
    offset: int = 0,
    block: tuple[int, int] = (128, 128),
    interpret: bool = False,
) -> jax.Array:
    """Fused lookup + Pearson ρ per target. Returns (N,) float32.

    The (N, Lp) prediction matrix never leaves VMEM (paper §3.4).
    """
    N, L = Y.shape
    Lp, k = idx.shape
    bj, bn, gj, gn, yT, it, wt = _tiles(Y, idx, w, offset=offset,
                                        block=block)
    # Aligned truth rows: row j of the tile is Y[:, j0 + j + offset].
    ytrue = jnp.pad(jax.lax.slice_in_dim(yT, offset, offset + Lp, axis=0),
                    ((0, gj * bj - Lp), (0, 0)))
    stats = pl.pallas_call(
        functools.partial(_kernel_rho, k=k, bj=bj, Lp=Lp),
        grid=(gn, gj),  # j innermost: stats block revisited across j
        in_specs=_target_specs(L, bn, bj, k) + [
            pl.BlockSpec((bj, bn), lambda n, j: (j, n)),
        ],
        out_specs=pl.BlockSpec((8, bn), lambda n, j: (0, n)),
        out_shape=jax.ShapeDtypeStruct((8, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bj, bn), jnp.float32)],
        compiler_params=_vmem_params(L, bn, bj),
        interpret=interpret,
    )(yT, it, wt, ytrue)
    M2a, M2b, C = stats[3], stats[4], stats[5]
    denom = jnp.sqrt(M2a * M2b)
    return jnp.where(denom > 0, C / jnp.maximum(denom, 1e-30), 0.0)
