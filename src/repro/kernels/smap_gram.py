"""Pallas TPU kernel: column-tiled S-Map weighted-Gram accumulation.

S-Map (the paper's other core EDM method, validated against cppEDM) fits,
for every query row j and locality θ, a locally weighted linear model over
ALL library points — there is no k-nearest truncation to exploit, so the
seed paid one ``lstsq`` per (j, θ) over a materialized (Lp, Lp) distance
matrix. This kernel replaces that with the normal-equations accumulation

    G[j, θ]    = Aᵀ W_{j,θ} A    (E+1, E+1)
    M[j, θ, n] = Aᵀ W_{j,θ} y_n  (E+1,)

streamed over library (column) tiles in the same design language as
``knn_multi_e.py``: each cell reads its tiles' lag-shifted slices of the
series (``ref.lag_rows``; the distances are fused in-kernel, never
touching HBM), the grid is (row blocks, phase, column blocks) with the
column axis minor/sequential, and the output blocks double as running
accumulators revisited across all column steps. VMEM per cell is
O(E·(br + bc) + br·bc + T·(E+1)²·br + T·N·(E+1)·br), independent of L —
no (rows, rows) weight or distance matrix ever exists anywhere.

The S-Map weight w_ij = exp(−θ d_ij / d̄_j) needs the full-row mean d̄_j
*before* any weight can be formed, which a single streaming pass cannot
provide. The middle grid axis is a two-phase sweep over the same column
tiles: phase 0 recomputes each (br, bc) distance block and accumulates the
row sums (→ d̄, an output block revisited across tiles), phase 1 recomputes
the block again (O(E·br·bc) VPU work that overlaps the phase-1 MXU
matmuls, and cheaper than round-tripping it through HBM) and accumulates, per θ, the E+1 rank-(E+1) MXU matmuls (w ⊙ aᵖ) @ A_tile
into the Gram/moment outputs. Degenerate rows (d̄ ≈ 0, constant series)
take ratio 0 ⇒ weight 1 — see ``ref.smap_ratio``.

Per-level semantics match ``ref.smap_gram`` exactly (library = the first
``rows`` embedded points, self distance included in d̄, self weight zeroed
under leave-one-out); the two agree to f32 accumulation-order noise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import _DBAR_TINY, lag_rows, num_embedded


def _kernel(xc_ref, xr_ref, a_ref, y_ref, ds_ref, g_ref, m_ref, *, E,
            rows, thetas, br, bc, exclude_self):
    i0 = pl.program_id(0) * br
    p = pl.program_id(1)  # 0: accumulate row sums (d̄) · 1: accumulate G, M
    j = pl.program_id(2)
    j0 = j * bc
    E1 = E + 1
    N = y_ref.shape[0]

    T = len(thetas)

    @pl.when((p == 0) & (j == 0))
    def _init():  # running accumulators live in the revisited out blocks
        ds_ref[...] = jnp.zeros((br, 1), jnp.float32)
        g_ref[...] = jnp.zeros((T, E1, br, E1), jnp.float32)
        m_ref[...] = jnp.zeros((T, N, br, E1), jnp.float32)

    rows_i = i0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 0)
    cols_i = j0 + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
    valid = cols_i < rows  # library = embedded points with Tp-ahead truth

    def compute_d():  # fused-embedding distance block, O(E·br·bc) VPU work
        acc = jnp.zeros((br, bc), jnp.float32)
        for e in range(E):  # E ≤ ~20: unrolled, as in pairwise_dist.py
            xi = xc_ref[:, e:e + 1]  # (br, 1) sublanes: lag e, row tile
            xj = xr_ref[e:e + 1, :]  # (1, bc) lanes: lag e, column tile
            d = xi - xj
            acc = acc + d * d
        return jnp.sqrt(jnp.maximum(acc, 0.0))

    @pl.when(p == 0)
    def _rowsum():  # d̄ numerator; self's zero distance is included
        ds_ref[...] += jnp.sum(jnp.where(valid, compute_d(), 0.0), axis=1,
                               keepdims=True)

    @pl.when(p == 1)
    def _gram():
        d = compute_d()  # recomputed: cheaper than a round trip via HBM
        dbar = ds_ref[...] * (1.0 / rows)  # (br, 1)
        ratio = d / jnp.where(dbar > _DBAR_TINY, dbar, 1.0)
        invalid = ~valid
        if exclude_self:
            invalid = invalid | (cols_i == rows_i)  # leave-one-out
        # Design-matrix tile in both layouts, no in-kernel transposes:
        # A_j = [1, x_j, …, x_{j+(E−1)τ}] as (bc, E1) rows and E1 lane rows.
        at = a_ref[...]  # (bc, E1)
        arows = [jnp.ones((1, bc), jnp.float32)] + [
            xr_ref[e:e + 1, :] for e in range(E)]
        for t, theta in enumerate(thetas):  # |θ| ≤ ~16: unrolled
            w = jnp.where(invalid, 0.0,
                          jnp.exp(jnp.float32(-theta) * ratio))  # (br, bc)
            for q in range(E1):  # Gᵀ row q: ((w ⊙ aᵠ) @ A_tile) on the MXU
                g_ref[t, q] += jax.lax.dot_general(
                    w * arows[q], at, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            for n in range(N):
                yn = y_ref[n:n + 1, :]  # (1, bc): Tp-ahead truth, target n
                m_ref[t, n] += jax.lax.dot_general(
                    w * yn, at, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("E", "tau", "Tp", "thetas", "exclude_self", "block",
                     "interpret"))
def smap_gram(
    x: jax.Array,
    Y: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 1,
    thetas: tuple[float, ...],
    exclude_self: bool = True,
    block: tuple[int, int] = (128, 1024),
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Streaming weighted Gram/moments → (G (rows,T,E+1,E+1), M (rows,T,N,E+1)).

    Semantics identical to ``ref.smap_gram`` (see its docstring); Y is the
    (N, L) target panel (Y = x[None] for self-prediction). ``thetas`` is
    a tuple: it is static, one program per θ set.
    """
    L = x.shape[-1]
    rows = num_embedded(L, E, tau) - max(Tp, 0)  # raises on too-short series
    if Y.shape[-1] != L:
        raise ValueError("library/target series length mismatch")
    off = (E - 1) * tau + Tp
    E1 = E + 1
    T = len(thetas)
    N = Y.shape[0]
    br = max(8, min(block[0], rows))
    bc = max(128, min(block[1], rows))
    gi = pl.cdiv(rows, br)
    gj = pl.cdiv(rows, bc)
    # Tile-aligned lag-shifted copies (``ref.lag_rows``), padded so the
    # row/col + lag/Tp reach of the last tiles stays in bounds.
    need = max(gi * br, gj * bc) + (E - 1) * tau + max(Tp, 0)
    xpad = jnp.pad(x.astype(jnp.float32), (0, need - L))
    ypad = jnp.pad(Y.astype(jnp.float32), ((0, 0), (0, need - L)))
    xc = lag_rows(xpad, E=E, tau=tau, width=gi * br).T  # (rows, E)
    xr = lag_rows(xpad, E=E, tau=tau, width=gj * bc)  # (E, cols)
    A = jnp.concatenate(  # (cols, E1) design matrix [1 | lags]
        [jnp.ones((gj * bc, 1), jnp.float32), xr.T], axis=1)
    yoff = jax.lax.slice_in_dim(ypad, off, off + gj * bc, axis=1)
    _, G, M = pl.pallas_call(
        functools.partial(_kernel, E=E, rows=rows, thetas=thetas, br=br,
                          bc=bc, exclude_self=exclude_self),
        grid=(gi, 2, gj),
        in_specs=[
            pl.BlockSpec((br, E), lambda i, p, j: (i, 0)),  # row-tile lags
            pl.BlockSpec((E, bc), lambda i, p, j: (0, j)),  # col-tile lags
            pl.BlockSpec((bc, E1), lambda i, p, j: (j, 0)),  # design tile
            pl.BlockSpec((N, bc), lambda i, p, j: (0, j)),  # target truth
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda i, p, j: (i, 0)),
            pl.BlockSpec((T, E1, br, E1), lambda i, p, j: (0, 0, i, 0)),
            pl.BlockSpec((T, N, br, E1), lambda i, p, j: (0, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((gi * br, 1), jnp.float32),     # Σ_i d_ij
            jax.ShapeDtypeStruct((T, E1, gi * br, E1), jnp.float32),
            jax.ShapeDtypeStruct((T, N, gi * br, E1), jnp.float32),
        ],
        interpret=interpret,
        name="smap_gram",
    )(xc, xr, A, yoff)
    # Kernel layout keeps (br, E1) matmul tiles contiguous; callers want
    # query-major (rows, T, …) for the batched Cholesky solve.
    G = jnp.transpose(G, (2, 0, 1, 3))[:rows]  # (rows, T, E1, E1)
    M = jnp.transpose(M, (2, 0, 1, 3))[:rows]  # (rows, T, N, E1)
    return G, M
