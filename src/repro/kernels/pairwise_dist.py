"""Pallas TPU kernel: fused time-delay embedding + pairwise distances.

The paper's Algorithm 1 (kEDM §3.3.1): compute the (Lp, Lp) squared-distance
matrix of the E-dimensional delay embedding *without materializing the
embedding*, reading only the raw 1-D series. On Kokkos the series is cached
in team scratch; here the (small) series lives in VMEM for every grid cell
and each cell computes one (bi, bj) output tile.

Two variants (DESIGN.md §2):

* ``vpu``  — the faithful port: unrolled k-loop of rank-1 differences,
  elementwise FMA on the VPU. Arithmetic intensity grows with E exactly as
  the paper reports.
* ``mxu``  — beyond-paper: the cross term is computed as a skinny matmul
  ``Z_i @ Z_jᵀ`` with E zero-padded to 128 so it runs on the MXU; the
  embedding tiles are still built in-kernel from contiguous VMEM slices
  (the fusion is preserved). ops.py centers the series first so the
  ‖z_i‖² + ‖z_j‖² − 2⟨z_i,z_j⟩ expansion is numerically safe.

Layout trick: the series is passed twice as lag-shifted copies
(``ref.lag_rows``), a (rows, E) block per row tile and an (E, cols) block
per column tile, so the i-axis lags land on sublanes and the j-axis lags
on lanes with no in-kernel transposes and no unaligned slices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import lag_rows, strict_sq

MXU_K = 128  # MXU contraction width the embedding dim is padded to.


def _kernel_vpu(xc_ref, xr_ref, o_ref, *, E: int, bi: int, bj: int):
    acc = jnp.zeros((bi, bj), jnp.float32)
    for k in range(E):  # E <= 20: unrolled, as in the paper's inner loop
        xi = xc_ref[:, k:k + 1]  # (bi, 1) sublanes: lag k of the row tile
        xj = xr_ref[k:k + 1, :]  # (1, bj) lanes: lag k of the column tile
        d = xi - xj
        acc = acc + strict_sq(d)
    o_ref[...] = acc


def _kernel_mxu(xc_ref, xr_ref, o_ref, *, E: int, bi: int, bj: int):
    # Embedding tiles straight from the lag blocks (fusion preserved),
    # zero-padded to the MXU contraction width.
    zi = jnp.concatenate(
        [xc_ref[...], jnp.zeros((bi, MXU_K - E), jnp.float32)], axis=1,
    )  # (bi, 128)
    zjT = jnp.concatenate(
        [xr_ref[...], jnp.zeros((MXU_K - E, bj), jnp.float32)], axis=0,
    )  # (128, bj)
    cross = jax.lax.dot_general(
        zi, zjT, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bi, bj) on the MXU
    ni = jnp.sum(zi * zi, axis=1, keepdims=True)  # (bi, 1)
    nj = jnp.sum(zjT * zjT, axis=0, keepdims=True)  # (1, bj)
    o_ref[...] = jnp.maximum(ni + nj - 2.0 * cross, 0.0)


@functools.partial(
    jax.jit, static_argnames=("E", "tau", "block", "variant", "interpret")
)
def pairwise_distances(
    x: jax.Array,
    *,
    E: int,
    tau: int,
    block: tuple[int, int] = (256, 256),
    variant: str = "vpu",
    interpret: bool = False,
) -> jax.Array:
    """Fused-embedding squared pairwise distances via Pallas. (Lp, Lp) f32."""
    L = x.shape[-1]
    Lp = L - (E - 1) * tau
    if Lp <= 0:
        raise ValueError(f"series too short: L={L}, E={E}, tau={tau}")
    bi, bj = (min(block[0], Lp), min(block[1], Lp))
    # Sublane/lane alignment: distances are cheap to over-tile; clamp to >=8.
    bi = max(8, bi)
    bj = max(8, bj)
    gi = pl.cdiv(Lp, bi)
    gj = pl.cdiv(Lp, bj)
    # Pad so the lag copies cover whole tiles: the last tile reads up to
    # (tiles*b - b) + (E-1)tau + b.
    need = max(gi * bi, gj * bj) + (E - 1) * tau
    x32 = x.astype(jnp.float32)
    # Centering makes the MXU norm-expansion numerically safe and is free
    # for distances; apply to both variants for bit-compat between them.
    x32 = x32 - jnp.mean(x32)
    xpad = jnp.pad(x32, (0, need - L))
    kern = _kernel_mxu if variant == "mxu" else _kernel_vpu
    return pl.pallas_call(
        functools.partial(kern, E=E, bi=bi, bj=bj),
        grid=(gi, gj),
        in_specs=[
            pl.BlockSpec((bi, E), lambda i, j: (i, 0)),  # row-tile lags
            pl.BlockSpec((E, bj), lambda i, j: (0, j)),  # column-tile lags
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Lp, Lp), jnp.float32),
        interpret=interpret,
    )(lag_rows(xpad, E=E, tau=tau, width=gi * bi).T,
      lag_rows(xpad, E=E, tau=tau, width=gj * bj))
