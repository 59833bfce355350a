"""Pallas TPU kernel: FUSED all-kNN — pairwise distances + top-k in one.

Beyond-paper optimization (EXPERIMENTS.md §Perf, EDM cell). kEDM follows
the exhaustive two-kernel design: materialize the (Lp, Lp) distance
matrix in global memory (Alg. 1), then partially sort each row (Alg. 2).
Its own roofline analysis (paper Figs. 6–7) shows exactly that matrix
write+read is the dominant memory term.

On TPU the two phases fuse: each grid cell computes a (br, Lp) row-block
of distances directly into VMEM — embedding fused as in pairwise_dist.py
— and immediately runs the k-pass argmin-extract on it. The distance
matrix never touches HBM: traffic drops from 2·4·Lp² bytes (write+read)
to 8·Lp·k bytes of results plus the series reads — ~470× less at the
paper's L=10⁴, k=21 scale, removing the dominant roofline term of both
kEDM kernels at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import lag_rows, strict_sq


def _kernel(mx_ref, xc_ref, xr_ref, dk_ref, ik_ref, *, E, k, br, Lp,
            exclude_self):
    i0 = pl.program_id(0) * br
    # ---- Alg. 1 (fused embedding) on a (br, Lp) row block, in VMEM
    acc = jnp.zeros((br, Lp), jnp.float32)
    for kk in range(E):  # E ≤ 20: unrolled
        xi = xc_ref[:, kk:kk + 1]  # (br, 1): lag kk of the row tile
        xj = xr_ref[kk:kk + 1, :]  # (1, Lp): lag kk of every column
        d = xi - xj
        acc = acc + strict_sq(d)
    # ---- Alg. 2 masking + k-pass extraction, still in VMEM
    cols = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    max_idx = mx_ref[0, 0]
    invalid = cols > max_idx
    if exclude_self:
        rows = i0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        invalid = invalid | (cols == rows)
    acc = jnp.where(invalid, jnp.inf, acc)
    dists, idxs = [], []
    for _ in range(k):
        m = jnp.min(acc, axis=1, keepdims=True)
        cand = jnp.where(acc == m, cols, 2**30)
        idx = jnp.min(cand, axis=1, keepdims=True)
        dists.append(m)
        idxs.append(idx)
        acc = jnp.where(cols == idx, jnp.inf, acc)
    dk_ref[...] = jnp.sqrt(jnp.maximum(jnp.concatenate(dists, axis=1), 0.0))
    ik_ref[...] = jnp.concatenate(idxs, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("E", "tau", "k", "exclude_self", "block_rows",
                     "interpret"))
def all_knn_fused(
    x: jax.Array,
    *,
    E: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused all-kNN over one series → (dists (Lp,k), idx (Lp,k))."""
    k = E + 1 if k is None else k
    L = x.shape[-1]
    Lp = L - (E - 1) * tau
    if Lp <= 0:
        raise ValueError(f"series too short: L={L}, E={E}, tau={tau}")
    br = max(8, min(block_rows, Lp))
    gi = pl.cdiv(Lp, br)
    need = gi * br + (E - 1) * tau  # lag copies cover whole row tiles
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32)
    xpad = jnp.pad(x32, (0, max(need, L) - L))
    mx = jnp.full((1, 1), Lp - 1 if max_idx is None else max_idx, jnp.int32)
    dk, ik = pl.pallas_call(
        functools.partial(_kernel, E=E, k=k, br=br, Lp=Lp,
                          exclude_self=exclude_self),
        grid=(gi,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # dynamic candidate cap
            pl.BlockSpec((br, E), lambda i: (i, 0)),  # row-tile lags
            pl.BlockSpec((E, Lp), lambda i: (0, 0)),  # every column's lags
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
    )(mx, lag_rows(xpad, E=E, tau=tau, width=gi * br).T,
      lag_rows(xpad, E=E, tau=tau, width=Lp))
    return dk, ik
