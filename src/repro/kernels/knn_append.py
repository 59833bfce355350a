"""Pallas kernel: k-best merge for incremental master append.

The serving-path companion to ``ref.master_append``: same O(Lp·(k+dt))
per-level stream-in/merge (see the append section of kernels/ref.py for
the contract and the strict-chain/tie-order/garbage rules), with the
per-row k-best selection lowered to a Pallas kernel instead of
``lax.top_k``.

The split of labor is deliberate: candidate *values* are produced by the
same strict-``jnp`` chains the reference uses (``ref.strict_sq`` keeps
them bit-identical to the cold build at any shape), and the kernel is
PURE SELECTION — no float arithmetic, only compares and gathers — so the
Pallas path inherits the reference's bit-parity guarantee for free. The
selection rule is ``knn_batch.py``'s retire-by-index min-merge
((value asc, index asc), distinct fill entries for < k-candidate rows),
which equals ``lax.top_k`` over the positionally-ordered candidate
layout (stored slots are already in global (value, index) order and
their indices all precede the appended columns').

One layout subtlety this kernel owns: a stored GARBAGE slot (dist=inf
from k_m exceeding a level's candidate count) carries the old build's
deterministic index pattern ``[i, Lp_old_e, …]`` — indices that collide
with now-valid appended columns. Retire-by-index would then retire a
real candidate along with the garbage slot, so garbage indices are
remapped to distinct ``_BIG_I + slot`` sentinels before the merge; every
surviving non-finite slot is re-normalized to the cold pattern
afterwards (``ref.normalize_garbage``, shared with the reference path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref
from repro.kernels.ref import PAD_IDX
from repro.kernels.topk import _BIG_I, merge_kbest


def _select_kernel(cd_ref, ci_ref, dk_ref, ik_ref, *, k):
    """Per-row k smallest (value asc, index asc) of a candidate block.

    Inputs are positive squared distances (inf = masked or garbage) with
    per-row-unique indices (sentinels ≥ _BIG_I for garbage). Pure
    selection — the output value bits are copies of input bits.
    """
    dk_ref[...], ik_ref[...] = merge_kbest(cd_ref[...], ci_ref[...], k,
                                           big=_BIG_I + 2**20)


def _select(cand_d, cand_i, *, k, block, interpret):
    """k-best rows of (R, C) candidates via the selection kernel."""
    R, C = cand_d.shape
    br = max(8, min(block, R))
    g = pl.cdiv(R, br)
    pad = g * br - R
    # Padding rows are all-inf/sentinel: selected then discarded.
    cand_d = jnp.pad(cand_d, ((0, pad), (0, 0)), constant_values=jnp.inf)
    cand_i = jnp.pad(cand_i, ((0, pad), (0, 0)), constant_values=_BIG_I)
    dk, ik = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0)),
            pl.BlockSpec((br, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g * br, k), jnp.float32),
            jax.ShapeDtypeStruct((g * br, k), jnp.int32),
        ],
        interpret=interpret,
    )(cand_d, cand_i)
    return dk[:R], ik[:R]


@functools.partial(jax.jit, static_argnames=("dt", "E_max", "tau", "block",
                                             "interpret"))
def _master_append(x, dM, iM, *, dt, E_max, tau, block, interpret):
    L_new = x.shape[-1]
    L_old = L_new - dt
    k_m = dM.shape[-1]
    xpad = jnp.pad(x.astype(jnp.float32), (0, (E_max - 1) * tau))
    xls = [jax.lax.dynamic_slice_in_dim(xpad, l * tau, L_new, axis=-1)
           for l in range(E_max)]
    slab = _ref.append_new_row_slab(x, dt=dt, E_max=E_max, tau=tau)
    outs_d, outs_i = [], []
    for e in range(E_max):  # level e ↔ embedding dim E = e+1
        Lp_old = L_old - e * tau
        Lp_new = L_new - e * tau
        rows_o = jnp.arange(Lp_old, dtype=jnp.int32)
        new_cols = Lp_old + jnp.arange(dt, dtype=jnp.int32)
        slot = jnp.arange(k_m, dtype=jnp.int32)[None, :]
        # -- old rows: strict-chain recompute of stored candidates -------
        i_o = iM[e, :Lp_old]
        ok = jnp.isfinite(dM[e, :Lp_old])
        jj = jnp.maximum(i_o, 0)
        acc_s = jnp.zeros((Lp_old, k_m), jnp.float32)
        for l in range(e + 1):
            xl = xls[l]
            ds = xl[:Lp_old, None] - xl[jj]
            acc_s = acc_s - _ref.strict_sq(ds)
        nd_new = slab[e, :, :Lp_old].T
        cand_d = jnp.concatenate(
            [jnp.where(ok, -acc_s, jnp.inf), -nd_new], axis=1)
        cand_i = jnp.concatenate(
            [jnp.where(ok, i_o, _BIG_I + slot),
             jnp.broadcast_to(new_cols, (Lp_old, dt))], axis=1)
        dk_o, ik_sel = _select(cand_d, cand_i, k=k_m, block=block,
                               interpret=interpret)
        ik_o = _ref.normalize_garbage(-dk_o, ik_sel, rows_o)
        # -- new rows: full slab rows, masked like the cold accumulator --
        rows_n = Lp_old + jnp.arange(dt, dtype=jnp.int32)
        colsL = jnp.arange(L_new, dtype=jnp.int32)[None, :]
        inval = (colsL > Lp_new - 1) | (colsL == rows_n[:, None])
        dk_n, ik_seln = _select(
            jnp.where(inval, jnp.inf, -slab[e]),
            jnp.broadcast_to(colsL, (dt, L_new)),
            k=k_m, block=block, interpret=interpret)
        ik_n = _ref.normalize_garbage(-dk_n, ik_seln, rows_n)
        # -- assemble the level ------------------------------------------
        dk = jnp.concatenate([dk_o, dk_n], axis=0)
        ik = jnp.concatenate([ik_o, ik_n], axis=0)
        d_lvl = jnp.sqrt(jnp.maximum(dk, 0.0))
        outs_d.append(jnp.pad(d_lvl, ((0, L_new - Lp_new), (0, 0)),
                              constant_values=jnp.inf))
        outs_i.append(jnp.pad(ik, ((0, L_new - Lp_new), (0, 0)),
                              constant_values=PAD_IDX))
    return jnp.stack(outs_d), jnp.stack(outs_i)


def master_append(
    x: jax.Array,
    dists: jax.Array,
    idx: jax.Array,
    *,
    tau: int = 1,
    block: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Kernel-path ``ref.master_append`` — bit-identical, same contract."""
    dt = _ref.check_append_args(x, dists, idx, tau)
    E_max = dists.shape[0]
    return _master_append(x, dists, idx, dt=dt, E_max=E_max, tau=tau,
                          block=block, interpret=interpret)
