"""Multi-pod pairwise CCM via shard_map (mpEDM's MPI design, SPMD-native).

2-D decomposition of the (library × target) skill matrix over the mesh:
library series are sharded across ``lib_axes`` (default "data", plus "pod"
on multi-pod meshes) and target series across ``tgt_axes`` (default
"model"). Each device drives its local library block through the
library-batched inner engine — local libraries go B at a time through
``ops.all_knn_batch`` (one fused distance + streaming top-k launch per
batch, B from ``core.ccm.auto_batch_libs``' memory budget) plus batched
fused-ρ lookups — and owns the matching ρ-matrix tile. No collective is
needed in the inner loop at all: the only data movement is the initial
placement of the two (replicated-axis) input views, matching mpEDM's
embarrassingly-parallel MPI layout.

Two embedding-dimension modes: a fixed E (the paper's synthetic
benchmarks), or a per-target ``E_opt`` table — targets are then laid out
so every shard owns an identical *static* segment structure of E-groups
(see ``_egroup_layout``) and the inner loop switches E per segment with
still zero collectives. The facade (``repro.edm.EDM.xmap``) feeds
``sharded_optimal_E``'s output straight into the ``E_opt`` mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.core.embedding import embed_offset, num_embedded, pred_rows
from repro.kernels import ops

from repro.compat import make_mesh as make_ccm_mesh  # noqa: F401 (re-export)
from repro.compat import shard_map

# Every per-shard body here runs Pallas kernels, whose out_shapes carry no
# varying-mesh-axes annotation, so shard_map's vma check cannot type them.
_shard_map = functools.partial(shard_map, check_vma=False)


def pad_to_multiple(x: jax.Array, multiple: int, axis: int = 0) -> jax.Array:
    """Zero-pad ``axis`` up to a multiple (devices need equal blocks)."""
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def mesh_axes_size(mesh, axes) -> int:
    """Total device count across the named mesh axes."""
    shape = dict(mesh.shape)
    size = 1
    for ax in axes:
        size *= int(shape[ax])
    return size


def pad_members(members: np.ndarray, multiple: int) -> np.ndarray:
    """Pad an index list to a multiple by repeating its last entry
    (real data — padded slots' results are discarded by the caller)."""
    pad = (-len(members)) % multiple
    if pad == 0:
        return members
    return np.concatenate([members, np.repeat(members[-1:], pad)])


def _egroup_layout(E_opt, S: int):
    """Device-side target layout giving every shard identical E-groups.

    Sharding a contiguously E-sorted target axis would hand each device
    an arbitrary mix of groups (data-dependent, untraceable). Instead
    each group's member list is padded to a multiple of the S target
    shards (repeating its last member — real data, results discarded)
    and split into S equal chunks; shard d's block is its chunk of every
    group in order. Every shard then shares ONE static segment structure
    ``segs = ((E, width), ...)``, so the SPMD inner loop switches E per
    segment with no collective and no data-dependent shapes.

    The (N,)-int ``E_opt`` table never round-trips to host (the old
    PR-3 layout pulled it back to form the permutation): the group
    order comes from a stable device-side argsort (ascending E, then
    index — identical to the old per-E ``nonzero`` concatenation), and
    only a per-level histogram (E_max + 1 ints, unavoidable — the
    segment structure must be static for tracing) crosses the boundary
    before compute. The padded gather pattern is pure host arithmetic
    on those static counts.

    Returns (perm, keep, segs): permuted-target order as a DEVICE array
    (``jnp.take(X, perm)`` stays on device; materialize it at result
    delivery for the host unpermute), the per-slot "not a pad" mask
    (static np bool), and the per-shard segments.
    """
    E_opt = jnp.asarray(E_opt, jnp.int32)
    hist = np.asarray(jnp.bincount(E_opt, length=int(E_opt.max()) + 1))
    order = jnp.argsort(E_opt)  # stable: groups ascending E, index tie order
    seg_gather, seg_keep, segs = [], [], []
    o = 0
    for E, cnt in enumerate(hist.tolist()):
        if cnt == 0:
            continue
        padded = cnt + (-cnt) % S
        gi = o + np.minimum(np.arange(padded), cnt - 1)  # repeat last member
        keep = np.arange(padded) < cnt
        w = padded // S
        segs.append((int(E), w))
        seg_gather.append(gi.reshape(S, w))
        seg_keep.append(keep.reshape(S, w))
        o += cnt
    gather = np.concatenate(seg_gather, axis=1).reshape(-1)
    keep = np.concatenate(seg_keep, axis=1).reshape(-1)
    perm = jnp.take(order, jnp.asarray(gather))
    return perm, keep, tuple(segs)


def _local_block(libs, tgts, *, E, tau, Tp, impl, batch_libs=None,
                 budget_mb=None):
    """ρ tile for (local libraries × local targets): (nl, nt).

    The per-shard inner engine is library-batched (ISSUE 5): local
    libraries are processed B at a time through ``ops.all_knn_batch``
    (one fused distance + streaming top-k launch per batch — the top-k
    never sits inside a per-series ``lax.map`` body), with B from the
    same memory-budget rule as the local engine
    (``core.ccm.auto_batch_libs``). Peak memory per device is one
    (B, Lp, Lp) distance stack; everything stays shard-local, so the
    zero-collective property is untouched.
    """
    from repro.core.ccm import auto_batch_libs, pad_batch, post_lookup_rho

    nl, L = libs.shape
    Lp = num_embedded(L, E, tau)
    rows, off = pred_rows(L, E, tau, Tp), embed_offset(E, tau, Tp)
    hard_max = Lp - 1 - max(Tp, 0)
    B = batch_libs if batch_libs is not None else auto_batch_libs(
        Lp, nl, budget_mb)
    B = max(1, min(int(B), nl))
    nb = -(-nl // B)
    # ragged final batch: repeat real series, drop their rows below
    libs = pad_batch(libs, nb * B)

    def one_batch(lb):
        d, ix = ops.all_knn_batch(lb, E=E, tau=tau, k=E + 1,
                                  exclude_self=True, max_idx=hard_max,
                                  impl=impl)
        return post_lookup_rho(tgts, d, ix, rows=rows, off=off, impl=impl)

    out = jax.lax.map(one_batch, libs.reshape(nb, B, L))
    return out.reshape(nb * B, -1)[:nl]


def _segmented_map(block_fn, segs, *, mesh, lib_axes, tgt_axes,
                   curves=False):
    """SPMD map of (libraries × targets) over the mesh, one static
    E-segment structure ``segs = ((E, width), ...)`` on every shard.

    ``block_fn(E)`` maps (local libs, local target segment) to a (nl, w)
    ρ tile — or, with ``curves=True``, to a (S, nl, w) convergence tile
    whose leading size axis is replicated; the segments' tiles are
    concatenated along the target axis, which stays minor.
    """

    def local(libs, tgts):
        outs, o = [], 0
        for Eg, w in segs:
            seg = jax.lax.slice_in_dim(tgts, o, o + w, axis=0)
            outs.append(block_fn(Eg)(libs, seg))
            o += w
        return jnp.concatenate(outs, axis=-1)

    return _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(lib_axes, None), P(tgt_axes, None)),
        out_specs=P(None, lib_axes, tgt_axes) if curves
        else P(lib_axes, tgt_axes),
    )


@functools.lru_cache(maxsize=64)
def _ccm_program(segs, *, tau, Tp, impl, batch_libs, budget_mb, mesh,
                 lib_axes, tgt_axes):
    """The SPMD program of ``sharded_ccm_matrix`` as a named ``jax.jit``.

    Kept per (E-segments, engine settings, mesh), so a call on shapes
    seen before compiles nothing, and the device trace names the program
    (``jit_sharded_ccm_program``) instead of an anonymous eager map.
    """

    def block_fn(E):
        return functools.partial(_local_block, E=E, tau=tau, Tp=Tp,
                                 impl=impl, batch_libs=batch_libs,
                                 budget_mb=budget_mb)

    mapped = _segmented_map(block_fn, segs, mesh=mesh, lib_axes=lib_axes,
                            tgt_axes=tgt_axes)

    def sharded_ccm_program(X_lib, X_tgt):
        return mapped(X_lib, X_tgt)

    return jax.jit(sharded_ccm_program)


def sharded_ccm_matrix(
    X_lib: jax.Array,
    X_tgt: jax.Array,
    *,
    E: int | None = None,
    tau: int = 1,
    Tp: int = 0,
    mesh: jax.sharding.Mesh,
    lib_axes=("data",),
    tgt_axes=("model",),
    impl: str = "ref",
    E_opt=None,
    batch_libs: int | None = None,
    batch_budget_mb: float | None = None,
    layout=None,
):
    """All-pairs CCM skill matrix on a device mesh.

    X_lib: (N_lib, L) — N_lib must divide evenly over ``lib_axes``.
    X_tgt: (N_tgt, L) — likewise over ``tgt_axes`` (use pad_to_multiple).

    Fixed-E mode (``E=``): returns (N_lib, N_tgt) ρ sharded as
    P(lib_axes, tgt_axes), never leaving the devices.
    Per-target optimal-E mode (``E_opt=`` (N_tgt,) table): targets are
    laid out per ``_egroup_layout`` so each shard runs identical static
    E-segments (zero collectives; libraries are auto-padded over
    ``lib_axes``); returns a host (N_lib, N_tgt) np.ndarray in the
    original target order. ``batch_libs`` / ``batch_budget_mb`` size the
    per-shard library-batched inner engine (see ``_local_block``).
    Both modes run one cached program per (E-segments, shapes, mesh).
    """
    L = X_lib.shape[-1]
    if X_tgt.shape[-1] != L:
        raise ValueError("library/target series length mismatch")
    if (E is None) == (E_opt is None):
        raise ValueError("pass exactly one of E= or E_opt=")
    lib_axes, tgt_axes = tuple(lib_axes), tuple(tgt_axes)

    program = functools.partial(
        _ccm_program, tau=tau, Tp=Tp, impl=impl, batch_libs=batch_libs,
        budget_mb=batch_budget_mb, mesh=mesh, lib_axes=lib_axes,
        tgt_axes=tgt_axes)

    telemetry.counter("edm_sharded_launches").inc()
    with telemetry.span("sharded.ccm_matrix", N_lib=int(X_lib.shape[0]),
                        N_tgt=int(X_tgt.shape[0]), fixed_E=E is not None):
        if E_opt is None:
            nt = X_tgt.shape[0] // mesh_axes_size(mesh, tgt_axes)
            return program(((int(E), nt),))(X_lib, X_tgt)
        return _egrouped_matrix(X_lib, X_tgt, program, E_opt=E_opt,
                                mesh=mesh, lib_axes=lib_axes,
                                tgt_axes=tgt_axes, layout=layout)


def _egrouped_matrix(X_lib, X_tgt, program, *, E_opt, mesh, lib_axes,
                     tgt_axes, curves: bool = False,
                     layout=None) -> np.ndarray:
    """Shared E-grouped driver: per-shard static E-segments, one SPMD
    program, no collectives; host unpermute at result delivery.

    ``program(segs)`` is the SPMD map of one static segment structure
    (``_segmented_map``): (padded libraries, laid-out targets) to the
    (nl, nt) ρ matrix — or, with ``curves=True``, the (S, nl, nt)
    convergence grid.

    ``E_opt`` (and the permutation derived from it) stays on device
    until result delivery — the host sees only the static layout
    metadata before compute (see ``_egroup_layout``). ``layout`` is an
    optional precomputed ``_egroup_layout(E_opt, S_t)`` triple: callers
    slicing the library axis into many calls over the SAME targets (the
    journaled chunked runs of ``edm.runner``) derive it once instead of
    re-sorting E_opt per chunk.
    """
    N_lib, N_tgt = X_lib.shape[0], X_tgt.shape[0]
    E_opt = jnp.broadcast_to(jnp.asarray(E_opt, jnp.int32), (N_tgt,))
    S_t = mesh_axes_size(mesh, tgt_axes)
    S_l = mesh_axes_size(mesh, lib_axes)
    perm_d, keep, segs = (_egroup_layout(E_opt, S_t)
                          if layout is None else layout)
    Xl = pad_to_multiple(X_lib, S_l, axis=0)
    Xt = jnp.take(jnp.asarray(X_tgt), perm_d, axis=0)
    R = np.asarray(program(segs)(Xl, Xt))
    perm = np.asarray(perm_d)  # delivered WITH the results, not before
    if curves:
        rho = np.zeros((R.shape[0], N_lib, N_tgt), np.float32)
        rho[:, :, perm[keep]] = R[:, :N_lib, keep]
    else:
        rho = np.zeros((N_lib, N_tgt), np.float32)
        rho[:, perm[keep]] = R[:N_lib, keep]
    return rho


def sharded_ccm_convergence(
    X_lib: jax.Array,
    X_tgt: jax.Array,
    *,
    lib_sizes,
    E: int | None = None,
    tau: int = 1,
    Tp: int = 0,
    mesh: jax.sharding.Mesh,
    lib_axes=("data",),
    tgt_axes=("model",),
    impl: str = "ref",
    E_opt=None,
):
    """All-pairs CCM *convergence* grids on a device mesh.

    The sharded counterpart of ``core.ccm.ccm_convergence``: every
    (library, target) pair's full library-size curve, shape
    (num_sizes, N_lib, N_tgt), with the same 2-D (library × target)
    decomposition and zero-collective inner loop as
    ``sharded_ccm_matrix``. Each device runs ONE multi-cap streaming
    top-k per local library (``ops.topk_select_sizes``) — never a
    per-size re-scan — and owns its curve tile; the size axis is
    replicated (it is |sizes| ≪ N² and shared by every pair).

    Fixed-E mode (``E=``): returns (S, N_lib, N_tgt) ρ sharded as
    P(None, lib_axes, tgt_axes). Per-target optimal-E mode (``E_opt=``
    (N_tgt,) table): targets are laid out per ``_egroup_layout`` so
    each shard runs identical static E-segments (zero collectives;
    sizes re-clamped per segment E); returns a host np.ndarray in the
    original target order. ``lib_sizes`` follows the caller's
    order/shape (validated / deduped / clamped as in
    ``core.ccm.normalize_lib_sizes``).
    """
    from repro.core.ccm import ccm_convergence_caps, normalize_lib_sizes

    L = X_lib.shape[-1]
    if X_tgt.shape[-1] != L:
        raise ValueError("library/target series length mismatch")
    if (E is None) == (E_opt is None):
        raise ValueError("pass exactly one of E= or E_opt=")

    def block_fn(Eb):
        caps, inv = normalize_lib_sizes(
            lib_sizes, Lp=num_embedded(L, Eb, tau), Tp=Tp)
        inv_j = jnp.asarray(inv)

        def block(libs, tgts):
            def one_library(x):
                return ccm_convergence_caps(
                    x, tgts, E=Eb, tau=tau, Tp=Tp, caps=caps,
                    exclude_self=True, impl=impl)  # (|caps|, nt)

            cur = jax.lax.map(one_library, libs)  # (nl, |caps|, nt)
            return jnp.take(jnp.moveaxis(cur, 1, 0), inv_j, axis=0)

        return block

    telemetry.counter("edm_sharded_launches").inc()
    with telemetry.span("sharded.ccm_convergence",
                        N_lib=int(X_lib.shape[0]),
                        N_tgt=int(X_tgt.shape[0])):
        if E_opt is None:
            mapped = _shard_map(
                block_fn(E),
                mesh=mesh,
                in_specs=(P(lib_axes, None), P(tgt_axes, None)),
                out_specs=P(None, lib_axes, tgt_axes),
            )
            return mapped(X_lib, X_tgt)
        return _egrouped_matrix(
            X_lib, X_tgt,
            functools.partial(_segmented_map, block_fn, mesh=mesh,
                              lib_axes=lib_axes, tgt_axes=tgt_axes,
                              curves=True),
            E_opt=E_opt, mesh=mesh, lib_axes=lib_axes, tgt_axes=tgt_axes,
            curves=True)


def sharded_optimal_E(
    X: jax.Array,
    *,
    E_max: int = 20,
    tau: int = 1,
    Tp: int = 1,
    mesh: jax.sharding.Mesh,
    axes=("data",),
    impl: str = "ref",
) -> tuple[jax.Array, jax.Array]:
    """Per-series optimal E on a device mesh → (E_opt (N,), ρ (N, E_max)).

    Series are sharded over ``axes``; each device runs the incremental
    multi-E engine (ONE all-kNN pass per local series instead of E_max
    pipelines — see kernels/knn_multi_e.py) on its shard with no
    collectives at all. This is the in-shard front half of the whole-brain
    CCM workload: the E_opt it emits feeds ``core.ccm.ccm_matrix``'s
    E-grouping or per-group ``sharded_ccm_matrix`` calls.

    N must divide evenly over ``axes`` (use pad_to_multiple).
    """
    from repro.core.simplex import optimal_E_batch

    def local(Xl):  # the local driver, verbatim, on the shard's series
        return optimal_E_batch(Xl, E_max=E_max, tau=tau, Tp=Tp, impl=impl)

    telemetry.counter("edm_sharded_launches").inc()
    with telemetry.span("sharded.optimal_E", N=int(X.shape[0]),
                        E_max=E_max):
        mapped = _shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes, None),),
            out_specs=(P(axes), P(axes, None)),
        )
        return mapped(X)


def sharded_smap_theta(
    X: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 1,
    thetas: tuple[float, ...] | None = None,
    ridge: float = 1e-6,
    mesh: jax.sharding.Mesh,
    axes=("data",),
    impl: str = "ref",
) -> jax.Array:
    """Per-series S-Map θ-sweeps on a device mesh → ρ (N, |θ|).

    The nonlinearity-test half of the whole-brain workload: series are
    sharded over ``axes`` and each device runs the batched S-Map engine
    (one Gram accumulation + one batched Cholesky per local series, every
    θ at once — core/smap_engine.py) on its shard with no collectives at
    all. N must divide evenly over ``axes`` (use pad_to_multiple).
    """
    from repro.core.smap_engine import DEFAULT_THETAS, smap_theta_sweep

    thetas = DEFAULT_THETAS if thetas is None else tuple(
        float(t) for t in thetas)

    def local(Xl):  # the local engine, verbatim, on the shard's series
        return smap_theta_sweep(Xl, E=E, tau=tau, Tp=Tp, thetas=thetas,
                                ridge=ridge, impl=impl)

    telemetry.counter("edm_sharded_launches").inc()
    with telemetry.span("sharded.smap_theta", N=int(X.shape[0]), E=E,
                        thetas=len(thetas)):
        mapped = _shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes, None),),
            out_specs=P(axes, None),
        )
        return mapped(X)


def sharded_smap_matrix(
    X_lib: jax.Array,
    X_tgt: jax.Array,
    *,
    E: int | None = None,
    tau: int = 1,
    Tp: int = 0,
    theta: float = 1.0,
    ridge: float = 1e-6,
    mesh: jax.sharding.Mesh,
    lib_axes=("data",),
    tgt_axes=("model",),
    impl: str = "ref",
    E_opt=None,
    layout=None,
):
    """All-pairs S-Map cross-map skill matrix on a device mesh.

    Same 2-D (library × target) decomposition and zero-collective inner
    loop as ``sharded_ccm_matrix``, with the simplex lookup replaced by
    the batched S-Map engine (fit on each local library's manifold,
    predict the local targets).

    Fixed-E mode (``E=``): returns (N_lib, N_tgt) ρ sharded as
    P(lib_axes, tgt_axes). Per-target optimal-E mode (``E_opt=`` (N_tgt,)
    table — ROADMAP item (b), fed by ``sharded_optimal_E``): each shard
    fits its local libraries at every E-segment of its static layout
    (see ``_egroup_layout``), still zero collectives; returns a host
    (N_lib, N_tgt) np.ndarray in the original target order. Exposed as
    ``repro.edm.EDM.xmap(method="smap")`` on mesh sessions.
    """
    from repro.core.smap_engine import smap_group

    if X_tgt.shape[-1] != X_lib.shape[-1]:
        raise ValueError("library/target series length mismatch")
    if (E is None) == (E_opt is None):
        raise ValueError("pass exactly one of E= or E_opt=")

    def block_fn(Eb):
        def block(libs, tgts):
            return smap_group(libs, tgts, E=Eb, tau=tau, Tp=Tp,
                              theta=float(theta), ridge=ridge, impl=impl)
        return block

    telemetry.counter("edm_sharded_launches").inc()
    with telemetry.span("sharded.smap_matrix", N_lib=int(X_lib.shape[0]),
                        N_tgt=int(X_tgt.shape[0]), fixed_E=E is not None):
        if E_opt is None:
            mapped = _shard_map(
                block_fn(E),
                mesh=mesh,
                in_specs=(P(lib_axes, None), P(tgt_axes, None)),
                out_specs=P(lib_axes, tgt_axes),
            )
            return mapped(X_lib, X_tgt)
        return _egrouped_matrix(
            X_lib, X_tgt,
            functools.partial(_segmented_map, block_fn, mesh=mesh,
                              lib_axes=lib_axes, tgt_axes=tgt_axes),
            E_opt=E_opt, mesh=mesh, lib_axes=lib_axes, tgt_axes=tgt_axes,
            layout=layout)


def ccm_step(X: jax.Array, *, E: int, tau: int, mesh: jax.sharding.Mesh,
             lib_axes=("data",), tgt_axes=("model",), impl: str = "ref"):
    """Dry-run entry point: all-pairs CCM of one (N, L) panel (lib == tgt)."""
    return sharded_ccm_matrix(
        X, X, E=E, tau=tau, mesh=mesh, lib_axes=lib_axes, tgt_axes=tgt_axes,
        impl=impl,
    )
