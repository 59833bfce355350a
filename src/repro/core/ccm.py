"""Convergent Cross Mapping (paper §2.1, Fig. 1; the headline workload).

Directionality convention (matches the paper): to ask whether ``target``
causally forces ``lib``, embed the *library* series, find its neighbors,
and cross-map the *target*: high skill ρ(target, target̂ | M_lib) is
evidence that information about ``target`` is encoded in ``lib``'s
dynamics, i.e. "target CCM-causes lib".

``ccm_matrix`` reproduces kEDM's pairwise CCM: one set of neighbor tables
per (library series × distinct optimal-E), batched lookups for all target
series sharing that E (§3.4's grouping), fused Pearson ρ.
"""

from __future__ import annotations

import functools
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.embedding import embed_offset, num_embedded, pred_rows
from repro.kernels import ops


def normalize_lib_sizes(lib_sizes, *, Lp: int, Tp: int = 0):
    """Validate a convergence-sweep size list → (caps, inverse map).

    Returns ``(caps, inv)``: ``caps`` is the ascending tuple of *unique*
    inclusive neighbor-index caps (``min(size − 1, Lp − 1 − Tp)``), and
    ``inv`` maps each requested size back to its cap's position, so
    callers compute each distinct cap once and scatter results to the
    caller's order/shape. Sizes must be >= 1 (ValueError otherwise);
    unsorted, duplicate, or oversized (> the Lp − Tp usable library
    points) inputs are accepted for compatibility but draw a single
    ``UserWarning`` naming what was cleaned — they used to be silently
    recomputed per entry (duplicates) or silently clamped (oversized).
    """
    sizes = [int(s) for s in lib_sizes]
    if not sizes:
        raise ValueError("lib_sizes must not be empty")
    bad = [s for s in sizes if s < 1]
    if bad:
        raise ValueError(f"lib_sizes must all be >= 1, got {bad}")
    hard_max = Lp - 1 - max(Tp, 0)
    issues = []
    if any(b < a for a, b in zip(sizes, sizes[1:])):
        issues.append("unsorted (computed on the sorted unique caps)")
    if len(set(sizes)) != len(sizes):
        issues.append("duplicates (each cap computed once)")
    over = [s for s in sizes if s - 1 > hard_max]
    if over:
        issues.append(
            f"sizes {over} exceed the {hard_max + 1} usable library "
            f"points (clamped)")
    if issues:
        warnings.warn(
            f"lib_sizes {tuple(sizes)}: " + "; ".join(issues),
            UserWarning, stacklevel=3)
    caps_all = [min(s - 1, hard_max) for s in sizes]
    caps = tuple(sorted(set(caps_all)))
    inv = np.asarray([caps.index(c) for c in caps_all], np.int32)
    return caps, inv


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "caps",
                                             "exclude_self", "impl"))
def ccm_convergence_caps(lib, targets, *, E, tau, Tp, caps, exclude_self,
                         impl):
    """(|caps|, N) curve grid: one distance pass, one multi-cap top-k.

    The caps-level engine under ``ccm_convergence`` — callers that
    already hold normalized ascending caps (the session's
    ``_ccm_curves``, the sharded convergence blocks) enter here and do
    their own size→cap bookkeeping/warnings via
    ``normalize_lib_sizes``.
    """
    L = lib.shape[-1]
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    D = ops.pairwise_distances(lib, E=E, tau=tau, impl=impl)
    dS, iS = ops.topk_select_sizes(D, k=E + 1, max_idxs=caps,
                                   exclude_self=exclude_self, impl=impl)
    curves = []
    for s in range(len(caps)):  # static, small: unrolled per-cap lookups
        w = ops.make_weights(dS[s])
        curves.append(ops.lookup_rho(targets, iS[s, :rows], w[:rows],
                                     offset=off, impl=impl))
    return jnp.stack(curves)


def ccm_convergence(
    lib: jax.Array,
    targets: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 0,
    lib_sizes,
    exclude_self: bool = True,
    impl: str = "auto",
) -> jax.Array:
    """Full CCM convergence curve grid → (num_sizes, N) ρ, one program.

    The batched replacement for ``cross_map``'s per-size host loop:
    one ``pairwise_distances`` pass and ONE multi-cap streaming top-k
    (``ops.topk_select_sizes``) produce every library-prefix neighbor
    table, then each cap's batched fused-ρ lookup runs inside the same
    jitted program. Bit-identical to the legacy loop (kept as
    ``cross_map_sizes_seed``) — ρ rising with library size is CCM's
    causality criterion, so the curve grid is the unit of work for
    significance testing (``repro.edm.EDM.surrogate_test``).

    ``lib_sizes`` follows the caller's order/shape (duplicates and
    oversized entries are computed once / clamped, with a warning —
    see ``normalize_lib_sizes``).
    """
    if targets.ndim == 1:
        targets = targets[None, :]
    Lp = num_embedded(lib.shape[-1], E, tau)
    caps, inv = normalize_lib_sizes(lib_sizes, Lp=Lp, Tp=Tp)
    curves = ccm_convergence_caps(lib, targets, E=E, tau=tau, Tp=Tp,
                                  caps=caps, exclude_self=exclude_self,
                                  impl=impl)
    return curves[inv]


def cross_map_sizes_seed(
    lib: jax.Array,
    targets: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 0,
    lib_sizes,
    exclude_self: bool = True,
    impl: str = "auto",
) -> jax.Array:
    """The seed per-size convergence loop → (num_sizes, N) ρ.

    One full ``topk_select`` re-scan of the distance matrix per library
    size, dispatched from the host. Kept verbatim as the oracle and
    benchmark baseline for ``ccm_convergence`` (the BENCH_ccm.json
    before/after), exactly like ``smap_predict_seed`` for the S-Map
    engine.
    """
    if targets.ndim == 1:
        targets = targets[None, :]
    L = lib.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    hard_max = Lp - 1 - max(Tp, 0)
    D = ops.pairwise_distances(lib, E=E, tau=tau, impl=impl)

    def rho_for(max_idx):
        d, i = ops.topk_select(D, k=E + 1, exclude_self=exclude_self,
                               max_idx=max_idx, impl=impl)
        w = ops.make_weights(d)
        return ops.lookup_rho(targets, i[:rows], w[:rows], offset=off,
                              impl=impl)

    return jnp.stack(
        [rho_for(jnp.minimum(int(s) - 1, hard_max)) for s in lib_sizes])


def cross_map(
    lib: jax.Array,
    targets: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 0,
    lib_sizes=None,
    exclude_self: bool = True,
    impl: str = "auto",
) -> jax.Array:
    """Cross-map skill of predicting each target from ``lib``'s manifold.

    targets: (N, L) (a 1-D series is promoted). Returns (N,) ρ — or
    (num_sizes, N) when ``lib_sizes`` is given (the *convergence* sweep:
    ρ rising with library size is CCM's causality criterion, computed by
    ``ccm_convergence``: one distance pass + one multi-cap streaming
    top-k instead of the seed's per-size re-scan loop). ``lib_sizes``
    entries are validated (>= 1), deduplicated, and clamped to the
    usable library with a warning.
    """
    squeeze = targets.ndim == 1
    if squeeze:
        targets = targets[None, :]
    if lib_sizes is not None:
        curves = ccm_convergence(
            lib, targets, E=E, tau=tau, Tp=Tp, lib_sizes=lib_sizes,
            exclude_self=exclude_self, impl=impl)
        return curves[:, 0] if squeeze else curves
    L = lib.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    D = ops.pairwise_distances(lib, E=E, tau=tau, impl=impl)
    d, i = ops.topk_select(D, k=E + 1, exclude_self=exclude_self,
                           max_idx=Lp - 1 - max(Tp, 0), impl=impl)
    w = ops.make_weights(d)
    rho = ops.lookup_rho(targets, i[:rows], w[:rows], offset=off, impl=impl)
    return rho[0] if squeeze else rho


#: Default memory budgets (MB) for the library-batched engine's in-flight
#: (B, Lp, Lp) f32 distance stack. The budget counts the primary stack;
#: transient copies (mask apply, top-k candidates) put the true peak at a
#: small multiple of it. Backend-dependent on purpose: an HBM-backed
#: accelerator wants launches big enough to amortize dispatch, while on
#: XLA CPU the stack competes with the last-level cache — the
#: ``bench_ccm --sweep-batch`` curves show pairs/s *falling* once
#: B·Lp²·4 outgrows ~tens of MB (B=48 at Lp=1022 is slower than B=8).
DEFAULT_BATCH_BUDGET_MB = 256
DEFAULT_BATCH_BUDGET_MB_CPU = 32


def _default_budget_mb() -> int:
    return (DEFAULT_BATCH_BUDGET_MB_CPU if jax.devices()[0].platform == "cpu"
            else DEFAULT_BATCH_BUDGET_MB)


def auto_batch_libs(Lp: int, Nl: int, budget_mb: float | None = None, *,
                    per_series_bytes: int | None = None) -> int:
    """Library batch size B with B·Lp² f32 under the memory budget.

    The ISSUE 5 sizing rule: one batched engine launch holds a
    (B, Lp, Lp) squared-distance stack in flight, so B is capped at the
    largest count that keeps it under ``budget_mb`` (default: backend-
    dependent, see ``DEFAULT_BATCH_BUDGET_MB*``), clamped to [1, Nl].
    Under that cap the launches are *equalized* — B = ceil(Nl / nb) for
    the smallest launch count nb the cap allows — because the ragged
    final launch is padded to a full B: a cap of 949 against Nl = 1024
    would otherwise run one full launch plus one padded 75→949 launch,
    wasting almost half the compute (measured: 545k vs 955k pairs/s).
    Short-series panels (tiny Lp) batch large swaths of the library axis
    per launch; long series fall back toward per-series steps.

    Engines whose in-flight footprint is NOT a distance stack (the
    cached-master derivation holds O(Lp·k_master) per series) pass their
    real ``per_series_bytes`` instead of inheriting the 4·Lp² default.
    """
    budget = _default_budget_mb() if budget_mb is None else budget_mb
    per = 4 * Lp * Lp if per_series_bytes is None else max(
        1, int(per_series_bytes))
    Nl = max(Nl, 1)
    cap = max(1, min(Nl, int(budget * 2**20) // per))
    nb = -(-Nl // cap)
    return -(-Nl // nb)


def post_lookup_rho(targets, d, i, *, rows, off, impl, live_rows=None):
    """Per-series weights + fused-ρ stage of every batched matrix engine.

    (d, i) are (B, Lp, k) neighbor tables; returns (B, Nt) ρ via a
    ``lax.map`` whose body runs on per-series shapes. This stage is THE
    load-bearing half of the batch-axis bit-parity contract — every
    rounding-sensitive op here must see shapes independent of B — so the
    direct engine (``_group_step``), the cached-master engine
    (``edm.plan._master_group_step``), and the per-shard engine
    (``distributed.sharded_ccm._local_block``) all share this one
    implementation instead of keeping three copies in sync.
    ``live_rows`` (an operand) correlates only the first ``live_rows``
    of the ``rows`` table rows: the capacity-panel engine's tables span
    the capacity, its valid prefix is shorter.
    """

    def post(args):
        dB, iB = args
        w = ops.make_weights(dB)
        return ops.lookup_rho(targets, iB[:rows], w[:rows], live_rows,
                              offset=off, impl=impl)

    return jax.lax.map(post, (d, i))


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "k", "impl"))
def _group_step(libs, targets, *, E, tau, Tp, k, impl):
    """One engine launch: fused distance→top-k→weights→ρ for B libraries.

    The kNN axis is batched through ``ops.all_knn_batch`` (the whole
    point — it hoists the top-k out of any ``lax.map`` body); the
    weights + fused-ρ lookup stay per-series ``lax.map`` sub-steps
    (``post_lookup_rho``) so every rounding-sensitive stage runs on
    per-series shapes, making the result bit-invariant in B (see
    kernels/ref.py).
    """
    L = libs.shape[-1]
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    hard_max = num_embedded(L, E, tau) - 1 - max(Tp, 0)
    d, i = ops.all_knn_batch(libs, E=E, tau=tau, k=k, exclude_self=True,
                             max_idx=hard_max, impl=impl)
    return post_lookup_rho(targets, d, i, rows=rows, off=off, impl=impl)


def pad_batch(chunk: jax.Array, B: int) -> jax.Array:
    """Pad a ragged final batch to B rows by repeating the last series.

    Real data, so the engine needs no masking; the driver discards the
    padded rows at assembly. Keeping every launch at the same (B, L)
    shape means ONE compiled program serves the whole library axis.
    """
    n = chunk.shape[0]
    if n == B:
        return chunk
    return jnp.concatenate([chunk, jnp.repeat(chunk[-1:], B - n, axis=0)])


def drive_batched(Nl: int, B: int, launch, *, start: int = 0,
                  on_block=None, monitor=None) -> np.ndarray:
    """Double-buffered host loop over ceil((Nl − start)/B) engine launches.

    ``launch(a, b, B)`` dispatches rows [a, b) (padded to B) and returns
    the not-yet-materialized device result. JAX dispatch is async, so
    while the host converts/assembles batch i's block the device is
    already computing batch i+1 — the ROADMAP session-item-(b) overlap.
    At most two batch results are in flight.

    Fault-tolerance hooks (``repro.edm.runner``, all optional and free
    when unused):

    * ``start`` — resume offset: rows [0, start) are assumed already
      assembled elsewhere (a journaled run's committed tiles) and are
      neither dispatched nor written; the returned array's rows below
      ``start`` are uninitialized.
    * ``on_block(a, b, block)`` — called after each block's rows [a, b)
      have materialized on host (``block`` is the (b − a, …) slice), the
      tile-journal commit point. A raise here (preemption checkpoint-
      and-exit) leaves no partially-written tile behind.
    * ``monitor`` — a ``distributed.fault.StragglerMonitor`` timed over
      each loop iteration (dispatch of tile i + landing of tile i−1),
      stamped with the landed tile's row offset. One iteration is ~one
      tile of work whether the engine is async (the land is the device
      wait) or synchronous like the sharded chunk path (the dispatch is
      the compute), so a flagged entry means that tile ran slow relative
      to the run's rolling median — the per-host straggler statistic.
    """
    if start >= Nl:  # resumed run with no tiles left: nothing to drive
        return None
    out = pending = None
    # Always-on per-launch metrics (float/int adds, no sink required):
    # the pairs/s numerator, the launch count, and the host seconds spent
    # inside ``launch`` (building and enqueuing it). The launch/land
    # spans are emitted only when telemetry is live.
    pairs = telemetry.counter("edm_pairs_total")
    launches = telemetry.counter("edm_launches")
    dispatch_s = telemetry.counter("edm_dispatch_seconds")

    def land(pending):
        nonlocal out
        (pa, pb), arr = pending
        with telemetry.span("engine.land", a=pa, b=pb):
            block = np.asarray(arr)       # the device sync point
            if out is None:
                out = np.empty((Nl,) + block.shape[1:], block.dtype)
            out[pa:pb] = block[: pb - pa]
        pairs.inc(int(block[: pb - pa].size))
        if on_block is not None:
            on_block(pa, pb, block[: pb - pa])

    with telemetry.span("engine.drive", Nl=Nl, B=B, start=start):
        for a in range(start, Nl, B):
            b = min(a + B, Nl)
            if monitor is not None:
                monitor.start()
            launches.inc()
            with telemetry.span("engine.launch", a=a, b=b, B=B):
                t0 = time.perf_counter()
                cur = launch(a, b, B)
                dispatch_s.inc(time.perf_counter() - t0)
            if pending is not None:
                land(pending)
                if monitor is not None:
                    monitor.stop(pending[0][0])
            pending = ((a, b), cur)
        if monitor is not None:
            monitor.start()
        land(pending)
        if monitor is not None:
            monitor.stop(pending[0][0])
    return out


def make_group_launch(libs, targets, *, E, tau, Tp, k, impl):
    """Launch closure of the direct batched engine: ``launch(a, b, B)``.

    Factored out of ``ccm_group_batched`` so the fault-tolerant driver
    (``repro.edm.runner``) can re-drive the SAME engine at a smaller B
    after an OOM backoff — results are bit-invariant in B, so the launch
    closure is the resumable unit, not the whole group call.
    """
    impl_r = ops.resolve_impl(impl)
    group_launches = telemetry.counter("edm_group_launches")

    def launch(a, b, B):
        group_launches.inc()
        return _group_step(pad_batch(libs[a:b], B), targets, E=E, tau=tau,
                           Tp=Tp, k=k, impl=impl_r)

    return launch


def ccm_group_batched(
    libs: jax.Array,
    targets: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 0,
    k: int | None = None,
    impl: str = "auto",
    batch_libs: int | None = None,
    budget_mb: float | None = None,
) -> np.ndarray:
    """Library-batched CCM block → (Nl, Nt) ρ (host ndarray).

    The production all-pairs engine (ISSUE 5): the library axis is cut
    into ceil(Nl/B) batches of B series (``batch_libs``, or
    ``auto_batch_libs``'s memory-budget rule), each batch is ONE jitted
    launch of fused distance→top-k→weights→``lookup_rho`` over
    ``ops.all_knn_batch``, and launches are double-buffered against host
    assembly (``drive_batched``). Results are bit-invariant in B —
    ragged final batches are padded with real data and discarded — with
    the per-series oracle being the B = 1 run; the legacy ``lax.map``
    path (``ccm_group``) agrees exactly on neighbor indices/tie order
    and to ~1 ULP on ρ (bit-equal at most shapes; see kernels/ref.py for
    the XLA-CPU map-body caveat).
    """
    libs = jnp.asarray(libs)
    targets = jnp.asarray(targets)
    if targets.ndim == 1:
        targets = targets[None, :]
    Nl = libs.shape[0]
    Lp = num_embedded(libs.shape[-1], E, tau)
    if Nl == 0:  # empty library axis: empty matrix, like ccm_group
        return np.zeros((0, targets.shape[0]), np.float32)
    B = batch_libs if batch_libs is not None else auto_batch_libs(
        Lp, Nl, budget_mb)
    B = max(1, min(int(B), Nl))
    telemetry.gauge("edm_batch_libs_effective").set(B)
    kk = E + 1 if k is None else int(k)
    launch = make_group_launch(libs, targets, E=E, tau=tau, Tp=Tp, k=kk,
                               impl=impl)
    return drive_batched(Nl, B, launch)


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "impl"))
def ccm_group(
    libs: jax.Array,
    targets: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 0,
    impl: str = "auto",
) -> jax.Array:
    """Per-series CCM block: every library × every target at one E → (Nl, Nt).

    One jitted program drives the whole library axis with a sequential
    ``lax.map`` (one (Lp, Lp) distance matrix in flight — kEDM's
    per-library loop, minus the host round trip per library).

    .. deprecated:: kept as the legacy per-series reference; production
       callers (the session's ``xmap``, ``ccm_matrix``) use
       ``ccm_group_batched``, which batches the kNN axis B series per
       launch. Audit note (ROADMAP lax.map × XLA-CPU-TopK): beyond the
       TopK slowdown, XLA CPU also contracts the distance accumulation
       differently inside this ``lax.map`` body at some shapes (~1 ULP
       vs the identical standalone pipeline, e.g. Lp = 94), so this
       path is index-exact but not universally bit-equal to the engine.
    """
    L = libs.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    hard_max = Lp - 1 - max(Tp, 0)

    def one_library(x):
        D = ops.pairwise_distances(x, E=E, tau=tau, impl=impl)
        d, i = ops.topk_select(D, k=E + 1, exclude_self=True,
                               max_idx=hard_max, impl=impl)
        w = ops.make_weights(d)
        return ops.lookup_rho(targets, i[:rows], w[:rows], offset=off,
                              impl=impl)

    return jax.lax.map(one_library, libs)


def ccm_matrix(
    X: jax.Array,
    E_opt=None,
    *,
    tau: int = 1,
    Tp: int = 0,
    impl: str = "auto",
) -> np.ndarray:
    """All-pairs CCM skill matrix, shape (N_lib, N_target).

    Entry (l, t) = skill of cross-mapping series t from series l's manifold
    (evidence "t causes l"). Per kEDM §3.4: the library is embedded at each
    *target's* optimal E, targets grouped by E so each E-group is one
    batched launch over the full library axis.

    .. deprecated:: thin wrapper over ``repro.edm.EDM.xmap`` kept for
       compatibility — a session reuses its kNN master tables and E_opt
       across *every* method call instead of per ``ccm_matrix`` call;
       prefer it for anything beyond a one-shot matrix. ``E_opt=None``
       now computes the per-series optimal E through the session cache.
    """
    from repro.edm import EDM, EDMConfig

    X = jnp.asarray(X)
    if E_opt is not None:
        E_opt = np.asarray(E_opt, dtype=np.int32)
        if E_opt.shape != (X.shape[0],):
            raise ValueError(
                f"E_opt must be ({X.shape[0]},), got {E_opt.shape}")
    sess = EDM(X, EDMConfig(tau=tau, Tp_cross=Tp, impl=impl,
                            E_max=int(np.max(E_opt)) if E_opt is not None
                            else 20))
    return sess.xmap(method="simplex", E_opt=E_opt)
