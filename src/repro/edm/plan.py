"""Plan layer: what each session method will run, and the cached-table
drivers it dispatches to.

Every ``EDM`` method builds a ``Plan`` first — which kernels at which
implementation, local vs sharded placement, and which session-cached
state it can reuse — then executes it. The expensive shared state is the
**multi-E kNN master table**: one uncapped ``ops.all_knn_multi_e`` pass
per series (k_master = max needed k + slack columns) from which every
per-(E, Tp) neighbor table the session needs is derived *post hoc*,
bit-identically:

* neighbor **indices**: the master rows are globally sorted by
  (distance, index) — exactly ``lax.top_k``'s tie order — so filtering
  out entries past a ``max_idx`` horizon cap and keeping the first k is
  identical to running the capped top-k directly, as long as the master
  carries ``slack`` >= number of excluded candidates spare columns
  (one per horizon step).
* neighbor **distances**: two bit-exact sources, matched to what the
  legacy path being replaced used. The optimal-E sweep reads the master
  distances directly (same multi-E accumulator the legacy sweep ran);
  simplex/CCM lookups recompute just the k selected distances in the
  same accumulation order as ``ops.pairwise_distances`` — O(rows·k·E)
  instead of O(E·Lp²) — because the per-E pipeline's floats differ from
  the multi-E accumulator's by ~1 ULP (negated-accumulator streams fuse
  differently) and parity with the legacy free functions is bit-exact,
  not approximate.

Memory: a master table holds 2 · N · E_max · L · k_master values (f32 +
i32). That is the deliberate price of "compute neighbors once, reuse
everywhere" (kEDM §2.1); sessions on panels too big for it set
``cache=False`` or a mesh (sharded plans keep state device-resident).
The tables derived from it are built one E level at a time, so a
program reading it holds at most a few level-sized arrays besides it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core.embedding import embed_offset, num_embedded, pred_rows
from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.ref import PAD_IDX, strict_sq


@dataclasses.dataclass(frozen=True)
class Plan:
    """What a session method resolved to run (introspectable, hashable)."""

    task: str              # "optimal_E" | "simplex" | "smap" | "ccm" | "xmap"
    impl: str              # concrete kernel implementation (never "auto")
    placement: str         # "local" | "sharded"
    E: str                 # "fixed:<n>" | "per-series" | "sweep:1..<E_max>"
    Tp: int
    reuse: tuple[str, ...]  # session cache keys this plan reads
    builds: tuple[str, ...]  # session cache keys this plan populates
    detail: str = ""

    def describe(self) -> str:
        reuse = ", ".join(self.reuse) if self.reuse else "nothing"
        builds = ", ".join(self.builds) if self.builds else "nothing"
        return (f"{self.task}[{self.placement}/{self.impl}] E={self.E} "
                f"Tp={self.Tp} reuses {reuse}; builds {builds}"
                + (f" ({self.detail})" if self.detail else ""))


# ---------------------------------------------------------------- master


@functools.partial(jax.jit, static_argnames=("E_max", "tau", "k", "impl"))
def panel_master(X, *, E_max, tau, k, impl):
    """Uncapped multi-E kNN master tables for a whole (N, L) panel.

    One ``all_knn_multi_e`` pass per series (sequential ``lax.map``
    bounds peak memory at one series' accumulator) →
    (dists, idx), both (N, E_max, L, k).
    """

    def one(x):
        return ops.all_knn_multi_e(x, E_max=E_max, tau=tau, k=k,
                                   exclude_self=True, max_idx=None, impl=impl)

    return jax.lax.map(one, X)


def pad_master(dM, iM, capacity: int):
    """Master tables padded along their row axis to ``capacity`` rows.

    A capacity master holds each series' exact tables in its first L
    rows and inf / ``PAD_IDX`` below — what the tables hold past a
    level's valid rows anyway, so every consumer that slices the rows it
    needs (``[:Lp]``, ``[:rows]``) reads the same bits as from the exact
    tables.
    """
    extra = int(capacity) - int(dM.shape[2])
    if extra == 0:
        return dM, iM
    pad = ((0, 0), (0, 0), (0, extra), (0, 0))
    return (jnp.pad(dM, pad, constant_values=jnp.inf),
            jnp.pad(iM, pad, constant_values=PAD_IDX))


@functools.partial(jax.jit, static_argnames=("tau",))
def panel_master_state(X, dM, iM, *, tau):
    """A panel master's append state: (sq, idx), each (E_max, k, N, C).

    ``ref.append_state`` per series (sequential ``lax.map``, as in
    ``panel_master``): the stored candidates' squared distances and
    indices, the slots leading and the rows minor — the physical order
    of the master tables themselves, so the tables a tick returns are
    the state's transposes without a copy. Recomputing the squares
    gathers every stored candidate's lag terms, so a session computes
    the state once per master and the appends carry it forward
    (``panel_master_append_sq``).
    """
    sq, idx = jax.lax.map(lambda a: ref.append_state(*a, tau=tau),
                          (X, dM, iM))
    return jnp.moveaxis(sq, 0, 2), jnp.moveaxis(idx, 0, 2)


@functools.partial(jax.jit, static_argnames=("dt", "tau", "impl"))
def panel_master_append_sq(X, sq, idx, length, *, dt, tau, impl):
    """One append tick of a whole capacity panel → (sq, idx, dM, iM).

    ``X`` is the (N, C) panel buffer holding the grown series in
    [0, length + dt); ``sq``/``idx`` the master's append state, valid
    for ``length`` points (an operand): one program per
    (N, C, dt, E_max) serves every tick within the capacity. Returns
    the grown state and the master tables it gives, (N, E_max, C, k) —
    bit-identical to ``panel_master`` on the grown panel padded to C
    rows, at O(Lp·(k+Δt)) per level instead of O(Lp²). k_master is
    preserved, so the ``master_slack_covers`` slack rule carries over
    unchanged.
    """
    sq, idx = ops.master_append_sq(X, sq, idx, length=length, dt=dt,
                                   tau=tau, impl=impl)
    return (sq, idx, jnp.sqrt(jnp.maximum(sq, 0.0)).transpose(2, 0, 3, 1),
            idx.transpose(2, 0, 3, 1))


def panel_master_append(X, dM, iM, *, tau, impl):
    """Grow a whole panel's master tables to cover appended points.

    ``X`` is the grown (N, L_new) panel; ``dM``/``iM`` the stored
    ``panel_master`` tables of its (N, L_old) prefix. Computes the
    append state (``panel_master_state``) and runs one tick → (N, E_max,
    L_new, k) tables bit-identical to ``panel_master`` on the grown
    panel. A serving session keeps the state between ticks instead
    (``EDM.append``).
    """
    L_old = int(dM.shape[2])
    dM, iM = pad_master(dM, iM, X.shape[-1])
    sq, idx = panel_master_state(X, dM, iM, tau=tau)
    return panel_master_append_sq(X, sq, idx, L_old, dt=X.shape[-1] - L_old,
                                  tau=tau, impl=impl)[2:]


def _compact(valid, tables, fills, *, k):
    """The first k valid slots of each row, in slot order, by selection.

    ``valid`` (…, K) marks the slots to keep; ``tables`` are (…, K)
    arrays read at those slots; ``fills`` the value of an output slot
    that no valid slot reaches. Returns ([(…, k) tables], (…, k) mask).

    A valid slot's output position is the count of valid slots before
    it (a cumulative sum along the slot axis). Output slot j keeps, by
    ``jnp.where``, the one slot whose position is j and sets every other
    slot to the dtype's lowest value, so a max over the slots reads that
    one value out. Each output value is a copy of one input value (no
    arithmetic, so inf stays inf), and the slots keep their master
    order: exactly what a stable argsort of the invalid flags followed
    by a gather keeps (the form the tests keep as the oracle), without
    the sort or a gather along the slot axis.
    """
    csum = jnp.cumsum(valid, axis=-1, dtype=jnp.int32)
    pos = jnp.where(valid, csum - 1, k)  # k: no output slot
    slot = jnp.arange(k, dtype=jnp.int32)
    hit = pos[..., None, :] == slot[:, None]  # (…, k, K): one slot or none
    ok = slot < csum[..., -1:]
    outs = []
    for t, f in zip(tables, fills):
        low = (-jnp.inf if jnp.issubdtype(t.dtype, jnp.floating)
               else jnp.iinfo(t.dtype).min)
        pick = jnp.max(jnp.where(hit, t[..., None, :], low), axis=-1)
        outs.append(jnp.where(ok, pick, jnp.asarray(f, t.dtype)))
    return outs, ok


def _derive_idx(iE, *, k, max_idx):
    """First k master indices surviving a ``max_idx`` cap (master order).

    iE: master index level rows, (…, rows, k_master) — one series or a
    (B, rows, k_master) batch; every op is row-independent, so the
    batched call equals the per-series calls bit-for-bit. Returns
    ((…, rows, k) idx with -1 in slots lacking a valid candidate,
    validity mask) — index-identical to a capped ``topk_select``. The
    cap may be traced (a capacity panel's valid length). ``_compact``
    keeps the valid slots in master order, as a stable sort of the
    invalid flags would, so the result is that sort's, bit for bit.
    """
    valid = (iE >= 0) & (iE <= max_idx)
    (i,), ok = _compact(valid, (iE,), (-1,), k=k)
    return i, ok


def _derive(dE, iE, *, k, max_idx):
    """Like ``_derive_idx`` but also carrying the master distances —
    bit-identical to a capped ``topk_select`` (see module docstring);
    slots lacking a valid candidate read inf."""
    valid = (iE >= 0) & (iE <= max_idx)
    (d, i), _ = _compact(valid, (dE, iE), (jnp.inf, -1), k=k)
    return d, i


def _gathered_dists(x, idx, ok, *, E, tau):
    """Euclidean distances of the selected neighbor pairs only.

    Same accumulation order as ``ops.pairwise_distances`` (acc += d²
    per lag k), so the values are bit-identical to the per-E pipeline's
    at O(rows·k·E) instead of O(E·Lp²). Invalid slots → inf.
    """
    Lp = num_embedded(x.shape[-1], E, tau)
    rows = idx.shape[0]
    ii = jnp.arange(rows, dtype=jnp.int32)[:, None]
    jj = jnp.maximum(idx, 0)
    acc = jnp.zeros(idx.shape, jnp.float32)
    xf = x.astype(jnp.float32)
    for lag in range(E):
        xk = jax.lax.dynamic_slice_in_dim(xf, lag * tau, Lp, axis=-1)
        d = xk[ii] - xk[jj]
        acc = acc + strict_sq(d)
    return jnp.where(ok, jnp.sqrt(jnp.maximum(acc, 0.0)), jnp.inf)


# ---------------------------------------------------- cached-table drivers


@functools.partial(jax.jit,
                   static_argnames=("E_max", "tau", "Tp", "impl"))
def rho_curves_from_master(X, dM, iM, *, E_max, tau, Tp, impl):
    """ρ(E) for every series from the master tables → (N, E_max).

    Reads the master's own distances (the legacy sweep ran the same
    multi-E accumulator, so this is bit-identical to
    ``core.simplex.rho_curve``) and derives each level's Tp-capped
    table post hoc instead of re-running the engine.

    A static loop over the E levels, each over all N series at once:
    level E derives its (N, rows, E + 1) tables from the master level
    (N, rows, k_master) by ``_derive``, then runs the weights and each
    series' self-lookup as a ``lax.map`` over the series, the same
    per-series ``ops.lookup_rho`` as the legacy sweep (a ``jax.vmap`` of
    the kernel would turn its flat SMEM tables into 2-D blocks, which
    Mosaic refuses). The program's intermediates are a few level-sized
    arrays, bounded by a small multiple of one master level (1/E_max of
    the master), never of the whole master.
    """
    L = X.shape[-1]
    rhos = []
    for E in range(1, E_max + 1):
        rows = pred_rows(L, E, tau, Tp)
        mx = num_embedded(L, E, tau) - 1 - Tp
        off = embed_offset(E, tau, Tp)
        dk, ik = _derive(dM[:, E - 1, :rows], iM[:, E - 1, :rows],
                         k=E + 1, max_idx=mx)

        def self_rho(args, off=off):
            x, d, i = args
            return ops.lookup_rho(x[None, :], i, ops.make_weights(d),
                                  offset=off, impl=impl)[0]

        rhos.append(jax.lax.map(self_rho, (X, dk, ik)))
    return jnp.stack(rhos, axis=1)


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "k", "impl"))
def simplex_skill_from_master(X, iM_E, *, E, tau, Tp, k, impl):
    """Leave-one-out simplex skill per series from cached indices → (N,).

    iM_E: (N, L, k_master) master index level E. Bit-identical to
    ``core.simplex.simplex_skill`` per series (indices derived, selected
    distances recomputed in pairwise order).
    """
    L = X.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)

    def one(args):
        x, iE = args
        ik, ok = _derive_idx(iE[:Lp], k=k, max_idx=Lp - 1 - Tp)
        d = _gathered_dists(x, ik, ok, E=E, tau=tau)
        w = ops.make_weights(d)
        return ops.lookup_rho(x[None, :], ik[:rows], w[:rows], offset=off,
                              impl=impl)[0]

    return jax.lax.map(one, (X, iM_E))


def master_slack_covers(caps, *, Lp: int, k: int, k_master: int) -> bool:
    """The k_master-slack rule for post-hoc library caps (ROADMAP (c)).

    Deriving a capped neighbor table from the uncapped master keeps the
    first k master entries with index <= cap. That equals the true
    capped top-k iff the master still *contains* k valid entries in the
    worst case: a cap at index m excludes the ``Lp − 1 − m`` columns
    beyond it, and all of them may outrank every valid candidate, so
    the master must carry ``k_master >= k + (Lp − 1 − min(caps))``
    columns. Large (near-full-library) convergence sizes satisfy this
    with the session's default slack; small sizes fall back to the
    one-pass multi-cap engine (``core.ccm.ccm_convergence``) — never to
    a per-size re-scan loop.
    """
    return k_master >= k + (Lp - 1 - min(caps))


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "caps", "k",
                                             "impl"))
def ccm_convergence_from_master(x, iM_E, targets, *, E, tau, Tp, caps, k,
                                impl):
    """Convergence curve grid from cached master indices → (|caps|, N).

    The cached-session counterpart of ``core.ccm.ccm_convergence``: each
    library-prefix cap's neighbor table is derived post hoc from ONE
    master index level (callers must check ``master_slack_covers``
    first), and only the k selected distances are recomputed — no
    pairwise pass, no top-k, bit-identical ρ to the legacy per-size
    sweep (see module docstring).
    """
    L = x.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    iE = iM_E[:Lp]
    curves = []
    for m in caps:  # static, small: unrolled per-cap derivations
        ik, ok = _derive_idx(iE, k=k, max_idx=m)
        d = _gathered_dists(x, ik, ok, E=E, tau=tau)
        w = ops.make_weights(d)
        curves.append(ops.lookup_rho(targets, ik[:rows], w[:rows],
                                     offset=off, impl=impl))
    return jnp.stack(curves)


def _gathered_dists_batch(X, idx, ok, *, E, tau):
    """Batched ``_gathered_dists``: selected-pair distances for B series.

    Same per-lag accumulation order on the gathered values; gathers are
    exact, so only the (B, rows, k)-shaped f32 chain is rounding-
    sensitive (bit-invariant in B in practice — the k axis, not the
    batch axis, is minor).
    """
    Lp = num_embedded(X.shape[-1], E, tau)
    B, rows, k = idx.shape
    jj = jnp.maximum(idx, 0).reshape(B, rows * k)
    acc = jnp.zeros(idx.shape, jnp.float32)
    xf = X.astype(jnp.float32)
    for lag in range(E):
        xk = jax.lax.dynamic_slice_in_dim(xf, lag * tau, Lp, axis=-1)
        d = (xk[:, :rows, None]
             - jnp.take_along_axis(xk, jj, axis=-1).reshape(B, rows, k))
        acc = acc + strict_sq(d)
    return jnp.where(ok, jnp.sqrt(jnp.maximum(acc, 0.0)), jnp.inf)


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "k", "impl"))
def _master_group_step(Xb, iMb, targets, length=None, *, E, tau, Tp, k,
                       impl):
    """One master-derived engine launch: (B, Nt) ρ for B libraries.

    The cached-session twin of ``core.ccm._group_step``: neighbor
    indices come from the batched stable filter over the master levels
    (zero kNN work), the k selected distances are recomputed in pairwise
    accumulation order, and weights + fused-ρ lookups run as per-series
    ``lax.map`` sub-steps (per-series shapes ⇒ bit-invariant in B).

    ``length`` (an operand) is the valid length of a capacity panel,
    whose series and master span C ≥ L: neighbour indices are capped at
    Lp − 1 − Tp of the valid length and the Pearson sums run over its
    first Lp − Tp rows only, so one program per (C, E, B) serves every
    L ≤ C. The masked sums round in another order than an exact-shape
    program's, so the two agree to float32 rounding.
    """
    from repro.core.ccm import post_lookup_rho

    L = Xb.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    live = None
    if length is None:
        hard_max = Lp - 1 - max(Tp, 0)
    else:
        Lp_live = length - (E - 1) * tau
        hard_max = Lp_live - 1 - max(Tp, 0)
        live = Lp_live - max(Tp, 0)
    ik, ok = _derive_idx(iMb[:, :Lp], k=k, max_idx=hard_max)
    d = _gathered_dists_batch(Xb, ik, ok, E=E, tau=tau)
    return post_lookup_rho(targets, d, ik, rows=rows, off=off, impl=impl,
                           live_rows=live)


def make_master_group_launch(X, iM_E, targets, *, E, tau, Tp, k, impl):
    """Launch closure of the master-derived engine: ``launch(a, b, B)``.

    The cached-master twin of ``core.ccm.make_group_launch``, factored
    out for the fault-tolerant driver (``repro.edm.runner``) — bit-
    invariance in B makes the closure re-drivable at any batch size
    after an OOM backoff or a resume.
    """
    from repro.core.ccm import pad_batch

    impl_r = ops.resolve_impl(impl)
    master_launches = telemetry.counter("edm_master_launches")

    def launch(a, b, B):
        master_launches.inc()
        return _master_group_step(
            pad_batch(X[a:b], B), pad_batch(iM_E[a:b], B), targets, E=E,
            tau=tau, Tp=Tp, k=k, impl=impl_r)

    return launch


def master_group_batch_bytes(Lp: int, k_master: int) -> int:
    """Per-series in-flight bytes of one master-derived launch.

    ~4 live (B, Lp, k_master)-sized 4-byte buffers per launch (the
    derivation's slot positions and validity, the derived indices, the
    recomputed distances) — the footprint ``auto_batch_libs`` should
    size against for this engine (NOT the direct engine's (B, Lp, Lp)
    distance stack, which derivation never holds). The compaction that
    replaced a sort holds positions where the sort held its keys and
    order, so the count is unchanged.
    """
    return 16 * Lp * int(k_master)


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "k", "impl"))
def _ccm_batch_step(P, iM, libs, length, *, E, tau, Tp, k, impl):
    """One ``EDM.ccm_batch`` launch: (B, N) ρ for the libraries ``libs``.

    ``P`` is the (N, C) panel buffer, ``iM`` the (N, E_levels, C,
    k_master) master, ``length`` the valid L: the libraries' rows are
    gathered inside the program, which is ``_master_group_step`` against
    every series, one program per (C, E, B).
    """
    Lp = num_embedded(P.shape[-1], E, tau)  # gather the rows it reads
    return _master_group_step(P[libs], iM[libs, E - 1, :Lp], P, length,
                              E=E, tau=tau, Tp=Tp, k=k, impl=impl)


def ccm_batch_from_master(P, iM, libs, length, *, E, tau, Tp, k, impl,
                          budget_mb=None) -> "np.ndarray":
    """(len(libs), N) ρ of the libraries ``libs`` against every series.

    The ``EDM.ccm_batch`` engine on a capacity panel: ``P`` (N, C), the
    master ``iM`` at the same capacity, ``length`` the valid L. B is
    sized against the engine's in-flight footprint
    (``master_group_batch_bytes``; a coalesced batch is normally one
    launch of B = len(libs)); each launch is one ``_ccm_batch_step``
    through ``drive_batched``, so the launch and dispatch counters and
    spans are those of every engine.
    """
    from repro.core.ccm import auto_batch_libs, drive_batched

    import numpy as np

    libs = np.asarray(libs, np.int32)
    Nl = len(libs)
    Lp = num_embedded(P.shape[-1], E, tau)
    B = auto_batch_libs(Lp, Nl, budget_mb,
                        per_series_bytes=master_group_batch_bytes(
                            Lp, iM.shape[-1]))
    B = max(1, min(int(B), Nl))
    telemetry.gauge("edm_batch_libs_effective").set(B)
    impl_r = ops.resolve_impl(impl)
    master_launches = telemetry.counter("edm_master_launches")
    L = np.int32(length)

    def launch(a, b, B):
        master_launches.inc()
        part = libs[a:b]
        part = np.concatenate([part, np.repeat(part[-1:], B - len(part))])
        return _ccm_batch_step(P, iM, part, L, E=E, tau=tau, Tp=Tp, k=k,
                               impl=impl_r)

    return drive_batched(Nl, B, launch)


@functools.partial(jax.jit, static_argnames=("E", "tau", "Tp", "k", "impl"))
def ccm_group_from_master(X, iM_E, targets, *, E, tau, Tp, k, impl):
    """Per-series CCM block from cached neighbor indices → (N_lib, N_tgt).

    The cached-session counterpart of ``core.ccm.ccm_group``: instead of
    one O(E·Lp²) pairwise + top-k pipeline per library, each library's
    neighbors are derived from its master index level (iM_E, (N, L,
    k_master)) and only the k selected distances are recomputed —
    bit-identical output (see module docstring). Kept as the legacy
    per-series reference; the session dispatches the batched engine
    (``make_master_group_launch``, ``ccm_batch_from_master``).
    """
    L = X.shape[-1]
    Lp = num_embedded(L, E, tau)
    rows = pred_rows(L, E, tau, Tp)
    off = embed_offset(E, tau, Tp)
    hard_max = Lp - 1 - max(Tp, 0)

    def one_library(args):
        x, iE = args
        ik, ok = _derive_idx(iE[:Lp], k=k, max_idx=hard_max)
        d = _gathered_dists(x, ik, ok, E=E, tau=tau)
        w = ops.make_weights(d)
        return ops.lookup_rho(targets, ik[:rows], w[:rows], offset=off,
                              impl=impl)

    return jax.lax.map(one_library, (X, iM_E))
