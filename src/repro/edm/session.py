"""The ``EDM`` session — one facade over the whole EDM toolkit.

kEDM's design win is a small user-facing API (``simplex``, ``smap``,
``xmap``) over a single dispatching codebase; this session object is that
facade for the reproduction. Bind a panel and a config once::

    sess = EDM(panel, EDMConfig(E_max=8, tau=2))
    E_opt, rho = sess.optimal_E()      # one multi-E kNN pass, cached
    skill = sess.simplex()             # free: read from the cached sweep
    causal = sess.xmap()               # reuses the SAME kNN master tables
    theta_curves = sess.smap()         # batched S-Map nonlinearity test
    curve = sess.ccm(0, 1, lib_sizes=(50, 200, 500))  # convergence sweep
    sig = sess.surrogate_test(0, 1)    # CCM significance vs a null ensemble

Every method builds a ``Plan`` (``sess.plan(task)`` shows it) choosing
kernels, implementation and local-vs-sharded placement once, then
executes it. The multi-E kNN master tables built by ``optimal_E`` are
held in the session and reused by ``simplex``/``xmap`` instead of being
recomputed per call site; a ``mesh=`` in the config transparently routes
plans through the zero-collective sharded engines in
``repro.distributed.sharded_ccm``.

Implementation pinning: the session resolves ``config.impl`` once at
bind time (``ops.resolve_impl``) and passes the concrete name into every
kernel call — the reliable form of ``ops.use_impl``'s scoped default,
which cannot retroactively re-key already-traced jitted programs (see
its docstring's caveat).
"""

from __future__ import annotations

import collections
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.edm.config import EDMConfig
from repro.edm.dataset import Dataset
from repro.edm.plan import (
    Plan,
    ccm_batch_from_master,
    ccm_convergence_from_master,
    master_slack_covers,
    pad_master,
    panel_master,
    panel_master_append_sq,
    panel_master_state,
    rho_curves_from_master,
    simplex_skill_from_master,
)
from repro.edm.surrogates import make_surrogates
from repro.core.embedding import num_embedded
from repro.kernels import ops


def _e_groups(E_opt, N: int):
    """Per-series E table → {E: member indices}, kEDM §3.4's grouping."""
    E_opt = np.broadcast_to(np.asarray(E_opt, np.int32), (N,)).copy()
    return E_opt, {
        int(E): np.nonzero(E_opt == E)[0]
        for E in sorted(collections.Counter(E_opt.tolist()))
    }


@dataclasses.dataclass
class SurrogateResult:
    """Outcome of one ``EDM.surrogate_test``: score, null ensemble, p."""

    rho: float | np.ndarray            # actual skill ((S,) with lib_sizes)
    surrogate_rho: np.ndarray          # (M,) or (S, M) null ensemble skills
    pvalue: float | np.ndarray         # rank-based, (1 + #{null ≥ ρ})/(1 + M)
    method: str
    num_surrogates: int

    @property
    def significant(self) -> bool | np.ndarray:
        """p < 0.05 (per size when a convergence sweep was run)."""
        return self.pvalue < 0.05


@dataclasses.dataclass
class PanelResult:
    """Results of one queued ``submit_panel`` ticket."""

    E_opt: np.ndarray | None = None
    rho: np.ndarray | None = None          # (N, E_max) optimal-E curves
    smap: np.ndarray | None = None         # (N, |thetas|) θ-sweep skill
    xmap: np.ndarray | None = None         # (N, N) cross-map matrix


class EDM:
    """Session facade: shared kNN/embedding state + plan-based dispatch."""

    def __init__(self, data, config: EDMConfig | None = None, **overrides):
        """Bind ``data`` (a ``Dataset`` or an (N, L) array) under
        ``config`` (or ``EDMConfig(**overrides)``).

        The panel and its kNN master are held at the dataset's capacity
        C ≥ L, with L an operand of the append and ``ccm_batch``
        programs. A panel starts exact (C = L); its first append sizes C
        with room to grow (``dataset.grown_capacity``), so later appends
        and ``ccm_batch`` compile nothing until L passes C, when the
        panel regrows — one recompile of each program, counted in
        ``edm_capacity_regrows``.
        """
        if config is None:
            config = EDMConfig(**overrides)
        elif overrides:
            config = config.replace(**overrides)
        self.data = data if isinstance(data, Dataset) else Dataset(
            data, on_invalid=config.on_invalid)
        self.config = config
        config.validate_panel(self.data.N, self.data.L)
        self._impl = ops.resolve_impl(config.impl)
        self._cache: dict[str, object] = {}
        self.stats: collections.Counter = collections.Counter()
        self._queue: list[tuple[int, jnp.ndarray, tuple[str, ...]]] = []
        self._next_ticket = 0

    def _bump(self, key: str, n: int = 1) -> None:
        """Session cache/run statistic: the in-session ``stats`` Counter
        AND the process-wide telemetry counter (``edm_<key>``) — the
        latter is the supported observation API
        (``telemetry.Recorder.counter_delta``)."""
        self.stats[key] += n
        telemetry.counter(f"edm_{key}").inc(n)

    def _plan_event(self, task: str) -> None:
        """Emit the resolved Plan as a ``plan.execute`` event (sinks
        only — ``plan()`` itself is too costly for the disabled path)."""
        if telemetry.active():
            telemetry.event("plan.execute", task=task,
                            plan=self.plan(task).describe())

    # ---------------------------------------------------- validity masking
    #
    # A Dataset bound with on_invalid="mask" keeps invalid series in the
    # panel (zeroed so kernels never see NaN) and the session NaN-flags
    # every output that touches one: per-series rows, matrix rows AND
    # columns, pairwise results. Clean panels (valid all-True) pay
    # nothing — every helper is a no-op returning its input unchanged.

    @property
    def _invalid(self):
        """Indices of masked-invalid series, or None for clean panels."""
        if self.data.num_invalid == 0:
            return None
        return np.nonzero(~self.data.valid)[0]

    def _mask_rows(self, out: np.ndarray) -> np.ndarray:
        """NaN the rows of a per-series output at invalid series."""
        bad = self._invalid
        if bad is not None:
            out = np.array(out, np.float32)
            out[bad] = np.nan
        return out

    def _mask_matrix(self, rho: np.ndarray) -> np.ndarray:
        """NaN the rows and columns of an (N, N) matrix at invalid series
        (applied at delivery — a journaled run's checkpoints hold the
        raw computed tiles, the mask is a view-level policy)."""
        bad = self._invalid
        if bad is not None:
            rho = np.array(rho, np.float32)
            rho[bad, :] = np.nan
            rho[:, bad] = np.nan
        return rho

    def _pair_invalid(self, *indices) -> bool:
        return any(not self.data.is_valid(i) for i in indices)

    # ------------------------------------------------------------- plans

    def plan(self, task: str, *, E=None) -> Plan:
        """The Plan a method call would execute (introspection)."""
        c = self.config
        sharded = c.mesh is not None
        placement = "sharded" if sharded else "local"
        cached = c.cache and not sharded
        have_master = "master" in self._cache
        have_rho = "rho" in self._cache
        if task == "optimal_E":
            return Plan(
                task=task, impl=self._impl, placement=placement,
                E=f"sweep:1..{c.E_max}", Tp=c.Tp,
                reuse=(("rho",) if have_rho else
                       ("master",) if (cached and have_master) else ()),
                builds=() if have_rho else (
                    ("master", "rho") if cached else ("rho",)),
                detail="sharded_optimal_E" if sharded else (
                    "derive per-E tables from kNN master" if cached
                    else "legacy optimal_E_batch"),
            )
        if task == "simplex":
            e_desc = (f"fixed:{E or c.E}" if (E or c.E) else "per-series")
            return Plan(
                task=task, impl=self._impl, placement="local",
                E=e_desc, Tp=c.Tp,
                reuse=(("master",) if (cached and (E or c.E)) else ("rho",)),
                builds=(),
                detail=("skill read off the cached ρ(E) sweep"
                        if not (E or c.E) else
                        "indices from kNN master, k distances recomputed"
                        if cached else "legacy per-series simplex_skill"),
            )
        if task == "smap":
            e_desc = f"fixed:{E or c.E}" if (E or c.E) else "per-series"
            return Plan(
                task=task, impl=self._impl, placement=placement,
                E=e_desc, Tp=c.Tp,
                reuse=() if (E or c.E) else ("rho",),
                builds=(),
                detail="sharded_smap_theta per E-group" if sharded
                else "batched Gram engine per E-group",
            )
        if task == "ccm":
            return Plan(
                task=task, impl=self._impl, placement="local",
                E=f"fixed:{E or c.E}" if (E or c.E) else "per-series",
                Tp=c.Tp_cross,
                reuse=(("master",) if (cached and have_master) else ())
                + (() if (E or c.E) else ("rho",)), builds=(),
                detail="sweep: capped tables from kNN master when "
                       "k_master slack covers, else one-pass multi-cap "
                       "convergence engine",
            )
        if task == "xmap":
            # Coverage for the DEFAULT call (E_opt=None): fixed E, else
            # the cached optimal-E table (which _rho would build —
            # together with the master — before the matrix runs anyway).
            # An explicit deeper `E_opt=` argument can still fall back
            # to the direct engine at execution time.
            hit = self._cache.get("master")
            levels = (c.E if c.E else
                      int(self._cache["rho"][0].max()) if have_rho
                      else c.E_max)
            covered = hit is not None and hit[3] >= levels
            master_next = cached and (
                covered or self.stats["xmap_direct_runs"] > 0
                or not (c.E or have_rho))
            return Plan(
                task=task, impl=self._impl, placement=placement,
                E=f"fixed:{c.E}" if c.E else "per-series", Tp=c.Tp_cross,
                reuse=(("master",) if (cached and covered) else ()) + (
                    () if c.E else ("rho",)),
                builds=(("master",) if (master_next and not covered)
                        else ()) + (() if (c.E or have_rho) else ("rho",)),
                detail="E-grouped sharded matrix, zero collectives"
                if sharded else (
                    "library-batched lookups on cached kNN master"
                    if master_next
                    else "library-batched direct engine, ceil(N/B) "
                         "launches per E-group"),
            )
        raise ValueError(f"unknown task {task!r}")

    # ------------------------------------------------------------ caches

    def _master(self, E_levels: int):
        """Multi-E kNN master tables covering levels 1..E_levels.

        Returns (dists, idx, k_master, levels). Built lazily at the
        highest level any method has needed so far: a fixed-E session
        never pays for (or crashes on) a full E_max sweep it will not
        use, and a later, deeper request rebuilds once and re-caches —
        reusing a master below the requested level would silently gather
        the wrong table (jnp clamps out-of-range indices).
        """
        c = self.config
        hit = self._cache.get("master")
        if hit is not None and hit[3] >= E_levels:
            self._bump("knn_master_hits")
            return hit
        k_m = max(E_levels + 1, c.k or 0) + c.slack
        self._cache.pop("append_state", None)  # the old master's
        with telemetry.span("session.master_build", E_levels=E_levels,
                            k_master=k_m, N=self.data.N):
            dM, iM = pad_master(*panel_master(
                self.data.panel, E_max=E_levels, tau=c.tau, k=k_m,
                impl=self._impl), self.data.capacity)
        self._bump("knn_master_builds")
        hit = self._cache["master"] = (dM, iM, k_m, E_levels)
        return hit

    def master_nbytes(self) -> int:
        """Resident bytes of the cached multi-E kNN master (0 if none).

        The serving LRU's accounting unit: the master is the session's
        only O(N·E·Lp·k) cache (distances + indices, and on a panel that
        appends the append state the ticks carry), everything else held
        here is O(N·E_max) or smaller.
        """
        hit = self._cache.get("master")
        if hit is None:
            return 0
        state = self._cache.get("append_state", ())
        return sum(int(a.nbytes) for a in (*hit[:2], *state))

    def evict_master(self) -> int:
        """Drop the cached kNN master; returns the bytes freed.

        Purely a memory event: the next method that needs the master
        lazily rebuilds it from the *current* panel (``_master``), and
        the incremental-append contract (append ≡ cold rebuild, bit
        identical) makes every later answer — and every later append —
        bit-identical to a never-evicted session. The serving layer's
        LRU byte budget calls this on cold panels.
        """
        freed = self.master_nbytes()
        if freed:
            self._cache.pop("master", None)
            self._cache.pop("append_state", None)
            self._bump("knn_master_evictions")
        return freed

    def append(self, delta) -> list[dict]:
        """Grow the bound panel by Δt points, updating caches in place.

        The serving tick primitive: screening covers only the new
        columns (``Dataset.append``), and a cached kNN master is grown
        by ``panel_master_append_sq`` — O(Lp·Δt) stream-in/merge per
        series, bit-identical to the cold O(Lp²) rebuild — so a warm
        session absorbs a tick without repaying its build. The merge
        runs on the master's append state (its stored candidates'
        squared distances and indices), which the first append after a
        build or a regrow computes once (``panel_master_state``) and
        every append then carries forward. Within the panel's capacity
        the tick writes into the held buffers and compiles nothing; an
        append that passes it regrows the panel and master (one
        recompile, counted in ``edm_capacity_regrows`` and logged as a
        ``session.capacity_regrow`` event) — which the first append of
        a panel always does, since a panel starts exact. Derived caches
        that summarize the whole panel (the optimal-E rho curves) are
        invalidated; the master survives. Under ``on_invalid="drop"``
        the master rows of dropped series are compacted to match the
        panel. Returns ``Dataset.append``'s records of series this delta
        invalidated (pre-append indices).
        """
        c = self.config
        old_N, old_L = self.data.N, self.data.L
        old_C = self.data.capacity
        with telemetry.span("session.append", N=old_N):
            records = self.data.append(delta)  # raises before mutating
            C = self.data.capacity
            if C != old_C:
                self._bump("capacity_regrows")
                telemetry.event("session.capacity_regrow", capacity_was=old_C,
                                capacity=C, L=self.data.L)
            self._cache.pop("rho", None)
            hit = self._cache.get("master")
            state = self._cache.pop("append_state", None)
            if hit is not None and c.cache:
                dM, iM, k_m, lv = hit
                if len(records) and self.data.N != old_N:  # drop compaction
                    keep = np.setdiff1d(
                        np.arange(old_N), [r["index"] for r in records])
                    dM, iM, state = dM[keep], iM[keep], None
                if C != old_C:
                    dM, iM, state = *pad_master(dM, iM, C), None
                if state is None:  # once per master and capacity
                    with telemetry.span("session.append_state",
                                        N=self.data.N):
                        state = panel_master_state(self.data.buffer, dM, iM,
                                                   tau=c.tau)
                dt = self.data.L - old_L
                with telemetry.span("session.master_append", dt=dt,
                                    E_levels=lv, N=self.data.N, capacity=C):
                    *state, dM, iM = panel_master_append_sq(
                        self.data.buffer, *state, np.int32(old_L), dt=dt,
                        tau=c.tau, impl=self._impl)
                self._cache["master"] = (dM, iM, k_m, lv)
                self._cache["append_state"] = tuple(state)
                self._bump("knn_master_appends")
            else:
                self._cache.pop("master", None)
            self._bump("appends")
        return records

    def _rho(self):
        """Cached (E_opt, rho-curve) pair, computing it on first use."""
        hit = self._cache.get("rho")
        if hit is None:
            hit = self._cache["rho"] = self._run_optimal_E()
        else:
            self._bump("rho_hits")
        return hit

    # ---------------------------------------------------------- optimal E

    def _run_optimal_E(self) -> tuple[np.ndarray, np.ndarray]:
        c = self.config
        X = self.data.panel
        if c.mesh is not None:
            from repro.distributed.sharded_ccm import (
                pad_to_multiple, sharded_optimal_E)
            size = c.mesh_axis_size(c.lib_axes)
            Xp = pad_to_multiple(X, size, axis=0)
            E_opt, rho = sharded_optimal_E(
                Xp, E_max=c.E_max, tau=c.tau, Tp=c.Tp, mesh=c.mesh,
                axes=c.lib_axes, impl=self._impl)
            E_opt = np.asarray(E_opt)[: self.data.N]
            rho = np.asarray(rho)[: self.data.N]
        elif c.cache:
            dM, iM, _, lv = self._master(c.E_max)
            rho = np.asarray(rho_curves_from_master(
                X, dM[:, :c.E_max], iM[:, :c.E_max], E_max=c.E_max,
                tau=c.tau, Tp=c.Tp, impl=self._impl))
            E_opt = (np.argmax(rho, axis=1) + 1).astype(np.int32)
        else:
            from repro.core.simplex import optimal_E_batch
            E_opt, rho = optimal_E_batch(
                X, E_max=c.E_max, tau=c.tau, Tp=c.Tp, impl=self._impl)
            E_opt, rho = np.asarray(E_opt), np.asarray(rho)
        bad = self._invalid
        if bad is not None:
            # Masked-invalid series: pin E to 1 (a deterministic group —
            # the zeroed data's argmax is meaningless) and NaN the ρ(E)
            # curve so everything read off the cache inherits the flag.
            E_opt = E_opt.copy()
            E_opt[bad] = 1
            rho = np.array(rho, np.float32)
            rho[bad] = np.nan
        return E_opt, rho

    def optimal_E(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-series optimal embedding dimension and the full ρ(E) sweep.

        Returns (E_opt (N,) int32, rho (N, E_max)). Cached: later
        ``simplex``/``smap``/``ccm``/``xmap`` calls reuse both the result
        and (locally) the kNN master tables built here.
        """
        with telemetry.span("session.optimal_E", E_max=self.config.E_max,
                            N=self.data.N):
            self._plan_event("optimal_E")
            E_opt, rho = self._rho()
        return E_opt.copy(), rho.copy()

    # ------------------------------------------------------------ simplex

    def simplex(self, E: int | None = None) -> np.ndarray:
        """Leave-one-out simplex forecast skill per series → (N,) ρ.

        ``E=None`` with a per-series config reads the skill straight off
        the cached optimal-E sweep (no compute); a fixed E reuses the
        cached kNN master (indices derived, k distances recomputed).
        """
        c = self.config
        E = E if E is not None else c.E
        with telemetry.span("session.simplex", N=self.data.N,
                            E=E or "per-series"):
            if E is None:
                E_opt, rho = self._rho()
                return rho[np.arange(self.data.N), E_opt - 1].copy()
            if c.cache and c.mesh is None:
                _, iM, _, _ = self._master(E)
                return self._mask_rows(np.asarray(
                    simplex_skill_from_master(
                        self.data.panel, iM[:, E - 1], E=E, tau=c.tau,
                        Tp=c.Tp, k=c.k_for(E), impl=self._impl)))
            from repro.core.simplex import simplex_skill
            return self._mask_rows(np.asarray([
                simplex_skill(x, E=E, tau=c.tau, Tp=c.Tp, impl=self._impl)
                for x in self.data.panel]))

    # -------------------------------------------------------------- smap

    def smap(self, E: int | None = None, thetas=None) -> np.ndarray:
        """S-Map θ-sweep (nonlinearity test) per series → (N, |θ|) ρ.

        Per-series E (the default) groups series by their cached optimal
        E so each group is ONE batched Gram-engine launch; a mesh routes
        each group through ``sharded_smap_theta`` (zero collectives).
        """
        c = self.config
        thetas = c.thetas if thetas is None else tuple(
            float(t) for t in thetas)
        E = E if E is not None else c.E
        with telemetry.span("session.smap", N=self.data.N,
                            E=E or "per-series", thetas=len(thetas)):
            if E is not None:
                groups = {int(E): np.arange(self.data.N)}
            else:
                E_opt, _ = self._rho()
                _, groups = _e_groups(E_opt, self.data.N)
            out = np.zeros((self.data.N, len(thetas)), np.float32)
            for Eg, members in groups.items():
                out[members] = self._smap_group_sweep(Eg, members, thetas)
            return self._mask_rows(out)

    def _smap_group_sweep(self, E, members, thetas) -> np.ndarray:
        c = self.config
        X = self.data.panel[np.asarray(members)]
        if c.mesh is not None:
            from repro.distributed.sharded_ccm import (
                pad_members, sharded_smap_theta)
            size = c.mesh_axis_size(c.lib_axes)
            padded = pad_members(np.arange(len(members)), size)
            rho = sharded_smap_theta(
                X[padded], E=E, tau=c.tau, Tp=c.Tp, thetas=thetas,
                ridge=c.ridge, mesh=c.mesh, axes=c.lib_axes,
                impl=self._impl)
            return np.asarray(rho)[: len(members)]
        from repro.core.smap_engine import smap_theta_sweep
        return np.asarray(smap_theta_sweep(
            X, E=E, tau=c.tau, Tp=c.Tp, thetas=thetas, ridge=c.ridge,
            impl=self._impl))

    # --------------------------------------------------------------- ccm

    def _resolve_pair_E(self, target_index: int, E: int | None) -> int:
        """E for a pairwise call: arg > config > target's cached optimum."""
        if E is None:
            E = self.config.E
        if E is None:
            E_opt, _ = self._rho()
            E = int(E_opt[target_index])
        return int(E)

    def ccm(self, lib, target, *, lib_sizes=None,
            E: int | None = None) -> np.ndarray:
        """Convergence cross-mapping between two panel series.

        Embeds series ``lib``'s manifold and cross-maps ``target`` (high
        skill = evidence "target causes lib"). ``lib_sizes`` returns the
        convergence curve — ρ rising with library size is CCM's causality
        criterion. E defaults to the *target's* cached optimal E (kEDM
        §3.4's convention).

        A sweep never re-scans per size: when the cached kNN master's
        slack covers every cap (``master_slack_covers``) the per-size
        tables are derived from it with zero additional kNN work,
        otherwise ONE multi-cap convergence-engine pass handles all
        sizes. Both are bit-identical to the legacy per-size loop.
        """
        c = self.config
        li = self.data.index_of(lib)
        ti = self.data.index_of(target)
        if self._pair_invalid(li, ti):  # masked series: NaN, no engine run
            if lib_sizes is None:
                return np.float32(np.nan)
            return np.full(len(tuple(lib_sizes)), np.nan, np.float32)
        E = self._resolve_pair_E(ti, E)
        with telemetry.span("session.ccm", lib=li, target=ti, E=E,
                            sweep=lib_sizes is not None):
            self._plan_event("ccm")
            return self._ccm_pair(li, ti, E, lib_sizes)

    def _ccm_pair(self, li, ti, E, lib_sizes) -> np.ndarray:
        c = self.config
        if lib_sizes is None:
            # Single full-library cap through the same curves path a
            # sweep uses: a covering cached master supplies the
            # neighbors with zero kNN work (exactly what plan("ccm")
            # advertises); without one it is one engine pass, same as
            # the legacy cross_map — and bit-identical either way.
            Lp = num_embedded(self.data.L, E, c.tau)
            curves = self._ccm_curves(
                li, self.data.panel[ti][None, :], E=E,
                lib_sizes=(Lp - max(c.Tp_cross, 0),))
            return curves[0, 0]
        curves = self._ccm_curves(li, self.data.panel[ti][None, :], E=E,
                                  lib_sizes=lib_sizes)
        return curves[:, 0]

    def _ccm_curves(self, li: int, targets, *, E: int,
                    lib_sizes) -> np.ndarray:
        """(num_sizes, N) convergence grid vs library ``li``'s manifold.

        Master-derived when the cached master's slack rule covers every
        requested cap; one multi-cap engine pass otherwise. k is the
        simplex default E + 1 (what the legacy ``cross_map`` sweep used),
        independent of ``config.k``.
        """
        from repro.core.ccm import ccm_convergence_caps, normalize_lib_sizes
        c = self.config
        x = self.data.panel[li]
        Lp = num_embedded(self.data.L, E, c.tau)
        caps, inv = normalize_lib_sizes(lib_sizes, Lp=Lp, Tp=c.Tp_cross)
        k = E + 1
        hit = self._cache.get("master")
        if (c.cache and c.mesh is None and hit is not None
                and hit[3] >= E
                and master_slack_covers(caps, Lp=Lp, k=k, k_master=hit[2])):
            self._bump("knn_master_hits")
            curves = ccm_convergence_from_master(
                x, hit[1][li, E - 1], targets, E=E, tau=c.tau,
                Tp=c.Tp_cross, caps=caps, k=k, impl=self._impl)
        else:
            curves = ccm_convergence_caps(
                x, targets, E=E, tau=c.tau, Tp=c.Tp_cross, caps=caps,
                exclude_self=True, impl=self._impl)
        return np.asarray(curves)[inv]

    def ccm_batch(self, pairs, *, E: int) -> np.ndarray:
        """Full-library CCM skill for many (lib, target) pairs → (n,) ρ.

        The serving primitive: n compatible requests (same panel, same
        E) become ONE library-batched engine launch
        (``plan.ccm_batch_from_master``, the master-derived matrix
        engine) instead of n single-pair passes, ~20× the pairs/s on
        saturated queues. The launch takes the panel buffer, the master
        and the library indices, gathers inside the program and reads
        the valid length as an operand: one program per (capacity, E,
        libraries per batch), which appends within the capacity leave
        warm. Its bit contract is *batch invariance*: the launch
        always cross-maps against the full panel's target set and the
        library axis is batch-invariant, so a pair's ρ is a pure
        function of (library state, lib, target, E) — the same bits no
        matter which other requests share its batch.
        ``ccm_batch([(l, t)], E=E)`` is therefore the quiesced oracle
        for any batched call. Values agree with the classic
        convergence-path ``ccm`` to the final ULP (different engines
        round differently); serving pins its answers to THIS method.
        Pairs touching masked-invalid series come back NaN; without a
        covering cached master (tiny panels, slack exhausted) it falls
        back to per-pair classic ``ccm``.
        """
        c = self.config
        E = int(E)
        idx = [(self.data.index_of(l), self.data.index_of(t))
               for l, t in pairs]
        out = np.full(len(idx), np.nan, np.float32)
        live = [(j, li, ti) for j, (li, ti) in enumerate(idx)
                if not self._pair_invalid(li, ti)]
        if not live:
            return out
        with telemetry.span("session.ccm_batch", pairs=len(idx), E=E) as sp:
            Lp = num_embedded(self.data.L, E, c.tau)
            cap = Lp - max(c.Tp_cross, 0)
            k = E + 1
            hit = (self._master(E) if c.cache and c.mesh is None else None)
            if hit is None or not master_slack_covers(
                    (cap,), Lp=Lp, k=k, k_master=hit[2]):
                for j, li, ti in live:
                    out[j] = self.ccm(li, ti, E=E)
                return out
            libs = sorted({li for _, li, _ in live})
            sp.annotate(libs=len(libs))
            lpos = {li: i for i, li in enumerate(libs)}
            g = ccm_batch_from_master(
                self.data.buffer, hit[1], libs, self.data.L, E=E,
                tau=c.tau, Tp=c.Tp_cross, k=k, impl=self._impl)
            for j, li, ti in live:
                out[j] = g[lpos[li], ti]
        self._bump("ccm_batch_pairs", len(live))
        return out

    def surrogate_test(self, lib, target, *, num_surrogates: int = 100,
                       method: str = "shuffle", period: int | None = None,
                       lib_sizes=None, E: int | None = None,
                       seed: int = 0) -> SurrogateResult:
        """CCM significance: rank the real skill against a null ensemble.

        Generates ``num_surrogates`` null versions of ``target``
        (``method="shuffle"`` destroys all temporal structure;
        ``"seasonal"`` permutes within phases of ``period`` so shared
        seasonal forcing survives into the null — the classic CCM false
        positive) and cross-maps ALL of them plus the real series as one
        (M+1)-target batch through a single jitted curve-grid program —
        the same batching discipline as ``submit_panel``, and the
        library's neighbor tables (session master or one engine pass)
        are shared by the whole ensemble. Returns a ``SurrogateResult``
        with the one-sided rank p-value ``(1 + #{ρ_null ≥ ρ}) / (1 + M)``
        (per size when ``lib_sizes`` is given).
        """
        c = self.config
        li = self.data.index_of(lib)
        ti = self.data.index_of(target)
        if self._pair_invalid(li, ti):  # masked series: NaN verdict
            if lib_sizes is None:
                return SurrogateResult(
                    float("nan"),
                    np.full(num_surrogates, np.nan, np.float32),
                    float("nan"), method, num_surrogates)
            S = len(tuple(lib_sizes))
            return SurrogateResult(
                np.full(S, np.nan, np.float32),
                np.full((S, num_surrogates), np.nan, np.float32),
                np.full(S, np.nan), method, num_surrogates)
        E = self._resolve_pair_E(ti, E)
        with telemetry.span("session.surrogate_test", lib=li, target=ti,
                            E=E, M=num_surrogates, method=method):
            y = np.asarray(self.data.panel[ti])
            surr = make_surrogates(y, num_surrogates, method=method,
                                   period=period, seed=seed)
            targets = jnp.concatenate(
                [jnp.asarray(y)[None, :], jnp.asarray(surr)], axis=0)
            squeeze = lib_sizes is None
            if squeeze:  # one cap: the full usable library
                Lp = num_embedded(self.data.L, E, c.tau)
                lib_sizes = (Lp - max(c.Tp_cross, 0),)
            curves = self._ccm_curves(li, targets, E=E,
                                      lib_sizes=lib_sizes)
        rho = curves[:, 0]
        null = curves[:, 1:]
        pval = ((1.0 + (null >= rho[:, None]).sum(axis=1))
                / (1.0 + num_surrogates))
        self._bump("surrogate_tests")
        if squeeze:
            return SurrogateResult(float(rho[0]), null[0], float(pval[0]),
                                   method, num_surrogates)
        return SurrogateResult(rho, null, pval, method, num_surrogates)

    # -------------------------------------------------------------- xmap

    def xmap(self, method: str = "simplex", *, E_opt=None,
             theta: float | None = None,
             run_dir: str | None = None) -> np.ndarray:
        """All-pairs cross-map skill matrix → (N, N) ρ.

        Entry (l, t) = skill of cross-mapping series t from series l's
        manifold at t's optimal E (evidence "t causes l"). The whole-
        brain CCM workload. ``method="simplex"`` is classic CCM;
        ``method="smap"`` swaps the lookup for the batched S-Map engine
        at locality ``theta`` (per-target optimal-E S-Map CCM).

        Each E-group is driven by the library-batched matrix engine —
        ceil(N/B) fused distance→top-k→lookup launches (``batch_libs`` /
        the memory-budget auto rule) with device compute double-buffered
        against host assembly, instead of N sequential per-series steps.
        Local sessions holding a cached multi-E kNN master (simplex
        method) derive neighbor indices from it with zero kNN work; mesh
        configs route through the E-grouped zero-collective sharded
        engines, whose per-shard inner loop uses the same batched
        engine.

        ``run_dir=`` makes the run **fault-tolerant and resumable**
        (``repro.edm.runner``): every engine tile is journaled under
        that directory, SIGTERM/SIGINT checkpoints and exits with code
        ``runner.PREEMPTED_EXIT`` (17), a device OOM halves the batch
        and retries, and calling again with the same run_dir resumes
        bit-identically from the last committed tile — a completed
        journal short-circuits to the stored matrix with zero compute.
        The journal is keyed by a content hash of panel + config + task,
        so a stale run_dir (anything changed) is refused, never reused.
        Masked-invalid series are NaN rows/columns in the returned
        matrix (and named in ``run_dir/report.json``).
        """
        if method not in ("simplex", "smap"):
            raise ValueError(f"unknown xmap method {method!r}")
        c = self.config
        N = self.data.N
        with telemetry.span("session.xmap", method=method, N=N,
                            journaled=run_dir is not None,
                            placement=("sharded" if c.mesh is not None
                                       else "local")):
            self._plan_event("xmap")
            if E_opt is None:
                E_opt = np.full(N, c.E, np.int32) if c.E else self._rho()[0]
            E_opt, groups = _e_groups(E_opt, N)
            if c.mesh is not None:
                rho = self._xmap_sharded(method, E_opt, theta, run_dir)
            else:
                rho = self._xmap_local(method, groups, theta, run_dir,
                                       E_opt)
        return self._mask_matrix(rho)

    def _xmap_group_launch(self, method, E, members, theta, iM):
        """One E-group's engine as a ``launch(a, b, B)`` closure + its B.

        The (launch, B) pair is the resumable unit the fault-tolerant
        runner re-drives (at any batch size — the engines are
        bit-invariant in B); the plain path drives the same closure
        through ``drive_batched`` directly, so journaled and
        un-journaled runs execute byte-identical launches.
        """
        c = self.config
        X = self.data.panel
        N = self.data.N
        tgts = X[np.asarray(members)]
        Lp = num_embedded(self.data.L, E, c.tau)
        if method == "smap":
            from repro.core.ccm import pad_batch
            from repro.core.smap_engine import smap_group
            th = float(c.theta if theta is None else theta)
            B = min(N, c.batch_libs) if c.batch_libs else N

            def launch(a, b, B):
                return smap_group(
                    pad_batch(X[a:b], B), tgts, E=E, tau=c.tau,
                    Tp=c.Tp_cross, theta=th, ridge=c.ridge,
                    impl=self._impl)

            return launch, B
        if iM is not None:
            from repro.core.ccm import auto_batch_libs
            from repro.edm.plan import (make_master_group_launch,
                                        master_group_batch_bytes)
            launch = make_master_group_launch(
                X, iM[:, E - 1], tgts, E=E, tau=c.tau, Tp=c.Tp_cross,
                k=c.k_for(E), impl=self._impl)
            B = c.batch_libs or auto_batch_libs(
                Lp, N, c.batch_budget_mb,
                per_series_bytes=master_group_batch_bytes(
                    Lp, iM.shape[-1]))
            return launch, max(1, min(int(B), N))
        from repro.core.ccm import auto_batch_libs, make_group_launch
        launch = make_group_launch(X, tgts, E=E, tau=c.tau, Tp=c.Tp_cross,
                                   k=c.k_for(E), impl=self._impl)
        B = c.batch_libs or auto_batch_libs(Lp, N, c.batch_budget_mb)
        return launch, max(1, min(int(B), N))

    def _xmap_local(self, method, groups, theta, run_dir=None,
                    E_opt=None) -> np.ndarray:
        """Local all-pairs matrix: library-batched engine per E-group.

        Each E-group runs as ceil(N/B) batched engine launches
        (``batch_libs`` / the auto memory-budget rule) with device
        compute double-buffered against host block assembly. A cached
        kNN master that covers the needed levels supplies the neighbor
        indices (zero kNN work); otherwise the direct
        ``ops.all_knn_batch`` engine runs — a one-shot matrix no longer
        pays for building a master it would use once. With ``run_dir``
        the same launches run under the journaled ``MatrixRunner``.
        """
        from repro.core.ccm import drive_batched
        c = self.config
        N = self.data.N
        hit = self._cache.get("master")
        use_master = method == "simplex" and c.cache and hit is not None \
            and hit[3] >= max(groups)
        if (method == "simplex" and c.cache and not use_master
                and self.stats["xmap_direct_runs"] > 0):
            # Second no-master xmap on a caching session: the workload is
            # repeating, so pay for the master NOW and derive this and
            # every later call from it — a one-shot matrix stays on the
            # direct engine, a repeated one keeps the amortization the
            # session API promises.
            use_master = True
        if use_master:
            iM = self._master(max(groups))[1]
        else:
            iM = None
            if method == "simplex" and c.cache:
                self._bump("xmap_direct_runs")
        entries = [
            (E, members) + self._xmap_group_launch(
                method, E, members, theta, iM)
            for E, members in groups.items()]
        if run_dir is not None:
            return self._run_journaled(run_dir, method, theta, entries,
                                       (N, N), E_opt)
        rho = np.zeros((N, N), np.float32)
        for E, members, launch, B in entries:
            rho[:, members] = drive_batched(N, B, launch)
        return rho

    def _xmap_sharded(self, method, E_opt, theta, run_dir=None) -> np.ndarray:
        c = self.config
        X = self.data.panel
        N = self.data.N
        from repro.distributed.sharded_ccm import (
            _egroup_layout, mesh_axes_size, sharded_ccm_matrix,
            sharded_smap_matrix)

        def matrix(X_lib, layout=None):
            if method == "smap":
                return np.asarray(sharded_smap_matrix(
                    X_lib, X, E_opt=E_opt, tau=c.tau, Tp=c.Tp_cross,
                    theta=float(c.theta if theta is None else theta),
                    ridge=c.ridge, mesh=c.mesh, lib_axes=c.lib_axes,
                    tgt_axes=c.tgt_axes, impl=self._impl, layout=layout))
            return np.asarray(sharded_ccm_matrix(
                X_lib, X, E_opt=E_opt, tau=c.tau, Tp=c.Tp_cross,
                mesh=c.mesh, lib_axes=c.lib_axes, tgt_axes=c.tgt_axes,
                impl=self._impl, batch_libs=c.batch_libs,
                batch_budget_mb=c.batch_budget_mb, layout=layout))

        if run_dir is None:
            return matrix(X)[:N]
        # Journaled mesh run: the lib axis is cut into row chunks and
        # each chunk is ONE SPMD matrix call (libraries auto-pad over
        # the lib shards; rows are independent, so chunking is
        # bit-identical) — completed chunks persist as journal tiles.
        # The static E-group target layout is computed once and reused
        # across every chunk instead of re-derived per call.
        S_l = c.mesh_axis_size(c.lib_axes)
        S_t = mesh_axes_size(c.mesh, c.tgt_axes)
        layout = _egroup_layout(
            jnp.broadcast_to(jnp.asarray(E_opt, jnp.int32), (N,)), S_t)
        tile = c.run_tile_rows or max(S_l, -(-N // 8))
        tile = -(-int(tile) // S_l) * S_l  # round up to full lib shards

        def launch(a, b, B):
            return matrix(X[a:b], layout=layout)

        entries = [(0, np.arange(N), launch, tile)]
        return self._run_journaled(run_dir, method, theta, entries, (N, N),
                                   E_opt)

    def _run_journaled(self, run_dir, method, theta, entries,
                       shape, E_opt) -> np.ndarray:
        """Drive xmap tile groups through a journaled ``MatrixRunner``."""
        from repro.edm.runner import MatrixRunner, run_key
        c = self.config
        groups_sig = [[E, len(members)] for E, members, _, _ in entries]
        th = (float(c.theta if theta is None else theta)
              if method == "smap" else None)
        # The task signature hashes the FULL per-series E table, not a
        # group-size summary: E_opt=[2,3] vs [3,2] keep group sizes but
        # assign different manifolds, and must key to different runs.
        e_table = np.ascontiguousarray(
            np.broadcast_to(np.asarray(E_opt, np.int32), (self.data.N,)))
        key = run_key(self.data.panel, c,
                      ("xmap", method, th, e_table.tobytes()))
        runner = MatrixRunner(
            run_dir, key=key, shape=shape, groups_sig=groups_sig,
            keep=c.checkpoint_keep, checkpoint_every=c.checkpoint_every,
            oom_retries=c.oom_retries,
            invalid_series=self.data.invalid_report,
            straggler_threshold=c.straggler_threshold)
        if runner.complete:
            # Finished journal: the stored matrix IS the result — zero
            # engine launches (restart loops may re-run unconditionally).
            self._bump("runs_short_circuited")
            runner.close()  # release the run_dir lock
            return runner.result()
        with runner:
            for g, (E, members, launch, B) in enumerate(entries):
                runner.drive_group(g, launch, B, members)
            out = runner.finalize()
        self._bump("rows_resumed", runner.resumed_rows)
        return out

    # ------------------------------------------------------ batched entry

    def submit_panel(self, panel, tasks=("optimal_E",)) -> int:
        """Queue a panel for batched execution; returns a ticket id.

        The serving-style entry point: queued panels of the same length
        are concatenated and driven through ONE jitted program per task
        at ``flush()`` (and every flush reuses the programs this
        session's config already compiled), instead of paying a dispatch
        + trace per panel.
        """
        allowed = ("optimal_E", "smap", "xmap")
        tasks = tuple(tasks)
        for t in tasks:
            if t not in allowed:
                raise ValueError(f"unknown task {t!r}; expected {allowed}")
        panel = jnp.asarray(panel)
        if panel.ndim == 1:
            panel = panel[None, :]
        ticket = self._next_ticket
        self._next_ticket += 1
        self._queue.append((ticket, panel, tasks))
        return ticket

    def flush(self) -> dict[int, PanelResult]:
        """Run every queued panel; returns {ticket: PanelResult}.

        Matrix tasks inherit the engine's double-buffered dispatch
        (ROADMAP session item (b)): each panel's xmap runs as batched
        launches with the device computing batch i+1 while the host
        assembles batch i's block (``core.ccm.drive_batched``).
        """
        queue, self._queue = self._queue, []
        with telemetry.span("session.flush", panels=len(queue)):
            return self._flush_batches(queue)

    def _flush_batches(self, queue) -> dict[int, PanelResult]:
        results = {t: PanelResult() for t, _, _ in queue}
        batches: dict[tuple, list] = collections.defaultdict(list)
        for ticket, panel, tasks in queue:
            batches[(panel.shape[1], tasks)].append((ticket, panel))
        for (L, tasks), items in batches.items():
            big = jnp.concatenate([p for _, p in items], axis=0)
            sess = EDM(big, self.config)
            offs = np.cumsum([0] + [p.shape[0] for _, p in items])
            if "optimal_E" in tasks:
                E_opt, rho = sess.optimal_E()
                for (ticket, _), a, b in zip(items, offs, offs[1:]):
                    results[ticket].E_opt = E_opt[a:b]
                    results[ticket].rho = rho[a:b]
            if "smap" in tasks:
                sweep = sess.smap()
                for (ticket, _), a, b in zip(items, offs, offs[1:]):
                    results[ticket].smap = sweep[a:b]
            if "xmap" in tasks:
                # cross terms force per-panel matrices, but the batch
                # session's per-series state slices cleanly: hand each
                # panel its E_opt slice and its rows of the kNN master
                # instead of re-running the multi-E engine per panel.
                E_all = None if self.config.E else sess._rho()[0]
                master = sess._cache.get("master")
                for (ticket, panel), a, b in zip(items, offs, offs[1:]):
                    psess = EDM(panel, self.config)
                    if master is not None:
                        dM, iM, k_m, lv = master
                        psess._cache["master"] = (dM[a:b], iM[a:b], k_m, lv)
                    results[ticket].xmap = psess.xmap(
                        E_opt=None if E_all is None else E_all[a:b])
            self._bump("panels_flushed", len(items))
        return results
