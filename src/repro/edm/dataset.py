"""``Dataset`` — a panel of time series plus cached delay embeddings.

Ingestion is hardened (ISSUE 6): every panel is screened for non-finite
values and constant series at construction, under an explicit
``on_invalid`` policy, instead of letting one corrupt electrode trace
NaN-poison an entire all-pairs matrix silently:

* ``"raise"`` (default) — refuse the panel with the offending series
  named. The safe default for pipelines that expect clean data.
* ``"mask"``  — keep the panel shape; non-finite entries are zeroed for
  compute (so sorts/top-k never see NaN) and the per-series validity
  mask propagates through the session: every output touching an invalid
  series is NaN, and the run report names the series.
* ``"drop"``  — remove invalid series before binding; indices/names of
  the surviving panel are compacted, the report records what was
  dropped (by original index and name).

``dataset.valid`` is the (N,) validity mask (all-True for clean
panels), ``dataset.invalid_report`` the JSON-ready list of
``{index, name, reason}`` records the fault-tolerant runner copies into
its run report.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

#: Accepted ``on_invalid`` policies, in documentation order.
INVALID_POLICIES = ("raise", "mask", "drop")


def series_stats(arr: np.ndarray) -> dict:
    """Running per-series screening stats of an (N, dt) column block.

    ``{"cnt": non-finite count, "lo"/"hi": finite min/max}`` — the
    sufficient statistic for the screen's two invalidity predicates
    (non-finite entries; constant series). Stats of column blocks
    compose via ``merge_stats``, which is what lets ``Dataset.append``
    re-screen a grown panel from only the Δt new columns in O(N·Δt).
    """
    arr = np.asarray(arr)
    finite = np.isfinite(arr)
    return {
        "cnt": (~finite).sum(axis=1).astype(np.int64),
        "lo": np.min(np.where(finite, arr, np.inf), axis=1,
                     initial=np.inf),
        "hi": np.max(np.where(finite, arr, -np.inf), axis=1,
                     initial=-np.inf),
    }


def merge_stats(a: dict, b: dict) -> dict:
    """Stats of the column-concatenation of two blocks."""
    return {"cnt": a["cnt"] + b["cnt"],
            "lo": np.minimum(a["lo"], b["lo"]),
            "hi": np.maximum(a["hi"], b["hi"])}


def _records(cnt, lo, hi, delta_cnt=None) -> list[dict]:
    """Invalid-series records from screening stats (empty = clean).

    ``delta_cnt`` (delta mode) attributes non-finite faults introduced
    by an appended block, so the report names where the corruption
    arrived.
    """
    bad = cnt > 0
    const = ~bad & (lo >= hi)  # no finite spread (lo > hi: no data)
    recs = []
    for i in np.nonzero(bad | const)[0]:
        if not bad[i]:
            reason = "constant series"
        elif delta_cnt is not None and delta_cnt[i] > 0:
            reason = (f"{int(delta_cnt[i])} non-finite values in "
                      f"appended delta")
        else:
            reason = f"{int(cnt[i])} non-finite values"
        recs.append({"index": int(i), "name": None, "reason": reason})
    return recs


def screen_panel(panel: np.ndarray, *, prior: dict | None = None
                 ) -> list[dict]:
    """Invalid-series records of an (N, L) panel (empty = clean).

    A series is invalid when it contains non-finite values (NaN/Inf —
    dead channel, transmission glitch) or is constant (zero variance —
    a flatlined electrode: every delay vector coincides, distances
    degenerate to ties and Pearson ρ divides by zero).

    Vectorized over the whole panel (no float64 copy, no per-series
    Python loop): at the 10⁵-series panels this module targets, the
    screen runs on every Dataset construction and must stay O(panel)
    flops with O(N) extra memory.

    Delta mode: with ``prior=`` (running ``series_stats`` of the
    already-screened columns), ``panel`` is only the appended (N, Δt)
    block and the screen is O(N·Δt) — the grown panel is judged from
    merged stats, with delta-introduced non-finite faults named as
    such. Used by ``Dataset.append``.
    """
    arr = np.asarray(panel)
    if arr.size == 0 and prior is None:
        return []
    stats = series_stats(arr)
    if prior is None:
        return _records(stats["cnt"], stats["lo"], stats["hi"])
    if len(prior["cnt"]) != arr.shape[0]:
        raise ValueError(
            f"delta has {arr.shape[0]} series but prior stats cover "
            f"{len(prior['cnt'])}")
    m = merge_stats(prior, stats)
    return _records(m["cnt"], m["lo"], m["hi"], delta_cnt=stats["cnt"])


def grown_capacity(L: int) -> int:
    """The capacity a live panel regrows to at length L: 1.25·L rounded
    up to a multiple of 128. Each regrow recompiles every serving
    program, so the room grows with the recording (regrows are
    logarithmic in its length), and the masked rows past L cost at most
    a quarter more work; 128, the vector lane width, keeps the row
    blocks of the capacity programs whole."""
    return -(-(-(-5 * int(L) // 4)) // 128) * 128


@jax.jit
def _write_columns(buf, delta, at):
    """``buf`` with ``delta`` written at column ``at`` (an operand): one
    program per (N, capacity, dt), whatever the valid length."""
    return jax.lax.dynamic_update_slice_in_dim(
        buf, delta.astype(buf.dtype), at, axis=1)


class Dataset:
    """An (N, L) panel of equal-length series with embedding caches.

    The facade's unit of state: every ``EDM`` session method operates on
    one Dataset, and materialized delay embeddings (used by S-Map design
    matrices and user inspection — the distance kernels fuse theirs) are
    computed once per (E, tau) and held here. ``on_invalid`` sets the
    NaN/Inf/constant-series policy (module docstring).

    **Capacity.** The series are stored in an (N, C) ``buffer`` whose
    first L columns are valid; ``panel`` is always the exact (N, L)
    view. A panel starts exact (C = L), so one that never appends keeps
    the shapes and the work it always had. An append that would pass C
    — the first append of a live panel, then each one that outgrows the
    room — regrows the buffer to ``grown_capacity(L_new)``, and every
    append within C writes into it and keeps every shape: the append and
    ``ccm_batch`` programs, which read L as an operand, compile nothing
    until the next regrow.
    """

    def __init__(self, panel, *, names=None, on_invalid: str = "raise"):
        if on_invalid not in INVALID_POLICIES:
            raise ValueError(
                f"unknown on_invalid policy {on_invalid!r}; expected one "
                f"of {INVALID_POLICIES}")
        panel = jnp.asarray(panel)
        if panel.ndim == 1:
            panel = panel[None, :]
        if panel.ndim != 2:
            raise ValueError(f"panel must be (N, L) or (L,), got {panel.shape}")
        if names is not None:
            names = list(names)
            if len(names) != panel.shape[0]:
                raise ValueError(
                    f"{len(names)} names for {panel.shape[0]} series")
        self.on_invalid = on_invalid
        stats = series_stats(np.asarray(panel))
        report = screen_panel(np.asarray(panel))
        for r in report:
            r["name"] = names[r["index"]] if names is not None else None
        self.invalid_report = report
        valid = np.ones(panel.shape[0], bool)
        for r in report:
            valid[r["index"]] = False
        if report and on_invalid == "raise":
            what = "; ".join(
                f"series {r['name'] if r['name'] is not None else r['index']}"
                f": {r['reason']}" for r in report)
            raise ValueError(
                f"panel contains invalid series ({what}); pass "
                f"on_invalid='mask' to NaN-flag them in outputs or "
                f"on_invalid='drop' to remove them")
        if report and on_invalid == "drop":
            panel = panel[np.nonzero(valid)[0]]
            stats = {k: v[valid] for k, v in stats.items()}
            if names is not None:
                names = [n for n, ok in zip(names, valid) if ok]
            if panel.shape[0] == 0:
                raise ValueError(
                    "every series in the panel is invalid; nothing left "
                    "after on_invalid='drop'")
            valid = np.ones(panel.shape[0], bool)
        elif report:  # mask: zero non-finite entries so kernels/top-k
            panel = jnp.nan_to_num(  # never see NaN; outputs touching
                panel, nan=0.0, posinf=0.0, neginf=0.0)  # them are NaN'd
        self.names = names
        self.valid = valid
        self._stats = stats  # running series_stats of the raw panel
        self._embeddings: dict[tuple[int, int], jax.Array] = {}
        self._bind(panel, int(panel.shape[1]), int(panel.shape[1]))

    def _bind(self, panel, L: int, C: int) -> None:
        """Hold the exact (N, L) ``panel`` in an (N, C) buffer."""
        self.buffer = panel if C == L else jnp.pad(panel,
                                                   ((0, 0), (0, C - L)))
        self._L = L
        self._view = panel

    def append(self, delta) -> list[dict]:
        """Grow every series by Δt points under the bound policy.

        The screen is O(N·Δt), not O(N·L): the running per-series stats
        kept since construction absorb only the new columns
        (``screen_panel`` delta mode). ``"raise"`` rejects the delta
        BEFORE mutating any state, naming the offending series;
        ``"mask"`` zeroes non-finite delta entries and flags the series
        invalid; ``"drop"`` removes series the delta invalidated.

        Returns the invalid-series records introduced by this delta.
        Indices are PRE-append — positions in the panel as it was when
        the call started — so callers holding per-series caches (the
        ``EDM`` session's kNN master) can compact them to match.
        Embedding caches are cleared; stats are computed on the raw
        delta, so a masked series never silently "heals".
        """
        delta = jnp.asarray(delta)
        if delta.ndim == 1:
            delta = delta[None, :]
        if delta.ndim != 2 or delta.shape[0] != self.N:
            raise ValueError(
                f"delta must be ({self.N}, dt), got {tuple(delta.shape)}")
        if delta.shape[1] < 1:
            raise ValueError("delta must append at least one point")
        arr = np.asarray(delta)
        fresh = [dict(r) for r in screen_panel(arr, prior=self._stats)
                 if self.valid[r["index"]]]
        for r in fresh:
            r["name"] = (self.names[r["index"]]
                         if self.names is not None else None)
        if fresh and self.on_invalid == "raise":
            what = "; ".join(
                f"series {r['name'] if r['name'] is not None else r['index']}"
                f": {r['reason']}" for r in fresh)
            raise ValueError(
                f"append rejected: delta would invalidate series ({what}); "
                f"bind the panel with on_invalid='mask' or 'drop' to accept "
                f"faulty ticks")
        merged = merge_stats(self._stats, series_stats(arr))
        if self.num_invalid or fresh:  # mask policy: keep NaN out of kernels
            delta = jnp.nan_to_num(delta, nan=0.0, posinf=0.0, neginf=0.0)
        keep = None
        if fresh and self.on_invalid == "drop":
            bad = {r["index"] for r in fresh}
            keep = np.array([i for i in range(self.N) if i not in bad], int)
            if keep.size == 0:
                raise ValueError(
                    "append would invalidate every remaining series; "
                    "refusing to drop the whole panel")
        L, dt = self._L, int(delta.shape[1])
        if L + dt > self.capacity:
            self._bind(self.panel, L, grown_capacity(L + dt))
        self.buffer = _write_columns(self.buffer, delta, np.int32(L))
        self._L, self._view = L + dt, None
        if keep is not None:
            self.buffer = self.buffer[keep]
            merged = {k: v[keep] for k, v in merged.items()}
            if self.names is not None:
                self.names = [self.names[i] for i in keep]
            self.valid = np.ones(len(keep), bool)
        else:
            self.valid = np.asarray(
                (merged["cnt"] == 0) & (merged["lo"] < merged["hi"]))
        self._stats = merged
        self.invalid_report = self.invalid_report + fresh
        self._embeddings.clear()
        return fresh

    @property
    def panel(self) -> jax.Array:
        """The exact (N, L) panel: the buffer's valid columns."""
        if self._view is None:
            self._view = self.buffer[:, :self._L]
        return self._view

    @property
    def capacity(self) -> int:
        """Columns the buffer holds (C ≥ L); see the class docstring."""
        return int(self.buffer.shape[1])

    @property
    def N(self) -> int:
        return self.buffer.shape[0]

    @property
    def L(self) -> int:
        return self._L

    @property
    def num_invalid(self) -> int:
        """Invalid series still in the panel (0 under raise/drop)."""
        return int((~self.valid).sum())

    def is_valid(self, i: int) -> bool:
        return bool(self.valid[i])

    def index_of(self, key) -> int:
        """Series index for an int position or a name."""
        if isinstance(key, str):
            if self.names is None:
                raise KeyError(f"panel has no names (asked for {key!r})")
            return self.names.index(key)
        return int(key)

    def series(self, key) -> jax.Array:
        return self.panel[self.index_of(key)]

    def embedding(self, E: int, tau: int = 1) -> jax.Array:
        """Cached (N, Lp, E) delay embeddings of every series."""
        key = (int(E), int(tau))
        if key not in self._embeddings:
            self._embeddings[key] = jax.vmap(
                lambda x: ops.delay_embed(x, E, tau))(self.panel)
        return self._embeddings[key]

    def __len__(self) -> int:
        return self.N

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        bad = f", invalid={self.num_invalid}" if self.num_invalid else ""
        cap = (f", capacity={self.capacity}" if self.capacity != self.L
               else "")
        return f"Dataset(N={self.N}, L={self.L}{cap}{bad})"
