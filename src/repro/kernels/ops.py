"""Single-source dispatch layer for the EDM kernels.

This is the repo's analog of kEDM's "single codebase, many backends"
portability story: every caller goes through these entry points, and the
implementation is chosen per platform —

  * ``pallas``    — Mosaic/TPU kernels (the performance path),
  * ``interpret`` — the same kernels executed by the Pallas interpreter
                    (CPU correctness validation; what CI runs here),
  * ``ref``       — pure-jnp oracles (also what multi-pod dry-runs lower,
                    since Mosaic cannot target the CPU backend).

``impl="auto"`` resolves to the innermost ``use_impl`` override if one is
active (the plan layer in ``repro.edm`` sets it per plan), else to
``pallas`` on TPU and ``ref`` elsewhere. Unknown impl names are an error
everywhere — they used to fall through to the kernel path and fail with
an obscure Mosaic error much later.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.kernels import lookup as _lookup_k
from repro.kernels import pairwise_dist as _pairwise_k
from repro.kernels import ref as _ref
from repro.kernels import topk as _topk_k

make_weights = _ref.make_weights
pearson_rows = _ref.pearson_rows
num_embedded = _ref.num_embedded
delay_embed = _ref.delay_embed

#: Every implementation name the dispatch layer accepts.
IMPLS = ("auto", "pallas", "interpret", "ref")

_impl_stack: list[str] = []  # innermost use_impl override wins


@functools.cache
def _platform_default() -> str:
    # No fallback: a backend that fails to start raises here, instead of
    # silently resolving the CPU oracle on a machine meant for the chip.
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


def default_impl() -> str:
    """Current default implementation: ``use_impl`` override, else platform."""
    if _impl_stack and _impl_stack[-1] != "auto":
        return _impl_stack[-1]
    return _platform_default()


@contextlib.contextmanager
def use_impl(name: str):
    """Scoped module-level default: ``with ops.use_impl("interpret"): ...``.

    Inside the block every ``impl="auto"`` call resolves to ``name``
    (``"auto"`` restores the platform default). This is how the plan layer
    (``repro.edm``) pins one backend for a whole plan instead of threading
    ``impl=`` through every call site.

    Caveat: resolution happens at *trace* time, and jitted callables key
    their cache on the static string ``"auto"``, not on what it resolved
    to — a program traced under one override is happily reused under
    another. Code that flips impls mid-session (the plan layer, tests)
    must pass the concrete name from ``resolve_impl`` into jitted
    functions rather than rely on ``"auto"`` inside the block.
    """
    if name not in IMPLS:
        raise ValueError(f"unknown impl {name!r}; expected one of {IMPLS}")
    _impl_stack.append(name)
    try:
        yield
    finally:
        _impl_stack.pop()


def resolve_impl(impl: str = "auto") -> str:
    """Concrete implementation name for ``impl`` (errors on unknown names)."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    return default_impl() if impl == "auto" else impl


_resolve = resolve_impl


def _tel(op: str, impl: str, **attrs) -> None:
    """Per-dispatch telemetry: an ``edm_ops_<op>_calls`` counter bump
    plus (when a sink is live) an ``ops.<op>`` event with static
    shape/impl attrs.

    Counters, not timed spans, on purpose: these dispatchers run at
    *trace* time inside jitted programs, where ``block_until_ready``
    cannot fence a tracer — a wall-time span here would measure trace
    overhead once and nothing on cached calls. Timed spans live at the
    driver level (``core.ccm.drive_batched``), where tile landings are
    real device syncs. A dispatch count therefore means "this op was
    traced", which is exactly the invocation-count contract the session
    cache tests assert (they clear jit caches first).
    """
    telemetry.counter(f"edm_ops_{op}_calls").inc()
    if telemetry.active():
        telemetry.event(f"ops.{op}", impl=impl, **attrs)


def pairwise_distances(
    x: jax.Array,
    *,
    E: int,
    tau: int = 1,
    impl: str = "auto",
    variant: str = "vpu",
    block: tuple[int, int] = (256, 256),
) -> jax.Array:
    """(Lp, Lp) squared distances of the delay embedding (fused, Alg. 1)."""
    impl = _resolve(impl)
    _tel("pairwise_distances", impl, E=E, tau=tau, L=int(x.shape[-1]))
    if impl == "ref":
        return _ref.pairwise_distances(x, E=E, tau=tau)
    return _pairwise_k.pairwise_distances(
        x, E=E, tau=tau, block=block, variant=variant,
        interpret=(impl == "interpret"),
    )


def topk_select(
    D: jax.Array,
    *,
    k: int,
    exclude_self: bool = True,
    max_idx=None,
    impl: str = "auto",
    block_rows: int = 8,
) -> tuple[jax.Array, jax.Array]:
    """k nearest per row → (Euclidean dists, int32 idx), ascending (Alg. 2)."""
    impl = _resolve(impl)
    _tel("topk_select", impl, k=k, Lp=int(D.shape[-1]))
    if impl == "ref":
        return _ref.topk_select(D, k=k, exclude_self=exclude_self,
                                max_idx=max_idx)
    return _topk_k.topk_select(
        D, k=k, exclude_self=exclude_self, max_idx=max_idx,
        block_rows=block_rows, interpret=(impl == "interpret"),
    )


def topk_select_sizes(
    D: jax.Array,
    *,
    k: int,
    max_idxs: tuple[int, ...],
    exclude_self: bool = True,
    impl: str = "auto",
    block: tuple[int, int] = (8, 512),
) -> tuple[jax.Array, jax.Array]:
    """k nearest per row under EVERY prefix cap in one pass → (S, Lp, k).

    ``max_idxs`` is an ascending tuple of inclusive candidate caps (one
    per library size); level s equals ``topk_select(D, k=k,
    max_idx=max_idxs[s])`` on every valid slot, with dist=inf /
    idx=``ref.PAD_IDX`` where a cap leaves fewer than k candidates. The
    CCM convergence-sweep primitive: one streaming pass instead of S
    full re-scans of the distance matrix (see kernels/topk.py).
    """
    impl = _resolve(impl)
    _tel("topk_select_sizes", impl, k=k, sizes=len(max_idxs),
         Lp=int(D.shape[-1]))
    if impl == "ref":
        return _ref.topk_select_sizes(
            D, k=k, max_idxs=tuple(int(m) for m in max_idxs),
            exclude_self=exclude_self)
    return _topk_k.topk_select_sizes(
        D, k=k, max_idxs=tuple(int(m) for m in max_idxs),
        exclude_self=exclude_self, block=block,
        interpret=(impl == "interpret"))


def all_knn(
    x: jax.Array,
    *,
    E: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
    impl: str = "auto",
    variant: str = "vpu",
    fused: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """All-kNN search over one library series (paper §3.3).

    Returns (dists (Lp, k), idx (Lp, k)); k defaults to E+1 (simplex).
    ``fused=True`` uses the single-kernel pairwise+top-k (beyond-paper:
    the distance matrix never reaches HBM; see kernels/knn_fused.py) —
    identical results, ~470× less kernel HBM traffic at paper scale.
    """
    k = E + 1 if k is None else k
    impl_r = _resolve(impl)
    _tel("all_knn", impl_r, E=E, k=k, fused=fused, L=int(x.shape[-1]))
    if fused and impl_r != "ref":
        from repro.kernels.knn_fused import all_knn_fused
        return all_knn_fused(
            x, E=E, tau=tau, k=k, exclude_self=exclude_self,
            max_idx=max_idx, interpret=(impl_r == "interpret"))
    D = pairwise_distances(x, E=E, tau=tau, impl=impl, variant=variant)
    return topk_select(D, k=k, exclude_self=exclude_self, max_idx=max_idx,
                       impl=impl)


def all_knn_batch(
    X: jax.Array,
    *,
    E: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
    impl: str = "auto",
    block: tuple[int, int] = (128, 1024),
) -> tuple[jax.Array, jax.Array]:
    """All-kNN tables for B library series in ONE launch → (B, Lp, k).

    The CCM matrix engine primitive: batches the kNN axis so an E-group
    of the all-pairs matrix costs ceil(N/B) launches instead of N
    sequential ``lax.map`` steps. Slice b equals the fused per-series
    pipeline on ``X[b]`` with ``lax.top_k``'s tie order, and results are
    bit-invariant in B (the per-series oracle is the B = 1 launch); see
    kernels/knn_batch.py and ``ref.all_knn_batch``.
    """
    impl = _resolve(impl)
    _tel("all_knn_batch", impl, E=E, B=int(X.shape[0]),
         L=int(X.shape[-1]))
    if impl == "ref":
        return _ref.all_knn_batch(
            X, E=E, tau=tau, k=k, exclude_self=exclude_self, max_idx=max_idx)
    from repro.kernels.knn_batch import all_knn_batch as _batch_k
    return _batch_k(
        X, E=E, tau=tau, k=k, exclude_self=exclude_self, max_idx=max_idx,
        block=block, interpret=(impl == "interpret"))


def all_knn_multi_e(
    x: jax.Array,
    *,
    E_max: int,
    tau: int = 1,
    k: int | None = None,
    exclude_self: bool = True,
    max_idx=None,
    impl: str = "auto",
    block: tuple[int, int] = (128, 1024),
):
    """Incremental all-kNN for every E in 1..E_max in ONE O(E_max·Lp²) pass.

    Returns (dists, idx), both (E_max, Lp_1, k_max) padded with inf/-1;
    ``[E-1, :Lp_E, :k_E]`` equals the per-E ``pairwise_distances`` +
    ``topk_select`` result. This is the optimal-E sweep engine: the seed
    per-E pipeline costs O(ΣE·Lp²); the recurrence D_E = D_{E-1} + one
    rank-1 lag term collapses it (see kernels/knn_multi_e.py).
    """
    impl = _resolve(impl)
    _tel("all_knn_multi_e", impl, E_max=E_max, L=int(x.shape[-1]))
    if impl == "ref":
        return _ref.all_knn_multi_e(
            x, E_max=E_max, tau=tau, k=k, exclude_self=exclude_self,
            max_idx=max_idx)
    from repro.kernels.knn_multi_e import all_knn_multi_e as _multi_e
    return _multi_e(
        x, E_max=E_max, tau=tau, k=k, exclude_self=exclude_self,
        max_idx=max_idx, block=block, interpret=(impl == "interpret"))


def master_append(
    x: jax.Array,
    dists: jax.Array,
    idx: jax.Array,
    *,
    tau: int = 1,
    impl: str = "auto",
    block: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """Stream dt appended points into a multi-E master — O(Lp·(k+dt))/level.

    ``x`` is the FULL grown series; ``dists``/``idx`` are the stored
    ``all_knn_multi_e`` tables of its prefix (uniform k — masters).
    Returns the grown (E_max, L_new, k_m) tables, bit-identical to a
    cold rebuild on ``x`` (see the append section in kernels/ref.py for
    the strict-chain rules that make that hold). The impl knob selects
    the merge-stage engine — ref's ``top_k`` and the Pallas k-best merge
    (kernels/knn_append.py) are bit-identical selection over the same
    candidate bits.
    """
    impl = _resolve(impl)
    _tel("master_append", impl, E_max=int(dists.shape[0]),
         L=int(x.shape[-1]), dt=int(x.shape[-1]) - int(dists.shape[1]))
    if impl == "ref":
        return _ref.master_append(x, dists, idx, tau=tau)
    from repro.kernels.knn_append import master_append as _append_k
    return _append_k(x, dists, idx, tau=tau, block=block,
                     interpret=(impl == "interpret"))


def smap_gram(
    x: jax.Array,
    Y: jax.Array,
    *,
    E: int,
    tau: int = 1,
    Tp: int = 1,
    thetas: tuple[float, ...],
    exclude_self: bool = True,
    impl: str = "auto",
    block: tuple[int, int] = (128, 1024),
) -> tuple[jax.Array, jax.Array]:
    """S-Map weighted normal-equations accumulation for every (row, θ, target).

    Returns (G (rows, T, E+1, E+1), M (rows, T, N, E+1)) — the AᵀWA Gram
    matrices and AᵀWy moments the batched S-Map engine solves downstream
    (core/smap_engine.py). The kernel path streams library column tiles
    and never materializes any (rows, rows) object (kernels/smap_gram.py);
    the ref path holds one (rows, rows) weight matrix at a time (never the
    (T, rows, rows) stack).
    """
    impl = _resolve(impl)
    thetas = tuple(float(t) for t in thetas)
    _tel("smap_gram", impl, E=E, thetas=len(thetas), L=int(x.shape[-1]))
    if impl == "ref":
        return _ref.smap_gram(x, Y, E=E, tau=tau, Tp=Tp, thetas=thetas,
                              exclude_self=exclude_self)
    from repro.kernels.smap_gram import smap_gram as _smap_gram_k
    return _smap_gram_k(
        x, Y, E=E, tau=tau, Tp=Tp, thetas=thetas, exclude_self=exclude_self,
        block=block, interpret=(impl == "interpret"))


def lookup(
    Y: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    *,
    offset: int = 0,
    impl: str = "auto",
    block: tuple[int, int] = (128, 128),
) -> jax.Array:
    """Batched simplex lookup → (N, Lp) predictions (Alg. 3)."""
    impl = _resolve(impl)
    _tel("lookup", impl, N=int(Y.shape[0]))
    if impl == "ref":
        return _ref.lookup(Y, idx, w, offset=offset)
    return _lookup_k.lookup(Y, idx, w, offset=offset, block=block,
                            interpret=(impl == "interpret"))


def lookup_rho(
    Y: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    *,
    offset: int = 0,
    impl: str = "auto",
    block: tuple[int, int] = (128, 128),
) -> jax.Array:
    """Fused lookup + Pearson ρ per target → (N,) (paper §3.4 fused path)."""
    impl = _resolve(impl)
    _tel("lookup_rho", impl, N=int(Y.shape[0]))
    if impl == "ref":
        return _ref.lookup_rho(Y, idx, w, offset=offset)
    return _lookup_k.lookup_rho(Y, idx, w, offset=offset, block=block,
                                interpret=(impl == "interpret"))
