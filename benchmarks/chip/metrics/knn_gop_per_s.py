"""Operations per second of the kNN kernels, in Gop/s.

The operations are those the algorithm needs for the window's calls,
from their shapes: the embedding distance costs 3 operations (subtract,
square, add) per lag term per pair of points, so

* ``knn`` (one library series at one E, the ``knn_batch`` kernel):
  3 · E · Lp² with Lp = L − (E − 1)τ;
* ``knn_multi_e`` (one series' optimal-E pass, E = 1..E_max
  incrementally, the ``knn_multi_e`` kernel): 3 · Σ_E Lp_E².

Each library is counted once, also where a mesh computes it on two
chips. The time is the summed device time of the ``knn_batch`` and
``knn_multi_e`` kernel ops in the trace, over all chips. Reads no peak:
these kernels run on the vector unit, for which no published v5e peak
exists.
"""

KERNELS = ("knn_batch", "knn_multi_e")


def knn_ops(work: dict) -> int:
    if work["op"] == "knn":
        return 3 * work["E"] * work["Lp"] ** 2 * work["libs"]
    if work["op"] == "knn_multi_e":
        L, tau = work["L"], work["tau"]
        return 3 * work["series"] * sum(
            (L - (E - 1) * tau) ** 2 for E in range(1, work["E_max"] + 1))
    return 0


def read(ctx):
    trace = ctx["trace"]
    ops = sum(knn_ops(w) for w in ctx["outcome"].work)
    t = trace.kernel_s(KERNELS) if trace is not None else 0.0
    if not ops or t <= 0:
        return None
    return ops / t / 1e9
