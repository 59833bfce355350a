"""Operations per second of the lookup/ρ kernel, in Gop/s.

The operations are those the algorithm needs for the window's calls:
the simplex prediction is a k-term weighted sum (a multiply and an add
per term) per prediction row per target, so a ``lookup`` work item
(libraries × targets at one E) needs 2 · k · rows · targets · libs
(``benchmarks/bench_roofline.py``'s count). The time is the summed
device time of the ``lookup_rho`` and ``lookup`` kernel ops in the
trace, over all chips. Reads no peak (vector-unit work).
"""

KERNELS = ("lookup_rho", "lookup")


def lookup_ops(work: dict) -> int:
    if work["op"] != "lookup":
        return 0
    return 2 * work["k"] * work["rows"] * work["targets"] * work["libs"]


def read(ctx):
    trace = ctx["trace"]
    ops = sum(lookup_ops(w) for w in ctx["outcome"].work)
    t = trace.kernel_s(KERNELS) if trace is not None else 0.0
    if not ops or t <= 0:
        return None
    return ops / t / 1e9
