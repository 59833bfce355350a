"""Operations per second of the incremental kNN-master append, in Gop/s.

The operations are the distance arithmetic the window's appends need,
from their shapes: per series and per level e (embedding dimension
E = e + 1, Lp_e = L_old − e·τ rows before the append), each of the dt
new rows is compared with every column of the grown level (the slab),
3 operations (subtract, square, accumulate) per lag term:

    3 · Σ_e (e + 1) · dt · (Lp_e + dt)

The stored candidates are not recomputed (the append carries their
squared distances), and the merges that follow are selection, no
arithmetic, so they add time but no operations.

The time is the device time of the whole append program, over all
chips: the union of its two kernels (``knn_append``, the new rows'
selection, and ``knn_append_fold``, the old rows' fold) and every other
op of the ``jit_panel_master_append_sq`` program in the trace. So the
number moves with everything an append costs the chip: a rate to
compare runs of one program by, not a throughput of the vector unit,
for which no published v5e peak exists. Nothing to read where the trace holds no
such op (a program that names them otherwise) or the window ran no
append.
"""

import devtrace

KERNELS = ("knn_append", "knn_append_fold")
PROGRAM = "jit_panel_master_append_sq/"


def append_ops(work: dict) -> int:
    if work["op"] != "knn_append":
        return 0
    dt, tau = work["dt"], work["tau"]
    per_series = 0
    for e in range(work["E_max"]):
        Lp = work["L_old"] - e * tau
        per_series += 3 * (e + 1) * dt * (Lp + dt)
    return work["series"] * per_series


def append_device_s(trace) -> float:
    """Device seconds of the append program, summed over the chips."""
    want = {f"kernel:{k}" for k in KERNELS}
    return sum(devtrace.union_ns([(s, e) for key, s, e in ops
                                  if key in want or key.startswith(PROGRAM)])
               for ops in trace.devices.values()) / 1e9


def read(ctx):
    trace = ctx["trace"]
    ops = sum(append_ops(w) for w in ctx["outcome"].work)
    t = append_device_s(trace) if trace is not None else 0.0
    if not ops or t <= 0:
        return None
    return ops / t / 1e9
