"""Mean time the server takes to execute one coalesced batch, in ms.

Source: the program's counters ``serve_exec_seconds`` (claim to results,
summed over executed batches) and ``serve_batches``, their deltas across
the window. Reads no peak. Nothing to read where the program keeps no
``serve_exec_seconds`` counter.
"""


def read(ctx):
    c = ctx["window"].counters
    batches = c.get("serve_batches", 0)
    if not batches or "serve_exec_seconds" not in c:
        return None
    return 1e3 * c["serve_exec_seconds"] / batches
