"""Mean host time the server spends applying one append, in ms.

Source: the program's counters ``serve_append_seconds`` (the
scheduler's execution of each append: screening and writing the delta,
dispatching the kNN master's append program, and the WAL write) and
``serve_appends``, their deltas across the window. The append
program's device time is not in it: the scheduler dispatches it and
moves on, and the next ccm batch waits for it on the chip
(``append_gop_per_s`` reads that time). Reads no peak. Nothing to read
where no append ran in the window, or where the program keeps no
``serve_append_seconds`` counter.
"""


def read(ctx):
    c = ctx["window"].counters
    appends = c.get("serve_appends", 0)
    if not appends or "serve_append_seconds" not in c:
        return None
    return 1e3 * c["serve_append_seconds"] / appends
