"""Mean time a served request waits in the queue before a batch takes it, in ms.

Source: the program's counters ``serve_queue_wait_seconds`` (submit to
claim, summed over the requests taken into batches) and
``serve_claimed`` (those requests), their deltas across the window.
Reads no peak. Nothing to read where no request was claimed, or where
the program keeps no such counters.
"""


def read(ctx):
    c = ctx["window"].counters
    claimed = c.get("serve_claimed", 0)
    if not claimed:
        return None
    return 1e3 * c.get("serve_queue_wait_seconds", 0.0) / claimed
