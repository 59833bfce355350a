"""Requests per executed batch in the server, over the window.

Source: the program's counters ``serve_requests`` (submitted) and
``serve_batches`` (executed), their deltas across the window. Reads no
peak.
"""


def read(ctx):
    c = ctx["window"].counters
    batches = c.get("serve_batches", 0)
    if not batches:
        return None
    return c.get("serve_requests", 0) / batches
