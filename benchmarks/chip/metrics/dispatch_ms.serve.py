"""Mean host time to build and enqueue one engine launch, in ms.

Source: the program's counters ``edm_dispatch_seconds`` (host seconds
inside each launch call of ``core.ccm.drive_batched``) and
``edm_launches``, their deltas across the window. Reads no peak.
Nothing to read where the program keeps no ``edm_dispatch_seconds``.
"""


def read(ctx):
    c = ctx["window"].counters
    launches = c.get("edm_launches", 0)
    if not launches or "edm_dispatch_seconds" not in c:
        return None
    return 1e3 * c["edm_dispatch_seconds"] / launches
