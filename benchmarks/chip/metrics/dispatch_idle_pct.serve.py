"""Share of the traced window in which the chip waits while the host
prepares and enqueues a served batch's work, in %.

Source: the device trace (``devtrace.Trace``): the idle gaps whose
innermost open program span is ``session.ccm_batch`` or
``engine.launch``, summed, divided by the number of chips, over the
window. Reads no peak. Nothing to read without a trace, or where the
trace holds neither span (a program that does not bridge them).
"""

LABELS = ("session.ccm_batch", "engine.launch")


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.devices or trace.window_s() <= 0:
        return None
    if not any(name in LABELS for name, _, _ in trace.host):
        return None
    idle_ns = sum(e - s for label, s, e in trace.idle_gaps()
                  if label in LABELS)
    return 100.0 * idle_ns / 1e9 / len(trace.devices) / trace.window_s()
