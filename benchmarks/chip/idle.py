"""Idle share of a traced window, shared by the ``device_idle_pct.*``
metrics (one per end-to-end metric they move)."""


def idle_pct(trace):
    if trace is None or trace.window_s() <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
