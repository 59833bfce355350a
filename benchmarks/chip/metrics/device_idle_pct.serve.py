"""Share of the traced window in which no op ran on the chip, in %.

Source: the device trace (``devtrace.Trace``): 1 − (union of the
``XLA Ops`` intervals ÷ the window), averaged over the chips. Reads no
peak. Nothing to read without a trace.
"""

import idle


def read(ctx):
    return idle.idle_pct(ctx["trace"])
