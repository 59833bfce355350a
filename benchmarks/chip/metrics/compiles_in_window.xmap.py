"""Backend compiles (XLA + Mosaic) inside the measured window.

Source: JAX's compile events, counted by ``harness.CompileClock`` while
the window is open. Reads no peak. Every shape is warmed in set-up, so
anything above 0 is a compile the window paid for.
"""


def read(ctx):
    return ctx["window"].compiles
