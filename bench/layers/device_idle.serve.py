"""Percent of the traced window in which no operation ran on the chip
(mean over chips): one minus the union of the device's operations over
the window."""
from bench import trace


def read(ctx):
    if not ctx["trace"]["device"]:
        return None
    return trace.idle_share(ctx["trace"], ctx["window"])
