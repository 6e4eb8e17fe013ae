"""Seconds per job of collective operations (ppermute, all-gather, psum)
during which no other operation ran on that chip, mean over chips."""
from bench import trace


def read(ctx):
    jobs = len(ctx.get("jobs", []))
    if len(ctx["trace"]["device"]) < 2 or not jobs:
        return None
    return trace.collective_exposed_s(ctx["trace"], ctx["window"]) / jobs
