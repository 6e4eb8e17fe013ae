"""Device time of one application of Stage 2's operator: the device
seconds of the operations under the program's ``spmv`` scope in the traced
window (``bench/scopes.py``), over the applications the window's jobs made
(``scopes.applications``).  Nothing where the program has no such scope."""
from bench import scopes


def read(ctx):
    apps = scopes.applications(ctx)
    smap = scopes.stage2_scopes(ctx) if apps else None
    if smap is None:
        return None
    secs = scopes.scope_seconds(ctx["trace"], smap, "spmv", ctx["window"])
    return 1e3 * secs / sum(apps) if secs > 0 else None
