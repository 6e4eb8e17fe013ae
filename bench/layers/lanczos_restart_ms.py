"""Device time of one Lanczos restart: the device seconds of the
operations under the program's ``restart`` scope (the projected ``eigh``,
the Ritz rotation, the thick-restart copy) in the traced window
(``bench/scopes.py``), over the restart cycles the window's jobs
reported."""
from bench import scopes


def read(ctx):
    restarts = sum(j["restarts"] for j in ctx.get("jobs", []))
    smap = scopes.stage2_scopes(ctx) if restarts else None
    if smap is None:
        return None
    secs = scopes.scope_seconds(ctx["trace"], smap, "restart", ctx["window"])
    return 1e3 * secs / restarts if secs > 0 else None
