"""Device time of Stage 2's Gram-Schmidt per operator application (one
Lanczos step each): the device seconds of the operations under the
program's ``orthogonalize`` scope in the traced window
(``bench/scopes.py``), over the applications the window's jobs made."""
from bench import scopes


def read(ctx):
    apps = scopes.applications(ctx)
    smap = scopes.stage2_scopes(ctx) if apps else None
    if smap is None:
        return None
    secs = scopes.scope_seconds(ctx["trace"], smap, "orthogonalize",
                                ctx["window"])
    return 1e3 * secs / sum(apps) if secs > 0 else None
