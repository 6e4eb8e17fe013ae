"""Device time of Stage 2's Gram-Schmidt per operator application (one
Lanczos step each) where Stage 2 is row-sharded over the chips: the device
seconds of the operations under the program's ``orthogonalize`` scope in
the traced window, its cross-chip all-reduces among them
(``bench/row_shards.py``, which compiles the map over the cell's chips),
mean over the chips, over the applications the window's jobs made.
Nothing where Stage 2 is not row-sharded."""
from bench import row_shards, scopes


def read(ctx):
    apps = scopes.applications(ctx)
    secs = row_shards.chip_mean(ctx, "orthogonalize") if apps else None
    return None if secs is None else 1e3 * secs / sum(apps)
