"""Device time of the all-gather of ``x`` in one application of Stage 2's
row-sharded operator: the device seconds of the operations under the
program's ``spmv_gather`` scope in the traced window (``bench/row_shards.py``),
mean over the chips, over the applications the window's jobs made.
Nothing where Stage 2 is not row-sharded."""
from bench import row_shards, scopes


def read(ctx):
    apps = scopes.applications(ctx)
    secs = row_shards.chip_mean(ctx, "gather") if apps else None
    return None if secs is None else 1e3 * secs / sum(apps)
