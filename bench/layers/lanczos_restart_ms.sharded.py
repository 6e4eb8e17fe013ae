"""Device time of one Lanczos restart where Stage 2 is row-sharded over
the chips: the device seconds of the operations under the program's
``restart`` scope (the projected ``eigh``, run on every chip, the Ritz
rotation of each chip's rows, the thick-restart copy) in the traced window
(``bench/row_shards.py``), mean over the chips, over the restart cycles the
window's jobs reported.  Nothing where Stage 2 is not row-sharded."""
from bench import row_shards


def read(ctx):
    restarts = sum(j["restarts"] for j in ctx.get("jobs", []))
    secs = row_shards.chip_mean(ctx, "restart") if restarts else None
    return None if secs is None else 1e3 * secs / restarts
