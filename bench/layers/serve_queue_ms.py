"""Mean time a served request waited, from when it was due until the
flush that served it began (the batcher's queue and max-wait)."""


def read(ctx):
    q = ctx.get("queue_ms")
    return sum(q) / len(q) if q else None
