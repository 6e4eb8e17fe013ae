"""Mean wall time of one flush: ``serve_fn`` on one padded batch, ending
in ``block_until_ready``."""


def read(ctx):
    c = ctx.get("call_ms")
    return sum(c) / len(c) if c else None
