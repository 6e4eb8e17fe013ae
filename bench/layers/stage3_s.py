"""Stage 3's wall time per job: the host clock around the stage-API call
(ending in ``block_until_ready``) under the ``stage3`` span of a
traced run, averaged over the window's jobs."""


def read(ctx):
    times = [s[2] for s in ctx.get("stage_s", [])]
    return sum(times) / len(times) if times else None
