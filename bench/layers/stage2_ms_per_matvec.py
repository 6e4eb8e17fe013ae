"""Stage 2's wall time per application of the sparse operator: ``stage2_s``
over the applications each job made, counted from its reported Lanczos
restarts and the basis and keep sizes the program reports
(``bench/work/spmv.py``).  Nothing where Stage 2 runs another solver."""
from bench import harness


def read(ctx):
    sizes = ctx.get("lanczos")
    if not ctx.get("stage_s") or sizes is None:
        return None
    spmv = harness.kernel_work("spmv")
    count = sum(spmv.matvecs(restarts=j["restarts"], **sizes)
                for j in ctx["jobs"])
    secs = sum(s[1] for s in ctx["stage_s"])
    return 1e3 * secs / count if count else None
