"""``kmeans_iter``'s share of its roofline in Stage 3: the least time for
the Lloyd iterations each job reported (``bench/work/kmeans_iter.py``) over
the kernel's device time in the traced window."""
from bench import deploy, roofline


def read(ctx):
    cfg = ctx["cfg"]
    n, k = deploy.n_nodes(cfg), cfg["n_clusters"]
    shapes = [dict(n=n, k=k, d=k, iterations=j["km_iters"])
              for j in ctx.get("jobs", [])]
    return roofline.share(ctx, "kmeans_iter", shapes)
