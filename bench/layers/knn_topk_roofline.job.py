"""``knn_topk``'s share of its roofline in Stage 1 of a job: the least time
for the exact kNN search over the positions (``bench/work/knn_topk.py``)
over the kernel's device time in the traced window."""
from bench import deploy, roofline


def read(ctx):
    cfg = ctx["cfg"]
    n = deploy.n_nodes(cfg)
    shapes = [dict(n_q=n, n_p=n, d=3, k=cfg["pipeline"]["knn_k"])
              for _ in ctx.get("jobs", [])]
    return roofline.share(ctx, "knn_topk", shapes)
