"""``knn_topk``'s share of its roofline in serving: each flush searches the
whole padded batch against the pool (``bench/work/knn_topk.py``), over the
kernel's device time in the traced window."""
from bench import roofline, trace


def read(ctx):
    calls = trace.kernel_calls(ctx["trace"], "knn_topk", ctx["window"])
    shapes = [dict(n_q=ctx["mix"]["batch_size"], n_p=ctx["pool_n"], d=3,
                   k=ctx["cfg"]["pipeline"]["knn_k"])] * calls
    return roofline.share(ctx, "knn_topk", shapes)
