"""Stage 2's operator applications' share of their roofline: the least
time of the applications the window's jobs made, each one product over its
job's nonzeros (``bench/work/spmv.py``) at the peak rate or bandwidth
(``bench/peaks.json``), over the device seconds under the program's
``spmv`` scope (``bench/scopes.py``)."""
from bench import deploy, harness, scopes


def read(ctx):
    apps = scopes.applications(ctx)
    smap = scopes.stage2_scopes(ctx) if apps else None
    if smap is None:
        return None
    secs = scopes.scope_seconds(ctx["trace"], smap, "spmv", ctx["window"])
    if secs <= 0:
        return None
    work = harness.kernel_work("spmv").work
    n = deploy.n_nodes(ctx["cfg"])
    ops = nbytes = 0
    for j, count in zip(ctx["jobs"], apps):
        o, b = work(nnz=j["nnz"], n=n)
        ops += o * count
        nbytes += b * count
    pk = ctx["peaks"]
    t_ops, t_mem = ops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"]
    return {"value": 100.0 * max(t_ops, t_mem) / secs,
            "bound": "compute" if t_ops >= t_mem else "memory"}
