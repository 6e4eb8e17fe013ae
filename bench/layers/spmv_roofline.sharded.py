"""Stage 2's row-sharded products' share of their roofline, mean over the
chips: on each chip, the least time of its own products, each over its own
nonzeros (``bench/work/spmv.py:work(nnz_chip, n)``, ``bench/row_shards.py``)
at the peak rate or bandwidth (``bench/peaks.json``), over the device
seconds under the program's ``spmv`` scope and not under ``spmv_gather``
on that chip.  Nothing where Stage 2 is not row-sharded."""
from bench import deploy, harness, row_shards, scopes


def read(ctx):
    apps = scopes.applications(ctx)
    chips = row_shards.chip_seconds(ctx) if apps else None
    nnz = row_shards.chip_nnz(ctx) if chips is not None else None
    if nnz is None:
        return None
    secs = {plane: c["local"] for plane, c in chips.items()}
    work = harness.kernel_work("spmv").work
    n = deploy.n_nodes(ctx["cfg"])
    pk = ctx["peaks"]
    shares, bound = [], []
    for c, plane in enumerate(sorted(secs, key=_chip)):
        if secs[plane] <= 0:
            return None
        ops = nbytes = 0
        for job, count in zip(nnz, apps):
            o, b = work(nnz=job[c], n=n)
            ops += o * count
            nbytes += b * count
        t_ops, t_mem = ops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"]
        shares.append(100.0 * max(t_ops, t_mem) / secs[plane])
        bound.append("compute" if t_ops >= t_mem else "memory")
    return {"value": sum(shares) / len(shares), "bound": bound[0],
            "chips": shares}


def _chip(plane: str) -> int:
    """``/device:TPU:2`` → 2: the chips' order, that of the mesh's rows."""
    return int(plane.rsplit(":", 1)[1])
