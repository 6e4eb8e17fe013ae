"""A kernel's share of its roofline, from its device time in the trace.

The least time the chip could take is the larger of the kernel's
operations over the peak rate and its bytes over the memory bandwidth (the
work comes from ``work/<kernel>.py``, the peaks from ``peaks.json``).  The
share is that least time over the kernel's device time.  The kernels here
compute in float32 at full precision (six bf16 passes on the MXU) and are
held against the published bf16 peak, so a share above a sixth is out of
their reach on compute.
"""
from __future__ import annotations

from typing import Iterable, Optional

from bench import harness, trace


def share(ctx: dict, kernel: str, shapes: Iterable[dict]) -> Optional[dict]:
    """``{"value": percent, "bound": "compute"|"memory"}`` for the calls of
    ``kernel`` in the traced window, whose shapes are ``shapes``; None where
    the trace holds none."""
    secs = trace.kernel_seconds(ctx["trace"], kernel, ctx["window"])
    if secs <= 0:
        return None
    work = harness.kernel_work(kernel).work
    ops = nbytes = 0
    for s in shapes:
        o, b = work(**s)
        ops += o
        nbytes += b
    pk = ctx["peaks"]
    t_ops, t_mem = ops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"]
    least = max(t_ops, t_mem)
    return {"value": 100.0 * least / secs,
            "bound": "compute" if t_ops >= t_mem else "memory"}
