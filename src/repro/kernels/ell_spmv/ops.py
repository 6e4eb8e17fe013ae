"""Public jit'd wrapper: BlockELL(+tail) SpMV with backend dispatch.

``spmv(m: BlockELL, x)`` — the drop-in matvec for the Lanczos eigensolver.
The Pallas kernel covers the ELL body; the COO overflow tail (heavy-degree
rows beyond the ELL width) goes through segment-sum and is added in.

The kernel does not compile for a TPU: Mosaic refuses its in-kernel gather
``jnp.take(x, cols)`` (:data:`MOSAIC_REFUSAL`).  So ``impl="auto"`` runs the
XLA path (``ell_spmv_ref``) on every backend, ``impl="pallas"`` on a TPU
raises with the compiler's reason, and the kernel body runs only in
interpret mode (tests) until it is rewritten without the gather.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels._util import ell_use_pallas
from repro.kernels.ell_spmv.kernel import ell_spmv_pallas
from repro.kernels.ell_spmv.ref import ell_spmv_ref
from repro.sparse.formats import BlockELL
from repro.sparse.ops import spmv_coo

# What the TPU compiler (jax 0.9.0, v5e) says about the in-kernel gather.
MOSAIC_REFUSAL = ("NotImplementedError: Only 2D gather is supported "
                  "(the [n] gather jnp.take(x, cols, axis=0))")


@partial(jax.jit, static_argnames=("impl", "interpret", "block_rows"))
def ell_spmv(
    m: BlockELL,
    x: jax.Array,
    *,
    impl: str = "auto",  # "auto" | "pallas" | "ref"
    interpret: bool | None = None,
    block_rows: int = 1024,
):
    nb, br, w = m.cols.shape
    n_rows_padded = nb * br
    cols2d = m.cols.reshape(n_rows_padded, w)
    vals2d = m.vals.reshape(n_rows_padded, w)

    if not ell_use_pallas("ell_spmv", MOSAIC_REFUSAL, impl, interpret):
        body = ell_spmv_ref(x, cols2d, vals2d)
    else:
        blk = block_rows
        while n_rows_padded % blk:
            blk //= 2
        body = ell_spmv_pallas(
            x.astype(jnp.float32), cols2d, vals2d, block_rows=max(blk, 1), interpret=True
        )
    y = body[: m.shape[0]]
    y = y + spmv_coo(m.tail, x).astype(jnp.float32)
    return y.astype(x.dtype)
