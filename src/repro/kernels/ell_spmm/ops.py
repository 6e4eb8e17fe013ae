"""Public jit'd wrapper: BlockELL(+tail) multi-vector SpMM with backend dispatch.

``ell_spmm(m: BlockELL, x)`` with ``x: [n, b]`` — the drop-in matmat for the
block-Lanczos eigensolver.  The Pallas kernel covers the ELL body; the COO
overflow tail (heavy-degree rows beyond the ELL width) goes through the
segment-sum SpMM and is added in.

The kernel does not compile for a TPU: Mosaic refuses its in-kernel gather
``jnp.take(x, cols)`` (:data:`MOSAIC_REFUSAL`).  So ``impl="auto"`` runs the
XLA path (``ell_spmm_ref``) on every backend, ``impl="pallas"`` on a TPU
raises with the compiler's reason, and the kernel body runs only in
interpret mode (tests) until it is rewritten without the gather.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels._util import ell_use_pallas
from repro.kernels.ell_spmm.kernel import ell_spmm_cheb_pallas, ell_spmm_pallas
from repro.kernels.ell_spmm.ref import ell_spmm_cheb_ref, ell_spmm_ref
from repro.sparse.formats import BlockELL
from repro.sparse.ops import spmm_coo

# What the TPU compiler (jax 0.9.0, v5e) says about the in-kernel gather.
MOSAIC_REFUSAL = ("ValueError: Shape mismatch in input, indices and output "
                  "(the [n, b] gather jnp.take(x, cols, axis=0))")


@partial(jax.jit, static_argnames=("impl", "interpret", "block_rows"))
def ell_spmm(
    m: BlockELL,
    x: jax.Array,  # [n, b]
    *,
    impl: str = "auto",  # "auto" | "pallas" | "ref"
    interpret: bool | None = None,
    block_rows: int = 512,
):
    assert x.ndim == 2, f"ell_spmm wants [n, b] multi-vectors, got {x.shape}"
    nb, br, w = m.cols.shape
    n_rows_padded = nb * br
    cols2d = m.cols.reshape(n_rows_padded, w)
    vals2d = m.vals.reshape(n_rows_padded, w)

    if not ell_use_pallas("ell_spmm", MOSAIC_REFUSAL, impl, interpret):
        body = ell_spmm_ref(x, cols2d, vals2d)
    else:
        blk = block_rows
        while n_rows_padded % blk:
            blk //= 2
        body = ell_spmm_pallas(
            x.astype(jnp.float32), cols2d, vals2d, block_rows=max(blk, 1), interpret=True
        )
    y = body[: m.shape[0]]
    y = y + spmm_coo(m.tail, x).astype(jnp.float32)
    return y.astype(x.dtype)


@partial(jax.jit, static_argnames=("impl", "interpret", "block_rows"))
def ell_spmm_cheb_step(
    m: BlockELL,
    x: jax.Array,  # [n, b] current iterate T_j
    prev: jax.Array,  # [n, b] previous iterate T_{j-1}
    ca: jax.Array,  # scalar: 4/(hi−lo) · sign
    cb: jax.Array,  # scalar: −2(hi+lo)/(hi−lo)
    *,
    impl: str = "auto",  # "auto" | "pallas" | "ref"
    interpret: bool | None = None,
    block_rows: int = 512,
):
    """One fused Chebyshev three-term step: ``ca·(A x) + cb·x − prev``.

    On the Pallas path the AXPY epilogue is fused into the ELL SpMM pass, so
    the [n, b] iterates are written once instead of read back for three
    separate elementwise ops; the COO tail contributes ``ca·(A_tail x)``
    outside the kernel (HYB layout, same as ``ell_spmm``).
    """
    assert x.ndim == 2, f"ell_spmm_cheb_step wants [n, b] multi-vectors, got {x.shape}"
    assert prev.shape == x.shape, (prev.shape, x.shape)
    nb, br, w = m.cols.shape
    n_rows_padded = nb * br
    n = m.shape[0]
    cols2d = m.cols.reshape(n_rows_padded, w)
    vals2d = m.vals.reshape(n_rows_padded, w)
    ca = jnp.asarray(ca, jnp.float32)
    cb = jnp.asarray(cb, jnp.float32)

    pad = ((0, n_rows_padded - n), (0, 0))
    xp = jnp.pad(x.astype(jnp.float32), pad)
    pp = jnp.pad(prev.astype(jnp.float32), pad)

    if not ell_use_pallas("ell_spmm", MOSAIC_REFUSAL, impl, interpret):
        body = ell_spmm_cheb_ref(xp, cols2d, vals2d, pp, ca, cb)
    else:
        blk = block_rows
        while n_rows_padded % blk:
            blk //= 2
        body = ell_spmm_cheb_pallas(
            xp,
            cols2d,
            vals2d,
            pp,
            jnp.stack([ca, cb]).reshape(1, 2),
            block_rows=max(blk, 1),
            interpret=True,
        )
    y = body[:n]
    y = y + ca * spmm_coo(m.tail, x).astype(jnp.float32)
    return y.astype(x.dtype)
