"""Sparse linear-algebra ops on the formats in :mod:`repro.sparse.formats`.

These are the jnp reference paths (pure JAX, shardable, differentiable).  On
a TPU, Stage 2's single-vector product runs the Pallas kernel of
:mod:`repro.kernels.coo_spmv` over a chunked layout of the same row-sorted
COO; ``repro.sparse.distributed`` wraps these paths in shard_map.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sparse.formats import COO, CSR, BlockELL, coo_from_edges

Array = jax.Array


def spmv_coo(m: COO, x: Array, *, sorted_rows: bool | None = None) -> Array:
    """y = W @ x  via gather + segment_sum (the TPU-native cusparseDcsrmv).

    Accumulates in fp32 regardless of storage dtype — Lanczos needs it.
    ``sorted_rows=None`` (default) trusts the matrix's own ``sorted_rows``
    tag; segment_sum with ``indices_are_sorted=True`` over unsorted rows is
    undefined behaviour on accelerator backends, so never override to True
    unless you know the layout.
    """
    if sorted_rows is None:
        sorted_rows = m.sorted_rows
    gathered = m.val.astype(jnp.float32) * x[m.col].astype(jnp.float32)
    y = jax.ops.segment_sum(
        gathered, m.row, num_segments=m.shape[0], indices_are_sorted=sorted_rows
    )
    return y.astype(x.dtype)


def spmm_coo(m: COO, x: Array, *, sorted_rows: bool | None = None) -> Array:
    """Y = W @ X for dense X [n, d] — the block-Lanczos / GNN aggregation op.

    Implemented as d statically-unrolled 1-D segment sums rather than one
    segment_sum over [nnz, d] rows: XLA lowers the rank-2 scatter-add to a
    serial per-row loop on CPU (~30× slower at nnz ≈ 1M) and gains nothing
    on TPU, where the fused multi-vector stream is the Pallas ``ell_spmm``
    kernel's job anyway.  Column count d is static under jit, so the unroll
    is free.
    """
    if sorted_rows is None:
        sorted_rows = m.sorted_rows
    val = m.val.astype(jnp.float32)
    cols = [
        jax.ops.segment_sum(
            val * x[:, j][m.col].astype(jnp.float32),
            m.row,
            num_segments=m.shape[0],
            indices_are_sorted=sorted_rows,
        )
        for j in range(x.shape[1])
    ]
    return jnp.stack(cols, axis=1).astype(x.dtype)


def spmv_csr(m: CSR, x: Array) -> Array:
    return spmv_coo(COO(m.row, m.indices, m.data, m.shape), x)


def spmv_blockell(m: BlockELL, x: Array) -> Array:
    """BlockELL SpMV, jnp path: dense gather over the padded layout + COO tail."""
    nb, br, w = m.cols.shape
    gathered = m.vals.astype(jnp.float32) * x[m.cols].astype(jnp.float32)
    y = gathered.sum(axis=-1).reshape(nb * br)[: m.shape[0]]
    y = y + spmv_coo(m.tail, x).astype(jnp.float32)
    return y.astype(x.dtype)


def spmm_blockell(m: BlockELL, x: Array) -> Array:
    """Y = W @ X for dense X [n, b] on the BlockELL layout, jnp path.

    One pass over the padded ELL body serves all b columns (the gather
    fetches [nb, br, w, b] tiles and the width axis is contracted for every
    column at once) — the arithmetic-intensity win the block-Lanczos SpMM
    kernel exploits (DESIGN.md §2).  Heavy-tail rows go through the COO SpMM.
    """
    nb, br, w = m.cols.shape
    gathered = m.vals.astype(jnp.float32)[..., None] * x[m.cols].astype(jnp.float32)
    y = gathered.sum(axis=2).reshape(nb * br, -1)[: m.shape[0]]
    y = y + spmm_coo(m.tail, x).astype(jnp.float32)
    return y.astype(x.dtype)


def degrees(m: COO) -> Array:
    """D_ii = sum_j W_ij (the paper computes this as W @ 1)."""
    return spmv_coo(m, jnp.ones((m.shape[1],), m.val.dtype))


def normalize_rw(m: COO, deg: Array | None = None) -> COO:
    """D^{-1} W — the paper's Alg. 2 (ScaleElements kernel).  Row-stochastic."""
    d = degrees(m) if deg is None else deg
    inv = jnp.where(d > 0, 1.0 / d, 0.0)
    return COO(m.row, m.col, m.val * inv[m.row], m.shape, sorted_rows=m.sorted_rows)


def normalize_sym(m: COO, deg: Array | None = None) -> COO:
    """D^{-1/2} W D^{-1/2} — symmetric normalization (our Lanczos-friendly
    form; same spectrum as D^{-1}W, see DESIGN.md §8)."""
    d = degrees(m) if deg is None else deg
    inv_sqrt = jnp.where(d > 0, jax.lax.rsqrt(d.astype(jnp.float32)), 0.0).astype(m.val.dtype)
    return COO(m.row, m.col, m.val * inv_sqrt[m.row] * inv_sqrt[m.col], m.shape,
               sorted_rows=m.sorted_rows)


def symmetrize_coo(m: COO) -> COO:
    """(W + Wᵀ)/2 expressed in host-free COO form: concat + re-sort not
    possible inside jit with static shapes, so this doubles nnz and relies on
    duplicate-tolerant segment sums downstream.  Use in pipelines that accept
    duplicate coordinates (all our consumers do).

    The result is tagged ``sorted_rows=False``: the appended transpose half
    carries the original *column* ids as rows, which are not sorted — feeding
    the output into a segment sum with ``indices_are_sorted=True`` silently
    corrupts results on accelerator backends.  :func:`sort_coo_rows` restores
    a sorted layout on device when downstream cost matters.
    """
    row = jnp.concatenate([m.row, m.col])
    col = jnp.concatenate([m.col, m.row])
    val = jnp.concatenate([m.val, m.val]) * 0.5
    return COO(row, col, val, m.shape, sorted_rows=False)


def sort_coo_rows(m: COO) -> COO:
    """Row-major re-sort *on device* (jit-safe, static nnz).  A stable sort
    on the row ids preserves in-row column order, which is all the segment
    sums and the CSR/ELL converters care about."""
    if m.sorted_rows:
        return m
    order = jnp.argsort(m.row, stable=True)
    return COO(m.row[order], m.col[order], m.val[order], m.shape, sorted_rows=True)


def coo_identity_minus(m: COO) -> COO:
    """I - M for a COO with no diagonal guarantees: appends an explicit
    diagonal and negates M.  Host-side helper for building L_sym etc."""
    import numpy as np

    n = m.shape[0]
    row = jnp.concatenate([m.row, jnp.arange(n, dtype=m.row.dtype)])
    col = jnp.concatenate([m.col, jnp.arange(n, dtype=m.col.dtype)])
    val = jnp.concatenate([-m.val, jnp.ones((n,), m.val.dtype)])
    order = np.lexsort((np.asarray(col), np.asarray(row)))
    return COO(row[order], col[order], val[order], m.shape)
