"""Random-hyperplane LSH hashing Pallas kernel (TPU target) — the Stage-1
approximate-NN candidate generator's compute core.

Per table t and point x: project onto ``n_bits`` random hyperplane normals,
pack the sign pattern into an int32 bucket code, and emit one extra scalar
projection (the *tie-break*, used by the wrapper to order points inside a
bucket — DESIGN.md §12).  Both outputs fall out of a single
[block_n, d] × [d, B_pad] MXU matmul per grid step: the plane block holds
the ``n_bits`` bit normals in columns 0..n_bits-1, the tie-break direction
in column ``n_bits``, and zeros beyond — so bit packing is one VPU
compare + masked power-of-two contraction over the projection tile.

Grid = (n_tables, n // block_n); tables are independent (no revisited
output blocks, unlike the knn_topk accumulator), so grid order is free.
Padded plane columns project to exactly 0.0 → sign bit 1, but their packing
weight is 0, so padding never perturbs codes.  Padded *rows* (n → block_n
multiple, zero vectors) produce well-defined garbage codes the wrapper
slices off.

VMEM working set per step: x tile (block_n·d_pad) + plane tile
(d_pad·B_pad) + proj tile (block_n·B_pad), all fp32 — ≈ 0.5 MB at the
default block_n=256, d ≤ 256, n_bits ≤ 24 (B_pad=128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(pows_ref, x_ref, planes_ref, codes_ref, tie_ref, *, n_bits: int):
    x = x_ref[...]  # [block_n, d_pad]
    pl_t = planes_ref[...][0]  # [d_pad, B_pad]
    proj = jax.lax.dot_general(
        x, pl_t,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [block_n, B_pad]
    bits = (proj >= 0.0).astype(jnp.int32)
    # pows carries 2^j at columns j < n_bits and 0 elsewhere (incl. the
    # tie-break column), so padded/tie columns never enter the code.
    codes_ref[...] = (bits * pows_ref[...]).sum(axis=1).reshape(codes_ref.shape)
    tie_ref[...] = proj[:, n_bits].reshape(tie_ref.shape)


def hash_codes_pallas(
    x: jax.Array,  # [n_pad, d_pad] padded points
    planes: jax.Array,  # [T, d_pad, B_pad] padded plane blocks
    pows: jax.Array,  # [1, B_pad] int32 packing weights (0 beyond n_bits)
    n_bits: int,
    *,
    block_n: int = 256,
    interpret: bool = False,
):
    """Raw kernel entry: returns (codes [T, 1, n_pad] int32, tie [T, 1, n_pad]
    f32).  The unit middle axis lets a (1, 1, block_n) block tile lanes for
    any table count T (a (1, block_n) block over [T, n] needs T == 1 or
    8 | 1)."""
    n, d = x.shape
    t, dp, bp = planes.shape
    assert n % block_n == 0 and d == dp, (x.shape, planes.shape, block_n)
    assert n_bits < bp, (n_bits, bp)
    grid = (t, n // block_n)
    return pl.pallas_call(
        functools.partial(_kernel, n_bits=n_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bp), lambda t, i: (0, 0)),  # packing weights
            pl.BlockSpec((block_n, d), lambda t, i: (i, 0)),  # point tile
            pl.BlockSpec((1, dp, bp), lambda t, i: (t, 0, 0)),  # table planes
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_n), lambda t, i: (t, 0, i)),  # codes
            pl.BlockSpec((1, 1, block_n), lambda t, i: (t, 0, i)),  # tie-break
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, 1, n), jnp.int32),
            jax.ShapeDtypeStruct((t, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(pows, x, planes)
