"""Fused k-means *iteration* Pallas kernel (TPU target).

One Lloyd iteration = assignment + centroid accumulation in a SINGLE pass
over the point matrix: per query tile the kernel folds the running
(min, argmin) pair online (same flash-style reduction as
``kernels/kmeans_assign``) and, once the centroid sweep for that tile
completes, scatter-accumulates the tile's rows into resident
``[k_pad, d_aug]`` partial-sum/count accumulators via a one-hot MXU
contraction.  The n×k one-hot never exists in HBM and x is streamed from
HBM exactly once per iteration (the two-pass path streams it twice and
round-trips the n×k one-hot).

Grid and revisiting discipline (TPU Pallas executes the grid sequentially):

* grid = (n // block_q, k // block_k), centroid axis minor — c tiles are
  streamed, so the *distance* working set is bounded regardless of k;
* ``min``/``idx`` outputs block over the major axis and are revisited across
  the minor sweep (consecutive visits — the legal accumulator pattern);
* the ``acc`` output uses a constant index map: every grid step maps to the
  same [k_pad, d_aug] block, so all visits are consecutive by construction
  and the block lives in VMEM for the whole grid, flushed once at the end.
  A blocked (kc-tile) accumulator would be revisited non-consecutively
  across the major axis, which Pallas' output pipelining forbids — hence
  the accumulator, unlike the centroid stream, must be VMEM-resident.  The
  wrapper enforces the resulting ``k_pad·d_aug`` VMEM budget and raises
  ``NotImplementedError`` beyond it (callers fall back to the chunked
  online path, which has no such bound);
* the counts ride inside the accumulator: the wrapper augments x with a
  ones-column at position ``d`` (zero on padded rows and on every centroid,
  so distances are unchanged), making ``accᵀ``'s column ``d`` the cluster
  populations — one dot_general produces sums and counts together.

VMEM working set per step: x tile (block_q·d_aug) + c tile (block_k·d_aug)
+ S tile (block_q·block_k) + one-hot chunk (block_q·block_k, transient —
the accumulate contraction is k-chunked so the accumulator is the only
full-k object) + acc (k_pad·d_aug), all fp32.  The wrapper models this sum
against a 12 MB budget and takes the chunked path past it; the (8, 128) fp32
tiling constraint fixes the padding multiples.  Mosaic needs more than the
model: double-buffered input tiles, and the bf16 splits of the fp32 operands
that ``Precision.HIGHEST`` multiplies (21.3 MB at the budget's edge, k=1024,
d=639).  So the kernel raises its scoped-VMEM limit past v5e's 16 MB default
to :data:`VMEM_LIMIT_BYTES` (a v5e core has 128 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import KMEANS_BLOCK_K, KMEANS_BLOCK_Q

VMEM_LIMIT_BYTES = 32 << 20


def _kernel(c_norm_ref, x_ref, c_ref, min_ref, idx_ref, acc_ref, *,
            block_k: int, k_pad: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init_rows():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [bq, d_aug] (column d of the unpadded layout is ones)
    c = c_ref[...]  # [bk, d_aug] (zero in the ones-column => distances exact)
    # S_tile = ‖c‖² − 2 x·cᵀ   (row-constant ‖x‖² added by the wrapper)
    s = c_norm_ref[...] - 2.0 * jax.lax.dot_general(
        x,
        c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [bq, bk]
    # per-row results go lane-dense ([1, bq]) like the output blocks
    tile_min = jnp.min(s, axis=1).reshape(1, -1)
    tile_arg = (jnp.argmin(s, axis=1).astype(jnp.int32)
                + j * block_k).reshape(1, -1)
    better = tile_min < min_ref[...]
    new_idx = jnp.where(better, tile_arg, idx_ref[...])  # [1, bq]
    idx_ref[...] = new_idx
    min_ref[...] = jnp.where(better, tile_min, min_ref[...])

    @pl.when(j == nk - 1)
    def _accumulate():  # labels for this query tile are now final
        # k-chunked one-hot contraction: the transient is [block_k, bq], not
        # [k_pad, bq] — the accumulator stays the only full-k VMEM object
        for kc in range(k_pad // block_k):
            rows = kc * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, x.shape[0]), 0)
            onehot_t = (rows == new_idx).astype(jnp.float32)  # [block_k, bq]
            acc_ref[kc * block_k:(kc + 1) * block_k, :] += jax.lax.dot_general(
                onehot_t,
                x,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )  # [block_k, d_aug] — padded x rows are all-zero, add nothing


def kmeans_iter_pallas(
    x: jax.Array,  # [n_p, d_aug] (n_p % block_q == 0, d_aug % 128 == 0)
    c: jax.Array,  # [k_p, d_aug] (k_p % block_k == 0, zero ones-column)
    c_norm: jax.Array,  # [1, k_p] with +inf on padded centroids
    *,
    block_q: int = KMEANS_BLOCK_Q,
    block_k: int = KMEANS_BLOCK_K,
    interpret: bool = False,
):
    """Raw kernel entry: returns (min [1, n_p] without the ‖x‖² row term,
    idx [1, n_p] int32, acc [k_p, d_aug] fp32).  Per-row vectors are 2-D
    [1, n] so their blocks tile lanes; a 1-D block must match the tiling
    XLA picks for the whole array, which Mosaic refuses for most sizes."""
    n, d_aug = x.shape
    k_p = c.shape[0]
    assert n % block_q == 0 and k_p % block_k == 0, (n, k_p, block_q, block_k)
    grid = (n // block_q, k_p // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, k_pad=k_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),  # ‖c‖² tile
            pl.BlockSpec((block_q, d_aug), lambda i, j: (i, 0)),  # x tile
            pl.BlockSpec((block_k, d_aug), lambda i, j: (j, 0)),  # c tile
        ],
        out_specs=[
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),  # running min
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),  # running argmin
            pl.BlockSpec((k_p, d_aug), lambda i, j: (0, 0)),  # resident acc
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((k_p, d_aug), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(c_norm, x, c)
