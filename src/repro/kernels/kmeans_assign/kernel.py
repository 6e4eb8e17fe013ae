"""Fused k-means assignment Pallas kernel (TPU target).

Computes ``labels[i] = argmin_j ‖x_i − c_j‖²`` and the minimum distance
without materializing the n×k distance matrix in HBM.

Design (flash-attention-style online reduction):

* grid = (n // block_q, k // block_k); the k dimension is the *minor* grid
  axis, so for a fixed query block the kernel sweeps centroid tiles
  sequentially and folds a running (min, argmin) pair held in the output
  VMEM blocks (revisited across the minor axis — TPU Pallas guarantees
  sequential grid order, so the accumulator pattern is safe);
* the distance tile uses the paper's BLAS identity (Eq. 12):
  ``S = ‖c‖² − 2 x·cᵀ`` — the per-row ‖x‖² term is constant under argmin and
  is added back by the wrapper, so the MXU does all the heavy lifting
  (block_q × d @ d × block_k matmul per tile, fp32 accumulation);
* VMEM working set per step: x tile (block_q·d) + c tile (block_k·d)
  + S tile (block_q·block_k), all fp32 ⇒ with the default 1024/512 blocks
  (``repro.kernels._util`` — shared with the config layer) and d ≤ 1024
  this is ≈ 8 MB, comfortably inside a v5e core's 16 MB VMEM; block shapes
  are multiples of (8, 128) to keep the MXU/VPU aligned.

The n×k HBM round-trip this removes is exactly what makes the paper's
unfused formulation memory-bound at large n·k — see EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._util import KMEANS_BLOCK_K, KMEANS_BLOCK_Q


def _kernel(c_norm_ref, x_ref, c_ref, min_ref, idx_ref, *, block_k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    x = x_ref[...]  # [bq, d]
    c = c_ref[...]  # [bk, d]
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
    idx_ref[...] = jnp.where(better, tile_arg, idx_ref[...])
    min_ref[...] = jnp.where(better, tile_min, min_ref[...])


def kmeans_assign_pallas(
    x: jax.Array,  # [n, d] (n % block_q == 0, d % 128 == 0)
    c: jax.Array,  # [k, d] (k % block_k == 0)
    c_norm: jax.Array,  # [1, k]
    *,
    block_q: int = KMEANS_BLOCK_Q,
    block_k: int = KMEANS_BLOCK_K,
    interpret: bool = False,
):
    """Raw kernel entry: returns (min [1, n] without the ‖x‖² row term,
    idx [1, n] int32) — per-row vectors are lane-dense 2-D blocks."""
    n, d = x.shape
    k = c.shape[0]
    assert n % block_q == 0 and k % block_k == 0, (n, k, block_q, block_k)
    grid = (n // block_q, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),  # c_norm tile
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),  # x tile
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),  # c tile
        ],
        out_specs=[
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),  # running min
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),  # running argmin
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(c_norm, x, c)
