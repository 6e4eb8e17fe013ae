"""Fused kNN top-k Pallas kernel (TPU target) — the Stage-1 neighbor search.

Computes, for every query point, the k nearest candidate points and their
squared distances WITHOUT materializing the n×n distance matrix in HBM —
the paper's Alg. 1 assumes the ε-edge list is given; at framework scale the
neighbor search itself is the scalability gate (221 s serial vs 0.033 s
parallel in Table III).

Design (flash-attention-style online reduction, same skeleton as
``kernels/kmeans_assign``):

* grid = (n_q // block_q, n_c // block_k); the candidate axis is the *minor*
  grid axis, so for a fixed query block the kernel sweeps candidate tiles
  sequentially and folds a running per-row (dist, idx) top-k pair held in
  the output VMEM blocks (revisited across the minor axis — TPU Pallas
  guarantees sequential grid order, so the accumulator pattern is safe);
* candidate tiles are visited nearest-first (:func:`visit_tile`): from the
  tile that holds the block's first global query id, clamped to the
  candidate range, outward by tile distance, so on spatially ordered points
  the running top-k is tight after a few tiles;
* the distance tile uses the paper's BLAS identity (Eq. 12):
  ``S = ‖c‖² − 2 x·cᵀ`` — the per-row ‖x‖² term is constant under the
  top-k ordering and is added back by the wrapper, so the MXU does the
  heavy lifting (block_q × d @ d × block_k matmul per tile, fp32 acc);
* the merge folds the candidate tile into the running top-k by ``k_pad``
  unrolled min-extract-mask passes over the [block_q, k_pad + block_k]
  concatenation — pure VPU reductions, no sort network needed.  Each pass
  takes the smallest candidate id among equal minima, so the rows come out
  in (dist, id) order whatever order the tiles arrive in;
* a tile is merged only if some row's smallest distance in it is ≤ that
  row's running k_pad-th: otherwise no entry of the tile can enter the
  (dist, id) top-k_pad and the merge would return the buffer unchanged, so
  skipping it leaves the output bitwise as it was.  The kernel counts, per
  query block, the tiles it merged and the tiles it visited;
* self-pairs (global query id == global candidate id) are masked to +inf
  inside the kernel; padded candidates are excluded by the wrapper setting
  their ‖c‖² to +inf (identical trick to ``kmeans_assign``);
* queries need not be the candidate set: the sharded Stage 1 passes its
  local row block as queries plus the block's global row offset (a
  scalar-prefetched ``axis_index · rows_per_shard`` under shard_map), which
  shifts the self-exclusion iota so shard-local row ids line up with global
  candidate ids, and starts the visit order at the candidates nearest the
  queries.

VMEM working set per step: x tile (block_q·d) + c tile (block_k·d) + S tile
(block_q·block_k) + merged (block_q·(k_pad+block_k))·2, all fp32 ⇒ with the
default 256/256 blocks, d ≤ 1024 and k_pad ≤ 128 this is ≈ 2 MB, well
inside a v5e core's 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# lanes of a query block's counter row: tiles merged, tiles visited
MERGED, VISITED = 0, 1


def visit_tile(i, j, off, *, block_q: int, block_k: int, n_tiles: int):
    """Candidate tile visited at step ``j`` by query block ``i``: the tile
    ``i0`` holding the block's first global id ``off + i·block_q`` (clamped
    to the candidate range), then ``i0+1, i0−1, i0+2, …``; once one side
    runs out the walk goes on along the other, with no wrap.  Every tile is
    visited exactly once."""
    i0 = jnp.minimum(jnp.maximum(off + i * block_q, 0) // block_k, n_tiles - 1)
    up, down = n_tiles - 1 - i0, i0
    m = jnp.minimum(up, down)
    h = (j + 1) // 2
    alternating = jnp.where(j % 2 == 1, i0 + h, i0 - h)
    one_sided = jnp.where(up > down, i0 + (j - m), i0 - (j - m))
    return jnp.where(j <= 2 * m, alternating, one_sided)


def _kernel(off_ref, cn_ref, xq_ref, xc_ref, dist_ref, idx_ref, tiles_ref, *,
            block_q: int, block_k: int, k_pad: int, n_tiles: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    off = off_ref[0]
    c = visit_tile(i, j, off, block_q=block_q, block_k=block_k,
                   n_tiles=n_tiles)
    count_lane = jax.lax.broadcasted_iota(jnp.int32, tiles_ref.shape, 1)

    @pl.when(j == 0)
    def _init():
        dist_ref[...] = jnp.full_like(dist_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        tiles_ref[...] = jnp.zeros_like(tiles_ref)

    xq = xq_ref[...]  # [bq, d]
    xc = xc_ref[...]  # [bk, d]
    # S_tile = ‖c‖² − 2 x·cᵀ   (row-constant ‖x‖² added by the wrapper)
    s = cn_ref[...] - 2.0 * jax.lax.dot_general(
        xq,
        xc,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [bq, bk]
    rows_g = (off + i * block_q
              + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    cols_g = c * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    s = jnp.where(rows_g == cols_g, jnp.inf, s)  # a point is not its own neighbor

    # A tile whose every row minimum lies above that row's running k_pad-th
    # cannot change the (dist, id) top-k_pad; a tie merges, since a tied
    # candidate with a smaller id would enter.
    row_min = jnp.min(s, axis=1, keepdims=True)  # [bq, 1]
    merge = jnp.max(jnp.where(row_min <= dist_ref[:, k_pad - 1:k_pad], 1, 0)) > 0
    tiles_ref[...] += jnp.where(count_lane == MERGED, merge.astype(jnp.int32),
                                (count_lane == VISITED).astype(jnp.int32))

    @pl.when(merge)
    def _merge():
        # k_pad min-extract-mask passes over the concatenation.  Ascending
        # extraction keeps the running buffer sorted; among equal minima the
        # smallest candidate id is taken (ids are distinct among finite
        # entries), so rows are the k_pad smallest in (dist, id) order.  The
        # buffer is sorted, so the first pass's minimum is already known.
        merged_d = jnp.concatenate([dist_ref[...], s], axis=1)  # [bq, k_pad+bk]
        merged_i = jnp.concatenate([idx_ref[...], cols_g], axis=1)
        no_id = jnp.iinfo(jnp.int32).max
        mn = jnp.minimum(row_min, dist_ref[:, 0:1])
        out_d, out_i = [], []
        for p in range(k_pad):
            if p:
                mn = jnp.min(merged_d, axis=1, keepdims=True)  # [bq, 1]
            key = jnp.where(merged_d == mn, merged_i, no_id)
            am = jnp.min(key, axis=1, keepdims=True)  # smallest tied id
            out_d.append(mn[:, 0])
            out_i.append(am[:, 0])
            merged_d = jnp.where(key == am, jnp.inf, merged_d)
        dist_ref[...] = jnp.stack(out_d, axis=1)
        idx_ref[...] = jnp.stack(out_i, axis=1)


def knn_topk_pallas(
    xq: jax.Array,  # [nq_p, d] padded queries
    xc: jax.Array,  # [nc_p, d] padded candidates
    c_norm: jax.Array,  # [1, nc_p] ‖c‖² with +inf on padded rows
    k_pad: int,
    *,
    query_offset: jax.Array | int = 0,  # global row id of xq[0]
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
):
    """Raw kernel entry: returns (dist [nq_p, k_pad] without the ‖x‖² row
    term, idx [nq_p, k_pad] int32; unfilled slots are (+inf, stale), tiles
    [nq_p // block_q · 8, 128] int32: each of query block ``i``'s rows
    ``8·i … 8·i+7`` holds its merged tiles in lane ``MERGED`` and its
    visited tiles in lane ``VISITED``)."""
    nq, d = xq.shape
    nc = xc.shape[0]
    assert nq % block_q == 0 and nc % block_k == 0, (nq, nc, block_q, block_k)
    n_tiles = nc // block_k
    grid = (nq // block_q, n_tiles)
    off = jnp.asarray(query_offset, jnp.int32).reshape(1)
    visit = functools.partial(visit_tile, block_q=block_q, block_k=block_k,
                              n_tiles=n_tiles)
    return pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          k_pad=k_pad, n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the global query-row offset
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_k),
                             lambda i, j, o: (0, visit(i, j, o[0]))),  # ‖c‖²
                pl.BlockSpec((block_q, d), lambda i, j, o: (i, 0)),  # queries
                pl.BlockSpec((block_k, d),
                             lambda i, j, o: (visit(i, j, o[0]), 0)),  # candidates
            ],
            out_specs=[
                pl.BlockSpec((block_q, k_pad), lambda i, j, o: (i, 0)),  # dists
                pl.BlockSpec((block_q, k_pad), lambda i, j, o: (i, 0)),  # ids
                pl.BlockSpec((8, 128), lambda i, j, o: (i, 0)),  # tile counts
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nq, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((nq, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((nq // block_q * 8, 128), jnp.int32),
        ],
        interpret=interpret,
    )(off, c_norm, xq, xc)
