"""Row-sorted sparse matrix × vector as one Pallas pass (TPU target).

``y = A x`` over the chunked layout that :func:`repro.kernels.coo_spmv.ops.
build_tiles` makes from a row-sorted COO (DESIGN.md §19):

* rows are grouped in tiles of 1024; a tile's ``y`` is one (8, 128) output
  block, which stays resident while the tile's chunks stream past it
  (consecutive revisits, the accumulator layout of ``kmeans_iter``);
* a chunk is eight (8, 128) vregs of nonzeros.  A tile's nonzeros fall in
  two sections, those near the tile's own columns and the rest, each
  starting a new chunk; in a section each lane holds up to 8 consecutive
  nonzeros of one row, down its sublanes, and a row takes
  ``ceil(degree / 8)`` consecutive lanes;
* ``x`` is held whole in VMEM as a ``(rows / 128, 128)`` table, fetched once.

Per chunk:

1. **Gather in registers.**  Mosaic gathers only within one vreg, so for
   every table row ``t`` the chunk's columns can reach (a scalar-prefetched
   range of 8-row table blocks), row ``t`` is broadcast to a vreg,
   lane-gathered by ``col % 128`` and kept where ``col // 128 == t``.
2. **Multiply** by the values and **sum down the sublanes**: one partial sum
   per lane, a lane per vreg row of an (8, 128) matrix.
3. **Reduce rows in registers.**  A segmented scan along the lanes, keyed by
   each lane's row, then across the eight vregs, leaves each row's chunk
   total at its last lane; a second in-vreg gather by the layout's
   ``ends`` (the last lane of each tile row in this chunk, −1 where none)
   moves the totals to their rows, which are added to the output block.

Everything is float32; only the order of the summation differs from the
segment-sum product.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
VREGS = 8  # vregs of nonzeros per chunk
TILE_ROWS = SUBLANES * LANES  # rows per output block
CHUNK_LANES = VREGS * LANES
CHUNK_SLOTS = CHUNK_LANES * SUBLANES
TABLE_BLOCK = SUBLANES * LANES  # columns per 8-row block of the x table


def _segmented_scan(v, key, axis: int, span: int):
    """Inclusive scan of ``v`` along ``axis`` that adds only across equal
    ``key`` (runs of a key are contiguous), in log2(span) rotate steps."""
    pos = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    d = 1
    while d < span:
        same = (pos >= d) & (pltpu.roll(key, d, axis) == key)
        v = v + jnp.where(same, pltpu.roll(v, d, axis), 0.0)
        d *= 2
    return v


def _gather_rows(table, hi, lo, n_rows: int):
    """``table[hi, lo]`` for an (n_rows, 128) value ``table`` held in
    registers: row by row, a broadcast, a lane gather and a select."""
    out = jnp.zeros(lo.shape, jnp.float32)
    for j in range(n_rows):
        row = jnp.broadcast_to(table[j:j + 1, :], lo.shape)
        out = jnp.where(hi == j,
                        jnp.take_along_axis(row, lo, axis=1), out)
    return out


def _kernel(tile_ref, blo_ref, bhi_ref, x_ref, col_ref, val_ref, key_ref,
            end_ref, o_ref):
    c = pl.program_id(0)
    prev = jnp.maximum(c - 1, 0)

    @pl.when((c == 0) | (tile_ref[c] != tile_ref[prev]))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(blo_ref[c] <= bhi_ref[c])  # empty chunks add nothing
    def _accumulate():
        cols = [col_ref[k * SUBLANES:(k + 1) * SUBLANES, :]
                for k in range(VREGS)]
        his = [col >> 7 for col in cols]  # table row; −1 on padding slots
        los = [col & (LANES - 1) for col in cols]

        def block(s, acc):
            start = pl.multiple_of(s * SUBLANES, SUBLANES)
            rows = x_ref[pl.ds(start, SUBLANES), :]
            acc = list(acc)
            for j in range(SUBLANES):
                row = jnp.broadcast_to(rows[j:j + 1, :], (SUBLANES, LANES))
                t = start + j
                for k in range(VREGS):
                    acc[k] = jnp.where(
                        his[k] == t,
                        jnp.take_along_axis(row, los[k], axis=1), acc[k])
            return tuple(acc)

        zero = jnp.zeros((SUBLANES, LANES), jnp.float32)
        xs = jax.lax.fori_loop(blo_ref[c], bhi_ref[c] + 1, block,
                               (zero,) * VREGS)
        # one partial sum per lane; vreg k's lanes become row k
        vreg = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
        sums = zero
        for k in range(VREGS):
            p = val_ref[k * SUBLANES:(k + 1) * SUBLANES, :] * xs[k]
            sums = jnp.where(vreg == k, jnp.sum(p, axis=0, keepdims=True),
                             sums)
        keys = key_ref[...]
        sums = _segmented_scan(sums, keys, axis=1, span=LANES)
        # carry a row that runs on from the end of one vreg into the next
        last = jnp.broadcast_to(sums[:, LANES - 1:], sums.shape)
        last_key = jnp.broadcast_to(keys[:, LANES - 1:], keys.shape)
        last = _segmented_scan(last, last_key, axis=0, span=VREGS)
        carry = (vreg >= 1) & (pltpu.roll(last_key, 1, 0) == keys)
        sums = sums + jnp.where(carry, pltpu.roll(last, 1, 0), 0.0)
        # each tile row's chunk total sits at its last lane in the chunk
        ends = end_ref[...]
        o_ref[...] += _gather_rows(sums, ends >> 7, ends & (LANES - 1),
                                   VREGS)


def coo_spmv_pallas(used, tile_of, blo, bhi, x_table, cols, vals, keys, ends,
                    *, out_rows: int | None = None, interpret: bool = False):
    """Raw kernel entry over a built layout: ``used`` [] int32, the chunks
    to run (the grid's length, at most n_chunks; they must reach every
    tile); ``tile_of``/``blo``/``bhi`` [n_chunks] int32 (scalar prefetch:
    each chunk's tile and the inclusive range of 8-row x-table blocks its
    columns reach), ``x_table`` [n_tiles·8, 128] f32, ``cols``/``vals``
    [n_chunks·64, 128], ``keys``/``ends`` [n_chunks·8, 128] int32.
    Returns ``y`` as an [out_rows, 128] f32 table: 8 rows for each tile of
    the layout, as many as the x table has for a square matrix (the
    default), fewer for a block of rows."""
    table_rows = x_table.shape[0]
    out_rows = table_rows if out_rows is None else out_rows
    chunk = VREGS * SUBLANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(used,),
        in_specs=[
            pl.BlockSpec((table_rows, LANES), lambda c, *_: (0, 0)),  # x
            pl.BlockSpec((chunk, LANES), lambda c, *_: (c, 0)),  # cols
            pl.BlockSpec((chunk, LANES), lambda c, *_: (c, 0)),  # vals
            pl.BlockSpec((VREGS, LANES), lambda c, *_: (c, 0)),  # keys
            pl.BlockSpec((SUBLANES, LANES), lambda c, *_: (c, 0)),  # ends
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES),
                               lambda c, tile, *_: (tile[c], 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tile_of, blo, bhi, x_table, cols, vals, keys, ends)
