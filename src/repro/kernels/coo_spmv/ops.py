"""The chunked layout of a row-sorted COO, and the product over it.

:func:`build_tiles` turns a row-sorted COO into the layout the kernel
streams (DESIGN.md §19), on the device and under ``jax.jit``: every size is
static, from ``n`` and ``nnz`` alone.  Each tile of 1024 rows has two
sections, each starting a new chunk: its *near* nonzeros (columns within
:data:`NEAR_BLOCKS` 1024-column blocks of the tile's own) and its *far*
ones.  A chunk of a near section reaches at most five blocks of ``x``; far
nonzeros, which may reach all of them, do not widen it.  In a section a row of degree d
takes ``ceil(d / 8)`` lanes, so a section of m nonzeros takes at most
``(m + 7·1024) / 8`` lanes; with a near section of at least one chunk, all
sections take at most ``ceil((nnz + 14 n) / 8192) + 2 n_tiles`` chunks, the
static count.  The kernel's grid runs the chunks the sections use.

A layout may hold a block of rows ``[r0, r0 + rows)`` of an n-column
matrix, as a chip of a row-sharded Stage 2 holds its own (DESIGN.md §20):
its tiles are the block's, "near" is measured from each nonzero's global
row, and the nonzeros of other rows are left out.  The whole matrix is the
block with ``r0 = 0`` and ``rows = n``.

:func:`coo_spmv` picks its engine as the other kernel packages do: the
kernel on a TPU, interpret mode where asked, the layout's jnp reference
otherwise.  :func:`kernel_applies` is the dispatch rule the pipeline's
operator choice reads: a TPU backend and ``n`` up to :data:`MAX_N`.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.coo_spmv.kernel import (CHUNK_LANES, CHUNK_SLOTS, LANES,
                                           SUBLANES, TABLE_BLOCK, TILE_ROWS,
                                           VREGS, coo_spmv_pallas)
from repro.kernels.coo_spmv.ref import coo_tiles_spmv_ref

# Largest n the kernel takes (DESIGN.md §19).  Its gather passes over every
# 1024-column block of x a chunk's columns reach, ~0.33 µs a pass for the
# chunk's 8192 slots on a v5e; a graph without column locality reaches all
# n/1024 of them, so a slot costs ~n·0.04 ps, and with lane padding
# (~1.25 slots a nonzero on the deployments' graphs) the ~16 ns a nonzero
# of XLA's gather and segment sum is passed near n = 330 k.  Graphs with
# locality (a kNN graph in lattice order reaches ~3 blocks a chunk) stay
# far below that at any n.  The x table takes 2 MB of VMEM at MAX_N.
MAX_N = 1 << 18


# Blocks on either side of a tile's own whose columns count as near.  A kNN
# graph in lattice order keeps every nonzero within two blocks of its row
# (its boundary voxels reach two lattice steps), so it has no far section;
# a planted partition's inter-block edges mostly fall outside.
NEAR_BLOCKS = 2


def kernel_applies(n: int) -> bool:
    """Whether the single-vector product of an n-row graph runs the kernel:
    on a TPU, for n up to :data:`MAX_N`; elsewhere the segment-sum path."""
    return jax.default_backend() == "tpu" and 0 < n <= MAX_N


def coo_spmv_engine(impl: str = "auto", interpret: bool | None = None) -> str:
    """The engine :func:`coo_spmv` runs: ``"pallas"`` (compiled for the
    TPU), ``"pallas-interpret"`` or ``"ref"`` (the layout's jnp reference,
    which ``auto`` picks off TPU unless ``interpret`` asks for the
    kernel)."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu and not interpret):
        return "ref"
    interpret = (not on_tpu) if interpret is None else interpret
    return "pallas-interpret" if interpret else "pallas"


def n_tiles(n: int) -> int:
    return -(-n // TILE_ROWS)


def n_chunks(n: int, nnz: int) -> int:
    """The static chunk count of an n-row layout of nnz nonzeros."""
    return -(-(nnz + 2 * (SUBLANES - 1) * n) // CHUNK_SLOTS) + 2 * n_tiles(n)


@dataclasses.dataclass(frozen=True)
class CooTiles:
    """A row-sorted COO in the kernel's chunked layout.

    ``cols``/``vals`` [n_chunks·64, 128]: chunk c's vreg k is rows
    ``64c + 8k ..+8``; lane l there holds up to 8 consecutive nonzeros of
    one row's section down its sublanes (padding: column −1, value 0).
    ``keys`` [n_chunks·8, 128]: each lane's row within its tile (−1:
    unused).  ``ends`` [n_chunks·8, 128]: for each row of the chunk's tile,
    its last lane in the chunk (``128k + l``), −1 where it has none.
    ``tile_of``, ``blo``, ``bhi`` [n_chunks]: each chunk's tile and the
    inclusive range of 1024-column blocks its columns reach (``blo > bhi``:
    empty).  ``used`` []: the chunks the sections take, those the kernel
    runs.  Rows and keys count from the block's first row."""

    cols: jax.Array
    vals: jax.Array
    keys: jax.Array
    ends: jax.Array
    tile_of: jax.Array
    blo: jax.Array
    bhi: jax.Array
    used: jax.Array
    n: int  # static: columns, the length of x
    rows: int  # static: the block's rows, the length of y

    @property
    def slots(self) -> int:
        """Nonzero slots the layout holds, padding included."""
        return int(self.cols.size)


jax.tree_util.register_dataclass(
    CooTiles,
    ["cols", "vals", "keys", "ends", "tile_of", "blo", "bhi", "used"],
    ["n", "rows"])


def _running(x: jax.Array, op) -> jax.Array:
    """Inclusive running ``op`` (``jnp.add`` or ``jnp.maximum``) of a 1-D
    int32 array, in levels of 1024: the TPU compiler takes about a minute
    over a one-level scan of a million elements, a second over this."""
    m = x.shape[0]
    scan = jnp.cumsum if op is jnp.add else jax.lax.cummax
    if m <= 1024:
        return scan(x)
    ident = 0 if op is jnp.add else jnp.iinfo(jnp.int32).min
    rows = jnp.pad(x, (0, -m % 1024), constant_values=ident).reshape(-1, 1024)
    inner = scan(rows, axis=1)
    carry = _running(inner[:, -1], op)  # through the end of each row
    carry = jnp.pad(carry[:-1], (1, 0), constant_values=ident)
    return op(inner, carry[:, None]).reshape(-1)[:m]


def build_tiles(row: jax.Array, col: jax.Array, val: jax.Array, n: int, *,
                r0=0, rows: int | None = None) -> CooTiles:
    """The chunked layout of rows ``[r0, r0 + rows)`` (all n by default) of
    an n-column COO whose ``row`` is non-decreasing; ``r0`` may be traced
    (a chip's first row under ``shard_map``), ``rows`` is static.  Device
    work only (traceable under jit): a binary search for the rows' starts,
    one scatter of the block's nonzeros into their slots, and otherwise
    passes over the rows, lanes and chunks and running maxima along the
    nonzeros.  A block's static chunk count is that of all nnz nonzeros
    in ``rows`` rows: any block may hold them all."""
    rows = n if rows is None else rows
    nnz = row.shape[0]
    nt, nc = n_tiles(rows), n_chunks(rows, nnz)
    row = row.astype(jnp.int32)
    col = col.astype(jnp.int32)
    far = jnp.abs(col // TABLE_BLOCK - row // TILE_ROWS) > NEAR_BLOCKS
    part = far.astype(jnp.int32)  # section: 0 near, 1 far
    first = jnp.arange(rows + 1, dtype=jnp.int32)
    bounds = jnp.searchsorted(row, r0 + first
                              ).astype(jnp.int32)  # each row's first nonzero
    far_cum = jnp.pad(_running(part, jnp.add), (1, 0))
    deg_far = far_cum[bounds[1:]] - far_cum[bounds[:-1]]
    deg = jnp.stack([bounds[1:] - bounds[:-1] - deg_far, deg_far], axis=1)
    lanes = (deg + SUBLANES - 1) // SUBLANES  # per row and section
    lanes_t = jnp.pad(lanes, ((0, nt * TILE_ROWS - rows), (0, 0))).reshape(
        nt, TILE_ROWS, 2)
    chunks_s = -(-lanes_t.sum(1) // CHUNK_LANES)  # [tile, section]
    chunks_s = chunks_s.at[:, 0].max(1).reshape(-1)  # every tile has a chunk
    chunk0_s = jnp.cumsum(chunks_s) - chunks_s  # sections in (tile, part)
    # each row's first lane in each section: the section's first chunk,
    # then the rows before it; non-decreasing along the rows
    lane0 = (jnp.cumsum(lanes_t, axis=1) - lanes_t
             + chunk0_s.reshape(nt, 1, 2) * CHUNK_LANES).reshape(-1, 2)[:rows]

    def per_nonzero(v):
        """A non-decreasing per-row value at each of the row's nonzeros:
        set at the row's first nonzero, carried on by a running maximum."""
        at_start = jnp.zeros((nnz,), jnp.int32).at[bounds[:-1]].max(
            v, mode="drop")
        return _running(at_start, jnp.maximum)

    # a nonzero's place in its row's section: the nonzeros of that section
    # before it in the row
    e = jnp.arange(nnz, dtype=jnp.int32)
    far_pos = far_cum[:-1] - per_nonzero(far_cum[bounds[:-1]])
    pos = jnp.where(far, far_pos, e - per_nonzero(bounds[:-1]) - far_pos)
    lane = jnp.where(far, per_nonzero(lane0[:, 1]),
                     per_nonzero(lane0[:, 0])) + pos // SUBLANES
    # chunk c's vreg k holds lanes 128k ..+128 of the chunk, a lane's 8
    # slots down its sublanes
    chunk, vlane = lane // CHUNK_LANES, lane % CHUNK_LANES
    slot = ((chunk * VREGS + vlane // LANES) * SUBLANES + pos % SUBLANES) \
        * LANES + vlane % LANES
    # other rows' nonzeros go to slots past the end, dropped
    local = row - r0
    slot = jnp.where((local >= 0) & (local < rows), slot, nc * CHUNK_SLOTS + e)
    packed = jnp.stack([col, jax.lax.bitcast_convert_type(
        val.astype(jnp.float32), jnp.int32), local % TILE_ROWS], axis=1)
    empty = jnp.array([-1, 0, -1], jnp.int32)  # column, value bits, key
    slots = jnp.broadcast_to(empty, (nc * CHUNK_SLOTS, 3)).at[slot].set(
        packed, unique_indices=True, mode="drop")
    cols = slots[:, 0].reshape(-1, LANES)
    vals = jax.lax.bitcast_convert_type(slots[:, 1], jnp.float32).reshape(
        -1, LANES)
    keys = slots[:, 2].reshape(nc * VREGS, SUBLANES, LANES)[:, 0]
    # a row's last lane in each chunk: the next lane holds another row, or
    # none (the chunk's last lane, or the section's end)
    keys_c = keys.reshape(nc, CHUNK_LANES)
    later = jnp.pad(keys_c[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    erow = jnp.where((keys_c >= 0) & (keys_c != later),
                     jnp.arange(nc, dtype=jnp.int32)[:, None] * SUBLANES
                     + keys_c // LANES, nc * SUBLANES)
    ends = jnp.full((nc * SUBLANES, LANES), -1, jnp.int32).at[
        erow, keys_c % LANES].set(
            jnp.broadcast_to(jnp.arange(CHUNK_LANES, dtype=jnp.int32),
                             keys_c.shape), mode="drop")
    # a chunk's section is the last to start at or before it (empty far
    # sections start where the next tile's near one does)
    section = jnp.searchsorted(chunk0_s, jnp.arange(nc, dtype=jnp.int32),
                               side="right") - 1
    tile_of = jnp.minimum(section // 2, nt - 1).astype(jnp.int32)
    block = cols.reshape(nc, CHUNK_SLOTS) // TABLE_BLOCK  # −1 on padding
    bhi = block.max(1)
    blo = jnp.where(block >= 0, block, bhi[:, None]).min(1)
    return CooTiles(cols, vals, keys, ends, tile_of,
                    jnp.where(bhi >= 0, blo, 0), bhi,
                    chunks_s.sum().astype(jnp.int32), n, rows)


@partial(jax.jit, static_argnames=("impl", "interpret"))
def coo_spmv(t: CooTiles, x: jax.Array, *, impl: str = "auto",
             interpret: bool | None = None) -> jax.Array:
    """``y = A x`` over the layout, accumulated in float32, in x's dtype:
    the layout's ``t.rows`` rows, from the first ``t.n`` entries of ``x``
    (a longer ``x``, as a row-sharded product gathers, is cut)."""
    engine = coo_spmv_engine(impl, interpret)
    x = x[:t.n]
    if engine == "ref":
        return coo_tiles_spmv_ref(t, x)
    table = jnp.pad(x.astype(jnp.float32),
                    (0, n_tiles(t.n) * TILE_ROWS - t.n)).reshape(-1, LANES)
    y = coo_spmv_pallas(t.used, t.tile_of, t.blo, t.bhi, table, t.cols,
                        t.vals, t.keys, t.ends,
                        out_rows=n_tiles(t.rows) * SUBLANES,
                        interpret=engine == "pallas-interpret")
    return y.reshape(-1)[:t.rows].astype(x.dtype)
