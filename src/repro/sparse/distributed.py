"""Pod-scale distributed SpMV (the paper's PCIe-aware split, re-done for ICI).

The paper keeps the sparse matrix resident on the GPU and ships one n-vector
per Arnoldi step over PCIe.  On a pod, the analogue is a 1-D row-block
partition of the graph over the ``data`` mesh axis:

* each shard owns ``rows_per_shard`` consecutive rows of W and *all* edges
  whose destination row lands in that block (edge lists are re-bucketed
  host-side by :func:`partition_coo_by_rows`);
* a matvec all-gathers the input vector x (n values over ICI — the analogue
  of the paper's per-step PCIe transfer, and subdominant for the same
  reason), multiplies against local edges, and segment-sums into the local
  row block.  No all-reduce is needed because scatter targets are local by
  construction.

Two execution paths share this layout:

``spmv_gspmd``    — paper-faithful baseline: plain segment_sum under jit with
                    sharding constraints; GSPMD inserts the collectives (it
                    cannot prove scatter locality, so it all-reduces the full
                    output — measurably worse; kept as the §Perf baseline).
``make_sharded_spmv`` — shard_map version exploiting locality (all-gather of
                    x only).  This is the optimized path.

A third layout needs no host bucketing: :func:`build_row_tiles` builds each
chip's block of rows of a row-sorted COO in the ``coo_spmv`` kernel's
chunked layout on the chips, under jit, and :func:`tiled_spmv` is its
product — one all-gather of x, then the kernel over the chip's own chunks
(DESIGN.md §20).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.compat import SHARD_MAP_NO_CHECK, shard_map as _shard_map
from repro.sparse.formats import COO

Array = jax.Array


def auto_mesh(mesh):
    """``mesh`` with every axis in ``AxisType.Auto`` mode (``None`` passes).

    ``jax.make_mesh`` builds ``Explicit`` axes, which put shardings into the
    array types: a gather from a row-sharded operand (``x[col]``, the argsort
    gather of graph assembly) then needs an ``out_sharding`` at every call
    site, and ``qr`` refuses row-sharded inputs.  The sharded plan relies on
    GSPMD propagation instead, so every library entry point that takes a
    mesh (``Plan``, the shard_map builders, ``shard_vector``/``shard_edges``)
    passes it through here — the one place the axis mode is fixed.
    """
    if mesh is None or all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@dataclasses.dataclass(frozen=True)
class ShardedCOO:
    """COO re-bucketed so shard ``i`` holds edges for rows
    ``[i*rows_per_shard, (i+1)*rows_per_shard)``, rows stored *locally*
    (0-based within the block).  All shards padded to equal edge counts with
    (row=0, col=0, val=0) null edges.

    Leading axes are ``num_shards * edges_per_shard``; sharding the leading
    axis over the data axis hands each device exactly its bucket.
    """

    row_local: jax.Array  # [S*E] int32, in-block row ids
    col: jax.Array  # [S*E] int32, global column ids
    val: jax.Array  # [S*E] float
    shape: Tuple[int, int]  # padded global shape (n_pad, n_pad)
    rows_per_shard: int
    num_shards: int
    edges_per_shard: int


jax.tree_util.register_dataclass(
    ShardedCOO,
    data_fields=["row_local", "col", "val"],
    meta_fields=["shape", "rows_per_shard", "num_shards", "edges_per_shard"],
)


def padded_rows(n: int, num_shards: int) -> int:
    return ((n + num_shards - 1) // num_shards) * num_shards


def global_rows(sm: "ShardedCOO") -> Array:
    """Per-edge global row ids recovered from the (shard, local-row) layout."""
    shard = jnp.arange(sm.num_shards, dtype=jnp.int32).repeat(sm.edges_per_shard)
    return sm.row_local + shard * sm.rows_per_shard


def normalize_sharded(sm: "ShardedCOO", deg: Array) -> "ShardedCOO":
    """val ← val · d^{-1/2}[row] · d^{-1/2}[col]  (sym normalization)."""
    isd = jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1e-30)), 0.0)
    grow = global_rows(sm)
    val = sm.val * isd[grow] * isd[sm.col]
    return dataclasses.replace(sm, val=val)


def partition_coo_by_rows(m: COO, num_shards: int) -> ShardedCOO:
    """Host-side re-bucketing of a row-sorted COO onto ``num_shards`` blocks."""
    row = np.asarray(m.row)
    col = np.asarray(m.col)
    val = np.asarray(m.val)
    n = m.shape[0]
    n_pad = padded_rows(n, num_shards)
    rps = n_pad // num_shards
    owner = row // rps
    counts = np.bincount(owner, minlength=num_shards)
    e_max = max(int(counts.max() if counts.size else 0), 1)
    rl = np.zeros((num_shards, e_max), np.int32)
    cl = np.zeros((num_shards, e_max), np.int32)
    vl = np.zeros((num_shards, e_max), val.dtype)
    for s in range(num_shards):
        sel = owner == s
        k = int(sel.sum())
        rl[s, :k] = row[sel] - s * rps
        cl[s, :k] = col[sel]
        vl[s, :k] = val[sel]
    return ShardedCOO(
        row_local=jnp.asarray(rl.reshape(-1)),
        col=jnp.asarray(cl.reshape(-1)),
        val=jnp.asarray(vl.reshape(-1)),
        shape=(n_pad, n_pad),
        rows_per_shard=rps,
        num_shards=num_shards,
        edges_per_shard=e_max,
    )


def sharded_coo_specs(axis=("data",)) -> ShardedCOO:
    """PartitionSpecs for a ShardedCOO's array fields (leading dim over data)."""
    p = P(axis)
    return ShardedCOO(p, p, p, None, None, None, None)  # type: ignore[arg-type]


# ---------------------------------------------------------------------------
# Path 1 — paper-faithful GSPMD baseline
# ---------------------------------------------------------------------------

def spmv_gspmd(sm: ShardedCOO, x: Array) -> Array:
    """Plain segment_sum over globally-indexed rows; GSPMD chooses the
    collectives.  Used as the §Perf baseline for the eigensolver cells."""
    grow = global_rows(sm)
    contrib = sm.val.astype(jnp.float32) * x[sm.col].astype(jnp.float32)
    y = jax.ops.segment_sum(contrib, grow, num_segments=sm.shape[0])
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Path 2 — locality-exploiting shard_map (optimized)
# ---------------------------------------------------------------------------

def make_sharded_spmv(mesh: Mesh, sm: ShardedCOO, *, axis: str | tuple = "data",
                      gather_dtype=None):
    """Returns ``spmv(row_local, col, val, x) -> y`` as a shard_map closure.

    x and y are sharded by rows over ``axis``; edges over their leading dim.
    ``gather_dtype`` optionally downcasts x for the all-gather (bf16 halves
    ICI bytes; accumulation stays fp32) — a §Perf knob.
    """
    mesh = auto_mesh(mesh)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    espec = P(axes)
    xspec = P(axes)

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(espec, espec, espec, xspec),
        out_specs=xspec,
    )
    def spmv(row_local, col, val, x_blk):
        xg = x_blk
        if gather_dtype is not None:
            xg = xg.astype(gather_dtype)
        x_full = xg
        for ax in axes:  # gather over every sharded axis (pod then data)
            x_full = jax.lax.all_gather(x_full, ax, axis=0, tiled=True)
        contrib = val.astype(jnp.float32) * x_full[col].astype(jnp.float32)
        y = jax.ops.segment_sum(contrib, row_local, num_segments=sm.rows_per_shard)
        return y.astype(x_blk.dtype)

    return spmv


# ---------------------------------------------------------------------------
# Path 3 — row blocks in the coo_spmv layout, built on the chips
# ---------------------------------------------------------------------------

def axis_tuple(axis) -> tuple:
    """A mesh axis name, or a tuple of them, as a tuple."""
    return (axis,) if isinstance(axis, str) else tuple(axis)


def num_shards(mesh: Mesh, axis="data") -> int:
    """The shards rows are split into over ``axis`` (a name or a tuple)."""
    return int(np.prod([mesh.shape[a] for a in axis_tuple(axis)]))


def build_row_tiles(mesh: Mesh, m: COO, *, axis="data"):
    """Each chip's block of ``padded_rows(n, S) / S`` consecutive rows of
    the row-sorted COO ``m``, in the ``coo_spmv`` layout
    (:func:`repro.kernels.coo_spmv.build_tiles`), built on the chips under
    one shard_map from the whole COO: no host bucketing, so it runs inside
    a jitted job on Stage 1's device output.  Every array of the returned
    :class:`~repro.kernels.coo_spmv.CooTiles` gains a leading axis of one
    entry per chip, sharded over ``axis``; rows past n are empty."""
    return _build_row_tiles(m.row, m.col, m.val, mesh=auto_mesh(mesh),
                            axes=axis_tuple(axis), n=m.shape[0])


@partial(jax.jit, static_argnames=("mesh", "axes", "n"))
def _build_row_tiles(row, col, val, *, mesh, axes, n):
    from repro.kernels.coo_spmv import build_tiles

    rows = padded_rows(n, num_shards(mesh, axes)) // num_shards(mesh, axes)

    @partial(_shard_map, mesh=mesh, in_specs=(P(), P(), P()),
             out_specs=P(axes), **SHARD_MAP_NO_CHECK)
    def build(row, col, val):
        r0 = jax.lax.axis_index(axes) * rows
        t = build_tiles(row, col, val, n, r0=r0, rows=rows)
        return jax.tree.map(lambda a: a[None], t)

    return build(row, col, val)


def tiled_spmv(mesh: Mesh, tiles, x: Array, *, axis="data", impl="auto",
               interpret=None) -> Array:
    """``y = A x`` over :func:`build_row_tiles`' layout, ``x`` and ``y``
    [S · rows] sharded by rows over ``axis``: each chip all-gathers x
    (scope ``spmv_gather``) and runs the ``coo_spmv`` kernel over its own
    chunks, giving its own rows.  A Mosaic kernel runs across chips only
    inside a shard_map."""
    return _tiled_spmv(tiles, x, mesh=auto_mesh(mesh), axes=axis_tuple(axis),
                       impl=impl, interpret=interpret)


@partial(jax.jit, static_argnames=("mesh", "axes", "impl", "interpret"))
def _tiled_spmv(tiles, x, *, mesh, axes, impl, interpret):
    from repro.kernels.coo_spmv import coo_spmv

    @partial(_shard_map, mesh=mesh, in_specs=(P(axes), P(axes)),
             out_specs=P(axes), **SHARD_MAP_NO_CHECK)
    def spmv(t, x_blk):
        with jax.named_scope("spmv_gather"):
            x_full = jax.lax.all_gather(x_blk, axes, axis=0, tiled=True)
        t = jax.tree.map(lambda a: a[0], t)
        return coo_spmv(t, x_full, impl=impl, interpret=interpret)

    return spmv(tiles, x)


# ---------------------------------------------------------------------------
# Multi-vector paths (block Lanczos) — one collective per b-column block
# ---------------------------------------------------------------------------

def spmm_gspmd(sm: ShardedCOO, x: Array) -> Array:
    """Y = W @ X for dense X [n, b] over globally-indexed rows (GSPMD
    baseline).  Per-column 1-D segment sums, same rationale as
    :func:`repro.sparse.ops.spmm_coo`."""
    grow = global_rows(sm)
    val = sm.val.astype(jnp.float32)
    cols = [
        jax.ops.segment_sum(val * x[:, j][sm.col].astype(jnp.float32), grow,
                            num_segments=sm.shape[0])
        for j in range(x.shape[1])
    ]
    return jnp.stack(cols, axis=1).astype(x.dtype)


def make_sharded_spmm(mesh: Mesh, sm: ShardedCOO, *, axis: str | tuple = "data",
                      gather_dtype=None):
    """Returns ``spmm(row_local, col, val, x) -> y`` for X/Y of shape [n, b],
    rows sharded over ``axis`` — the block-Lanczos matmat engine.

    The single-vector SpMV pays one all-gather of x per Lanczos step; here
    ONE all-gather moves the whole [n, b] block, so the per-vector collective
    cost drops b× alongside the b× nnz-stream amortization — the two wins
    the block eigensolver was built for (DESIGN.md §3-4).
    """
    mesh = auto_mesh(mesh)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    espec = P(axes)
    xspec = P(axes, None)

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(espec, espec, espec, xspec),
        out_specs=xspec,
    )
    def spmm(row_local, col, val, x_blk):
        xg = x_blk
        if gather_dtype is not None:
            xg = xg.astype(gather_dtype)
        x_full = xg
        for ax in axes:  # one gather of the whole block per sharded axis
            x_full = jax.lax.all_gather(x_full, ax, axis=0, tiled=True)
        valf = val.astype(jnp.float32)
        cols = [
            jax.ops.segment_sum(valf * x_full[:, j][col].astype(jnp.float32),
                                row_local, num_segments=sm.rows_per_shard)
            for j in range(x_blk.shape[1])
        ]
        return jnp.stack(cols, axis=1).astype(x_blk.dtype)

    return spmm


# ---------------------------------------------------------------------------
# Ring exchange + collective accounting (Stage-1 ring candidate exchange)
# ---------------------------------------------------------------------------

def ring_perm(size: int):
    """The forward ring permutation over a ``size``-shard axis: shard i
    sends to shard (i+1) % size.  After t applications, shard i holds the
    payload that started on shard (i - t) % size."""
    return [(i, (i + 1) % size) for i in range(size)]


def ring_shift(tree, axis: str, size: int):
    """One forward ring step of an arbitrary pytree of arrays over the named
    mesh axis (inside shard_map).  Each leaf moves ``leaf.nbytes`` per step —
    the whole point: S-1 steps move (S-1)/S · n·d floats per shard instead of
    the all-gather's (S-1)/S · n·d *at once into a full-pool buffer*, and the
    peak per-shard footprint stays O(n/S)."""
    perm = ring_perm(size)
    return jax.tree.map(lambda a: jax.lax.ppermute(a, axis, perm), tree)


def collective_bytes(jaxpr) -> dict:
    """Measured per-shard collective traffic of a traced computation:
    ``{primitive: bytes_received_per_shard}`` summed over every collective
    eqn in the (closed) jaxpr, recursing through pjit/shard_map/scan/cond
    sub-jaxprs.

    The model (bytes RECEIVED per shard per eqn):

    * ``all_gather``  — ``(axis_size - 1) · operand_bytes`` (each shard
      receives every other shard's block);
    * ``ppermute``    — ``operand_bytes`` (one peer block per step);
    * ``psum``        — ``operand_bytes`` (ring all-reduce moves
      ``2·(S-1)/S ≈ 2×`` the operand, halved here to count receive-side
      only, rounded to the operand size — a lower bound).

    Loop bodies (scan/while) are counted ONCE — trip counts are not
    multiplied in, so apply this to unrolled programs (the Stage-1 ring is
    unrolled) or scale externally.
    """
    from jax.extend import core

    totals: dict = {}

    def visit(jx) -> None:
        if hasattr(jx, "jaxpr"):  # ClosedJaxpr
            jx = jx.jaxpr
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in ("all_gather", "ppermute", "psum", "all_to_all"):
                op_bytes = sum(
                    int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                    for v in eqn.invars if hasattr(v.aval, "shape"))
                if name == "all_gather":
                    op_bytes *= max(int(eqn.params.get("axis_size", 2)) - 1, 1)
                totals[name] = totals.get(name, 0) + op_bytes
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    if isinstance(sub, (core.Jaxpr, core.ClosedJaxpr)):
                        visit(sub)

    visit(jaxpr)
    totals["total"] = sum(totals.values())
    return totals


def trace_collective_bytes(fn, *args) -> dict:
    """:func:`collective_bytes` of ``jax.make_jaxpr(fn)(*args)``."""
    return collective_bytes(jax.make_jaxpr(fn)(*args))


def shard_vector(mesh: Mesh, x: Array, axis="data") -> Array:
    return jax.device_put(x, NamedSharding(auto_mesh(mesh), P(axis)))


def shard_edges(mesh: Mesh, sm: ShardedCOO, axis="data") -> ShardedCOO:
    s = NamedSharding(auto_mesh(mesh), P(axis))
    return dataclasses.replace(
        sm,
        row_local=jax.device_put(sm.row_local, s),
        col=jax.device_put(sm.col, s),
        val=jax.device_put(sm.val, s),
    )
