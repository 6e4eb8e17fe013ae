"""The `LinearOperator` protocol — ARPACK reverse communication, formalized.

The paper drives ARPACK through its *reverse-communication interface*: the
eigensolver never sees the matrix, only a contract "apply the operator to
this vector" that any implementation (CPU SpMV, GPU cuSPARSE, a PCIe-staged
hybrid) can fulfil.  Our jax-native analogue is this protocol: ``shape``,
``dtype``, ``mv`` ([n] → [n]) and ``mm`` ([n, b] → [n, b]), plus an optional
mesh descriptor for sharded implementations.  Everything downstream
(:func:`repro.core.lanczos.eigsh`, :class:`repro.core.spectral.SpectralPipeline`)
programs against the protocol, so operator representations — COO segment-sum,
BlockELL Pallas SpMM, the shard_map pod SpMV — swap freely behind a stable
eigensolver, exactly the composability RCI buys the paper (and the property
the Chebyshev-Davidson line of work relies on to swap eigensolvers).

Concrete implementations are registered dataclass pytrees: the wrapped
matrices are children (traced/sharded), execution knobs are static metadata,
so operators cross jit boundaries like any other container.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro.sparse.formats import COO, BlockELL
from repro.sparse.ops import spmm_blockell, spmm_coo, spmv_blockell, spmv_coo

Array = jax.Array


@runtime_checkable
class LinearOperator(Protocol):
    """Symmetric linear operator contract driven by the eigensolver.

    ``mv`` applies the operator to one vector ([n] → [n]); ``mm`` applies it
    to a multi-vector block ([n, b] → [n, b]) — the block-Lanczos stream.
    Implementations may carry a ``mesh`` attribute describing where their
    collectives run (``None`` for single-device operators).

    Matrix-backed implementations additionally expose ``nnz`` — the number
    of stored entries one application streams (padding slots included, since
    they are streamed too).  :func:`repro.core.lanczos.streamed_nnz`
    multiplies it by the solver's stream count for the cross-representation
    cost figure; closure-backed operators (:class:`CallableOperator`) have
    no meaningful value and simply omit the attribute.
    """

    @property
    def shape(self) -> Tuple[int, int]: ...

    @property
    def dtype(self) -> Any: ...

    def mv(self, x: Array) -> Array: ...

    def mm(self, x: Array) -> Array: ...


@dataclasses.dataclass(frozen=True)
class CooOperator:
    """Segment-sum SpMV/SpMM over a (pre-normalized) COO adjacency — the
    reference single-device operator behind :class:`SpectralPipeline`."""

    a: COO
    mesh: Any = None  # single-device: no collective placement

    @property
    def shape(self) -> Tuple[int, int]:
        return self.a.shape

    @property
    def dtype(self):
        return self.a.val.dtype

    @property
    def nnz(self) -> int:
        return self.a.nnz

    def mv(self, x: Array) -> Array:
        return spmv_coo(self.a, x)

    def mm(self, x: Array) -> Array:
        return spmm_coo(self.a, x)


jax.tree_util.register_dataclass(CooOperator, ["a"], ["mesh"])


@dataclasses.dataclass(frozen=True)
class TiledCooOperator:
    """A row-sorted COO whose single-vector product is the ``coo_spmv``
    Pallas kernel over its chunked layout (DESIGN.md §19): ``x`` held in
    VMEM, gathered in registers, rows reduced in the kernel.  ``mm`` keeps
    the segment-sum path of :class:`CooOperator`.  Build it with
    :meth:`build`, outside the product (the layout is built once, on the
    device); ``impl``/``interpret`` mirror the ``coo_spmv`` wrapper's
    knobs."""

    a: COO
    tiles: Any  # repro.kernels.coo_spmv.CooTiles
    impl: str = "auto"  # "auto" | "pallas" | "ref"
    interpret: Optional[bool] = None
    mesh: Any = None

    @classmethod
    def build(cls, a: COO, **knobs) -> "TiledCooOperator":
        from repro.kernels.coo_spmv import build_tiles
        from repro.sparse.ops import sort_coo_rows

        s = sort_coo_rows(a)
        return cls(a, build_tiles(s.row, s.col, s.val, a.shape[0]), **knobs)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.a.shape

    @property
    def dtype(self):
        return self.a.val.dtype

    @property
    def nnz(self) -> int:
        # the layout's slots, padding included: the static bound of what
        # one product streams (the kernel runs the chunks the rows use)
        return self.tiles.slots

    def mv(self, x: Array) -> Array:
        from repro.kernels.coo_spmv import coo_spmv

        return coo_spmv(self.tiles, x, impl=self.impl,
                        interpret=self.interpret)

    def mm(self, x: Array) -> Array:
        return spmm_coo(self.a, x)


jax.tree_util.register_dataclass(TiledCooOperator, ["a", "tiles"],
                                 ["impl", "interpret", "mesh"])


@dataclasses.dataclass(frozen=True)
class RowTiledCooOperator:
    """:class:`TiledCooOperator` with its rows split in equal blocks over a
    mesh axis (DESIGN.md §20).  Each chip holds its own block in the
    ``coo_spmv`` layout, built on the chips from the whole row-sorted COO
    (:func:`repro.sparse.distributed.build_row_tiles`); ``mv`` is one
    shard_map: one all-gather of ``x``, then the kernel over the chip's
    own chunks (:func:`repro.sparse.distributed.tiled_spmv`).  Vectors are
    [n], sharded by rows (``row_sharding``); where the chips do not divide
    n, ``mv`` pads ``x`` with zeros and cuts ``y`` back, so the padding
    rows never enter the caller's vectors.  ``mm`` keeps the segment-sum
    path of :class:`CooOperator`."""

    a: COO
    tiles: Any  # repro.kernels.coo_spmv.CooTiles, one block per chip
    mesh: Any
    axis: Any = "data"
    impl: str = "auto"  # "auto" | "pallas" | "ref"
    interpret: Optional[bool] = None

    @classmethod
    def build(cls, a: COO, mesh, axis="data", **knobs) -> "RowTiledCooOperator":
        from repro.sparse.distributed import auto_mesh, build_row_tiles
        from repro.sparse.ops import sort_coo_rows

        mesh = auto_mesh(mesh)
        return cls(a, build_row_tiles(mesh, sort_coo_rows(a), axis=axis),
                   mesh, axis, **knobs)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.a.shape

    @property
    def dtype(self):
        return self.a.val.dtype

    @property
    def nnz(self) -> int:
        # one chip's slots, padding included: each chip's layout is sized
        # to hold all the nonzeros, and the chips' blocks split them, so a
        # product streams about this many over all chips, not S times it
        return self.tiles.slots // self.shards

    @property
    def shards(self) -> int:
        return self.tiles.cols.shape[0]

    @property
    def row_sharding(self):
        """Where an [n] vector's rows live: split over the mesh axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.axis))

    def mv(self, x: Array) -> Array:
        from repro.sparse.distributed import tiled_spmv

        n, pad = self.shape[0], self.shards * self.tiles.rows - self.shape[0]
        y = tiled_spmv(self.mesh, self.tiles, jnp.pad(x, (0, pad)) if pad
                       else x, axis=self.axis, impl=self.impl,
                       interpret=self.interpret)
        return y[:n] if pad else y

    def mm(self, x: Array) -> Array:
        return spmm_coo(self.a, x)


jax.tree_util.register_dataclass(RowTiledCooOperator, ["a", "tiles"],
                                 ["mesh", "axis", "impl", "interpret"])


@dataclasses.dataclass(frozen=True)
class BlockEllOperator:
    """BlockELL(+COO tail) operator: dense strided ELL-body loads, with the
    multi-vector ``mm`` going through the ``ell_spmm`` wrapper, which runs
    its XLA path on TPU until the kernel compiles there (``impl``/
    ``interpret`` mirror the wrapper's knobs)."""

    a: BlockELL
    impl: str = "auto"  # "auto" | "pallas" | "ref"
    interpret: Optional[bool] = None
    mesh: Any = None

    def __post_init__(self):
        if self.impl not in ("auto", "pallas", "ref"):
            raise ValueError(
                f"BlockEllOperator.impl must be one of 'auto', 'pallas', "
                f"'ref', got {self.impl!r}")

    @property
    def shape(self) -> Tuple[int, int]:
        return self.a.shape

    @property
    def dtype(self):
        return self.a.vals.dtype

    @property
    def nnz(self) -> int:
        # ELL padding slots are streamed like real entries; the tail rides
        # the segment-sum path — both count toward bytes-per-application
        return int(self.a.vals.size) + self.a.tail.nnz

    def mv(self, x: Array) -> Array:
        return spmv_blockell(self.a, x)

    def mm(self, x: Array) -> Array:
        if self.impl == "ref":
            return spmm_blockell(self.a, x)
        from repro.kernels.ell_spmm.ops import ell_spmm

        return ell_spmm(self.a, x, impl=self.impl, interpret=self.interpret)

    def cheb_step(self, x: Array, prev: Array, ca: Array, cb: Array) -> Array:
        """Fused Chebyshev three-term step ``ca·(A x) + cb·x − prev``.

        Optional protocol hook consumed by
        :func:`repro.core.chebyshev.chebyshev_filter`: the recurrence's AXPY
        chain rides the ``ell_spmm`` epilogue instead of issuing three extra
        elementwise passes over the [n, b] iterates.
        """
        from repro.kernels.ell_spmm.ops import ell_spmm_cheb_step

        return ell_spmm_cheb_step(
            self.a, x, prev, ca, cb, impl=self.impl, interpret=self.interpret)


jax.tree_util.register_dataclass(BlockEllOperator, ["a"], ["impl", "interpret", "mesh"])


@dataclasses.dataclass(frozen=True)
class ShardedCooOperator:
    """Row-block-partitioned pod operator over a :class:`ShardedCOO`.

    ``variant="gspmd"`` is the paper-faithful baseline (segment_sum over
    global rows; GSPMD inserts the collectives); ``variant="shard_map"`` is
    the locality-exploiting explicit path (one all-gather of x per
    application — the ICI analogue of the paper's one-PCIe-transfer design;
    ``gather_dtype=bf16`` halves those bytes).  ``mm`` moves one [n, b]
    block per collective — the block-Lanczos amortization (DESIGN.md §4).
    """

    sm: Any  # ShardedCOO (kept untyped here to avoid a hard import cycle)
    variant: str = "gspmd"
    mesh: Any = None
    axis: Any = "data"
    gather_dtype: Any = None

    def __post_init__(self):
        if self.variant not in ("gspmd", "shard_map"):
            raise ValueError(
                f"ShardedCooOperator.variant must be 'gspmd' or 'shard_map', "
                f"got {self.variant!r}")
        if self.variant == "shard_map" and self.mesh is None:
            raise ValueError(
                "ShardedCooOperator(variant='shard_map') needs a mesh — the "
                "explicit-collective SpMV is built per mesh axis")

    @property
    def shape(self) -> Tuple[int, int]:
        return self.sm.shape

    @property
    def dtype(self):
        return self.sm.val.dtype

    @property
    def nnz(self) -> int:
        # per-shard padding (null edges) is streamed like real entries
        return int(self.sm.val.shape[0])

    def mv(self, x: Array) -> Array:
        from repro.sparse.distributed import make_sharded_spmv, spmv_gspmd

        if self.variant == "shard_map":
            inner = make_sharded_spmv(self.mesh, self.sm, axis=self.axis,
                                      gather_dtype=self.gather_dtype)
            return inner(self.sm.row_local, self.sm.col, self.sm.val, x)
        return spmv_gspmd(self.sm, x)

    def mm(self, x: Array) -> Array:
        from repro.sparse.distributed import make_sharded_spmm, spmm_gspmd

        if self.variant == "shard_map":
            inner = make_sharded_spmm(self.mesh, self.sm, axis=self.axis,
                                      gather_dtype=self.gather_dtype)
            return inner(self.sm.row_local, self.sm.col, self.sm.val, x)
        return spmm_gspmd(self.sm, x)


jax.tree_util.register_dataclass(
    ShardedCooOperator, ["sm"], ["variant", "mesh", "axis", "gather_dtype"])


@dataclasses.dataclass(frozen=True)
class CallableOperator:
    """Adapter wrapping bare ``matvec``/``matmat`` closures into the protocol
    (the legacy surface; also handy for tests and custom operators).

    Without an explicit ``matmat``, ``mm`` vmaps ``matvec`` over columns — a
    correctness fallback that forfeits the single-stream amortization.
    Not a pytree (it captures closures); construct it at trace time.
    """

    n: int
    matvec: Optional[Callable[[Array], Array]] = None
    matmat: Optional[Callable[[Array], Array]] = None
    dtype: Any = jnp.float32
    mesh: Any = None

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n, self.n)

    def mv(self, x: Array) -> Array:
        assert self.matvec is not None, "need matvec for single-vector mode"
        return self.matvec(x)

    def mm(self, x: Array) -> Array:
        if self.matmat is not None:
            return self.matmat(x)
        assert self.matvec is not None, "need matvec or matmat"
        return jax.vmap(self.matvec, in_axes=1, out_axes=1)(x)
