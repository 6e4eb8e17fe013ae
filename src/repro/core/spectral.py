"""Unified stage-graph API: one entry point for local and sharded execution.

The paper's architecture is a three-stage pipeline — kNN similarity graph →
Laplacian eigensolver → k-means — glued together by ARPACK's reverse-
communication interface.  :class:`SpectralPipeline` is that architecture as
a facade: nested per-stage configs (:class:`GraphConfig`,
:class:`EigConfig`, :class:`~repro.core.kmeans.KMeansConfig`), an execution
:class:`Plan` (single device or a mesh), and three independently runnable,
resumable stages::

    pipe  = SpectralPipeline(n_clusters=8)
    state = pipe.build_graph(x)        # Stage 1 (or pipe.prepare(w) for a
                                       #   prebuilt COO / ShardedCOO graph)
    emb   = pipe.embed(state, key)     # Stage 2: Lanczos → spectral embedding
    out   = pipe.cluster(emb, key2)    # Stage 3: k-means on the embedding
    out   = pipe.run(x_or_graph, key)  # or all three at once

Stage boundaries are real state objects, so serving-shaped reuse is free:
``pipe.cluster(emb, key, n_clusters=2 * k)`` re-clusters a cached embedding
at a different k without re-entering the eigensolver.

The facade is literally a stage DAG: ``run`` threads a typed
:class:`PipelineState` through the ordered ``stages`` tuple (default
``("prepare", "embed", "cluster")``), and graph-reduction stages from
:mod:`repro.core.reduce` interpose without forking the API::

    pipe = SpectralPipeline(n_clusters=8,
                            stages=("prepare", "sparsify", "embed", "cluster"),
                            sparsify=SparsifyConfig(target_nnz_ratio=0.4))
    out  = pipe.run(x, key)   # Stage 1.5 shrinks the operator before Stage 2

Plan dispatch replaces the old parallel ``_sharded`` code paths: the same
stage graph runs on one device (``Plan()``), over a row-partitioned
:class:`~repro.sparse.distributed.ShardedCOO` (operator collectives chosen
by ``plan.variant``), or with a row-block-sharded Stage 1 for raw points
(``Plan(device="sharded", mesh=...)``).  All operator plumbing goes through
the :class:`~repro.core.operator.LinearOperator` protocol — no bare
matvec/matmat closures anywhere in the stage graph.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.health as health
import repro.core.kmeans as km
import repro.core.lanczos as lz
import repro.core.laplacian as lap
from repro.core.health import HealthConfig, PipelineError, StageReport
from repro.core.operator import (CooOperator, LinearOperator,
                                 RowTiledCooOperator, ShardedCooOperator,
                                 TiledCooOperator)
from repro.core.reduce import (
    CoarsenConfig,
    ReduceInfo,
    ReductionState,
    SparsifyConfig,
)
from repro.kernels.lsh_candidates.ops import (
    DEFAULT_N_BITS as _DEFAULT_LSH_BITS,
    DEFAULT_N_TABLES as _DEFAULT_LSH_TABLES,
    MAX_N_BITS as _MAX_LSH_BITS,
)
from repro.core.similarity import build_knn_graph, graph_from_knn
from repro.kernels.coo_spmv import kernel_applies
from repro.sparse.distributed import (
    ShardedCOO,
    auto_mesh,
    global_rows,
    normalize_sharded,
    partition_coo_by_rows,
    spmv_gspmd,
)
from repro.sparse.formats import COO

Array = jax.Array

KMeansConfig = km.KMeansConfig  # the Stage-3 nested config (re-exported)

_MEASURES = ("cosine", "cross_correlation", "exp_decay")
_METHODS = ("exact", "lsh")
_KNN_IMPLS = ("auto", "pallas", "ref")
_DEVICES = ("single", "sharded")
_VARIANTS = ("gspmd", "shard_map")
_EXCHANGES = ("gather", "ring")


class SpectralResult(NamedTuple):
    labels: Array  # [n] cluster assignment
    embedding: Array  # [n, k] row-normalized spectral embedding
    eigenvalues: Array  # [k] of L_sym (ascending; ~0 first)
    eig_residuals: Array
    kmeans_inertia: Array
    lanczos_restarts: Array
    kmeans_iterations: Array
    reports: Tuple[StageReport, ...] = ()  # per-stage health trail (run())
    operator_applications: Any = None  # [] Stage-2 mv/mm calls (all attempts)


def default_basis_size(n: int, k: int, b: int = 1) -> int:
    """ARPACK-style ncv ≥ 2k, widened with the Krylov block so every restart
    cycle still runs several block steps (block mode loses polynomial degree
    per basis column; extra columns buy it back — DESIGN.md §3)."""
    return min(n, max(2 * k, k + 16, k + 8 * b))


# ---------------------------------------------------------------------------
# Per-stage configs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphConfig:
    """Stage-1 knobs (kNN similarity-graph construction, paper Alg. 1).

    ``method`` selects the neighbor search: ``"exact"`` (default, the fused
    O(n²d) ``knn_topk`` kernel) or ``"lsh"`` (random-hyperplane candidate
    generation + exact rerank, O(n·m·d) — the n ≫ 100k regime; DESIGN.md
    §12).  ``n_tables``/``n_bits``/``candidates``/``lsh_seed`` are the LSH
    recall knobs; ``candidates=None`` derives m from ``knn_k``/``n_tables``
    (:func:`repro.kernels.lsh_candidates.ops.default_candidates`).

    ``block_q``/``block_k`` default to the per-path kernel tile choices
    (256 on the single-device search, 1024 rows/shard on the row-block
    sharded search) when left ``None``.
    """

    knn_k: int = 10
    measure: str = "exp_decay"  # "cosine" | "cross_correlation" | "exp_decay"
    sigma: float = 1.0
    eps: Union[float, Array, None] = None  # degree-capped ε-ball radius
    method: str = "exact"  # neighbor search: "exact" | "lsh"
    n_tables: int = _DEFAULT_LSH_TABLES  # LSH hash tables (recall ∝ union)
    n_bits: int = _DEFAULT_LSH_BITS  # hyperplane bits/table (bucket resolution)
    candidates: Optional[int] = None  # per-query candidate budget m; None=auto
    lsh_seed: int = 0  # hyperplane PRNG seed (static, serializable)
    impl: str = "auto"  # knn_topk dispatch: "auto" | "pallas" | "ref"
    block_q: Optional[int] = None
    block_k: Optional[int] = None
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.measure not in _MEASURES:
            raise ValueError(
                f"GraphConfig.measure must be one of {_MEASURES}, got "
                f"{self.measure!r}")
        if self.method not in _METHODS:
            raise ValueError(
                f"GraphConfig.method must be one of {_METHODS} (neighbor-"
                f"search dispatch), got {self.method!r}")
        if self.impl not in _KNN_IMPLS:
            raise ValueError(
                f"GraphConfig.impl must be one of {_KNN_IMPLS} (knn_topk "
                f"kernel dispatch), got {self.impl!r}")
        if self.knn_k < 1:
            raise ValueError(f"GraphConfig.knn_k must be >= 1, got {self.knn_k}")
        if self.n_tables < 1:
            raise ValueError(
                f"GraphConfig.n_tables must be >= 1, got {self.n_tables}")
        if not 1 <= self.n_bits <= _MAX_LSH_BITS:
            raise ValueError(
                f"GraphConfig.n_bits must be in [1, {_MAX_LSH_BITS}] (codes "
                f"pack into fp32-exact int32), got {self.n_bits}")
        if self.candidates is not None and self.candidates < self.n_tables:
            raise ValueError(
                f"GraphConfig.candidates={self.candidates} < n_tables="
                f"{self.n_tables} — each table needs a window of at least 1")

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if d["eps"] is not None:
            if getattr(d["eps"], "size", 1) != 1:
                raise ValueError(
                    "GraphConfig.eps is a per-node array — not JSON-"
                    "serializable; to_dict() needs a scalar radius (or None)")
            d["eps"] = float(d["eps"])
        return d


_SOLVERS = ("lanczos", "chebyshev")
_REPRESENTATIONS = ("coo", "blockell")


@dataclasses.dataclass(frozen=True)
class EigConfig:
    """Stage-2 knobs (paper Alg. 2-3).

    ``solver`` selects the embedding engine: ``"lanczos"`` (default, the
    thick-restart Lanczos — exact eigenpairs, reorthogonalization-bound at
    large k) or ``"chebyshev"`` (Jackson-damped polynomial-filter embedding
    of ``n_signals`` random sketches — fixed operator-stream cost, no
    reorthogonalization, no global QR per step; DESIGN.md §13).  The
    chebyshev knobs: ``cheb_degree`` (filter sharpness), ``n_signals``
    (sketch width R; ``None`` → k + 8), ``lambda_cut`` (passband edge in
    adjacency-eigenvalue units, "keep θ ≥ λ_cut"; ``None`` locates it by
    eigencount bisection targeting k).

    ``representation`` picks the single-device Stage-2 operator layout:
    ``"coo"`` (segment-sum SpMM) or ``"blockell"`` (host-side
    ``csr_to_blockell`` conversion at the operator injection point, so both
    solvers stream the Pallas ``ell_spmm`` kernel).  The conversion is
    host-side data-pipeline work: under a jit trace the graph values are
    abstract, so the pipeline falls back to COO with a warning — build the
    graph state eagerly (or pass ``operator=`` to :meth:`SpectralPipeline
    .embed`) to get the fast path inside a jitted embed.
    """

    n_eigvecs: Optional[int] = None  # embedding width; default: n_clusters
    basis_m: Optional[int] = None  # Krylov basis (ARPACK ncv); default 2k-ish
    tol: float = 1e-5
    max_restarts: int = 60
    block_size: int = 1  # Krylov block width b (>1: multi-vector SpMM mode)
    drop_first: bool = False  # drop the trivial eigenvector from the embedding
    fixed_restarts: Optional[int] = None  # static-cost mode (dry-run/bench)
    solver: str = "lanczos"  # "lanczos" | "chebyshev" (polynomial filter)
    cheb_degree: int = 64  # Chebyshev filter degree (transition sharpness)
    n_signals: Optional[int] = None  # chebyshev sketch width R; None → k + 8
    lambda_cut: Optional[float] = None  # passband edge; None → bisection
    cheb_margin: float = 0.01  # spectral-interval safety margin (bounds est.)
    representation: str = "coo"  # single-device operator: "coo" | "blockell"
    strict: bool = False  # raise PipelineError on unconverged embed (CI/bench)

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(
                f"EigConfig.block_size must be >= 1, got {self.block_size}")
        if self.tol <= 0:
            raise ValueError(f"EigConfig.tol must be > 0, got {self.tol}")
        if self.solver not in _SOLVERS:
            raise ValueError(
                f"EigConfig.solver must be one of {_SOLVERS} (Stage-2 "
                f"engine dispatch), got {self.solver!r}")
        if self.cheb_degree < 1:
            raise ValueError(
                f"EigConfig.cheb_degree must be >= 1, got {self.cheb_degree}")
        if self.n_signals is not None and self.n_signals < 1:
            raise ValueError(
                f"EigConfig.n_signals must be >= 1 (or None for the k + 8 "
                f"default), got {self.n_signals}")
        if self.cheb_margin <= 0:
            raise ValueError(
                f"EigConfig.cheb_margin must be > 0 (the bounds estimator "
                f"needs a containment margin), got {self.cheb_margin}")
        if self.representation not in _REPRESENTATIONS:
            raise ValueError(
                f"EigConfig.representation must be one of {_REPRESENTATIONS} "
                f"(Stage-2 operator layout), got {self.representation!r}")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Execution plan: where the stage graph runs and which collective
    schedule the sharded operator uses.

    device        "single" (default) or "sharded".  A ShardedCOO input always
                  runs the sharded Stage 2-3 regardless (its layout implies
                  the mesh); ``device="sharded"`` additionally row-block-
                  shards Stage 1 for raw-points inputs, on a TPU row-shards
                  Stage 2's single-vector Lanczos over the chips on the
                  ``coo_spmv`` kernel (DESIGN.md §20), and enables the
                  explicit-collective Stage 3 under ``variant="shard_map"``.
    mesh          jax Mesh (required for shard_map collectives and the
                  sharded Stage 1; not serialized by :meth:`to_dict`).
    axis          mesh axis name (or tuple) the rows are partitioned over.
    variant       sharded operator engine: "gspmd" (paper-faithful baseline,
                  partitioner-chosen collectives) | "shard_map" (explicit
                  one-all-gather-per-application schedule).
    gather_dtype  optional downcast for shard_map all-gathers (e.g.
                  "bfloat16" halves ICI bytes; accumulation stays fp32).
    stage1_exchange
                  sharded Stage-1 candidate exchange: "gather" (default —
                  every shard all-gathers the full point set; bitwise the
                  pre-knob behavior) | "ring" (peer row blocks stream via
                  ``ppermute`` with an online per-row top-k merge; no shard
                  materializes the full pool — per-shard traffic O(n·d/S)
                  per step instead of O(n·d) at once.  Exact method stays
                  bitwise-equal to "gather"; LSH routes by bucket code and
                  is recall-gated).  See
                  :func:`repro.core.distributed_pipeline.make_knn_rowblock`.
    """

    device: str = "single"
    mesh: Any = None
    axis: Any = "data"
    variant: str = "gspmd"
    gather_dtype: Any = None
    stage1_exchange: str = "gather"

    def __post_init__(self):
        if self.device not in _DEVICES:
            raise ValueError(
                f"Plan.device must be one of {_DEVICES}, got {self.device!r} "
                f"(pass mesh/axis/variant for the sharded plan)")
        if self.variant not in _VARIANTS:
            raise ValueError(
                f"Plan.variant must be one of {_VARIANTS}, got "
                f"{self.variant!r}")
        if self.stage1_exchange not in _EXCHANGES:
            raise ValueError(
                f"Plan.stage1_exchange must be one of {_EXCHANGES}, got "
                f"{self.stage1_exchange!r}")
        # NOTE: variant="shard_map" needs a mesh at *dispatch* time (the
        # ShardedCooOperator raises); construction stays mesh-free so plans
        # round-trip through to_dict()/from_dict() and get the mesh
        # reattached afterwards.
        object.__setattr__(self, "mesh", auto_mesh(self.mesh))
        if self.gather_dtype is not None:
            # canonicalize to the dtype name so configs stay JSON-safe and
            # round-trip equal (astype accepts the string form)
            object.__setattr__(self, "gather_dtype",
                               jnp.dtype(self.gather_dtype).name)

    def to_dict(self) -> dict:
        return {
            "device": self.device,
            "axis": list(self.axis) if isinstance(self.axis, tuple) else self.axis,
            "variant": self.variant,
            "gather_dtype": self.gather_dtype,
            "stage1_exchange": self.stage1_exchange,
            # mesh is a runtime resource, not config — reattach it after
            # from_dict via dataclasses.replace(plan, mesh=mesh)
        }

    @classmethod
    def from_dict(cls, d: dict, *, mesh: Any = None) -> "Plan":
        axis = d.get("axis", "data")
        return cls(
            device=d.get("device", "single"),
            mesh=mesh,
            axis=tuple(axis) if isinstance(axis, list) else axis,
            variant=d.get("variant", "gspmd"),
            gather_dtype=d.get("gather_dtype"),
            stage1_exchange=d.get("stage1_exchange", "gather"),
        )


# ---------------------------------------------------------------------------
# Stage states (the resumable checkpoints between stages)
# ---------------------------------------------------------------------------

class GraphState(NamedTuple):
    """Stage-1 output: the sym-normalized adjacency + degree bookkeeping.
    ``adj`` is a COO (single-device operator) or ShardedCOO (pod operator)."""

    adj: Union[COO, ShardedCOO]  # D^{-1/2} W D^{-1/2}
    deg: Array  # [n] degrees of the raw graph
    inv_sqrt_deg: Array  # [n] D^{-1/2} (0 where isolated)
    knn_tiles: Any = None  # [2] int32 knn_topk tiles merged, visited


class EmbedState(NamedTuple):
    """Stage-2 output: the spectral embedding, cacheable/re-clusterable."""

    embedding: Array  # [n, k] row-normalized spectral embedding
    eigenvalues: Array  # [k] Laplacian eigenvalues 1-θ (ascending; ~0 first)
    residuals: Array  # eigensolver residuals (pre drop_first bookkeeping)
    restarts: Array  # [] Lanczos restart count
    converged: Any = True  # [] solver convergence flag (bool or 0-d array)
    operator_applications: Any = None  # [] operator mv/mm calls executed


# ---------------------------------------------------------------------------
# The stage DAG
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PipelineState:
    """The typed value the stage DAG threads: every stage is a named
    transform ``PipelineState → PipelineState`` that fills (or replaces) the
    slots it owns and appends to ``provenance``.

    The slots are exactly the resumable checkpoints the facade already
    exposed — ``graph`` is a :class:`GraphState`, ``embedding`` an
    :class:`EmbedState`, ``result`` a :class:`SpectralResult` — plus the
    reduction bookkeeping (:class:`~repro.core.reduce.ReductionState`) that
    ``refine`` consumes and the per-stage PRNG keys ``run`` splits up front
    (one split, fixed order, so the default stage tuple is bitwise-identical
    to the pre-DAG pipeline).
    """

    points: Optional[Array] = None  # raw [n, d] features (Stage-1 input)
    search_points: Optional[Array] = None  # optional separate kNN coordinates
    input_graph: Union[COO, ShardedCOO, None] = None  # prebuilt graph input
    graph: Optional[GraphState] = None  # Stage-1 (or reduced) output
    embedding: Optional[EmbedState] = None  # Stage-2 output
    result: Optional["SpectralResult"] = None  # Stage-3 output
    reduction: Optional[ReductionState] = None  # coarsen→refine hand-off
    reductions: Tuple[ReduceInfo, ...] = ()  # all reduction provenance numbers
    key_embed: Optional[Array] = None  # Stage-2 PRNG key
    key_cluster: Optional[Array] = None  # Stage-3 PRNG key
    operator_override: Optional[LinearOperator] = None  # embed operator=
    provenance: Tuple[str, ...] = ()  # executed-stage trail (human-readable)
    reports: Tuple[StageReport, ...] = ()  # per-stage health records


# Canonical stage order.  ``stages`` must be a subsequence of this: the
# reductions sit between graph construction and the eigensolve (Stage 1.5),
# and refine — the coarse→fine lift — must follow embed.
_STAGE_ORDER = ("prepare", "sparsify", "coarsen", "embed", "refine", "cluster")


def _stage_done(name: str, provenance: Tuple[str, ...]) -> bool:
    """Has ``name`` already run in this state?  Provenance entries are the
    stage name or ``name[annotation]`` (reductions record their numbers)."""
    return any(p == name or p.startswith(name + "[") for p in provenance)
_REQUIRED_STAGES = ("prepare", "embed", "cluster")
DEFAULT_STAGES = ("prepare", "embed", "cluster")


def _raw_weights(state: GraphState, *, host_compact: bool = False) -> COO:
    """Recover the raw similarity weights from a Stage-1 state by undoing the
    sym normalization: ``W = D^{1/2} A_sym D^{1/2}`` entrywise (``adj`` is
    ``D^{-1/2} W D^{-1/2}`` and ``deg`` is kept exactly for this).

    The reduction stages resample/merge *raw* weights and then re-derive
    degrees + normalization on the reduced graph — reusing :meth:`
    SpectralPipeline.prepare` so reduced states satisfy the same invariants
    (v0 = √deg, NJW row maps) as unreduced ones.

    ``host_compact=True`` (the sharded paths, which re-bucket host-side
    anyway) additionally drops the null padding edges so reduction ratios
    are measured on real nnz; it needs concrete arrays.
    """
    sq = jnp.sqrt(jnp.maximum(state.deg.astype(jnp.float32), 0.0))
    adj = state.adj
    if isinstance(adj, ShardedCOO):
        grow = global_rows(adj)
        val = adj.val.astype(jnp.float32) * sq[grow] * sq[adj.col]
        w = COO(row=grow, col=adj.col, val=val, shape=adj.shape,
                sorted_rows=False)
    else:
        val = adj.val.astype(jnp.float32) * sq[adj.row] * sq[adj.col]
        w = COO(row=adj.row, col=adj.col, val=val, shape=adj.shape,
                sorted_rows=adj.sorted_rows)
    if host_compact:
        try:
            row = np.asarray(w.row)
            col = np.asarray(w.col)
            val = np.asarray(w.val)
        except jax.errors.TracerArrayConversionError as e:
            raise TypeError(
                "the sharded reduction stages re-bucket edges host-side "
                "(partition_coo_by_rows) and need concrete graph arrays — "
                "run the reduction eagerly, then jit embed/cluster on the "
                "reduced state") from e
        keep = val != 0
        w = COO(row=jnp.asarray(row[keep]), col=jnp.asarray(col[keep]),
                val=jnp.asarray(val[keep]), shape=w.shape, sorted_rows=False)
    return w


def _row_blocks_note(op: RowTiledCooOperator) -> str:
    """The Stage-2 report's note of the row-sharded kernel path: chips,
    rows and layout slots a chip; where the layout is concrete (an eager
    run), the shards' balance too: the most nonzeros and chunks a chip
    holds.  A note is static, so a jitted job's report has no balance."""
    t, s = op.tiles, op.shards
    note = (f"coo_spmv_rows[shards={s},rows={t.rows},nnz={op.a.nnz},"
            f"slots={op.nnz}")
    if health.is_concrete(t.used):
        nnz = np.asarray((t.cols >= 0).sum(axis=(1, 2)))
        note += f",nnz_max={int(nnz.max())},used_max={int(np.max(t.used))}"
    return note + "]"


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpectralPipeline:
    """The paper's three-stage pipeline as a single configured object.

    A frozen dataclass: hashable, closable over by jit, and JSON-round-
    trippable via :meth:`to_dict` / :meth:`from_dict` (the serving dry-run
    reproducibility contract — only ``plan.mesh`` is a runtime resource that
    must be reattached after deserialization).
    """

    n_clusters: int
    graph: GraphConfig = GraphConfig()
    eig: EigConfig = EigConfig()
    kmeans: KMeansConfig = KMeansConfig()
    plan: Plan = Plan()
    stages: Tuple[str, ...] = DEFAULT_STAGES  # ordered stage DAG
    sparsify: SparsifyConfig = SparsifyConfig()  # Stage-1.5 edge sampling
    coarsen: CoarsenConfig = CoarsenConfig()  # Stage-1.5 HEM + refine knobs
    health: HealthConfig = HealthConfig()  # fail-soft guards + escalation

    def __post_init__(self):
        if self.n_clusters < 1:
            raise ValueError(
                f"SpectralPipeline.n_clusters must be >= 1, got {self.n_clusters}")
        if self.kmeans.k is not None and self.kmeans.k != self.n_clusters:
            raise ValueError(
                f"KMeansConfig.k={self.kmeans.k} conflicts with "
                f"n_clusters={self.n_clusters} — leave k unset (the pipeline "
                f"fills it) or pass n_clusters= to cluster() to re-cluster "
                f"at a different k")
        stages = tuple(self.stages)
        object.__setattr__(self, "stages", stages)  # list → tuple (from_dict)
        unknown = [s for s in stages if s not in _STAGE_ORDER]
        if unknown:
            raise ValueError(
                f"SpectralPipeline.stages contains unknown stage(s) "
                f"{unknown} — known stages (canonical order): {_STAGE_ORDER}")
        if len(set(stages)) != len(stages):
            raise ValueError(
                f"SpectralPipeline.stages has duplicates: {stages}")
        ranks = [_STAGE_ORDER.index(s) for s in stages]
        if ranks != sorted(ranks):
            raise ValueError(
                f"SpectralPipeline.stages must follow the canonical order "
                f"{_STAGE_ORDER} (reductions between prepare and embed, "
                f"refine after embed), got {stages}")
        missing = [s for s in _REQUIRED_STAGES if s not in stages]
        if missing:
            raise ValueError(
                f"SpectralPipeline.stages must include {_REQUIRED_STAGES} "
                f"(missing {missing}) — run stages individually via "
                f"prepare/embed/cluster for partial execution")
        if ("coarsen" in stages) != ("refine" in stages):
            raise ValueError(
                "coarsen and refine are paired: coarsen shrinks the node set "
                "so cluster needs refine's coarse→fine lift (and refine has "
                "no prolongation map without coarsen) — include both or "
                "neither")

    # -- config plumbing ----------------------------------------------------

    def _lanczos_config(self, n: int,
                        eig: Optional[EigConfig] = None) -> lz.LanczosConfig:
        e = eig if eig is not None else self.eig
        k = e.n_eigvecs or self.n_clusters
        b = e.block_size
        m = e.basis_m or default_basis_size(n, k, b)
        return lz.LanczosConfig(
            k=k + (1 if e.drop_first else 0),
            m=max(m, k + (2 if e.drop_first else 1)),
            max_restarts=e.max_restarts,
            tol=e.tol,
            which="LA",
            fixed_restarts=e.fixed_restarts,
            block_size=b,
        )

    def _cheb_config(self, n: int, eig: Optional[EigConfig] = None):
        from repro.core.chebyshev import ChebConfig

        e = eig if eig is not None else self.eig
        k = (e.n_eigvecs or self.n_clusters) + (1 if e.drop_first else 0)
        return ChebConfig(
            k=k,
            degree=e.cheb_degree,
            n_signals=e.n_signals,
            lambda_cut=e.lambda_cut,
            margin=e.cheb_margin,
            which="LA",
        )

    def _eig_config(self, n: int, eig: Optional[EigConfig] = None):
        """The engine config :func:`repro.core.lanczos.eigsh` dispatches on —
        the solver="lanczos" branch is byte-identical to the pre-chebyshev
        call chain (the bitwise shim tests pin this).  ``eig`` overrides the
        pipeline's Stage-2 config: the escalation controller's handle for
        widened-basis / widened-margin / fallback-solver retries."""
        e = eig if eig is not None else self.eig
        if e.solver == "chebyshev":
            return self._cheb_config(n, e)
        return self._lanczos_config(n, e)

    def operator(self, state: GraphState) -> LinearOperator:
        """The Stage-2 operator for this graph under this plan — the single
        place operator representations are chosen (swap freely here).

        ``eig.representation="blockell"`` converts the COO graph to
        BlockELL(+tail) host-side so both solvers stream the Pallas
        ``ell_spmm`` kernel.  Conversion needs concrete arrays — under a jit
        trace it falls back to the COO operator with a warning (build the
        state eagerly, or pass ``operator=`` into :meth:`embed`).

        On a TPU, single-vector Lanczos gets the ``coo_spmv`` kernel
        (DESIGN.md §19) for graphs of up to
        ``repro.kernels.coo_spmv.ops.MAX_N`` nodes: on one device the
        :class:`~repro.core.operator.TiledCooOperator`, under a sharded plan
        with a mesh the :class:`~repro.core.operator.RowTiledCooOperator`,
        each chip's rows in its own layout (DESIGN.md §20).  The layout is
        built here, on the device, and the stage report notes the path.
        """
        return self._operator_with_notes(state)[0]

    def _kernel_stage2(self, n: int) -> bool:
        """Whether Stage 2's products run the ``coo_spmv`` kernel: single-
        vector Lanczos on a TPU, n up to ``MAX_N``."""
        e = self.eig
        return (e.solver == "lanczos" and e.block_size == 1
                and kernel_applies(n))

    def _rows_over_chips(self, n: int) -> bool:
        """Whether Stage 2 runs row-sharded over the plan's mesh: the
        kernel's path under a sharded plan with a mesh."""
        return (self.plan.device == "sharded" and self.plan.mesh is not None
                and self._kernel_stage2(n))

    def _operator_with_notes(
            self, state: GraphState) -> Tuple[LinearOperator, Tuple[str, ...]]:
        """:meth:`operator` plus the representation-fallback trail — the
        BlockELL→COO degradation under a jit trace is a rung of the same
        recovery ladder the escalation controllers report, so the stage
        report records it instead of only a warning."""
        if isinstance(state.adj, ShardedCOO):
            return ShardedCooOperator(
                state.adj, variant=self.plan.variant, mesh=self.plan.mesh,
                axis=self.plan.axis, gather_dtype=self.plan.gather_dtype), ()
        if self.eig.representation == "blockell":
            from repro.core.operator import BlockEllOperator
            from repro.sparse.formats import coo_to_csr, csr_to_blockell

            try:
                # host-side conversion: raises on traced arrays — including
                # closure-constant states, whose indptr gets staged by the
                # device_put inside coo_to_csr
                return BlockEllOperator(
                    csr_to_blockell(coo_to_csr(state.adj))), ()
            except jax.errors.TracerArrayConversionError:
                import warnings

                warnings.warn(
                    "EigConfig.representation='blockell' needs concrete "
                    "graph arrays (csr_to_blockell is host-side); falling "
                    "back to the COO operator under this jit trace — build "
                    "the operator eagerly (pipe.operator(state)) and pass "
                    "operator= to embed()",
                    RuntimeWarning, stacklevel=3)
                return CooOperator(state.adj), ("blockell_to_coo_fallback",)
        adj = state.adj
        if self.plan.device == "single" and self._kernel_stage2(adj.shape[0]):
            # Lanczos's single-vector products run the coo_spmv kernel; its
            # layout is built here, once, outside the product
            with jax.named_scope("stage2"):
                op = TiledCooOperator.build(adj)
            return op, (f"coo_spmv[nnz={adj.nnz},slots={op.nnz}]",)
        if self._rows_over_chips(adj.shape[0]):
            # each chip builds the layout of its own rows, here, on the chips
            with jax.named_scope("stage2"):
                op = RowTiledCooOperator.build(adj, self.plan.mesh,
                                               self.plan.axis)
            return op, (_row_blocks_note(op),)
        return CooOperator(adj), ()

    # -- Stage 1 ------------------------------------------------------------

    def prepare(self, w: Union[COO, ShardedCOO]) -> GraphState:
        """Admit a prebuilt similarity graph as Stage-1 output (normalize +
        degree bookkeeping).  Accepts a COO or a row-partitioned ShardedCOO."""
        with jax.named_scope("stage1"):
            return self._normalize(w)

    def _normalize(self, w: Union[COO, ShardedCOO]) -> GraphState:
        if isinstance(w, ShardedCOO):
            ones = jnp.ones((w.shape[0],), jnp.float32)
            deg = spmv_gspmd(w, ones)  # degree pass (cheap, once)
            isd = jnp.where(deg > 0,
                            jax.lax.rsqrt(jnp.maximum(deg, 1e-30)), 0.0)
            return GraphState(adj=normalize_sharded(w, deg), deg=deg,
                              inv_sqrt_deg=isd)
        g = lap.normalized_graph(w)
        return GraphState(adj=g.adj_sym, deg=g.deg,
                          inv_sqrt_deg=g.inv_sqrt_deg)

    def build_graph(self, x: Array, *, points: Optional[Array] = None) -> GraphState:
        """Stage 1 from raw points: kNN search → similarity → normalized
        COO.  Under ``Plan(device="sharded")`` the neighbor search — O(n²d)
        exact or O(n·m·d) LSH-reranked, per ``graph.method`` — runs
        row-block-parallel over the mesh; assembly and normalization stay on
        the plain jit path (their cost is O(nk)).

        ``points`` optionally separates the neighbor-search coordinates from
        the similarity features (DTI: spatial kNN, profile cross-correlation)
        on both plans — the sharded path searches the row-block-sharded
        ``points`` and weighs edges from the gathered ``x`` features.
        """
        with jax.named_scope("stage1"):
            return self._build_graph(x, points)

    def _build_graph(self, x: Array, points: Optional[Array]) -> GraphState:
        g = self.graph
        if self.plan.device == "sharded":
            # the single-device branch delegates this check to build_knn_graph
            if points is not None and points.shape[0] != x.shape[0]:
                raise ValueError(
                    f"points rows ({points.shape[0]}) must match feature rows "
                    f"({x.shape[0]}) — one search point per feature row")
            if self.plan.mesh is None:
                raise ValueError(
                    "Plan(device='sharded') needs a mesh for the row-block "
                    "Stage 1 (build_graph)")
            from repro.core.distributed_pipeline import make_knn_rowblock

            p = x if points is None else points
            axis = self.plan.axis
            axis = axis if isinstance(axis, str) else axis[0]
            knn = make_knn_rowblock(
                self.plan.mesh, g.knn_k, axis=axis,
                block_q=g.block_q or 1024, impl=g.impl, interpret=g.interpret,
                method=g.method, n_tables=g.n_tables, n_bits=g.n_bits,
                candidates=g.candidates, lsh_seed=g.lsh_seed,
                exchange=self.plan.stage1_exchange)
            dist2, idx, tiles = knn(p, with_tiles=True)
            w = graph_from_knn(x, dist2, idx, measure=g.measure, sigma=g.sigma,
                               eps=g.eps, dist2_in_x_space=points is None)
            return self._normalize(w)._replace(knn_tiles=tiles)
        w, tiles = build_knn_graph(
            x, g.knn_k, points=points, measure=g.measure, sigma=g.sigma,
            eps=g.eps, method=g.method, n_tables=g.n_tables, n_bits=g.n_bits,
            candidates=g.candidates, lsh_seed=g.lsh_seed, impl=g.impl,
            block_q=g.block_q, block_k=g.block_k, interpret=g.interpret,
            with_tiles=True)
        return self._normalize(w)._replace(knn_tiles=tiles)

    # -- Stage 2 ------------------------------------------------------------

    def embed(self, state: GraphState, key: Array, *,
              operator: Optional[LinearOperator] = None,
              eig: Optional[EigConfig] = None) -> EmbedState:
        """Stage 2: the spectral embedding of the normalized adjacency — the
        top-k eigenpairs via thick-restart Lanczos (``eig.solver="lanczos"``)
        or the Chebyshev polynomial-filter sketch (``"chebyshev"``), mapped
        to the Ng-Jordan-Weiss rows.  ``operator`` overrides the plan-chosen
        operator (any :class:`LinearOperator` — e.g. a
        :class:`~repro.core.operator.BlockEllOperator`); ``eig`` overrides
        the Stage-2 config (the escalation controller's retry handle)."""
        n = state.adj.shape[0]
        op = self.operator(state) if operator is None else operator
        scfg = self._eig_config(n, eig)
        with jax.named_scope("stage2"):
            # deterministic, informative start: D^{1/2}·1 is exactly the
            # trivial eigenvector of A_sym — Lanczos deflates it in one step
            # (the chebyshev path seeds its sketch with it for the same
            # reason).
            v0 = jnp.sqrt(jnp.maximum(state.deg.astype(jnp.float32), 0.0)) \
                + 1e-3
            ecfg = eig if eig is not None else self.eig
            res = lz.eigsh(op, scfg, v0=v0, key=key)
            vecs = res.eigenvectors
            vals = res.eigenvalues
            if ecfg.drop_first:
                vecs = vecs[:, 1:]
                vals = vals[1:]
            # the single-vector solver keeps a row-sharded operator's rows
            rows = (isinstance(op, RowTiledCooOperator)
                    and ecfg.solver == "lanczos" and ecfg.block_size == 1)
            h = self._hand_over(lap.embed_rows(vecs, state.inv_sqrt_deg),
                                rows_sharded=rows)
            return EmbedState(
                embedding=h,
                eigenvalues=lap.smallest_laplacian_eigs_from_adj(vals),
                residuals=res.residuals,
                restarts=res.restarts,
                converged=res.converged,
                operator_applications=res.operator_applications,
            )

    def _hand_over(self, h: Array, *, rows_sharded: bool) -> Array:
        """The embedding as Stage 2 hands it to Stage 3.  Under the GSPMD
        plan, where Stage 3 is ``kmeans_sharded`` and Stage 2's products
        were not those of the row-sharded operator (``rows_sharded``),
        GSPMD ran Stage 2 replicated: the embedding is pinned replicated
        there, or the shard_map's row blocks propagate back into the
        Lanczos reductions and reorder their sums (DESIGN.md §10).  A
        row-sharded Stage 2 hands its row blocks over as they are."""
        if (self.plan.variant != "gspmd" or rows_sharded
                or not self._kmeans_sharded_dispatch(
                    *h.shape, self.kmeans.resolved(self.n_clusters))):
            return h
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            h, NamedSharding(self.plan.mesh, P()))

    # -- Stage 3 ------------------------------------------------------------

    def cluster(self, state: EmbedState, key: Array, *,
                n_clusters: Optional[int] = None,
                kmeans: Optional[KMeansConfig] = None) -> SpectralResult:
        """Stage 3: k-means over a (possibly cached) spectral embedding.

        ``n_clusters`` overrides the pipeline's k — re-clustering a cached
        embedding at a different granularity without re-entering the
        eigensolver (the serving scenario).  ``kmeans`` overrides the Stage-3
        config (the escalation controller's empty-cluster reseed retry).
        """
        base = kmeans if kmeans is not None else self.kmeans
        kcfg = base.resolved(n_clusters or self.n_clusters)
        with jax.named_scope("stage3"):
            res = self._run_kmeans(state.embedding, kcfg, key)
        return SpectralResult(
            labels=res.labels,
            embedding=state.embedding,
            eigenvalues=state.eigenvalues,
            eig_residuals=state.residuals,
            kmeans_inertia=res.inertia,
            lanczos_restarts=state.restarts,
            kmeans_iterations=res.iterations,
            operator_applications=state.operator_applications,
        )

    def _kmeans_sharded_dispatch(self, n: int, d: int,
                                 kcfg: KMeansConfig) -> bool:
        """True iff Stage 3 routes to the shard_map ``kmeans_sharded`` loop:
        under the shard_map plan, and under any sharded plan whose Stage 3
        runs a Mosaic kernel (GSPMD cannot partition one).  The reseed rung
        is available there too: ``empty="reseed_farthest"`` adds a second
        packed psum of per-shard farthest-point candidates (it only needs
        k rows per shard)."""
        plan = self.plan
        return (plan.device == "sharded" and kcfg.iter == "fused"
                and plan.mesh is not None
                and (plan.variant == "shard_map"
                     or km.runs_mosaic(n, d, kcfg)))

    def _run_kmeans(self, h: Array, kcfg: KMeansConfig, key: Array):
        # Plan dispatch: the shard_map plan, and a sharded plan whose Stage 3
        # runs Mosaic, get the explicit one-psum-per-iteration Lloyd loop
        # (fused iteration only — the two-pass modes stay on the GSPMD
        # formulation, where a Mosaic assign kernel cannot go).
        if self._kmeans_sharded_dispatch(*h.shape, kcfg):
            from repro.core.distributed_pipeline import kmeans_sharded

            return kmeans_sharded(h, kcfg, key, mesh=self.plan.mesh,
                                  axis=self.plan.axis)
        if (self.plan.device == "sharded" and self.plan.mesh is not None
                and km.runs_mosaic(h.shape[0], h.shape[1], kcfg)):
            raise ValueError(
                f"KMeansConfig(iter={kcfg.iter!r}) runs a Mosaic assign "
                "kernel, which GSPMD cannot partition over the sharded plan's "
                "mesh: use iter='fused' (kmeans_sharded) or assign='ref'")
        return km.kmeans(h, kcfg, key)

    # -- the stage DAG ------------------------------------------------------

    def _stage_prepare(self, st: PipelineState) -> PipelineState:
        t0 = time.perf_counter()
        if self.health.enabled:
            # eager input guards (no-ops on traced inputs): the degeneracies
            # that poison every downstream stage are cheapest to name here
            if st.input_graph is not None:
                health.check_graph(st.input_graph.val)
            elif st.points is not None:
                health.check_points(st.points, self.n_clusters)
        if st.input_graph is not None:
            g = self.prepare(st.input_graph)
        elif st.points is not None:
            g = self.build_graph(st.points, points=st.search_points)
        else:
            raise ValueError(
                "the prepare stage needs a PipelineState with points= or "
                "input_graph= set")
        notes: Tuple[str, ...] = ()
        eager = health.is_concrete(g.deg)
        if self.health.enabled and eager:
            # isolated vertices are handled (inv_sqrt_deg pins them to 0, so
            # they ride along as their own embedding rows) — note, not fault
            iso = int((np.asarray(g.deg) <= 0).sum())
            if iso:
                notes += (f"isolated_vertices[{iso}]",)
        rep = StageReport(
            "prepare", escalations=notes,
            wall_s=time.perf_counter() - t0 if eager else -1.0)
        return dataclasses.replace(
            st, graph=g, reports=st.reports + (rep,),
            provenance=st.provenance + ("prepare",))

    def _stage_sparsify(self, st: PipelineState) -> PipelineState:
        from repro.core import reduce as red

        if st.graph is None:
            raise ValueError("sparsify runs after prepare (no graph in state)")
        sharded = isinstance(st.graph.adj, ShardedCOO)
        w = _raw_weights(st.graph, host_compact=sharded)
        ws = red.sparsify_coo(w, self.sparsify)
        nnz_after = ws.nnz
        if sharded:
            # re-bucket onto the same mesh layout (host-side, like the
            # original partitioning) — shard count is preserved, so the
            # plan's collectives are unchanged
            ws = partition_coo_by_rows(ws, st.graph.adj.num_shards)
        g = self.prepare(ws)
        info = ReduceInfo(kind="sparsify", n_before=w.shape[0],
                          n_after=w.shape[0], nnz_before=w.nnz,
                          nnz_after=nnz_after)
        return dataclasses.replace(
            st, graph=g, reductions=st.reductions + (info,),
            provenance=st.provenance
            + (f"sparsify[nnz {info.nnz_before}→{info.nnz_after}]",))

    def _stage_coarsen(self, st: PipelineState) -> PipelineState:
        from repro.core import reduce as red

        if st.graph is None:
            raise ValueError("coarsen runs after prepare (no graph in state)")
        sharded = isinstance(st.graph.adj, ShardedCOO)
        w = _raw_weights(st.graph, host_compact=sharded)
        wc, prolong = red.coarsen_coo(w, self.coarsen)
        info = ReduceInfo(kind="coarsen", n_before=w.shape[0],
                          n_after=wc.shape[0], nnz_before=w.nnz,
                          nnz_after=wc.nnz)
        if sharded:
            wc = partition_coo_by_rows(wc, st.graph.adj.num_shards)
        g = self.prepare(wc)
        reduction = ReductionState(fine_graph=st.graph,
                                   prolong=jnp.asarray(prolong), info=info)
        return dataclasses.replace(
            st, graph=g, reduction=reduction,
            reductions=st.reductions + (info,),
            provenance=st.provenance
            + (f"coarsen[n {info.n_before}→{info.n_after}]",))

    def _embed_failure(self, emb: EmbedState,
                       ecfg: EigConfig) -> Optional[str]:
        """Classify a *concrete* Stage-2 output: ``None`` (healthy),
        ``"cheb_diverged"`` (polynomial filter left the bounds interval —
        Tremblay-style garbage subspace), ``"nonfinite"`` (NaN/Inf leaked
        into the embedding), or ``"unconverged"`` (residuals above tol)."""
        bad = int(health.nonfinite_count(emb.embedding)) \
            + int(health.nonfinite_count(emb.eigenvalues))
        if ecfg.solver == "chebyshev":
            from repro.core import chebyshev as cheb

            if bad or cheb.diverged(emb.eigenvalues):
                return "cheb_diverged"
        if bad:
            return "nonfinite"
        if not bool(np.asarray(emb.converged).all()):
            return "unconverged"
        return None

    def _escalate_embed(self, ecfg: EigConfig, failure: str,
                        n: int) -> Tuple[Optional[EigConfig], str]:
        """The next rung of the Stage-2 recovery ladder for this failure
        class, or ``(None, "")`` when no rung applies.

        chebyshev: a containment miss first widens the bounds margin
        (``HealthConfig.margin_widen``× — the filter diverges geometrically
        when an eigenvalue escapes the mapped interval, so a wider interval
        is the cheap fix), then falls back to the exact Lanczos solver.
        lanczos: ARPACK's remedy — widen the Krylov basis and double the
        restart budget (:func:`repro.core.lanczos.escalate_basis`).
        """
        hc = self.health
        if ecfg.solver == "chebyshev":
            if ecfg.cheb_margin < self.eig.cheb_margin * hc.margin_widen:
                new = dataclasses.replace(
                    ecfg, cheb_margin=ecfg.cheb_margin * hc.margin_widen)
                return new, f"cheb_margin_widen[{new.cheb_margin:g}]"
            return dataclasses.replace(ecfg, solver="lanczos"), \
                "fallback_lanczos"
        if failure in ("unconverged", "nonfinite"):
            lcfg = self._lanczos_config(n, ecfg)
            wid = lz.escalate_basis(lcfg, n, widen=hc.basis_widen)
            new = dataclasses.replace(
                ecfg, basis_m=wid.m, max_restarts=wid.max_restarts)
            return new, f"lanczos_widen[m={wid.m},restarts={wid.max_restarts}]"
        return None, ""

    def _stage_embed(self, st: PipelineState) -> PipelineState:
        if st.graph is None:
            raise ValueError("embed runs after prepare (no graph in state)")
        if st.key_embed is None:
            raise ValueError("embed needs PipelineState.key_embed")
        hc = self.health
        t0 = time.perf_counter()
        if st.operator_override is not None:
            op, notes = st.operator_override, ()
        else:
            op, notes = self._operator_with_notes(st.graph)
        # first attempt: the exact pre-guard computation with the exact
        # pre-guard key — the no-fault path stays bitwise-identical
        ecfg = self.eig
        emb = self.embed(st.graph, st.key_embed, operator=op, eig=ecfg)
        applied = emb.operator_applications
        attempts = 1
        rungs = list(notes)
        failure = None
        if hc.enabled and health.is_concrete(
                emb.embedding, emb.eigenvalues, emb.converged):
            # host-driven escalation: only possible on concrete outputs (a
            # widened basis changes static shapes; a traced converged flag
            # cannot steer this loop).  Jitted callers enforce post-hoc via
            # health.result_problems.
            failure = self._embed_failure(emb, ecfg)
            while failure and attempts < hc.max_attempts:
                ecfg, rung = self._escalate_embed(
                    ecfg, failure, st.graph.adj.shape[0])
                if ecfg is None:
                    break
                rungs.append(rung)
                key = jax.random.fold_in(st.key_embed, attempts)
                emb = self.embed(st.graph, key, operator=op, eig=ecfg)
                applied = applied + emb.operator_applications
                attempts += 1
                failure = self._embed_failure(emb, ecfg)
            emb = emb._replace(operator_applications=applied)
            if failure in ("nonfinite", "cheb_diverged"):
                raise PipelineError(
                    "embed",
                    f"spectral embedding is {failure.replace('_', ' ')} "
                    f"after {attempts} attempt(s)",
                    ladder=tuple(rungs),
                    remedy="check the similarity graph / operator for "
                           "degenerate values (health.check_graph), or raise "
                           "HealthConfig.max_attempts")
            if failure == "unconverged" and self.eig.strict:
                raise PipelineError(
                    "embed",
                    f"eigensolver unconverged after {attempts} attempt(s) "
                    f"(residual_max="
                    f"{float(np.max(np.asarray(emb.residuals))):.3e}, "
                    f"tol={self.eig.tol:g}) and EigConfig.strict is set",
                    ladder=tuple(rungs),
                    remedy="raise max_restarts/basis_m, loosen tol, or drop "
                           "strict to accept the degraded subspace")
        eager = health.is_concrete(emb.embedding)
        rep = StageReport(
            "embed", escalations=tuple(rungs), attempts=attempts,
            converged=jnp.asarray(emb.converged).all(),
            residual_max=jnp.max(jnp.asarray(emb.residuals, jnp.float32)),
            wall_s=time.perf_counter() - t0 if eager else -1.0)
        return dataclasses.replace(
            st, embedding=emb, reports=st.reports + (rep,),
            provenance=st.provenance + ("embed",))

    def _stage_refine(self, st: PipelineState) -> PipelineState:
        from repro.core import reduce as red

        if st.reduction is None or st.reduction.prolong is None:
            raise ValueError(
                "refine needs the coarsen stage's ReductionState (prolong "
                "map) in the PipelineState — stage order is prepare → "
                "coarsen → embed → refine → cluster")
        if st.embedding is None:
            raise ValueError("refine runs after embed (no embedding in state)")
        fine = st.reduction.fine_graph
        # lift through the partition prolongation, smooth on the *fine*
        # operator (GPIC-style), re-map to NJW rows with fine degrees
        u0 = st.embedding.embedding[st.reduction.prolong]
        op = self.operator(fine)
        u, theta, resid = red.lift_and_smooth(
            op, u0, steps=self.coarsen.refine_steps)
        emb = EmbedState(
            embedding=self._hand_over(lap.embed_rows(u, fine.inv_sqrt_deg),
                                      rows_sharded=False),
            eigenvalues=lap.smallest_laplacian_eigs_from_adj(theta),
            residuals=resid,
            restarts=st.embedding.restarts,
            converged=st.embedding.converged,
            operator_applications=st.embedding.operator_applications,
        )
        return dataclasses.replace(
            st, graph=fine, embedding=emb, reduction=None,
            provenance=st.provenance + ("refine",))

    def _stage_cluster(self, st: PipelineState) -> PipelineState:
        if st.embedding is None:
            raise ValueError("cluster runs after embed (no embedding in state)")
        if st.key_cluster is None:
            raise ValueError("cluster needs PipelineState.key_cluster")
        hc = self.health
        t0 = time.perf_counter()
        kcfg = self.kmeans.resolved(self.n_clusters)
        res = self.cluster(st.embedding, st.key_cluster)
        attempts = 1
        rungs: list = []
        eager = health.is_concrete(
            res.labels, res.kmeans_inertia, st.embedding.embedding)
        if hc.enabled and eager:
            if int(health.nonfinite_count(st.embedding.embedding)):
                raise PipelineError(
                    "cluster", "input embedding contains non-finite values",
                    remedy="run the embed stage with health enabled (its "
                           "ladder catches this) or sanitize the cached "
                           "embedding before re-clustering")
            empty = kcfg.k - int(np.unique(np.asarray(res.labels)).size)
            bad = not np.isfinite(np.asarray(res.kmeans_inertia)).all()
            # one reseed rung: dead centroids revive from the farthest
            # points.  Unavailable only when the config already reseeds
            # (the shard_map path reseeds too, via its second packed psum
            # of per-shard farthest candidates — needs k rows per shard).
            n_rows, width = st.embedding.embedding.shape
            can_reseed = kcfg.empty == "keep"
            if can_reseed and self._kmeans_sharded_dispatch(n_rows, width,
                                                             kcfg):
                import math as _math

                axes = (self.plan.axis,) if isinstance(self.plan.axis, str) \
                    else tuple(self.plan.axis)
                shards = _math.prod(self.plan.mesh.shape[a] for a in axes)
                can_reseed = -(-n_rows // shards) >= kcfg.k  # padded rows
            if (empty > 0 or bad) and attempts < hc.max_attempts \
                    and can_reseed:
                rungs.append(f"kmeans_reseed_farthest[empty={empty}]")
                retry = dataclasses.replace(
                    self.kmeans, empty="reseed_farthest")
                key = jax.random.fold_in(st.key_cluster, attempts)
                res = self.cluster(st.embedding, key, kmeans=retry)
                attempts += 1
                bad = not np.isfinite(np.asarray(res.kmeans_inertia)).all()
            if bad:
                raise PipelineError(
                    "cluster", "k-means inertia is non-finite",
                    ladder=tuple(rungs),
                    remedy="inspect the embedding scale — k-means over a "
                           "finite embedding cannot produce non-finite "
                           "inertia")
        # jit-safe liveness: all k clusters occupied (works traced or eager)
        counts = jnp.zeros((kcfg.k,), jnp.int32).at[res.labels].add(1)
        rep = StageReport(
            "cluster", escalations=tuple(rungs), attempts=attempts,
            converged=(counts > 0).sum() == kcfg.k,
            residual_max=jnp.asarray(res.kmeans_inertia, jnp.float32),
            wall_s=time.perf_counter() - t0 if eager else -1.0)
        reports = st.reports + (rep,)
        res = res._replace(reports=reports)
        return dataclasses.replace(
            st, result=res, reports=reports,
            provenance=st.provenance + ("cluster",))

    def run_stages(self, state: PipelineState, *,
                   checkpoint_dir: Optional[str] = None) -> PipelineState:
        """Execute the configured stage DAG over a :class:`PipelineState` —
        the spelled-out form of :meth:`run` (which builds the initial state,
        splits the keys, and returns ``state.result``).  Each stage is the
        ``_stage_<name>`` method; the tuple was validated at construction to
        be a canonical-order subsequence with the required stages present.

        Stages already recorded in ``state.provenance`` are skipped — that
        is the whole resume mechanism: a state restored from a checkpoint
        re-enters here and only the unfinished suffix runs.  With
        ``checkpoint_dir`` set, a :class:`PipelineError` first persists the
        completed-stage prefix (crash-consistent, via
        :mod:`repro.core.state_io`) and gains a ``checkpoint`` attribute
        naming the directory before propagating.
        """
        for name in self.stages:
            if _stage_done(name, state.provenance):
                continue
            try:
                state = getattr(self, f"_stage_{name}")(state)
            except PipelineError as e:
                if checkpoint_dir is not None:
                    from repro.core import state_io

                    e.checkpoint = state_io.save_state(
                        checkpoint_dir, state, self)
                    note = (f"completed-stage prefix saved to "
                            f"{checkpoint_dir!r} — fix the config and "
                            f"run(resume_from=...)")
                    e.remedy = (e.remedy + "; " if e.remedy else "") + note
                    e.args = (f"{e.args[0]}; {note}",) if e.args else (note,)
                raise
        return state

    # -- end to end ---------------------------------------------------------

    def run(self, data: Union[Array, COO, ShardedCOO, None] = None,
            key: Optional[Array] = None, *,
            points: Optional[Array] = None,
            operator: Optional[LinearOperator] = None,
            checkpoint_dir: Optional[str] = None,
            resume_from: Optional[str] = None) -> SpectralResult:
        """Points/graph in, labels out — the whole stage DAG under one call.

        ``data`` may be raw points ([n, d] array → Stage 1 runs), a COO
        similarity graph, or a row-partitioned ShardedCOO (pod operator).
        ``operator`` overrides the plan-chosen Stage-2 operator (forwarded
        to :meth:`embed` — the deprecation shims route their prebuilt
        operators through here).

        The key is split once, up front, in the same order as the pre-DAG
        pipeline — labels on the default stage tuple are bitwise-identical.

        ``checkpoint_dir`` arms crash recovery: a :class:`PipelineError`
        persists the completed-stage prefix there before propagating.
        ``resume_from`` loads such a prefix instead of taking ``data``/
        ``key`` (pass neither) — completed stages are skipped, the stored
        per-stage PRNG keys keep the remainder deterministic.
        """
        return self.run_state(data, key, points=points, operator=operator,
                              checkpoint_dir=checkpoint_dir,
                              resume_from=resume_from).result

    def run_state(self, data: Union[Array, COO, ShardedCOO, None] = None,
                  key: Optional[Array] = None, *,
                  points: Optional[Array] = None,
                  operator: Optional[LinearOperator] = None,
                  checkpoint_dir: Optional[str] = None,
                  resume_from: Optional[str] = None) -> PipelineState:
        """:meth:`run`, but returning the final :class:`PipelineState` —
        the serving export hook: the state carries everything
        :func:`repro.serve.oos.build_index` needs (points + result) plus
        the graph/embedding slots a later re-cluster or checkpoint wants."""
        if resume_from is not None:
            if data is not None or key is not None or points is not None:
                raise ValueError(
                    "run(resume_from=...) restores points/graph/keys from "
                    "the checkpoint — don't pass data/key/points alongside")
            from repro.core import state_io

            state, _ = state_io.load_state(resume_from, self)
            if operator is not None:
                state = dataclasses.replace(state,
                                            operator_override=operator)
            return self.run_stages(state, checkpoint_dir=checkpoint_dir)
        if data is None or key is None:
            raise ValueError("run needs (data, key) — or resume_from=")
        if isinstance(data, (COO, ShardedCOO)):
            if points is not None:
                raise ValueError(
                    "points= only applies to Stage 1 (raw-points input); a "
                    "prebuilt graph already fixed its neighbor structure")
            state = PipelineState(input_graph=data)
        else:
            state = PipelineState(points=data, search_points=points)
        if operator is not None and ("sparsify" in self.stages
                                     or "coarsen" in self.stages):
            raise ValueError(
                "operator= overrides the Stage-2 operator for the *input* "
                "graph, but a reduction stage replaces that graph — drop "
                "the override or the reduction stages")
        key, k_eig, k_km = jax.random.split(key, 3)
        state = dataclasses.replace(state, key_embed=k_eig,
                                    key_cluster=k_km,
                                    operator_override=operator)
        return self.run_stages(state, checkpoint_dir=checkpoint_dir)

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> dict:
        """JSON-safe nested dict (serve/dry-run reproducibility).  The plan's
        mesh is a runtime resource and is not serialized."""
        return {
            "n_clusters": self.n_clusters,
            "graph": self.graph.to_dict(),
            "eig": self.eig.to_dict(),
            "kmeans": dataclasses.asdict(self.kmeans),
            "plan": self.plan.to_dict(),
            "stages": list(self.stages),
            "sparsify": self.sparsify.to_dict(),
            "coarsen": self.coarsen.to_dict(),
            "health": self.health.to_dict(),
        }

    @classmethod
    def from_dict(cls, d: dict, *, mesh: Any = None) -> "SpectralPipeline":
        return cls(
            n_clusters=d["n_clusters"],
            graph=GraphConfig(**d.get("graph", {})),
            eig=EigConfig(**d.get("eig", {})),
            kmeans=KMeansConfig(**d.get("kmeans", {})),
            plan=Plan.from_dict(d.get("plan", {}), mesh=mesh),
            # pre-DAG config blobs carry no stage keys → the default tuple
            stages=tuple(d.get("stages", DEFAULT_STAGES)),
            sparsify=SparsifyConfig(**d.get("sparsify", {})),
            coarsen=CoarsenConfig(**d.get("coarsen", {})),
            health=HealthConfig(**d.get("health", {})),
        )
