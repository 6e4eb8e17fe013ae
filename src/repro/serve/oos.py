"""Out-of-sample extension — label unseen points without touching Stage 2.

The pipeline ends at labels-for-the-training-set; serving needs labels for
points that were never in the eigensolve.  The Nyström view: the spectral
embedding is (approximately) an eigenfunction of the kernel integral
operator, so an unseen point's embedding row is the kernel-weighted average
of its neighbors' rows,

    h(q) ≈ normalize( Σ_j w(q, x_j) · H[j]  /  Σ_j w(q, x_j) ),

with w the same exp(−‖q − x‖² / 2σ²) similarity Stage 1 uses and the final
row normalization the same NJW map :func:`repro.core.laplacian.embed_rows`
applies.  Compressive Spectral Clustering (Tremblay et al.) recovers
membership for *all* points from a small embedded sample exactly this way.
The label is then the nearest cached k-means centroid — O(knn_k·d + k·d)
per query, no eigensolver.

Neighbor search reuses the Stage-1 kernels against the cached training
points:

* ``method="exact"`` — :func:`repro.kernels.knn_topk.ops.knn_topk` with
  ``queries=`` and ``query_offset=n`` (query row ids sit past the pool, so
  the kernel's self-exclusion never fires on a pool point);
* ``method="lsh"`` — PERSISTENT tables: :func:`build_index` hashes the
  pool once and stores the per-table sorted (bucket code, tie-break
  projection) structure (:class:`repro.kernels.lsh_candidates.ops
  .LshTables`) on the :class:`ServingIndex`; at serve time only the query
  rows are hashed and positioned into the persisted tables by their
  lexicographic insertion rank (:func:`repro.kernels.lsh_candidates.ops
  .routed_candidates` — a jit-safe searchsorted), then the exact
  :func:`repro.kernels.knn_topk.ops.knn_topk_rerank` over the windows.
  Per-call hash work drops from O((n+q)·d·T·b) + a T·(n+q)·log(n+q) sort
  to O(q·d·T·b) + a T·(n+q)·log rank pass — ``BENCH_serving.json``
  records the per-label win.  An index restored without tables (an old
  snapshot) falls back to the legacy hash-[pool; queries]-together path
  (:func:`_lsh_neighbors_rehash`), kept as the bench counterfactual.

Everything here is jit-safe with static shapes: :func:`oos_labels` is the
ONE compiled function the batcher flushes into (the :class:`ServingIndex`
is a pytree *argument*, so a registry version swap reuses the compiled
executable — no retrace).  Per-row outputs depend only on that row's query
point, which is what makes the padded-batch contract (bitwise invariance
to pad rows) hold — asserted in tests/test_serving.py.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.kmeans as km
from repro.kernels.knn_topk.ops import knn_topk, knn_topk_rerank
from repro.kernels.lsh_candidates.ops import (
    DEFAULT_N_BITS,
    DEFAULT_N_TABLES,
    MAX_N_BITS,
    LshTables,
    default_candidates,
    hash_codes,
    lsh_candidates,
    make_planes,
    routed_candidates,
    sorted_tables,
)

Array = jax.Array

_METHODS = ("exact", "lsh")


@dataclasses.dataclass(frozen=True)
class OOSConfig:
    """Out-of-sample query knobs (hashable — static under jit).

    ``knn_k``/``sigma`` mirror the Stage-1 graph config: the interpolation
    weights should come from the same kernel the graph was built with, or
    the served embedding rows live on a different scale than the cached
    ones.  :meth:`from_graph_config` copies them from a pipeline's
    ``GraphConfig`` for exactly that reason.
    """

    knn_k: int = 10
    sigma: float = 1.0
    method: str = "exact"  # neighbor search: "exact" | "lsh"
    n_tables: int = DEFAULT_N_TABLES
    n_bits: int = DEFAULT_N_BITS
    candidates: Optional[int] = None  # LSH budget m; None → default_candidates
    lsh_seed: int = 0
    impl: str = "auto"  # knn_topk kernel dispatch: "auto" | "pallas" | "ref"
    block_q: Optional[int] = None
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.method not in _METHODS:
            raise ValueError(
                f"OOSConfig.method must be one of {_METHODS}, got "
                f"{self.method!r}")
        if self.knn_k < 1:
            raise ValueError(f"OOSConfig.knn_k must be >= 1, got {self.knn_k}")
        if self.sigma <= 0:
            raise ValueError(f"OOSConfig.sigma must be > 0, got {self.sigma}")
        if not 1 <= self.n_bits <= MAX_N_BITS:
            raise ValueError(
                f"OOSConfig.n_bits must be in [1, {MAX_N_BITS}], got "
                f"{self.n_bits}")

    @classmethod
    def from_graph_config(cls, g, **overrides) -> "OOSConfig":
        """The OOS config matching a pipeline ``GraphConfig`` — same kernel
        bandwidth, same neighbor count, same search method and LSH knobs."""
        base = dict(
            knn_k=g.knn_k, sigma=g.sigma, method=g.method,
            n_tables=g.n_tables, n_bits=g.n_bits, candidates=g.candidates,
            lsh_seed=g.lsh_seed, impl=g.impl, interpret=g.interpret)
        base.update(overrides)
        return cls(**base)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class ServingIndex:
    """Everything a query needs, as one pytree: the cached training points,
    their embedding rows, the k-means centroids (in embedding space), and
    the training labels (diagnostics + streaming-refresh seeding).

    Registered as a pytree with the config as static metadata, so the index
    passes through jit as an *argument* — swapping in a new version (same
    shapes) reuses the compiled serving function.
    """

    points: Array  # [n, d] training points (neighbor-search pool)
    embedding: Array  # [n, ke] NJW-normalized spectral embedding rows
    centroids: Array  # [kc, ke] k-means centroids in embedding space
    labels: Array  # [n] int32 training cluster assignment
    config: OOSConfig = OOSConfig()
    # persistent LSH structure (method="lsh" only): pool hashed ONCE at
    # build time; serve hashes queries only.  None ⇒ legacy rehash path.
    lsh_tables: Optional[LshTables] = None

    @property
    def n_points(self) -> int:
        return self.points.shape[0]

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]


jax.tree_util.register_dataclass(
    ServingIndex,
    ["points", "embedding", "centroids", "labels", "lsh_tables"], ["config"])


class OOSResult(NamedTuple):
    """Per-query serving output (all leading dims = n_queries)."""

    labels: Array  # [q] int32 nearest-centroid assignment
    dist2: Array  # [q] squared distance to the winning centroid
    embedding: Array  # [q, ke] interpolated + normalized embedding rows
    weight_sum: Array  # [q] Σ_j w(q, x_j) — 0 ⇒ query far from all neighbors
    neighbors: Array  # [q, knn_k] int32 pool ids used (−1 = invalid slot)


def build_index(points: Array, result, *, n_clusters: Optional[int] = None,
                config: OOSConfig = OOSConfig()) -> ServingIndex:
    """A :class:`ServingIndex` from a pipeline run: cache the points, the
    embedding, and the per-cluster embedding means.

    ``result`` is a :class:`~repro.core.spectral.SpectralResult` (or
    anything with ``.labels``/``.embedding``).  Centroids are recomputed as
    per-label means of the embedding — identical to the converged k-means
    centroids up to the final Lloyd update, and well-defined even for a
    result produced by a re-cluster at a different k.  ``n_clusters`` is
    static; when ``None`` it is inferred from the labels (eager input only).
    """
    labels = jnp.asarray(result.labels, jnp.int32)
    h = jnp.asarray(result.embedding, jnp.float32)
    if points.shape[0] != h.shape[0]:
        raise ValueError(
            f"points rows ({points.shape[0]}) must match embedding rows "
            f"({h.shape[0]}) — one cached point per embedded row")
    if n_clusters is None:
        try:
            n_clusters = int(np.asarray(labels).max()) + 1
        except jax.errors.TracerArrayConversionError as e:
            raise ValueError(
                "build_index needs a static n_clusters= under jit (labels "
                "are traced, so k cannot be inferred)") from e
    sums = jnp.zeros((n_clusters, h.shape[1]), jnp.float32).at[labels].add(h)
    counts = jnp.zeros((n_clusters,), jnp.float32).at[labels].add(1.0)
    centroids = km.centroids_from_sums(
        sums, counts, jnp.zeros_like(sums))
    pts = jnp.asarray(points, jnp.float32)
    tables = None
    if config.method == "lsh":
        # hash the pool ONCE here; every serve call then hashes only its
        # query rows and ranks them into this persisted sorted structure
        planes = make_planes(pts.shape[1], config.n_tables, config.n_bits,
                             config.lsh_seed)
        codes, ties = hash_codes(pts, planes, impl=config.impl,
                                 interpret=config.interpret)
        tables = sorted_tables(codes, ties)
    return ServingIndex(points=pts,
                        embedding=h, centroids=centroids, labels=labels,
                        config=config, lsh_tables=tables)


def _lsh_neighbors_rehash(index: ServingIndex, queries: Array):
    """Legacy LSH path (pre-persistent-tables): hash [pool; queries]
    together per call so the per-table (code, tie) sort positions the
    queries among the pool, take the window ids, drop other-query ids,
    rerank exactly.  Serves indices restored from old snapshots (no
    ``lsh_tables`` leaf) and is the counterfactual ``bench_serving.py``
    times the persistent path against."""
    cfg = index.config
    n = index.n_points
    q = queries.shape[0]
    m = cfg.candidates or default_candidates(cfg.knn_k, cfg.n_tables)
    both = jnp.concatenate(
        [index.points, queries.astype(index.points.dtype)], axis=0)
    qrows = n + jnp.arange(q, dtype=jnp.int32)
    cand = lsh_candidates(
        both, m=m, n_tables=cfg.n_tables, n_bits=cfg.n_bits,
        seed=cfg.lsh_seed, query_rows=qrows, impl=cfg.impl,
        interpret=cfg.interpret)
    cand = jnp.where(cand >= n, -1, cand)  # other queries are not the pool
    return knn_topk_rerank(index.points, cand, cfg.knn_k, queries=queries,
                           query_rows=qrows)


def _lsh_neighbors(index: ServingIndex, queries: Array):
    """LSH candidate windows for out-of-pool queries against the PERSISTED
    per-table sorted structure: hash only the query rows, position them by
    lexicographic insertion rank (``routed_candidates``'s jit-safe
    searchsorted), window, rerank exactly.  Same candidate-set contract as
    the rehash path (same tables, same window budget m // n_tables) — only
    the per-call hash/sort work changes."""
    cfg = index.config
    if index.lsh_tables is None:  # old snapshot without tables
        return _lsh_neighbors_rehash(index, queries)
    n = index.n_points
    q = queries.shape[0]
    m = cfg.candidates or default_candidates(cfg.knn_k, cfg.n_tables)
    win = min(max(m // cfg.n_tables, 1), n)
    planes = make_planes(queries.shape[1], cfg.n_tables, cfg.n_bits,
                         cfg.lsh_seed)
    qcodes, qties = hash_codes(queries.astype(jnp.float32), planes,
                               impl=cfg.impl, interpret=cfg.interpret)
    cand = routed_candidates(index.lsh_tables, qcodes, qties, win=win)
    qrows = n + jnp.arange(q, dtype=jnp.int32)  # never matches a pool id
    return knn_topk_rerank(index.points, cand, cfg.knn_k, queries=queries,
                           query_rows=qrows)


def oos_embed(index: ServingIndex, queries: Array):
    """Interpolated embedding rows for unseen points.

    Returns ``(h [q, ke], weight_sum [q], neighbors [q, knn_k])`` — the
    kernel-weighted average of the ``knn_k`` nearest cached rows, NJW row
    normalized.  A query with ``weight_sum == 0`` (all weights underflowed
    — it is far from every training point) gets the zero row; downstream
    the nearest-centroid assignment is still deterministic, and the serving
    health gate reports the coverage drop.
    """
    cfg = index.config
    qf = queries.astype(jnp.float32)
    with jax.named_scope("oos_knn"):
        if cfg.method == "lsh":
            dist2, idx = _lsh_neighbors(index, qf)
        else:
            dist2, idx = knn_topk(
                index.points, cfg.knn_k, queries=qf,
                query_offset=index.n_points, impl=cfg.impl,
                **({"block_q": cfg.block_q} if cfg.block_q else {}),
                interpret=cfg.interpret)
    with jax.named_scope("oos_interpolate"):
        valid = idx >= 0
        w = jnp.where(valid,
                      jnp.exp(-jnp.where(valid, dist2, 0.0)
                              / (2.0 * cfg.sigma ** 2)),
                      0.0)  # [q, k]
        rows = index.embedding[jnp.maximum(idx, 0)]  # [q, k, ke]
        num = jnp.einsum("qk,qke->qe", w, rows)
        wsum = w.sum(axis=1)
        # zero-coverage guard via where, NOT tiny-ε clamps: XLA fuses the
        # two divisions into num / (clamp(wsum)·clamp(norm)), and ε·ε
        # underflows to a flushed subnormal → 0/0 = NaN under jit.  where
        # keeps the divisor exactly 1 for uncovered rows (h stays the zero
        # row) while a genuinely NaN query still propagates (NaN > 0 is
        # False, but num is already NaN — the post-hoc serving gate relies
        # on that).
        h = num / jnp.where(wsum > 0, wsum, 1.0)[:, None]
        norm2 = jnp.sum(h * h, axis=1, keepdims=True)
        h = h / jnp.sqrt(jnp.where(norm2 > 0, norm2, 1.0))
    return h, wsum, idx


def oos_labels(index: ServingIndex, queries: Array) -> OOSResult:
    """Labels for unseen points — THE serving function (one jit, batched).

    Row-independent by construction: each output row is a function of that
    query row and the index alone, so a padded batch returns bitwise-
    identical rows for the real queries regardless of how many pad rows
    ride along (the batcher's contract).
    """
    h, wsum, idx = oos_embed(index, queries)
    with jax.named_scope("oos_assign"):
        labels, dmin = km.assign_ref(h, index.centroids)
    return OOSResult(labels=labels, dist2=dmin, embedding=h,
                     weight_sum=wsum, neighbors=idx)


# the ONE compiled serving entry point (index is a pytree argument: a
# version swap with unchanged shapes reuses the executable)
serve_fn = jax.jit(oos_labels)


def index_problems(index: ServingIndex) -> Tuple[str, ...]:
    """Structural problems that make an index unservable — the registry's
    default health gate (same shape as :func:`repro.core.health
    .result_problems`): empty string tuple ⇔ healthy."""
    import repro.core.health as health

    problems = []
    n = index.points.shape[0]
    if n == 0:
        problems.append("index_empty[n=0]")
    if index.embedding.shape[0] != n or index.labels.shape[0] != n:
        problems.append(
            f"index_shape_mismatch[points={n},embedding="
            f"{index.embedding.shape[0]},labels={index.labels.shape[0]}]")
    if index.centroids.shape[1] != index.embedding.shape[1]:
        problems.append(
            f"centroid_width_mismatch[centroids={index.centroids.shape[1]},"
            f"embedding={index.embedding.shape[1]}]")
    for name, arr in (("points", index.points),
                      ("embedding", index.embedding),
                      ("centroids", index.centroids)):
        bad = int(health.nonfinite_count(arr))
        if bad:
            problems.append(f"nonfinite_{name}[{bad}]")
    return tuple(problems)
