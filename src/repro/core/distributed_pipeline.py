"""Pod-scale sharded building blocks + deprecated ``_sharded`` entry shims.

What lives here now:

* :func:`make_knn_rowblock` — row-block-sharded Stage-1 neighbor search;
* :func:`kmeans_sharded` — explicit-collective Stage 3 (one packed psum per
  Lloyd iteration);
* deprecated shims :func:`spectral_cluster_sharded` /
  :func:`spectral_cluster_from_points_sharded`, now thin wrappers that build
  a ``Plan(device="sharded", ...)`` and dispatch through
  :class:`repro.core.spectral.SpectralPipeline` — the parallel ``_sharded``
  code paths collapsed into plan dispatch.

The sharded *operator* itself (gspmd / shard_map SpMV+SpMM engines behind
one protocol) is :class:`repro.core.operator.ShardedCooOperator`; the
normalization helper moved to :func:`repro.sparse.distributed.normalize_sharded`
(re-exported here for compatibility).

Stage-2 solver dispatch is representation-agnostic: because both engines in
:func:`repro.core.lanczos.eigsh` (thick-restart Lanczos and the Chebyshev
polynomial filter, ``EigConfig(solver="chebyshev")``) drive the operator only
through ``op.mm``, the sharded plan runs *distributed filtering* for free —
every Chebyshev recurrence step is the existing one-all-gather-per-application
SpMM, and the filter adds zero new collectives (no per-step orthogonalization,
no global QR inside the iteration; the single trailing QR + Rayleigh-Ritz on
the [n, R] filtered block happens once, outside the recurrence).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import repro.core.kmeans as km
from repro.compat import SHARD_MAP_NO_CHECK, shard_map as _shard_map
from repro.core.pipeline import SpectralClusteringConfig
from repro.core.spectral import GraphConfig, Plan, SpectralResult
from repro.kernels.knn_topk.ops import knn_topk, knn_topk_engine, knn_topk_rerank
from repro.kernels.lsh_candidates.ops import (
    DEFAULT_N_BITS,
    DEFAULT_N_TABLES,
    default_candidates,
    hash_codes,
    lsh_candidates,
    make_planes,
    routed_candidates,
    sorted_tables,
)
from repro.sparse.distributed import (  # noqa: F401  (normalize_sharded re-export)
    ShardedCOO,
    auto_mesh,
    axis_tuple,
    normalize_sharded,
    num_shards,
    padded_rows,
    ring_shift,
)

Array = jax.Array

_EXCHANGES = ("gather", "ring")


def merge_topk(best_d: Array, best_i: Array, new_d: Array, new_i: Array,
               k: int):
    """Online per-row top-k merge for the ring exchange: keep the k smallest
    (dist², global id) pairs of the running best and a new block's results.

    Selection is LEXICOGRAPHIC on (dist, id) — ties resolve to the smallest
    global id, which is exactly how a full-pool ``knn_topk`` resolves them
    (``lax.top_k`` picks the first occurrence, and the pool is in global-id
    order) — so the streamed merge is bitwise-faithful to the gathered
    computation, not just value-equal.  Invalid slots travel as (+inf, −1)
    and sort to the tail; ids are re-canonicalized to −1 afterwards.
    """
    cd = jnp.concatenate([best_d, new_d], axis=1)
    ci = jnp.concatenate([best_i, new_i], axis=1)
    p1 = jnp.argsort(ci, axis=1)
    cd = jnp.take_along_axis(cd, p1, axis=1)
    ci = jnp.take_along_axis(ci, p1, axis=1)
    p2 = jnp.argsort(cd, axis=1, stable=True)
    cd = jnp.take_along_axis(cd, p2, axis=1)[:, :k]
    ci = jnp.take_along_axis(ci, p2, axis=1)[:, :k]
    return cd, jnp.where(jnp.isinf(cd), -1, ci)


def make_knn_rowblock(mesh, k: int, *, axis: str = "data", block_q: int = 1024,
                      impl: str = "auto", interpret: Optional[bool] = None,
                      method: str = "exact", n_tables: int = DEFAULT_N_TABLES,
                      n_bits: int = DEFAULT_N_BITS,
                      candidates: Optional[int] = None, lsh_seed: int = 0,
                      exchange: str = "gather"):
    """Row-block-sharded Stage-1 neighbor search (the kNN analogue of
    :func:`repro.sparse.distributed.make_sharded_spmv`'s layout).

    Two exchange disciplines (``Plan.stage1_exchange`` selects):

    ``exchange="gather"`` (default) — each shard all-gathers the full point
    set once (the same one-collective-per-pass discipline as the SpMV; the
    analogue of the paper keeping the data matrix GPU-resident) and computes
    its rows' kNN against it.  Self-pairs are excluded via the shard's
    global row offset (``axis_index · rows_local``) threaded into the
    kernel's self-exclusion mask.  ``method="lsh"`` hashes the full gathered
    pool on EVERY shard (identical tables from the static ``lsh_seed`` —
    redundant O(n·d·T·b) compute) and windows/reranks only its own rows.
    Per-shard receive traffic: (S−1)/S · n·d floats into a full-pool
    buffer — the >1-host wall.

    ``exchange="ring"`` — no shard ever materializes the full pool.  Exact
    mode streams peer row blocks around the ring (S−1 ``ppermute`` steps),
    runs the existing ``knn_topk`` kernel block-vs-block at each step, and
    maintains an online per-row top-k via :func:`merge_topk`; the
    lexicographic (dist, id) merge makes the result bitwise-equal to the
    gathered computation.  LSH mode hashes ONLY the local block (ending the
    every-shard-hashes-everything scheme), builds its per-table sorted
    bucket structure once (:func:`~repro.kernels.lsh_candidates.ops
    .sorted_tables`), and streams (block, tables) around the ring: at each
    step a shard routes its queries by bucket code into the visiting
    tables (:func:`~repro.kernels.lsh_candidates.ops.routed_candidates`
    — per-table windows of ⌈m/(T·S)⌉ around the lexicographic insertion
    rank), reranks against the visiting block with ``knn_topk_rerank``,
    and merges.  Per-step traffic: n·d/S point floats + 3·T·n/S table
    words; peak footprint O(n/S + T·n/S) — per-shard communication is
    O(n·d/S + candidate traffic) per step and independent of host count
    at fixed per-shard rows.

    Returns ``knn(x) -> (dist² [n, k], idx [n, k])`` with rows sharded over
    ``axis``; outputs feed :func:`repro.core.similarity.graph_from_knn`.
    ``knn(x, with_tiles=True)`` appends ``knn_topk``'s int32 [2] count of
    candidate tiles merged and visited, summed over ring steps and chips
    (``None`` on the jnp reference and the LSH path).  Any
    n works: rows are padded to equal blocks (as
    :func:`~repro.sparse.distributed.partition_coo_by_rows` pads graphs)
    with a point farther from every real point than any two real points are
    from each other, so pads never enter a real row's top-k (they could only
    when n − 1 < k, and are masked to (+inf, −1) then); pad rows are dropped.
    """
    if method not in ("exact", "lsh"):
        raise ValueError(
            f"make_knn_rowblock method must be 'exact'|'lsh', got {method!r}")
    if exchange not in _EXCHANGES:
        raise ValueError(
            f"make_knn_rowblock exchange must be one of {_EXCHANGES}, got "
            f"{exchange!r}")
    mesh = auto_mesh(mesh)
    m = default_candidates(k, n_tables) if candidates is None else candidates
    n_shards = num_shards(mesh, axis)
    # the kernel counts its tiles; each chip's count leaves as a row of [S, 2]
    counted = method == "exact" and knn_topk_engine(impl, interpret) != "ref"

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(axis, None),),
        out_specs=(P(axis, None),) * (3 if counted else 2),
        # jax 0.4.x has no replication rule for pallas_call; outputs are all
        # explicitly sharded over `axis`, so the check adds nothing here.
        **SHARD_MAP_NO_CHECK,
    )
    def search(x_blk):
        if exchange == "ring":
            return _knn_ring(x_blk)
        x_full = jax.lax.all_gather(x_blk, axis, axis=0, tiled=True)
        offset = jax.lax.axis_index(axis) * x_blk.shape[0]
        if method == "lsh":
            qrows = offset + jnp.arange(x_blk.shape[0], dtype=jnp.int32)
            cand = lsh_candidates(x_full, m=m, n_tables=n_tables,
                                  n_bits=n_bits, seed=lsh_seed,
                                  query_rows=qrows, impl=impl,
                                  interpret=interpret)
            return knn_topk_rerank(x_full, cand, k, queries=x_blk,
                                   query_rows=qrows, block_q=block_q)
        out = knn_topk(x_full, k, queries=x_blk, query_offset=offset,
                       block_q=block_q, impl=impl, interpret=interpret,
                       with_tiles=True)
        return out[:2] + (out[2][None],) if counted else out[:2]

    def _knn_ring(x_blk):
        nl = x_blk.shape[0]
        S = n_shards
        my = jax.lax.axis_index(axis)
        best_d = jnp.full((nl, k), jnp.inf, jnp.float32)
        best_i = jnp.full((nl, k), -1, jnp.int32)
        tiles = jnp.zeros((2,), jnp.int32)
        arange_l = jnp.arange(nl, dtype=jnp.int32)
        if method == "lsh":
            # hash ONCE, at home: codes/ties for the local block + the
            # per-table sorted structure that travels with it
            planes = make_planes(x_blk.shape[1], n_tables, n_bits, lsh_seed)
            qcodes, qties = hash_codes(x_blk, planes, impl=impl,
                                       interpret=interpret)
            tables = sorted_tables(qcodes, qties)
            # the full-pool window m/T, spread over the S visiting blocks
            win_full = min(max(m // n_tables, 1), S * nl)
            win_step = max(-(-win_full // S), 1)
            payload = (x_blk, tables)
        else:
            payload = x_blk
        for t in range(S):
            # owner of the block visiting at step t (ring rotates forward)
            src = jax.lax.rem(my - t + S, S)
            # query ids in the VISITING block's local coordinates: equal to
            # arange(nl) only at home (t=0), outside [0, nl) otherwise — so
            # the kernels' self-exclusion fires exactly at the home step
            qrows_vis = (my - src) * nl + arange_l
            if method == "lsh":
                blk, tbl = payload
                cand = routed_candidates(tbl, qcodes, qties, win=win_step,
                                         query_rows=qrows_vis)
                d_t, i_t = knn_topk_rerank(blk, cand, k, queries=x_blk,
                                           query_rows=qrows_vis,
                                           block_q=block_q)
            else:
                blk = payload
                d_t, i_t, t_t = knn_topk(blk, k, queries=x_blk,
                                         query_offset=(my - src) * nl,
                                         block_q=block_q, impl=impl,
                                         interpret=interpret, with_tiles=True)
                if counted:
                    tiles = tiles + t_t
            i_g = jnp.where(i_t >= 0, i_t + src * nl, -1)
            best_d, best_i = merge_topk(best_d, best_i,
                                        d_t.astype(jnp.float32), i_g, k)
            if t < S - 1:
                payload = ring_shift(payload, axis, S)
        return (best_d, best_i, tiles[None]) if counted else (best_d, best_i)

    def knn(x, with_tiles: bool = False):
        n = x.shape[0]
        n_pad = padded_rows(n, n_shards)
        if n_pad > n:
            # |coordinates| <= M puts real pairs within d·(2M)² and pads at
            # least d·(3M+1)² from every real point
            far = 4.0 * jnp.max(jnp.abs(x.astype(jnp.float32))) + 1.0
            x = jnp.concatenate(
                [x, jnp.full((n_pad - n, x.shape[1]), far, x.dtype)])
        out = search(x)
        dist2, idx = out[:2]
        if n_pad > n:
            pad_hit = idx[:n] >= n
            dist2 = jnp.where(pad_hit, jnp.inf, dist2[:n])
            idx = jnp.where(pad_hit, -1, idx[:n])
        if not with_tiles:
            return dist2, idx
        return dist2, idx, out[2].sum(0) if counted else None

    return knn


def spectral_cluster_from_points_sharded(
    x: Array,
    cfg: SpectralClusteringConfig,
    key: Array,
    *,
    mesh,
    knn_k: int = 10,
    axis: str = "data",
    measure: str = "exp_decay",
    sigma: float = 1.0,
    knn_eps: Array | float | None = None,
) -> SpectralResult:
    """Deprecated: ``SpectralPipeline(..., plan=Plan(device="sharded",
    mesh=mesh)).run(x, key)``.

    The O(n²d) neighbor search — the dominant Stage-1 cost — runs shard_map
    row-parallel over ``axis``; graph assembly and Stages 2-3 are the plain
    jit pipeline, whose collectives GSPMD derives from the sharded operands.
    ``x.shape[0]`` must divide evenly by the mesh axis size.
    """
    import warnings

    warnings.warn(
        "spectral_cluster_from_points_sharded is deprecated; use "
        "SpectralPipeline with Plan(device='sharded', mesh=...) "
        "(repro.core.spectral)", DeprecationWarning, stacklevel=2)
    pipe = cfg.to_pipeline(
        graph=GraphConfig(knn_k=knn_k, measure=measure, sigma=sigma,
                          eps=knn_eps),
        plan=Plan(device="sharded", mesh=mesh, axis=axis),
    )
    return pipe.run(x, key)


def kmeans_sharded(
    x: Array,
    cfg: km.KMeansConfig,
    key: Array,
    *,
    mesh,
    axis="data",
    init_centroids: Optional[Array] = None,
) -> km.KMeansResult:
    """Explicit-collective Stage 3: row-sharded Lloyd iterations with ONE
    all-reduce per iteration.

    Each shard runs the fused one-pass iteration
    (:func:`repro.core.kmeans.lloyd_iter`) on its row block, packs its
    partial statistics into a single ``[k, d+2]`` block —
    ``[Σx | counts | label-changes]`` per cluster — and psums that once;
    centroids, the convergence test, and the empty-cluster policy are then
    computed redundantly-replicated per shard.  This replaces the GSPMD
    formulation, whose one-hot GEMM update replicates the n×k one-hot
    contraction and leaves the collective schedule to the partitioner.
    The final-inertia psum happens once, outside the loop.

    ``KMeansConfig(empty="reseed_farthest")`` adds a SECOND packed psum per
    iteration, only under that config: each shard contributes its k locally
    farthest points as ``[row | dmin]`` candidates written into a disjoint
    slice of a zero ``[S·k, d+1]`` buffer (``dynamic_update_slice`` at
    ``shard_index·k``), the psum overlays the slices, and a global
    ``top_k`` over the S·k candidate distances selects the donors — every
    point in the global top-k is in its own shard's top-k, so the
    candidate set is exact and the reseed matches the single-device
    :func:`repro.core.kmeans.reseed_empty_farthest` bitwise on tie-free
    data (the parity test in tests/test_distributed.py pins it).  Needs
    ``n // S >= k`` rows per shard so each shard can fill its slice.

    Any n works: rows are padded with zeros to equal blocks (as
    :func:`~repro.sparse.distributed.partition_coo_by_rows` pads graphs).  A
    zero row adds nothing to Σx; its count, label change and distance are
    masked out of the packed statistics, and pad labels are dropped.
    Seeding runs on the unpadded global (GSPMD-sharded) array — ``row_at``'s
    one-hot contractions already shard cleanly.
    """
    if cfg.iter != "fused":
        raise ValueError(
            "kmeans_sharded runs the fused one-pass engine only (the "
            "two-pass modes stay on the GSPMD formulation via km.kmeans); "
            f"got KMeansConfig.iter={cfg.iter!r}")
    if cfg.k is None:
        raise ValueError("KMeansConfig.k is unset — standalone kmeans_sharded "
                         "needs an explicit k (use cfg.resolved(k))")
    mesh = auto_mesh(mesh)
    axes = axis_tuple(axis)
    n, d = x.shape
    k = cfg.k
    n_shards = num_shards(mesh, axes)
    n_pad = padded_rows(n, n_shards)
    if cfg.empty == "reseed_farthest" and n_pad // n_shards < k:
        raise ValueError(
            f"KMeansConfig(empty='reseed_farthest') under kmeans_sharded "
            f"needs at least k rows per shard (each shard contributes k "
            f"farthest-point candidates): n//S = {n_pad // n_shards} < k = {k}")
    c0 = km.seed_centroids(x, cfg, key) if init_centroids is None else init_centroids
    if n_pad != n:
        x = jnp.concatenate([x, jnp.zeros((n_pad - n, d), x.dtype)])

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=(P(axes), P(None, None), P(), P(), P()),
        **SHARD_MAP_NO_CHECK,
    )
    def run(x_blk, c0):
        xf = x_blk.astype(jnp.float32)
        x_norm = (xf * xf).sum(1)
        labels0 = jnp.full((x_blk.shape[0],), -1, jnp.int32)

        def shard_index():
            # linearized index over the (possibly multi-)axis tuple,
            # row-major like the row partitioning itself
            idx = jnp.zeros((), jnp.int32)
            for a in axes:
                idx = idx * num_shards(mesh, (a,)) + jax.lax.axis_index(a)
            return idx

        def global_farthest(dmin):
            # the reseed donor pool: psum #2 overlays each shard's k
            # locally-farthest [row | dmin] candidates into its own slice
            # of a zero [S·k, d+1] buffer, then a replicated top_k picks
            # the global k — exact, since a globally-farthest point is
            # locally farthest on its shard
            vals, idx = jax.lax.top_k(dmin, k)
            cand = jnp.concatenate([xf[idx], vals[:, None]], axis=1)
            buf = jnp.zeros((n_shards * k, d + 1), jnp.float32)
            buf = jax.lax.dynamic_update_slice(
                buf, cand, (shard_index() * k, jnp.zeros((), jnp.int32)))
            buf = jax.lax.psum(buf, axes)  # reseed-only second collective
            _, sel = jax.lax.top_k(buf[:, d], k)
            return buf[sel, :d]  # [k, d] donors, farthest first

        nl = x_blk.shape[0]
        real = (shard_index() * nl + jnp.arange(nl) < n) if n_pad != n else None

        def one_iter(c, labels):
            new_labels, dmin, sums, counts = km.lloyd_iter(x_blk, c, x_norm, cfg)
            change = (new_labels != labels).astype(jnp.float32)
            if real is not None:  # zero pad rows: uncount, unchange, no donor
                counts = counts - jax.ops.segment_sum(
                    (~real).astype(counts.dtype), new_labels, num_segments=k)
                change = jnp.where(real, change, 0.0)
                dmin = jnp.where(real, dmin, -1.0)
            changed_pc = jax.ops.segment_sum(change, new_labels,
                                             num_segments=k)
            packed = jnp.concatenate(
                [sums, counts[:, None], changed_pc[:, None]], axis=1)
            packed = jax.lax.psum(packed, axes)  # the iteration's one collective
            new_c = km.centroids_from_sums(packed[:, :d], packed[:, d], c)
            if cfg.empty == "reseed_farthest":  # static branch, like km.kmeans
                counts_g = packed[:, d]
                empty = counts_g <= 0
                donors = global_farthest(dmin)
                rank = jnp.clip(jnp.cumsum(empty.astype(jnp.int32)) - 1,
                                0, k - 1)
                new_c = jnp.where(empty[:, None], donors[rank],
                                  new_c.astype(jnp.float32)).astype(new_c.dtype)
            return new_c, new_labels, dmin, packed[:, d + 1].sum()

        if cfg.fixed_iters is not None:
            def fbody(_, st):
                c, labels, dmin, changed = st
                return one_iter(c, labels)

            c, labels, dmin, changed = jax.lax.fori_loop(
                0, cfg.fixed_iters, fbody,
                (c0, labels0, jnp.zeros_like(x_norm), jnp.asarray(float(n))))
            iters = jnp.asarray(cfg.fixed_iters)
        else:
            def wcond(st):
                _, _, _, changed, it = st
                return jnp.logical_and(changed > cfg.tol_changes,
                                       it < cfg.max_iters)

            def wbody(st):
                c, labels, dmin, _, it = st
                c, labels, dmin, changed = one_iter(c, labels)
                return c, labels, dmin, changed, it + 1

            c, labels, dmin, changed, iters = jax.lax.while_loop(
                wcond, wbody,
                (c0, labels0, jnp.zeros_like(x_norm), jnp.asarray(float(n)),
                 jnp.asarray(0)))

        if real is not None:
            dmin = jnp.where(real, dmin, 0.0)
        inertia = jax.lax.psum(dmin.sum(), axes)  # once, outside the loop
        return labels, c, inertia, iters, changed

    labels, c, inertia, iters, changed = run(x, c0)
    return km.KMeansResult(
        labels=labels[:n],
        centroids=c.astype(x.dtype),
        inertia=inertia,
        iterations=iters,
        shifted=changed,
    )


def spectral_cluster_sharded(
    sm: ShardedCOO,
    cfg: SpectralClusteringConfig,
    key: Array,
    *,
    variant: str = "gspmd",
    mesh=None,
    axis="data",
    gather_dtype=None,
) -> SpectralResult:
    """Deprecated: ``cfg.to_pipeline(plan=Plan(device="sharded", mesh=mesh,
    variant=variant, ...)).run(sm, key)``.

    Stage 2 runs over the row-partitioned edges with the
    :class:`~repro.core.operator.ShardedCooOperator` engine selected by
    ``variant`` ("gspmd" baseline | "shard_map" explicit collectives); the
    shard_map plan also gets the one-psum-per-iteration Stage 3.

    Behavior note: ``cfg.drop_first=True`` now works here — the pre-PR-4
    implementation silently ignored it on the sharded path; the unified
    pipeline applies the same trivial-eigenvector bookkeeping as the
    single-device path (an intentional fix, not a regression).  All other
    configs are bitwise-identical to the old implementation.
    """
    import warnings

    warnings.warn(
        "spectral_cluster_sharded is deprecated; use SpectralPipeline with "
        "Plan(device='sharded', variant=..., mesh=...) (repro.core.spectral)",
        DeprecationWarning, stacklevel=2)
    plan = Plan(device="sharded", mesh=mesh, axis=axis, variant=variant,
                gather_dtype=gather_dtype)
    return cfg.to_pipeline(plan=plan).run(sm, key)
