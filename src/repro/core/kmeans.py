"""Stage 3 — parallel k-means with k-means++ seeding (paper Alg. 4-5).

TPU adaptation of the paper's GPU k-means:

* distance matrix via the BLAS trick ``S = ‖v‖² + ‖c‖² − 2 V Cᵀ`` (Eq. 12-16)
  — an MXU matmul, exactly the paper's cuBLAS mapping;
* **fused iteration** (beyond-paper, the default): one Lloyd iteration =
  assignment AND centroid accumulation from a single stream over the point
  matrix — :mod:`repro.kernels.kmeans_iter` (Pallas on TPU: online argmin +
  resident [k, d+1] accumulator; chunked ``lax.scan`` elsewhere).  Neither
  the n×k distance matrix nor the n×k one-hot ever reaches HBM; per
  iteration x is read once (the two-pass formulation reads it twice and
  round-trips the n×k one-hot — memory-bound exactly where the paper's
  large-k DTI runs live).  Traffic model in DESIGN.md §10;
* **two-pass mode** (``iter="two_pass"``): the paper-faithful split kept for
  comparison benchmarks — fused assign kernel
  (:mod:`repro.kernels.kmeans_assign`) or materialized reference, then a
  separate centroid update.  The paper sorts points by label (Thrust radix
  sort) and reduces runs; TPU sorts are expensive, so the update is either
  ``segment_sum`` (VPU scatter-add) or a one-hot matmul ``Hᵀ V`` (MXU) —
  selectable, benchmarked in benchmarks/bench_kmeans.py;
* k-means++ (Alg. 5) runs fully on device: the categorical draw
  ``P_j ∝ Dist_j²`` is a Gumbel-max over ``log Dist²`` — no host round trips.

All entry points are jit-safe and shard cleanly with points over the data
axis (centroids replicated).  Under GSPMD the fused iteration reduces to a
single [k, d+1] all-reduce per iteration; the explicit-collective variant
(one packed [k, d+2] psum carrying sums+counts+label-changes) lives in
:mod:`repro.core.distributed_pipeline`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels._util import KMEANS_BLOCK_K, KMEANS_BLOCK_Q

Array = jax.Array


class KMeansResult(NamedTuple):
    labels: Array  # [n] int32
    centroids: Array  # [k, d]
    inertia: Array  # [] sum of squared distances to assigned centroid
    iterations: Array  # []
    shifted: Array  # [] labels changed in last iteration (0 => converged)


@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    # ``k=None`` is allowed only as a pipeline-stage config: the
    # SpectralPipeline fills it from ``n_clusters`` at dispatch.  Standalone
    # ``kmeans``/``kmeans_sharded`` calls require an explicit k.
    k: Optional[int] = None
    max_iters: int = 100
    tol_changes: int = 0  # stop when <= this many labels change
    init: str = "kmeans++"  # "kmeans++" | "random"
    iter: str = "fused"  # "fused" (one-pass kmeans_iter) | "two_pass"
    update: str = "matmul"  # two-pass update: "matmul" (MXU) | "segment" (VPU)
    assign: str = "auto"  # two-pass assignment: "auto" | "ref" | "fused"
    empty: str = "keep"  # dead centroids: "keep" (paper) | "reseed_farthest"
    fixed_iters: Optional[int] = None  # static trip count (dry-run/bench)
    # kernel tile sizes — single source of truth in repro.kernels._util
    block_q: int = KMEANS_BLOCK_Q
    block_k: int = KMEANS_BLOCK_K
    interpret: Optional[bool] = None  # run Pallas bodies in interpret mode

    def __post_init__(self):
        # a typo'd engine name must not silently select the other engine
        if self.iter not in ("fused", "two_pass"):
            raise ValueError(f"KMeansConfig.iter must be 'fused' or "
                             f"'two_pass', got {self.iter!r}")
        if self.init not in ("kmeans++", "random"):
            raise ValueError(f"KMeansConfig.init must be 'kmeans++' or "
                             f"'random', got {self.init!r}")
        if self.update not in ("matmul", "segment"):
            raise ValueError(f"KMeansConfig.update must be 'matmul' (MXU "
                             f"one-hot) or 'segment' (VPU scatter-add), "
                             f"got {self.update!r}")
        if self.assign not in ("auto", "ref", "fused"):
            raise ValueError(f"KMeansConfig.assign must be one of 'auto', "
                             f"'ref', 'fused', got {self.assign!r}")
        if self.empty not in ("keep", "reseed_farthest"):
            raise ValueError(f"KMeansConfig.empty must be 'keep' (paper "
                             f"behavior: dead centroids stay) or "
                             f"'reseed_farthest', got {self.empty!r}")
        if self.k is not None and self.k < 1:
            raise ValueError(f"KMeansConfig.k must be >= 1, got {self.k}")

    def resolved(self, k: int) -> "KMeansConfig":
        """This config with ``k`` filled in (pipeline-stage dispatch)."""
        return self if self.k == k else dataclasses.replace(self, k=k)


# ---------------------------------------------------------------------------
# assignment step (two-pass mode)
# ---------------------------------------------------------------------------

def assign_ref(x: Array, c: Array, x_norm: Optional[Array] = None):
    """labels, min-dist² via the materialized distance matrix (paper Alg. 4)."""
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    xn = (xf * xf).sum(1) if x_norm is None else x_norm
    cn = (cf * cf).sum(1)
    s = xn[:, None] + cn[None, :] - 2.0 * (xf @ cf.T)  # Eq. 12/15/16
    labels = jnp.argmin(s, axis=1).astype(jnp.int32)
    dmin = jnp.maximum(jnp.min(s, axis=1), 0.0)
    return labels, dmin


def _assign(x, c, x_norm, cfg: KMeansConfig):
    """Two-pass assignment.  ``"auto"``/``"fused"`` call the fused kernel
    wrapper, whose own dispatch picks Pallas on TPU and the reference
    elsewhere; ``"ref"`` is the materialized reference.  Nothing falls back:
    a kernel the compiler refuses raises rather than running the reference
    unannounced."""
    if cfg.assign == "ref":
        return assign_ref(x, c, x_norm)
    from repro.kernels.kmeans_assign import ops

    return ops.kmeans_assign(x, c, x_norm=x_norm, block_q=cfg.block_q,
                             block_k=cfg.block_k, interpret=cfg.interpret)


# ---------------------------------------------------------------------------
# fused iteration (assign + accumulate in one data stream)
# ---------------------------------------------------------------------------

def lloyd_iter(x: Array, c: Array, x_norm: Optional[Array], cfg: KMeansConfig):
    """One Lloyd iteration's statistics ``(labels, dmin, sums, counts)``
    from a single pass over ``x`` — see :mod:`repro.kernels.kmeans_iter`.

    The wrapper picks its engine from the shapes before the call
    (:func:`repro.kernels.kmeans_iter.ops.kmeans_iter_engine` names it); the
    chunked online path is a peer implementation, not a degraded shim.
    """
    from repro.kernels.kmeans_iter.ops import kmeans_iter

    return kmeans_iter(x, c, x_norm=x_norm, block_q=cfg.block_q,
                       block_k=cfg.block_k, interpret=cfg.interpret)


def runs_mosaic(n: int, d: int, cfg: KMeansConfig) -> bool:
    """Whether Stage 3 on ``x: [n, d]`` compiles a Mosaic (TPU Pallas)
    kernel.  GSPMD cannot partition one: in a program over several devices
    it must sit inside a ``shard_map``."""
    if cfg.iter == "fused":
        from repro.kernels.kmeans_iter.ops import kmeans_iter_engine

        return kmeans_iter_engine(
            n, d, cfg.k, interpret=cfg.interpret, block_q=cfg.block_q,
            block_k=cfg.block_k) == "pallas"
    return (cfg.assign != "ref" and not cfg.interpret
            and jax.default_backend() == "tpu")


def centroids_from_sums(sums: Array, counts: Array, prev: Array) -> Array:
    """Means from accumulated (sums, counts); empty clusters keep their
    previous centroid (the paper's implementation implicitly does the same)."""
    safe = jnp.maximum(counts, 1.0)[:, None]
    c = sums / safe
    return jnp.where(counts[:, None] > 0, c, prev.astype(jnp.float32)).astype(prev.dtype)


def reseed_empty_farthest(c: Array, counts: Array, x: Array,
                          dmin: Array) -> Array:
    """Revive dead centroids from the points farthest from their assigned
    centroid (``KMeansConfig(empty="reseed_farthest")``).

    Jit-safe with static shapes: the ``k`` globally-farthest points are the
    donor pool (``lax.top_k`` over dmin), the i-th empty cluster takes the
    i-th donor (rank = cumsum over the empty mask), full clusters keep their
    mean.  A reseeded centroid captures at least its donor point next
    iteration, so Lloyd keeps iterating until no cluster is dead — the
    classic escape from the pinned-forever empty centroid.
    """
    k = c.shape[0]
    empty = counts <= 0
    _, donor_idx = jax.lax.top_k(dmin, k)  # k farthest points (desc)
    donors = x.astype(jnp.float32)[donor_idx]  # [k, d]
    rank = jnp.clip(jnp.cumsum(empty.astype(jnp.int32)) - 1, 0, k - 1)
    return jnp.where(empty[:, None], donors[rank],
                     c.astype(jnp.float32)).astype(c.dtype)


# ---------------------------------------------------------------------------
# update step (two-pass mode)
# ---------------------------------------------------------------------------

def update_centroids(x: Array, labels: Array, k: int, prev: Array, *, how: str = "matmul"):
    """New centroids = per-cluster means via a full second pass over ``x``
    (materializes the n×k one-hot under ``how="matmul"``)."""
    xf = x.astype(jnp.float32)
    if how == "matmul":
        h = jax.nn.one_hot(labels, k, dtype=jnp.float32)  # [n, k]
        sums = h.T @ xf  # MXU
        counts = h.sum(axis=0)
    else:
        sums = jax.ops.segment_sum(xf, labels, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones_like(labels, jnp.float32), labels, num_segments=k)
    return centroids_from_sums(sums, counts, prev)


# ---------------------------------------------------------------------------
# k-means++ (Alg. 5)
# ---------------------------------------------------------------------------

def row_at(x: Array, idx: Array) -> Array:
    """x[idx] for a row-sharded x, without gathering x: a one-hot
    contraction over the sharded axis (GSPMD: local dot + psum of d floats
    — the dynamic-gather formulation all-gathers the whole matrix, which
    dominated the spectral cells' collective roofline, see §Perf)."""
    onehot = (jnp.arange(x.shape[0]) == idx).astype(jnp.float32)
    return onehot @ x.astype(jnp.float32)


def kmeanspp_init(x: Array, k: int, key: Array) -> Array:
    """On-device k-means++ seeding.  O(nkd) — one fused pass per centroid."""
    n, d = x.shape
    xf = x.astype(jnp.float32)
    xn = (xf * xf).sum(1)

    key, sub = jax.random.split(key)
    i0 = jax.random.randint(sub, (), 0, n)
    c0 = row_at(xf, i0)

    def d2_to(c):
        return jnp.maximum(xn - 2.0 * (xf @ c) + (c * c).sum(), 0.0)

    dist2 = d2_to(c0)
    C = jnp.zeros((k, d), jnp.float32).at[0].set(c0)

    def body(i, carry):
        C, dist2, key = carry
        key, sub = jax.random.split(key)
        # Gumbel-max categorical draw with P_j ∝ dist2_j  (log 0 -> -inf ok)
        g = jax.random.gumbel(sub, (n,), jnp.float32)
        idx = jnp.argmax(jnp.log(jnp.maximum(dist2, 1e-30)) + g)
        c = row_at(xf, idx)
        C = C.at[i].set(c)
        dist2 = jnp.minimum(dist2, d2_to(c))
        return C, dist2, key

    C, _, _ = jax.lax.fori_loop(1, k, body, (C, dist2, key))
    return C.astype(x.dtype)


def random_init(x: Array, k: int, key: Array) -> Array:
    """k distinct random rows via ``row_at`` (batched): the dynamic gather
    ``x[idx]`` would all-gather the row-sharded point matrix under GSPMD."""
    idx = jax.random.choice(key, x.shape[0], (k,), replace=False)
    return jax.vmap(lambda i: row_at(x, i))(idx).astype(x.dtype)


def seed_centroids(x: Array, cfg: KMeansConfig, key: Array) -> Array:
    """Dispatch the configured seeding (shared with the sharded Lloyd loop),
    traced under the ``kmeans_seed`` scope."""
    with jax.named_scope("kmeans_seed"):
        if cfg.init == "kmeans++":
            return kmeanspp_init(x, cfg.k, key)
        return random_init(x, cfg.k, key)


# ---------------------------------------------------------------------------
# driver (Alg. 4)
# ---------------------------------------------------------------------------

def kmeans(x: Array, cfg: KMeansConfig, key: Array, *, init_centroids: Optional[Array] = None) -> KMeansResult:
    if cfg.k is None:
        raise ValueError("KMeansConfig.k is unset — standalone kmeans() needs "
                         "an explicit k (the SpectralPipeline fills it from "
                         "n_clusters; use cfg.resolved(k))")
    n, d = x.shape
    k = cfg.k
    xf32 = x.astype(jnp.float32)
    x_norm = (xf32 * xf32).sum(1)

    if init_centroids is not None:
        c0 = init_centroids
    else:
        c0 = seed_centroids(x, cfg, key)

    labels0 = jnp.full((n,), -1, jnp.int32)

    def one_iter(c, labels):
        if cfg.iter == "fused":
            new_labels, dmin, sums, counts = lloyd_iter(x, c, x_norm, cfg)
            new_c = centroids_from_sums(sums, counts, c)
        else:  # two_pass: re-stream x for the update
            new_labels, dmin = _assign(x, c, x_norm, cfg)
            new_c = update_centroids(x, new_labels, k, c, how=cfg.update)
            if cfg.empty == "reseed_farthest":
                counts = jax.ops.segment_sum(
                    jnp.ones_like(new_labels, jnp.float32), new_labels,
                    num_segments=k)
        if cfg.empty == "reseed_farthest":  # static branch: "keep" is
            new_c = reseed_empty_farthest(new_c, counts, x, dmin)  # untouched
        changed = (new_labels != labels).sum()
        return new_c, new_labels, dmin, changed

    if cfg.fixed_iters is not None:
        def fbody(_, st):
            c, labels, dmin, changed = st
            return one_iter(c, labels)

        c, labels, dmin, changed = jax.lax.fori_loop(
            0, cfg.fixed_iters, fbody, (c0, labels0, jnp.zeros((n,), jnp.float32), jnp.asarray(n))
        )
        iters = jnp.asarray(cfg.fixed_iters)
    else:
        def wcond(st):
            _, _, _, changed, it = st
            return jnp.logical_and(changed > cfg.tol_changes, it < cfg.max_iters)

        def wbody(st):
            c, labels, dmin, _, it = st
            c, labels, dmin, changed = one_iter(c, labels)
            return c, labels, dmin, changed, it + 1

        c, labels, dmin, changed, iters = jax.lax.while_loop(
            wcond, wbody, (c0, labels0, jnp.zeros((n,), jnp.float32), jnp.asarray(n), jnp.asarray(0))
        )

    return KMeansResult(
        labels=labels,
        centroids=c.astype(x.dtype),
        inertia=dmin.sum(),
        iterations=iters,
        shifted=changed,
    )
