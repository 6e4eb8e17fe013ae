"""Stage 1 — sparse similarity-graph construction (paper Alg. 1).

Given data points ``X ∈ R^{n×d}`` and a neighborhood edge list
``E ∈ N^{nnz×2}`` (the paper's ε-distance pairs, e.g. voxels within 4 mm),
compute the per-edge similarity and emit a COO graph.  The paper maps one
CUDA thread per edge; on TPU the same computation is a batched gather +
row-wise contraction that the VPU vectorizes — we additionally chunk it with
``jax.lax.map`` so the nnz×d gather working set stays HBM-friendly.

The paper assumes E is given; a real framework has to build it.  Two
builders coexist:

* :func:`build_knn_graph` — device-resident (jit-safe) construction: the
  fused ``kernels/knn_topk`` neighbor search → edge similarity →
  symmetrization → row-sorted COO, all on device with static shapes
  (nnz = 2·n·k duplicate-coordinate layout).  This is the Stage-1 path the
  paper's Table III speedup is about (DESIGN.md §9).
* :func:`eps_neighbors` / :func:`knn_edges` — host-side numpy fallbacks
  (blocked brute force) used by the data pipeline and the NequIP/Equiformer
  radius graphs, and as the oracle the device path is tested against.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.knn_topk.ops import knn_topk, knn_topk_rerank
from repro.kernels.lsh_candidates.ops import (
    DEFAULT_N_BITS,
    DEFAULT_N_TABLES,
    default_candidates,
    lsh_candidates,
)
from repro.sparse.formats import COO, coo_from_edges
from repro.sparse.ops import sort_coo_rows, symmetrize_coo

Array = jax.Array

Measure = Literal["cosine", "cross_correlation", "exp_decay"]
Method = Literal["exact", "lsh"]


def _center_and_norms(x: Array, measure: Measure) -> Tuple[Array, Array]:
    """Paper Alg. 1 steps 4-5: per-point mean removal + L2 norms."""
    if measure == "cross_correlation":
        x = x - x.mean(axis=1, keepdims=True)
    norm = jnp.sqrt((x * x).sum(axis=1))
    return x, norm


def edge_similarities(
    x: Array,
    edges: Array,
    *,
    measure: Measure = "cross_correlation",
    sigma: float = 1.0,
    chunk: int = 65536,
) -> Array:
    """Similarity value per edge (paper Alg. 1 step 6).

    x     : [n, d] data points.
    edges : [nnz, 2] int32 endpoint indices.
    chunk : edges processed per lax.map step (bounds the gather working set).
    """
    x = x.astype(jnp.float32)
    if measure in ("cosine", "cross_correlation"):
        xc, norm = _center_and_norms(x, measure)

        def body(e):
            xi = xc[e[:, 0]]
            xj = xc[e[:, 1]]
            num = (xi * xj).sum(axis=1)
            den = norm[e[:, 0]] * norm[e[:, 1]]
            return num / jnp.maximum(den, 1e-12)

    elif measure == "exp_decay":

        def body(e):
            diff = x[e[:, 0]] - x[e[:, 1]]
            return jnp.exp(-(diff * diff).sum(axis=1) / (2.0 * sigma**2))

    else:  # pragma: no cover - guarded by Literal
        raise ValueError(f"unknown measure {measure}")

    nnz = edges.shape[0]
    if nnz <= chunk:
        return body(edges)
    # pad to a multiple of chunk, map, then slice back
    pad = (-nnz) % chunk
    ep = jnp.concatenate([edges, jnp.zeros((pad, 2), edges.dtype)]) if pad else edges
    out = jax.lax.map(body, ep.reshape(-1, chunk, 2))
    return out.reshape(-1)[:nnz]


def build_similarity_graph(
    x: np.ndarray,
    edges: np.ndarray,
    n: int | None = None,
    *,
    measure: Measure = "cross_correlation",
    sigma: float = 1.0,
    symmetrize: bool = True,
    clip_negative: bool = True,
) -> COO:
    """End-to-end Stage 1: edge similarities → row-sorted COO (host wrapper).

    ``symmetrize`` mirrors each (i, j) pair to (j, i) — the paper's edge list
    contains unordered pairs.  ``clip_negative`` drops negative correlations
    (a similarity graph needs non-negative weights for D to be positive).
    """
    n = int(x.shape[0]) if n is None else n
    edges = np.asarray(edges, np.int32)
    vals = np.asarray(jax.jit(functools.partial(edge_similarities, measure=measure, sigma=sigma))(
        jnp.asarray(x), jnp.asarray(edges)))
    if clip_negative:
        keep = vals > 0
        edges, vals = edges[keep], vals[keep]
    r, c = edges[:, 0], edges[:, 1]
    if symmetrize:
        mask = r != c  # never duplicate self loops
        r = np.concatenate([r, c[mask]])
        c2 = np.concatenate([c, edges[:, 0][mask]])
        vals = np.concatenate([vals, vals[mask]])
        c = c2
    return coo_from_edges(r, c, vals, (n, n), sort=True, sum_duplicates=True)


# ---------------------------------------------------------------------------
# Device-resident Stage 1 (jit-safe; DESIGN.md §9)
# ---------------------------------------------------------------------------

def graph_from_knn(
    x: Array,
    dist2: Array,  # [n, k] squared neighbor distances (+inf on invalid slots)
    idx: Array,  # [n, k] neighbor ids (-1 on invalid slots)
    *,
    measure: Measure = "exp_decay",
    sigma: float = 1.0,
    eps: Array | float | None = None,
    clip_negative: bool = True,
    sim_chunk: int = 65536,
    dist2_in_x_space: bool = True,
) -> COO:
    """kNN search results → symmetric row-sorted COO, fully on device.

    Static shapes under jit: entries cannot be dropped, so invalid slots
    (masked neighbors, clipped similarities) become zero-valued self edges —
    harmless to every consumer (degrees, normalization, SpMV).  The
    symmetrization is the duplicate-coordinate ``(W + Wᵀ)/2``; mutual-kNN
    pairs appear twice with half weight each, one-sided pairs once.

    ``dist2_in_x_space=False`` declares that ``dist2`` was measured in a
    *different* space than ``x`` (neighbor search on positions, weights from
    features): the exp_decay shortcut of reusing the search distances would
    then weight edges by the wrong metric, so distances are recomputed from
    ``x`` via the chunked edge gather instead.
    """
    n, k = idx.shape
    row = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    valid = (idx >= 0).reshape(-1)
    if eps is not None:
        valid &= (dist2 <= jnp.asarray(eps, jnp.float32) ** 2).reshape(-1)
    col = jnp.where(valid, idx.reshape(-1).astype(jnp.int32), row)
    if measure == "exp_decay" and dist2_in_x_space:
        # the neighbor search already produced the distances — no regather
        vals = jnp.exp(-dist2.reshape(-1) / (2.0 * sigma**2))
    else:
        edges = jnp.stack([row, col], axis=1)
        vals = edge_similarities(x, edges, measure=measure, sigma=sigma, chunk=sim_chunk)
    if clip_negative:
        vals = jnp.maximum(vals, 0.0)
    vals = jnp.where(valid, vals, 0.0).astype(jnp.float32)
    w = symmetrize_coo(COO(row, col, vals, (n, n)))
    return sort_coo_rows(w)


def build_knn_graph(
    x: Array,
    k: int,
    *,
    points: Optional[Array] = None,
    measure: Measure = "exp_decay",
    sigma: float = 1.0,
    eps: Array | float | None = None,
    clip_negative: bool = True,
    method: Method = "exact",
    n_tables: int = DEFAULT_N_TABLES,
    n_bits: int = DEFAULT_N_BITS,
    candidates: Optional[int] = None,
    lsh_seed: int = 0,
    impl: str = "auto",
    block_q: Optional[int] = None,  # None → per-method default (256 exact
    block_k: Optional[int] = None,  # search tile, 1024 rerank chunk)
    interpret: bool | None = None,
    with_tiles: bool = False,
):
    """End-to-end device Stage 1: kNN search → similarity → symmetric
    row-sorted COO.  jit-safe (static nnz = 2·n·k); no host neighbor loop.

    ``method`` selects the neighbor search: ``"exact"`` is the fused O(n²d)
    ``knn_topk`` kernel (bitwise-unchanged default); ``"lsh"`` generates
    bounded candidate sets of size ``candidates = m ≪ n`` by random-
    hyperplane hashing (``kernels/lsh_candidates``) and reranks them with
    the exact ``knn_topk_rerank`` — O(n·m·d), the n ≫ 100k regime where the
    quadratic search dominates the pipeline (DESIGN.md §12).  ``n_tables``/
    ``n_bits``/``candidates``/``lsh_seed`` are the LSH recall knobs
    (``candidates=None`` → ``default_candidates(k, n_tables)``); low-recall
    rows degrade to fewer-than-k neighbors, never to wrong distances — the
    rerank is exact over the candidates it is fed.

    ``points`` optionally separates the neighbor-search space from the
    similarity features (the paper's DTI workflow: spatial ε/kNN neighbors,
    cross-correlation of connectivity profiles as weights).  ``eps`` turns
    the kNN search into a degree-capped ε-ball (neighbors beyond the radius
    are dropped).  With ``measure="exp_decay"`` and ``points=None`` the
    search distances are reused directly — no second gather pass.

    Returns the COO; ``with_tiles=True`` returns ``(COO, tiles)``, where
    ``tiles`` is ``knn_topk``'s int32 [2] count of candidate tiles merged
    and visited (``None`` on the jnp reference and the LSH path).
    """
    p = x if points is None else points
    tiles = None
    if points is not None and points.shape[0] != x.shape[0]:
        raise ValueError(
            f"points rows ({points.shape[0]}) must match feature rows "
            f"({x.shape[0]}) — one search point per feature row")
    if method == "lsh":
        m = default_candidates(k, n_tables) if candidates is None else candidates
        cand = lsh_candidates(p, m=m, n_tables=n_tables, n_bits=n_bits,
                              seed=lsh_seed, impl=impl, interpret=interpret)
        # eps masking is left to graph_from_knn, same as the exact branch
        dist2, idx = knn_topk_rerank(p, cand, k, block_q=block_q or 1024)
    elif method == "exact":
        # NB: eps is NOT threaded into the search here — graph_from_knn
        # applies the radius mask, exactly as before the method= split
        # (keeps the exact path bitwise-unchanged)
        dist2, idx, tiles = knn_topk(p, k, impl=impl, block_q=block_q or 256,
                                     block_k=block_k or 256,
                                     interpret=interpret, with_tiles=True)
    else:  # pragma: no cover - guarded by Literal / GraphConfig validation
        raise ValueError(f"unknown method {method!r} (expected 'exact'|'lsh')")
    w = graph_from_knn(x, dist2, idx, measure=measure, sigma=sigma, eps=eps,
                       clip_negative=clip_negative,
                       dist2_in_x_space=points is None)
    return (w, tiles) if with_tiles else w


# ---------------------------------------------------------------------------
# Neighborhood builders (host-side; the paper assumes E is given)
# ---------------------------------------------------------------------------

def eps_neighbors(points: np.ndarray, eps: float, *, block: int = 2048) -> np.ndarray:
    """All pairs (i < j) with ‖p_i − p_j‖ ≤ eps, by blocked brute force."""
    pts = np.asarray(points, np.float32)
    n = pts.shape[0]
    out = []
    for i0 in range(0, n, block):
        pi = pts[i0 : i0 + block]
        for j0 in range(i0, n, block):
            pj = pts[j0 : j0 + block]
            d2 = ((pi[:, None, :] - pj[None, :, :]) ** 2).sum(-1)
            ii, jj = np.nonzero(d2 <= eps * eps)
            gi, gj = ii + i0, jj + j0
            keep = gi < gj
            out.append(np.stack([gi[keep], gj[keep]], axis=1))
    return np.concatenate(out, axis=0) if out else np.zeros((0, 2), np.int64)


def knn_edges(points: np.ndarray, k: int, *, block: int = 2048) -> np.ndarray:
    """Directed kNN pairs (i, j) — j among the k nearest of i (i ≠ j).

    Emits exactly ``min(k, n-1)`` edges per source row: the self distance is
    pinned to −inf so the self index is *always* among the k+1 candidates and
    dropping it leaves k survivors.  (Selecting the raw top-(k+1) and masking
    ``idx != src`` is not enough — duplicate points can push the self index
    out of the candidate set and leave k+1 neighbors.)
    """
    pts = np.asarray(points, np.float32)
    n = pts.shape[0]
    nrm = (pts * pts).sum(1)
    kk = min(k, n - 1)
    out = []
    for i0 in range(0, n, block):
        pi = pts[i0 : i0 + block]
        bsz = pi.shape[0]
        d2 = nrm[i0 : i0 + bsz, None] + nrm[None, :] - 2.0 * pi @ pts.T
        d2[np.arange(bsz), np.arange(i0, i0 + bsz)] = -np.inf
        idx = np.argpartition(d2, kth=kk, axis=1)[:, : kk + 1]
        # [bsz, kk+1] candidates including the pinned self; drop it
        src = np.broadcast_to(
            np.arange(i0, i0 + bsz, dtype=np.int64)[:, None], idx.shape
        )
        keep = idx != src
        out.append(np.stack([src[keep], idx[keep].astype(np.int64)], axis=1))
    return (
        np.concatenate(out, axis=0) if out else np.zeros((0, 2), np.int64)
    )
