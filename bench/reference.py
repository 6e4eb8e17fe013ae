"""The plain reference, its lower-precision control, and the comparisons
that decide ``correct``.

Nothing here imports the program.  Graphs, eigenpairs, k-means means and
out-of-sample labels are recomputed in float64 with numpy and scipy
(ARPACK, as in the paper) from the inputs the benchmark made, and compared
with what the timed path returned:

- ``graph_err``: the program's normalized adjacency against the reference's
  (exact kNN in (distance², id) order, cross-correlation weights clipped at
  0, ``(W + Wᵀ)/2``, ``D^-1/2 W D^-1/2``), largest entry gap over the
  largest entry;
- ``eig_err``: the eigenvalues the program returned against the reference's
  smallest of ``L_sym``, the i-th smallest against the i-th smallest (a
  Ritz value with residual r lies within r of an eigenvalue); the largest
  gap;
- ``embed_err``: the share of the program's embedding that lies outside
  the reference's top ``k`` eigenvectors (with any further ones whose
  eigenvalue lies within the solver's tolerance of the k-th, which may
  mix), under the most favourable row lengths (the embedding's rows are
  normalized, so their lengths are not known), or its loss of rank where
  that is larger: 0 for an embedding that spans exactly that space;
- ``row_norm_err``: how far the program's embedding rows lie from unit
  length (the Ng-Jordan-Weiss normalization); the largest;
- ``kmeans_gap``: the reference's Lloyd step run once over the program's
  embedding and labels: every row assigned to the nearest mean of those
  labels, whose inertia the program's reported inertia is compared with,
  relative.  Labels that are not a Lloyd fixed point, and an inertia that
  is not theirs, both open the gap;
- ``oos_label_wrong``: served labels that are not the nearest centroid of
  the reference's embedding row, counted where that centroid leads the next
  by more than ``LABEL_MARGIN`` in squared distance, so that the program's
  own distance arithmetic cannot have swapped them; exact (0);
- ``oos_embed_err``: the largest gap between a served embedding row and the
  reference's.

The control is this reference put in the program's place and computed in
bfloat16, the precision below the float32 the deployments state: inputs,
weights, the adjacency and every result rounded to bfloat16, and the
eigensolver stopped at bfloat16's unit roundoff, below which a bfloat16
Lanczos cannot go.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import ml_dtypes
import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as sla

BF16_EPS = 2.0 ** -8  # bfloat16's unit roundoff (8 significand bits)
# Serving's centroid distances come from a matmul at the TPU's default
# precision, one bfloat16 pass: with unit rows and centroids inside the unit
# ball, each distance lies within 4 × BF16_EPS of the exact one, so two
# distances can swap only where they lie within twice that.
LABEL_MARGIN = 8 * BF16_EPS


def exact(x):
    return np.asarray(x, np.float64)


def bf16(x):
    """``x`` rounded to bfloat16 (to nearest), returned as float64."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def knn_lexicographic(points: np.ndarray, k: int, *, queries=None,
                      exclude_self: bool = True) -> np.ndarray:
    """The k nearest points of each query in (distance², id) order, by
    blocked brute force in float64: every point within the k-th distance is
    a candidate, and candidates are sorted by distance, then id."""
    p = np.asarray(points, np.float64)
    q = p if queries is None else np.asarray(queries, np.float64)
    block = max(1, min(512, 2 ** 24 // len(p)))  # distances of ≤ 128 MiB
    pn = (p * p).sum(1)
    out = np.empty((len(q), k), np.int64)
    for s in range(0, len(q), block):
        qb = q[s:s + block]
        d2 = (qb * qb).sum(1)[:, None] + pn[None, :] - 2.0 * qb @ p.T
        if exclude_self and queries is None:
            d2[np.arange(len(d2)), s + np.arange(len(d2))] = np.inf
        kth = np.partition(d2, k - 1, axis=1)[:, k - 1]
        r, c = np.nonzero(d2 <= kth[:, None])
        order = np.lexsort((c, d2[r, c], r))
        r, c = r[order], c[order]
        first = np.searchsorted(r, np.arange(len(qb)))
        take = first[:, None] + np.arange(k)[None, :]
        out[s:s + block] = c[take]
    return out


def cross_correlation_knn_graph(positions, profiles, k: int,
                                rnd: Callable = exact) -> sp.csr_matrix:
    """``(W + Wᵀ)/2`` of the spatial kNN graph weighted by the profiles'
    cross-correlation, negative correlations clipped to 0."""
    n = len(positions)
    idx = knn_lexicographic(positions, k)
    x = rnd(profiles)
    x = rnd(x - x.mean(1, keepdims=True))
    x = rnd(x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12))
    rows = np.repeat(np.arange(n), k)
    vals = rnd(np.maximum((x[rows] * x[idx.ravel()]).sum(1), 0.0))
    w = sp.coo_matrix((vals, (rows, idx.ravel())), shape=(n, n)).tocsr()
    return ((w + w.T) * 0.5).tocsr()


def graph_from_edges(row, col, val, n: int) -> sp.csr_matrix:
    return sp.coo_matrix((np.asarray(val, np.float64),
                          (np.asarray(row), np.asarray(col))),
                         shape=(n, n)).tocsr()


def normalized_adjacency(w: sp.csr_matrix, rnd: Callable = exact):
    """``D^-1/2 W D^-1/2`` and the degrees."""
    deg = np.asarray(w.sum(1)).ravel()
    isd = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-300)), 0.0)
    a = (sp.diags(isd) @ w @ sp.diags(isd)).tocsr()
    a.data = rnd(a.data)
    return a, deg


def top_eigenpairs(a: sp.csr_matrix, k: int, v0: Optional[np.ndarray] = None,
                   tol: float = 1e-10):
    """The ``k`` largest eigenpairs of ``a`` by ARPACK in float64,
    descending, each with residual under ``tol`` times its value."""
    vals, vecs = sla.eigsh(a, k=k, which="LA", tol=tol, v0=v0)
    order = np.argsort(vals)[::-1]
    return vals[order], vecs[:, order]


def njw_rows(vecs: np.ndarray) -> np.ndarray:
    """Ng-Jordan-Weiss rows: each row scaled to unit length."""
    nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
    return vecs / np.maximum(nrm, 1e-12)


def sq_dists(h: np.ndarray, c: np.ndarray, rnd: Callable = exact):
    h, c = rnd(h), rnd(c)
    return rnd((h * h).sum(1)[:, None] + (c * c).sum(1)[None] - 2 * h @ c.T)


def lloyd(h: np.ndarray, k: int, rng: np.random.Generator,
          iters: int = 100, rnd: Callable = exact):
    """Plain k-means++ seeding and Lloyd iterations; returns labels and
    means."""
    n = len(h)
    c = np.empty((k, h.shape[1]))
    c[0] = h[rng.integers(n)]
    d2 = ((h - c[0]) ** 2).sum(1)
    for i in range(1, k):
        c[i] = h[rng.choice(n, p=d2 / d2.sum())]
        d2 = np.minimum(d2, ((h - c[i]) ** 2).sum(1))
    labels = np.full(n, -1)
    for _ in range(iters):
        new = sq_dists(h, c, rnd).argmin(1)
        if (new == labels).all():
            break
        labels = new
        for j in range(k):
            members = labels == j
            if members.any():
                c[j] = rnd(h[members].mean(0))
    return labels, c


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def graph_err(a_prog: sp.csr_matrix, a_ref: sp.csr_matrix) -> float:
    diff = abs(a_prog - a_ref)
    return float(diff.max() / abs(a_ref).max())


def eig_err(lam_prog: np.ndarray, lam_ref: np.ndarray) -> float:
    """Largest gap between the i-th smallest eigenvalue the program returned
    and the i-th smallest of the reference's, for every i: a pair missed, or
    repeated, shifts every one after it."""
    lam = np.sort(np.asarray(lam_prog, np.float64))
    want = np.sort(np.asarray(lam_ref, np.float64))[:lam.size]
    return float(np.abs(lam - want).max())


def span_width(vals: np.ndarray, k: int, tol: float) -> int:
    """How many of the reference's leading eigenvectors (``vals``
    descending) the top ``k`` span may mix with: the top ``k`` and every
    further one whose eigenvalue lies within ``tol`` of the k-th."""
    vals = np.asarray(vals, np.float64)
    return k + int((vals[k:] >= vals[k - 1] - tol).sum())


def embed_err(h_prog: np.ndarray, u_ref: np.ndarray) -> float:
    """How far the program's embedding is from spanning ``u_ref``'s
    columns: the larger of its leak out of that span and its loss of rank.

    The program's eigenvectors are ``V = diag(g) h`` for the row lengths
    ``g`` it normalized away.  The leak is the least share of ``diag(g) h``
    outside the span of ``u_ref`` over all ``g``: ``|(I - UUᵀ) diag(g) h|² /
    |g|²`` is ``1 - gᵀ K g / |g|²`` with ``K = (hhᵀ) ∘ (UUᵀ) = Z Zᵀ``, ``Z_i
    = h_i ⊗ U_i``, whose least value is ``1 - σ_max(Z)²``.  A leak of 0
    leaves a subspace of too few dimensions unseen (a pair returned twice):
    for orthonormal ``V``, whose rows are at most of unit length, every
    singular value of ``h`` is at least 1, so the rank term ``1 - σ_min(h)``
    is at most 0 for a sound embedding and 1 for a repeated vector."""
    h = np.asarray(h_prog, np.float64)
    nrm = np.linalg.norm(h, axis=1)
    live = nrm > 0
    h = h[live] / nrm[live, None]
    u = np.asarray(u_ref, np.float64)[live]
    k, e = h.shape[1], u.shape[1]

    def zt_z(x):
        y = ((h @ x.reshape(k, e)) * u).sum(1)  # Z x
        return (h.T @ (y[:, None] * u)).ravel()  # Zᵀ y

    op = sla.LinearOperator((k * e, k * e), matvec=zt_z, dtype=np.float64)
    top = sla.eigsh(op, k=1, which="LA", tol=1e-12,
                    v0=np.ones(k * e))[0][0]
    rank = 1.0 - np.linalg.svd(h, compute_uv=False).min()
    return float(max(0.0, 1.0 - top, rank))


def row_norm_err(h_prog: np.ndarray) -> float:
    return float(np.abs(np.linalg.norm(exact(h_prog), axis=1) - 1.0).max())


def kmeans_gap(h: np.ndarray, labels: np.ndarray, inertia: float,
               k: int) -> float:
    h = exact(h)
    labels = np.asarray(labels)
    counts = np.bincount(labels, minlength=k).astype(np.float64)
    sums = np.zeros((k, h.shape[1]))
    np.add.at(sums, labels, h)
    live = counts > 0
    best = sq_dists(h, sums[live] / counts[live, None]).min(1).sum()
    return float(abs(float(inertia) - best) / best)


def oos_reference(points, embedding, centroids, queries, knn_k: int,
                  sigma: float, rnd: Callable = exact):
    """Labels and embedding rows of out-of-sample queries: kernel-weighted
    mean of the k nearest pool rows, unit length, nearest centroid."""
    idx = knn_lexicographic(points, knn_k, queries=queries,
                            exclude_self=False)
    q = rnd(queries)
    p = rnd(points)
    d2 = rnd(((q[:, None, :] - p[idx]) ** 2).sum(-1))
    w = rnd(np.exp(-d2 / (2.0 * sigma ** 2)))
    h = np.einsum("qk,qke->qe", w, rnd(np.asarray(embedding)[idx]))
    h /= np.where(w.sum(1) > 0, w.sum(1), 1.0)[:, None]
    nrm = np.sqrt((h * h).sum(1, keepdims=True))
    h = rnd(h / np.where(nrm > 0, nrm, 1.0))
    return sq_dists(h, centroids, rnd).argmin(1), h


def compare_oos(labels, h_prog, h_ref, centroids) -> Dict[str, float]:
    """The served labels against the nearest centroid of the reference's
    rows, and the served rows against the reference's."""
    d2 = sq_dists(h_ref, centroids)
    two = np.partition(d2, 1, axis=1)[:, :2]
    clear = two[:, 1] - two[:, 0] > LABEL_MARGIN
    wrong = np.asarray(labels) != d2.argmin(1)
    return {"oos_label_wrong": float((wrong & clear).sum()),
            "oos_embed_err": float(np.abs(exact(h_prog) - h_ref).max())}
