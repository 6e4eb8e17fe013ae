"""Stage 1 (Alg. 1) similarity construction vs numpy oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.similarity import (
    build_knn_graph, build_similarity_graph, edge_similarities, eps_neighbors,
    graph_from_knn, knn_edges,
)
from repro.kernels.knn_topk.ops import knn_topk


def _dense(w, n):
    d = np.zeros((n, n))
    np.add.at(d, (np.asarray(w.row), np.asarray(w.col)), np.asarray(w.val))
    return d


def _oracle_crosscorr(x, e):
    xc = x - x.mean(1, keepdims=True)
    num = (xc[e[:, 0]] * xc[e[:, 1]]).sum(1)
    den = np.linalg.norm(xc[e[:, 0]], axis=1) * np.linalg.norm(xc[e[:, 1]], axis=1)
    return num / np.maximum(den, 1e-12)


@pytest.mark.parametrize("measure", ["cosine", "cross_correlation", "exp_decay"])
def test_edge_similarities_match_oracle(measure):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(50, 16)).astype(np.float32)
    e = rng.integers(0, 50, size=(200, 2)).astype(np.int32)
    got = np.asarray(edge_similarities(jnp.asarray(x), jnp.asarray(e), measure=measure, sigma=1.3))
    if measure == "cross_correlation":
        want = _oracle_crosscorr(x, e)
    elif measure == "cosine":
        want = (x[e[:, 0]] * x[e[:, 1]]).sum(1) / (
            np.linalg.norm(x[e[:, 0]], axis=1) * np.linalg.norm(x[e[:, 1]], axis=1)
        )
    else:
        want = np.exp(-((x[e[:, 0]] - x[e[:, 1]]) ** 2).sum(1) / (2 * 1.3**2))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_chunked_equals_unchunked():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    e = jnp.asarray(rng.integers(0, 64, size=(1000, 2)), jnp.int32)
    a = edge_similarities(x, e, chunk=10**6)
    b = edge_similarities(x, e, chunk=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_build_graph_is_symmetric_nonnegative_sorted():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(40, 12)).astype(np.float32)
    e = rng.integers(0, 40, size=(150, 2)).astype(np.int32)
    e = e[e[:, 0] != e[:, 1]]
    w = build_similarity_graph(x, e)
    r, c, v = np.asarray(w.row), np.asarray(w.col), np.asarray(w.val)
    assert (v > 0).all()
    dense = np.zeros((40, 40))
    dense[r, c] = v
    np.testing.assert_allclose(dense, dense.T, atol=1e-6)
    assert (np.diff(r) >= 0).all()  # row-sorted


def test_eps_neighbors_matches_bruteforce():
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(120, 3)).astype(np.float32)
    e = eps_neighbors(pts, 0.8, block=32)
    d2 = ((pts[:, None] - pts[None]) ** 2).sum(-1)
    want = {(i, j) for i in range(120) for j in range(i + 1, 120) if d2[i, j] <= 0.64 + 1e-9}
    got = {tuple(p) for p in e.tolist()}
    assert got == want


@settings(max_examples=10, deadline=None)
@given(n=st.integers(10, 60), k=st.integers(1, 5), seed=st.integers(0, 10**5))
def test_property_knn_degree(n, k, seed):
    pts = np.random.default_rng(seed).normal(size=(n, 4)).astype(np.float32)
    e = knn_edges(pts, min(k, n - 1))
    # every node appears as a source exactly min(k, n-1) times
    src_counts = np.bincount(e[:, 0], minlength=n)
    assert (src_counts == min(k, n - 1)).all()


@settings(max_examples=10, deadline=None)
@given(n=st.integers(4, 30), dup=st.integers(2, 4), k=st.integers(1, 6),
       seed=st.integers(0, 10**5))
def test_property_knn_degree_duplicate_points(n, dup, k, seed):
    """Duplicate points must not inflate the per-row degree: pre-fix,
    argpartition could drop the self index from the candidate set and leave
    k+1 survivors."""
    base = np.random.default_rng(seed).normal(size=(n, 3)).astype(np.float32)
    pts = np.repeat(base, dup, axis=0)  # every point has dup-1 exact twins
    total = pts.shape[0]
    kk = min(k, total - 1)
    e = knn_edges(pts, kk, block=7)  # odd block: exercise block boundaries
    src_counts = np.bincount(e[:, 0], minlength=total)
    assert (src_counts == kk).all()
    assert (e[:, 0] != e[:, 1]).all()


# ---------------------------------------------------------------------------
# Device-resident Stage 1 (build_knn_graph)
# ---------------------------------------------------------------------------

def _numpy_knn_graph(x, dist2, idx, *, measure, sigma, eps, dist2_in_x_space):
    """graph_from_knn's semantics in plain numpy (float64): each valid kNN
    slot (i, j) weighs max(0, sim(i, j)); invalid slots (id -1, or beyond
    eps) are zero-valued self edges; the result is (W + Wᵀ)/2 as duplicate
    coordinates, 2·n·k entries."""
    n, k = idx.shape
    row = np.repeat(np.arange(n), k)
    valid = (idx >= 0).reshape(-1)
    if eps is not None:
        valid &= (dist2 <= np.float32(eps) ** 2).reshape(-1)
    col = np.where(valid, idx.reshape(-1), row)
    xf = x.astype(np.float64)
    if measure == "exp_decay":
        d2 = (dist2.reshape(-1).astype(np.float64) if dist2_in_x_space
              else ((xf[row] - xf[col]) ** 2).sum(1))
        val = np.exp(-d2 / (2.0 * sigma ** 2))
    else:
        if measure == "cross_correlation":
            xf = xf - xf.mean(1, keepdims=True)
        nrm = np.linalg.norm(xf, axis=1)
        val = (xf[row] * xf[col]).sum(1) / np.maximum(nrm[row] * nrm[col], 1e-12)
    val = np.where(valid, np.maximum(val, 0.0), 0.0)
    return (np.concatenate([row, col]), np.concatenate([col, row]),
            np.concatenate([val, val]) / 2.0)


# build_knn_graph against the host path (knn_edges + build_similarity_graph),
# which sums mirrored duplicates where the device path averages them
HOST_TOL = {"exp_decay": dict(rtol=1e-4, atol=1e-6),
            "cross_correlation": dict(rtol=2e-4, atol=1e-5),
            "cosine": dict(rtol=2e-4, atol=1e-5)}


@pytest.mark.parametrize("with_eps", [False, True], ids=["no_eps", "eps"])
@pytest.mark.parametrize("dist2_in_x_space", [True, False],
                         ids=["x_space", "search_space"])
@pytest.mark.parametrize("measure", ["cosine", "cross_correlation", "exp_decay"])
def test_graph_from_knn_matches_numpy_assembly(measure, dist2_in_x_space,
                                               with_eps):
    """Device Stage 1's graph assembly against plain numpy: the same
    (row, col, value) entries, rows non-decreasing, and duplicate
    coordinates that sum to (W + Wᵀ)/2; masked slots (id -1, or beyond
    eps) carry nothing.  Without eps, build_knn_graph end to end is twice
    the host path's graph."""
    rng = np.random.default_rng(4)
    n, k, sigma = 120, 6, 1.3
    x = rng.normal(size=(n, 5)).astype(np.float32)
    search = x if dist2_in_x_space else rng.normal(size=(n, 3)).astype(np.float32)
    dist2, idx = (np.array(a) for a in knn_topk(jnp.asarray(search), k,
                                                impl="ref"))
    eps = float(np.median(np.sqrt(dist2))) if with_eps else None
    dist2[::7, -1], idx[::7, -1] = np.inf, -1  # masked slots
    w = graph_from_knn(jnp.asarray(x), jnp.asarray(dist2), jnp.asarray(idx),
                       measure=measure, sigma=sigma, eps=eps,
                       dist2_in_x_space=dist2_in_x_space)
    row, col, val = _numpy_knn_graph(x, dist2, idx, measure=measure,
                                     sigma=sigma, eps=eps,
                                     dist2_in_x_space=dist2_in_x_space)
    gr, gc, gv = (np.asarray(a) for a in (w.row, w.col, w.val))
    assert w.sorted_rows is True and w.nnz == 2 * n * k == row.size
    assert (np.diff(gr) >= 0).all()
    got, want = np.lexsort((gv, gc, gr)), np.lexsort((val, col, row))
    np.testing.assert_array_equal(gr[got], row[want])
    np.testing.assert_array_equal(gc[got], col[want])
    np.testing.assert_allclose(gv[got], val[want], rtol=1e-5, atol=1e-7)
    dense = _dense(w, n)
    want_dense = np.zeros((n, n))
    np.add.at(want_dense, (row, col), val)
    np.testing.assert_allclose(dense, want_dense, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dense, dense.T, atol=1e-6)
    if eps is None:
        points = None if dist2_in_x_space else jnp.asarray(search)
        wd = build_knn_graph(jnp.asarray(x), k, points=points,
                             measure=measure, sigma=sigma)
        wh = build_similarity_graph(x, knn_edges(search, k), measure=measure,
                                    sigma=sigma)
        assert wd.sorted_rows is True and wd.nnz == 2 * n * k
        np.testing.assert_allclose(2.0 * _dense(wd, n), _dense(wh, n),
                                   **HOST_TOL[measure])


def test_build_knn_graph_separate_points_space():
    """Neighbor search on positions, weights from profiles (DTI contract)."""
    rng = np.random.default_rng(11)
    n, k = 90, 4
    pos = rng.normal(size=(n, 3)).astype(np.float32)
    prof = rng.normal(size=(n, 16)).astype(np.float32)
    wd = build_knn_graph(jnp.asarray(prof), k, points=jnp.asarray(pos),
                         measure="cross_correlation")
    wh = build_similarity_graph(prof, knn_edges(pos, k), measure="cross_correlation")
    np.testing.assert_allclose(2.0 * _dense(wd, n), _dense(wh, n), rtol=2e-4, atol=1e-5)


def test_build_knn_graph_separate_points_exp_decay_uses_feature_distances():
    """exp_decay weights must be measured in feature space even when the
    neighbor search ran in a separate ``points`` space — the fused
    distance-reuse shortcut only applies when the two spaces coincide."""
    rng = np.random.default_rng(13)
    n, k = 70, 5
    pos = rng.normal(size=(n, 3)).astype(np.float32)
    prof = rng.normal(size=(n, 10)).astype(np.float32)
    wd = build_knn_graph(jnp.asarray(prof), k, points=jnp.asarray(pos),
                         measure="exp_decay", sigma=1.7)
    wh = build_similarity_graph(prof, knn_edges(pos, k), measure="exp_decay",
                                sigma=1.7)
    np.testing.assert_allclose(2.0 * _dense(wd, n), _dense(wh, n), rtol=2e-4, atol=1e-5)


def test_build_knn_graph_is_jit_safe():
    """The whole Stage 1 must trace (no host neighbor loop in the jit path)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(64, 6)), jnp.float32)
    fn = jax.jit(lambda xx: build_knn_graph(xx, 4, measure="exp_decay"))
    w = fn(x)
    w2 = build_knn_graph(x, 4, measure="exp_decay")
    np.testing.assert_allclose(np.asarray(w.val), np.asarray(w2.val), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(w.row), np.asarray(w2.row))


def test_build_knn_graph_eps_caps_radius():
    rng = np.random.default_rng(9)
    n, k, eps = 100, 8, 1.0
    x = rng.normal(size=(n, 4)).astype(np.float32)
    w = build_knn_graph(jnp.asarray(x), k, measure="exp_decay", eps=eps)
    r, c, v = np.asarray(w.row), np.asarray(w.col), np.asarray(w.val)
    live = v > 0
    d = np.sqrt(((x[r[live]] - x[c[live]]) ** 2).sum(1))
    assert (d <= eps + 1e-5).all()
