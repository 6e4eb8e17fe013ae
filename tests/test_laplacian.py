"""Stage 2a's normalized Laplacian operators (``core/laplacian.py``) against
dense numpy: D^-1/2 W D^-1/2, D^-1 W, the eigenvalue bookkeeping and the
embedding rows, on graphs with isolated vertices and duplicate
coordinates as well as a planted partition."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.laplacian import (embed_rows, normalized_graph,
                                  random_walk_matrix,
                                  smallest_laplacian_eigs_from_adj)
from repro.data.sbm import sbm_graph
from repro.sparse.formats import COO

ISOLATED = (0, 37, 119)


def _sbm():
    return sbm_graph(30, 4, 0.3, 0.02, seed=1)[0]


def _isolated():
    """The planted partition with every edge of three vertices removed:
    degree 0, so their inverse square-root degree must be 0, not inf."""
    w = _sbm()
    row, col, val = (np.asarray(a) for a in (w.row, w.col, w.val))
    keep = ~np.isin(row, ISOLATED) & ~np.isin(col, ISOLATED)
    return COO(jnp.asarray(row[keep]), jnp.asarray(col[keep]),
               jnp.asarray(val[keep]), w.shape, sorted_rows=True)


def _duplicates():
    """Each coordinate twice, its weight split 1:3, unsorted — the
    duplicate-coordinate layout of Stage 1's (W + Wᵀ)/2."""
    w = _sbm()
    order = np.random.default_rng(2).permutation(2 * w.nnz)
    row, col, val = (np.concatenate([np.asarray(a)] * 2)[order]
                     for a in (w.row, w.col, w.val))
    split = np.concatenate([np.full(w.nnz, 0.25), np.full(w.nnz, 0.75)])
    return COO(jnp.asarray(row), jnp.asarray(col),
               jnp.asarray((val * split[order]).astype(np.float32)), w.shape,
               sorted_rows=False)


GRAPHS = {"sbm": _sbm, "isolated": _isolated, "duplicates": _duplicates}


def _dense(w):
    d = np.zeros(w.shape)
    np.add.at(d, (np.asarray(w.row), np.asarray(w.col)), np.asarray(w.val))
    return d


def _dense_operators(w):
    """W, D, D^-1/2 (0 where D is 0), D^-1/2 W D^-1/2 and D^-1 W in float64."""
    wd = _dense(w)
    deg = wd.sum(1)
    isd = np.where(deg > 0, 1.0 / np.sqrt(np.where(deg > 0, deg, 1.0)), 0.0)
    a = isd[:, None] * wd * isd[None, :]
    p = wd * np.where(deg > 0, isd ** 2, 0.0)[:, None]
    return wd, deg, isd, a, p


@pytest.mark.parametrize("graph", list(GRAPHS))
def test_normalized_graph_matches_dense(graph):
    w = GRAPHS[graph]()
    _, deg, isd, a, _ = _dense_operators(w)
    ng = normalized_graph(w)
    np.testing.assert_allclose(np.asarray(ng.deg), deg, rtol=1e-5)
    got_isd = np.asarray(ng.inv_sqrt_deg)
    np.testing.assert_allclose(got_isd, isd, rtol=1e-5)
    assert np.isfinite(got_isd).all() and (got_isd[deg == 0] == 0).all()
    assert ((deg == 0).sum() > 0) == (graph == "isolated")
    np.testing.assert_allclose(_dense(ng.adj_sym), a, rtol=1e-5, atol=1e-7)
    assert ng.adj_sym.sorted_rows == w.sorted_rows


@pytest.mark.parametrize("graph", list(GRAPHS))
def test_random_walk_matrix_rows_sum_to_one(graph):
    w = GRAPHS[graph]()
    _, deg, _, _, p = _dense_operators(w)
    got = _dense(random_walk_matrix(w))
    np.testing.assert_allclose(got, p, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got.sum(1), (deg > 0).astype(float),
                               rtol=1e-5, atol=1e-6)


def test_smallest_laplacian_eigs_from_adj_are_those_of_both_laplacians():
    """The top eigenvalues θ of A = D^-1/2 W D^-1/2 map to the smallest
    eigenvalues 1 − θ of L_sym = I − A and of L_rw = I − D^-1 W."""
    w = _sbm()
    _, _, _, a, p = _dense_operators(w)
    n, k = a.shape[0], 6
    theta = np.linalg.eigvalsh(a)[::-1][:k]
    got = np.asarray(smallest_laplacian_eigs_from_adj(
        jnp.asarray(theta, jnp.float32)))
    np.testing.assert_allclose(got, np.linalg.eigvalsh(np.eye(n) - a)[:k],
                               atol=1e-6)
    rw = np.sort(np.linalg.eigvals(np.eye(n) - p).real)[:k]
    np.testing.assert_allclose(got, rw, atol=1e-6)
    assert abs(got[0]) < 1e-6  # one connected graph: one zero eigenvalue


@pytest.mark.parametrize("row_normalize", [False, True],
                         ids=["raw", "row_normalized"])
@pytest.mark.parametrize("graph", ["sbm", "isolated"])
def test_embed_rows_maps_to_random_walk_eigenvectors(graph, row_normalize):
    """D^-1/2 v of A's top eigenvectors are D^-1 W's eigenvectors; scaled
    to unit rows (Ng-Jordan-Weiss) they keep their direction, and an
    isolated vertex's row stays exactly 0, not NaN."""
    w = GRAPHS[graph]()
    _, deg, isd, a, p = _dense_operators(w)
    k = 4
    theta, v = np.linalg.eigh(a)
    theta, v = theta[::-1][:k], v[:, ::-1][:, :k]
    h = np.asarray(embed_rows(jnp.asarray(v, jnp.float32),
                              jnp.asarray(isd, jnp.float32),
                              row_normalize=row_normalize))
    assert np.isfinite(h).all() and not h[deg == 0].any()
    u = v * isd[:, None]
    live = deg > 0
    if row_normalize:
        np.testing.assert_allclose(np.linalg.norm(h[live], axis=1), 1.0,
                                   rtol=1e-5)
        u = u / np.linalg.norm(u, axis=1, keepdims=True).clip(1e-300)
        np.testing.assert_allclose(h[live], u[live], atol=1e-5)
    else:
        np.testing.assert_allclose(h, u, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(p @ h, h * theta[None, :], atol=1e-5)
