"""Eigensolver vs numpy.linalg.eigh oracles + invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.lanczos import LanczosConfig, lanczos_topk
from repro.sparse.formats import coo_from_edges
from repro.sparse.ops import normalize_sym, spmv_coo


def _sym_sparse(n, density, seed):
    rng = np.random.default_rng(seed)
    W = (rng.random((n, n)) < density) * rng.random((n, n)).astype(np.float32)
    W = np.triu(W, 1)
    W = W + W.T
    r, c = np.nonzero(W)
    return W, coo_from_edges(r, c, W[r, c], (n, n))


@pytest.mark.parametrize("n,k,m", [(120, 4, 24), (200, 8, 32), (150, 12, 40)])
def test_topk_eigs_match_numpy(n, k, m):
    W, coo = _sym_sparse(n, 0.08, seed=n)
    adj = normalize_sym(coo)
    dense = np.zeros((n, n))
    dense[np.asarray(adj.row), np.asarray(adj.col)] = np.asarray(adj.val)
    want = np.linalg.eigvalsh(dense)[::-1][:k]
    res = jax.jit(
        lambda key: lanczos_topk(lambda x: spmv_coo(adj, x), n,
                                 LanczosConfig(k=k, m=m, tol=1e-6, max_restarts=80), key=key)
    )(jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(res.eigenvalues), want, rtol=2e-4, atol=2e-5)
    assert bool(res.converged)
    # eigenvector residuals ‖Av − λv‖
    V = np.asarray(res.eigenvectors)
    resid = np.abs(dense @ V - V * np.asarray(res.eigenvalues)[None, :]).max()
    assert resid < 5e-4
    # orthonormal basis
    np.testing.assert_allclose(V.T @ V, np.eye(k), atol=5e-4)


def test_smallest_algebraic_mode():
    W, coo = _sym_sparse(100, 0.1, seed=5)
    adj = normalize_sym(coo)
    dense = np.zeros((100, 100))
    dense[np.asarray(adj.row), np.asarray(adj.col)] = np.asarray(adj.val)
    want = np.linalg.eigvalsh(dense)[:4]
    res = lanczos_topk(lambda x: spmv_coo(adj, x), 100,
                       LanczosConfig(k=4, m=24, which="SA", tol=1e-6, max_restarts=80),
                       key=jax.random.PRNGKey(1))
    got = np.sort(np.asarray(res.eigenvalues))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-5)


def test_fixed_restarts_static_mode_matches():
    """The fixed-trip-count mode gives the same answer."""
    W, coo = _sym_sparse(150, 0.08, seed=9)
    adj = normalize_sym(coo)
    mv = lambda x: spmv_coo(adj, x)
    a = lanczos_topk(mv, 150, LanczosConfig(k=6, m=30, max_restarts=50, tol=1e-6),
                     key=jax.random.PRNGKey(0))
    b = lanczos_topk(mv, 150, LanczosConfig(k=6, m=30, fixed_restarts=10),
                     key=jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(a.eigenvalues), np.asarray(b.eigenvalues),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Block mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [2, 4, 8])
def test_block_matches_numpy(b):
    n, k = 180, 6
    W, coo = _sym_sparse(n, 0.08, seed=77)
    adj = normalize_sym(coo)
    dense = np.zeros((n, n))
    dense[np.asarray(adj.row), np.asarray(adj.col)] = np.asarray(adj.val)
    want = np.linalg.eigvalsh(dense)[::-1][:k]
    from repro.sparse.ops import spmm_coo

    res = jax.jit(
        lambda key: lanczos_topk(
            lambda x: spmv_coo(adj, x), n,
            LanczosConfig(k=k, m=32, tol=1e-6, max_restarts=80, block_size=b),
            key=key, matmat=lambda X: spmm_coo(adj, X),
        )
    )(jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(res.eigenvalues), want, rtol=2e-4, atol=2e-5)
    assert bool(res.converged)
    V = np.asarray(res.eigenvectors)
    np.testing.assert_allclose(V.T @ V, np.eye(k), atol=5e-4)
    resid = np.abs(dense @ V - V * np.asarray(res.eigenvalues)[None, :]).max()
    assert resid < 5e-4


def test_block_vs_single_equivalence_sbm():
    """Block (b=4) and single-vector modes agree on an SBM graph's spectrum
    to 1e-4, and block mode streams the operator fewer times."""
    from repro.core.lanczos import operator_passes
    from repro.data.sbm import sbm_graph
    from repro.sparse.ops import spmm_coo

    coo, _ = sbm_graph(100, 4, 0.3, 0.01, seed=3)
    n = coo.shape[0]
    adj = normalize_sym(coo)
    mv = lambda x: spmv_coo(adj, x)
    mm = lambda X: spmm_coo(adj, X)
    res = {}
    passes = {}
    for b in (1, 4):
        cfg = LanczosConfig(k=6, m=40, tol=1e-6, max_restarts=80, block_size=b)
        r = jax.jit(
            lambda key: lanczos_topk(mv, n, cfg, key=key, matmat=mm)
        )(jax.random.PRNGKey(0))
        assert bool(r.converged), f"b={b} did not converge"
        res[b] = np.asarray(r.eigenvalues)
        passes[b] = operator_passes(cfg, int(r.restarts))
    np.testing.assert_allclose(res[4], res[1], rtol=1e-4, atol=1e-4)
    assert passes[4] < passes[1], (passes[4], passes[1])


def test_block_matmat_fallback_via_vmap():
    """Without an explicit matmat, block mode vmaps the matvec — same answer."""
    W, coo = _sym_sparse(120, 0.08, seed=31)
    adj = normalize_sym(coo)
    from repro.sparse.ops import spmm_coo

    cfg = LanczosConfig(k=4, m=24, tol=1e-6, max_restarts=60, block_size=4)
    a = lanczos_topk(lambda x: spmv_coo(adj, x), 120, cfg, key=jax.random.PRNGKey(2))
    b = lanczos_topk(
        lambda x: spmv_coo(adj, x), 120, cfg, key=jax.random.PRNGKey(2),
        matmat=lambda X: spmm_coo(adj, X),
    )
    np.testing.assert_allclose(
        np.asarray(a.eigenvalues), np.asarray(b.eigenvalues), rtol=1e-5, atol=1e-6
    )


def test_block_fixed_restarts_static_mode_matches():
    W, coo = _sym_sparse(150, 0.08, seed=9)
    adj = normalize_sym(coo)
    from repro.sparse.ops import spmm_coo

    mv = lambda x: spmv_coo(adj, x)
    mm = lambda X: spmm_coo(adj, X)
    a = lanczos_topk(mv, 150, LanczosConfig(k=6, m=32, max_restarts=50, tol=1e-6,
                                            block_size=4),
                     key=jax.random.PRNGKey(0), matmat=mm)
    b = lanczos_topk(mv, 150, LanczosConfig(k=6, m=32, fixed_restarts=12, block_size=4),
                     key=jax.random.PRNGKey(0), matmat=mm)
    np.testing.assert_allclose(np.asarray(a.eigenvalues), np.asarray(b.eigenvalues),
                               rtol=1e-4, atol=1e-5)


def test_block_smallest_algebraic_mode():
    W, coo = _sym_sparse(100, 0.1, seed=5)
    adj = normalize_sym(coo)
    dense = np.zeros((100, 100))
    dense[np.asarray(adj.row), np.asarray(adj.col)] = np.asarray(adj.val)
    want = np.linalg.eigvalsh(dense)[:4]
    from repro.sparse.ops import spmm_coo

    res = lanczos_topk(lambda x: spmv_coo(adj, x), 100,
                       LanczosConfig(k=4, m=24, which="SA", tol=1e-6, max_restarts=80,
                                     block_size=4),
                       key=jax.random.PRNGKey(1), matmat=lambda X: spmm_coo(adj, X))
    got = np.sort(np.asarray(res.eigenvalues))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-5)


def test_operator_passes_accounting():
    """Static pass-count helper: block mode divides per-cycle streams by b."""
    from repro.core.lanczos import (effective_basis_size, operator_passes,
                                    restart_keep_size)

    c1 = LanczosConfig(k=10, m=40, block_size=1)
    c4 = LanczosConfig(k=10, m=40, block_size=4)
    assert effective_basis_size(c1) == 40 and effective_basis_size(c4) == 40
    l1, l4 = restart_keep_size(c1), restart_keep_size(c4)
    assert l4 % 4 == 0 and l4 >= l1
    assert operator_passes(c1, 1) == 40
    assert operator_passes(c4, 1) == 10
    # per steady cycle: (m - l)/b streams
    assert operator_passes(c1, 3) == 40 + 2 * (40 - l1)
    assert operator_passes(c4, 3) == 10 + 2 * (40 - l4) // 4


# (n, k, b) -> the basis and the vectors kept at a restart: each cell's own
# job (dti.job and dti_sharded4.job, syn200.job, the published DTI size of
# dti.serve's atlas), a graph smaller than 2k, and a block of 4
BASIS_SIZES = {
    "dti": ((28508, 100, 1), 200, 150),
    "syn200": ((20000, 200, 1), 400, 300),
    "dti_full": ((142541, 500, 1), 1000, 750),
    "n_below_2k": ((30, 20, 1), 30, 25),
    "block_4": ((28508, 100, 4), 200, 152),
}


@pytest.mark.parametrize("case", list(BASIS_SIZES))
def test_pipeline_basis_and_keep_sizes(case):
    """The Lanczos basis and restart-keep sizes the pipeline configures,
    as the benchmark reads them for ``stage2_ms_per_matvec``: ARPACK's
    ncv = 2k capped at n, and k + half the excess kept, rounded up to the
    block in block mode."""
    from bench.deploy import lanczos_sizes
    from repro.core.spectral import (EigConfig, SpectralPipeline,
                                     default_basis_size)

    (n, k, b), basis, keep = BASIS_SIZES[case]
    assert default_basis_size(n, k, b) == basis
    pipe = SpectralPipeline(n_clusters=k, eig=EigConfig(block_size=b))
    assert lanczos_sizes(pipe, n) == {"basis": basis, "keep": keep,
                                      "block": b}


@settings(max_examples=8, deadline=None)
@given(n=st.integers(40, 150), seed=st.integers(0, 10**6))
def test_property_eigenvalues_within_gershgorin(n, seed):
    """Normalized adjacency spectrum must lie in [-1, 1]; returned values
    sorted descending; residual estimates small for converged runs."""
    W, coo = _sym_sparse(n, 0.1, seed=seed)
    adj = normalize_sym(coo)
    res = lanczos_topk(lambda x: spmv_coo(adj, x), n,
                       LanczosConfig(k=4, m=min(n - 1, 20), tol=1e-5, max_restarts=60),
                       key=jax.random.PRNGKey(seed % 17))
    vals = np.asarray(res.eigenvalues)
    assert (vals <= 1.0 + 1e-4).all() and (vals >= -1.0 - 1e-4).all()
    assert (np.diff(vals) <= 1e-5).all()


# ---------------------------------------------------------------------------
# Basis-size validation: degenerate k/m requests fail loudly, not with a
# shape error from inside the restart loop
# ---------------------------------------------------------------------------

def test_validate_basis_rejects_oversized_requests():
    from repro.core.lanczos import validate_basis

    with pytest.raises(ValueError, match="k must be >= 1"):
        validate_basis(LanczosConfig(k=0, m=10), 100)
    with pytest.raises(ValueError, match="must exceed k"):
        validate_basis(LanczosConfig(k=10, m=10), 100)
    # the n_eigvecs > n//2-ish degenerate case: m + b exceeds n
    with pytest.raises(ValueError, match="reduce"):
        validate_basis(LanczosConfig(k=30, m=60), 50)
    with pytest.raises(ValueError, match="two block steps"):
        validate_basis(LanczosConfig(k=8, m=12, block_size=4), 100)
    # the boundary m + b == n is fine
    validate_basis(LanczosConfig(k=10, m=49), 50)


def test_eigsh_raises_actionable_error_for_large_k():
    """k ≈ n/2 through the public entries surfaces the actionable message."""
    from repro.core.lanczos import eigsh
    from repro.core.operator import CooOperator
    from repro.core.spectral import EigConfig, SpectralPipeline

    n = 40
    _, coo = _sym_sparse(n, 0.2, seed=0)
    adj = normalize_sym(coo)
    with pytest.raises(ValueError, match="n_eigvecs"):
        eigsh(CooOperator(adj), LanczosConfig(k=25, m=50),
              key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="n_eigvecs"):
        lanczos_topk(lambda x: spmv_coo(adj, x), n, LanczosConfig(k=25, m=50),
                     key=jax.random.PRNGKey(0))
    # and through the pipeline (EigConfig → LanczosConfig plumbing)
    pipe = SpectralPipeline(n_clusters=2, eig=EigConfig(n_eigvecs=25))
    with pytest.raises(ValueError, match="n_eigvecs"):
        pipe.run(adj, jax.random.PRNGKey(0))
