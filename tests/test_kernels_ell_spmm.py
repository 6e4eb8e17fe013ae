"""Per-kernel validation: BlockELL multi-vector SpMM vs jnp oracle + dense W @ X."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.sparse.formats import coo_from_edges, coo_to_csr, csr_to_blockell
from repro.kernels.ell_spmm.ops import ell_spmm
from repro.kernels.ell_spmm.ref import ell_spmm_ref


def _random_sparse(n, density, seed, dtype=np.float32):
    rng = np.random.default_rng(seed)
    W = (rng.random((n, n)) < density) * rng.random((n, n)).astype(dtype)
    r, c = np.nonzero(W)
    return W, coo_from_edges(r, c, W[r, c], (n, n))


@pytest.mark.parametrize(
    "n,b,density,block_rows,wq",
    [
        (64, 4, 0.1, 8, 1.0),  # no tail
        (300, 2, 0.05, 8, 0.8),  # tail spill
        (513, 8, 0.03, 128, 0.5),  # unaligned rows, heavy tail
        (200, 3, 0.05, 64, 0.9),  # b not a lane-friendly width
        (100, 1, 0.1, 8, 0.7),  # degenerate single column
    ],
)
def test_spmm_matches_dense(n, b, density, block_rows, wq):
    W, coo = _random_sparse(n, density, seed=n + b)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=block_rows, width_quantile=wq)
    X = jnp.asarray(np.random.default_rng(0).normal(size=(n, b)), jnp.float32)
    Y = np.asarray(ell_spmm(ell, X, impl="pallas", interpret=True, block_rows=block_rows))
    np.testing.assert_allclose(Y, W @ np.asarray(X), rtol=1e-4, atol=1e-4)


def test_kernel_matches_jnp_ref_exactly_on_body():
    n, b = 256, 4
    _, coo = _random_sparse(n, 0.05, seed=5)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=8, width_quantile=1.0)
    X = jnp.asarray(np.random.default_rng(1).normal(size=(n, b)), jnp.float32)
    nb, br, w = ell.cols.shape
    cols2d, vals2d = ell.cols.reshape(-1, w), ell.vals.reshape(-1, w)
    from repro.kernels.ell_spmm.kernel import ell_spmm_pallas

    y_k = np.asarray(ell_spmm_pallas(X, cols2d, vals2d, block_rows=8, interpret=True))
    y_r = np.asarray(ell_spmm_ref(X, cols2d, vals2d))
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-6)


def test_spmm_consistent_with_spmv_per_column():
    """Each SpMM output column must equal the SpMV of that input column."""
    from repro.kernels.ell_spmv.ops import ell_spmv

    n, b = 200, 5
    _, coo = _random_sparse(n, 0.05, seed=3)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=8, width_quantile=0.7)
    X = jnp.asarray(np.random.default_rng(2).normal(size=(n, b)), jnp.float32)
    Y = np.asarray(ell_spmm(ell, X, impl="pallas", interpret=True, block_rows=8))
    for j in range(b):
        yj = np.asarray(ell_spmv(ell, X[:, j], impl="pallas", interpret=True, block_rows=8))
        np.testing.assert_allclose(Y[:, j], yj, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    n, b = 200, 4
    W, coo = _random_sparse(n, 0.05, seed=2)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=8)
    X = jnp.asarray(np.random.default_rng(3).normal(size=(n, b)), dtype)
    Y = np.asarray(ell_spmm(ell, X, impl="pallas", interpret=True, block_rows=8), np.float32)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(Y, W @ np.asarray(X, np.float32), rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(8, 250), b=st.integers(1, 8), density=st.floats(0.005, 0.2),
       seed=st.integers(0, 10**6))
def test_property_linear_operator(n, b, density, seed):
    """SpMM must be linear: A(aX+bY) == a·AX + b·AY, and match dense."""
    W, coo = _random_sparse(n, density, seed=seed)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=8, width_quantile=0.7)
    rng = np.random.default_rng(seed + 1)
    X = jnp.asarray(rng.normal(size=(n, b)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(n, b)), jnp.float32)
    AX = ell_spmm(ell, X, impl="pallas", interpret=True, block_rows=8)
    AY = ell_spmm(ell, Y, impl="pallas", interpret=True, block_rows=8)
    AXY = ell_spmm(ell, 2.0 * X - 3.0 * Y, impl="pallas", interpret=True, block_rows=8)
    np.testing.assert_allclose(
        np.asarray(AXY), 2 * np.asarray(AX) - 3 * np.asarray(AY), rtol=1e-3, atol=1e-4
    )
    np.testing.assert_allclose(np.asarray(AX), W @ np.asarray(X), rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# Fused Chebyshev step: ca·(A x) + cb·x − prev riding the SpMM epilogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "n,b,density,block_rows,wq",
    [
        (64, 4, 0.1, 8, 1.0),  # no tail
        (300, 6, 0.05, 8, 0.8),  # tail spill
        (513, 8, 0.03, 128, 0.5),  # unaligned rows, heavy tail
    ],
)
def test_cheb_step_matches_dense(n, b, density, block_rows, wq):
    from repro.kernels.ell_spmm.ops import ell_spmm_cheb_step

    W, coo = _random_sparse(n, density, seed=n + b)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=block_rows, width_quantile=wq)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(n, b)), jnp.float32)
    P = jnp.asarray(rng.normal(size=(n, b)), jnp.float32)
    ca, cb = 0.37, -1.21
    want = ca * (W @ np.asarray(X)) + cb * np.asarray(X) - np.asarray(P)
    for kw in (dict(impl="ref"),
               dict(impl="pallas", interpret=True, block_rows=block_rows)):
        got = np.asarray(ell_spmm_cheb_step(ell, X, P, ca, cb, **kw))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_cheb_step_kernel_matches_ref_on_body():
    """Interpret-mode Pallas vs the jnp oracle, padded-body exact."""
    from repro.kernels.ell_spmm.kernel import ell_spmm_cheb_pallas
    from repro.kernels.ell_spmm.ref import ell_spmm_cheb_ref

    n, b = 256, 4
    _, coo = _random_sparse(n, 0.05, seed=5)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=8, width_quantile=1.0)
    nb, br, w = ell.cols.shape
    cols2d, vals2d = ell.cols.reshape(-1, w), ell.vals.reshape(-1, w)
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(nb * br, b)), jnp.float32)
    P = jnp.asarray(rng.normal(size=(nb * br, b)), jnp.float32)
    ca = jnp.float32(2.5)
    cb = jnp.float32(-0.75)
    coef = jnp.stack([ca, cb]).reshape(1, 2)
    y_k = np.asarray(ell_spmm_cheb_pallas(X, cols2d, vals2d, P, coef,
                                          block_rows=8, interpret=True))
    y_r = np.asarray(ell_spmm_cheb_ref(X, cols2d, vals2d, P, ca, cb))
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-5)


def test_block_ell_operator_cheb_step_hook():
    """The operator-protocol hook equals mm-then-AXPY (the generic path)."""
    from repro.core.operator import BlockEllOperator

    n, b = 200, 5
    W, coo = _random_sparse(n, 0.05, seed=9)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=8, width_quantile=0.7)
    op = BlockEllOperator(ell)
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.normal(size=(n, b)), jnp.float32)
    P = jnp.asarray(rng.normal(size=(n, b)), jnp.float32)
    ca = jnp.float32(-1.5)
    cb = jnp.float32(0.25)
    fused = np.asarray(op.cheb_step(X, P, ca, cb))
    generic = np.asarray(ca * op.mm(X) + cb * X - P)
    np.testing.assert_allclose(fused, generic, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("op", ["spmm", "cheb_step", "spmv"])
def test_ell_kernels_take_the_xla_path_on_tpu(monkeypatch, op):
    """Mosaic refuses the kernels' in-kernel gather, so on a TPU ``auto``
    runs the XLA path and ``impl="pallas"`` raises with the compiler's
    reason (the backend is steered here; the dispatch reads it at trace)."""
    import re

    import jax

    from repro.kernels.ell_spmm import ops as mm_ops
    from repro.kernels.ell_spmv import ops as mv_ops

    n = 64
    W, coo = _random_sparse(n, 0.1, seed=3)
    ell = csr_to_blockell(coo_to_csr(coo), block_rows=8, width_quantile=0.8)
    X = jnp.asarray(np.random.default_rng(0).normal(size=(n, 2)), jnp.float32)
    Xn = np.asarray(X)
    fn, reason, want = {
        "spmm": (lambda **kw: mm_ops.ell_spmm(ell, X, **kw),
                 mm_ops.MOSAIC_REFUSAL, W @ Xn),
        "cheb_step": (lambda **kw: mm_ops.ell_spmm_cheb_step(
            ell, X, X, 1.0, 0.0, **kw), mm_ops.MOSAIC_REFUSAL, W @ Xn - Xn),
        "spmv": (lambda **kw: mv_ops.ell_spmv(ell, X[:, 0], **kw),
                 mv_ops.MOSAIC_REFUSAL, W @ Xn[:, 0]),
    }[op]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    try:
        with pytest.raises(NotImplementedError, match=re.escape(reason)):
            fn(impl="pallas")
        np.testing.assert_allclose(np.asarray(fn()), want, rtol=1e-4,
                                   atol=1e-4)
    finally:
        jax.clear_caches()
