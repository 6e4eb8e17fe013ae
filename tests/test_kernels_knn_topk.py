"""Fused kNN top-k kernel: interpret-mode Pallas vs jnp reference vs
np.argsort brute force, across n/k/d grids incl. non-multiple-of-block
shapes, duplicate points, and the ε-ball variant."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.knn_topk.ops import knn_topk
from repro.kernels.knn_topk.ref import knn_topk_ref


def _brute(x, k):
    """Squared kNN distances/ids by full argsort (self excluded)."""
    d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1).astype(np.float64)
    np.fill_diagonal(d2, np.inf)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d2, order, 1), order


def _check_valid_knn(x, dist, idx, k):
    """Invariants that hold regardless of tie-breaking differences."""
    n = x.shape[0]
    kk = min(k, n - 1)
    want_d, _ = _brute(x, k)
    # distances match the brute-force kth-statistics
    np.testing.assert_allclose(dist[:, :kk], want_d[:, :kk], rtol=1e-3, atol=1e-3)
    # rows ascending
    assert (np.diff(dist[:, :kk], axis=1) >= -1e-5).all()
    # slots beyond the candidate supply are masked
    assert (idx[:, kk:] == -1).all()
    assert np.isinf(dist[:, kk:]).all()
    # chosen ids are in range, never the query itself, never duplicated
    valid = idx[:, :kk]
    assert ((valid >= 0) & (valid < n)).all()
    assert (valid != np.arange(n)[:, None]).all()
    for r in range(n):
        assert len(set(valid[r].tolist())) == kk, (r, valid[r])
    # reported distances are consistent with the reported ids
    got = ((x[:, None, :] - x[valid]) ** 2).sum(-1)
    np.testing.assert_allclose(dist[:, :kk], got, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,d,k", [
    (32, 4, 3), (100, 8, 10), (257, 16, 5), (300, 3, 7), (64, 130, 4), (10, 2, 12),
])
def test_ref_matches_bruteforce(n, d, k):
    x = np.random.default_rng(n + d + k).normal(size=(n, d)).astype(np.float32)
    dist, idx = knn_topk(jnp.asarray(x), k, impl="ref")
    _check_valid_knn(x, np.asarray(dist), np.asarray(idx), k)


@pytest.mark.parametrize("n,d,k,bq,bk", [
    (64, 8, 4, 32, 32),     # exact tiling
    (100, 8, 10, 32, 64),   # n not a block multiple (pads to 128)
    (130, 5, 3, 64, 128),   # bq < bk, n not a multiple of either
    (96, 200, 8, 32, 32),   # d not a multiple of 128
    (48, 6, 11, 16, 16),    # k > block sizes' sublane, k_pad rounding
])
def test_kernel_interpret_matches_bruteforce(n, d, k, bq, bk):
    x = np.random.default_rng(7 * n + k).normal(size=(n, d)).astype(np.float32)
    dist, idx = knn_topk(jnp.asarray(x), k, impl="pallas", interpret=True,
                         block_q=bq, block_k=bk)
    _check_valid_knn(x, np.asarray(dist), np.asarray(idx), k)


@pytest.mark.parametrize("impl,kw", [
    ("ref", {}),
    ("pallas", dict(interpret=True, block_q=32, block_k=32)),
])
def test_duplicate_points(impl, kw):
    """Duplicated points must not leak self-pairs or duplicate neighbor ids
    (the failure mode of the pre-fix host knn_edges)."""
    rng = np.random.default_rng(0)
    base = rng.normal(size=(20, 4)).astype(np.float32)
    x = np.concatenate([base, base, base])  # every point has 2 exact twins
    n, k = x.shape[0], 5
    dist, idx = knn_topk(jnp.asarray(x), k, impl=impl, **kw)
    dist, idx = np.asarray(dist), np.asarray(idx)
    assert (idx != np.arange(n)[:, None]).all()
    for r in range(n):
        assert len(set(idx[r].tolist())) == k
    # the two twins are the nearest neighbors, at distance 0
    np.testing.assert_allclose(dist[:, :2], 0.0, atol=1e-5)


def test_eps_variant_masks_beyond_radius():
    x = np.random.default_rng(3).normal(size=(80, 6)).astype(np.float32)
    k, eps = 10, 1.5
    dist, idx = knn_topk(jnp.asarray(x), k, impl="ref", eps=eps)
    dist, idx = np.asarray(dist), np.asarray(idx)
    full_d, _ = _brute(x, k)
    inside = full_d <= eps**2 + 1e-6
    # masked slots are exactly the beyond-radius ones (up to float fuzz)
    assert ((idx >= 0) == (np.isfinite(dist))).all()
    assert (dist[np.isfinite(dist)] <= eps**2 + 1e-5).all()
    assert np.isfinite(dist).sum() == inside.sum()


def test_ref_query_block_offset():
    """The sharded entry: queries = a row block, self-exclusion via offset."""
    x = np.random.default_rng(5).normal(size=(96, 7)).astype(np.float32)
    k = 6
    full_d, _ = _brute(x, k)
    off = 32
    dist, idx = knn_topk_ref(jnp.asarray(x), k, queries=jnp.asarray(x[off:64]),
                             query_offset=off, block_q=16)
    dist, idx = np.asarray(dist), np.asarray(idx)
    np.testing.assert_allclose(dist, full_d[off:64], rtol=1e-3, atol=1e-3)
    assert (idx != (np.arange(off, 64))[:, None]).all()


@pytest.mark.parametrize("off,nq,bq,bk", [
    (0, 32, 32, 32),    # leading block, exact tiling
    (32, 32, 16, 64),   # interior block
    (64, 34, 16, 32),   # trailing block, nq not a block multiple
])
def test_kernel_query_block_offset(off, nq, bq, bk):
    """The Pallas kernel's self-exclusion mask under a global query-row
    offset (the per-shard dispatch of the sharded Stage 1) — must match the
    reference block-query path exactly, including neighbor ids."""
    x = np.random.default_rng(9).normal(size=(98, 5)).astype(np.float32)
    k = 4
    q = jnp.asarray(x[off:off + nq])
    d_ker, i_ker = knn_topk(jnp.asarray(x), k, queries=q, query_offset=off,
                            impl="pallas", interpret=True, block_q=bq, block_k=bk)
    d_ref, i_ref = knn_topk_ref(jnp.asarray(x), k, queries=q, query_offset=off)
    np.testing.assert_allclose(np.asarray(d_ker), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i_ker), np.asarray(i_ref))
    assert (np.asarray(i_ker) != (np.arange(off, off + nq))[:, None]).all()


def test_kernel_offset_traced_under_jit():
    """query_offset is traced (shard_map passes axis_index-derived values):
    one compiled function must serve every block offset."""
    import jax

    x = np.random.default_rng(1).normal(size=(64, 4)).astype(np.float32)
    k = 3
    fn = jax.jit(lambda xs, q, o: knn_topk(xs, k, queries=q, query_offset=o,
                                           impl="pallas", interpret=True,
                                           block_q=16, block_k=32))
    for off in (0, 16, 48):
        got_d, got_i = fn(jnp.asarray(x), jnp.asarray(x[off:off + 16]),
                          jnp.asarray(off))
        ref_d, ref_i = knn_topk_ref(jnp.asarray(x), k,
                                    queries=jnp.asarray(x[off:off + 16]),
                                    query_offset=off)
        np.testing.assert_allclose(np.asarray(got_d), np.asarray(ref_d),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))


def _lattice(n):
    side = int(np.ceil(n ** (1 / 3)))
    g = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"), -1)
    return g.reshape(-1, 3)[:n].astype(np.float32)


@pytest.mark.parametrize("n,k,bq,bk", [
    (343, 16, 32, 64),    # 7³ lattice: ties straddle query and key tiles
    (300, 16, 64, 128),   # partial lattice, n not a block multiple
    (216, 7, 16, 32),     # k cuts through a shell of equal distances
])
def test_kernel_breaks_ties_by_id_on_a_lattice(n, k, bq, bk):
    """Lattice distances are exact integers with many ties: the kernel must
    return the k smallest in (dist², id) order — the one answer that the
    sharded ring exchange's merge reproduces bitwise."""
    x = _lattice(n)
    dist, idx = knn_topk(jnp.asarray(x), k, impl="pallas", interpret=True,
                         block_q=bq, block_k=bk)
    want_d, want_i = _brute(x, k)
    np.testing.assert_array_equal(np.asarray(idx), want_i)
    np.testing.assert_array_equal(np.asarray(dist), want_d.astype(np.float32))


def test_lattice_blocks_merged_in_ring_order_equal_full_pool():
    """Four key blocks searched apart (query offsets as the ring passes
    them) and merged in ring order give the full-pool (dist², id) answer."""
    from repro.core.distributed_pipeline import merge_topk

    n, k, nb = 512, 16, 128
    x = jnp.asarray(_lattice(n))
    kw = dict(impl="pallas", interpret=True, block_q=64, block_k=64)
    d_full, i_full = knn_topk(x, k, **kw)
    bd = jnp.full((n, k), jnp.inf, jnp.float32)
    bi = jnp.full((n, k), -1, jnp.int32)
    for src in (0, 3, 2, 1):
        d_t, i_t = knn_topk(x[src * nb:(src + 1) * nb], k, queries=x,
                            query_offset=-src * nb, **kw)
        bd, bi = merge_topk(bd, bi, d_t,
                            jnp.where(i_t >= 0, i_t + src * nb, -1), k)
    np.testing.assert_array_equal(np.asarray(bi), np.asarray(i_full))
    np.testing.assert_array_equal(np.asarray(bd), np.asarray(d_full))
