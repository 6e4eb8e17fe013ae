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


# ---------------------------------------------------------------------------
# Nearest-first visit order, ties by id, and the skipped merges
# ---------------------------------------------------------------------------

def _grid_points(n, seed, step=0.25, side=16):
    """Random points on a grid fine enough to be random and coarse enough
    that every squared distance is exact in float32, so the kernel's
    distances equal the float64 ones bit for bit."""
    rng = np.random.default_rng(seed)
    return (rng.integers(0, int(side / step), size=(n, 3)) * step).astype(
        np.float32)


def _lex_topk(xc, xq, k, off=0):
    """Plain (dist², id) top-k of each query among the candidates; the pair
    (query r, candidate c) is left out where ``off + r == c``."""
    d2 = ((xq[:, None, :].astype(np.float64)
           - xc[None, :, :].astype(np.float64)) ** 2).sum(-1)
    d2[off + np.arange(len(xq))[:, None] == np.arange(len(xc))[None, :]] = np.inf
    ids = np.broadcast_to(np.arange(len(xc)), d2.shape)
    order = np.lexsort((ids, d2), axis=1)[:, :k]
    dist = np.take_along_axis(d2, order, 1)
    idx = np.where(np.isinf(dist), -1, order)
    if k > len(xc):
        pad = ((0, 0), (0, k - len(xc)))
        dist = np.pad(dist, pad, constant_values=np.inf)
        idx = np.pad(idx, pad, constant_values=-1)
    return dist.astype(np.float32), idx


def _assert_lex_topk(xc, xq, k, off=0, **kw):
    dist, idx = knn_topk(jnp.asarray(xc), k, queries=jnp.asarray(xq),
                         query_offset=off, impl="pallas", interpret=True, **kw)
    want_d, want_i = _lex_topk(xc, xq, k, off)
    np.testing.assert_array_equal(np.asarray(idx), want_i)
    np.testing.assert_array_equal(np.asarray(dist), want_d)


@pytest.mark.parametrize("name", ["lattice_7cubed", "lattice_300",
                                  "random", "duplicates"])
def test_kernel_equals_lexicographic_topk_bitwise(name):
    """Whatever order the tiles are visited in and whichever are skipped,
    the output is the (dist², id) top-k, bit for bit."""
    x = {"lattice_7cubed": lambda: _lattice(343),
         "lattice_300": lambda: _lattice(300),
         "random": lambda: _grid_points(300, 1),
         "duplicates": lambda: np.concatenate([_grid_points(100, 2)] * 3),
         }[name]()
    _assert_lex_topk(x, x, 16, block_q=32, block_k=64)


@pytest.mark.parametrize("bq,bk", [(32, 64), (64, 32), (24, 128)])
@pytest.mark.parametrize("shift", [-2, -1, 1, 2])
def test_kernel_ring_offsets_bitwise(bq, bk, shift):
    """Ring steps: the visiting block's local ids sit ``shift`` blocks away
    from the queries', so the walk starts at its nearest boundary and no
    self-pair exists."""
    nl, my = 90, 2
    x = _lattice(5 * nl)
    src = my - shift
    blk = x[src * nl:(src + 1) * nl]
    _assert_lex_topk(blk, x[my * nl:(my + 1) * nl], 16, off=shift * nl,
                     block_q=bq, block_k=bk)


@pytest.mark.parametrize("off", [0, 49, 200])
def test_kernel_home_and_serving_offsets_bitwise(off):
    """A chip's own rows among all points (offset inside the pool, bq ≠ bk,
    n not a block multiple), and serving's queries at an offset ≥ n, which
    clamps the walk's start to the last tile."""
    x = _lattice(200)
    q = _grid_points(50, 3, step=0.5, side=6) if off >= 200 else x[off:off + 50]
    _assert_lex_topk(x, q, 16, off=off, block_q=16, block_k=128)
    _assert_lex_topk(x, q, 16, off=off, block_q=48, block_k=128)


@pytest.mark.parametrize("nq,bq,bk,off", [(7, 8, 32, 0), (100, 16, 32, 0),
                                          (100, 32, 16, 37), (343, 8, 128, -343)])
def test_visit_order_is_nearest_first_permutation(nq, bq, bk, off):
    from repro.kernels.knn_topk.kernel import visit_tile

    n_tiles = 6
    for i in range(-(-nq // bq)):
        got = [int(visit_tile(i, j, off, block_q=bq, block_k=bk,
                              n_tiles=n_tiles)) for j in range(n_tiles)]
        i0 = min(max(off + i * bq, 0) // bk, n_tiles - 1)
        want = sorted(range(n_tiles), key=lambda c: (abs(c - i0), c < i0))
        assert got == want, (i, got, want)


def _simulate_tiles(xc, xq, k, off, bq, bk):
    """The skip rule in plain numpy: each query block walks the candidate
    tiles nearest-first and merges a tile only if some row's smallest
    distance in it is at most that row's running k_pad-th (a tie merges).
    Returns (merged, visited).  Pad query rows repeat the last query."""
    k_pad = -(-k // 8) * 8
    n, nq = len(xc), len(xq)
    n_tiles, n_blocks = -(-n // bk), -(-nq // bq)
    c = np.zeros((n_tiles * bk, xc.shape[1])); c[:n] = xc
    q = np.concatenate([xq, np.repeat(xq[-1:], n_blocks * bq - nq, 0)])
    merged = 0
    for i in range(n_blocks):
        rows = np.arange(i * bq, (i + 1) * bq)
        i0 = min(max(off + i * bq, 0) // bk, n_tiles - 1)
        buf_d = np.full((bq, 0), np.inf)
        buf_i = np.zeros((bq, 0), np.int64)
        for t in sorted(range(n_tiles), key=lambda t: (abs(t - i0), t < i0)):
            cols = np.arange(t * bk, (t + 1) * bk)
            s = ((q[rows, None, :] - c[None, cols, :]) ** 2).sum(-1)
            s[:, cols >= n] = np.inf
            s[(off + rows)[:, None] == cols[None, :]] = np.inf
            kth = buf_d[:, -1] if buf_d.shape[1] == k_pad else np.inf
            if not (s.min(1) <= kth).any():
                continue
            merged += 1
            d = np.concatenate([buf_d, s], 1)
            ids = np.concatenate([buf_i, np.broadcast_to(cols, s.shape)], 1)
            o = np.lexsort((ids, d), axis=1)[:, :k_pad]
            buf_d = np.take_along_axis(d, o, 1)
            buf_i = np.take_along_axis(ids, o, 1)
    return merged, n_blocks * n_tiles


@pytest.mark.parametrize("queries,off,bq,bk", [
    ("all", 0, 32, 64),
    ("all", 0, 64, 32),
    ("block", 2 * 86, 40, 64),   # a ring step two blocks away
    ("block", -86, 40, 64),      # a ring step one block the other way
])
def test_tile_counter_equals_simulation_on_a_lattice(queries, off, bq, bk):
    x = _lattice(343)
    xc, xq = (x, x) if queries == "all" else (x[86:172], x[:86])
    _, _, tiles = knn_topk(jnp.asarray(xc), 16, queries=jnp.asarray(xq),
                           query_offset=off, impl="pallas", interpret=True,
                           block_q=bq, block_k=bk, with_tiles=True)
    merged, visited = _simulate_tiles(xc, xq, 16, off, bq, bk)
    assert tuple(np.asarray(tiles).tolist()) == (merged, visited)
    if queries == "all":
        assert merged < visited  # lattice order lets some tiles go


def test_tile_counter_merges_nearly_every_tile_of_shuffled_points():
    x = _lattice(343)[np.random.default_rng(0).permutation(343)]
    _, _, tiles = knn_topk(jnp.asarray(x), 16, impl="pallas", interpret=True,
                           block_q=32, block_k=32, with_tiles=True)
    merged, visited = np.asarray(tiles).tolist()
    assert visited == 11 * 11
    assert merged >= 0.9 * visited


def test_tile_counter_is_absent_on_the_reference():
    x = jnp.asarray(_lattice(64))
    out = knn_topk(x, 4, impl="ref", with_tiles=True)
    assert len(out) == 3 and out[2] is None
    assert len(knn_topk(x, 4, impl="ref")) == 2
