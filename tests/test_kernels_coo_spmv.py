"""Row-sorted sparse product as one Pallas pass (``kernels/coo_spmv``):
the chunked layout and the kernel (interpret mode) against the segment-sum
product, Lanczos through the new operator against the XLA path, the layout
built under ``jax.jit``, and the dispatch rule of the pipeline."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import lanczos as lz
from repro.core.operator import CooOperator, TiledCooOperator
from repro.core.spectral import EigConfig, GraphConfig, SpectralPipeline
from repro.data.sbm import sbm_graph
from repro.kernels.coo_spmv import build_tiles, coo_spmv, kernel_applies
from repro.kernels.coo_spmv.ops import MAX_N, n_chunks
from repro.kernels.coo_spmv.kernel import CHUNK_SLOTS, TILE_ROWS
from repro.sparse.formats import COO
from repro.sparse.ops import spmv_coo


def _coo(row, col, n, seed=0):
    """A COO of the given coordinates, sorted by row, with random values."""
    order = np.argsort(row, kind="stable")
    val = np.random.default_rng(seed).uniform(0.1, 1.0, row.size)
    return COO(jnp.asarray(row[order], jnp.int32),
               jnp.asarray(col[order], jnp.int32),
               jnp.asarray(val[order], jnp.float32), (n, n))


def _random(n, nnz, rows=None, seed=0):
    rng = np.random.default_rng(seed)
    pool = np.arange(n) if rows is None else rows
    return _coo(rng.choice(pool, nnz), rng.integers(0, n, nnz), n, seed)


def _empty_rows():  # two thirds of the rows hold nothing
    return _random(700, 3000, rows=np.arange(0, 700, 3), seed=1)


def _long_row():  # one row longer than a chunk's 8192 slots and a tile
    rng = np.random.default_rng(2)
    n = 1500
    row = np.concatenate([np.full(9000, 600), rng.integers(0, n, 4000)])
    return _coo(row, rng.integers(0, n, row.size), n, seed=2)


def _tile_straddle():  # dense rows on both sides of the tile boundaries
    rng = np.random.default_rng(3)
    n = 2300
    edge = np.concatenate([np.arange(1000, 1050), np.arange(2030, 2060)])
    row = np.concatenate([np.repeat(edge, 40), rng.integers(0, n, 5000)])
    return _coo(row, rng.integers(0, n, row.size), n, seed=3)


def _odd_n():  # n not a multiple of 128
    return _random(131, 900, seed=4)


def _degree_nine():  # every row of degree 9: the most lanes for its nnz
    n = 2100
    rng = np.random.default_rng(5)
    return _coo(np.repeat(np.arange(n), 9), rng.integers(0, n, 9 * n), n, 5)


def _duplicates():  # repeated coordinates, as the (W + Wᵀ)/2 graphs have
    a = _random(400, 2500, seed=6)
    return COO(jnp.concatenate([a.row, a.row]), jnp.concatenate([a.col, a.col]),
               jnp.concatenate([a.val, a.val]), a.shape, sorted_rows=False)


def _dti_knn():  # the DTI deployment's graph at a small size
    rng = np.random.default_rng(7)
    side = 11
    pos = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"),
                   -1).reshape(-1, 3).astype(np.float32)
    prof = rng.normal(size=(pos.shape[0], 90)).astype(np.float32)
    pipe = SpectralPipeline(
        n_clusters=8, graph=GraphConfig(knn_k=16, measure="cross_correlation"))
    return pipe.build_graph(jnp.asarray(prof), points=jnp.asarray(pos)).adj


def _sbm():
    w, _ = sbm_graph(50, 12, p_in=0.3, p_out=0.01, seed=8)
    return SpectralPipeline(n_clusters=12).prepare(w).adj


GRAPHS = {"empty_rows": _empty_rows, "long_row": _long_row,
          "tile_straddle": _tile_straddle, "odd_n": _odd_n,
          "degree_nine": _degree_nine, "duplicates": _duplicates,
          "dti_knn": _dti_knn, "sbm": _sbm}


def _tiles(a):
    return TiledCooOperator.build(a).tiles


@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("graph", list(GRAPHS))
def test_product_matches_segment_sum(graph, impl):
    a = GRAPHS[graph]()
    x = jnp.asarray(np.random.default_rng(9).normal(size=a.shape[0]),
                    jnp.float32)
    want = np.asarray(spmv_coo(a, x))
    got = np.asarray(coo_spmv(_tiles(a), x, impl=impl, interpret=True))
    # only the summation order differs: float32 rounding of |A| |x|
    scale = np.asarray(spmv_coo(COO(a.row, a.col, jnp.abs(a.val), a.shape,
                                    sorted_rows=a.sorted_rows), jnp.abs(x)))
    assert np.all(np.abs(got - want) <= 1e-6 * scale + 1e-7), graph


@pytest.mark.parametrize("graph", list(GRAPHS))
def test_layout_holds_every_nonzero(graph):
    """Slots cover the nonzeros within the static chunk count, the chunks
    the kernel runs reach every tile, in order, and the slots' values are
    the nonzeros' values."""
    a = GRAPHS[graph]()
    t = _tiles(a)
    n = a.shape[0]
    assert t.slots == n_chunks(n, a.nnz) * CHUNK_SLOTS >= a.nnz
    used = int(t.used)
    assert used <= n_chunks(n, a.nnz)
    tile_of = np.asarray(t.tile_of)
    assert (np.diff(tile_of) >= 0).all()
    assert set(tile_of[:used].tolist()) == set(range(-(-n // TILE_ROWS)))
    assert (np.asarray(t.bhi)[used:] < 0).all()  # nothing past them
    cols = np.asarray(t.cols)
    assert (cols >= 0).sum() == a.nnz
    np.testing.assert_allclose(np.sort(np.asarray(t.vals)[cols >= 0]),
                               np.sort(np.asarray(a.val)))


@pytest.mark.parametrize("m", [1, 1023, 1025, 5000])
def test_running_scans_match_numpy(m):
    from repro.kernels.coo_spmv.ops import _running

    x = np.random.default_rng(m).integers(-50, 50, m).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(_running(jnp.asarray(x), jnp.add)), np.cumsum(x))
    np.testing.assert_array_equal(
        np.asarray(_running(jnp.asarray(x), jnp.maximum)),
        np.maximum.accumulate(x))


def test_layout_builds_under_jit_without_host_transfers():
    a = _long_row()
    n = a.shape[0]
    build = jax.jit(lambda r, c, v: build_tiles(r, c, v, n))
    with jax.transfer_guard("disallow"):
        t = build(a.row, a.col, a.val)
    eager = build_tiles(a.row, a.col, a.val, n)
    for f in ("cols", "vals", "keys", "ends", "tile_of", "blo", "bhi",
              "used"):
        np.testing.assert_array_equal(np.asarray(getattr(t, f)),
                                      np.asarray(getattr(eager, f)))


@pytest.mark.parametrize("graph", ["sbm", "dti_knn"])
def test_lanczos_through_kernel_matches_xla_path(graph):
    a = GRAPHS[graph]()
    cfg = lz.LanczosConfig(k=8, m=24, max_restarts=60, tol=1e-4, which="LA")
    key = jax.random.PRNGKey(0)
    want = lz.eigsh(CooOperator(a), cfg, key=key)
    got = lz.eigsh(TiledCooOperator.build(a, impl="pallas", interpret=True),
                   cfg, key=key)
    assert bool(got.converged) == bool(want.converged)
    np.testing.assert_allclose(np.asarray(got.eigenvalues),
                               np.asarray(want.eigenvalues), atol=1e-4)


def test_dispatch_reads_the_backend_and_n(monkeypatch):
    """The XLA path off a TPU; on one the kernel for n up to MAX_N, and only
    for single-vector Lanczos on one device."""
    assert not kernel_applies(1000)  # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernel_applies(1000) and kernel_applies(MAX_N)
    assert not kernel_applies(MAX_N + 1)
    a = _sbm()
    state = SpectralPipeline(n_clusters=12).prepare(
        COO(a.row, a.col, a.val, a.shape))
    op, notes = SpectralPipeline(n_clusters=12)._operator_with_notes(state)
    assert isinstance(op, TiledCooOperator)
    assert notes == (f"coo_spmv[nnz={a.nnz},slots={op.nnz}]",)
    for eig in (EigConfig(block_size=4), EigConfig(solver="chebyshev")):
        op, notes = SpectralPipeline(
            n_clusters=12, eig=eig)._operator_with_notes(state)
        assert isinstance(op, CooOperator) and notes == ()


def test_stage2_report_records_the_kernel_path(monkeypatch):
    """The note reaches the Stage-2 report, and the run agrees with the
    segment-sum operator's (the layout's jnp reference runs off a TPU)."""
    import repro.core.spectral as spectral

    w, _ = sbm_graph(40, 6, p_in=0.4, p_out=0.01, seed=10)
    pipe = SpectralPipeline(n_clusters=6)
    key = jax.random.PRNGKey(1)
    base = pipe.run_state(w, key)
    monkeypatch.setattr(spectral, "kernel_applies", lambda n: True)
    st = pipe.run_state(w, key)
    embed = [r for r in st.reports if r.stage == "embed"][0]
    slots = n_chunks(w.shape[0], w.nnz) * CHUNK_SLOTS
    assert embed.escalations == (f"coo_spmv[nnz={w.nnz},slots={slots}]",)
    np.testing.assert_allclose(np.asarray(st.result.eigenvalues),
                               np.asarray(base.result.eigenvalues), atol=1e-4)
    assert int(st.result.operator_applications) == int(
        base.result.operator_applications)


# -- row blocks: a chip's own rows of an n-column matrix (DESIGN.md §20) -----

def _row_blocks_graph():
    """n odd and not a multiple of 1024; columns anywhere in five column
    blocks, so that tiles have far sections; the third of four row blocks
    holds no nonzero, and every seventh row elsewhere none either."""
    n = 5001
    rows = -(-n // 4)
    pool = np.setdiff1d(np.arange(n), np.arange(2 * rows, 3 * rows))
    pool = pool[pool % 7 != 0]
    return _random(n, 20000, rows=pool, seed=11), rows


@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("block", range(4))
def test_row_block_product_matches_dense(block, impl):
    """Each of four chips' blocks, the last one past n, times x against the
    dense rows; the empty block gives zeros."""
    a, rows = _row_blocks_graph()
    n = a.shape[0]
    r0 = block * rows
    t = build_tiles(a.row, a.col, a.val, n, r0=r0, rows=rows)
    assert (t.n, t.rows) == (n, rows)
    x = np.random.default_rng(12).normal(size=n).astype(np.float32)
    row, col, val = (np.asarray(v) for v in (a.row, a.col, a.val))
    own = (row >= r0) & (row < r0 + rows)
    want, scale = np.zeros(rows), np.zeros(rows)  # the dense rows, in f64
    np.add.at(want, row[own] - r0, val[own] * x[col[own]].astype(np.float64))
    np.add.at(scale, row[own] - r0, np.abs(val[own] * x[col[own]]))
    got = np.asarray(coo_spmv(t, jnp.asarray(x), impl=impl, interpret=True))
    # only the summation order differs: float32 rounding of |A| |x|
    assert np.all(np.abs(got - want) <= 1e-6 * scale + 1e-7)
    assert int((np.asarray(t.cols) >= 0).sum()) == int(own.sum())
    far = np.abs(col[own] // 1024 - row[own] // TILE_ROWS) > 2
    assert far.any() == (block != 2)  # a far section, but in the empty one
    if block == 2:
        assert not own.any() and not got.any()


def _lattice_knn(side=12, k=16, seed=13):
    """The DTI deployment's graph in numpy: a lattice's exact kNN by
    (distance², id), as (K + Kᵀ) entries sorted by row, random weights."""
    pos = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"),
                   -1).reshape(-1, 3)
    d2 = ((pos[:, None] - pos[None]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.iinfo(np.int64).max)
    nb = np.argsort(d2, axis=1, kind="stable")[:, :k]
    n = pos.shape[0]
    row = np.concatenate([np.repeat(np.arange(n), k), nb.reshape(-1)])
    col = np.concatenate([nb.reshape(-1), np.repeat(np.arange(n), k)])
    return _coo(row, col, n, seed)


def _planted(blocks=100, size=50, seed=14):
    """The Syn200 deployment's shape in numpy: dense blocks and sparse
    edges between them, both directions, sorted by row; n spans five
    column blocks, so most inter-block edges are far."""
    rng = np.random.default_rng(seed)
    n = blocks * size
    r, c = np.triu_indices(size, 1)
    keep = rng.random(r.size) < 0.5
    b = np.repeat(np.arange(blocks), keep.sum()) * size
    ir, ic = np.tile(r[keep], blocks) + b, np.tile(c[keep], blocks) + b
    orow, ocol = rng.integers(0, n, 4000), rng.integers(0, n, 4000)
    row = np.concatenate([ir, ic, orow, ocol])
    col = np.concatenate([ic, ir, ocol, orow])
    return _coo(row, col, n, seed)


# sha256 of the layout's arrays as the square build made them before it
# took blocks of rows (the same graphs)
SQUARE_LAYOUT_SHA256 = {
    "dti_shaped":
        "2f31bfec7e20fbecb588d02f6ba1959a991a58a1321bb36d6ddeaa2e9127850e",
    "syn200_shaped":
        "36a09f3eac5269880c1e66fb48730f32d4f7857c1c108e52ad96670c7641717b",
}
SQUARE_GRAPHS = {"dti_shaped": _lattice_knn, "syn200_shaped": _planted}


def _layout_digest(t) -> str:
    import hashlib

    h = hashlib.sha256()
    for f in ("cols", "vals", "keys", "ends", "tile_of", "blo", "bhi",
              "used"):
        h.update(np.ascontiguousarray(np.asarray(getattr(t, f))).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("graph", list(SQUARE_GRAPHS))
def test_square_layout_is_bit_identical(graph):
    """The whole matrix builds the layout it built before blocks of rows
    existed, and so does a traced first row of 0 (a chip's offset under
    shard_map)."""
    a = SQUARE_GRAPHS[graph]()
    n = a.shape[0]
    t = build_tiles(a.row, a.col, a.val, n)
    assert _layout_digest(t) == SQUARE_LAYOUT_SHA256[graph]
    traced = jax.jit(lambda r0: build_tiles(a.row, a.col, a.val, n, r0=r0,
                                            rows=n))(jnp.int32(0))
    assert _layout_digest(traced) == _layout_digest(t)


def test_sharded_plan_dispatch_takes_row_blocks(monkeypatch):
    """Under a sharded plan with a mesh, the kernel's path on a TPU is the
    row-sharded operator, noted with the chips' balance where the layout is
    concrete; off a TPU, and for block Lanczos or Chebyshev, the
    segment-sum operator as before."""
    from jax.sharding import Mesh

    from repro.core.operator import RowTiledCooOperator
    from repro.core.spectral import Plan

    plan = Plan(device="sharded", mesh=Mesh(np.array(jax.devices()[:1]),
                                            ("data",)))
    a = _sbm()
    n = a.shape[0]
    state = SpectralPipeline(n_clusters=12).prepare(
        COO(a.row, a.col, a.val, a.shape))
    pipe = SpectralPipeline(n_clusters=12, plan=plan)
    op, notes = pipe._operator_with_notes(state)
    assert isinstance(op, CooOperator) and notes == ()  # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    op, notes = pipe._operator_with_notes(state)
    assert isinstance(op, RowTiledCooOperator) and op.shape == (n, n)
    slots = n_chunks(n, a.nnz) * CHUNK_SLOTS
    assert notes == (f"coo_spmv_rows[shards=1,rows={n},nnz={a.nnz},"
                     f"slots={slots},nnz_max={a.nnz},"
                     f"used_max={int(op.tiles.used[0])}]",)
    for eig in (EigConfig(block_size=4), EigConfig(solver="chebyshev")):
        op, notes = SpectralPipeline(
            n_clusters=12, eig=eig, plan=plan)._operator_with_notes(state)
        assert isinstance(op, CooOperator) and notes == ()
