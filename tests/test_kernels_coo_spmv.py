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
