"""Stage 1.5 (graph reduction) invariants + stage-DAG composition.

Property tests for :mod:`repro.core.reduce`:

- sparsify: output symmetric, Laplacian zero row-sum, nnz ratio hit exactly
  (the Gumbel top-m count is static), backbone covers every non-isolated
  vertex, jit-safe and deterministic;
- coarsen: the prolongation is a partition (each fine node → exactly one
  coarse node; columns of P sum to fine cluster sizes), the coarse operator
  is the Galerkin triple product PᵀWP, total edge weight is conserved;
- quality gates: top-k Laplacian eigenvalue drift bounded, end-to-end ARI
  ≥ 0.99× the unreduced pipeline on both reduction paths;
- the stage DAG itself: tuple validation, serialization round-trip,
  provenance/bitwise-default behavior, sharded composition.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.reduce import (
    CoarsenConfig,
    SparsifyConfig,
    coarsen_coo,
    heavy_edge_matching,
    lift_and_smooth,
    sparsify_coo,
    target_upper_count,
    topk_eigenvalue_drift,
)
from repro.core.spectral import (
    DEFAULT_STAGES,
    PipelineState,
    SpectralPipeline,
)
from repro.data.sbm import sbm_graph
from repro.sparse.formats import COO
from tests.test_kernels_lsh_candidates import adjusted_rand_index


def _dense(w: COO) -> np.ndarray:
    a = np.zeros(w.shape, np.float64)
    np.add.at(a, (np.asarray(w.row), np.asarray(w.col)), np.asarray(w.val))
    return a


def _blobs(n_per=100, k=2, d=3, scale=2.0, seed=0):
    rng = np.random.default_rng(seed)
    centers = np.eye(k, d) * scale * 2
    x = np.concatenate(
        [c + rng.normal(0, 0.3, (n_per, d)) for c in centers])
    return jnp.asarray(x.astype(np.float32)), np.repeat(np.arange(k), n_per)


def _sbm_weights(n_per=60, r=4, seed=0) -> COO:
    w, _ = sbm_graph(n_per, r, 0.3, 0.02, seed=seed, weighted=True)
    return w


# ---------------------------------------------------------------------------
# sparsify invariants
# ---------------------------------------------------------------------------

def test_sparsify_preserves_symmetry_and_zero_laplacian_rowsum():
    w = _sbm_weights()
    ws = sparsify_coo(w, SparsifyConfig(target_nnz_ratio=0.5))
    a = _dense(ws)
    np.testing.assert_allclose(a, a.T, rtol=0, atol=0)  # exactly symmetric
    # L = D − W has zero row sums by the degree definition — the invariant
    # downstream normalization (v0 = √deg) relies on
    deg = a.sum(1)
    lap_rowsum = deg - a.sum(1)
    np.testing.assert_allclose(lap_rowsum, 0.0, atol=0)
    assert (a >= 0).all()


def test_sparsify_hits_requested_nnz_ratio():
    w = _sbm_weights()
    for ratio in (0.2, 0.4, 0.7):
        ws = sparsify_coo(w, SparsifyConfig(target_nnz_ratio=ratio))
        # static output size: exactly 2·target_upper_count entries
        assert ws.nnz == 2 * target_upper_count(w.nnz, ratio)
        achieved = ws.nnz / w.nnz
        assert abs(achieved - ratio) <= 2.0 / w.nnz + 1e-9, (achieved, ratio)


def test_sparsify_backbone_covers_every_nonisolated_vertex():
    w = _sbm_weights()
    ws = sparsify_coo(w, SparsifyConfig(target_nnz_ratio=0.2))
    deg_before = _dense(w).sum(1)
    deg_after = _dense(ws).sum(1)
    # every vertex with an edge keeps its heaviest incident edge (π = 1)
    assert (deg_after[deg_before > 0] > 0).all()


def test_sparsify_backbone_weights_exact():
    w = _sbm_weights()
    ws = sparsify_coo(w, SparsifyConfig(target_nnz_ratio=0.3))
    a, s = _dense(w), _dense(ws)
    # the per-row heaviest edge survives with its original weight (no
    # Horvitz–Thompson inflation on the backbone)
    for u in range(a.shape[0]):
        if a[u].max() <= 0:
            continue
        v = int(a[u].argmax())
        assert s[u, v] > 0
        np.testing.assert_allclose(s[u, v], a[u, v], rtol=1e-5)


def test_sparsify_is_jit_safe_and_deterministic():
    w = _sbm_weights()
    cfg = SparsifyConfig(target_nnz_ratio=0.4, seed=3)
    eager = sparsify_coo(w, cfg)
    jitted = jax.jit(lambda m: sparsify_coo(m, cfg))(w)
    np.testing.assert_array_equal(np.asarray(eager.row), np.asarray(jitted.row))
    np.testing.assert_array_equal(np.asarray(eager.col), np.asarray(jitted.col))
    np.testing.assert_allclose(np.asarray(eager.val), np.asarray(jitted.val),
                               rtol=1e-6)


def test_sparsify_eigenvalue_drift_bounded():
    w = _sbm_weights(n_per=50, r=3)
    ws = sparsify_coo(w, SparsifyConfig(target_nnz_ratio=0.5))

    def lap_eigs(m, k):
        a = _dense(m)
        d = a.sum(1)
        isd = np.where(d > 0, 1.0 / np.sqrt(np.maximum(d, 1e-30)), 0.0)
        lsym = np.eye(a.shape[0]) - isd[:, None] * a * isd[None, :]
        return np.linalg.eigvalsh(lsym)[:k]

    k = 3
    drift = topk_eigenvalue_drift(lap_eigs(w, k), lap_eigs(ws, k), k)
    # half the edges dropped, spectrum of the k smallest Laplacian
    # eigenvalues moves by at most a modest fraction of its scale
    assert drift < 0.35, drift


# ---------------------------------------------------------------------------
# coarsen invariants
# ---------------------------------------------------------------------------

def test_heavy_edge_matching_is_mutual_involution():
    w = _sbm_weights()
    n = w.shape[0]
    match = np.asarray(heavy_edge_matching(w.row, w.col, w.val, n))
    assert match.shape == (n,)
    # involution: partner's partner is you (unmatched nodes are fixpoints)
    np.testing.assert_array_equal(match[match], np.arange(n))
    assert (match != np.arange(n)).sum() > 0  # something actually matched


def test_coarsen_prolongation_is_partition():
    w = _sbm_weights()
    n = w.shape[0]
    wc, prolong = coarsen_coo(w, CoarsenConfig(levels=2, min_nodes=8))
    nc = wc.shape[0]
    # each fine node maps to exactly one coarse node, every coarse id hit
    assert prolong.shape == (n,)
    assert prolong.min() == 0 and prolong.max() == nc - 1
    assert np.unique(prolong).size == nc
    # columns of the partition prolongation P sum to fine cluster sizes
    sizes = np.bincount(prolong, minlength=nc)
    p = np.zeros((n, nc))
    p[np.arange(n), prolong] = 1.0
    np.testing.assert_array_equal(p.sum(0), sizes)
    np.testing.assert_array_equal(p.sum(1), np.ones(n))  # exactly one 1/row
    assert nc < n  # it actually coarsened


def test_coarsen_is_galerkin_triple_product():
    w = _sbm_weights(n_per=40, r=3)
    wc, prolong = coarsen_coo(w, CoarsenConfig(levels=1, min_nodes=8))
    nc = wc.shape[0]
    p = np.zeros((w.shape[0], nc))
    p[np.arange(w.shape[0]), prolong] = 1.0
    np.testing.assert_allclose(_dense(wc), p.T @ _dense(w) @ p,
                               rtol=1e-5, atol=1e-8)
    # total edge weight (incl. the intra-pair self-loops) is conserved
    np.testing.assert_allclose(_dense(wc).sum(), _dense(w).sum(), rtol=1e-6)


def test_coarsen_raises_actionable_under_jit():
    w = _sbm_weights(n_per=20, r=2)
    with pytest.raises(TypeError, match="host-side"):
        jax.jit(lambda m: coarsen_coo(m, CoarsenConfig())[0].val)(w)


def test_lift_and_smooth_returns_orthonormal_ritz_basis():
    from repro.core.operator import CooOperator
    from repro.sparse.ops import normalize_sym

    w = _sbm_weights(n_per=40, r=3)
    op = CooOperator(normalize_sym(w))
    u0 = jax.random.normal(jax.random.PRNGKey(0), (w.shape[0], 4))
    u, theta, resid = lift_and_smooth(op, u0, steps=2)
    np.testing.assert_allclose(np.asarray(u.T @ u), np.eye(4),
                               rtol=0, atol=1e-4)
    th = np.asarray(theta)
    assert (np.diff(th) <= 1e-6).all()  # descending Ritz values
    assert np.asarray(resid).shape == (4,)


# ---------------------------------------------------------------------------
# end-to-end quality gates (the ARI ≥ 0.99× contract)
# ---------------------------------------------------------------------------

def test_sparsify_pipeline_ari_gate():
    x, truth = _blobs(n_per=100)
    key = jax.random.PRNGKey(0)
    ref = SpectralPipeline(n_clusters=2).run(x, key)
    red = SpectralPipeline(
        n_clusters=2, stages=("prepare", "sparsify", "embed", "cluster"),
        sparsify=SparsifyConfig(target_nnz_ratio=0.4)).run(x, key)
    ari_ref = adjusted_rand_index(np.asarray(ref.labels), truth)
    ari_red = adjusted_rand_index(np.asarray(red.labels), truth)
    assert ari_red >= 0.99 * ari_ref, (ari_red, ari_ref)


def test_coarsen_refine_pipeline_ari_gate_and_node_reduction():
    x, truth = _blobs(n_per=100)
    key = jax.random.PRNGKey(0)
    pipe = SpectralPipeline(
        n_clusters=2,
        stages=("prepare", "coarsen", "embed", "refine", "cluster"),
        coarsen=CoarsenConfig(levels=2, min_nodes=16))
    st = PipelineState(points=x)
    _, ke, kk = jax.random.split(key, 3)
    st = dataclasses.replace(st, key_embed=ke, key_cluster=kk)
    fin = pipe.run_stages(st)
    ref = SpectralPipeline(n_clusters=2).run(x, key)
    ari_ref = adjusted_rand_index(np.asarray(ref.labels), truth)
    ari_red = adjusted_rand_index(np.asarray(fin.result.labels), truth)
    assert ari_red >= 0.99 * ari_ref, (ari_red, ari_ref)
    info = fin.reductions[-1]
    assert info.n_before >= 2 * info.n_after  # ≥ 2× node reduction
    # labels are fine-sized again after refine
    assert fin.result.labels.shape[0] == x.shape[0]


def test_reduction_stages_compose_with_sharded_plan():
    from repro.sparse.distributed import partition_coo_by_rows
    from repro.core.similarity import build_knn_graph

    x, truth = _blobs(n_per=64)
    n = x.shape[0]
    sm = partition_coo_by_rows(build_knn_graph(x, 10, sigma=2.0), 4)
    key = jax.random.PRNGKey(0)
    out_s = SpectralPipeline(
        n_clusters=2, stages=("prepare", "sparsify", "embed", "cluster"),
        sparsify=SparsifyConfig(target_nnz_ratio=0.5)).run(sm, key)
    out_c = SpectralPipeline(
        n_clusters=2,
        stages=("prepare", "coarsen", "embed", "refine", "cluster"),
        coarsen=CoarsenConfig(levels=1, min_nodes=16)).run(sm, key)
    for out in (out_s, out_c):
        ari = adjusted_rand_index(np.asarray(out.labels)[:n], truth)
        assert ari > 0.95, ari


# ---------------------------------------------------------------------------
# the stage DAG contract
# ---------------------------------------------------------------------------

def test_stage_tuple_validation():
    with pytest.raises(ValueError, match="unknown stage"):
        SpectralPipeline(n_clusters=2, stages=("prepare", "frobnicate",
                                               "embed", "cluster"))
    with pytest.raises(ValueError, match="canonical order"):
        SpectralPipeline(n_clusters=2, stages=("prepare", "embed",
                                               "sparsify", "cluster"))
    with pytest.raises(ValueError, match="must include"):
        SpectralPipeline(n_clusters=2, stages=("prepare", "cluster"))
    with pytest.raises(ValueError, match="duplicates"):
        SpectralPipeline(n_clusters=2, stages=("prepare", "embed", "embed",
                                               "cluster"))
    with pytest.raises(ValueError, match="paired"):
        SpectralPipeline(n_clusters=2, stages=("prepare", "coarsen", "embed",
                                               "cluster"))
    with pytest.raises(ValueError, match="paired"):
        SpectralPipeline(n_clusters=2, stages=("prepare", "embed", "refine",
                                               "cluster"))


def test_operator_override_rejected_with_reduction_stages():
    from repro.core.operator import CallableOperator

    w = _sbm_weights(n_per=20, r=2)
    pipe = SpectralPipeline(n_clusters=2,
                            stages=("prepare", "sparsify", "embed", "cluster"))
    op = CallableOperator(n=w.shape[0], matvec=lambda v: v)
    with pytest.raises(ValueError, match="reduction stage"):
        pipe.run(w, jax.random.PRNGKey(0), operator=op)


def test_stages_round_trip_through_json():
    import json

    pipe = SpectralPipeline(
        n_clusters=4, stages=("prepare", "sparsify", "embed", "cluster"),
        sparsify=SparsifyConfig(target_nnz_ratio=0.3, seed=7),
        coarsen=CoarsenConfig(levels=2, refine_steps=3))
    blob = json.dumps(pipe.to_dict())
    back = SpectralPipeline.from_dict(json.loads(blob))
    assert back == pipe
    # pre-DAG blobs (no stage keys) default to the classic three stages
    legacy = {"n_clusters": 2}
    assert SpectralPipeline.from_dict(legacy).stages == DEFAULT_STAGES


def test_default_stages_bitwise_identical_to_staged_calls():
    x, _ = _blobs(n_per=50)
    key = jax.random.PRNGKey(42)
    pipe = SpectralPipeline(n_clusters=2)
    out = pipe.run(x, key)
    # the pre-DAG call sequence, spelled out
    g = pipe.build_graph(x)
    _, ke, kk = jax.random.split(key, 3)
    emb = pipe.embed(g, ke)
    ref = pipe.cluster(emb, kk)
    np.testing.assert_array_equal(np.asarray(out.labels),
                                  np.asarray(ref.labels))
    np.testing.assert_array_equal(np.asarray(out.embedding),
                                  np.asarray(ref.embedding))


def test_run_stages_records_provenance():
    x, _ = _blobs(n_per=50)
    pipe = SpectralPipeline(
        n_clusters=2,
        stages=("prepare", "sparsify", "embed", "cluster"),
        sparsify=SparsifyConfig(target_nnz_ratio=0.5))
    _, ke, kk = jax.random.split(jax.random.PRNGKey(0), 3)
    st = PipelineState(points=x, key_embed=ke, key_cluster=kk)
    fin = pipe.run_stages(st)
    assert fin.provenance[0] == "prepare"
    assert fin.provenance[1].startswith("sparsify[nnz ")
    assert fin.provenance[2:] == ("embed", "cluster")
    assert len(fin.reductions) == 1 and fin.reductions[0].kind == "sparsify"
    assert fin.result is not None


# ---------------------------------------------------------------------------
# unified stream accounting (satellite: operator_passes/streams fold)
# ---------------------------------------------------------------------------

def test_solver_streams_unifies_both_engines():
    from repro.core.chebyshev import ChebConfig, operator_streams
    from repro.core.lanczos import (LanczosConfig, operator_passes,
                                    solver_streams, streamed_nnz)
    from repro.core.operator import CooOperator

    lcfg = LanczosConfig(k=4, m=16)
    ccfg = ChebConfig(k=4, degree=32)
    assert solver_streams(lcfg, 3) == operator_passes(lcfg, 3)
    assert solver_streams(ccfg) == operator_streams(ccfg)
    with pytest.raises(ValueError, match="restart count"):
        solver_streams(lcfg)
    with pytest.raises(TypeError, match="LanczosConfig or ChebConfig"):
        solver_streams(object())

    w = _sbm_weights(n_per=20, r=2)
    op = CooOperator(w)
    assert op.nnz == w.nnz
    assert streamed_nnz(op, ccfg) == operator_streams(ccfg) * w.nnz
    from repro.core.operator import CallableOperator
    with pytest.raises(TypeError, match="no nnz"):
        streamed_nnz(CallableOperator(n=4, matvec=lambda v: v), ccfg)
