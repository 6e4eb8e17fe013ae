"""The unified stage-graph API (SpectralPipeline + Plan + LinearOperator).

Covers the redesign's contracts:
* the four deprecated entry points are bitwise-identical shims over the new
  pipeline (fixed seed, per scenario);
* stages are independently runnable/resumable — re-clustering a cached
  embedding never re-enters the eigensolver;
* nested configs validate their string enums at construction and round-trip
  through JSON (serve/dry-run reproducibility);
* the drop_first path is exercised end-to-end (embedding width + eigenvalue
  bookkeeping).
"""
import json
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core.spectral as spectral
from repro.core.kmeans import KMeansConfig
from repro.core.pipeline import (
    SpectralClusteringConfig,
    spectral_cluster,
    spectral_cluster_from_points,
)
from repro.core.spectral import (
    EigConfig,
    GraphConfig,
    Plan,
    SpectralPipeline,
)
from repro.data.sbm import sbm_graph


def _blobs(k, n_per, d, spread=1.0, seed=0):
    rng = np.random.default_rng(seed)
    centers = (rng.permutation(np.eye(k, d)) * 20.0).astype(np.float32)
    x = np.concatenate([c + spread * rng.normal(size=(n_per, d)) for c in centers])
    return x.astype(np.float32), np.repeat(np.arange(k), n_per)


def _one_device_mesh():
    return jax.make_mesh((1,), ("data",))


# ---------------------------------------------------------------------------
# Deprecated shims: bitwise-identical labels, one test per old entry point
# ---------------------------------------------------------------------------

def test_shim_spectral_cluster_bitwise_identical():
    coo, _ = sbm_graph(80, 4, 0.3, 0.01, seed=13)
    cfg = SpectralClusteringConfig(n_clusters=4)
    with pytest.warns(DeprecationWarning, match="spectral_cluster"):
        old = spectral_cluster(coo, cfg, jax.random.PRNGKey(0))
    new = cfg.to_pipeline().run(coo, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(old.labels), np.asarray(new.labels))
    np.testing.assert_array_equal(np.asarray(old.eigenvalues),
                                  np.asarray(new.eigenvalues))
    np.testing.assert_array_equal(np.asarray(old.embedding),
                                  np.asarray(new.embedding))


def test_shim_spectral_cluster_from_points_bitwise_identical():
    x, _ = _blobs(3, 50, 6, seed=7)
    cfg = SpectralClusteringConfig(n_clusters=3, lanczos_block_size=3)
    with pytest.warns(DeprecationWarning, match="from_points"):
        old = spectral_cluster_from_points(
            jnp.asarray(x), cfg, jax.random.PRNGKey(0), knn_k=8, sigma=2.0)
    pipe = cfg.to_pipeline(graph=GraphConfig(knn_k=8, sigma=2.0))
    new = pipe.run(jnp.asarray(x), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(old.labels), np.asarray(new.labels))
    np.testing.assert_array_equal(np.asarray(old.eigenvalues),
                                  np.asarray(new.eigenvalues))


@pytest.mark.parametrize("variant", ["gspmd", "shard_map"])
def test_shim_spectral_cluster_sharded_bitwise_identical(variant):
    from repro.core.distributed_pipeline import spectral_cluster_sharded
    from repro.sparse.distributed import partition_coo_by_rows

    coo, _ = sbm_graph(60, 4, 0.3, 0.01, seed=21)
    cfg = SpectralClusteringConfig(n_clusters=4, kmeans_assign="ref")
    # shard count must match the mesh axis the shard_map engine runs over
    # (1 in-process device); the gspmd engine takes any bucketing
    sm = partition_coo_by_rows(coo, 1 if variant == "shard_map" else 4)
    mesh = _one_device_mesh() if variant == "shard_map" else None
    with pytest.warns(DeprecationWarning, match="sharded"):
        old = spectral_cluster_sharded(
            sm, cfg, jax.random.PRNGKey(0), variant=variant, mesh=mesh)
    plan = Plan(device="sharded", variant=variant, mesh=mesh)
    new = cfg.to_pipeline(plan=plan).run(sm, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(old.labels), np.asarray(new.labels))
    np.testing.assert_array_equal(np.asarray(old.eigenvalues),
                                  np.asarray(new.eigenvalues))


def test_shim_spectral_cluster_from_points_sharded_bitwise_identical():
    from repro.core.distributed_pipeline import spectral_cluster_from_points_sharded

    x, _ = _blobs(4, 32, 8, seed=3)
    mesh = _one_device_mesh()
    cfg = SpectralClusteringConfig(n_clusters=4, lanczos_block_size=4,
                                   kmeans_assign="ref")
    with pytest.warns(DeprecationWarning, match="sharded"):
        old = spectral_cluster_from_points_sharded(
            jnp.asarray(x), cfg, jax.random.PRNGKey(0), mesh=mesh, knn_k=8,
            sigma=2.0)
    pipe = cfg.to_pipeline(graph=GraphConfig(knn_k=8, sigma=2.0),
                           plan=Plan(device="sharded", mesh=mesh))
    new = pipe.run(jnp.asarray(x), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(old.labels), np.asarray(new.labels))


# ---------------------------------------------------------------------------
# Stage resumability
# ---------------------------------------------------------------------------

def test_recluster_cached_embedding_skips_eigensolver(monkeypatch):
    """Stage 3 at a second k must not re-enter Stage 2: after embed(), the
    eigensolver is poisoned and cluster() still succeeds; the restart
    counter is carried from the cached EmbedState, not recomputed."""
    coo, _ = sbm_graph(80, 4, 0.3, 0.01, seed=5)
    pipe = SpectralPipeline(n_clusters=4)
    state = pipe.prepare(coo)
    key, k_eig, k_km = jax.random.split(jax.random.PRNGKey(0), 3)
    emb = pipe.embed(state, k_eig)

    def _boom(*a, **kw):  # pragma: no cover - must never run
        raise AssertionError("cluster() re-entered the eigensolver")

    monkeypatch.setattr(spectral.lz, "eigsh", _boom)
    out8 = pipe.cluster(emb, k_km, n_clusters=8)
    assert np.asarray(out8.labels).shape == (coo.shape[0],)
    assert np.asarray(out8.labels).max() < 8
    # restart bookkeeping rides the cached state
    assert int(out8.lanczos_restarts) == int(emb.restarts)
    # and the embedding served both granularities unchanged
    out4 = pipe.cluster(emb, k_km)
    np.testing.assert_array_equal(np.asarray(out4.embedding),
                                  np.asarray(out8.embedding))


def test_staged_run_matches_fused_run():
    """prepare → embed → cluster with run()'s key split == run()."""
    coo, _ = sbm_graph(60, 4, 0.3, 0.01, seed=9)
    pipe = SpectralPipeline(n_clusters=4)
    fused = pipe.run(coo, jax.random.PRNGKey(0))
    _, k_eig, k_km = jax.random.split(jax.random.PRNGKey(0), 3)
    staged = pipe.cluster(pipe.embed(pipe.prepare(coo), k_eig), k_km)
    np.testing.assert_array_equal(np.asarray(fused.labels),
                                  np.asarray(staged.labels))


# ---------------------------------------------------------------------------
# drop_first end-to-end
# ---------------------------------------------------------------------------

def test_drop_first_embedding_width_and_eigenvalues():
    coo, truth = sbm_graph(100, 4, 0.3, 0.01, seed=4)
    base = SpectralPipeline(n_clusters=4)
    drop = SpectralPipeline(n_clusters=4, eig=EigConfig(drop_first=True))
    out_b = base.run(coo, jax.random.PRNGKey(0))
    out_d = drop.run(coo, jax.random.PRNGKey(0))
    # same embedding width (k columns), but the trivial pair is gone: the
    # base embedding leads with λ≈0 while drop_first starts one pair later
    assert np.asarray(out_d.embedding).shape == np.asarray(out_b.embedding).shape
    assert np.asarray(out_d.eigenvalues).shape == (4,)
    ev_b = np.asarray(out_b.eigenvalues)
    ev_d = np.asarray(out_d.eigenvalues)
    assert ev_b[0] < 1e-3
    np.testing.assert_allclose(ev_d[:3], ev_b[1:4], atol=1e-3)
    # labels remain a valid 4-way clustering of all rows
    labels = np.asarray(out_d.labels)
    assert labels.shape == (coo.shape[0],)
    assert set(np.unique(labels)) <= set(range(4))


def test_drop_first_through_deprecated_shim_matches_pipeline():
    coo, _ = sbm_graph(80, 4, 0.3, 0.01, seed=6)
    cfg = SpectralClusteringConfig(n_clusters=4, drop_first=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old = spectral_cluster(coo, cfg, jax.random.PRNGKey(0))
    new = cfg.to_pipeline().run(coo, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(old.labels), np.asarray(new.labels))
    np.testing.assert_array_equal(np.asarray(old.eigenvalues),
                                  np.asarray(new.eigenvalues))


# ---------------------------------------------------------------------------
# Config serialization round-trip
# ---------------------------------------------------------------------------

def test_config_json_round_trip():
    pipe = SpectralPipeline(
        n_clusters=12,
        graph=GraphConfig(knn_k=16, measure="cross_correlation", sigma=2.5,
                          eps=1.75, impl="ref"),
        eig=EigConfig(n_eigvecs=10, basis_m=48, tol=1e-4, max_restarts=17,
                      block_size=4, drop_first=True, fixed_restarts=2),
        kmeans=KMeansConfig(max_iters=33, iter="two_pass", update="segment",
                            assign="ref", fixed_iters=3),
        plan=Plan(device="sharded", axis=("data",), variant="shard_map",
                  gather_dtype="bfloat16", mesh=_one_device_mesh()),
    )
    blob = json.dumps(pipe.to_dict())  # must be JSON-safe
    back = SpectralPipeline.from_dict(json.loads(blob))
    # the mesh is a runtime resource: everything else must round-trip equal
    import dataclasses

    assert back == dataclasses.replace(pipe, plan=dataclasses.replace(
        pipe.plan, mesh=None))
    # and reattaching the mesh restores full equality
    back2 = SpectralPipeline.from_dict(json.loads(blob), mesh=pipe.plan.mesh)
    assert back2 == pipe


def test_config_round_trip_defaults():
    pipe = SpectralPipeline(n_clusters=3)
    assert SpectralPipeline.from_dict(json.loads(json.dumps(pipe.to_dict()))) == pipe


def test_graph_config_lsh_fields_round_trip_and_validate():
    """The ANN Stage-1 knobs: JSON round-trip + enum/range validation."""
    cfg = GraphConfig(method="lsh", n_tables=8, n_bits=20, candidates=256,
                      lsh_seed=7)
    back = GraphConfig(**json.loads(json.dumps(cfg.to_dict())))
    assert back == cfg
    pipe = SpectralPipeline(n_clusters=4, graph=cfg)
    assert SpectralPipeline.from_dict(json.loads(json.dumps(pipe.to_dict()))) == pipe
    with pytest.raises(ValueError, match="method"):
        GraphConfig(method="annoy")
    with pytest.raises(ValueError, match="n_tables"):
        GraphConfig(n_tables=0)
    with pytest.raises(ValueError, match="n_bits"):
        GraphConfig(n_bits=25)  # codes must stay fp32-exact int32
    with pytest.raises(ValueError, match="candidates"):
        GraphConfig(n_tables=16, candidates=8)  # < one slot per table


def test_array_eps_rejected_by_to_dict():
    cfg = GraphConfig(eps=jnp.full((5,), 0.5))  # valid at runtime...
    with pytest.raises(ValueError, match="not JSON-serializable"):
        cfg.to_dict()  # ...but not serializable
    assert GraphConfig(eps=1.5).to_dict()["eps"] == 1.5


def test_run_rejects_points_with_prebuilt_graph():
    coo, _ = sbm_graph(30, 2, 0.3, 0.05, seed=2)
    pipe = SpectralPipeline(n_clusters=2)
    with pytest.raises(ValueError, match="points"):
        pipe.run(coo, jax.random.PRNGKey(0), points=jnp.zeros((60, 3)))


# ---------------------------------------------------------------------------
# Enum validation at construction
# ---------------------------------------------------------------------------

def test_graph_config_rejects_unknown_measure_and_impl():
    with pytest.raises(ValueError, match="measure"):
        GraphConfig(measure="euclidean")
    with pytest.raises(ValueError, match="impl"):
        GraphConfig(impl="cuda")
    with pytest.raises(ValueError, match="knn_k"):
        GraphConfig(knn_k=0)


def test_plan_rejects_unknown_device_and_variant():
    with pytest.raises(ValueError, match="device"):
        Plan(device="tpu")
    with pytest.raises(ValueError, match="variant"):
        Plan(variant="pmap")
    # shard_map without a mesh constructs (plans must deserialize mesh-free)
    # but fails loudly at operator-dispatch time
    from repro.sparse.distributed import partition_coo_by_rows
    from repro.data.sbm import sbm_graph

    coo, _ = sbm_graph(30, 2, 0.3, 0.05, seed=1)
    sm = partition_coo_by_rows(coo, 1)
    pipe = SpectralPipeline(
        n_clusters=2, plan=Plan(device="sharded", variant="shard_map"))
    with pytest.raises(ValueError, match="mesh"):
        pipe.run(sm, jax.random.PRNGKey(0))


def test_kmeans_config_rejects_unknown_update_and_assign():
    with pytest.raises(ValueError, match="update"):
        KMeansConfig(k=3, update="sort")
    with pytest.raises(ValueError, match="assign"):
        KMeansConfig(k=3, assign="brute")


def test_eig_config_rejects_bad_block_size_and_tol():
    with pytest.raises(ValueError, match="block_size"):
        EigConfig(block_size=0)
    with pytest.raises(ValueError, match="tol"):
        EigConfig(tol=0.0)


def test_pipeline_rejects_conflicting_kmeans_k():
    with pytest.raises(ValueError, match="conflicts"):
        SpectralPipeline(n_clusters=4, kmeans=KMeansConfig(k=5))
    # matching k is fine
    SpectralPipeline(n_clusters=4, kmeans=KMeansConfig(k=4))


def test_standalone_kmeans_requires_k():
    from repro.core.kmeans import kmeans

    with pytest.raises(ValueError, match="k is unset"):
        kmeans(jnp.zeros((8, 2)), KMeansConfig(), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# BlockELL operator fast path (EigConfig.representation="blockell")
# ---------------------------------------------------------------------------

def test_blockell_representation_selects_blockell_operator():
    from repro.core.operator import BlockEllOperator, CooOperator

    coo, _ = sbm_graph(40, 3, 0.3, 0.03, seed=4)
    pipe = SpectralPipeline(
        n_clusters=3, eig=EigConfig(representation="blockell"))
    state = pipe.prepare(coo)
    assert isinstance(pipe.operator(state), BlockEllOperator)
    # default stays COO
    base = SpectralPipeline(n_clusters=3)
    assert isinstance(base.operator(state), CooOperator)


@pytest.mark.parametrize("solver", ["lanczos", "chebyshev"])
def test_blockell_embedding_matches_coo(solver):
    """Same graph, same key: the BlockELL fast path reproduces the COO
    operator's labels for both solvers (the operator is mathematically the
    same matrix; eigenvalues agree to fp tolerance)."""
    coo, _ = sbm_graph(50, 3, 0.3, 0.03, seed=5)
    a = SpectralPipeline(n_clusters=3, eig=EigConfig(solver=solver))
    b = SpectralPipeline(
        n_clusters=3, eig=EigConfig(solver=solver, representation="blockell"))
    ra = a.run(coo, jax.random.PRNGKey(0))
    rb = b.run(coo, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(ra.eigenvalues),
                               np.asarray(rb.eigenvalues), atol=1e-4)
    assert (np.asarray(ra.labels) == np.asarray(rb.labels)).mean() > 0.99


def test_blockell_under_jit_falls_back_with_warning():
    """csr_to_blockell is host-side numpy: a traced GraphState cannot convert
    — the pipeline warns and keeps the COO operator instead of crashing."""
    coo, _ = sbm_graph(40, 2, 0.3, 0.03, seed=6)
    pipe = SpectralPipeline(
        n_clusters=2, eig=EigConfig(representation="blockell"))
    state = pipe.prepare(coo)

    with pytest.warns(RuntimeWarning, match="blockell"):
        out = jax.jit(lambda s, k: pipe.embed(s, k).embedding)(
            state, jax.random.PRNGKey(0))
    assert out.shape[1] == 2
