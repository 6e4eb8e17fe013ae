"""Multi-device semantics (8 virtual CPU devices via a subprocess — the
main test process must keep the default single device)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=480,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


def test_sharded_spmv_matches_dense():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.sparse.formats import coo_from_edges
        from repro.sparse.distributed import (partition_coo_by_rows,
            make_sharded_spmv, shard_edges, shard_vector, spmv_gspmd)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        n = 64
        W = (rng.random((n,n)) < 0.2) * rng.random((n,n)).astype(np.float32)
        r, c = np.nonzero(W)
        coo = coo_from_edges(r, c, W[r,c], (n,n))
        sm = partition_coo_by_rows(coo, 4)
        sm = shard_edges(mesh, sm, "data")
        x = rng.normal(size=(sm.shape[0],)).astype(np.float32)
        xs = shard_vector(mesh, jnp.asarray(x), "data")
        spmv = make_sharded_spmv(mesh, sm, axis="data")
        y = jax.jit(spmv)(sm.row_local, sm.col, sm.val, xs)
        np.testing.assert_allclose(np.asarray(y)[:n], W @ x[:n], rtol=1e-4, atol=1e-5)
        yg = jax.jit(lambda s, v: spmv_gspmd(s, v))(sm, xs)
        np.testing.assert_allclose(np.asarray(yg)[:n], W @ x[:n], rtol=1e-4, atol=1e-5)
        print("SPMV-OK")
    """))


def test_distributed_spectral_pipeline_recovers_sbm():
    print(_run("""
        import numpy as np, jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.data.sbm import sbm_graph
        from repro.sparse.distributed import partition_coo_by_rows, shard_edges
        from repro.core.pipeline import SpectralClusteringConfig
        from repro.core.distributed_pipeline import spectral_cluster_sharded
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        coo, truth = sbm_graph(64, 4, 0.35, 0.01, seed=5)
        sm = shard_edges(mesh, partition_coo_by_rows(coo, 4), "data")
        cfg = SpectralClusteringConfig(n_clusters=4, kmeans_assign="ref")
        for variant in ("gspmd", "shard_map"):
            out = jax.jit(lambda s, k: spectral_cluster_sharded(
                s, cfg, k, variant=variant, mesh=mesh, axis=("data",)))(
                sm, jax.random.PRNGKey(0))
            lab = np.asarray(out.labels)[:256]
            # purity
            pur = 0
            for c in np.unique(lab):
                vals, counts = np.unique(truth[lab==c], return_counts=True)
                pur += counts.max()
            assert pur / 256 > 0.95, (variant, pur / 256)
        print("PIPELINE-OK")
    """))


def test_sharded_points_stage1_matches_single_device():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed_pipeline import (
            make_knn_rowblock, spectral_cluster_from_points_sharded)
        from repro.core.pipeline import SpectralClusteringConfig
        from repro.core.similarity import build_knn_graph
        from repro.kernels.knn_topk.ops import knn_topk
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        k_blobs, n_per, d, k = 4, 64, 8, 8
        centers = (rng.permutation(np.eye(k_blobs, d)) * 20.0).astype(np.float32)
        x = np.concatenate([c + rng.normal(size=(n_per, d)) for c in centers]).astype(np.float32)
        truth = np.repeat(np.arange(k_blobs), n_per)
        xj = jnp.asarray(x)
        # row-block kNN == single-device kNN
        d_sh, i_sh = jax.jit(make_knn_rowblock(mesh, k, axis="data"))(xj)
        d_1, i_1 = knn_topk(xj, k, impl="ref")
        np.testing.assert_allclose(np.asarray(d_sh), np.asarray(d_1), rtol=1e-4, atol=1e-4)
        # end-to-end sharded points pipeline recovers the blobs
        cfg = SpectralClusteringConfig(n_clusters=4, lanczos_block_size=4,
                                       kmeans_assign="ref")
        out = jax.jit(lambda xx, key: spectral_cluster_from_points_sharded(
            xx, cfg, key, mesh=mesh, knn_k=k, sigma=2.0))(xj, jax.random.PRNGKey(0))
        lab = np.asarray(out.labels)
        pur = 0
        for c in np.unique(lab):
            vals, counts = np.unique(truth[lab == c], return_counts=True)
            pur += counts.max()
        assert pur / len(truth) > 0.95, pur / len(truth)
        print("POINTS-STAGE1-OK")
    """))


def test_sharded_stage1_separate_points_matches_single_device():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.spectral import GraphConfig, Plan, SpectralPipeline
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        n, d = 256, 12
        pos = rng.normal(size=(n, 3)).astype(np.float32)   # search space
        prof = rng.normal(size=(n, d)).astype(np.float32)  # feature space
        g = GraphConfig(knn_k=6, measure="cross_correlation")
        key = jax.random.PRNGKey(0)
        single = SpectralPipeline(n_clusters=4, graph=g)
        sharded = SpectralPipeline(n_clusters=4, graph=g,
                                   plan=Plan(device="sharded", mesh=mesh))
        out1 = single.run(jnp.asarray(prof), key, points=jnp.asarray(pos))
        out2 = sharded.run(jnp.asarray(prof), key, points=jnp.asarray(pos))
        np.testing.assert_array_equal(np.asarray(out1.labels),
                                      np.asarray(out2.labels))
        np.testing.assert_allclose(np.asarray(out1.eigenvalues),
                                   np.asarray(out2.eigenvalues),
                                   rtol=1e-5, atol=1e-6)
        print("POINTS-SEPARATE-OK")
    """))


def test_sharded_stage1_lsh_matches_single_device():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed_pipeline import make_knn_rowblock
        from repro.kernels.knn_topk.ops import knn_topk_rerank
        from repro.kernels.lsh_candidates.ops import (default_candidates,
            lsh_candidates)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(1)
        n, d, k = 256, 8, 6
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        # per-shard hash tables over the gathered pool == single-device tables
        d_sh, i_sh = jax.jit(make_knn_rowblock(mesh, k, method="lsh"))(x)
        cand = lsh_candidates(x, m=default_candidates(k))
        d_1, i_1 = knn_topk_rerank(x, cand, k)
        np.testing.assert_allclose(np.asarray(d_sh), np.asarray(d_1),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(i_sh), np.asarray(i_1))
        print("LSH-ROWBLOCK-OK")
    """))


def test_sharded_kmeans_matches_single_device_and_one_allreduce_per_iter():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.kmeans import KMeansConfig, kmeans
        from repro.core.distributed_pipeline import kmeans_sharded
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(256, 6)), jnp.float32)
        cfg = KMeansConfig(k=5, max_iters=30)
        key = jax.random.PRNGKey(0)
        r1 = jax.jit(lambda x, k: kmeans(x, cfg, k))(x, key)
        r2 = jax.jit(lambda x, k: kmeans_sharded(x, cfg, k, mesh=mesh, axis="data"))(x, key)
        # Stage-3 equivalence: identical trajectory, shard count invisible
        np.testing.assert_array_equal(np.asarray(r1.labels), np.asarray(r2.labels))
        np.testing.assert_allclose(np.asarray(r1.centroids), np.asarray(r2.centroids),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(r1.inertia), float(r2.inertia), rtol=1e-5)
        assert int(r1.iterations) == int(r2.iterations)
        # exactly ONE psum (the packed [k, d+2] partial-stats block) inside
        # the Lloyd loop body — the design contract of the sharded Stage 3
        # (inertia psums once, outside the loop)
        def psums_in_loops(jaxpr, loop_prims, in_loop=False):
            cnt = 0
            for eqn in jaxpr.eqns:
                sub_in_loop = in_loop or eqn.primitive.name in loop_prims
                if eqn.primitive.name == "psum" and in_loop:
                    cnt += 1
                for v in eqn.params.values():
                    for j in (v if isinstance(v, (list, tuple)) else [v]):
                        inner = getattr(j, "jaxpr", j)
                        if hasattr(inner, "eqns"):
                            cnt += psums_in_loops(inner, loop_prims, sub_in_loop)
            return cnt
        jaxpr = jax.make_jaxpr(lambda x, k: kmeans_sharded(
            x, cfg, k, mesh=mesh, axis="data"))(x, key)
        n_loop_psums = psums_in_loops(jaxpr.jaxpr, ("while",))
        assert n_loop_psums == 1, n_loop_psums
        # fixed-iteration (benchmark) variant holds the same contract; its
        # fori lowers through scan, and the chunked iteration's inner scan
        # must not hide extra collectives either
        fcfg = KMeansConfig(k=5, fixed_iters=3)
        jaxpr_f = jax.make_jaxpr(lambda x, k: kmeans_sharded(
            x, fcfg, k, mesh=mesh, axis="data"))(x, key)
        assert psums_in_loops(jaxpr_f.jaxpr, ("while", "scan")) == 1
        print("KMEANS-SHARDED-OK")
    """))


def test_sharded_kmeans_pads_rows_that_do_not_tile_the_mesh():
    # n = 253 over 4 shards: zero pad rows must leave counts, the change
    # test, the inertia and the labels as on one device
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.kmeans import KMeansConfig, kmeans
        from repro.core.distributed_pipeline import kmeans_sharded
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(253, 6)) + 3.0, jnp.float32)
        key = jax.random.PRNGKey(0)
        for empty in ("keep", "reseed_farthest"):
            cfg = KMeansConfig(k=5, max_iters=30, empty=empty)
            r1 = jax.jit(lambda x, k: kmeans(x, cfg, k))(x, key)
            r2 = jax.jit(lambda x, k: kmeans_sharded(
                x, cfg, k, mesh=mesh, axis="data"))(x, key)
            assert r2.labels.shape == (253,)
            np.testing.assert_array_equal(np.asarray(r1.labels),
                                          np.asarray(r2.labels))
            np.testing.assert_allclose(np.asarray(r1.centroids),
                                       np.asarray(r2.centroids),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(r1.inertia), float(r2.inertia),
                                       rtol=1e-5)
            assert int(r1.iterations) == int(r2.iterations)
        print("KMEANS-SHARDED-PAD-OK")
    """))


def test_sharded_kmeans_reseed_matches_single_device():
    # empty="reseed_farthest" sharded: the second packed psum overlays each
    # shard's k farthest [row | dmin] candidates; the revived-centroid
    # trajectory must match the single-device reseed (and cost exactly one
    # extra in-loop collective)
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.kmeans import KMeansConfig, kmeans
        from repro.core.distributed_pipeline import kmeans_sharded
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        kb, n_per, d = 4, 64, 6
        centers = np.eye(kb, d).astype(np.float32) * 20.0
        x = jnp.asarray(np.concatenate(
            [c + rng.normal(size=(n_per, d)) for c in centers]), jnp.float32)
        # 5th centroid starts far from all data -> guaranteed empty -> the
        # reseed rung must revive it from the globally farthest point
        init = jnp.concatenate(
            [jnp.asarray(centers), jnp.full((1, d), 1e3, jnp.float32)])
        cfg = KMeansConfig(k=5, max_iters=30, empty="reseed_farthest")
        key = jax.random.PRNGKey(0)
        r1 = jax.jit(lambda x, k: kmeans(x, cfg, k, init_centroids=init))(x, key)
        r2 = jax.jit(lambda x, k: kmeans_sharded(
            x, cfg, k, mesh=mesh, axis="data", init_centroids=init))(x, key)
        np.testing.assert_array_equal(np.asarray(r1.labels), np.asarray(r2.labels))
        np.testing.assert_allclose(np.asarray(r1.centroids), np.asarray(r2.centroids),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(r1.inertia), float(r2.inertia), rtol=1e-5)
        # the revive actually happened: all 5 clusters occupied
        assert np.unique(np.asarray(r2.labels)).size == 5
        # collective budget: default config 1 psum in-loop, reseed exactly 2
        def psums_in_loops(jaxpr, loop_prims, in_loop=False):
            cnt = 0
            for eqn in jaxpr.eqns:
                sub_in_loop = in_loop or eqn.primitive.name in loop_prims
                if eqn.primitive.name == "psum" and in_loop:
                    cnt += 1
                for v in eqn.params.values():
                    for j in (v if isinstance(v, (list, tuple)) else [v]):
                        inner = getattr(j, "jaxpr", j)
                        if hasattr(inner, "eqns"):
                            cnt += psums_in_loops(inner, loop_prims, sub_in_loop)
            return cnt
        jaxpr = jax.make_jaxpr(lambda x, k: kmeans_sharded(
            x, cfg, k, mesh=mesh, axis="data", init_centroids=init))(x, key)
        assert psums_in_loops(jaxpr.jaxpr, ("while",)) == 2
        print("KMEANS-RESEED-SHARDED-OK")
    """))


def test_sharded_stage1_pallas_dispatch_matches_ref():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed_pipeline import make_knn_rowblock
        from repro.kernels.knn_topk.ops import knn_topk
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(256, 6)), jnp.float32)
        k = 8
        # per-shard Pallas kernel (interpret) vs single-device reference:
        # the axis_index-derived query offset must keep self-exclusion exact
        d_sh, i_sh = jax.jit(make_knn_rowblock(
            mesh, k, axis="data", impl="pallas", interpret=True, block_q=32))(x)
        d_1, i_1 = knn_topk(x, k, impl="ref")
        np.testing.assert_allclose(np.asarray(d_sh), np.asarray(d_1),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(i_sh), np.asarray(i_1))
        assert (np.asarray(i_sh) != np.arange(256)[:, None]).all()
        print("STAGE1-PALLAS-OK")
    """))


def test_sharded_pipeline_stage3_shard_map_variant():
    print(_run("""
        import numpy as np, jax
        from repro.data.sbm import sbm_graph
        from repro.sparse.distributed import partition_coo_by_rows, shard_edges
        from repro.core.pipeline import SpectralClusteringConfig
        from repro.core.distributed_pipeline import spectral_cluster_sharded
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        coo, truth = sbm_graph(64, 4, 0.35, 0.01, seed=5)
        sm = shard_edges(mesh, partition_coo_by_rows(coo, 4), "data")
        # fused Stage 3 rides the explicit one-psum Lloyd loop under shard_map
        cfg = SpectralClusteringConfig(n_clusters=4, kmeans_iter="fused")
        out = jax.jit(lambda s, k: spectral_cluster_sharded(
            s, cfg, k, variant="shard_map", mesh=mesh, axis=("data",)))(
            sm, jax.random.PRNGKey(0))
        lab = np.asarray(out.labels)[:256]
        pur = 0
        for c in np.unique(lab):
            vals, counts = np.unique(truth[lab==c], return_counts=True)
            pur += counts.max()
        assert pur / 256 > 0.95, pur / 256
        print("STAGE3-SHARDMAP-OK")
    """))


def test_moe_shard_map_matches_gspmd_reference():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.moe import MoEConfig, init_moe_params, moe_ffn_gspmd, moe_ffn_shard_map
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=16, capacity_factor=8.0)
        d, T = 32, 64
        p = init_moe_params(jax.random.PRNGKey(0), d, cfg, 1, jnp.float32)
        lp = jax.tree.map(lambda a: a[0], p)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, d), jnp.float32)
        y_ref, _ = moe_ffn_gspmd(lp, x, cfg)   # huge capacity => no drops
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        lps = {
            "router": jax.device_put(lp["router"], NamedSharding(mesh, P())),
            "w_gate": jax.device_put(lp["w_gate"], NamedSharding(mesh, P("model"))),
            "w_up": jax.device_put(lp["w_up"], NamedSharding(mesh, P("model"))),
            "w_down": jax.device_put(lp["w_down"], NamedSharding(mesh, P("model"))),
        }
        y_sm, _ = jax.jit(lambda p_, x_: moe_ffn_shard_map(p_, x_, cfg, mesh))(lps, xs)
        np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref), rtol=2e-3, atol=2e-3)
        print("MOE-OK")
    """))


def test_compressed_psum_mean():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.optim.compress import compressed_psum_mean
        mesh = jax.make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 128), jnp.float32)
        r = jnp.zeros((8, 128), jnp.float32)
        from repro.compat import shard_map
        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data")))
        def f(gl, rl):
            m, nr = compressed_psum_mean(gl[0], rl[0], "data")
            return m[None], nr[None]
        mean, resid = jax.jit(f)(g, r)
        want = np.asarray(g).mean(0)
        got = np.asarray(mean)[0]
        scale = np.abs(np.asarray(g)).max() / 127
        assert np.abs(got - want).max() < scale, (np.abs(got-want).max(), scale)
        print("COMPRESS-OK")
    """))


def test_elastic_resharding():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.ckpt.elastic import plan_elastic_mesh, reshard_tree
        from repro.launch.sharding import logical_spec as L
        from repro.launch.mesh import rules_for_mesh
        # job "restarts" with 6 of 8 devices, model axis kept at 2
        mesh = plan_elastic_mesh(6, 2)
        assert mesh.devices.shape == (3, 2)
        tree = {"w": jnp.arange(24, dtype=jnp.float32).reshape(6, 4)}
        logical = {"w": L((None, "mlp"))}
        out = reshard_tree(tree, logical, rules_for_mesh(mesh), mesh)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
        assert len(out["w"].sharding.device_set) >= 2
        print("ELASTIC-OK")
    """))


def test_ring_counts_knn_tiles_over_steps_and_chips():
    """On four chips the ring's Stage 1 sums the kernel's tile counter over
    its steps and chips: each chip's count is that of its own knn_topk
    calls, one per visiting block."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.spectral import GraphConfig, Plan, SpectralPipeline
        from repro.kernels.knn_topk.ops import knn_topk
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        side = np.arange(13, dtype=np.float32)
        x = np.stack(np.meshgrid(side, side, side, indexing="ij"),
                     -1).reshape(-1, 3)[:2048]
        nl, kw = 512, dict(impl="pallas", interpret=True, block_q=128)
        graph = GraphConfig(knn_k=16, **kw)
        pipe = SpectralPipeline(n_clusters=4, graph=graph, plan=Plan(
            device="sharded", mesh=mesh, stage1_exchange="ring"))
        got = np.asarray(jax.jit(pipe.build_graph)(jnp.asarray(x)).knn_tiles)
        want = sum(np.asarray(knn_topk(
            jnp.asarray(x[src * nl:(src + 1) * nl]), 16,
            queries=jnp.asarray(x[my * nl:(my + 1) * nl]),
            query_offset=(my - src) * nl, with_tiles=True, **kw)[2])
            for my in range(4) for src in range(4))
        assert got.tolist() == want.tolist(), (got, want)
        merged, visited = got.tolist()
        assert visited == 16 * 4 * 2 and 0 < merged < visited, got
        single = SpectralPipeline(n_clusters=4, graph=graph)
        assert single.build_graph(jnp.asarray(x)).knn_tiles is not None
        lsh = SpectralPipeline(n_clusters=4, graph=GraphConfig(
            knn_k=16, method="lsh"), plan=Plan(device="sharded", mesh=mesh,
                                               stage1_exchange="ring"))
        assert jax.jit(lsh.build_graph)(jnp.asarray(x)).knn_tiles is None
        print("RING-TILES-OK")
    """))


def test_ring_exact_bitwise_matches_gather_and_single_device():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed_pipeline import make_knn_rowblock
        from repro.core.spectral import Plan, SpectralPipeline
        from repro.kernels.knn_topk.ops import knn_topk
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        n, d, k = 1024, 16, 10
        centers = rng.normal(size=(8, d)) * 6
        x = jnp.asarray((centers[rng.integers(8, size=n)] +
                         rng.normal(size=(n, d))).astype(np.float32))
        # kernel level: ring == gather == single-device, BITWISE (the
        # lexicographic (dist, id) merge reproduces lax.top_k tie-breaking)
        d_ref, i_ref = knn_topk(x, k)
        d_g, i_g = jax.jit(make_knn_rowblock(mesh, k))(x)
        d_r, i_r = jax.jit(make_knn_rowblock(mesh, k, exchange="ring"))(x)
        np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_g))
        np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_ref))
        assert (np.asarray(d_r).view(np.uint32)
                == np.asarray(d_g).view(np.uint32)).all()
        assert (np.asarray(d_r).view(np.uint32)
                == np.asarray(d_ref).view(np.uint32)).all()
        # end to end: ring-sharded pipeline labels == single-device labels
        key = jax.random.PRNGKey(0)
        single = SpectralPipeline(n_clusters=8).run(x, key)
        ring = SpectralPipeline(
            n_clusters=8, plan=Plan(device="sharded", mesh=mesh,
                                    stage1_exchange="ring")).run(x, key)
        np.testing.assert_array_equal(np.asarray(ring.labels),
                                      np.asarray(single.labels))
        print("RING-EXACT-OK")
    """))


def test_ring_lsh_recall_and_e2e_ari():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed_pipeline import make_knn_rowblock
        from repro.core.spectral import GraphConfig, Plan, SpectralPipeline
        from repro.kernels.knn_topk.ops import knn_topk
        from repro.serve import adjusted_rand_index
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        n, d, k, kc = 1024, 16, 10, 8
        centers = rng.normal(size=(kc, d)) * 6
        x = jnp.asarray((centers[rng.integers(kc, size=n)] +
                         rng.normal(size=(n, d))).astype(np.float32))
        # routed-LSH ring recall@k against exact neighbors
        _, i_ref = knn_topk(x, k)
        _, i_r = jax.jit(make_knn_rowblock(mesh, k, method="lsh",
                                           exchange="ring"))(x)
        hits = sum(len(set(a[a >= 0].tolist()) & set(b[b >= 0].tolist()))
                   for a, b in zip(np.asarray(i_r), np.asarray(i_ref)))
        recall = hits / max((np.asarray(i_ref) >= 0).sum(), 1)
        assert recall >= 0.95, f"ring LSH recall@{k} {recall:.4f} < 0.95"
        # end to end: ring LSH clustering quality >= 0.99x the gather LSH
        # (both against the exact single-device labels)
        key = jax.random.PRNGKey(0)
        single = SpectralPipeline(n_clusters=kc).run(x, key)
        aris = {}
        for exch in ("gather", "ring"):
            out = SpectralPipeline(
                n_clusters=kc, graph=GraphConfig(method="lsh"),
                plan=Plan(device="sharded", mesh=mesh,
                          stage1_exchange=exch)).run(x, key)
            aris[exch] = adjusted_rand_index(np.asarray(out.labels),
                                             np.asarray(single.labels))
        assert aris["ring"] >= 0.99 * aris["gather"], aris
        print(f"RING-LSH-OK recall={recall:.4f} aris={aris}")
    """))


def test_ring_collective_bytes_model():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed_pipeline import make_knn_rowblock
        from repro.sparse.distributed import trace_collective_bytes
        mesh = jax.make_mesh((8,), ("data",))
        S, n, d, k = 8, 512, 16, 8
        x = jnp.zeros((n, d), jnp.float32)
        nl = n // S
        payload = (S - 1) * nl * d * 4  # per-shard point traffic, both modes
        bg = trace_collective_bytes(jax.jit(make_knn_rowblock(mesh, k)), x)
        br = trace_collective_bytes(
            jax.jit(make_knn_rowblock(mesh, k, exchange="ring")), x)
        # gather moves the pool through ONE all_gather into an O(n*d)
        # buffer; ring moves the same point bytes as S-1 O(n*d/S) ppermute
        # steps and never materializes the pool
        assert bg.get("all_gather", 0) == payload, bg
        assert br.get("all_gather", 0) == 0, br
        assert br.get("ppermute", 0) == payload, br
        # ring LSH adds the candidate-routing traffic (3 table words/row)
        brl = trace_collective_bytes(
            jax.jit(make_knn_rowblock(mesh, k, method="lsh",
                                      exchange="ring")), x)
        from repro.kernels.lsh_candidates.ops import DEFAULT_N_TABLES
        tables = (S - 1) * 3 * DEFAULT_N_TABLES * nl * 4
        assert brl.get("ppermute", 0) == payload + tables, brl
        print("BYTES-MODEL-OK")
    """))
