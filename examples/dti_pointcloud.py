"""End-to-end driver — the paper's DTI workflow (its flagship experiment).

    PYTHONPATH=src python examples/dti_pointcloud.py            # scaled-down
    PYTHONPATH=src python examples/dti_pointcloud.py --full     # 142k voxels
    PYTHONPATH=src python examples/dti_pointcloud.py --device-stage1

Pipeline (paper Fig. 2): 3-D voxel lattice with 90-dim connectivity
profiles → ε-distance edge list → cross-correlation similarity graph
(Alg. 1) → normalized Laplacian eigenvectors via restarted Lanczos
(Alg. 2-3) → k-means++ clustering (Alg. 4-5).  Reports per-stage timings —
the same decomposition as the paper's Table III.

``--device-stage1`` swaps the host ε-edge construction for the device-
resident fused path: spatial kNN via the ``knn_topk`` kernel + profile
cross-correlation weights, points→labels under a single jit
(``SpectralPipeline.run`` on raw points, with ``GraphConfig.knn_k`` and a
separate ``points=`` search space).  ``--graph-method lsh`` additionally
swaps the exact O(n²d) neighbor search for LSH candidate generation +
exact rerank (O(n·m·d) — the paper-scale 142k-voxel regime; DESIGN.md §12).
"""
import argparse
import time

import numpy as np
import jax

from repro.core.spectral import EigConfig, GraphConfig, KMeansConfig, SpectralPipeline
from repro.core.similarity import build_similarity_graph
from repro.data.pointcloud import dti_like_pointcloud
from repro.launch.cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale: 142k voxels, k=500")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--clusters", type=int, default=12)
    ap.add_argument("--device-stage1", action="store_true",
                    help="device-resident Stage 1 (kNN kernel), points→labels in one jit")
    ap.add_argument("--knn", type=int, default=16, help="neighbors per voxel (device Stage 1)")
    ap.add_argument("--graph-method", choices=("exact", "lsh"), default="exact",
                    help="device Stage-1 neighbor search: exact O(n²d) kernel "
                         "or LSH candidates + exact rerank (n ≫ 100k)")
    ap.add_argument("--kmeans-iter", choices=("fused", "two_pass"), default="fused",
                    help="Stage-3 Lloyd engine (fused = one data stream/iter)")
    ap.add_argument("--solver", default="lanczos",
                    choices=("lanczos", "chebyshev"),
                    help="Stage-2 engine: thick-restart Lanczos or the "
                         "Chebyshev polynomial filter — at paper scale "
                         "(--full: k=500) the filter's fixed stream count "
                         "sidesteps the reorthogonalization wall")
    ap.add_argument("--sparsify", type=float, default=None, metavar="RATIO",
                    help="Stage 1.5: spectrum-preserving edge sampling at "
                         "this target nnz ratio before the eigensolve — "
                         "every Lanczos/Chebyshev stream is O(nnz), so 0.4 "
                         "cuts Stage-2 bytes ~2.5x at ARI >= 0.99x parity")
    ap.add_argument("--coarsen", type=int, default=None, metavar="LEVELS",
                    help="Stage 1.5: heavy-edge-matching coarsening (this "
                         "many levels) + GPIC-style refine lift back to the "
                         "voxel graph (host-side compaction — runs eagerly)")
    args = ap.parse_args()
    if args.graph_method == "lsh" and not args.device_stage1:
        ap.error("--graph-method lsh requires --device-stage1 (the host "
                 "ε-edge path has no LSH front-end)")
    n = 142541 if args.full else args.n
    k = 500 if args.full else args.clusters

    t0 = time.perf_counter()
    # the device path builds its own neighbor graph on device — skip the
    # host O(n²) edge sweep entirely, that's the point of the flag
    pos, profiles, edges, region = dti_like_pointcloud(
        n, d_profile=90, n_regions=max(k // 2, 4), eps=1.8, seed=0,
        neighbors="none" if args.device_stage1 else "eps",
    )
    print(f"[data] {len(pos)} voxels, {len(edges)} ε-pairs "
          f"({time.perf_counter()-t0:.2f}s)")

    # optional Stage 1.5 reduction stages in the stage DAG
    stages = ["prepare", "embed", "cluster"]
    reduce_kw = {}
    if args.sparsify is not None:
        from repro.core.reduce import SparsifyConfig

        stages.insert(1, "sparsify")
        reduce_kw["sparsify"] = SparsifyConfig(target_nnz_ratio=args.sparsify)
    if args.coarsen is not None:
        from repro.core.reduce import CoarsenConfig

        stages.insert(stages.index("embed"), "coarsen")
        stages.insert(stages.index("embed") + 1, "refine")
        reduce_kw["coarsen"] = CoarsenConfig(levels=args.coarsen)

    pipe = SpectralPipeline(
        n_clusters=k,
        graph=GraphConfig(knn_k=args.knn, measure="cross_correlation",
                          method=args.graph_method),
        eig=EigConfig(tol=1e-4, solver=args.solver),
        kmeans=KMeansConfig(iter=args.kmeans_iter),
        stages=tuple(stages), **reduce_kw,
    )
    # coarsen's id compaction is host-side — run the whole DAG eagerly then
    maybe_jit = (lambda f: f) if args.coarsen is not None else jax.jit
    if args.device_stage1:
        import jax.numpy as jnp

        t0 = time.perf_counter()
        out = maybe_jit(lambda x, p, key: pipe.run(x, key, points=p))(
            jnp.asarray(profiles), jnp.asarray(pos), jax.random.PRNGKey(0))
        jax.block_until_ready(out.labels)
        t_solve = time.perf_counter() - t0
        print(f"[stages 1-3, device] points→labels: {t_solve:.3f}s "
              f"(nnz={2 * n * args.knn}, restarts={int(out.lanczos_restarts)}, "
              f"km_iters={int(out.kmeans_iterations)})")
    else:
        t0 = time.perf_counter()
        w = build_similarity_graph(profiles, edges, measure="cross_correlation")
        t_sim = time.perf_counter() - t0
        print(f"[stage 1] similarity graph: nnz={w.nnz} ({t_sim:.3f}s)")

        t0 = time.perf_counter()
        out = maybe_jit(lambda w, key: pipe.run(w, key))(w, jax.random.PRNGKey(0))
        jax.block_until_ready(out.labels)
        t_solve = time.perf_counter() - t0
        print(f"[stages 2+3] eigensolver+kmeans: {t_solve:.3f}s "
              f"(restarts={int(out.lanczos_restarts)}, km_iters={int(out.kmeans_iterations)})")

    labels = np.asarray(out.labels)
    sizes = np.bincount(labels, minlength=k)
    print(f"[result] {int((sizes > 0).sum())}/{k} non-empty clusters; "
          f"largest={sizes.max()}, median={int(np.median(sizes[sizes > 0]))}")
    from collections import Counter

    purity = sum(Counter(region[labels == i]).most_common(1)[0][1]
                 for i in np.unique(labels)) / len(region)
    print(f"[result] purity vs latent regions: {purity:.3f}")


if __name__ == "__main__":
    main()
