"""IVF candidate retrieval = the paper's k-means as a serving component.

    PYTHONPATH=src python examples/ann_retrieval.py

Candidate retrieval scores each query against a large candidate pool.
This example builds the paper-motivated accelerator for it: cluster the
candidate embeddings with the fast k-means (k-means++ + BLAS-trick assign),
then at query time score only the top-``nprobe`` clusters.  Reports
recall@10 vs exact search and the scored-candidate reduction.
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.kmeans import KMeansConfig, kmeans
from repro.launch.cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidates", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--clusters", type=int, default=256)
    ap.add_argument("--nprobe", type=int, default=16)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--kmeans-iter", choices=("fused", "two_pass"), default="fused",
                    help="Lloyd engine: one-pass fused iteration (default) or "
                         "the two-pass assignment+update baseline")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    # clustered candidate distribution (realistic embedding geometry)
    centers = rng.normal(size=(64, args.dim)).astype(np.float32) * 2
    cand = (centers[rng.integers(0, 64, args.candidates)]
            + rng.normal(size=(args.candidates, args.dim)).astype(np.float32) * 0.7)
    cand /= np.linalg.norm(cand, axis=1, keepdims=True)
    q = cand[rng.integers(0, args.candidates, args.queries)] + \
        rng.normal(size=(args.queries, args.dim)).astype(np.float32) * 0.05
    q /= np.linalg.norm(q, axis=1, keepdims=True)

    t0 = time.perf_counter()
    res = jax.jit(lambda x, key: kmeans(
        x, KMeansConfig(k=args.clusters, max_iters=15, iter=args.kmeans_iter), key
    ))(jnp.asarray(cand), jax.random.PRNGKey(0))
    jax.block_until_ready(res.centroids)
    print(f"[build] k-means IVF index: k={args.clusters} ({args.kmeans_iter}) "
          f"in {time.perf_counter()-t0:.2f}s ({int(res.iterations)} Lloyd iters)")

    labels = np.asarray(res.labels)
    C = np.asarray(res.centroids)

    # exact top-10
    exact = np.argsort(-(q @ cand.T), axis=1)[:, :10]

    # IVF probe
    t0 = time.perf_counter()
    probe = np.argsort(-(q @ C.T), axis=1)[:, : args.nprobe]
    recall, scored = 0.0, 0
    for i in range(args.queries):
        mask = np.isin(labels, probe[i])
        idx = np.nonzero(mask)[0]
        scored += len(idx)
        top = idx[np.argsort(-(q[i] @ cand[idx].T))[:10]]
        recall += len(set(top.tolist()) & set(exact[i].tolist())) / 10
    dt = time.perf_counter() - t0
    recall /= args.queries
    frac = scored / (args.queries * args.candidates)
    print(f"[query] recall@10={recall:.3f}  scored {frac*100:.1f}% of candidates "
          f"({dt/args.queries*1e3:.2f} ms/query host-side)")


if __name__ == "__main__":
    main()
