"""Smoke of the spectral-clustering path on a TPU, through its entry points.

    python chip_smoke.py              # one chip: kernels, DTI clustering, serving
    python chip_smoke.py --chips 4    # four chips: sharded Stage 1 vs one device

One chip runs four phases in this process, in order:

1. device: the platform, device kind and count JAX reports;
2. kernels: each kernel ``impl="auto"`` sends to Pallas on a TPU against its
   reference at small real shapes (the reference at full fp32 matmul
   precision);
3. clustering: the paper's DTI workflow at its published scale — 142,541
   voxels, spatial kNN (k=16) over 3-D positions, cross-correlation weights on
   90-dim profiles, thick-restart Lanczos (tol 1e-4), fused k-means at k=500 —
   through ``SpectralPipeline.run`` under one ``jax.jit``, compiled ahead and
   timed apart from its one run;
4. serving: ``serve_online`` (``MicroBatcher`` -> ``serve_fn``) on
   ``bench_serving``'s blob pool (20,000 points, d=16, k=16) with 32
   requests.  Larger blob pools (65,536 points, k=64) leave the top
   eigenvalue k-fold degenerate, and Lanczos then escalates through every
   attempt without reaching ``tol``.

``--chips 4`` runs only the sharded path and its comparison: the DTI points
on a 4-device mesh under ``Plan(device="sharded")`` with the gather and the
ring Stage-1 exchange, against the one-device run in the same process (exact
kNN equal, labels at ARI >= 0.99).

The last line of standard output is ``{"ok": ..., "device": {"platform",
"kind", "count"}}``; ``ok`` is true only on a TPU with every phase passed, and
the exit code is 0 only then.  Without a TPU the phases run only at a size
given with ``--n`` (a CPU rehearsal, e.g. ``JAX_PLATFORMS=cpu python
chip_smoke.py --n 1000 --clusters 12 --regions 6 --interpret ...``), and the
last line says ``"ok": false``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

DTI_N = 142_541  # voxels in the paper's DTI run (published scale)
DTI_CLUSTERS = 500
DTI_REGIONS = 250  # latent regions: examples/dti_pointcloud.py uses k // 2
KNN_K = 16  # spatial neighbours per voxel
TOL = 1e-4  # Lanczos tolerance, and the embed stage's residual_max gate
# CPU purity of `examples/dti_pointcloud.py --device-stage1 --n 4000
# --clusters 12` (0.935) less 0.05
PURITY_FLOOR = 0.885


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded Stage-1 comparison")
    ap.add_argument("--n", type=int, default=None,
                    help=f"DTI voxels (default {DTI_N}; required off TPU)")
    ap.add_argument("--clusters", type=int, default=DTI_CLUSTERS)
    ap.add_argument("--regions", type=int, default=DTI_REGIONS)
    ap.add_argument("--kernel-rows", type=int, default=8192,
                    help="rows of the phase-2 parity inputs")
    ap.add_argument("--serve-n", type=int, default=20_000)
    ap.add_argument("--serve-dim", type=int, default=16)
    ap.add_argument("--serve-clusters", type=int, default=16)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in interpret mode (CPU "
                         "rehearsal only; never on the chip)")
    return ap


class PhaseFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def purity(labels, truth) -> float:
    import numpy as np

    counts = np.zeros((labels.max() + 1, truth.max() + 1), np.int64)
    np.add.at(counts, (labels, truth), 1)
    return float(counts.max(1).sum() / len(truth))


# ---------------------------------------------------------------------------
# phase 2: kernel parity
# ---------------------------------------------------------------------------

def phase_kernels(args, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed_pipeline import merge_topk
    from repro.data.pointcloud import dti_like_pointcloud
    from repro.kernels.ell_spmm import ops as ell_spmm_ops
    from repro.kernels.ell_spmv import ops as ell_spmv_ops
    from repro.kernels.kmeans_iter.ops import kmeans_iter, kmeans_iter_engine
    from repro.kernels.knn_topk.ops import knn_topk, knn_topk_engine
    from repro.kernels.lsh_candidates.ops import hash_codes, make_planes

    n = args.kernel_rows
    rng = np.random.default_rng(0)
    highest = jax.default_matmul_precision("highest")

    # knn_topk at the DTI widths: lattice positions (d=3, many exact ties)
    pos = dti_like_pointcloud(n, d_profile=1, n_regions=2, seed=0,
                              neighbors="none")[0]
    x = jnp.asarray(pos)
    k = KNN_K
    print(f"  knn_topk engine: {knn_topk_engine('pallas', interpret)} "
          f"(n={n}, d=3, k={k})")
    dp, ip = jax.device_get(knn_topk(x, k, impl="pallas", interpret=interpret))
    with highest:
        dr, ir = jax.device_get(knn_topk(x, k, impl="ref"))
    derr = float(np.abs(dp - dr).max())
    # neighbour sets may differ only among points tied at the k-th distance
    bad_rows = 0
    for r in np.nonzero((np.sort(ip, 1) != np.sort(ir, 1)).any(1))[0]:
        extra = np.setdiff1d(ip[r], ir[r])
        at_edge = np.abs(dp[r][np.isin(ip[r], extra)] - dr[r, -1]) <= 1e-3
        bad_rows += int(not at_edge.all())
    # lattice distances are exact integers: the k smallest in (dist², id)
    # order are one answer, which the sharded ring exchange reproduces
    lat = pos.astype(np.int64)
    key = np.empty((n, k), np.int64)
    for s in range(0, n, 256):
        d2 = ((lat[s:s + 256, None, :] - lat[None]) ** 2).sum(-1) * n
        d2 += np.arange(n)
        d2[np.arange(len(d2)), s + np.arange(len(d2))] = np.iinfo(np.int64).max
        key[s:s + 256] = np.sort(np.partition(d2, k, axis=1)[:, :k], axis=1)
    off_order = int((ip != key % n).any(1).sum())
    print(f"  knn_topk: max |dist² - ref| = {derr}, rows whose neighbour "
          f"set differs beyond ties = {bad_rows}, rows off the (dist², id) "
          f"order = {off_order}")
    check(derr <= 1e-3 and bad_rows == 0, "knn_topk disagrees with its ref")
    check(off_order == 0, "knn_topk breaks ties off the (dist², id) order")
    # the ring exchange's work on one chip: four candidate blocks searched
    # apart and merged in ring order must give the full-pool answer
    nb = n // 4
    bd = jnp.full((n, k), jnp.inf, jnp.float32)
    bi = jnp.full((n, k), -1, jnp.int32)
    for src in (0, 3, 2, 1):
        d_t, i_t = knn_topk(x[src * nb:(src + 1) * nb], k, queries=x,
                            query_offset=-src * nb, impl="pallas",
                            interpret=interpret)
        bd, bi = merge_topk(bd, bi, d_t, jnp.where(i_t >= 0, i_t + src * nb,
                                                   -1), k)
    bd, bi = jax.device_get((bd, bi))
    ring_idx, ring_derr = bool((bi == ip).all()), float(np.abs(bd - dp).max())
    print(f"  knn_topk: four blocks merged in ring order equal the full "
          f"pool: idx {ring_idx}, max |dist² diff| = {ring_derr}")
    check(ring_idx and ring_derr == 0.0,
          "four blocks merged in ring order differ from the full pool")

    # kmeans_iter at k=500, d=500 (the DTI Stage-3 widths)
    kk, d = 500, 500
    xe = rng.normal(size=(n, d)).astype(np.float32)
    ce = xe[rng.choice(n, kk, replace=False)]
    eng = kmeans_iter_engine(n, d, kk, impl="pallas", interpret=interpret)
    print(f"  kmeans_iter engine: {eng} (n={n}, d={d}, k={kk})")
    lp, dmp, sp, cp = jax.device_get(kmeans_iter(
        jnp.asarray(xe), jnp.asarray(ce), impl="pallas", interpret=interpret))
    x64, c64 = xe.astype(np.float64), ce.astype(np.float64)
    d64 = ((x64 ** 2).sum(1)[:, None] + (c64 ** 2).sum(1)[None, :]
           - 2.0 * x64 @ c64.T)
    best = d64.min(1)
    scale = (x64 ** 2).sum(1) + (c64 ** 2).sum(1).max()
    # a label is right when its centroid is nearest within rounding
    lab_err = float(((d64[np.arange(n), lp] - best) / scale).max())
    dmin_err = float((np.abs(dmp - best) / scale).max())
    sums64 = np.zeros((kk, d))
    np.add.at(sums64, lp, x64)
    sum_err = float(np.abs(sp - sums64).max() / np.abs(x64).max())
    counts_ok = bool((cp == np.bincount(lp, minlength=kk)).all())
    print(f"  kmeans_iter: label slack {lab_err:.3e}, dmin err {dmin_err:.3e} "
          f"(relative to ‖x‖²+‖c‖²), sums err {sum_err:.3e} (relative to "
          f"max |x|), counts exact: {counts_ok}")
    check(lab_err <= 1e-5 and dmin_err <= 1e-5 and sum_err <= 1e-4
          and counts_ok, "kmeans_iter disagrees with the fp64 reference")

    # lsh_candidates.hash_codes at the profile width (d=90)
    xp = rng.normal(size=(n, 90)).astype(np.float32)
    planes = make_planes(90, 16, 16, 0)
    codes, tie = jax.device_get(hash_codes(jnp.asarray(xp), planes,
                                           impl="pallas", interpret=interpret))
    proj = np.einsum("nd,tdb->tnb", xp.astype(np.float64),
                     np.asarray(planes, np.float64))
    bits = (proj[..., :16] >= 0).astype(np.int64)
    ref_codes = (bits << np.arange(16)).sum(-1)
    flipped = (((codes[..., None] >> np.arange(16)) & 1) != bits)
    # a bit may flip only where its projection is within rounding of zero
    pscale = np.linalg.norm(xp, axis=1)[None, :, None] * 10.0
    bad_bits = int((flipped & (np.abs(proj[..., :16]) > 1e-4 * pscale)).sum())
    tie_err = float((np.abs(tie - proj[..., 16]) / pscale[..., 0]).max())
    print(f"  hash_codes: codes equal {float((codes == ref_codes).mean()):.6f}, "
          f"flips away from zero = {bad_bits}, tie err {tie_err:.3e}")
    check(bad_bits == 0 and tie_err <= 1e-5, "hash_codes disagrees with fp64")

    print(f"  ell_spmm, ell_spmm_cheb_step: XLA path on TPU "
          f"({ell_spmm_ops.MOSAIC_REFUSAL})")
    print(f"  ell_spmv: XLA path on TPU ({ell_spmv_ops.MOSAIC_REFUSAL})")


# ---------------------------------------------------------------------------
# phase 3: DTI clustering
# ---------------------------------------------------------------------------

def dti_inputs(args):
    from repro.data.pointcloud import dti_like_pointcloud

    n = args.n or DTI_N
    t0 = time.perf_counter()
    pos, prof, _, region = dti_like_pointcloud(
        n, d_profile=90, n_regions=args.regions, seed=0, neighbors="none")
    print(f"  data: {n} voxels, 90-dim profiles, {args.regions} regions "
          f"({time.perf_counter() - t0:.3f}s host set-up)")
    return pos, prof, region


def dti_pipeline(args, interpret, plan=None):
    from repro.core.spectral import (EigConfig, GraphConfig, KMeansConfig,
                                     Plan, SpectralPipeline)

    return SpectralPipeline(
        n_clusters=args.clusters,
        graph=GraphConfig(knn_k=KNN_K, measure="cross_correlation",
                          interpret=interpret),
        eig=EigConfig(tol=TOL),
        kmeans=KMeansConfig(iter="fused", interpret=interpret),
        plan=plan or Plan())


def check_result(out, args, tag: str):
    import numpy as np

    for rep in out.reports:
        conv, res = bool(rep.converged), float(rep.residual_max)
        print(f"  {tag} report {rep.stage}: converged={conv} "
              f"residual_max={res} escalations={list(rep.escalations)}")
        check(conv, f"{tag}: stage {rep.stage} reports converged=False")
        if rep.stage == "embed":  # cluster's residual_max is the inertia
            check(res <= TOL, f"{tag}: embed residual_max {res} > tol {TOL}")
    for name in ("embedding", "eigenvalues", "kmeans_inertia"):
        check(bool(np.isfinite(np.asarray(getattr(out, name))).all()),
              f"{tag}: {name} is not finite")
    labels = np.asarray(out.labels)
    check(bool(((labels >= 0) & (labels < args.clusters)).all()),
          f"{tag}: labels out of range")
    return labels


def phase_clustering(args, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.kmeans_iter.ops import kmeans_iter_engine
    from repro.kernels.knn_topk.ops import knn_topk_engine

    pos, prof, region = dti_inputs(args)
    n = len(pos)
    pipe = dti_pipeline(args, interpret)
    d_emb = pipe.eig.n_eigvecs or args.clusters
    print(f"  Stage 1 engine: knn_topk "
          f"{knn_topk_engine(pipe.graph.impl, pipe.graph.interpret)}")
    print(f"  Stage 3 engine: kmeans_iter "
          f"{kmeans_iter_engine(n, d_emb, args.clusters, interpret=interpret)}")
    x, p = jnp.asarray(prof), jnp.asarray(pos)
    key = jax.random.PRNGKey(0)
    # One call, its compile timed apart: at full size a second call would
    # take the smoke past its time limit.
    t0 = time.perf_counter()
    run = jax.jit(lambda x, p, key: pipe.run(x, key, points=p)).lower(
        x, p, key).compile()
    t1 = time.perf_counter()
    out = run(x, p, key)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    print(f"  wall: cold {t2 - t0}s = compile {t1 - t0}s + run {t2 - t1}s")
    embed = [r for r in out.reports if r.stage == "embed"][0]
    op = ("COO fallback from BlockELL" if "blockell_to_coo_fallback"
          in embed.escalations else pipe.eig.representation)
    print(f"  Stage 2 operator: {op} (segment-sum SpMM, XLA)" if op == "coo"
          else f"  Stage 2 operator: {op}")
    labels = check_result(out, args, "dti")
    pur = purity(labels, region)
    print(f"  n={n} k={args.clusters}: restarts={int(out.lanczos_restarts)} "
          f"km_iters={int(out.kmeans_iterations)} "
          f"non-empty={len(np.unique(labels))}/{args.clusters} "
          f"purity={pur} (floor {PURITY_FLOOR})")
    check(pur >= PURITY_FLOOR, f"purity {pur} < {PURITY_FLOOR}")


# ---------------------------------------------------------------------------
# phase 4: serving
# ---------------------------------------------------------------------------

def phase_serving(args, interpret):
    from repro.launch.serve import build_parser as serve_parser
    from repro.launch.serve import serve_online

    sargs = serve_parser().parse_args([
        "--mode", "serve", "--n", str(args.serve_n),
        "--clusters", str(args.serve_clusters), "--dim", str(args.serve_dim),
        "--requests", str(args.requests)])
    summary = serve_online(sargs)
    check(summary["train_converged"],
          "a training stage report says converged=False")
    check(summary["failures"] == 0, f"{summary['failures']} requests failed")
    check(summary["train_ari_vs_served"] >= 0.95,
          f"train_ari_vs_served {summary['train_ari_vs_served']} < 0.95")


# ---------------------------------------------------------------------------
# --chips 4: sharded Stage 1 against one device
# ---------------------------------------------------------------------------

def phase_sharded(args, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.core.kmeans as km
    from repro.core.distributed_pipeline import make_knn_rowblock
    from repro.core.spectral import Plan
    from repro.kernels.kmeans_iter.ops import kmeans_iter_engine
    from repro.kernels.knn_topk.ops import knn_topk
    from repro.serve import adjusted_rand_index

    check(len(jax.devices()) >= 4, f"needs 4 devices, has {len(jax.devices())}")
    mesh = jax.make_mesh((4,), ("data",))  # Explicit axes, as users build it
    pos, prof, _ = dti_inputs(args)
    x, p = jnp.asarray(prof), jnp.asarray(pos)
    key = jax.random.PRNGKey(0)
    d1, i1 = jax.device_get(jax.jit(lambda q: knn_topk(
        q, KNN_K, interpret=interpret))(p))
    pipe1 = dti_pipeline(args, interpret)
    n, kc = len(pos), args.clusters
    if km.runs_mosaic(n, kc, pipe1.kmeans.resolved(kc)):
        print("  sharded Stage 3: kmeans_sharded (Mosaic kmeans_iter per "
              "shard, rows padded)")
    else:
        print(f"  sharded Stage 3: kmeans under GSPMD (kmeans_iter "
              f"{kmeans_iter_engine(n, kc, kc, interpret=interpret)})")
    t0 = time.perf_counter()
    out1 = jax.jit(lambda a, b, kk: pipe1.run(a, kk, points=b))(x, p, key)
    jax.block_until_ready(out1)
    print(f"  one device: {time.perf_counter() - t0}s (compile included)")
    lab1 = check_result(out1, args, "single")
    for exchange in ("gather", "ring"):
        knn = jax.jit(make_knn_rowblock(mesh, KNN_K, exchange=exchange,
                                        interpret=interpret))
        ds, is_ = jax.device_get(knn(p))
        same_idx = bool((is_ == i1).all())
        derr = float(np.abs(ds - d1).max())
        print(f"  {exchange}: kNN idx equal to one device: {same_idx}, "
              f"max |dist² diff| = {derr}")
        check(same_idx and derr == 0.0, f"{exchange}: exact kNN differs")
        pipe = dti_pipeline(args, interpret, Plan(
            device="sharded", mesh=mesh, stage1_exchange=exchange))
        t0 = time.perf_counter()
        out = jax.jit(lambda a, b, kk: pipe.run(a, kk, points=b))(x, p, key)
        jax.block_until_ready(out)
        lab = check_result(out, args, exchange)
        ari = adjusted_rand_index(lab, lab1)
        print(f"  {exchange}: {time.perf_counter() - t0}s (compile included), "
              f"ARI vs one device = {ari}")
        check(ari >= 0.99, f"{exchange}: ARI {ari} < 0.99")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[phase 1 device] platform={device['platform']} "
          f"kind={device['kind']} count={device['count']}", flush=True)
    on_tpu = device["platform"] == "tpu"
    if on_tpu and args.interpret:
        print("--interpret is for CPU rehearsals; refusing it on the chip")
        phases = []
        results = {"device": False}
    elif not on_tpu and args.n is None:
        print("no TPU: phases skipped (rehearse on CPU with --n)")
        phases = []
        results = {"device": False}
    else:
        phases = ([("sharded", phase_sharded)] if args.chips == 4 else
                  [("kernels", phase_kernels), ("clustering", phase_clustering),
                   ("serving", phase_serving)])
        results = {"device": True}
    interpret = True if args.interpret else None
    for i, (name, fn) in enumerate(phases, start=2):
        print(f"[phase {i} {name}]", flush=True)
        t0 = time.perf_counter()
        try:
            fn(args, interpret)
            results[name] = True
        except Exception:  # report the phase and go on; the exit code fails
            traceback.print_exc()
            results[name] = False
        print(f"[phase {i} {name}] {'PASS' if results[name] else 'FAIL'} "
              f"({time.perf_counter() - t0}s)", flush=True)
    ok = on_tpu and all(results.values())
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
