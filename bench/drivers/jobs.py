"""Closed-loop clustering jobs: one job at a time, back to back.

Set-up makes the deployment's datasets (the configuration's
``data_seeds``, the same for every run seed) and compiles the job's
program ahead.  The window then runs jobs until ``seconds`` have passed; a
job that starts before the deadline runs to its end, and the window ends
with it.  The run seed orders the datasets and draws each job's key (which
seeds k-means++; Stage 2 starts from the degrees), so every run clusters
the same datasets.  ``job_s`` is the window over the jobs it completed.

With ``trace`` the same jobs run as three calls of the stage API, each
under a host span (``stage1``, ``stage2``, ``stage3``), under the profiler.

Once the window has closed and the device's peak memory has been read, a
sample of the jobs drawn from the seed is compared with the plain
reference (``bench/reference.py``).
"""
from __future__ import annotations

import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import deploy, gen, harness
from bench import reference as ref
from bench import trace as tr

MAX_JOBS = 1024  # keys made ahead; a window never runs more jobs


def job_keys(seed: int, count: int) -> np.ndarray:
    """Raw ``uint32[2]`` PRNG keys drawn from the run seed."""
    return gen.rng_for(seed, 1).integers(0, 2 ** 32, size=(count, 2),
                                         dtype=np.uint32)


def dataset_order(seed: int, pool: int, count: int) -> np.ndarray:
    """Job ``i`` runs dataset ``order[i]``: every dataset once per round,
    each round in an order drawn from the seed."""
    rng = gen.rng_for(seed, 2)
    rounds = -(-count // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(rounds)])[:count]


def reference_for(cfg: dict, ds: dict, rnd=ref.exact) -> dict:
    """The reference's graph and eigenpairs of one dataset: in float64, or
    for the control in the precision ``rnd`` rounds to."""
    w = deploy.generator(cfg).reference_graph(cfg, ds, rnd)
    a, deg = ref.normalized_adjacency(w, rnd)
    k = cfg["n_clusters"]
    if rnd is ref.exact:
        vals, vecs = ref.top_eigenpairs(a, k + cfg["check_extra_eigs"],
                                        v0=np.sqrt(deg))
    else:
        vals, vecs = ref.top_eigenpairs(a, k, v0=np.sqrt(deg),
                                        tol=ref.BF16_EPS)
    return {"adjacency": a, "vals": vals, "vecs": vecs}


def control_job(cfg: dict, ds: dict, seed: int) -> dict:
    """The reference in the program's place, computed in bfloat16: what a
    job returns, in the form ``to_host`` gives the program's."""
    k = cfg["n_clusters"]
    r = reference_for(cfg, ds, ref.bf16)
    h = ref.bf16(ref.njw_rows(r["vecs"][:, :k]))
    labels, means = ref.lloyd(h, k, gen.rng_for(seed, 6), rnd=ref.bf16)
    inertia = ref.bf16(ref.sq_dists(h, means, ref.bf16).min(1).sum())
    a = r["adjacency"].tocoo()
    return {"labels": labels, "embedding": h, "inertia": float(inertia),
            "eigenvalues": ref.bf16(1.0 - r["vals"][:k]),
            "row": a.row, "col": a.col, "val": a.data}


def compare_job(cfg: dict, out: dict, r: dict) -> Dict[str, float]:
    """The compared numbers of one job (see ``bench/reference.py``)."""
    n, k = deploy.n_nodes(cfg), cfg["n_clusters"]
    a_prog = ref.graph_from_edges(out["row"], out["col"], out["val"], n)
    width = ref.span_width(r["vals"], k, cfg["pipeline"]["tol"])
    return {
        "graph_err": ref.graph_err(a_prog, r["adjacency"]),
        "eig_err": ref.eig_err(out["eigenvalues"], 1.0 - r["vals"]),
        "embed_err": ref.embed_err(out["embedding"], r["vecs"][:, :width]),
        "row_norm_err": ref.row_norm_err(out["embedding"]),
        "kmeans_gap": ref.kmeans_gap(out["embedding"], out["labels"],
                                     out["inertia"], k),
    }


def to_host(result, adj) -> dict:
    import jax

    res, a = jax.device_get((result, adj))
    return {"labels": res.labels, "embedding": res.embedding,
            "eigenvalues": res.eigenvalues,
            "inertia": float(res.kmeans_inertia),
            "restarts": int(res.lanczos_restarts),
            "km_iters": int(res.kmeans_iterations),
            "row": a.row, "col": a.col, "val": a.val, "nnz": int(a.row.size)}


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        trace: bool, devs, t_start: float,
        wrap: Optional[Callable] = None,
        ref_cache: Optional[dict] = None) -> dict:
    """One run of a job cell.  ``wrap`` replaces the compiled job (the
    tests break the timed path with it); ``ref_cache`` keeps reference
    results across runs of one process, keyed by data seed."""
    import jax

    gen_mod = deploy.generator(cfg)
    pipe = deploy.pipeline(cfg, devs)
    pool = [gen_mod.dataset(cfg, s) for s in cfg["data_seeds"]]
    inputs = [gen_mod.inputs(cfg, ds) for ds in pool]
    t_data = time.time() - t_start
    keys = job_keys(seed, MAX_JOBS)
    order = dataset_order(seed, len(pool), MAX_JOBS)
    key0 = keys[0]
    if trace:
        build = gen_mod.stage1(cfg, pipe)

        # the programs are named after the host spans they run under
        def stage1(*args):
            return build(*args)

        def stage2(graph, key):
            return pipe.embed(graph, key)

        def stage3(embedding, key):
            return pipe.cluster(embedding, key)

        c1 = jax.jit(stage1).lower(*inputs[0]).compile()
        g0 = c1(*inputs[0])  # a graph placed as Stage 1 places it
        sub = np.asarray(jax.jit(jax.vmap(
            lambda k: jax.random.split(k, 3)))(keys))  # as run() splits
        c2 = jax.jit(stage2).lower(g0, sub[0, 1]).compile()
        e_shape = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(stage2, g0, sub[0, 1]), c2.output_shardings)
        c3 = jax.jit(stage3).lower(e_shape, sub[0, 2]).compile()
        del g0

        def one(i):
            with jax.profiler.TraceAnnotation("stage1"):
                t0 = time.perf_counter()
                g = jax.block_until_ready(c1(*inputs[order[i]]))
                t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("stage2"):
                e = jax.block_until_ready(c2(g, sub[i, 1]))
                t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("stage3"):
                res = jax.block_until_ready(c3(e, sub[i, 2]))
                t3 = time.perf_counter()
            stage_s.append((t1 - t0, t2 - t1, t3 - t2))
            return res, g.adj
    else:
        compiled = jax.jit(gen_mod.job(cfg, pipe)).lower(
            *inputs[0], key0).compile()
        if wrap is not None:
            compiled = wrap(compiled)

        def one(i):
            return jax.block_until_ready(compiled(*inputs[order[i]], keys[i]))

    stage_s: List[tuple] = []
    outs, ends, failed = [], [], 0
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    t_w0 = time.perf_counter()
    setup_s = time.time() - t_start
    deadline = t_w0 + seconds
    i = 0
    with jax.profiler.TraceAnnotation("window"):
        while time.perf_counter() < deadline and i < MAX_JOBS:
            try:
                outs.append(one(i))
            except Exception as e:  # a failed job counts; the window goes on
                print(f"job {i} failed: {e!r}", flush=True)
                outs.append(None)
                failed += 1
            ends.append(time.perf_counter())
            i += 1
    t_w1 = ends[-1]
    if trace:
        jax.profiler.stop_trace()
    attempted = i
    device = harness.device_record(devs)

    # the sample compared with the reference, drawn from the seed
    rng = gen.rng_for(seed, 3)
    done = [j for j in range(attempted) if outs[j] is not None]
    sample = sorted(rng.choice(done, min(cfg["check_jobs"], len(done)),
                               replace=False).tolist()) if done else []
    host = {j: to_host(*outs[j]) for j in sample}
    info = [to_host(*o) if trace and o is not None else None for o in outs]
    del outs, inputs
    if not trace:
        del compiled
    refs = {} if ref_cache is None else ref_cache
    worst: Dict[str, float] = {}
    for j in sample:
        d = cfg["data_seeds"][int(order[j])]
        if d not in refs:
            refs[d] = reference_for(cfg, pool[int(order[j])])
        for name, v in compare_job(cfg, host[j], refs[d]).items():
            worst[name] = max(worst.get(name, -np.inf), v)
    limits = cfg["limits"]
    checks = {name: {"value": worst.get(name), "limit": limits[name]}
              for name in cfg["checks_jobs"]}

    n_done = attempted - failed
    job_s = (t_w1 - t_w0) / max(1, n_done)
    metrics = {"job_s": harness.metric(job_s, "s"),
               "setup_s": harness.metric(setup_s, "s")}
    extra = {"jobs": attempted, "window_s": t_w1 - t_w0,
             "setup_phases_s": {"data": t_data, "window": setup_s},
             "datasets": [cfg["data_seeds"][int(order[j])] for j in sample],
             "restarts": [host[j]["restarts"] for j in sample],
             "km_iters": [host[j]["km_iters"] for j in sample]}
    result = {"attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device, "checks": checks, "extra": extra}
    if trace:
        spans = ("window", "stage1", "stage2", "stage3")
        reduced = tr.load_xplane(trace_dir, spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["trace"] = reduced
        result["ctx"] = {
            "kind": "jobs", "cfg": cfg, "stage_s": stage_s,
            "lanczos": deploy.lanczos_sizes(pipe, deploy.n_nodes(cfg)),
            "jobs": [{"restarts": o["restarts"], "km_iters": o["km_iters"],
                      "nnz": o["nnz"]} for o in info if o is not None],
            "trace": reduced}
    return result
