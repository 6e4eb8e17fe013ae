"""Serving launcher: batched spectral-clustering jobs and online OOS labels.

    python -m repro.launch.serve --mode cluster --n 20000 --clusters 64
    python -m repro.launch.serve --mode serve --n 4000 --clusters 8 \\
        --requests 64 --registry-dir /tmp/reg

``cluster`` mode is the paper's serving shape: accept graphs, return labels
(the batched-requests analogue for a clustering system).  ``serve`` mode is
the online subsystem (:mod:`repro.serve`): train one index, answer point
queries via out-of-sample extension through the micro-batcher — no
eigensolve per request.  Either mode exits with its failure count.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.launch.cache import enable_compile_cache


def serve_cluster(args) -> int:
    """Request loop with per-request fault isolation.

    One failing request logs a structured JSON error line and the loop
    continues; the return value is the failure count (the process exit
    code).  Three enforcement layers per request:

    * in-flight: the pipeline's own guards/ladders — live when running
      eagerly (``--strict``, where the escalation controllers are
      host-driven and ``EigConfig(strict=True)`` raises on unconverged
      embeds); under jit (the default) they degrade to signals-only;
    * post-hoc: :func:`repro.core.health.result_problems` on the concrete
      outputs — the jitted path's complement (non-finite outputs or
      ``converged=False`` stage reports fail the request);
    * ``--deadline-s``: a wall-clock budget; a slower request is a failure
      (jit dispatch is blocking, so the deadline is checked post-hoc, not
      preemptively).

    ``--inject-fault nan-graph`` poisons every odd request's edge weights —
    the CI smoke proof that a poisoned request fails *structurally* while
    its neighbors keep serving.
    """
    import json
    import math
    import sys

    from repro.core import health

    def _json_safe(o):
        # strict-JSON logs: a NaN residual in a stage report must not
        # produce a line downstream parsers reject
        if isinstance(o, float) and not math.isfinite(o):
            return str(o)
        if isinstance(o, dict):
            return {k: _json_safe(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            return [_json_safe(v) for v in o]
        return o
    from repro.core.health import PipelineError
    from repro.core.spectral import EigConfig, SpectralPipeline
    from repro.data.sbm import sbm_graph

    pipe = SpectralPipeline(n_clusters=args.clusters,
                            eig=EigConfig(strict=args.strict))
    print(f"[config] {pipe.to_dict()}")  # the reproducibility record
    jit = (lambda f: f) if args.strict else jax.jit
    fn = jit(lambda w, key: pipe.run(w, key))
    prepare = jit(pipe.prepare)
    embed = jit(pipe.embed)
    recluster = {
        k2: jit(lambda e, key, k2=k2: pipe.cluster(e, key, n_clusters=k2))
        for k2 in (args.recluster_k or [])
    }
    failures = 0

    def fail(req, stage, error, **extra):
        nonlocal failures
        failures += 1
        print(json.dumps(_json_safe({"event": "request_error", "req": req,
                                     "stage": stage, "error": error,
                                     **extra})),
              file=sys.stderr, flush=True)

    for req in range(args.requests):
        coo, _ = sbm_graph(args.n // args.clusters, args.clusters, 0.2, 0.01, seed=req)
        if args.inject_fault == "nan-graph" and req % 2 == 1:
            from repro.testing.faults import poison_graph

            coo = poison_graph(coo)
        t0 = time.perf_counter()
        try:
            out = fn(coo, jax.random.PRNGKey(req))
            jax.block_until_ready(out.labels)
            latency = time.perf_counter() - t0
            problems = health.result_problems(out)
            if problems:
                fail(req, "post_hoc", "; ".join(problems),
                     reports=health.reports_to_dict(out.reports))
                continue
            if args.deadline_s is not None and latency > args.deadline_s:
                fail(req, "deadline", f"latency {latency:.3f}s exceeds "
                                      f"--deadline-s {args.deadline_s}",
                     latency_s=latency)
                continue
            print(f"[req {req}] n={coo.shape[0]} k={args.clusters} "
                  f"latency={latency:.3f}s "
                  f"restarts={int(out.lanczos_restarts)} "
                  f"reports="
                  f"{json.dumps(_json_safe(health.reports_to_dict(out.reports)))}")
            if recluster:
                # the stage-graph serving shape: embed once, serve many k —
                # Stage 3 reruns on the cached embedding, Lanczos does not
                t0 = time.perf_counter()
                emb = embed(prepare(coo), jax.random.PRNGKey(req))
                jax.block_until_ready(emb.embedding)
                t_embed = time.perf_counter() - t0
                for k2, fn2 in recluster.items():
                    t0 = time.perf_counter()
                    out2 = fn2(emb, jax.random.PRNGKey(1000 + req))
                    jax.block_until_ready(out2.labels)
                    print(f"[req {req}]   re-cluster k={k2}: "
                          f"{time.perf_counter()-t0:.3f}s on the cached "
                          f"embedding (embed once: {t_embed:.3f}s)")
        except PipelineError as e:
            fail(req, e.stage, e.detail, ladder=list(e.ladder),
                 remedy=e.remedy)
        except Exception as e:  # isolation: a request must not kill the loop
            fail(req, "unknown", repr(e))
    print(json.dumps({"event": "serve_summary", "requests": args.requests,
                      "failures": failures}), flush=True)
    return failures


def serve_online(args) -> dict:
    """Online point-labelling over the :mod:`repro.serve` subsystem.

    Train once (full pipeline on a blob pool), build a
    :class:`~repro.serve.oos.ServingIndex`, optionally publish it through
    the versioned registry, then drive query requests through the
    :class:`~repro.serve.batcher.MicroBatcher` into the ONE compiled
    :func:`~repro.serve.oos.serve_fn`.  Served embeddings feed the
    mini-batch k-means stream; when centroid drift crosses the threshold a
    refreshed index version is published (health-gated, atomic swap) and
    hot-swapped into the batcher via ``set_fn`` — the registry/stream loop
    end to end.

    Keeps the PR 8 contract: per-request fault isolation (a poisoned
    request fails structurally via
    :func:`~repro.core.health.numeric_problems` on its rows, neighbors
    keep serving), ``--deadline-s`` wall budgets, exit code = failure
    count.  ``--inject-fault nan-query`` poisons every odd request.

    Returns the ``serve_summary`` record it prints last (``failures``,
    batch fill, latency percentiles, ``train_converged`` over the training
    run's stage reports, ``train_ari_vs_served``).
    """
    import functools
    import json
    import sys

    import numpy as np

    from repro.core.health import numeric_problems
    from repro.core.spectral import SpectralPipeline
    from repro.serve import (
        BatchConfig,
        MicroBatcher,
        OOSConfig,
        adjusted_rand_index,
        build_index,
        needs_refresh,
        rebase,
        serve_fn,
        stream_from_index,
        stream_update,
    )
    from repro.serve.oos import ServingIndex
    from repro.serve.registry import EmbeddingRegistry, RegistryGateError

    rng = np.random.default_rng(0)
    k, d = args.clusters, args.dim
    centers = rng.normal(size=(k, d)) * 8.0
    pool = np.concatenate([
        centers[i] + rng.normal(size=(args.n // k, d))
        for i in range(k)]).astype(np.float32)

    pipe = SpectralPipeline(n_clusters=k)
    print(f"[config] {pipe.to_dict()}")
    t0 = time.perf_counter()
    result = pipe.run(jnp.asarray(pool), jax.random.PRNGKey(0))
    jax.block_until_ready(result.labels)
    print(f"[train] full pipeline on n={args.n}: "
          f"{time.perf_counter() - t0:.2f}s")

    oos_cfg = OOSConfig.from_graph_config(pipe.graph, method=args.oos_method)
    index = build_index(jnp.asarray(pool), result, config=oos_cfg)
    registry = None
    if args.registry_dir:
        registry = EmbeddingRegistry(args.registry_dir)
        v = registry.publish(index)
        print(json.dumps({"event": "index_published", "version": v}))

    stream = stream_from_index(index)
    failures = 0
    latencies = []

    def fail(req, stage, error):
        nonlocal failures
        failures += 1
        print(json.dumps({"event": "request_error", "req": req,
                          "stage": stage, "error": error}),
              file=sys.stderr, flush=True)

    with MicroBatcher(functools.partial(serve_fn, index), d,
                      BatchConfig(batch_size=args.batch_size,
                                  max_wait_s=args.max_wait_ms / 1e3)) as mb:
        for req in range(args.requests):
            tru = rng.integers(k)
            q = (centers[tru] + rng.normal(size=(args.rows_per_request, d))
                 ).astype(np.float32)
            if args.inject_fault == "nan-query" and req % 2 == 1:
                q[0, 0] = np.nan
            t0 = time.perf_counter()
            try:
                out = mb.label(q, timeout=30.0)
            except Exception as e:  # isolation: this request only
                fail(req, "serve_fn", repr(e))
                continue
            latency = time.perf_counter() - t0
            problems = numeric_problems(
                {"embedding": out.embedding, "dist2": out.dist2},
                context=f"req {req}")
            if problems:
                fail(req, "post_hoc", "; ".join(problems))
                continue
            if args.deadline_s is not None and latency > args.deadline_s:
                fail(req, "deadline",
                     f"latency {latency:.3f}s exceeds {args.deadline_s}")
                continue
            latencies.append(latency)
            stream, _ = stream_update(stream, jnp.asarray(out.embedding))
            if bool(needs_refresh(stream)):
                # drift: publish refreshed centroids as a new version and
                # hot-swap it into the batcher (full re-embed is the
                # offline analogue — see DESIGN.md §16)
                new_index = ServingIndex(
                    points=index.points, embedding=index.embedding,
                    centroids=stream.centroids, labels=index.labels,
                    config=index.config,
                    # the pool is unchanged, so the persisted LSH tables
                    # stay valid across a centroid-only refresh
                    lsh_tables=index.lsh_tables)
                if registry is not None:
                    try:
                        v = registry.publish(new_index)
                        print(json.dumps(
                            {"event": "drift_refresh", "req": req,
                             "version": v}))
                    except RegistryGateError as e:
                        fail(req, "refresh_gate", str(e))
                        continue
                index = new_index
                mb.set_fn(functools.partial(serve_fn, index))
                stream = rebase(stream)

        stats = mb.stats
    lat = np.sort(np.asarray(latencies)) if latencies else np.zeros(1)
    summary = {
        "event": "serve_summary", "requests": args.requests,
        "failures": failures, "batches": stats.batches,
        "fill": round(stats.fill, 3),
        "p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 2),
        "p99_ms": round(float(lat[min(int(len(lat) * 0.99),
                                      len(lat) - 1)]) * 1e3, 2),
        "train_converged": all(bool(r.converged) for r in result.reports),
        "train_ari_vs_served": None,
    }
    # diagnostic: re-serve the pool through OOS — labels should reproduce
    # the training clustering (the cheap in-process parity signal; the
    # held-out gate is tests/test_serving.py's OOS parity test)
    pool_out = serve_fn(index, jnp.asarray(pool[:min(args.n, 2048)]))
    summary["train_ari_vs_served"] = round(adjusted_rand_index(
        np.asarray(pool_out.labels),
        np.asarray(result.labels)[:min(args.n, 2048)]), 4)
    print(json.dumps(summary), flush=True)
    return summary


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["cluster", "serve"],
                    default="cluster")
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--clusters", type=int, default=16)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--dim", type=int, default=16,
                    help="serve mode: point dimensionality")
    ap.add_argument("--oos-method", choices=["exact", "lsh"], default="exact",
                    help="serve mode: out-of-sample neighbor search")
    ap.add_argument("--batch-size", type=int, default=64,
                    help="serve mode: static rows of the compiled batch")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="serve mode: micro-batcher max-wait flush")
    ap.add_argument("--rows-per-request", type=int, default=4)
    ap.add_argument("--registry-dir", default=None,
                    help="serve mode: publish versioned index snapshots here")
    ap.add_argument("--recluster-k", type=int, nargs="*", default=None,
                    help="extra cluster counts served from the cached "
                         "embedding (Stage 3 only, no second eigensolve)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall budget; slower requests count as "
                         "failures (cluster mode)")
    ap.add_argument("--strict", action="store_true",
                    help="cluster mode: run eagerly with EigConfig(strict=True)"
                         " — live escalation ladders, unconverged embeds raise")
    ap.add_argument("--inject-fault",
                    choices=["none", "nan-graph", "nan-query"],
                    default="none",
                    help="poison every odd request (nan-graph: cluster mode; "
                         "nan-query: serve mode) — fault-isolation smoke: "
                         "the loop must survive, exit code counts them)")
    return ap


def main(argv=None):
    import sys

    args = build_parser().parse_args(argv)
    enable_compile_cache()
    failures = (serve_cluster(args) if args.mode == "cluster"
                else serve_online(args)["failures"])
    # exit code = failure count (clamped below the shell's reserved range)
    # so orchestrators see partial failure without log parsing
    sys.exit(min(failures, 125))


if __name__ == "__main__":
    main()
