"""Open-loop serving: requests arrive on a schedule, whatever the server
does, and go through ``MicroBatcher`` into ``serve_fn``.

Set-up makes the served index, the deployment's generator's
``served_index``: the data the program serves, as weights are a model's,
made by the benchmark, so the reference below takes nothing the program
made.  The mix fixes the rate, the rows per request and the batcher's
settings.  The arrivals are the same Poisson gaps in every run, ordered by
the seed; the queries are drawn from the seed by the generator's
``queries``.

Latency runs from the time a request was due, not from when the generator
got round to it, so a late generator shows as latency; how late it ran is
reported too.  After the window, every request is waited for (up to a
minute past the close), and a sample drawn from the seed is compared with
the reference's out-of-sample labels.
"""
from __future__ import annotations

import shutil
import tempfile
import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from bench import deploy, gen, harness
from bench import reference as ref
from bench import trace as tr

DRAIN_S = 60.0  # how long past the window's close answers are waited for


def serving_inputs(cfg: dict, mix: dict, seed: int, seconds: float):
    """What a run serves, from the seed: the index (``served_index`` of the
    deployment's generator), the requests' due offsets and their queries
    ``[count, rows, 3]``, and the generator that then draws the compared
    sample."""
    g = deploy.generator(cfg)
    idx = g.served_index(cfg, cfg["data_seeds"][0])
    due_off = gen.arrival_offsets(mix["rate_hz"], seconds, mix["arrival_seed"],
                                  seed)
    rng = gen.rng_for(seed, 5)
    rows = mix["rows_per_request"]
    queries = g.queries(idx["points"], len(due_off) * rows,
                        rng).reshape(len(due_off), rows, 3)
    return idx, due_off, queries, rng


def on_host(idx: dict) -> dict:
    return {k: np.asarray(v) for k, v in idx.items()}


def control_readings(cfg: dict, mix: dict, seed: int,
                     seconds: float) -> Dict[str, float]:
    """The compared numbers of the control: the reference in the program's
    place, computed in bfloat16, over as many requests as a run compares."""
    idx, _, queries, rng = serving_inputs(cfg, mix, seed, seconds)
    idx = on_host(idx)
    count = len(queries)
    ids = sorted(rng.choice(count, min(mix["check_requests"], count),
                            replace=False).tolist())
    q = queries[ids].reshape(-1, 3)
    k, sigma = cfg["pipeline"]["knn_k"], cfg["pipeline"]["sigma"]
    _, h_ref = ref.oos_reference(idx["points"], idx["embedding"],
                                 idx["centroids"], q, k, sigma)
    lab, h = ref.oos_reference(idx["points"], idx["embedding"],
                               idx["centroids"], q, k, sigma, ref.bf16)
    return ref.compare_oos(lab, h, h_ref, idx["centroids"])


def latency_ms(due: np.ndarray, done: np.ndarray) -> np.ndarray:
    """Per-request latency from its due time; a request never answered
    (NaN) counts as infinitely late."""
    lat = (done - due) * 1e3
    return np.where(np.isnan(lat), np.inf, lat)


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        trace: bool, devs, t_start: float,
        wrap: Optional[Callable] = None) -> dict:
    """One run of an open-loop serving cell.  ``wrap`` replaces the serving
    function (the tests break the timed path with it)."""
    import jax
    import jax.numpy as jnp

    from repro.serve.batcher import BatchConfig, MicroBatcher
    from repro.serve.oos import OOSConfig, ServingIndex, serve_fn

    pipe = deploy.pipeline(cfg, devs)
    idx, due_off, queries, rng = serving_inputs(cfg, mix, seed, seconds)
    t_data = time.time() - t_start
    oos = OOSConfig.from_graph_config(pipe.graph)
    index = ServingIndex(points=jnp.asarray(idx["points"]),
                         embedding=idx["embedding"],
                         centroids=idx["centroids"], labels=idx["labels"],
                         config=oos)
    bs, rows = mix["batch_size"], mix["rows_per_request"]
    count = len(due_off)

    def call(batch):
        return serve_fn(index, batch)

    if wrap is not None:
        call = wrap(call)
    jax.block_until_ready(call(np.zeros((bs, 3), np.float32)))  # compile

    flush = {"start": 0.0}
    flushes = []

    def fn(batch):
        with jax.profiler.TraceAnnotation("serve_batch"):
            t0 = time.perf_counter()
            flush["start"] = t0
            out = jax.block_until_ready(call(batch))
            flushes.append(time.perf_counter() - t0)
        return out

    due = np.empty(count)
    done = np.full(count, np.nan)
    flushed = np.full(count, np.nan)
    answers: Dict[int, object] = {}
    errors = [0]
    left = [count]
    all_done = threading.Event()
    lock = threading.Lock()
    sample = set(rng.choice(count, min(mix["check_requests"], count),
                            replace=False).tolist())

    def on_done(i):
        def cb(fut):
            t = time.perf_counter()
            with lock:
                if fut.exception() is None:
                    done[i] = t
                    flushed[i] = flush["start"]
                    if i in sample:
                        answers[i] = fut.result()
                else:
                    errors[0] += 1
                left[0] -= 1
                if left[0] == 0:
                    all_done.set()
        return cb

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    lag = np.zeros(count)
    with MicroBatcher(fn, 3, BatchConfig(batch_size=bs,
                                         max_wait_s=mix["max_wait_s"])) as mb:
        if trace:
            jax.profiler.start_trace(trace_dir)
        t_w0 = time.perf_counter()
        setup_s = time.time() - t_start
        due[:] = t_w0 + due_off
        with jax.profiler.TraceAnnotation("window"):
            for i in range(count):
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                lag[i] = time.perf_counter() - due[i]
                mb.submit(queries[i]).add_done_callback(on_done(i))
            all_done.wait(timeout=max(
                0.0, t_w0 + seconds + DRAIN_S - time.perf_counter()))
        if trace:
            jax.profiler.stop_trace()
        stats = mb.stats
    device = harness.device_record(devs)
    del index
    idx = on_host(idx)

    lat = latency_ms(due, done)
    ok = ~np.isnan(done)
    n_ok = int(ok.sum())
    failed = count - n_ok
    span = (np.nanmax(done) - due[0]) if n_ok else np.inf
    metrics = {
        "serve_p95_ms": harness.metric(np.percentile(lat, 95), "ms"),
        "serve_labels_per_s": harness.metric(n_ok * rows / span, "labels/s"),
        "setup_s": harness.metric(setup_s, "s")}

    # compare the sampled answers with the reference
    ids = sorted(i for i in sample if i in answers)
    limits = cfg["limits"]
    if ids:
        q = queries[ids].reshape(-1, 3)
        _, h_ref = ref.oos_reference(idx["points"], idx["embedding"],
                                     idx["centroids"], q,
                                     cfg["pipeline"]["knn_k"],
                                     cfg["pipeline"]["sigma"])
        lab = np.concatenate([np.asarray(answers[i].labels) for i in ids])
        emb = np.concatenate([np.asarray(answers[i].embedding) for i in ids])
        nums = ref.compare_oos(lab, emb, h_ref, idx["centroids"])
    else:
        nums = {}
    checks = {name: {"value": nums.get(name), "limit": limits[name]}
              for name in cfg["checks_serve"]}
    extra = {"requests": count,
             "setup_phases_s": {"data": t_data, "window": setup_s},
             "lag_p95_ms": float(np.percentile(lag, 95) * 1e3),
             "lag_max_ms": float(lag.max() * 1e3), "batches": stats.batches,
             "call_ms_mean": float(np.mean(flushes) * 1e3) if flushes else None,
             "fill": stats.fill, "compared_rows": len(ids) * rows}
    result = {"attempted": count, "failed": failed, "metrics": metrics,
              "device": device, "checks": checks, "extra": extra}
    if trace:
        reduced = tr.load_xplane(trace_dir, ("window", "serve_batch"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["trace"] = reduced
        result["ctx"] = {
            "kind": "open_loop", "cfg": cfg, "mix": mix,
            "queue_ms": ((flushed - due)[ok] * 1e3).tolist(),
            "call_ms": [f * 1e3 for f in flushes],
            "pool_n": int(len(idx["points"])), "trace": reduced}
    return result
