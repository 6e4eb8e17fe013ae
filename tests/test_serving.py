"""The online-serving subsystem (ISSUE 9): OOS parity with full
re-clustering, the padded-batch bitwise contract, mini-batch streaming
convergence, registry swap/rollback atomicity, fault-injected bursts, and
pipeline-state checkpoint/resume.

The acceptance gates pinned here:

* OOS labels for held-out points agree with a full pipeline re-clustering
  of pool+queries at ARI >= 0.95 (exact and LSH neighbor search);
* serve_fn outputs for real rows are BITWISE invariant to pad rows under
  jit (the micro-batcher's one-compiled-function contract);
* mini-batch k-means lands within 10% of full-Lloyd inertia;
* a registry publish that fails its health gate leaves ACTIVE untouched
  (that is the rollback) and deletes the rejected snapshot;
* a poisoned request in a shared batch fails structurally while its batch
  neighbors' rows stay bitwise correct.
"""
import functools
import json
import os
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core.kmeans as km
from repro.core import health, state_io
from repro.core.health import HealthConfig, PipelineError
from repro.core.kmeans import KMeansConfig
from repro.core.spectral import EigConfig, SpectralPipeline
from repro.serve import (
    BatchConfig,
    EmbeddingRegistry,
    MicroBatcher,
    OOSConfig,
    RegistryGateError,
    ServingIndex,
    adjusted_rand_index,
    build_index,
    drift,
    index_problems,
    needs_refresh,
    rebase,
    serve_fn,
    stream_from_index,
    stream_init,
    stream_update,
)
from repro.testing import faults

KEY = jax.random.PRNGKey(0)
K, D = 3, 6


def _blobs(n_per, k=K, d=D, seed=0, scale=20.0):
    rng = np.random.default_rng(seed)
    centers = (np.eye(k, d) * scale).astype(np.float32)
    x = np.concatenate([centers[i] + rng.normal(size=(n_per, d))
                        for i in range(k)]).astype(np.float32)
    truth = np.repeat(np.arange(k), n_per)
    return jnp.asarray(x), truth


@pytest.fixture(scope="module")
def trained():
    """One pipeline run shared by the OOS/batcher/stream tests."""
    pool, truth = _blobs(n_per=80)
    pipe = SpectralPipeline(n_clusters=K)
    result = pipe.run(pool, KEY)
    index = build_index(pool, result, config=OOSConfig(knn_k=10, sigma=1.0))
    return {"pool": pool, "truth": truth, "pipe": pipe,
            "result": result, "index": index}


# ---------------------------------------------------------------------------
# OOS parity with full re-clustering (THE acceptance gate)
# ---------------------------------------------------------------------------

def test_oos_parity_with_full_reclustering(trained):
    pool = trained["pool"]
    queries, _ = _blobs(n_per=40, seed=7)
    served = serve_fn(trained["index"], queries)
    # the expensive alternative: rerun the whole pipeline on pool+queries
    full = trained["pipe"].run(jnp.concatenate([pool, queries]),
                               jax.random.PRNGKey(1))
    full_q = np.asarray(full.labels)[pool.shape[0]:]
    ari = adjusted_rand_index(np.asarray(served.labels), full_q)
    assert ari >= 0.95, f"OOS/full-reclustering ARI {ari:.3f} < 0.95"


def test_oos_lsh_matches_exact(trained):
    queries, _ = _blobs(n_per=40, seed=11)
    exact = serve_fn(trained["index"], queries)
    lsh_index = ServingIndex(
        points=trained["index"].points,
        embedding=trained["index"].embedding,
        centroids=trained["index"].centroids,
        labels=trained["index"].labels,
        config=OOSConfig(knn_k=10, sigma=1.0, method="lsh"))
    lsh = serve_fn(lsh_index, queries)
    ari = adjusted_rand_index(np.asarray(lsh.labels),
                              np.asarray(exact.labels))
    assert ari >= 0.95, f"LSH/exact OOS ARI {ari:.3f} < 0.95"


def test_oos_weight_sum_flags_far_queries(trained):
    far = jnp.full((4, D), 1e4, jnp.float32)
    out = serve_fn(trained["index"], far)
    assert np.asarray(out.weight_sum).max() == 0.0  # all weights underflow
    assert np.isfinite(np.asarray(out.embedding)).all()  # still servable


def test_build_index_needs_static_k_under_jit(trained):
    pool, result = trained["pool"], trained["result"]

    with pytest.raises(ValueError, match="static n_clusters"):
        jax.jit(lambda p, r: build_index(p, r))(pool, result)
    idx = jax.jit(lambda p, r: build_index(p, r, n_clusters=K))(pool, result)
    np.testing.assert_array_equal(np.asarray(idx.labels),
                                  np.asarray(trained["index"].labels))


# ---------------------------------------------------------------------------
# Padded-batch bitwise invariance (the one-compiled-function contract)
# ---------------------------------------------------------------------------

def test_padded_batch_bitwise_invariance(trained):
    B = 32
    q, _ = _blobs(n_per=4, seed=3)  # 12 real rows
    other, _ = _blobs(n_per=3, seed=5)  # 9 different co-batched rows
    b1 = jnp.zeros((B, D), jnp.float32).at[:12].set(q)
    b2 = jnp.zeros((B, D), jnp.float32).at[:12].set(q).at[12:21].set(other)
    o1 = serve_fn(trained["index"], b1)
    o2 = serve_fn(trained["index"], b2)
    for field in o1._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(o1, field))[:12],
            np.asarray(getattr(o2, field))[:12],
            err_msg=f"OOSResult.{field} not pad-invariant")


def test_microbatcher_matches_direct_call(trained):
    B = 16
    index = trained["index"]
    reqs = [np.asarray(_blobs(n_per=2, seed=s)[0]) for s in range(5)]
    with MicroBatcher(functools.partial(serve_fn, index), D,
                      BatchConfig(batch_size=B, max_wait_s=0.003)) as mb:
        futs = [mb.submit(r) for r in reqs]
        outs = [f.result(timeout=30.0) for f in futs]
    for r, out in zip(reqs, outs):
        padded = jnp.zeros((B, D), jnp.float32).at[:r.shape[0]].set(r)
        direct = serve_fn(index, padded)
        np.testing.assert_array_equal(out.labels,
                                      np.asarray(direct.labels)[:r.shape[0]])
        np.testing.assert_array_equal(
            out.embedding, np.asarray(direct.embedding)[:r.shape[0]])


def test_microbatcher_flush_isolation(trained):
    """A serving-fn exception fails the futures of that flush only; the
    thread survives and later submits succeed."""
    index = trained["index"]
    good = functools.partial(serve_fn, index)

    def bad(batch):
        raise RuntimeError("injected flush fault")

    with MicroBatcher(good, D, BatchConfig(batch_size=8,
                                           max_wait_s=0.003)) as mb:
        mb.set_fn(bad)
        f1 = mb.submit(np.zeros((2, D), np.float32))
        with pytest.raises(RuntimeError, match="injected flush fault"):
            f1.result(timeout=30.0)
        mb.set_fn(good)
        out = mb.label(np.asarray(trained["pool"])[:3], timeout=30.0)
        assert out.labels.shape == (3,)
        assert mb.stats.failed_batches == 1
    assert mb.stats.batches >= 1


def test_fault_injected_burst_isolates_poisoned_requests(trained):
    """PR 8 contract at the batch level: NaN-poisoned requests
    (repro.testing.faults) fail structurally via numeric_problems while
    clean requests IN THE SAME BATCH return bitwise-correct rows."""
    index = trained["index"]
    B = 32
    clean = [np.asarray(_blobs(n_per=1, seed=s)[0]) for s in range(4)]  # 3 rows each
    poisoned = [faults.poison_points(c, n_bad=2, seed=s)
                for s, c in enumerate(clean[:2])]
    with MicroBatcher(functools.partial(serve_fn, index), D,
                      BatchConfig(batch_size=B, max_wait_s=0.05)) as mb:
        futs = {}
        for i, r in enumerate(clean):
            futs[("clean", i)] = mb.submit(r)
        for i, r in enumerate(poisoned):
            futs[("poisoned", i)] = mb.submit(r)
        outs = {kk: f.result(timeout=30.0) for kk, f in futs.items()}
    assert mb.stats.batches == 1  # everything rode one padded batch
    for i, r in enumerate(clean):
        out = outs[("clean", i)]
        assert health.numeric_problems(
            {"embedding": out.embedding, "dist2": out.dist2}) == ()
        padded = jnp.zeros((B, D), jnp.float32).at[:r.shape[0]].set(r)
        np.testing.assert_array_equal(
            out.labels, np.asarray(serve_fn(index, padded).labels)[:r.shape[0]])
    for i in range(len(poisoned)):
        out = outs[("poisoned", i)]
        problems = health.numeric_problems(
            {"embedding": out.embedding, "dist2": out.dist2})
        assert problems, "poisoned request should fail the post-hoc gate"


# ---------------------------------------------------------------------------
# Mini-batch streaming k-means
# ---------------------------------------------------------------------------

def _unit_rows(n_per, k=K, ke=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = np.eye(k, ke).astype(np.float32)
    x = np.concatenate([centers[i] + 0.05 * rng.normal(size=(n_per, ke))
                        for i in range(k)]).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return jnp.asarray(rng.permutation(x))


def test_stream_minibatch_converges_to_lloyd_inertia():
    h = _unit_rows(n_per=200)
    full = km.kmeans(h, KMeansConfig(k=K, max_iters=50), KEY)
    # stream the same rows in batches of 32 from a rough warm start
    init = h[:K] + 0.1
    state = stream_init(init)
    for i in range(0, h.shape[0], 32):
        state, _ = stream_update(state, h[i:i + 32])
    _, dmin = km.assign_ref(h, state.centroids)
    stream_inertia = float(dmin.sum())
    assert stream_inertia <= 1.10 * float(full.inertia) + 1e-6, (
        f"mini-batch inertia {stream_inertia:.4f} vs Lloyd "
        f"{float(full.inertia):.4f}")


def test_stream_update_pad_correction_is_exact():
    h = _unit_rows(n_per=40, seed=2)
    batch = h[:24]
    padded = jnp.zeros((32, h.shape[1]), jnp.float32).at[:24].set(batch)
    s0 = stream_init(h[:K])
    s_plain, _ = stream_update(s0, batch)
    s_padded, _ = stream_update(s0, padded, n_pad=8)
    np.testing.assert_array_equal(np.asarray(s_plain.counts),
                                  np.asarray(s_padded.counts))
    np.testing.assert_array_equal(np.asarray(s_plain.centroids),
                                  np.asarray(s_padded.centroids))


def test_stream_drift_detection_and_rebase(trained):
    state = stream_from_index(trained["index"])
    assert float(drift(state)) == 0.0
    # traffic drawn far from every training cluster drags centroids
    rng = np.random.default_rng(5)
    shifted = jnp.asarray(
        rng.normal(size=(512, trained["index"].embedding.shape[1]))
        .astype(np.float32) + 3.0)
    shifted = shifted / jnp.linalg.norm(shifted, axis=1, keepdims=True)
    for i in range(0, 512, 64):
        state, _ = stream_update(state, shifted[i:i + 64])
    assert bool(needs_refresh(state))
    state = rebase(state)
    assert float(drift(state)) == 0.0
    assert int(state.updates) == 0


# ---------------------------------------------------------------------------
# Registry: versioned swap, gate rejection = rollback, operator rollback
# ---------------------------------------------------------------------------

def _toy_index(tag: float) -> ServingIndex:
    n, d, ke = 12, 4, 3
    rng = np.random.default_rng(int(tag))
    h = rng.normal(size=(n, ke)).astype(np.float32)
    return ServingIndex(
        points=jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
        embedding=jnp.asarray(h),
        centroids=jnp.asarray(h[:K] + np.float32(tag)),
        labels=jnp.asarray(rng.integers(0, K, size=n).astype(np.int32)),
        config=OOSConfig(knn_k=3))


def test_registry_publish_load_rollback(tmp_path):
    reg = EmbeddingRegistry(str(tmp_path))
    v1 = reg.publish(_toy_index(1.0))
    v2 = reg.publish(_toy_index(2.0))
    assert (v1, v2) == (1, 2)
    assert reg.active_version() == 2
    ver, idx = reg.load()
    assert ver == 2
    np.testing.assert_array_equal(np.asarray(idx.centroids),
                                  np.asarray(_toy_index(2.0).centroids))
    assert idx.config == OOSConfig(knn_k=3)
    assert reg.rollback() == 1
    ver, idx = reg.load()
    assert ver == 1
    np.testing.assert_array_equal(np.asarray(idx.centroids),
                                  np.asarray(_toy_index(1.0).centroids))


def test_registry_gate_rejection_is_rollback(tmp_path):
    reg = EmbeddingRegistry(str(tmp_path))
    reg.publish(_toy_index(1.0))
    bad = _toy_index(2.0)
    bad = ServingIndex(points=bad.points, embedding=bad.embedding,
                       centroids=bad.centroids.at[0, 0].set(jnp.nan),
                       labels=bad.labels, config=bad.config)
    with pytest.raises(RegistryGateError, match="nonfinite_centroids"):
        reg.publish(bad)
    # ACTIVE untouched, rejected snapshot gone: serving continues on v1
    assert reg.active_version() == 1
    assert reg.versions() == [1]
    _, idx = reg.load()
    assert np.isfinite(np.asarray(idx.centroids)).all()


def test_registry_active_swap_is_atomic(tmp_path):
    reg = EmbeddingRegistry(str(tmp_path))
    reg.publish(_toy_index(1.0))
    reg.publish(_toy_index(2.0))
    # no half-written pointer file left behind by the tmp+rename idiom
    assert not os.path.exists(os.path.join(str(tmp_path), "ACTIVE.json.tmp"))
    # a corrupt ACTIVE falls back to the newest intact snapshot
    with open(os.path.join(str(tmp_path), "ACTIVE.json"), "w") as f:
        f.write("{corrupt")
    assert reg.active_version() == 2
    ver, _ = reg.load()
    assert ver == 2


def test_index_problems_gate():
    good = _toy_index(1.0)
    assert index_problems(good) == ()
    nan_pts = ServingIndex(points=good.points.at[0, 0].set(jnp.nan),
                           embedding=good.embedding,
                           centroids=good.centroids, labels=good.labels,
                           config=good.config)
    assert any("nonfinite_points" in p for p in index_problems(nan_pts))
    mismatched = ServingIndex(points=good.points, embedding=good.embedding,
                              centroids=good.centroids,
                              labels=good.labels[:-1], config=good.config)
    assert any("shape_mismatch" in p for p in index_problems(mismatched))


# ---------------------------------------------------------------------------
# numeric_problems (the serving loop's structural gate)
# ---------------------------------------------------------------------------

def test_numeric_problems_scans_nested_trees():
    assert health.numeric_problems({"a": 1.0, "b": [2.0, 3.0]}) == ()
    probs = health.numeric_problems(
        {"m": {"x": np.float32("nan")}, "ok": "a string", "n": None})
    assert probs == ("non-finite value at 'm.x'",)
    probs = health.numeric_problems({"v": np.array([1.0, np.inf, np.nan])},
                                    context="cell")
    assert "2 entries" in probs[0] and "cell" in probs[0]


# ---------------------------------------------------------------------------
# The serving launcher (python -m repro.launch.serve)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("argv", [
    ["--mode", "decode"], ["--arch", "qwen3-0.6b"], ["--smoke"],
    ["--seq", "64"], ["--tokens", "16"]], ids=lambda a: a[0].lstrip("-"))
def test_serve_parser_has_only_the_clustering_modes(argv):
    """The launcher has two modes, clustering jobs and OOS labels: any
    other mode, and flags of neither, are refused as usage errors."""
    from repro.launch.serve import build_parser

    with pytest.raises(SystemExit) as e:
        build_parser().parse_args(argv)
    assert e.value.code == 2


def test_serve_mode_exit_code_counts_poisoned_queries(tmp_path):
    """``--mode serve`` with every odd request poisoned: the loop serves the
    rest and exits with the failure count, 4 of 8."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--mode", "serve",
         "--n", "512", "--clusters", "4", "--dim", "8", "--requests", "8",
         "--inject-fault", "nan-query",
         "--registry-dir", str(tmp_path / "reg")],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 4, out.stdout + out.stderr
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["event"] == "serve_summary" and summary["failures"] == 4
    errors = [json.loads(ln) for ln in out.stderr.splitlines()
              if ln.startswith('{"event": "request_error"')]
    assert [e["req"] for e in errors] == [1, 3, 5, 7]


# ---------------------------------------------------------------------------
# Pipeline-state checkpoint / resume
# ---------------------------------------------------------------------------

def test_state_roundtrip_bitwise(tmp_path):
    x, _ = _blobs(n_per=40, seed=9)
    pipe = SpectralPipeline(n_clusters=K)
    st = pipe.run_state(x, KEY)
    state_io.save_state(str(tmp_path), st, pipe)
    st2, pipe_dict = state_io.load_state(str(tmp_path), pipe)
    assert pipe_dict == pipe.to_dict()
    assert st2.provenance == st.provenance
    np.testing.assert_array_equal(np.asarray(st2.result.labels),
                                  np.asarray(st.result.labels))
    np.testing.assert_array_equal(np.asarray(st2.result.embedding),
                                  np.asarray(st.result.embedding))
    np.testing.assert_array_equal(np.asarray(st2.graph.deg),
                                  np.asarray(st.graph.deg))


def test_checkpoint_on_error_then_resume(tmp_path):
    """A PipelineError saves the completed-stage prefix; resume skips those
    stages and lands bitwise on the no-fault result."""
    x, _ = _blobs(n_per=40, seed=4)
    pipe = SpectralPipeline(n_clusters=K,
                            eig=EigConfig(strict=True, max_restarts=60),
                            health=HealthConfig(max_attempts=1))
    with pytest.raises(PipelineError) as ei:
        with faults.forced_nonconvergence():
            pipe.run(x, KEY, checkpoint_dir=str(tmp_path))
    assert ei.value.checkpoint == str(tmp_path)
    assert "resume_from" in str(ei.value)
    # the saved prefix holds Stage 1 but not the failed embed
    st, _ = state_io.load_state(str(tmp_path))
    assert "prepare" in st.provenance
    assert st.embedding is None
    out = pipe.run(resume_from=str(tmp_path))
    fresh = pipe.run(x, KEY)
    np.testing.assert_array_equal(np.asarray(out.labels),
                                  np.asarray(fresh.labels))


def test_resume_rejects_conflicting_inputs(tmp_path):
    x, _ = _blobs(n_per=30, seed=6)
    pipe = SpectralPipeline(n_clusters=K)
    st = pipe.run_state(x, KEY)
    state_io.save_state(str(tmp_path), st, pipe)
    with pytest.raises(ValueError, match="resume_from"):
        pipe.run(x, KEY, resume_from=str(tmp_path))


def test_sharded_checkpoint_resume_parity(tmp_path):
    """A ShardedCOO input round-trips through the state codec (kind-tagged
    meta) and a checkpoint-on-error resume lands bitwise on the no-fault
    sharded result."""
    import dataclasses as _dc

    from repro.data.sbm import sbm_graph
    from repro.sparse.distributed import ShardedCOO, partition_coo_by_rows

    coo, _ = sbm_graph(100, 4, 0.2, 0.01, seed=3)
    sm = partition_coo_by_rows(coo, 4)
    pipe = SpectralPipeline(n_clusters=4,
                            eig=EigConfig(strict=True, max_restarts=60),
                            health=HealthConfig(max_attempts=1))
    fresh = pipe.run(sm, KEY)

    # codec roundtrip keeps the sharded layout and every bucket bitwise
    st = pipe.run_state(sm, KEY)
    st2, _ = state_io.state_from_tree(state_io.state_to_tree(st, pipe))
    for name in ("input_graph",):
        a, b = getattr(st, name), getattr(st2, name)
        assert isinstance(b, ShardedCOO), type(b)
        assert b.shape == a.shape and b.num_shards == a.num_shards
    adj, adj2 = st.graph.adj, st2.graph.adj
    assert isinstance(adj2, ShardedCOO)
    np.testing.assert_array_equal(np.asarray(adj2.row_local),
                                  np.asarray(adj.row_local))
    np.testing.assert_array_equal(np.asarray(adj2.col), np.asarray(adj.col))
    np.testing.assert_array_equal(np.asarray(adj2.val), np.asarray(adj.val))

    # checkpoint on a forced embed failure, then resume from the prefix
    with pytest.raises(PipelineError):
        with faults.forced_nonconvergence():
            pipe.run(sm, KEY, checkpoint_dir=str(tmp_path))
    st, _ = state_io.load_state(str(tmp_path))
    assert "prepare" in st.provenance and st.embedding is None
    assert isinstance(st.graph.adj, ShardedCOO)
    out = pipe.run(resume_from=str(tmp_path))
    np.testing.assert_array_equal(np.asarray(out.labels),
                                  np.asarray(fresh.labels))


# ---------------------------------------------------------------------------
# oversized-request splitting (batcher) and persistent LSH tables
# ---------------------------------------------------------------------------

def test_batcher_splits_oversized_request():
    """A request larger than batch_size is split into chunks inside the
    batcher and the parent future resolves to the bitwise reassembly."""
    d = 4

    def fn(batch):
        return {"double": batch * 2.0, "sum": batch.sum(axis=1)}

    with MicroBatcher(fn, d, BatchConfig(batch_size=8,
                                         max_wait_s=0.005)) as mb:
        big = np.arange(150 * d, dtype=np.float32).reshape(150, d)
        out = mb.submit(big).result(timeout=60)
        assert out["double"].shape == (150, d)
        np.testing.assert_array_equal(out["double"], big * 2.0)
        np.testing.assert_array_equal(out["sum"], big.sum(axis=1))
        assert mb.stats.split_requests == 1
        assert mb.stats.rows == 150


def test_batcher_split_failure_isolation():
    """A failing flush fails only the requests riding in it: the split
    request whose chunk was poisoned gets the error, a co-queued healthy
    request still resolves."""
    d = 4

    def picky_fn(batch):
        if np.isnan(batch).any():
            raise ValueError("poisoned batch")
        return batch * 2.0

    with MicroBatcher(picky_fn, d, BatchConfig(batch_size=8,
                                               max_wait_s=0.005)) as mb:
        poisoned = np.ones((20, d), np.float32)
        poisoned[13, 2] = np.nan
        f_bad = mb.submit(poisoned)
        good = np.ones((3, d), np.float32)
        f_good = mb.submit(good)
        np.testing.assert_array_equal(f_good.result(timeout=60), good * 2.0)
        assert isinstance(f_bad.exception(timeout=60), ValueError)
        assert mb.stats.failed_batches >= 1


def test_persistent_lsh_tables_match_rehash(trained):
    """build_index persists the pool's LSH tables; serving with them agrees
    with the historical hash-pool-per-call path and keeps the ARI gate."""
    import dataclasses as _dc

    lsh_index = build_index(
        trained["pool"], trained["result"],
        config=OOSConfig(knn_k=10, sigma=1.0, method="lsh"))
    assert lsh_index.lsh_tables is not None
    assert lsh_index.lsh_tables.order.shape[1] == trained["pool"].shape[0]
    queries, _ = _blobs(n_per=40, seed=13)
    out_new = serve_fn(lsh_index, queries)
    out_old = serve_fn(_dc.replace(lsh_index, lsh_tables=None), queries)
    agree = float((np.asarray(out_new.labels)
                   == np.asarray(out_old.labels)).mean())
    assert agree >= 0.99, f"persistent/rehash label agreement {agree:.3f}"
    exact = serve_fn(trained["index"], queries)
    ari = adjusted_rand_index(np.asarray(out_new.labels),
                              np.asarray(exact.labels))
    assert ari >= 0.95, f"persistent-LSH/exact ARI {ari:.3f} < 0.95"


def test_registry_roundtrip_persists_lsh_tables(tmp_path, trained):
    """publish → load keeps the LSH tables (no silent rehash fallback after
    a registry restore) and the restored index serves identical labels."""
    lsh_index = build_index(
        trained["pool"], trained["result"],
        config=OOSConfig(knn_k=10, sigma=1.0, method="lsh"))
    reg = EmbeddingRegistry(str(tmp_path))
    reg.publish(lsh_index)
    _, loaded = reg.load()
    assert loaded.lsh_tables is not None
    np.testing.assert_array_equal(np.asarray(loaded.lsh_tables.order),
                                  np.asarray(lsh_index.lsh_tables.order))
    np.testing.assert_array_equal(np.asarray(loaded.lsh_tables.codes),
                                  np.asarray(lsh_index.lsh_tables.codes))
    queries, _ = _blobs(n_per=20, seed=17)
    np.testing.assert_array_equal(
        np.asarray(serve_fn(loaded, queries).labels),
        np.asarray(serve_fn(lsh_index, queries).labels))
