"""The program's own measurement: named scopes in the compiled program, the
operator-application counter, and the serving batcher's profiler spans."""
import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.lanczos as lz
from repro.core import state_io
from repro.core.chebyshev import operator_streams
from repro.core.operator import CooOperator
from repro.core.spectral import (EigConfig, EmbedState, GraphConfig,
                                 GraphState, SpectralPipeline)
from repro.kernels.knn_topk.ops import knn_topk
from repro.serve.batcher import BatchConfig, MicroBatcher
from repro.testing import faults

KEY = jax.random.PRNGKey(0)
OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%([^ ]+) = [^ ]+ ([a-z][a-z0-9\-]*)\(.*?'
    r'metadata=\{op_name="([^"]*)"', re.M)


def _blobs(k=3, n_per=30, d=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = (rng.permutation(np.eye(k, d)) * 20.0).astype(np.float32)
    x = np.concatenate([c + rng.normal(size=(n_per, d)) for c in centers])
    return jnp.asarray(x.astype(np.float32))


SOLVERS = {
    "lanczos_b1": EigConfig(),
    "lanczos_b4": EigConfig(block_size=4),
    "chebyshev": EigConfig(solver="chebyshev", cheb_degree=24),
}


def _compiled_ops(pipe, x):
    """``[(opcode, op_name path)]`` of the compiled fused job's HLO."""
    def job(x, key):
        return pipe.run_state(x, key).result

    text = jax.jit(job).lower(x, KEY).compile().as_text()
    return [(op, name.split("/")) for _, op, name in OP_NAME.findall(text)]


@pytest.mark.parametrize("solver", sorted(SOLVERS))
def test_compiled_program_names_stage2_parts(solver):
    pipe = SpectralPipeline(n_clusters=3, eig=SOLVERS[solver])
    ops = _compiled_ops(pipe, _blobs())

    def under(*scopes):
        return [op for op, path in ops
                if all(s in path for s in scopes)]

    # the COO operator's gather and segment-sum are the applications
    spmv = under("stage2", "spmv")
    assert {"gather", "scatter"} & set(spmv), spmv
    # every other gather or scatter of Stage 2 is bookkeeping of the
    # other two parts (the restart's T update, block QR's diagonal)
    assert not [op for op, path in ops if "stage2" in path
                and op in ("gather", "scatter")
                and not {"spmv", "orthogonalize", "restart"} & set(path)]
    # Gram-Schmidt's dots (Chebyshev: its whitening QR) under orthogonalize
    assert under("stage2", "orthogonalize")
    if solver.startswith("lanczos"):
        assert "dot" in under("stage2", "orthogonalize")
    # the projected eigenproblem and the Ritz rotation under restart
    restart = [path for op, path in ops if "restart" in path]
    assert any("eigh" in p[-1] for p in restart)
    assert "dot" in under("stage2", "restart")
    # the stage scopes around the other two stages
    assert under("stage1") and under("stage3")
    assert under("stage3", "kmeans_seed")


@pytest.mark.parametrize("solver", sorted(SOLVERS))
def test_operator_applications_match_solver_streams(solver):
    pipe = SpectralPipeline(n_clusters=3, eig=SOLVERS[solver])
    g = pipe.build_graph(_blobs())
    cfg = pipe._eig_config(g.adj.shape[0])
    res = lz.eigsh(CooOperator(g.adj), cfg, key=KEY)
    assert int(res.operator_applications) == lz.solver_streams(cfg, res)
    # and through the pipeline, fused under jit
    out = jax.jit(lambda x, k: pipe.run(x, k))(_blobs(), KEY)
    assert int(out.operator_applications) == lz.solver_streams(
        cfg, int(out.lanczos_restarts))


def test_operator_applications_under_fixed_restarts():
    pipe = SpectralPipeline(n_clusters=3, eig=EigConfig(fixed_restarts=4))
    g = pipe.build_graph(_blobs())
    cfg = pipe._eig_config(g.adj.shape[0])
    res = lz.eigsh(CooOperator(g.adj), cfg, key=KEY)
    assert int(res.restarts) == 5
    assert int(res.operator_applications) == lz.operator_passes(cfg, 5)


def test_chebyshev_counts_what_its_loops_run():
    """A tiny operator clamps the bounds estimate's steps to n - 1: the
    executed count says so where the static stream count cannot."""
    from repro.core.chebyshev import ChebConfig, bounds_steps

    w = np.abs(np.random.default_rng(0).normal(size=(8, 8))).astype(np.float32)
    w = (w + w.T) / 2
    from repro.sparse.formats import COO

    r, c = np.nonzero(w)
    op = CooOperator(COO(jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32),
                         jnp.asarray(w[r, c]), (8, 8)))
    cfg = ChebConfig(k=2, degree=6, n_signals=2, bounds_iters=12)
    res = lz.eigsh(op, cfg, key=KEY)
    assert bounds_steps(8, 12) == 7
    assert int(res.operator_applications) == operator_streams(cfg) - 5


def test_operator_applications_sum_over_escalations(monkeypatch):
    per_call = []
    with faults.forced_nonconvergence(recover_after=1):
        poisoned = lz.eigsh

        def recording(op, cfg, **kw):
            res = poisoned(op, cfg, **kw)
            per_call.append(int(res.operator_applications))
            return res

        monkeypatch.setattr(lz, "eigsh", recording)
        out = SpectralPipeline(n_clusters=3).run(_blobs(), KEY)
    assert len(per_call) == 2  # the poisoned attempt and the widened retry
    assert int(out.operator_applications) == sum(per_call)


def test_state_io_round_trips_the_count(tmp_path):
    pipe = SpectralPipeline(n_clusters=3)
    st = pipe.run_state(_blobs(), KEY)
    apps = int(st.result.operator_applications)
    assert apps > 0
    state_io.save_state(str(tmp_path / "new"), st, pipe)
    back, _ = state_io.load_state(str(tmp_path / "new"))
    assert int(back.embedding.operator_applications) == apps
    assert int(back.result.operator_applications) == apps


def test_state_io_restores_a_checkpoint_without_the_count():
    """A checkpoint written before the field existed restores with None,
    and positional construction of the state tuples still works."""
    pipe = SpectralPipeline(n_clusters=3)
    st = pipe.run_state(_blobs(), KEY)
    tree = state_io.state_to_tree(st, pipe)
    for k in ("embedding.operator_applications",
              "result.operator_applications"):
        del tree[k]
    back, _ = state_io.state_from_tree(tree)
    assert back.embedding.operator_applications is None
    assert back.result.operator_applications is None
    np.testing.assert_array_equal(back.result.labels, st.result.labels)
    e = st.embedding
    old = EmbedState(e.embedding, e.eigenvalues, e.residuals, e.restarts,
                     e.converged)
    assert old.operator_applications is None
    assert pipe.cluster(old, KEY).operator_applications is None


def _lattice_pipeline(**graph):
    side = np.arange(7, dtype=np.float32)
    x = np.stack(np.meshgrid(side, side, side, indexing="ij"), -1).reshape(-1, 3)
    cfg = dict(knn_k=16, impl="pallas", interpret=True, block_q=32, block_k=64)
    cfg.update(graph)
    return SpectralPipeline(n_clusters=3, graph=GraphConfig(**cfg)), jnp.asarray(x)


def test_knn_tiles_counted_by_the_one_chip_pipeline():
    """Stage 1 carries the kernel's merged and visited tiles: on a lattice
    in lattice order some tiles go unmerged."""
    pipe, x = _lattice_pipeline()
    st = pipe.run_state(x, KEY)
    _, _, want = knn_topk(x, 16, impl="pallas", interpret=True, block_q=32,
                          block_k=64, with_tiles=True)
    got = np.asarray(st.graph.knn_tiles)
    assert got.dtype == np.int32 and got.tolist() == np.asarray(want).tolist()
    merged, visited = got.tolist()
    assert visited == 11 * 6 and 0 < merged < visited


@pytest.mark.parametrize("graph", [dict(impl="ref", interpret=None),
                                   dict(method="lsh")], ids=["ref", "lsh"])
def test_knn_tiles_absent_without_the_kernel(graph):
    pipe, x = _lattice_pipeline(**graph)
    assert pipe.build_graph(x).knn_tiles is None


def test_state_io_round_trips_knn_tiles(tmp_path):
    pipe, x = _lattice_pipeline()
    st = pipe.run_state(x, KEY)
    tiles = np.asarray(st.graph.knn_tiles).tolist()
    state_io.save_state(str(tmp_path / "new"), st, pipe)
    back, _ = state_io.load_state(str(tmp_path / "new"))
    assert np.asarray(back.graph.knn_tiles).tolist() == tiles
    # a checkpoint written before the field existed restores None
    tree = state_io.state_to_tree(st, pipe)
    del tree["graph.knn_tiles"]
    old, _ = state_io.state_from_tree(tree)
    assert old.graph.knn_tiles is None
    np.testing.assert_array_equal(old.graph.deg, st.graph.deg)
    g = st.graph
    assert GraphState(g.adj, g.deg, g.inv_sqrt_deg).knn_tiles is None


def _batcher_spans(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("batcher."):
                    spans.append((ev.name, ev.start_ns, ev.duration_ns,
                                  dict(ev.stats)))
    return spans


def test_batcher_spans_account_for_every_flush(tmp_path):
    max_wait = 0.02
    cfg = BatchConfig(batch_size=8, max_wait_s=max_wait)
    rng = np.random.default_rng(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with MicroBatcher(lambda b: jnp.asarray(b) * 2.0, 3, cfg) as mb:
            futs = []
            for i in range(40):  # 79 rows in all
                futs.append(mb.submit(rng.normal(size=(1 + i % 3, 3))))
                if i % 7 == 0:
                    time.sleep(0.03)  # let the max wait flush some batches
            for f in futs:
                f.result(timeout=30)
    finally:
        jax.profiler.stop_trace()
    stats = mb.stats
    spans = _batcher_spans(str(tmp_path))
    flushes = [s for s in spans if s[0] == "batcher.flush"]
    assert len(flushes) == stats.batches
    attrs = [a for _, _, _, a in flushes]
    assert sum(a["rows"] for a in attrs) == stats.rows == 79
    assert sum(a["requests"] for a in attrs) == stats.requests == 40
    assert sum(a["full"] for a in attrs) == stats.full_flushes
    assert sorted(a["flush"] for a in attrs) == list(range(stats.batches))
    for a in attrs:
        assert 0 <= a["wait_us_max"] <= a["wait_us_sum"]
        # the oldest request waits out the max wait, plus scheduling slack
        assert a["wait_us_max"] <= (max_wait + 0.25) * 1e6
    by_id = {a["flush"]: (s, d) for _, s, d, a in flushes}
    children = [s for s in spans if s[0] in (
        "batcher.assemble", "batcher.call", "batcher.to_host",
        "batcher.resolve")]
    assert len(children) == 4 * stats.batches
    for name, s, d, a in children:
        fs, fd = by_id[a["flush"]]
        assert fs <= s and s + d <= fs + fd, name
    assert {s[0] for s in spans} >= {"batcher.idle", "batcher.fill_wait"}


def test_batcher_spans_cost_nothing_without_a_profiler():
    """Spans with no profiler running leave the batcher's results as they
    were: every request answered with its own rows."""
    with MicroBatcher(lambda b: jnp.asarray(b) + 1.0, 2,
                      BatchConfig(batch_size=4, max_wait_s=0.005)) as mb:
        outs = {}
        threads = [threading.Thread(
            target=lambda i=i: outs.__setitem__(
                i, mb.label(np.full((1, 2), i, np.float32), timeout=30)))
            for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    for i, o in outs.items():
        np.testing.assert_array_equal(o, np.full((1, 2), i + 1.0))
    assert mb.stats.requests == 12
