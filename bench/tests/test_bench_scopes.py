"""The program's named scopes read back for the device trace, the Stage-2
per-layer readers, and a chip trace recorded with the program's scopes and
spans."""
import json
from pathlib import Path

import pytest

from bench import harness, scopes
from bench import trace as tr

DATA = Path(__file__).parent / "data"

STAGE2_SCOPES = {"jit_stage2": {
    "fusion.1": "jit(stage2)/stage2/while/body/closed_call/spmv/scatter-add",
    "fusion.2": "jit(stage2)/stage2/while/body/closed_call/spmv/gather",
    "dot.3": "jit(stage2)/stage2/while/body/orthogonalize/dot_general",
    "custom-call.4": "jit(stage2)/stage2/while/body/restart/eigh",
    "fusion.5": "jit(stage2)/stage2/add",
    "copy-done.6": ""}}


def stage2_trace():
    """One chip, a window of 10,000 ns: Stage 1's kernel in its program,
    then a Stage-2 run with two products (3,000 ns under ``spmv``), one
    Gram-Schmidt pass (500), one restart (1,000) and 150 ns of neither."""
    return {
        "device": {"/device:TPU:0": [
            ["knn_topk.1", "custom-call", 100, 700],
            ["fusion.1", "fusion", 1000, 2000], ["fusion.2", "fusion", 3000, 1000],
            ["dot.3", "convolution", 4000, 500],
            ["custom-call.4", "custom-call", 5000, 1000],
            ["fusion.5", "fusion", 6000, 100],
            ["copy-done.6", "copy-done", 6100, 50]]},
        "modules": {"/device:TPU:0": [["jit_stage1(1)", 0, 900],
                                      ["jit_stage2(2)", 1000, 8000]]},
        "host": [["window", 0, 10000], ["stage1", 0, 950],
                 ["stage2", 950, 8100]]}


def test_scope_map_reads_the_compiled_programs_metadata():
    import jax
    import jax.numpy as jnp

    def stage2(x):
        with jax.named_scope("stage2"):
            with jax.named_scope("spmv"):
                y = jnp.sin(x) @ x.T
            return y.sum()

    text = jax.jit(stage2).lower(jnp.ones((8, 8))).compile().as_text()
    (prog, ops), = scopes.scope_map(text).items()
    assert prog == "jit_stage2"
    assert any(scopes.scope_of(n) == "spmv" for n in ops.values())
    assert "" in ops.values()  # instructions with no op_name are kept too
    assert scopes.scope_of("jit(f)/stage2/while/body/spmv/gather") == "spmv"
    assert scopes.scope_of("jit(f)/stage2/add") == "stage2"
    assert scopes.scope_of("jit(f)/add") is None
    assert scopes.scope_of("") is None
    assert scopes.program_of("jit_stage2(123)") == "jit_stage2"
    with pytest.raises(ValueError):
        scopes.scope_map("not hlo")


def test_scope_seconds_by_the_program_run_that_holds_the_op():
    t = stage2_trace()
    assert scopes.scope_seconds(t, STAGE2_SCOPES, "spmv") == \
        pytest.approx(3e-6)
    assert scopes.scope_seconds(t, STAGE2_SCOPES, "orthogonalize") == \
        pytest.approx(5e-7)
    assert scopes.scope_seconds(t, STAGE2_SCOPES, "stage2") == \
        pytest.approx(4.6e-6)
    assert scopes.scope_seconds(
        t, STAGE2_SCOPES, ("spmv", "orthogonalize", "restart")) == \
        pytest.approx(4.5e-6)
    # clipped to the window; an op outside any known program is not counted
    assert scopes.scope_seconds(t, STAGE2_SCOPES, "spmv", (2000, 3500)) == \
        pytest.approx(1.5e-6)
    assert scopes.scope_seconds(t, {}, "spmv") == 0
    # the same instruction name in another program is not this one
    t["modules"]["/device:TPU:0"][1][0] = "jit_other(2)"
    assert scopes.scope_seconds(t, STAGE2_SCOPES, "spmv") == 0


def job_ctx(trace, jobs, smap=STAGE2_SCOPES, config="syn200"):
    cfg = harness.load_config(harness.load_benchmark(), config)
    return {"kind": "jobs", "cfg": cfg, "trace": trace, "jobs": jobs,
            "scopes": smap, "window": (0, 10000),
            "lanczos": {"basis": 4, "keep": 2, "block": 1},
            "peaks": harness.peaks("TPU v5 lite")}


def read(metric, ctx):
    return harness.layer_reader(metric).read(ctx)


def test_stage2_readers():
    # one restart cycle of a 4-wide basis: 4 applications, as reported
    jobs = [{"restarts": 1, "km_iters": 3, "nnz": 1000}]
    ctx = job_ctx(stage2_trace(), jobs)
    assert scopes.applications(ctx) == [4]
    assert read("spmv_ms", ctx) == pytest.approx(7.5e-4)  # 3,000 ns / 4
    assert read("lanczos_orth_ms", ctx) == pytest.approx(1.25e-4)
    assert read("lanczos_restart_ms", ctx) == pytest.approx(1e-3)
    share = read("spmv_roofline", ctx)
    # four products over 1,000 nonzeros of a 20,000-node graph, each
    # streaming 4 x (4 x 1,000 + 20,000) bytes at 819 GB/s, in 3,000 ns
    assert share["bound"] == "memory"
    assert share["value"] == pytest.approx(
        100 * 4 * 4 * (4 * 1000 + 20000) / 819e9 / 3e-6)
    # the program's own counter, where the run passes it, is the base
    counted = [dict(jobs[0], operator_applications=2)]
    assert scopes.applications(job_ctx(stage2_trace(), counted)) == [2]
    assert read("spmv_ms", job_ctx(stage2_trace(), counted)) == \
        pytest.approx(1.5e-3)


def test_stage2_readers_find_nothing_without_the_scopes():
    """A program without the scopes (the parent's), a scope map of another
    program, or no jobs: each reader returns nothing and raises nothing."""
    jobs = [{"restarts": 1, "km_iters": 3, "nnz": 1000}]
    bare = {"jit_stage2": {k: "jit(stage2)/while/body/" + k
                           for k in STAGE2_SCOPES["jit_stage2"]}}
    other = {"jit_stage2": {"fusion.9": "jit(stage2)/stage2/spmv/gather"}}
    for metric in ("spmv_ms", "spmv_roofline", "lanczos_orth_ms",
                   "lanczos_restart_ms"):
        assert read(metric, job_ctx(stage2_trace(), jobs, bare)) is None
        assert read(metric, job_ctx(stage2_trace(), jobs, other)) is None
        assert read(metric, job_ctx(stage2_trace(), [])) is None


def test_the_reader_compiles_the_program_the_traced_job_runs(monkeypatch):
    """Without a scope map in the run's context, the readers compile the
    Stage-2 program again; its map names the instructions as the traced
    job's own compile does."""
    import time

    import jax

    from bench.drivers import jobs

    compiled = []
    real = jax.stages.Lowered.compile

    def recording(self, *a, **kw):
        c = real(self, *a, **kw)
        compiled.append(c.as_text())
        return c

    monkeypatch.setattr(jax.stages.Lowered, "compile", recording)
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    cfg = harness.load_config(harness.load_benchmark(), "dti")
    cfg.update(n_points=400, n_clusters=6, n_regions=3, data_seeds=[5],
               check_jobs=1)
    res = jobs.run({}, cfg, harness.load_mix("job"), 2 ** 33 + 5, 0.2, True,
                   jax.devices()[:1], time.time())
    traced = [scopes.scope_map(t) for t in compiled
              if t.startswith("HloModule jit_stage2,")]
    assert len(traced) == 1
    again = scopes.scope_map(scopes.traced_stage2_text(cfg))
    assert again == traced[0]
    assert {scopes.scope_of(n) for n in again["jit_stage2"].values()} >= {
        "spmv", "orthogonalize", "restart", "stage2"}
    assert res["ctx"]["lanczos"] is not None


def recorded(part):
    """A trace recorded on a TPU v5e, with the programs' scope maps: the
    first job of a traced ``dti.job`` run (Stage 2 cut to its first and
    last 150 ms) and 70 ms of a traced ``dti.serve`` window, six flushes
    with their ``batcher.*`` spans (``[name, start, duration,
    attributes]``)."""
    t = json.loads((DATA / "traced_scopes_small.json").read_text())[part]
    return t, t.pop("scopes")


def test_recorded_stage2_lies_under_its_three_scopes():
    t, smap = recorded("job")
    win = tr.span_windows(t, "window")[0]
    stage2 = sum(o[3] for _, o, prog, _ in scopes.scoped_ops(t, smap, win)
                 if prog == "jit_stage2") * 1e-9
    parts = scopes.scope_seconds(t, smap, ("spmv", "orthogonalize",
                                           "restart"), win)
    assert stage2 > 0.25
    assert parts >= 0.98 * stage2
    assert scopes.scope_seconds(t, smap, "spmv", win) > 0.9 * stage2
    # the readers, on the program's own counter and on the count that the
    # reported restarts imply, which agree
    ctx = dict(job_ctx(t, t["jobs"], smap, "dti"), window=win,
               lanczos={"basis": 200, "keep": 150, "block": 1})
    assert scopes.stage2_scopes(ctx) is smap
    inferred = dict(ctx, jobs=[{k: v for k, v in j.items()
                                if k != "operator_applications"}
                               for j in t["jobs"]])
    assert scopes.applications(inferred) == scopes.applications(ctx) == [
        1250]
    assert read("spmv_ms", ctx) == read("spmv_ms", inferred) > 0
    assert 0 < read("spmv_roofline", ctx)["value"] < 100


def test_recorded_serve_kernel_runs_inside_the_batchers_call():
    """Host and device share the profiler's clock closely enough that the
    kernel's device time falls inside the host's ``batcher.call`` spans;
    each flush's attributes and children are in the trace."""
    t, smap = recorded("serve")
    ops = next(iter(t["device"].values()))
    knn = tr.merge(tr.op_intervals(
        ops, lambda o: tr.family(o[0]) == "knn_topk"))
    host = {}
    for name, s, d, *attrs in t["host"]:
        host.setdefault(name, []).append((s, d, attrs[0] if attrs else {}))
    calls = tr.merge([(s, s + d) for s, d, _ in host["batcher.call"]])
    assert tr.total(knn) > 0
    assert tr.total(tr.subtract(knn, calls)) <= 0.05 * tr.total(knn)
    flushes = {a["flush"]: (s, d, a) for s, d, a in host["batcher.flush"]}
    assert len(flushes) >= 5
    for s, d, a in flushes.values():
        assert 0 < a["rows"] <= 256 and 0 < a["requests"] <= a["rows"]
        assert 0 <= a["wait_us_max"] <= a["wait_us_sum"]
    for name in ("batcher.assemble", "batcher.call", "batcher.resolve"):
        for s, d, a in host[name]:
            if a["flush"] in flushes:
                fs, fd, _ = flushes[a["flush"]]
                assert fs <= s and s + d <= fs + fd
    knn_names = {scopes.scope_of(smap["jit_oos_labels"].get(o[0]))
                 for o in ops if tr.family(o[0]) == "knn_topk"}
    assert knn_names == {"oos_knn"}
