"""The harness: no chip, no result; cells found by file name."""
import json
import shutil

import pytest

from bench import harness
from bench import run as brun


def test_refuses_a_machine_without_a_tpu():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


def test_run_prints_no_result_without_a_tpu(capsys):
    rc = brun.main(["--workload", "dti.job", "--seed", "2147483659",
                    "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == brun.EXIT_NO_CHIP
    assert out.out == ""
    assert "no TPU" in out.err


def test_every_cell_finds_its_files():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        cfg = harness.load_config(bench, cell["config"])
        mix = harness.load_mix(cell["traffic"])
        assert cfg["name"] == cell["config"]
        assert (harness.BENCH / "drivers" / f"{mix['kind']}.py").exists()
        for m in harness.per_layer_for(bench, cell["name"]):
            assert callable(harness.layer_reader(m["name"]).read)
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_new_files_make_a_new_cell(tmp_path, monkeypatch):
    """A configuration, a mix and a per-layer metric added as new files
    make a new cell that runs, with no other file edited."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = harness.load_benchmark()
    cfg = json.loads((root / "bench/configs/dti.json").read_text())
    cfg.update(name="dti_tiny", n_points=400, n_clusters=6, n_regions=3,
               data_seeds=[5], check_jobs=1)
    (root / "bench/configs/dti_tiny.json").write_text(json.dumps(cfg))
    (root / "bench/mixes/job_once.json").write_text(json.dumps(
        {"kind": "jobs", "loop": "closed", "clients": 1}))
    (root / "bench/layers/jobs_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx['jobs']))\n")
    bench["configs"].append({"name": "dti_tiny", "source": "x",
                             "file": "bench/configs/dti_tiny.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "dti_tiny.job_once",
                               "config": "dti_tiny", "traffic": "job_once",
                               "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("dti_tiny.job_once")
    bench["per_layer"].append({"name": "jobs_seen", "unit": "jobs",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "job_s",
                               "workloads": ["dti_tiny.job_once"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    import jax

    real_peaks = harness.peaks
    monkeypatch.setattr(harness, "peaks", lambda kind: real_peaks("TPU v5 lite"))
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    loaded = harness.load_benchmark(root)
    res = brun.run_cell(loaded, "dti_tiny.job_once", 3, 0.5, False, 0.0,
                        devs=jax.devices()[:1], root=root)
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"job_s", "setup_s"}
    assert harness.checks_pass(res["checks"])
    res = brun.run_cell(loaded, "dti_tiny.job_once", 4, 0.5, True, 0.0,
                        devs=jax.devices()[:1], root=root)
    assert res["metrics"]["jobs_seen"]["value"] >= 1


CLIQUES = '''"""A ring of dense blocks (each pair joined with probability 0.7), each
block joined to the next by one edge."""
from pathlib import Path

import numpy as np

from bench import harness

_sbm = harness.load_module(Path(__file__).with_name("sbm.py"), "sbm_base")
n_nodes, inputs, job, stage1 = _sbm.n_nodes, _sbm.inputs, _sbm.job, _sbm.stage1
reference_graph = _sbm.reference_graph


def dataset(cfg, data_seed):
    b, s = cfg["n_blocks"], cfg["block_size"]
    rng = np.random.default_rng(data_seed)
    iu, ju = np.triu_indices(s, 1)
    keep = [rng.random(iu.size) < 0.7 for _ in range(b)]
    r = np.concatenate([iu[m] + i * s for i, m in enumerate(keep)]
                       + [np.arange(b) * s])
    c = np.concatenate([ju[m] + i * s for i, m in enumerate(keep)]
                       + [(np.arange(b) * s + s + 1) % (b * s)])
    row, col = np.concatenate([r, c]), np.concatenate([c, r])
    order = np.lexsort((col, row))
    return {"row": row[order].astype(np.int32),
            "col": col[order].astype(np.int32),
            "val": np.ones(row.size, np.float32)}
'''


def test_a_new_generator_is_found_by_name(tmp_path, monkeypatch):
    """A deployment whose data comes from a new generator is added as files
    (the generator, its configuration) with no other file edited."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "bench/generators/cliques.py").write_text(CLIQUES)
    cfg = json.loads((root / "bench/configs/syn200.json").read_text())
    cfg.update(name="cliques", generator="cliques", n_blocks=5, block_size=24,
               n_clusters=5, data_seeds=[0])
    (root / "bench/configs/cliques.json").write_text(json.dumps(cfg))
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "cliques", "source": "x",
                             "file": "bench/configs/cliques.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "cliques.job", "config": "cliques",
                               "traffic": "job", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("cliques.job")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    import jax

    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    res = brun.run_cell(harness.load_benchmark(root), "cliques.job", 3, 0.5,
                        False, 0.0, devs=jax.devices()[:1], root=root)
    assert res["attempted"] >= 1 and res["failed"] == 0
    # compared with the reference of the new generator's own graph
    assert all(v["value"] is not None for v in res["checks"].values())
    assert res["checks"]["graph_err"]["value"] < 1e-5
