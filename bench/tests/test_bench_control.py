"""The control, the plain reference computed in bfloat16 and put in the
program's place, comes out as not correct, at a size a test run holds."""
import pytest

from bench import deploy, harness
from bench.drivers import jobs, open_loop

SMALL = {
    "dti": dict(n_points=1000, n_clusters=12, n_regions=6, data_seeds=[1]),
    "syn200": dict(n_blocks=10, block_size=50, intra_edges_per_block=600,
                   inter_edges=500, n_clusters=10, data_seeds=[1]),
}


def small(name):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    cfg.update(SMALL[name])
    return cfg


def verdict(cfg, nums, names):
    return harness.checks_pass({n: {"value": nums[n], "limit": cfg["limits"][n]}
                                for n in names})


@pytest.mark.parametrize("config", sorted(SMALL))
def test_job_control_is_not_correct(config):
    cfg = small(config)
    ds = deploy.generator(cfg).dataset(cfg, cfg["data_seeds"][0])
    r = jobs.reference_for(cfg, ds)
    nums = jobs.compare_job(cfg, jobs.control_job(cfg, ds, 2 ** 33 + 1), r)
    assert not verdict(cfg, nums, cfg["checks_jobs"]), nums
    # the float64 reference in the program's place passes
    k = cfg["n_clusters"]
    a = r["adjacency"].tocoo()
    same = {"labels": None, "embedding": None, "row": a.row, "col": a.col,
            "val": a.data, "eigenvalues": 1.0 - r["vals"][:k]}
    from bench import reference as ref

    same["embedding"] = ref.njw_rows(r["vecs"][:, :k])
    same["labels"], means = ref.lloyd(same["embedding"], k,
                                      ref.np.random.default_rng(0))
    same["inertia"] = ref.sq_dists(same["embedding"], means).min(1).sum()
    nums = jobs.compare_job(cfg, same, r)
    assert verdict(cfg, nums, cfg["checks_jobs"]), nums


def test_serve_control_is_not_correct():
    cfg = harness.load_json(harness.BENCH / "configs" / "dti_full.json")
    cfg.update(n_points=1000, n_clusters=12)
    mix = dict(harness.load_mix("serve"), rate_hz=100.0, check_requests=100)
    nums = open_loop.control_readings(cfg, mix, 2 ** 33 + 2, 1.0)
    assert not verdict(cfg, nums, cfg["checks_serve"]), nums
