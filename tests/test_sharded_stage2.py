"""Stage 2 row-sharded over four chips on the ``coo_spmv`` layout
(DESIGN.md §20), on four virtual CPU devices in one subprocess (the test
process keeps its one device).  The dispatch is steered to the kernel's
path as on a TPU; off one the layout's jnp reference runs, and the kernel
in interpret mode where asked.

The deployment is the four-chip DTI cell's (``bench/configs/
dti_sharded4.json``) at n = 1,001, compared with the plain reference
(``bench/reference.py``) on the cell's own checks and limits, and with the
single-device kernel path; the same job with the ring exchange of Stage 1,
or the all-gather of the product, left out must fail those checks."""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    import repro.core.spectral as spectral
    from bench import deploy, harness
    from bench.drivers import jobs
    from repro.core.operator import RowTiledCooOperator
    from repro.sparse.formats import COO

    devs = jax.devices()[:4]
    out = {}

    # four chips' row blocks of a graph with n odd, far sections, and no
    # nonzero in the third block, against the product in float64
    rng = np.random.default_rng(0)
    n, nnz = 5001, 20000
    rows = -(-n // 4)
    pool = np.setdiff1d(np.arange(n), np.arange(2 * rows, 3 * rows))
    row = np.sort(rng.choice(pool, nnz))
    col = rng.integers(0, n, nnz)
    val = rng.uniform(0.1, 1.0, nnz).astype(np.float32)
    x = rng.normal(size=n).astype(np.float32)
    want, scale = np.zeros(n), np.zeros(n)
    np.add.at(want, row, val * x[col].astype(np.float64))
    np.add.at(scale, row, np.abs(val * x[col]))
    a = COO(jnp.asarray(row, jnp.int32), jnp.asarray(col, jnp.int32),
            jnp.asarray(val), (n, n))
    mesh = Mesh(np.array(devs), ("data",))
    for impl, interpret in (("ref", None), ("pallas", True)):
        op = RowTiledCooOperator.build(a, mesh, impl=impl, interpret=interpret)
        y = np.asarray(jax.jit(lambda o, v: o.mv(v))(op, jnp.asarray(x)))
        out["mv_" + impl] = float(np.max(np.abs(y - want) - 1e-6 * scale))

    # the cell's deployment, cut to n = 1,001, on the kernel's path
    spectral.kernel_applies = lambda n: True
    cfg = harness.load_json(harness.BENCH / "configs" / "dti_sharded4.json")
    cfg.update(n_points=1001, n_clusters=12, n_regions=6)
    gen_mod = deploy.generator(cfg)
    ds = gen_mod.dataset(cfg, 1)
    inputs = gen_mod.inputs(cfg, ds)
    ref = jobs.reference_for(cfg, ds)
    key = jobs.job_keys(2 ** 33 + 9, 1)[0]

    def job(pipe):
        jax.clear_caches()  # trace again, with the collectives as they are now
        res, adj = jax.jit(gen_mod.job(cfg, pipe))(*inputs, key)
        host = jobs.to_host(res, adj)
        return {"checks": jobs.compare_job(cfg, host, ref),
                "eigenvalues": host["eigenvalues"].tolist(),
                "notes": [e for r in res.reports for e in r.escalations]}

    single = dict(cfg, pipeline=dict(cfg["pipeline"], plan="single"))
    out["single"] = job(deploy.pipeline(single))
    out["sharded"] = job(deploy.pipeline(cfg, devs))
    ppermute, all_gather = jax.lax.ppermute, jax.lax.all_gather
    # every ppermute of the ring hands a block back to its own chip
    jax.lax.ppermute = lambda x, axis_name, perm: x
    out["exchange_left_out"] = job(deploy.pipeline(cfg, devs))
    jax.lax.ppermute = ppermute
    # every chip's product reads its own block of x in place of the others
    jax.lax.all_gather = lambda x, axis_name, axis=0, tiled=False: (
        jnp.concatenate([x] * 4, axis))
    out["gather_left_out"] = job(deploy.pipeline(cfg, devs))
    jax.lax.all_gather = all_gather
    out["limits"] = cfg["limits"]
    out["tol"] = cfg["pipeline"]["tol"]
    print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SCRIPT)],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("engine", ["ref", "pallas"])
def test_row_blocks_product_on_four_devices(runs, engine):
    # only the summation order differs: float32 rounding of |A| |x|
    assert runs["mv_" + engine] <= 1e-7


def test_sharded_job_runs_the_row_sharded_kernel_path(runs):
    assert any(note.startswith("coo_spmv_rows[shards=4,rows=251,")
               for note in runs["sharded"]["notes"]), runs["sharded"]["notes"]
    assert any(note.startswith("coo_spmv[") for note in runs["single"]["notes"])


@pytest.mark.parametrize("plan", ["single", "sharded"])
def test_job_within_the_cells_limits(runs, plan):
    """The cell's checks against the float64 reference, at the cell's
    limits (PERF.md §2 gives each limit's two readings)."""
    for name, value in runs[plan]["checks"].items():
        assert value <= runs["limits"][name], (plan, name, value)


def test_sharded_eigenvalues_match_one_device(runs):
    """Each run's Ritz values lie within tol × θ_max ≤ tol of the
    eigenvalues they converged to, so two converged runs differ by at most
    twice the tolerance."""
    gap = max(abs(a - b) for a, b in zip(runs["sharded"]["eigenvalues"],
                                         runs["single"]["eigenvalues"]))
    assert gap <= 2 * runs["tol"]


@pytest.mark.parametrize("fault", ["exchange_left_out", "gather_left_out"])
def test_left_out_collective_fails_the_checks(runs, fault):
    checks = runs[fault]["checks"]
    assert any(v > runs["limits"][k] for k, v in checks.items()), checks


def _sharding_constraints(jaxpr, path=""):
    """``(scopes, spec)`` of every sharding constraint of a traced program."""
    from jax.extend import core

    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "sharding_constraint":
            yield here, tuple(eqn.params["sharding"].spec)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, (core.Jaxpr, core.ClosedJaxpr)):
                    yield from _sharding_constraints(sub, here)


@pytest.mark.parametrize("case", ["row_tiled", "blockell", "operator",
                                  "sharded_coo", "block_lanczos"])
def test_embedding_pinned_where_stage2_is_not_row_sharded(monkeypatch, case):
    """Under the GSPMD sharded plan with Stage 3 in ``kmeans_sharded``, the
    embedding is pinned replicated unless Stage 2's products were the
    row-sharded operator's: decided from the operator that ran, so a
    ShardedCOO input, a BlockELL representation (the COO fallback under
    jit), an operator passed in, or block Lanczos keeps the pin on a TPU
    (steered here) where the kernel's path applies to n (DESIGN.md §10)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.operator import CooOperator
    from repro.core.spectral import EigConfig, Plan, SpectralPipeline
    from repro.data.sbm import sbm_graph
    from repro.sparse.distributed import partition_coo_by_rows
    from repro.sparse.ops import sort_coo_rows

    w = sort_coo_rows(sbm_graph(50, 12, p_in=0.3, p_out=0.01, seed=8)[0])
    eig = {"blockell": EigConfig(representation="blockell"),
           "block_lanczos": EigConfig(block_size=4)}.get(case, EigConfig())
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    pipe = SpectralPipeline(n_clusters=12, eig=eig,
                            plan=Plan(device="sharded", mesh=mesh))
    data = partition_coo_by_rows(w, 1) if case == "sharded_coo" else w
    op = CooOperator(w) if case == "operator" else None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # BlockELL fallback
        jaxpr = jax.make_jaxpr(lambda d, k: pipe.run(d, k, operator=op))(
            data, jax.random.PRNGKey(0))
    found = list(_sharding_constraints(jaxpr))
    pinned = [path for path, spec in found if spec == ()]
    if case == "row_tiled":
        assert not pinned, found
        assert any("data" in spec for _, spec in found), found
    else:
        assert pinned and all("stage2" in path for path in pinned), found
