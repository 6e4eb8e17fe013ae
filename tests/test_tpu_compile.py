"""Compile the Pallas kernels of the main path for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without a chip, compiles
each kernel at the DTI widths for one chip of a ``v5e:2x2`` topology and
raises what the chip's compiler would raise (tile alignment, scoped-VMEM
limits) — what interpret-mode tests cannot see.  ``ell_spmm``/``ell_spmv``
are not here: Mosaic refuses their gather over all of ``x``, so a TPU runs
their XLA path (tests/test_kernels_ell_spmm.py checks that refusal);
``coo_spmv`` gathers within one vreg, which Mosaic compiles.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the test workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in the program
    return text


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("d,k,ring", [(3, 16, False), (90, 10, False),
                                      (200, 64, False), (3, 16, True)],
                         ids=["dti_stage1", "serving_oos", "merge_vmem_cap",
                              "ring_step"])
def test_knn_topk_compiles(one_chip, d, k, ring):
    """The kernel with its scalar-prefetched visit order, the merge's
    predicate and the tile counter.  ``ring_step`` is a ring call of the
    four-chip DTI cell: a chip's 7,127 rows against a visiting block at a
    traced offset, ``block_q=1024`` capped to 480 rows by the merge's VMEM
    budget."""
    from repro.kernels.knn_topk.ops import _merge_rows, knn_topk

    if not ring:
        _compile(lambda x: knn_topk(x, k, impl="pallas", interpret=False,
                                    with_tiles=True),
                 one_chip, ((4096, d), F32))
        return
    assert _merge_rows(16, 256) == 480
    _compile(lambda x, q, off: knn_topk(x, k, queries=q, query_offset=off,
                                        block_q=1024, impl="pallas",
                                        interpret=False, with_tiles=True),
             one_chip, ((7127, d), F32), ((7127, d), F32), ((), I32))


@pytest.mark.parametrize("k,d", [(500, 500), (1024, 639)],
                         ids=["dti_stage3", "vmem_budget_edge"])
def test_kmeans_iter_compiles(one_chip, k, d):
    from repro.kernels.kmeans_iter.ops import (ACC_VMEM_BUDGET_BYTES,
                                               kmeans_iter,
                                               pallas_workset_bytes)

    assert pallas_workset_bytes(4096, d, k) <= ACC_VMEM_BUDGET_BYTES
    _compile(lambda x, c: kmeans_iter(x, c, impl="pallas", interpret=False),
             one_chip, ((4096, d), F32), ((k, d), F32))


def test_kmeans_assign_compiles(one_chip):
    from repro.kernels.kmeans_assign.ops import kmeans_assign

    _compile(lambda x, c: kmeans_assign(x, c, impl="pallas", interpret=False),
             one_chip, ((4096, 500), F32), ((500, 500), F32))


def test_lsh_hash_codes_compile(one_chip):
    from repro.kernels.lsh_candidates.ops import hash_codes

    _compile(lambda x, p: hash_codes(x, p, impl="pallas", interpret=False),
             one_chip, ((4096, 90), F32), ((16, 90, 17), F32))


@pytest.mark.parametrize("n,nnz", [(28508, 912256), (20000, 1546776),
                                   (142541, 4561312)],
                         ids=["dti", "syn200", "dti_full"])
def test_coo_spmv_compiles(one_chip, n, nnz):
    """Stage 2's single-vector product at the deployments' graph sizes,
    with the layout it is built from: their shapes follow from n and nnz
    alone."""
    from repro.kernels.coo_spmv import build_tiles, coo_spmv

    _compile(lambda r, c, v, x: coo_spmv(build_tiles(r, c, v, n), x,
                                         impl="pallas", interpret=False),
             one_chip, ((nnz,), I32), ((nnz,), I32), ((nnz,), F32),
             ((n,), F32))


@pytest.mark.parametrize("exchange", [None, "gather", "ring"],
                         ids=["one_chip", "four_chips_gather",
                              "four_chips_ring"])
def test_dti_pipeline_compiles(topo, one_chip, monkeypatch, exchange):
    """The whole DTI program (Stage 1 kNN → Lanczos → fused k-means) at a
    small n, on one chip and sharded over four.  ``jax.default_backend`` is
    steered to "tpu" so ``impl="auto"`` takes its TPU branches, as on the
    chip; across four chips every Mosaic kernel must sit in a shard_map,
    and Stage 2's product is the kernel over each chip's rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.spectral import (EigConfig, GraphConfig, Plan,
                                     SpectralPipeline)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    plan, where = Plan(), one_chip
    if exchange is not None:
        mesh = Mesh(np.array(topo.devices), ("data",))
        plan = Plan(device="sharded", mesh=mesh, stage1_exchange=exchange)
        where = NamedSharding(mesh, P())
    pipe = SpectralPipeline(
        n_clusters=12, graph=GraphConfig(knn_k=16, measure="cross_correlation"),
        eig=EigConfig(tol=1e-4), plan=plan)
    n = 1001  # odd: the sharded Stage 1 pads its row blocks
    args = [jax.ShapeDtypeStruct(s, dt, sharding=where)
            for s, dt in (((n, 90), F32), ((n, 3), F32), ((2,), jnp.uint32))]
    try:
        traced = jax.jit(lambda x, p, key: pipe.run(x, key, points=p)).trace(
            *args)
        text = traced.lower().compile().as_text()
    finally:
        jax.clear_caches()
    assert text.count("tpu_custom_call") >= 2  # knn_topk and kmeans_iter
    # Lanczos's products run coo_spmv: on one device, or on each chip's own
    # rows after one all-gather of x (DESIGN.md §20)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "/spmv/" in line]
    assert calls, "no Pallas kernel under the spmv scope"
    if exchange is not None:
        # the program's one all-gather of x a product, read from the traced
        # program: the TPU compiler runs it as an all-reduce of a zero-padded
        # x, and at this n drops its metadata doing so
        gathers = [path for prim, path in _scoped_primitives(traced.jaxpr)
                   if prim == "all_gather" and "/spmv/" in path
                   and path.endswith("/spmv_gather")]
        assert gathers, "no all-gather of x under the spmv_gather scope"


def _scoped_primitives(jaxpr, path=""):
    """``(primitive, scopes)`` of every equation of a traced program, its
    scopes those of the equations that hold it followed by its own."""
    from jax.extend import core

    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, here
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, (core.Jaxpr, core.ClosedJaxpr)):
                    yield from _scoped_primitives(sub, here)
