"""The readers of a cell whose Stage 2 is row-sharded over its chips
(``bench/row_shards.py``): the all-gather's, Gram-Schmidt's and a
restart's time and each chip's share of its product's roofline, from a
two-chip trace; nothing
from a program without the ``spmv_gather`` scope (a Stage 2 that is not
row-sharded, as before this layout existed)."""
import pytest

from bench import harness

SHARDED = {"jit_stage2": {
    "fusion.1": "jit(stage2)/stage2/while/body/spmv/shard_map/jit(coo_spmv)"
                "/pallas_call",
    "all-reduce.2": "jit(stage2)/stage2/while/body/spmv/shard_map/"
                    "spmv_gather/all_gather",
    "dot.3": "jit(stage2)/stage2/while/body/orthogonalize/dot_general",
    "custom-call.4": "jit(stage2)/stage2/while/body/restart/eigh"}}
REPLICATED = {"jit_stage2": {
    "fusion.1": "jit(stage2)/stage2/while/body/spmv/gather",
    "all-reduce.2": "jit(stage2)/stage2/while/body/spmv/scatter-add",
    "dot.3": "jit(stage2)/stage2/while/body/orthogonalize/dot_general",
    "custom-call.4": "jit(stage2)/stage2/while/body/restart/eigh"}}
SHARDED_READERS = ["spmv_gather_ms", "spmv_roofline.sharded",
                   "lanczos_orth_ms.sharded", "lanczos_restart_ms.sharded"]


def ctx(smap):
    """Two chips, one job of 4 applications and 2 restarts: chip 0 spends
    4,000 ns in its kernel, 400 ns gathering, 100 ns in Gram-Schmidt and
    500 ns restarting, chip 1 2,000, 800, 300 and 700 ns."""
    def chip(kernel, gather, orth, restart):
        return [["fusion.1", "custom-call", 1000, kernel],
                ["all-reduce.2", "all-reduce", 1000 + kernel, gather],
                ["dot.3", "convolution", 6000, orth],
                ["custom-call.4", "custom-call", 7000, restart]]
    return {
        "cfg": {"generator": "dti_pointcloud", "n_points": 1000, "chips": 2},
        "trace": {"device": {"/device:TPU:0": chip(4000, 400, 100, 500),
                             "/device:TPU:1": chip(2000, 800, 300, 700)},
                  "modules": {p: [["jit_stage2(1)", 0, 9000]]
                              for p in ("/device:TPU:0", "/device:TPU:1")},
                  "host": [["window", 0, 10000]]},
        "window": (0, 10000),
        "scopes": smap,
        "jobs": [{"restarts": 2, "km_iters": 3, "nnz": 600,
                  "operator_applications": 4}],
        "chip_nnz": {600: {(400, 200)}},
        "peaks": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e12},
    }


def read(metric, c):
    return harness.layer_reader(metric).read(c)


def test_gather_time_is_a_chips_mean_per_application():
    # (400 + 800) / 2 ns over 4 applications
    assert read("spmv_gather_ms", ctx(SHARDED)) == pytest.approx(1.5e-4)


def test_gram_schmidt_time_is_a_chips_mean_per_application():
    # (100 + 300) / 2 ns over 4 applications
    assert read("lanczos_orth_ms.sharded", ctx(SHARDED)) == pytest.approx(
        5e-5)


def test_restart_time_is_a_chips_mean_per_restart():
    # (500 + 700) / 2 ns over 2 restarts
    assert read("lanczos_restart_ms.sharded", ctx(SHARDED)) == pytest.approx(
        3e-4)


def test_roofline_share_is_each_chips_own_work_over_its_kernel():
    got = read("spmv_roofline.sharded", ctx(SHARDED))
    work = harness.kernel_work("spmv").work
    shares = []
    for nnz, secs in ((400, 4000e-9), (200, 2000e-9)):
        ops, nbytes = work(nnz=nnz, n=1000)
        shares.append(100.0 * 4 * max(ops, nbytes) / 1e12 / secs)
    assert got["chips"] == pytest.approx(shares)
    assert got["value"] == pytest.approx(sum(shares) / 2)
    assert got["bound"] == "memory"


def test_a_job_whose_split_is_not_known_reads_nothing():
    c = ctx(SHARDED)
    c["chip_nnz"] = {600: {(400, 200), (300, 300)}}
    assert read("spmv_roofline.sharded", c) is None


@pytest.mark.parametrize("metric", SHARDED_READERS)
def test_nothing_without_the_row_sharded_product(metric):
    assert read(metric, ctx(REPLICATED)) is None


@pytest.mark.parametrize("metric", SHARDED_READERS)
def test_nothing_from_a_map_that_is_not_the_program_that_ran(metric):
    smap = {"jit_stage2": {k: v for k, v in SHARDED["jit_stage2"].items()
                           if k != "dot.3"}}
    assert read(metric, ctx(smap)) is None
