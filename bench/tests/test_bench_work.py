"""Work functions against counts made by hand, and the peaks table."""
import pytest

from bench import harness


def test_knn_topk_work():
    # 4 queries x 5 points x 3 coordinates, a multiply-add each; reads
    # (4 + 5) x 3 floats, writes 4 x 2 (distance, id) pairs
    assert harness.kernel_work("knn_topk").work(n_q=4, n_p=5, d=3, k=2) == (
        120, 4 * 27 + 8 * 8)


def test_kmeans_iter_work():
    # 10 points x 3 centroids x 2 dims x 2 ops, twice; per iteration reads
    # x (20 floats), the centroids and writes their sums (12), labels (10)
    assert harness.kernel_work("kmeans_iter").work(
        n=10, k=3, d=2, iterations=2) == (240, 2 * (4 * 32 + 40))


def test_spmv_counts():
    spmv = harness.kernel_work("spmv")
    # k=100: basis 200, keeps 150, so 50 new products per later cycle
    assert spmv.matvecs(basis=200, keep=150, block=1, restarts=3) == 300
    # the published DTI run: k=500, 23 cycles -> 1000 + 22 x 250
    assert spmv.matvecs(basis=1000, keep=750, block=1, restarts=23) == 6500
    # block width 8: 64 / 8 applications, then (64 - 40) / 8 a cycle
    assert spmv.matvecs(basis=64, keep=40, block=8, restarts=3) == 14
    assert spmv.work(nnz=10, n=4) == (20, 4 * 44)


@pytest.mark.parametrize("config,n,sizes", [
    ("dti", 28508, {"basis": 200, "keep": 150, "block": 1}),
    ("syn200", 20000, {"basis": 400, "keep": 300, "block": 1}),
])
def test_lanczos_sizes_come_from_the_configured_pipeline(config, n, sizes):
    from bench import deploy

    cfg = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    assert deploy.lanczos_sizes(deploy.pipeline(cfg), n) == sizes


def test_peaks_lookup():
    pk = harness.peaks("TPU v5 lite")
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.peaks("TPU v9 imaginary")
