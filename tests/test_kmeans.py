"""k-means / k-means++ (paper Alg. 4-5) behaviour tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.kmeans import (
    KMeansConfig, assign_ref, kmeans, kmeanspp_init, update_centroids,
)


def _blobs(k, n_per, d, spread=0.25, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * 6
    X = np.concatenate([centers[i] + rng.normal(size=(n_per, d)) * spread for i in range(k)])
    labels = np.repeat(np.arange(k), n_per)
    return X.astype(np.float32), labels, centers.astype(np.float32)


def _purity(pred, truth):
    from collections import Counter

    return sum(Counter(truth[pred == i]).most_common(1)[0][1]
               for i in np.unique(pred)) / len(truth)


@pytest.mark.parametrize("mode", [("two_pass", "matmul"), ("two_pass", "segment"), ("fused", "matmul")])
def test_recovers_blobs(mode):
    it, update = mode
    X, truth, _ = _blobs(6, 300, 8)
    cfg = KMeansConfig(k=6, iter=it, update=update, assign="ref")
    res = jax.jit(lambda x, key: kmeans(x, cfg, key))(
        jnp.asarray(X), jax.random.PRNGKey(0)
    )
    assert _purity(np.asarray(res.labels), truth) > 0.98
    assert int(res.shifted) == 0  # converged


@pytest.mark.parametrize("n,k,d", [(200, 7, 5), (513, 37, 9), (130, 3, 17)])
def test_fused_iteration_matches_two_pass_driver(n, k, d):
    """Full-driver parity on non-multiple-of-block shapes: the one-pass
    iteration must track assign_ref + update_centroids — identical labels
    and iteration count, centroids to accumulation-order tolerance."""
    rng = np.random.default_rng(n + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    key = jax.random.PRNGKey(1)
    r_fused = kmeans(x, KMeansConfig(k=k, iter="fused", max_iters=25), key)
    r_two = kmeans(x, KMeansConfig(k=k, iter="two_pass", assign="ref", max_iters=25), key)
    np.testing.assert_array_equal(np.asarray(r_fused.labels), np.asarray(r_two.labels))
    assert int(r_fused.iterations) == int(r_two.iterations)
    np.testing.assert_allclose(np.asarray(r_fused.centroids),
                               np.asarray(r_two.centroids), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(r_fused.inertia), float(r_two.inertia),
                               rtol=1e-5)


def test_fused_driver_handles_duplicate_points():
    """Many exact twins (tied distances everywhere) must not double-count
    mass or diverge from the reference path."""
    rng = np.random.default_rng(2)
    base = rng.normal(size=(30, 4)).astype(np.float32)
    x = jnp.asarray(np.concatenate([base] * 4))
    key = jax.random.PRNGKey(3)
    r_fused = kmeans(x, KMeansConfig(k=5, iter="fused", max_iters=15), key)
    r_two = kmeans(x, KMeansConfig(k=5, iter="two_pass", assign="ref", max_iters=15), key)
    np.testing.assert_array_equal(np.asarray(r_fused.labels), np.asarray(r_two.labels))
    lab = np.asarray(r_fused.labels)
    np.testing.assert_array_equal(lab[:30], lab[90:])  # twins co-assigned


def test_fused_empty_cluster_keeps_previous_centroid():
    """Empty-cluster carryover through the fused driver: a centroid seeded
    unreachably far keeps its position, two-pass-identically."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(40, 3)), jnp.float32)
    init = jnp.concatenate([x[:2], jnp.full((1, 3), 50.0, jnp.float32)])
    r = kmeans(x, KMeansConfig(k=3, iter="fused", max_iters=5),
               jax.random.PRNGKey(0), init_centroids=init)
    np.testing.assert_allclose(np.asarray(r.centroids[2]), 50.0)
    assert int(np.asarray(r.labels).max()) < 2


def test_update_variants_agree():
    X, truth, _ = _blobs(4, 100, 5)
    labels, _ = assign_ref(jnp.asarray(X), jnp.asarray(X[:4]))
    prev = jnp.zeros((4, 5), jnp.float32)
    a = update_centroids(jnp.asarray(X), labels, 4, prev, how="matmul")
    b = update_centroids(jnp.asarray(X), labels, 4, prev, how="segment")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_empty_cluster_keeps_previous_centroid():
    X = jnp.asarray(np.random.default_rng(0).normal(size=(20, 3)), jnp.float32)
    labels = jnp.zeros((20,), jnp.int32)  # everything in cluster 0
    prev = jnp.full((3, 3), 7.0)
    c = update_centroids(X, labels, 3, prev)
    np.testing.assert_allclose(np.asarray(c[1:]), 7.0)


def test_config_rejects_unknown_engine():
    """A typo'd engine/init name must fail loudly at construction, not
    silently select the other code path."""
    with pytest.raises(ValueError, match="iter"):
        KMeansConfig(k=3, iter="one_pass")
    with pytest.raises(ValueError, match="init"):
        KMeansConfig(k=3, init="k-means++")
    import repro.core.distributed_pipeline as dp
    with pytest.raises(ValueError, match="fused"):
        dp.kmeans_sharded(jnp.zeros((8, 2)), KMeansConfig(k=2, iter="two_pass"),
                          jax.random.PRNGKey(0), mesh=None)


def test_interpret_plumbs_through_driver():
    """KMeansConfig.interpret must reach the Pallas wrappers so the kernel
    bodies run (interpret mode) off-TPU without monkeypatching backend
    detection — both the fused iteration and the two-pass assign."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(48, 6)), jnp.float32)
    key = jax.random.PRNGKey(0)
    want = kmeans(x, KMeansConfig(k=4, iter="two_pass", assign="ref", max_iters=8), key)
    for cfg in (KMeansConfig(k=4, iter="fused", interpret=True, max_iters=8, block_q=16, block_k=128),
                KMeansConfig(k=4, iter="two_pass", assign="fused", interpret=True,
                             max_iters=8, block_q=16, block_k=128)):
        got = kmeans(x, cfg, key)
        np.testing.assert_array_equal(np.asarray(got.labels), np.asarray(want.labels))
        np.testing.assert_allclose(np.asarray(got.centroids),
                                   np.asarray(want.centroids), rtol=1e-4, atol=1e-4)


def test_kmeanspp_spreads_seeds():
    """++ seeding must pick one seed per well-separated blob (w.h.p.)."""
    X, truth, centers = _blobs(8, 200, 4, spread=0.05, seed=3)
    C = np.asarray(kmeanspp_init(jnp.asarray(X), 8, jax.random.PRNGKey(0)))
    d2 = ((C[:, None, :] - centers[None]) ** 2).sum(-1)
    owners = d2.argmin(1)
    assert len(set(owners.tolist())) == 8  # all blobs covered


def test_kmeanspp_beats_random_init_inertia():
    X, *_ = _blobs(16, 100, 6, spread=0.3, seed=5)
    x = jnp.asarray(X)
    r_pp = kmeans(x, KMeansConfig(k=16, init="kmeans++", max_iters=3, assign="ref"), jax.random.PRNGKey(2))
    r_rd = kmeans(x, KMeansConfig(k=16, init="random", max_iters=3, assign="ref"), jax.random.PRNGKey(2))
    assert float(r_pp.inertia) <= float(r_rd.inertia) * 1.05


def test_assign_auto_propagates_real_kernel_bugs(monkeypatch):
    """`assign="auto"` never swaps the kernel for the reference behind the
    caller's back: a kernel bug and a kernel that is unavailable (the error
    a compiler refusal surfaces as) both propagate."""
    import repro.core.kmeans as km_mod
    import repro.kernels.kmeans_assign.ops as ops_mod

    x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 4)), jnp.float32)
    c = x[:3]
    cfg = KMeansConfig(k=3, iter="two_pass", assign="auto")

    def broken(*a, **kw):
        raise ValueError("kernel bug")

    monkeypatch.setattr(ops_mod, "kmeans_assign", broken)
    with pytest.raises(ValueError, match="kernel bug"):
        km_mod._assign(x, c, None, cfg)

    def unavailable(*a, **kw):
        raise NotImplementedError("kernel refused")

    monkeypatch.setattr(ops_mod, "kmeans_assign", unavailable)
    for assign in ("auto", "fused"):
        with pytest.raises(NotImplementedError, match="kernel refused"):
            km_mod._assign(x, c, None, KMeansConfig(k=3, iter="two_pass",
                                                    assign=assign))
    # assign="ref" is the explicit reference request and never calls it
    labels, _ = km_mod._assign(x, c, None, KMeansConfig(
        k=3, iter="two_pass", assign="ref"))
    want_labels, _ = assign_ref(x, c)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(want_labels))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(20, 200), k=st.integers(2, 8), d=st.integers(1, 10), seed=st.integers(0, 10**6))
def test_property_lloyd_never_increases_inertia(n, k, d, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    key = jax.random.PRNGKey(seed % 13)
    prev_inertia = None
    C = kmeanspp_init(x, k, key)
    for _ in range(4):
        labels, dmin = assign_ref(x, C)
        inertia = float(dmin.sum())
        if prev_inertia is not None:
            assert inertia <= prev_inertia * (1 + 1e-4)
        prev_inertia = inertia
        C = update_centroids(x, labels, k, C)
