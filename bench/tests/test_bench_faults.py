"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run (the chip's look skipped, CPU, a small
deployment) with the program's output spoilt where it is produced: an
answer altered, half of the work left out and the rest's answer given
for it, or an eigenpair repeated or swapped for one from the bulk.
"""
import time

import pytest

from bench import harness
from bench.drivers import jobs, open_loop


def small(name, **kw):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    cfg.update(kw)
    return cfg


@pytest.fixture(scope="module")
def devs():
    import jax

    return jax.devices()[:1]


def labels_shifted(res, m):
    """Every tenth row's label moved to the next cluster."""
    import jax.numpy as jnp

    k = int(res.labels.max()) + 1
    n = res.labels.shape[0]
    shift = jnp.where(jnp.arange(n) % 10 == 0, 1, 0)
    return res._replace(labels=(res.labels + shift) % k)


def half_left_out(res, m):
    """Of the ``m`` real rows, the second half answered with the first
    half's answers."""
    import jax.numpy as jnp

    i = jnp.arange(res.labels.shape[0])
    src = jnp.where(i < m, i % max(1, m // 2), i)
    return res._replace(labels=res.labels[src], embedding=res.embedding[src])


def eigvec_repeated(res, m):
    """The second eigenvector returned again in place of the third, the
    eigenvalues left as they were (a ghost Ritz pair)."""
    import jax.numpy as jnp

    h = res.embedding.at[:, 2].set(res.embedding[:, 1])
    return res._replace(embedding=h / jnp.linalg.norm(h, axis=1,
                                                      keepdims=True))


def eigpair_swapped(res, m):
    """The second eigenpair replaced by one from the bulk: a value below the
    returned ones and a vector outside their span."""
    import jax
    import jax.numpy as jnp

    lam = res.eigenvalues.at[1].set(res.eigenvalues.max() + 0.1)
    h = res.embedding.at[:, 1].set(
        jax.random.normal(jax.random.key(0), res.embedding.shape[:1]))
    return res._replace(eigenvalues=lam, embedding=h / jnp.linalg.norm(
        h, axis=1, keepdims=True))


def spoil_job(fault):
    def wrap(compiled):
        def f(*args):
            res, adj = compiled(*args)
            return fault(res, res.labels.shape[0]), adj
        return f
    return wrap


def spoil_serve(fault):
    """The fault applied to the real rows of a padded batch (pad rows are
    zero rows; no query is)."""
    import numpy as np

    def wrap(call):
        def f(batch):
            m = int(np.any(np.asarray(batch) != 0, axis=1).sum())
            return fault(call(batch), m)
        return f
    return wrap


JOB_CELLS = {
    "dti": dict(n_points=1000, n_clusters=12, n_regions=6, data_seeds=[1]),
    "syn200": dict(n_blocks=10, block_size=50, intra_edges_per_block=600,
                   inter_edges=500, n_clusters=10, data_seeds=[1]),
}


@pytest.mark.parametrize("config", sorted(JOB_CELLS))
@pytest.mark.parametrize("fault", [None, labels_shifted, half_left_out,
                                   eigvec_repeated, eigpair_swapped],
                         ids=["sound", "answer_altered", "half_left_out",
                              "eigvec_repeated", "eigpair_swapped"])
def test_job_run_correct_only_when_sound(config, fault, devs):
    cfg = small(config, **JOB_CELLS[config])
    res = jobs.run({}, cfg, harness.load_mix("job"), 2 ** 33 + 5, 1e-3, False,
                   devs, time.time(),
                   wrap=None if fault is None else spoil_job(fault))
    assert harness.checks_pass(res["checks"]) == (fault is None), res["checks"]
    if fault in (eigvec_repeated, eigpair_swapped):
        # Stage 2's own comparison sees it, not only Stage 3's
        emb = res["checks"]["embed_err"]
        assert emb["value"] > emb["limit"], res["checks"]


@pytest.mark.parametrize("fault", [None, labels_shifted, half_left_out],
                         ids=["sound", "answer_altered", "half_left_out"])
def test_serve_run_correct_only_when_sound(fault, devs):
    cfg = small("dti_full", n_points=1000, n_clusters=12)
    mix = dict(harness.load_mix("serve"), rate_hz=100.0, check_requests=100)
    res = open_loop.run({}, cfg, mix, 2 ** 33 + 6, 1.0, False, devs,
                        time.time(),
                        wrap=None if fault is None else spoil_serve(fault))
    assert res["failed"] == 0
    assert harness.checks_pass(res["checks"]) == (fault is None), res["checks"]
