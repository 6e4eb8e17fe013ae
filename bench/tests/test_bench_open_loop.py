"""The open-loop generator: arrivals, and latency taken from the due time."""
import json
import time

import numpy as np
import pytest

from bench import gen, harness
from bench.drivers import open_loop


def test_every_seed_offers_the_same_arrivals_in_another_order():
    a = gen.arrival_offsets(100.0, 10.0, 0, 2 ** 40 + 7)
    b = gen.arrival_offsets(100.0, 10.0, 0, 3)
    assert len(a) == len(b) == 1000
    # the same gaps (all but the one left after the last arrival)
    common = np.intersect1d(np.round(np.diff(a), 12), np.round(np.diff(b), 12))
    assert len(common) >= len(a) - 2
    assert a[-1] < 10.0 and not np.allclose(a, b)
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)


def test_latency_counts_from_the_due_time():
    due = np.array([0.0, 0.010, 0.020, 0.030])
    done = np.array([0.005, 0.300, 0.300, np.nan])  # a stall, then a loss
    assert open_loop.latency_ms(due, done).tolist() == pytest.approx(
        [5.0, 290.0, 280.0, np.inf])


@pytest.fixture(scope="module")
def tiny_dti():
    cfg = harness.load_json(harness.BENCH / "configs" / "dti_full.json")
    cfg.update(n_points=600, n_clusters=6, data_seeds=[1])
    return cfg


def test_a_stalled_server_shows_in_the_tail(tiny_dti):
    """The serving function stalls 0.4 s once: every request due meanwhile
    waits, and the latency from its due time says so."""
    import jax

    mix = dict(harness.load_mix("serve"), rate_hz=100.0, check_requests=50)
    calls = {"n": 0}

    def stall_once(call):
        def f(batch):
            calls["n"] += 1
            if calls["n"] == 3:  # warm-up is call 1
                time.sleep(0.4)
            return call(batch)
        return f

    res = open_loop.run({}, tiny_dti, mix, 11, 1.0, False, jax.devices()[:1],
                        time.time(), wrap=stall_once)
    assert res["failed"] == 0 and res["attempted"] == 100
    assert res["metrics"]["serve_p95_ms"]["value"] > 200.0
    assert harness.checks_pass(res["checks"])
    json.dumps(res["metrics"])
