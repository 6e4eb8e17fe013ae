"""The four-chip cell's run on four virtual CPU devices (a subprocess, so
that the test process keeps its one device): correct when sound, and not
correct with the ring exchange between chips left out."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

SCRIPT = """
    import json, sys, time
    import jax
    if {left_out!r}:
        # every ppermute of the ring hands a block back to its own chip
        jax.lax.ppermute = lambda x, axis_name, perm: x
    from bench import harness
    from bench.drivers import jobs
    cfg = harness.load_json(harness.BENCH / "configs" / "dti_sharded4.json")
    cfg.update(n_points=1001, n_clusters=12, n_regions=6, data_seeds=[1])
    res = jobs.run({{}}, cfg, harness.load_mix("job"), 2 ** 33 + 9, 1e-3,
                   False, jax.devices()[:4], time.time())
    print(json.dumps({{"failed": res["failed"],
                      "pass": harness.checks_pass(res["checks"]),
                      "checks": res["checks"]}}))
"""


@pytest.mark.parametrize("left_out", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_sharded_run_correct_only_when_sound(left_out):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SCRIPT.format(
            left_out=left_out))],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["failed"] == 0
    assert res["pass"] == (not left_out), res["checks"]
