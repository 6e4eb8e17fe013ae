"""Readings that set the limits of ``correct``: the compared numbers of the
program on many seeds and of the control on a few, at the cell's own size,
in one process on the chip.

    python bench/calibrate.py --workload dti.job --seeds 12 --control-seeds 3

The control is the plain reference put in the program's place and computed
in bfloat16 (``bench/reference.py``).  A job cell's reading is one job of a
run's own path; a serving cell's is ``--seconds`` of its mix.  Each reading
is one JSON line; the last line sums them up: per number, the largest
reading of the program and the smallest of the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def control(kind: str, cfg: dict, mix: dict, seed: int, i: int,
            seconds: float, refs: dict) -> dict:
    from bench import deploy
    from bench.drivers import jobs, open_loop

    if kind == "open_loop":
        return open_loop.control_readings(cfg, mix, seed, seconds)
    d = cfg["data_seeds"][i % len(cfg["data_seeds"])]
    ds = deploy.generator(cfg).dataset(cfg, d)
    if d not in refs:
        refs[d] = jobs.reference_for(cfg, ds)
    return jobs.compare_job(cfg, jobs.control_job(cfg, ds, seed), refs[d])


def main(argv=None) -> int:
    import importlib

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="window of a serving reading")
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_config(bench, cell["config"])
    mix = harness.load_mix(cell["traffic"])
    try:
        devs = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"no readings: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    kind = mix["kind"]
    driver = importlib.import_module(f"bench.drivers.{kind}")
    seconds = 1e-3 if kind == "jobs" else args.seconds  # one job a reading
    refs: dict = {}
    summary: dict = {"program": {}, "control": {}}
    plan = ([("program", i) for i in range(args.seeds)]
            + [("control", i) for i in range(args.control_seeds)])
    for side, i in plan:
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        extra: dict = {}
        try:
            if side == "program":
                kw = {"ref_cache": refs} if kind == "jobs" else {}
                res = driver.run(cell, cfg, mix, seed, seconds, False, devs,
                                 time.time(), **kw)
                vals = {k: v["value"] for k, v in res["checks"].items()}
                extra = res.get("extra", {})
            else:
                vals = control(kind, cfg, mix, seed, i, seconds, refs)
        except Exception as e:  # a control that crashes has failed
            vals = {"error": repr(e)}
        print(json.dumps({"side": side, "seed": seed, "checks": vals,
                          "extra": extra, "wall_s": time.time() - t0}),
              flush=True)
        agg = summary[side]
        for k, v in vals.items():
            if isinstance(v, float):
                agg[k] = (max if side == "program" else min)(agg.get(k, v), v)
    print(json.dumps({"summary": summary, "limits": cfg["limits"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
