"""Find the highest rate a serving cell sustains: its mix at each of a few
fixed rates, one after another in one process on the chip.

    python bench/sweep.py --workload dti.serve --rates 250,1000,4000 --seconds 10

Each rate prints one JSON line: the offered and the served labels per
second, the 95th-percentile latency from the due time, how late the
generator ran, and the batches' fill.  A rate is sustained where the served
rate keeps up with the offered one and the tail stays within the limit the
mix states (``sustain_p95_ms``).  The cell's rate is then fixed in its mix
file by hand; the benchmark never searches for one.
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


def main(argv=None) -> int:
    from bench import harness
    from bench.drivers import open_loop

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="requests per second, comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=4_000_000_007)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_config(bench, cell["config"])
    mix = harness.load_mix(cell["traffic"])
    try:
        devs = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"no sweep: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        # a sweep looks for the knee; a few answers compared are enough
        res = open_loop.run(cell, cfg, dict(mix, rate_hz=rate, check_requests=16),
                            args.seed, args.seconds, False, devs, time.time())
        m, x = res["metrics"], res["extra"]
        print(json.dumps({
            "rate_hz": rate,
            "offered_labels_per_s": rate * mix["rows_per_request"],
            "served_labels_per_s": m["serve_labels_per_s"]["value"],
            "p95_ms": m["serve_p95_ms"]["value"],
            "limit_p95_ms": mix["sustain_p95_ms"],
            "failed": res["failed"], "correct": harness.checks_pass(res["checks"]),
            "lag_p95_ms": x["lag_p95_ms"],
            "fill": x["fill"], "batches": x["batches"],
            "call_ms_mean": x["call_ms_mean"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
