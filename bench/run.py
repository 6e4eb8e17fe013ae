"""Run one cell of ``BENCHMARK.json`` once, on the TPU this process finds.

    python bench/run.py --workload dti.job --seed 7 --seconds 51 --trace 0

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` a run of its own under the profiler gives its per-layer
metrics, each read by ``bench/layers/<metric>.py``, and a ``breakdown``.
Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_NO_CHIP = 3


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the reduced trace as JSON to this file")
    return ap


def per_layer(bench: dict, cell: str, ctx: dict, bench_dir) -> dict:
    """Each per-layer metric of the cell its reader finds something for."""
    from bench import harness

    out = {}
    for m in harness.per_layer_for(bench, cell):
        got = harness.layer_reader(m["name"], bench_dir).read(ctx)
        if got is None:
            continue
        if not isinstance(got, dict):
            got = {"value": got}
        out[m["name"]] = {"value": float(got.pop("value")), "unit": m["unit"],
                          **got}
    return out


def breakdown(ctx: dict) -> dict:
    from bench import trace as tr

    default = "between_jobs" if ctx["kind"] == "jobs" else "between_batches"
    return {"device_ops": tr.top_device_ops(ctx["trace"], ctx["window"],
                                            default),
            "idle_gaps": tr.longest_idle_gaps(ctx["trace"], ctx["window"],
                                              default)}


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, devs=None, root=None, **driver_kw) -> dict:
    """Drive one run of cell ``name`` of ``bench`` (the ``BENCHMARK.json``
    at ``root``); the result before it is printed."""
    import importlib

    from bench import harness
    from bench import trace as tr

    root = harness.ROOT if root is None else root
    cell = harness.find_cell(bench, name)
    cfg = harness.load_config(bench, cell["config"], root)
    mix = harness.load_mix(cell["traffic"], root / "bench")
    if devs is None:
        devs = harness.require_chips(cell["chips"])
    harness.enable_cache()
    t_devices = time.time() - t_start
    driver = importlib.import_module(f"bench.drivers.{mix['kind']}")
    res = driver.run(cell, cfg, mix, seed, seconds, trace, devs, t_start,
                     **driver_kw)
    res["extra"]["setup_phases_s"]["devices"] = t_devices
    wanted = {m["name"] for m in harness.end_to_end_for(bench, name)}
    res["metrics"] = {k: v for k, v in res["metrics"].items() if k in wanted}
    if trace:
        ctx = res.pop("ctx")
        win = tr.span_windows(ctx["trace"], "window")
        ctx["window"] = win[0]
        ctx["peaks"] = harness.peaks(res["device"]["kind"])
        res["device"]["busy_s"] = tr.busy_s(ctx["trace"], ctx["window"])
        res["device"]["window_s"] = (win[0][1] - win[0][0]) * 1e-9
        res["metrics"] = per_layer(bench, name, ctx, root / "bench")
        res["breakdown"] = breakdown(ctx)
    return res


def main(argv=None) -> int:
    from bench import harness

    t_start = harness.process_start_wall()
    args = build_parser().parse_args(argv)
    bench = harness.load_benchmark()
    try:
        res = run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    if args.keep_trace and "trace" in res:
        with open(args.keep_trace, "w") as f:
            json.dump(res["trace"], f)
    print(json.dumps({"extra": res.get("extra", {})}), file=sys.stderr)
    # a job or request that never came back is wrong as well
    checks = dict(res["checks"], failed={"value": res["failed"], "limit": 0})
    harness.print_result(harness.checks_pass(checks), res["attempted"],
                         res["failed"], res["metrics"], res["device"], checks,
                         res.get("breakdown"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
