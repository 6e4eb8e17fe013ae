"""What every cell shares: its files, the chip it needs, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; both
are data files found by name, and each per-layer metric is a reader module
found by name, so a cell, a deployment or a metric is added by adding files.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux ``/proc``); the
    set-up time runs from here.  Falls back to now where ``/proc`` lacks it."""
    try:
        with open("/proc/self/stat") as f:
            # field 22 (starttime, clock ticks after boot) follows the
            # parenthesised command name, which may itself hold spaces
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(cells: {[c['name'] for c in bench['workloads']]})")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration's file, with the benchmark directory its generator
    is found in (``bench_dir``)."""
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return dict(load_json(root / cfg["file"]),
                        bench_dir=str(root / "bench"))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_mix(name: str, bench_dir: Path = BENCH) -> dict:
    return load_json(bench_dir / "mixes" / f"{name}.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end_for(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if applies(m, cell)]


def per_layer_for(bench: dict, cell: str) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_module(path: Path, name: str):
    """A module of the benchmark found by its file's name."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str, bench_dir: Path = BENCH):
    """The reader module ``layers/<metric>.py``: ``read(ctx) -> float|None``."""
    return load_module(bench_dir / "layers" / f"{metric}.py",
                       f"bench_layer_{metric.replace('.', '_')}")


def kernel_work(kernel: str, bench_dir: Path = BENCH):
    """The work module ``work/<kernel>.py``: ``work(**shapes) -> (ops, bytes)``."""
    return load_module(bench_dir / "work" / f"{kernel}.py",
                       f"bench_work_{kernel}")


def peaks(device_kind: str, bench_dir: Path = BENCH) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = load_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)}); add its published peaks")
    return table[device_kind]


def require_chips(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise.
    Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs[0].platform if devs else 'none'}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``launch/cache.enable_compile_cache``), holding every program however
    fast it compiled, so that a cell's later runs compile nothing."""
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_record(devs) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def checks_pass(checks: Dict[str, dict]) -> bool:
    return all(v["value"] is not None and v["value"] == v["value"]
               and v["value"] <= v["limit"] for v in checks.values())


def print_result(correct: bool, attempted: int, failed: int,
                 metrics: Dict[str, dict], device: dict,
                 checks: Dict[str, dict],
                 breakdown: Optional[dict] = None) -> None:
    """The numbers compared as the last lines of standard error, then the
    contract's JSON object as the last line of standard output (the
    compared numbers under ``checks``, its last key)."""
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
