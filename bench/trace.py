"""From a profiler trace to per-layer numbers.

A trace is reduced to a plain dict, which is also how a small recorded
trace is kept for the tests::

    {"device":  {"<plane>": [[instruction, opcode, start_ns, dur_ns], ...]},
     "modules": {"<plane>": [[program name, start_ns, dur_ns], ...]},
     "host":    [[span name, start_ns, dur_ns], ...]}

``device`` holds, per chip, the operations of the profiler's ``XLA Ops``
line, named by their HLO instruction (``fusion.45``, ``knn_topk.1``).  The
control-flow operations (``while``, ``conditional``, ``call``) are left
out: their events span the whole loop or branch, whose body operations are
events of their own, so keeping them would count the gaps inside a loop as
busy.  ``modules`` holds, per chip, the runs of whole compiled programs
(the ``XLA Modules`` line, ``jit_stage2(...)``).  ``host`` holds the
benchmark's own spans (``jax.profiler.TraceAnnotation``).

The profiler puts device and host events on one clock only to within some
milliseconds, so an operation is named by the program it ran in where that
program is named after one of the host spans (the traced jobs' ``stage1``,
``stage2``, ``stage3``), and by the host span open at its start otherwise.
"""
from __future__ import annotations

import bisect
import glob
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = {"while", "conditional", "call"}
OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all",
    re.I)


def parse_op(text: str) -> Tuple[str, str]:
    """``(instruction, opcode)`` of an ``XLA Ops`` event, whose name is the
    HLO instruction's text (``%fusion.4 = f32[8]{0} fusion(...), ...``)
    or, on some versions, the instruction's name alone."""
    if " = " not in text:
        name = text.lstrip("%")
        return name, family(name)
    lhs, rhs = text.split(" = ", 1)
    m = OPCODE.search(" " + rhs)
    return lhs.strip().lstrip("%"), (m.group(1) if m else "?")


def family(name: str) -> str:
    """An instruction's name without its instance number (``fusion.12`` →
    ``fusion``, ``knn_topk.1`` → ``knn_topk``)."""
    return re.sub(r"\.\d+$", "", name)


def load_xplane(trace_dir: str, span_names: Iterable[str]) -> dict:
    """Read the one ``.xplane.pb`` under ``trace_dir`` with JAX's own reader
    and keep the device operations and the named host spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    spans = set(span_names)
    out: dict = {"device": {}, "modules": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods += [[ev.name, ev.start_ns, ev.duration_ns]
                             for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, opcode = parse_op(ev.name)
                    if opcode not in CONTAINERS:
                        ops.append([name, opcode, ev.start_ns, ev.duration_ns])
            out["device"][plane.name] = ops
            out["modules"][plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        out["host"].append([ev.name, ev.start_ns,
                                            ev.duration_ns])
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_intervals(ops, keep: Optional[Callable[[list], bool]] = None):
    return [(o[2], o[2] + o[3]) for o in ops if keep is None or keep(o)]


def busy_s(trace: dict, window: Interval) -> float:
    """Seconds in the window in which some operation ran, averaged over
    the chips."""
    planes = list(trace["device"].values())
    if not planes:
        return 0.0
    return sum(total(clip(merge(op_intervals(ops)), window))
               for ops in planes) / len(planes) * 1e-9


def idle_share(trace: dict, window: Interval) -> float:
    """Percent of the window in which no operation ran (mean over chips)."""
    span = (window[1] - window[0]) * 1e-9
    return 100.0 * (1.0 - busy_s(trace, window) / span)


def kernel_seconds(trace: dict, kernel: str,
                   window: Optional[Interval] = None) -> float:
    """Device seconds of the Pallas kernel ``kernel`` (its instructions are
    named after it), summed over the chips, clipped to ``window`` where
    given."""
    def keep(o):
        return family(o[0]) == kernel

    t = 0.0
    for ops in trace["device"].values():
        iv = op_intervals(ops, keep)
        t += total(clip(iv, window) if window else iv)
    return t * 1e-9


def kernel_calls(trace: dict, kernel: str, window: Interval) -> int:
    """How many times ``kernel`` ran in the window, summed over the chips."""
    return sum(1 for ops in trace["device"].values() for o in ops
               if family(o[0]) == kernel and window[0] <= o[2] < window[1])


def is_collective(op: list) -> bool:
    return bool(COLLECTIVE.search(op[1]) or COLLECTIVE.search(op[0]))


def collective_exposed_s(trace: dict, window: Interval) -> float:
    """Seconds of collective operations during which no other operation ran
    on that chip, averaged over the chips."""
    planes = list(trace["device"].values())
    if not planes:
        return 0.0
    t = 0.0
    for ops in planes:
        coll = clip(merge(op_intervals(ops, is_collective)), window)
        comp = merge(op_intervals(ops, lambda o: not is_collective(o)))
        t += total(subtract(coll, comp))
    return t / len(planes) * 1e-9


class Where:
    """What an instant on a chip belongs to: the host span that names the
    program running there, else the benchmark's host span (other than
    ``window``) open at that instant, else ``default``."""

    def __init__(self, trace: dict, default: str):
        spans = sorted((s, s + d, name) for name, s, d in trace["host"]
                       if name != "window")
        self.spans = spans
        self.starts = [s for s, _, _ in spans]
        self.default = default
        names = sorted({name for _, _, name in spans})
        self.modules = {}
        for plane, evs in trace.get("modules", {}).items():
            runs = sorted((s, s + d, _named(prog, names))
                          for prog, s, d in evs)
            self.modules[plane] = ([s for s, _, _ in runs], runs)

    def at(self, t: float, plane: Optional[str] = None) -> str:
        starts, runs = self.modules.get(plane, ([], []))
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < runs[i][1] and runs[i][2]:
            return runs[i][2]
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.spans[i][1]:
            return self.spans[i][2]
        return self.default


def _named(program: str, names: List[str]) -> Optional[str]:
    """The host span a program is named after (``jit_stage2(7)`` →
    ``stage2``), if any."""
    m = re.match(r"(?:jit_)?([A-Za-z0-9_]+)", program)
    return m.group(1) if m and m.group(1) in names else None


def top_device_ops(trace: dict, window: Interval, default: str, n: int = 10):
    """The ``n`` instructions that took most device seconds in the window
    (mean over chips), each named ``<span>:<instruction>`` by what it
    belongs to (:class:`Where`), as ``[name, seconds]``."""
    planes = list(trace["device"].items())
    where = Where(trace, default)
    acc: Dict[str, float] = {}
    for plane, ops in planes:
        for o in ops:
            s, e = max(o[2], window[0]), min(o[2] + o[3], window[1])
            if e > s:
                key = f"{where.at(o[2], plane)}:{o[0]}"
                acc[key] = acc.get(key, 0.0) + (e - s) * 1e-9
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(1, len(planes))] for k, v in ranked]


def longest_idle_gaps(trace: dict, window: Interval, default: str,
                      n: int = 10):
    """The ``n`` longest stretches of the window in which the first chip ran
    nothing, each named by what its middle belongs to (:class:`Where`):
    a gap inside a named program's run by that program, one between runs
    by the host span open then, ``default`` where only the window is."""
    if not trace["device"]:
        return []
    plane, ops = next(iter(trace["device"].items()))
    busy = clip(merge(op_intervals(ops)), window)
    gaps = subtract([window], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    where = Where(trace, default)
    return [[where.at((s + e) / 2, plane), (e - s) * 1e-9]
            for s, e in gaps[:n]]


def span_windows(trace: dict, name: str) -> List[Interval]:
    return [(s, s + d) for nm, s, d in trace["host"] if nm == name]
