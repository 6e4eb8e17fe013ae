"""The program's named scopes, read back for a traced job's Stage 2.

The program wraps every application of Stage 2's operator in
``jax.named_scope("spmv")``, its Gram-Schmidt in ``"orthogonalize"`` and its
restarts in ``"restart"`` (DESIGN.md §18).  A scope is HLO metadata: the
compiled program's text gives each instruction an ``op_name`` such as
``jit(stage2)/stage2/while/body/spmv/gather``, and the device trace names
each operation by its instruction (``fusion.128``).  So the device seconds
under a scope are the durations of the operations, run inside a program's
run on the ``XLA Modules`` line, whose instruction's ``op_name`` holds it.

A traced job (``jobs.run``) runs Stage 2 as the program ``jit_stage2``.
:func:`stage2_scopes` compiles that program again, as the traced job does
(the compile cache holds it), reads its text, and keeps the map only if it
names every operation the trace ran in that program.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional

from bench import deploy, harness

STAGE2 = "jit_stage2"  # the traced job's Stage-2 program
# the program's scopes (DESIGN.md §18); the innermost comes last in an op_name
SCOPES = ("stage1", "stage2", "stage3", "spmv", "orthogonalize", "restart",
          "kmeans_seed", "oos_knn", "oos_interpolate", "oos_assign")
HLO_MODULE = re.compile(r"^HloModule ([^ ,]+)")
HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^ ]+) = (.*)$", re.M)
OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def scope_map(hlo_text: str) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` from a compiled program's
    ``as_text()``, ``""`` for an instruction the compiler added with no
    ``op_name`` (``copy-done``); the program is the HLO module's name
    (``jit_stage2``), as the ``XLA Modules`` line names its runs
    (``jit_stage2(7)``)."""
    m = HLO_MODULE.match(hlo_text)
    if m is None:
        raise ValueError("not the text of an HLO module")
    ops = {}
    for name, rest in HLO_INSTRUCTION.findall(hlo_text):
        op_name = OP_NAME.search(rest)
        ops[name] = op_name.group(1) if op_name else ""
    return {m.group(1): ops}


def program_of(run: str) -> str:
    """A program run's program: ``jit_stage2(7)`` → ``jit_stage2``."""
    return run.split("(", 1)[0]


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost of the program's scopes in an op_name
    (``jit(stage2)/stage2/while/body/spmv/gather`` → ``spmv``)."""
    inner = [p for p in (op_name or "").split("/") if p in SCOPES]
    return inner[-1] if inner else None


def scoped_ops(trace: dict, scopes: Dict[str, Dict[str, str]],
               window=None) -> Iterable[tuple]:
    """``(plane, op, program, op_name)`` for each device operation (clipped
    to ``window`` where given) that ran inside a run of a program in
    ``scopes``: the ``XLA Modules`` run that holds the op's start."""
    for plane, ops in trace["device"].items():
        runs = sorted((s, s + d, program_of(name))
                      for name, s, d in trace.get("modules", {}).get(plane, []))
        starts = [r[0] for r in runs]
        for o in ops:
            if window:
                s, e = max(o[2], window[0]), min(o[2] + o[3], window[1])
                if e <= s:
                    continue
                o = [o[0], o[1], s, e - s]
            i = bisect.bisect_right(starts, o[2]) - 1
            if i < 0 or o[2] >= runs[i][1] or runs[i][2] not in scopes:
                continue
            prog = runs[i][2]
            yield plane, o, prog, scopes[prog].get(o[0])


def scope_seconds(trace: dict, scopes: Dict[str, Dict[str, str]], scope,
                  window=None) -> float:
    """Device seconds of the operations whose op_name lies under ``scope``
    (a scope's name, or a tuple of names: under any of them), summed over
    the chips, clipped to ``window`` where given."""
    names = {scope} if isinstance(scope, str) else set(scope)
    t = 0.0
    for _, o, _, op_name in scoped_ops(trace, scopes, window):
        if op_name and names & set(op_name.split("/")):
            t += o[3]
    return t * 1e-9


def traced_stage2_text(cfg: dict) -> str:
    """The HLO text of the Stage-2 program a traced job of the deployment
    runs: ``pipe.embed`` jitted as ``stage2`` over a graph that Stage 1
    placed, and a raw key, as a traced job compiles it."""
    import jax
    import numpy as np

    gen_mod = deploy.generator(cfg)
    pipe = deploy.pipeline(cfg)
    inputs = gen_mod.inputs(cfg, gen_mod.dataset(cfg, cfg["data_seeds"][0]))
    build = gen_mod.stage1(cfg, pipe)

    def stage1(*args):
        return build(*args)

    def stage2(graph, key):
        return pipe.embed(graph, key)

    g0 = jax.jit(stage1)(*inputs)
    key = np.zeros(2, np.uint32)
    return jax.jit(stage2).lower(g0, key).compile().as_text()


def stage2_scopes(ctx: dict) -> Optional[Dict[str, Dict[str, str]]]:
    """The traced job's Stage-2 scope map, or None where it does not name
    every operation the trace ran in that program: then it is not the
    program that ran.  The map is ``ctx["scopes"]``; where the run gives
    none, :func:`traced_stage2_text`'s, kept there for the other readers."""
    if "scopes" not in ctx:
        ctx["scopes"] = scope_map(traced_stage2_text(ctx["cfg"]))
    scopes = ctx["scopes"]
    if STAGE2 not in scopes:
        return None
    ran = [o[0] for _, o, prog, _ in scoped_ops(ctx["trace"], scopes,
                                                ctx["window"])
           if prog == STAGE2]
    if not ran or any(name not in scopes[STAGE2] for name in ran):
        return None
    return scopes


def applications(ctx: dict) -> Optional[List[int]]:
    """Operator applications of each of the window's jobs: its
    ``operator_applications`` where the run passes the program's
    counter, else the count its reported restarts imply
    (``work/spmv.py:matvecs``, the base of ``stage2_ms_per_matvec``)."""
    jobs = ctx.get("jobs", [])
    if jobs and all(j.get("operator_applications") is not None
                    for j in jobs):
        return [j["operator_applications"] for j in jobs]
    sizes = ctx.get("lanczos")
    if not jobs or sizes is None:
        return None
    spmv = harness.kernel_work("spmv")
    return [spmv.matvecs(restarts=j["restarts"], **sizes) for j in jobs]
