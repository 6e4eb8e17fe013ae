"""What the readers of a cell whose Stage 2 runs row-sharded over its chips
need (the program's DESIGN.md §20): the scope map of the Stage-2 program a
traced job of the cell runs, compiled over the cell's chips, and each
chip's nonzeros.

A chip holds ``ceil(n / S)`` consecutive rows of the graph and, in its
Stage-2 product, all-gathers ``x`` under the program's scope
``spmv_gather`` inside ``spmv``, then runs the ``coo_spmv`` kernel over its
own rows.  Where the program has no ``spmv_gather`` scope (its Stage 2 is
not row-sharded), the readers find nothing.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import deploy, scopes

GATHER = "spmv_gather"


def devices(cfg: dict):
    import jax

    return jax.devices()[:cfg.get("chips", 1)]


def stage2_text(cfg: dict) -> str:
    """The HLO text of the Stage-2 program a traced job runs over the cell's
    chips: ``pipe.embed`` jitted as ``stage2`` over a graph that Stage 1
    placed, and a raw key, as a traced job compiles it."""
    import jax

    gen_mod = deploy.generator(cfg)
    pipe = deploy.pipeline(cfg, devices(cfg))
    inputs = gen_mod.inputs(cfg, gen_mod.dataset(cfg, cfg["data_seeds"][0]))

    def stage2(graph, key):
        return pipe.embed(graph, key)

    g0 = _stage1(cfg, pipe)(*inputs)
    key = np.zeros(2, np.uint32)
    return jax.jit(stage2).lower(g0, key).compile().as_text()


def _stage1(cfg: dict, pipe):
    """Stage 1 jitted as a traced job jits it (the same program name, so
    the compile cache holds it)."""
    import jax

    build = deploy.generator(cfg).stage1(cfg, pipe)

    def stage1(*args):
        return build(*args)

    return jax.jit(stage1)


def chip_seconds(ctx: dict) -> Optional[Dict[str, Dict[str, float]]]:
    """Device seconds of Stage 2's scopes on each chip in the traced window:
    under ``spmv_gather`` (``"gather"``), under ``spmv`` but not
    ``spmv_gather`` (``"local"``, the chip's own product), under
    ``orthogonalize`` and under ``restart``.  None where the map (compiled
    over the cell's chips) does not name every operation the trace ran in
    the Stage-2 program, or has no ``spmv_gather`` scope.  One pass over
    the trace, kept in ``ctx`` for the other readers: a four-chip window
    holds tens of millions of operations."""
    if "row_shards" in ctx:
        return ctx["row_shards"]
    ctx["row_shards"] = None
    if "scopes" not in ctx:
        ctx["scopes"] = scopes.scope_map(stage2_text(ctx["cfg"]))
    smap = ctx["scopes"]
    names = smap.get(scopes.STAGE2, {})
    if not any(GATHER in name.split("/") for name in names.values()):
        return None
    out = {plane: dict.fromkeys(KINDS, 0.0) for plane in ctx["trace"]["device"]}
    kind: Dict[str, Optional[str]] = {}  # instruction -> where it counts
    ran = False
    for plane, o, prog, _ in scopes.scoped_ops(ctx["trace"], smap,
                                               ctx["window"]):
        if prog != scopes.STAGE2:
            continue
        ran = True
        if o[0] not in kind:
            if o[0] not in names:
                return None  # not the program that ran
            kind[o[0]] = _kind(names[o[0]].split("/"))
        if kind[o[0]] is not None:
            out[plane][kind[o[0]]] += o[3] * 1e-9
    ctx["row_shards"] = out if ran else None
    return ctx["row_shards"]


KINDS = ("gather", "local", "orthogonalize", "restart")


def _kind(parts: List[str]) -> Optional[str]:
    """Where an operation of the Stage-2 program counts, from its op_name's
    parts."""
    if GATHER in parts:
        return "gather"
    if "spmv" in parts:
        return "local"
    for scope in ("orthogonalize", "restart"):
        if scope in parts:
            return scope
    return None


def chip_mean(ctx: dict, kind: str) -> Optional[float]:
    """Seconds under ``kind`` (one of :data:`KINDS`) in the traced window,
    mean over the chips; None as :func:`chip_seconds`, or where it is 0."""
    chips = chip_seconds(ctx)
    if chips is None:
        return None
    secs = sum(c[kind] for c in chips.values()) / len(chips)
    return secs if secs > 0 else None


def chip_nnz(ctx: dict) -> Optional[List[List[int]]]:
    """Each window job's nonzeros on each chip, in the chips' order: the
    graph Stage 1 makes of each dataset, run again on the cell's chips, cut
    into ``ceil(n / S)`` rows a chip; a job takes the split of the datasets
    whose graph has its nonzeros.  None where datasets of that many
    nonzeros split differently."""
    cfg = ctx["cfg"]
    if "chip_nnz" not in ctx:
        gen_mod = deploy.generator(cfg)
        devs = devices(cfg)
        stage1 = _stage1(cfg, deploy.pipeline(cfg, devs))
        n, s = deploy.n_nodes(cfg), len(devs)
        rows = -(-n // s)
        splits: Dict[int, set] = {}
        for seed in cfg["data_seeds"]:
            inputs = gen_mod.inputs(cfg, gen_mod.dataset(cfg, seed))
            row = np.asarray(stage1(*inputs).adj.row)
            split = tuple(np.bincount(row // rows, minlength=s).tolist())
            splits.setdefault(int(row.size), set()).add(split)
        ctx["chip_nnz"] = splits
    splits = ctx["chip_nnz"]
    out = []
    for j in ctx.get("jobs", []):
        found = splits.get(j["nnz"], set())
        if len(found) != 1:
            return None
        out.append(list(next(iter(found))))
    return out
