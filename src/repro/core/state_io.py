"""Pipeline-state checkpoints — a crashed embed must not redo Stage 1.

A :class:`~repro.core.spectral.PipelineState` is the value the stage DAG
threads; persisting the completed-stage prefix turns every
:class:`~repro.core.health.PipelineError` into a resumable interruption:

    try:
        out = pipe.run(x, key, checkpoint_dir="ckpt/run1")
    except PipelineError as e:
        ...fix the config/graph...
        out = pipe.run(resume_from="ckpt/run1")   # skips completed stages

The codec flattens the state into a FLAT name→array dict (dotted names for
nesting: ``graph.adj.row`` …) plus one uint8 leaf carrying a JSON meta
blob (provenance, reductions, reports, COO shapes, the pipeline config for
a mismatch warning).  Flat dicts are the one tree shape
:meth:`repro.ckpt.manager.CheckpointManager.restore_dict` can restore
without an example pytree — which is the point: resume happens in a fresh
process that has no live state to imitate.  The serving registry
(:mod:`repro.serve.registry`) uses the same discipline for its index
snapshots.

Sharded states round-trip too: a ShardedCOO serializes its
(row_local, col, val) buckets plus the partition meta (rows_per_shard /
num_shards / edges_per_shard) — the row-block LAYOUT is pure data; only
the mesh placement is a runtime resource, and restore returns host-side
arrays that the sharded operator re-places on first use (device_put /
jit resharding), exactly like every other restored leaf.
"""
from __future__ import annotations

import json
import warnings
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.core.health import StageReport
from repro.core.reduce import ReduceInfo, ReductionState
from repro.sparse.distributed import ShardedCOO
from repro.sparse.formats import COO

_META_KEY = "__meta__"
STATE_STEP = 0  # one checkpoint per directory: the latest prefix wins


def _put_coo(tree: Dict[str, np.ndarray], meta: dict, name: str,
             coo) -> None:
    if isinstance(coo, ShardedCOO):
        tree[f"{name}.row_local"] = np.asarray(coo.row_local)
        tree[f"{name}.col"] = np.asarray(coo.col)
        tree[f"{name}.val"] = np.asarray(coo.val)
        meta[name] = {"kind": "sharded", "shape": list(coo.shape),
                      "rows_per_shard": int(coo.rows_per_shard),
                      "num_shards": int(coo.num_shards),
                      "edges_per_shard": int(coo.edges_per_shard)}
        return
    tree[f"{name}.row"] = np.asarray(coo.row)
    tree[f"{name}.col"] = np.asarray(coo.col)
    tree[f"{name}.val"] = np.asarray(coo.val)
    meta[name] = {"kind": "coo", "shape": list(coo.shape),
                  "sorted_rows": bool(coo.sorted_rows)}


def _get_coo(tree: Dict[str, np.ndarray], meta: dict, name: str):
    m = meta[name]
    # pre-ShardedCOO checkpoints carry no "kind" tag — they are plain COO
    if m.get("kind", "coo") == "sharded":
        return ShardedCOO(row_local=jnp.asarray(tree[f"{name}.row_local"]),
                          col=jnp.asarray(tree[f"{name}.col"]),
                          val=jnp.asarray(tree[f"{name}.val"]),
                          shape=tuple(m["shape"]),
                          rows_per_shard=m["rows_per_shard"],
                          num_shards=m["num_shards"],
                          edges_per_shard=m["edges_per_shard"])
    return COO(row=jnp.asarray(tree[f"{name}.row"]),
               col=jnp.asarray(tree[f"{name}.col"]),
               val=jnp.asarray(tree[f"{name}.val"]),
               shape=tuple(m["shape"]), sorted_rows=m["sorted_rows"])


def _put_graph(tree, meta, name, g) -> None:
    _put_coo(tree, meta, f"{name}.adj", g.adj)
    tree[f"{name}.deg"] = np.asarray(g.deg)
    tree[f"{name}.inv_sqrt_deg"] = np.asarray(g.inv_sqrt_deg)
    if g.knn_tiles is not None:
        tree[f"{name}.knn_tiles"] = np.asarray(g.knn_tiles)


def _get_graph(tree, meta, name):
    from repro.core.spectral import GraphState

    return GraphState(adj=_get_coo(tree, meta, f"{name}.adj"),
                      deg=jnp.asarray(tree[f"{name}.deg"]),
                      inv_sqrt_deg=jnp.asarray(tree[f"{name}.inv_sqrt_deg"]),
                      knn_tiles=_optional(tree, f"{name}.knn_tiles"))


def state_to_tree(state, pipeline=None) -> Dict[str, np.ndarray]:
    """Flatten a :class:`PipelineState` to the flat dict the checkpoint
    manager stores.  ``pipeline`` (optional) embeds its ``to_dict()`` so
    resume can warn on a config mismatch."""
    tree: Dict[str, np.ndarray] = {}
    meta: dict = {
        "provenance": list(state.provenance),
        "reductions": [i._asdict() for i in state.reductions],
        "reports": [r.to_dict() for r in state.reports],
        "pipeline": pipeline.to_dict() if pipeline is not None else None,
    }
    if state.operator_override is not None:
        warnings.warn(
            "PipelineState.operator_override is a runtime resource and is "
            "not checkpointed — re-pass operator= after resume if the "
            "override mattered", RuntimeWarning, stacklevel=2)
    for name in ("points", "search_points", "key_embed", "key_cluster"):
        v = getattr(state, name)
        if v is not None:
            tree[name] = np.asarray(v)
    if state.input_graph is not None:
        _put_coo(tree, meta, "input_graph", state.input_graph)
    if state.graph is not None:
        _put_graph(tree, meta, "graph", state.graph)
    if state.embedding is not None:
        e = state.embedding
        tree["embedding.embedding"] = np.asarray(e.embedding)
        tree["embedding.eigenvalues"] = np.asarray(e.eigenvalues)
        tree["embedding.residuals"] = np.asarray(e.residuals)
        tree["embedding.restarts"] = np.asarray(e.restarts)
        tree["embedding.converged"] = np.asarray(e.converged)
        if e.operator_applications is not None:
            tree["embedding.operator_applications"] = np.asarray(
                e.operator_applications)
    if state.result is not None:
        r = state.result
        for f in ("labels", "embedding", "eigenvalues", "eig_residuals",
                  "kmeans_inertia", "lanczos_restarts", "kmeans_iterations"):
            tree[f"result.{f}"] = np.asarray(getattr(r, f))
        if r.operator_applications is not None:
            tree["result.operator_applications"] = np.asarray(
                r.operator_applications)
        meta["result_reports"] = [rep.to_dict() for rep in r.reports]
    if state.reduction is not None:
        red = state.reduction
        _put_graph(tree, meta, "reduction.fine", red.fine_graph)
        if red.prolong is not None:
            tree["reduction.prolong"] = np.asarray(red.prolong)
        meta["reduction_info"] = red.info._asdict()
    blob = json.dumps(meta).encode("utf-8")
    tree[_META_KEY] = np.frombuffer(blob, np.uint8).copy()
    return tree


def _reports_from_meta(items) -> Tuple[StageReport, ...]:
    return tuple(
        StageReport(stage=d["stage"], escalations=tuple(d["escalations"]),
                    attempts=d["attempts"], converged=d["converged"],
                    residual_max=d["residual_max"], wall_s=d["wall_s"])
        for d in items)


def _optional(tree: Dict[str, np.ndarray], key: str):
    """A field later versions added: ``None`` in an older checkpoint."""
    return jnp.asarray(tree[key]) if key in tree else None


def state_from_tree(tree: Dict[str, np.ndarray]):
    """Rebuild the :class:`PipelineState` (inverse of
    :func:`state_to_tree`).  Returns ``(state, pipeline_dict_or_None)``."""
    from repro.core.spectral import (
        EmbedState, PipelineState, SpectralResult)

    meta = json.loads(bytes(np.asarray(tree[_META_KEY])).decode("utf-8"))
    kw: Dict[str, Any] = {
        "provenance": tuple(meta["provenance"]),
        "reductions": tuple(ReduceInfo(**i) for i in meta["reductions"]),
        "reports": _reports_from_meta(meta["reports"]),
    }
    for name in ("points", "search_points", "key_embed", "key_cluster"):
        if name in tree:
            kw[name] = jnp.asarray(tree[name])
    if "input_graph" in meta:  # keyed via meta: COO and ShardedCOO differ
        kw["input_graph"] = _get_coo(tree, meta, "input_graph")
    if "graph.deg" in tree:
        kw["graph"] = _get_graph(tree, meta, "graph")
    if "embedding.embedding" in tree:
        kw["embedding"] = EmbedState(
            embedding=jnp.asarray(tree["embedding.embedding"]),
            eigenvalues=jnp.asarray(tree["embedding.eigenvalues"]),
            residuals=jnp.asarray(tree["embedding.residuals"]),
            restarts=jnp.asarray(tree["embedding.restarts"]),
            converged=jnp.asarray(tree["embedding.converged"]),
            operator_applications=_optional(
                tree, "embedding.operator_applications"))
    if "result.labels" in tree:
        kw["result"] = SpectralResult(
            labels=jnp.asarray(tree["result.labels"]),
            embedding=jnp.asarray(tree["result.embedding"]),
            eigenvalues=jnp.asarray(tree["result.eigenvalues"]),
            eig_residuals=jnp.asarray(tree["result.eig_residuals"]),
            kmeans_inertia=jnp.asarray(tree["result.kmeans_inertia"]),
            lanczos_restarts=jnp.asarray(tree["result.lanczos_restarts"]),
            kmeans_iterations=jnp.asarray(tree["result.kmeans_iterations"]),
            reports=_reports_from_meta(meta.get("result_reports", [])),
            operator_applications=_optional(
                tree, "result.operator_applications"))
    if "reduction.fine.deg" in tree:
        prolong = (jnp.asarray(tree["reduction.prolong"])
                   if "reduction.prolong" in tree else None)
        kw["reduction"] = ReductionState(
            fine_graph=_get_graph(tree, meta, "reduction.fine"),
            prolong=prolong, info=ReduceInfo(**meta["reduction_info"]))
    return PipelineState(**kw), meta.get("pipeline")


def save_state(directory: str, state, pipeline=None) -> str:
    """Persist the state prefix (crash-consistent via the checkpoint
    manager's tmp+fsync+rename).  One slot per directory — a later save
    (more completed stages) replaces the earlier one.  Returns the dir."""
    mgr = CheckpointManager(directory, keep=1)
    mgr.save(STATE_STEP, state_to_tree(state, pipeline), blocking=True)
    return directory


def load_state(directory: str, pipeline=None):
    """``(state, pipeline_dict)`` from :func:`save_state`'s slot.  When
    ``pipeline`` is given, warns if its config differs from the one the
    state was produced under (resume still proceeds — a *changed* config
    is exactly how an escalation-style manual fix resumes)."""
    mgr = CheckpointManager(directory, keep=1)
    if not mgr._complete(STATE_STEP):
        raise FileNotFoundError(
            f"no intact pipeline-state checkpoint in {directory!r}")
    state, pipe_dict = state_from_tree(mgr.restore_dict(STATE_STEP))
    if pipeline is not None and pipe_dict is not None \
            and pipeline.to_dict() != pipe_dict:
        warnings.warn(
            "resuming a pipeline-state checkpoint under a different "
            "pipeline config than the one that produced it — completed "
            "stages keep their old-config outputs",
            RuntimeWarning, stacklevel=2)
    return state, pipe_dict
