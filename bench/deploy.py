"""A deployment, from its configuration file to the program's objects: the
pipeline it runs, and the generator that makes its data.

A configuration names its generator (``"generator"``), a module
``generators/<name>.py`` found by name, so that a deployment with data of a
new kind is added as files.  A generator gives:

- ``dataset(cfg, data_seed)``: one dataset, made on the host;
- ``n_nodes(cfg)``: the nodes of the graph a job clusters;
- ``inputs(cfg, ds)``: the arrays a job hands the program, on the device;
- ``job(cfg, pipe)``: ``job(*inputs, key) -> (SpectralResult, adjacency)``,
  one clustering job through ``SpectralPipeline.run_state``;
- ``stage1(cfg, pipe)``: ``stage1(*inputs) -> GraphState``, Stage 1 alone
  (traced runs call the three stages apart);
- ``reference_graph(cfg, ds, rnd)``: the plain reference's graph ``W``;

and, for a deployment that serves, ``served_index(cfg, data_seed)`` and
``queries(points, count, rng)``.
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Optional

import numpy as np

from bench import harness


_GENERATORS: dict = {}


def generator(cfg: dict):
    """The module ``generators/<cfg["generator"]>.py`` of the checkout the
    configuration was loaded from."""
    name = cfg["generator"]
    path = Path(cfg.get("bench_dir", harness.BENCH)) / "generators" / f"{name}.py"
    if path not in _GENERATORS:
        _GENERATORS[path] = harness.load_module(path, f"bench_generator_{name}")
    return _GENERATORS[path]


def n_nodes(cfg: dict) -> int:
    return generator(cfg).n_nodes(cfg)


def pipeline(cfg: dict, devs: Optional[List] = None):
    """The ``SpectralPipeline`` the configuration states."""
    import jax

    from repro.core.spectral import (EigConfig, GraphConfig, KMeansConfig,
                                     Plan, SpectralPipeline)

    p = cfg["pipeline"]
    plan = Plan()
    if p.get("plan", "single") == "sharded":
        mesh = jax.sharding.Mesh(np.asarray(devs), ("data",))
        plan = Plan(device="sharded", mesh=mesh,
                    stage1_exchange=p["stage1_exchange"])
    graph = GraphConfig()  # graph input: Stage 1 only normalizes
    if "knn_k" in p:
        graph = GraphConfig(knn_k=p["knn_k"], measure=p["measure"],
                            sigma=p["sigma"])
    return SpectralPipeline(
        n_clusters=cfg["n_clusters"], graph=graph, eig=EigConfig(tol=p["tol"]),
        kmeans=KMeansConfig(iter=p["kmeans_iter"]),
        plan=plan)


def lanczos_sizes(pipe, n: int) -> Optional[dict]:
    """The basis, the vectors kept at a restart and the block width of the
    Lanczos run the pipeline configures for ``n`` nodes, as the program
    reports them; None where Stage 2 runs another solver."""
    from repro.core import lanczos as lz

    cfg = pipe._eig_config(n)
    if not isinstance(cfg, lz.LanczosConfig):
        return None
    return {"basis": lz.effective_basis_size(cfg),
            "keep": lz.restart_keep_size(cfg),
            "block": max(1, cfg.block_size)}
