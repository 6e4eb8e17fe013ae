"""Planted-partition graphs (the paper's Syn200): graph input, so Stage 1
only normalizes.

``dataset`` follows the program's ``repro.data.sbm.sbm_graph`` but places a
fixed number of edges in each block pair, so that every data seed has
exactly the published edge count.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench import reference as ref


def dataset(cfg: dict, data_seed: int) -> Dict[str, np.ndarray]:
    """``intra_edges_per_block`` distinct pairs drawn inside every block,
    ``inter_edges`` spread as evenly as whole numbers allow over the block
    pairs (which pairs get one more is drawn), unit weights, both
    directions, rows sorted."""
    n_blocks, size = cfg["n_blocks"], cfg["block_size"]
    rng = np.random.default_rng(data_seed)
    iu, ju = np.triu_indices(size, 1)
    rows, cols = [], []
    for b in range(n_blocks):
        sel = rng.choice(iu.size, cfg["intra_edges_per_block"], replace=False)
        rows.append(iu[sel] + b * size)
        cols.append(ju[sel] + b * size)
    bi, bj = np.triu_indices(n_blocks, 1)
    per, extra = divmod(cfg["inter_edges"], bi.size)
    count = np.full(bi.size, per)
    count[rng.permutation(bi.size)[:extra]] += 1
    for i, j, c in zip(bi, bj, count):
        sel = rng.choice(size * size, c, replace=False)
        rows.append(sel // size + i * size)
        cols.append(sel % size + j * size)
    r = np.concatenate(rows)
    c = np.concatenate(cols)
    row = np.concatenate([r, c])
    col = np.concatenate([c, r])
    order = np.lexsort((col, row))
    return {"row": row[order].astype(np.int32),
            "col": col[order].astype(np.int32),
            "val": np.ones(row.size, np.float32),
            "truth": np.repeat(np.arange(n_blocks), size)}


def n_nodes(cfg: dict) -> int:
    return cfg["n_blocks"] * cfg["block_size"]


def inputs(cfg: dict, ds: dict) -> tuple:
    import jax.numpy as jnp

    return (jnp.asarray(ds["row"]), jnp.asarray(ds["col"]),
            jnp.asarray(ds["val"]))


def job(cfg: dict, pipe):
    """``job(row, col, val, key) -> (SpectralResult, adjacency)``."""
    from repro.sparse.formats import COO

    n = n_nodes(cfg)

    def run(row, col, val, key):
        st = pipe.run_state(COO(row, col, val, (n, n)), key)
        return st.result, st.graph.adj
    return run


def stage1(cfg: dict, pipe):
    from repro.sparse.formats import COO

    n = n_nodes(cfg)

    def prepare(row, col, val):
        return pipe.prepare(COO(row, col, val, (n, n)))
    return prepare


def reference_graph(cfg: dict, ds: dict, rnd=ref.exact):
    return ref.graph_from_edges(ds["row"], ds["col"], ds["val"], n_nodes(cfg))
