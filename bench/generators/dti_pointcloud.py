"""DTI brain-voxel deployments (Jin and JaJa 2018, section V-A): voxels of a
cubic lattice patch, each with a connectivity profile.

Jobs cluster a dataset of voxels by the spatial kNN graph weighted by the
profiles' cross-correlation.  Serving labels new voxels against an atlas
over the lattice.  ``dataset`` is a copy of the program's
``repro.data.pointcloud.dti_like_pointcloud`` (its ``neighbors="none"``
branch), kept here so that no change to the program can change the
benchmark's inputs.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench import reference as ref


def lattice(n_points: int) -> np.ndarray:
    """The first ``n_points`` voxels of a cube's lattice, in C order."""
    side = int(np.ceil(n_points ** (1 / 3)))
    grid = np.stack(np.meshgrid(*[np.arange(side)] * 3, indexing="ij"),
                    -1).reshape(-1, 3)
    return grid[:n_points].astype(np.float32)


def dataset(cfg: dict, data_seed: int) -> Dict[str, np.ndarray]:
    """Voxels with the connectivity profile of their latent region (a Voronoi
    cell of random centres) plus unit noise."""
    n, d, regions = cfg["n_points"], cfg["d_profile"], cfg["n_regions"]
    rng = np.random.default_rng(data_seed)
    side = int(np.ceil(n ** (1 / 3)))
    pos = lattice(n)
    centers = rng.uniform(0, side, (regions, 3)).astype(np.float32)
    d2 = ((pos[:, None, :] - centers[None]) ** 2).sum(-1)
    region = d2.argmin(1)
    base = rng.normal(size=(regions, d)).astype(np.float32) * 3
    profiles = base[region] + rng.normal(size=(n, d)).astype(np.float32)
    return {"positions": pos, "profiles": profiles, "truth": region}


def n_nodes(cfg: dict) -> int:
    return cfg["n_points"]


def inputs(cfg: dict, ds: dict) -> tuple:
    import jax.numpy as jnp

    return (jnp.asarray(ds["profiles"]), jnp.asarray(ds["positions"]))


def job(cfg: dict, pipe):
    """``job(profiles, positions, key) -> (SpectralResult, adjacency)``."""
    def run(profiles, positions, key):
        st = pipe.run_state(profiles, key, points=positions)
        return st.result, st.graph.adj
    return run


def stage1(cfg: dict, pipe):
    def build(profiles, positions):
        return pipe.build_graph(profiles, points=positions)
    return build


def reference_graph(cfg: dict, ds: dict, rnd=ref.exact):
    return ref.cross_correlation_knn_graph(ds["positions"], ds["profiles"],
                                           cfg["pipeline"]["knn_k"], rnd)


# ---------------------------------------------------------------------------
# serving: an atlas of the lattice, and new voxels to label
# ---------------------------------------------------------------------------

def served_index(cfg: dict, data_seed: int) -> dict:
    """The atlas a serving deployment holds: every voxel of the lattice, its
    parcel (a Voronoi cell of ``n_clusters`` random centres), an embedding
    row near its parcel's axis (unit length: the axis plus Gaussian noise of
    norm about ``index_noise``) and each parcel's mean row.  The rows are
    made on the device in one call from ``data_seed``; serving's work
    depends on their shapes, not on their values.  Returns the positions on
    the host and the rest on the device."""
    import jax
    import jax.numpy as jnp
    from scipy.spatial import cKDTree

    n, k = cfg["n_points"], cfg["n_clusters"]
    pos = lattice(n)
    rng = np.random.default_rng([data_seed, 7])
    side = int(np.ceil(n ** (1 / 3)))
    centers = rng.uniform(0, side, (k, 3))
    parcel = cKDTree(centers).query(pos.astype(np.float64))[1].astype(np.int32)

    @jax.jit
    def rows(key, parcel):
        noise = jax.random.normal(key, (n, k), jnp.float32)
        h = jax.nn.one_hot(parcel, k, dtype=jnp.float32) + noise * (
            cfg["index_noise"] / np.sqrt(k))
        h = h / jnp.linalg.norm(h, axis=1, keepdims=True)
        sums = jax.ops.segment_sum(h, parcel, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones(n, jnp.float32), parcel,
                                     num_segments=k)
        return h, sums / jnp.maximum(counts, 1.0)[:, None]

    h, means = rows(jax.random.key(data_seed), jnp.asarray(parcel))
    return {"points": pos, "embedding": h, "centroids": means,
            "labels": jnp.asarray(parcel)}


def queries(points: np.ndarray, count: int,
            rng: np.random.Generator) -> np.ndarray:
    """``count`` points at the centres of lattice cells whose eight corner
    voxels all exist: label transfer to a grid offset by half a voxel."""
    lat = points.astype(np.int64)
    side = int(lat.max()) + 1
    present = np.zeros((side + 1,) * 3, bool)
    present[lat[:, 0], lat[:, 1], lat[:, 2]] = True
    full = np.ones((side,) * 3, bool)
    for dx in (0, 1):
        for dy in (0, 1):
            for dz in (0, 1):
                full &= present[dx:dx + side, dy:dy + side, dz:dz + side]
    cells = np.argwhere(full)
    pick = cells[rng.integers(0, len(cells), count)]
    return (pick + 0.5).astype(np.float32)
