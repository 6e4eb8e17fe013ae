"""The jnp reference of the chunked product: every slot's row recovered
from its lane's key and its chunk's tile, then a gather and a segment sum
over the slots, as the COO product does over the nonzeros."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def coo_tiles_spmv_ref(t, x: jax.Array) -> jax.Array:
    """``y = A x`` over a :class:`~repro.kernels.coo_spmv.ops.CooTiles`:
    its ``t.rows`` rows, from ``x`` [t.n]."""
    nc = t.tile_of.shape[0]
    lanes = t.cols.shape[1]
    sub = t.cols.shape[0] // t.keys.shape[0]
    keys = t.keys.reshape(nc, -1, 1, lanes)  # [chunk, vreg, 1, lane]
    rows = t.tile_of[:, None, None, None] * (sub * lanes) + keys
    rows = jnp.broadcast_to(rows, (nc, keys.shape[1], sub, lanes)).reshape(-1)
    cols = t.cols.reshape(-1)
    live = (keys >= 0).repeat(sub, axis=2).reshape(-1) & (cols >= 0)
    xf = x.astype(jnp.float32)
    prod = jnp.where(live, t.vals.reshape(-1) * xf[jnp.maximum(cols, 0)], 0.0)
    y = jax.ops.segment_sum(prod, jnp.where(live, rows, t.rows), t.rows + 1)
    return y[:t.rows].astype(x.dtype)
