"""Public jit'd wrapper for the fused k-means assignment kernel.

Handles shape padding (n→block_q, k→block_k, d→128 multiples), adds the
row-constant ‖x‖² back into the returned distances, and picks the execution
path: real Pallas on TPU, interpret-mode Pallas for validation, or the jnp
reference on other backends (the wrapper is what `repro.core.kmeans` calls).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels._util import (
    KMEANS_BLOCK_K,
    KMEANS_BLOCK_Q,
    pad_to as _pad_to,
    round_up as _round_up,
)
from repro.kernels.kmeans_assign.kernel import kmeans_assign_pallas
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref


@partial(jax.jit, static_argnames=("block_q", "block_k", "impl", "interpret"))
def kmeans_assign(
    x: jax.Array,
    c: jax.Array,
    *,
    x_norm: jax.Array | None = None,
    block_q: int = KMEANS_BLOCK_Q,
    block_k: int = KMEANS_BLOCK_K,
    impl: str = "auto",  # "auto" | "pallas" | "ref"
    interpret: bool | None = None,
):
    """labels[i], dist²[i] = argmin_j / min_j ‖x_i − c_j‖².

    On non-TPU backends ``auto`` falls back to the jnp reference — the Pallas
    kernel is the TPU target and interpret mode is for tests (it executes the
    kernel body in Python and is far too slow for production CPU use).
    """
    n, d = x.shape
    k = c.shape[0]
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu and not interpret):
        return kmeans_assign_ref(x, c, x_norm)

    if interpret is None:
        interpret = not on_tpu

    bq = min(block_q, _round_up(n, 8))
    bk = min(block_k, _round_up(k, 128))
    n_p = _round_up(n, bq)
    k_p = _round_up(k, bk)
    d_p = _round_up(d, 128)

    xf = _pad_to(_pad_to(x.astype(jnp.float32), n_p, 0), d_p, 1)
    cf = _pad_to(_pad_to(c.astype(jnp.float32), k_p, 0), d_p, 1)
    cn = (cf * cf).sum(1)
    # padded centroids must never win the argmin
    if k_p > k:
        cn = cn.at[k:].set(jnp.inf)

    tile_min, labels = kmeans_assign_pallas(
        xf, cf, cn[None, :], block_q=bq, block_k=bk, interpret=interpret
    )
    tile_min, labels = tile_min[0], labels[0]
    xn = (x.astype(jnp.float32) ** 2).sum(1) if x_norm is None else x_norm.astype(jnp.float32)
    dist2 = jnp.maximum(tile_min[:n] + xn, 0.0)
    return labels[:n], dist2
