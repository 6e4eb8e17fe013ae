"""Shared shape-padding helpers + tile defaults for the kernel wrapper layer."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Single source of truth for the k-means kernel tile sizes — consumed by the
# `kmeans_assign` / `kmeans_iter` kernel packages AND by
# :class:`repro.core.kmeans.KMeansConfig` (which used to carry a drifted
# block_q=1024 default while the kernels defaulted to 512).  1024 wins the
# CPU chunked-scan sweep at n=20k/k=2048 (fewer, better-threaded GEMM steps)
# and keeps the TPU per-step VMEM working set ≤ ~8 MB.
KMEANS_BLOCK_Q = 1024
KMEANS_BLOCK_K = 512


def pad_to(a: jax.Array, size: int, axis: int, value=0.0):
    """Zero-pad (or ``value``-pad) ``a`` up to ``size`` along ``axis``."""
    pad = size - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def ell_use_pallas(name: str, refusal: str, impl: str,
                   interpret: bool | None) -> bool:
    """Whether an ELL wrapper runs its Pallas body.  Mosaic refuses the ELL
    kernels' in-kernel gather (``refusal`` is what it says), so the body runs
    only in interpret mode; ``impl="pallas"`` where it would have to compile
    for the TPU raises with the compiler's reason."""
    if impl == "pallas" and not interpret and jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"the {name} Pallas kernel does not compile for TPU: {refusal}; "
            f"use impl='auto' (XLA path)")
    return impl == "pallas" or (impl == "auto" and bool(interpret))
