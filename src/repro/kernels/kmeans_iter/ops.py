"""Public jit'd wrapper for the fused k-means iteration.

One call = one Lloyd iteration's statistics: ``(labels, dmin, sums,
counts)`` from a single stream over the point matrix.  Three execution
paths, picked by ``impl``:

* ``pallas`` — the TPU kernel (:mod:`.kernel`): online argmin + resident
  accumulator, counts folded into an augmented ones-column.  Raises
  ``NotImplementedError`` when asked for explicitly past the VMEM budget;
* ``chunked`` — the online jnp formulation for non-TPU backends: a
  ``lax.scan`` over row blocks carrying running (sums‖counts) and emitting
  per-block (labels, dmin).  Only a ``[block_q, k]`` distance tile is ever
  live — never the n×k matrices the two-pass ``assign_ref`` +
  one-hot-GEMM update materializes — and the accumulation is a per-block
  scatter-add, so the update costs O(n·d) instead of the one-hot GEMM's
  n·k·d.  This is the production CPU/GPU path (and where the large-k CPU
  bench win comes from), not a test shim;
* ``ref`` — the materialized oracle (:mod:`.ref`), tests only.

``auto`` = pallas on TPU (chunked if the accumulator exceeds VMEM),
pallas-interpret when ``interpret`` is set (kernel validation on CPU),
chunked otherwise.  :func:`kmeans_iter_engine` makes that choice from the
shapes before the call, so a caller can name the engine it got.
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
from repro.kernels.kmeans_iter.kernel import kmeans_iter_pallas
from repro.kernels.kmeans_iter.ref import kmeans_iter_ref

# Modeled per-step VMEM working set budget for the Pallas path (resident
# accumulator + streamed tiles; a v5e core has 16 MB).  Past this, `auto`
# chooses the chunked online path, which is accumulator-unbounded.
ACC_VMEM_BUDGET_BYTES = 12 << 20


def _chunked(x, c, x_norm, block_q: int):
    """Online single-pass iteration: scan over row blocks, carry the
    combined ``[k, d+1]`` accumulator (sums ‖ counts — the counts ride in an
    augmented ones-column that is zero on padded rows and on every centroid,
    so distances are exact and one GEMM produces both).  The distance tile
    uses the reference expression (‖x‖² included before the argmin) so
    labels match ``assign_ref`` bit-for-bit, ties broken low."""
    n, d = x.shape
    k = c.shape[0]
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    xn = (xf * xf).sum(1) if x_norm is None else x_norm.astype(jnp.float32)
    cn = (cf * cf).sum(1)

    bq = min(block_q, n)
    n_p = _round_up(n, bq)
    valid = (jnp.arange(n_p) < n).astype(jnp.float32)
    xa = jnp.concatenate([_pad_to(xf, n_p, 0), valid[:, None]], axis=1)
    xnp = _pad_to(xn, n_p, 0)
    ca = jnp.concatenate([cf, jnp.zeros((k, 1), jnp.float32)], axis=1)

    def step(acc, blk):
        xb, xnb = blk  # [bq, d+1], [bq]
        s = xnb[:, None] + cn[None, :] - 2.0 * (xb @ ca.T)  # [bq, k]
        labels = jnp.argmin(s, axis=1).astype(jnp.int32)
        # min(s) == s[argmin] bitwise — a [bq] gather instead of a second
        # full-tile reduction pass
        dmin = jnp.maximum(jnp.take_along_axis(s, labels[:, None], 1)[:, 0], 0.0)
        # scatter-add, NOT the kernel's one-hot contraction: on CPU the
        # [bq, k] one-hot GEMM costs the same n·k·d FLOPs as the distance
        # GEMM to add 99.9%-zeros, and measures ~1.7× slower end-to-end at
        # k=2048 than this O(n·d) scatter.  (The TPU kernel keeps the MXU
        # contraction — matmul throughput is effectively free there.)
        # Padded rows are all-zero in xb (ones-column included), so their
        # scattered contribution vanishes wherever their label lands.
        acc = acc + jax.ops.segment_sum(xb, labels, num_segments=k)
        return acc, (labels, dmin)

    init = jnp.zeros((k, d + 1), jnp.float32)
    blocks = (xa.reshape(-1, bq, d + 1), xnp.reshape(-1, bq))
    acc, (labels, dmin) = jax.lax.scan(step, init, blocks)
    return labels.reshape(-1)[:n], dmin.reshape(-1)[:n], acc[:, :d], acc[:, d]


def _tiles(n: int, d: int, k: int, block_q: int, block_k: int):
    """The Pallas path's (bq, bk, n_p, k_p, d_aug) for these shapes."""
    bq = min(block_q, _round_up(n, 8))
    bk = min(block_k, _round_up(k, 128))
    d_aug = _round_up(d + 1, 128)  # one pad column repurposed as the counter
    return bq, bk, _round_up(n, bq), _round_up(k, bk), d_aug


def pallas_workset_bytes(n: int, d: int, k: int, *,
                         block_q: int = KMEANS_BLOCK_Q,
                         block_k: int = KMEANS_BLOCK_K) -> int:
    """Modeled VMEM working set of the Pallas path: resident acc + S tile +
    one-hot chunk + x/c tiles (kernel.py header), fp32."""
    bq, bk, _, k_p, d_aug = _tiles(n, d, k, block_q, block_k)
    return 4 * (k_p * d_aug + 2 * bq * bk + (bq + bk) * d_aug)


def kmeans_iter_engine(n: int, d: int, k: int, *, impl: str = "auto",
                       interpret: bool | None = None,
                       block_q: int = KMEANS_BLOCK_Q,
                       block_k: int = KMEANS_BLOCK_K) -> str:
    """The engine :func:`kmeans_iter` runs for ``x: [n, d]`` and ``k``
    centroids: ``"pallas"`` (compiled for the TPU), ``"pallas-interpret"``,
    ``"chunked"`` or ``"ref"``.  Decided from the
    shapes and the backend alone, before any kernel is traced, so callers can
    report it; ``auto`` takes the chunked path where the Pallas accumulator
    would exceed :data:`ACC_VMEM_BUDGET_BYTES`."""
    if impl in ("ref", "chunked"):
        return impl
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu if impl == "pallas" else False
    pallas = "pallas-interpret" if interpret else "pallas"
    if impl == "pallas":
        return pallas
    if not on_tpu and not interpret:
        return "chunked"
    over = pallas_workset_bytes(n, d, k, block_q=block_q,
                                block_k=block_k) > ACC_VMEM_BUDGET_BYTES
    return "chunked" if over else pallas


def _pallas(x, c, x_norm, block_q: int, block_k: int, interpret: bool):
    n, d = x.shape
    k = c.shape[0]
    bq, bk, n_p, k_p, d_aug = _tiles(n, d, k, block_q, block_k)
    workset = pallas_workset_bytes(n, d, k, block_q=block_q, block_k=block_k)
    if workset > ACC_VMEM_BUDGET_BYTES:
        raise NotImplementedError(
            f"kmeans_iter modeled working set {workset >> 20} MB "
            f"(acc [{k_p}, {d_aug}] fp32 + tiles) exceeds the "
            f"{ACC_VMEM_BUDGET_BYTES >> 20} MB VMEM budget — use the "
            "chunked online path"
        )

    xf = _pad_to(_pad_to(x.astype(jnp.float32), n_p, 0), d_aug, 1)
    ones_col = (jnp.arange(n_p) < n).astype(jnp.float32)
    xf = xf.at[:, d].set(ones_col)  # zero on padded rows => zero count weight
    cf = _pad_to(_pad_to(c.astype(jnp.float32), k_p, 0), d_aug, 1)
    cn = (cf * cf).sum(1)  # ones-column is zero on centroids: distances exact
    if k_p > k:  # padded centroids must never win the argmin
        cn = cn.at[k:].set(jnp.inf)

    tile_min, labels, acc = kmeans_iter_pallas(
        xf, cf, cn[None, :], block_q=bq, block_k=bk, interpret=interpret
    )
    xn = (x.astype(jnp.float32) ** 2).sum(1) if x_norm is None else x_norm.astype(jnp.float32)
    dmin = jnp.maximum(tile_min[0, :n] + xn, 0.0)
    return labels[0, :n], dmin, acc[:k, :d], acc[:k, d]


@partial(jax.jit, static_argnames=("block_q", "block_k", "impl", "interpret"))
def kmeans_iter(
    x: jax.Array,
    c: jax.Array,
    *,
    x_norm: jax.Array | None = None,
    block_q: int = KMEANS_BLOCK_Q,
    block_k: int = KMEANS_BLOCK_K,
    impl: str = "auto",  # "auto" | "pallas" | "chunked" | "ref"
    interpret: bool | None = None,
):
    """labels[i], dist²[i], per-cluster sums [k, d] and counts [k] — one
    Lloyd iteration from one pass over ``x``.  Empty-cluster policy is the
    caller's (counts==0 rows carry zero sums).  The engine is
    :func:`kmeans_iter_engine` of the shapes; ``impl="pallas"`` past the
    VMEM budget raises ``NotImplementedError``."""
    engine = kmeans_iter_engine(x.shape[0], x.shape[1], c.shape[0], impl=impl,
                                interpret=interpret, block_q=block_q,
                                block_k=block_k)
    if engine == "ref":
        return kmeans_iter_ref(x, c, x_norm)
    if engine == "chunked":
        return _chunked(x, c, x_norm, block_q)
    return _pallas(x, c, x_norm, block_q, block_k,
                   interpret=engine == "pallas-interpret")
