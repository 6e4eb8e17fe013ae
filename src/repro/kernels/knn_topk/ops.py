"""Public jit'd wrapper for the fused kNN top-k kernel.

Handles shape padding (n→block multiples, d→128 multiple, k→8 multiple),
adds the row-constant ‖x‖² back into the returned distances, masks padded /
exhausted slots to (+inf, -1), and picks the execution path: real Pallas on
TPU, interpret-mode Pallas for validation, or the jnp reference on other
backends (the wrapper is what ``core.similarity.build_knn_graph`` calls).

The ε-ball variant rides on the same reduction: ``eps`` additionally masks
neighbors beyond the radius to (+inf, -1), giving a static-shape [n, k]
ε-neighborhood (k caps the per-row degree — the HYB-style bound that keeps
the result jit-friendly).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels._util import pad_to as _pad_to, round_up as _round_up
from repro.kernels.knn_topk.kernel import MERGED, VISITED, knn_topk_pallas
from repro.kernels.knn_topk.ref import knn_topk_ref


# The kernel's merge unrolls k_pad min-extract passes, and Mosaic keeps one
# [block_q, k_pad + block_k] fp32 tile live per pass.  Rows per query block
# are capped so those tiles stay within this budget, under v5e's 16 MB
# default scoped-VMEM limit (at k=64, block_q=256 the compiler asks 24 MB).
MERGE_VMEM_BUDGET_BYTES = 8 << 20


def _merge_rows(k_pad: int, block_k: int) -> int:
    """Largest multiple of 8 query rows whose merge tiles fit the budget."""
    rows = MERGE_VMEM_BUDGET_BYTES // (4 * k_pad * (k_pad + block_k))
    return max(8, rows // 8 * 8)


def knn_topk_engine(impl: str = "auto", interpret: bool | None = None) -> str:
    """The engine :func:`knn_topk` runs: ``"pallas"`` (compiled for the
    TPU), ``"pallas-interpret"`` or ``"ref"`` (the jnp reference, which
    ``auto`` picks off TPU unless ``interpret`` asks for the kernel)."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu and not interpret):
        return "ref"
    interpret = (not on_tpu) if interpret is None else interpret
    return "pallas-interpret" if interpret else "pallas"


@partial(jax.jit, static_argnames=("k", "block_q", "block_k", "impl", "interpret",
                                   "with_tiles"))
def knn_topk(
    x: jax.Array,  # [n, d] candidate points
    k: int,
    *,
    queries: jax.Array | None = None,  # [nq, d]; defaults to x (all-pairs)
    query_offset: jax.Array | int = 0,  # global row id of queries[0]
    eps: jax.Array | float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    impl: str = "auto",  # "auto" | "pallas" | "ref"
    interpret: bool | None = None,
    with_tiles: bool = False,
):
    """dist²[i, :], idx[i, :] = the k nearest neighbors of x_i (self excluded),
    ascending by (distance, id).  Invalid slots (k ≥ n, or beyond ``eps``)
    are (+inf, -1).

    ``with_tiles=True`` appends the kernel's tile counter: int32 [2], the
    candidate tiles it merged and the tiles it visited, summed over query
    blocks (``None`` on the jnp reference, which has no tiles).

    ``queries``/``query_offset`` serve the row-block sharded Stage 1: a shard
    passes its local row block and its global row offset (traced —
    ``axis_index * rows_per_shard`` under shard_map) so self-pairs are still
    excluded against global candidate ids.

    On non-TPU backends ``auto`` falls back to the jnp reference — the Pallas
    kernel is the TPU target and interpret mode is for tests.
    """
    n, d = x.shape
    assert k >= 1, k
    engine = knn_topk_engine(impl, interpret)
    tiles = None
    if engine == "ref":
        dist, idx = knn_topk_ref(x, k, queries=queries,
                                 query_offset=query_offset)
    else:
        interpret = engine == "pallas-interpret"
        q = x if queries is None else queries
        nq = q.shape[0]
        bk = min(block_k, _round_up(n, 128))
        k_pad = _round_up(k, 8)
        bq = min(block_q, _round_up(nq, 8), _merge_rows(k_pad, bk))
        nq_p = _round_up(nq, bq)
        nc_p = _round_up(n, bk)
        d_p = _round_up(d, 128)

        xf = _pad_to(_pad_to(x.astype(jnp.float32), nc_p, 0), d_p, 1)
        # pad rows repeat the last query, so they need the tiles it needs
        # (rows of zeros would pull the merges toward the origin's tiles)
        qf = jnp.pad(q.astype(jnp.float32), ((0, nq_p - nq), (0, 0)),
                     mode="edge")
        qf = _pad_to(qf, d_p, 1)
        cn = (xf * xf).sum(1)
        if nc_p > n:  # padded candidates must never enter the top-k
            cn = cn.at[n:].set(jnp.inf)
        raw, idx, counts = knn_topk_pallas(
            qf, xf, cn[None, :], k_pad, query_offset=query_offset,
            block_q=bq, block_k=bk, interpret=interpret)
        tiles = counts[::8, [MERGED, VISITED]].sum(0)  # one row a block
        raw, idx = raw[:nq, :k], idx[:nq, :k]
        qn = (q.astype(jnp.float32) ** 2).sum(1)
        invalid = jnp.isinf(raw)
        dist = jnp.where(invalid, jnp.inf, jnp.maximum(raw + qn[:, None], 0.0))
        idx = jnp.where(invalid, -1, idx)

    if eps is not None:
        beyond = dist > jnp.asarray(eps, jnp.float32) ** 2
        dist = jnp.where(beyond, jnp.inf, dist)
        idx = jnp.where(beyond, -1, idx)
    return (dist, idx, tiles) if with_tiles else (dist, idx)


@partial(jax.jit, static_argnames=("k", "block_q"))
def knn_topk_rerank(
    x: jax.Array,  # [n, d] candidate pool
    cand: jax.Array,  # [nq, m] int32 candidate ids (−1 = padding), unique/row
    k: int,
    *,
    queries: jax.Array | None = None,  # [nq, d]; defaults to x (cand is [n, m])
    query_rows: jax.Array | None = None,  # [nq] global ids; default arange(nq)
    eps: jax.Array | float | None = None,
    block_q: int = 1024,
):
    """Exact top-k over bounded per-query candidate sets — the rerank stage of
    the approximate Stage 1.  Same output contract as :func:`knn_topk`
    (dist² ascending, idx int32, invalid slots (+inf, −1)); only the
    *candidate supply* differs: the ``m ≪ n`` ids in ``cand`` (from
    ``repro.kernels.lsh_candidates``) instead of all n points, so the
    distance work drops from O(n²d) to O(n·m·d).

    Reuses ``knn_topk``'s BLAS identity per row over the gathered candidates
    (‖q‖² + ‖c‖² − 2 q·c, a [nq, d] × [nq, m, d] batched contraction the MXU
    streams) — there is no Pallas kernel here because the irregular gather
    ``x[cand]`` is already XLA-native and the arithmetic is dense.  ``cand``
    rows must be duplicate-free (the ``lsh_candidates`` contract): top-k
    over a row with repeated ids would report the same neighbor twice.

    Slots where a row has fewer than k valid candidates (or beyond ``eps``)
    come back (+inf, −1) — downstream ``graph_from_knn`` masks them to
    zero-weight self edges, so low-recall rows degrade instead of failing.
    """
    xf = x.astype(jnp.float32)
    cn = (xf * xf).sum(1)
    q = xf if queries is None else queries.astype(jnp.float32)
    nq, m = q.shape[0], cand.shape[1]
    assert cand.shape[0] == nq, (cand.shape, q.shape)
    qrow = (jnp.arange(nq, dtype=jnp.int32) if query_rows is None
            else query_rows.astype(jnp.int32))
    qn = (q * q).sum(1)
    ko = min(k, m)

    def body(args):
        qb, qnb, rb, cb = args  # [bq, d], [bq], [bq], [bq, m]
        valid = (cb >= 0) & (cb != rb[:, None])
        safe = jnp.where(cb >= 0, cb, 0)
        d2 = (qnb[:, None] + cn[safe]
              - 2.0 * jnp.einsum("qd,qmd->qm", qb, xf[safe],
                                 preferred_element_type=jnp.float32))
        d2 = jnp.where(valid, jnp.maximum(d2, 0.0), jnp.inf)
        neg, sel = jax.lax.top_k(-d2, ko)  # ties → lowest pos = smallest id
        return -neg, jnp.take_along_axis(safe, sel, axis=1)

    # chunk queries with lax.map so only a [bq, m, d] gather tile is live
    bq = min(block_q, nq)
    pad = (-nq) % bq
    qp = _pad_to(q, nq + pad, 0)
    qnp_ = _pad_to(qn, nq + pad, 0)
    rp = _pad_to(qrow, nq + pad, 0, value=-2)  # never matches a candidate id
    cp = _pad_to(cand.astype(jnp.int32), nq + pad, 0, value=-1)
    d_blk, i_blk = jax.lax.map(
        body, (qp.reshape(-1, bq, q.shape[1]), qnp_.reshape(-1, bq),
               rp.reshape(-1, bq), cp.reshape(-1, bq, m)))
    dist = d_blk.reshape(-1, ko)[:nq]
    idx = i_blk.reshape(-1, ko)[:nq]
    idx = jnp.where(jnp.isinf(dist), -1, idx)  # canonicalize invalid slots
    if ko < k:  # fewer candidates than requested neighbors
        dist = jnp.pad(dist, ((0, 0), (0, k - ko)), constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - ko)), constant_values=-1)
    if eps is not None:
        beyond = dist > jnp.asarray(eps, jnp.float32) ** 2
        dist = jnp.where(beyond, jnp.inf, dist)
        idx = jnp.where(beyond, -1, idx)
    return dist, idx
