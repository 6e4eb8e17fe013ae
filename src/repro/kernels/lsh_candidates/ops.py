"""Public jit'd wrappers for LSH candidate generation (approximate Stage 1).

Two entry points:

* :func:`hash_codes` — the kernel dispatcher (Pallas on TPU / interpret for
  validation / jnp reference elsewhere), mirroring ``knn_topk``'s dispatch.
* :func:`lsh_candidates` — hashing → per-table lexicographic
  (code, tie-break) sort → fixed-size rank windows → per-query dedup.
  Returns a bounded candidate set ``[nq, m]`` (unique ids ascending, −1
  padding at the end, the query itself excluded) that
  :func:`repro.kernels.knn_topk.ops.knn_topk_rerank` reranks exactly —
  turning Stage 1 from O(n²d) into O(n·m·d) + O(T·n log n) sort work.

Candidate windowing (DESIGN.md §12): per table, points are sorted by
(bucket code, tie-break projection); a query's candidates are the ``m //
n_tables`` points around its own sorted position.  Equal codes group
bucket members contiguously, and the tie-break orders *within* a bucket by
a 1-D random projection — so the window degrades gracefully for buckets
larger than the window instead of sampling them uniformly.  Recall comes
from the union over ``n_tables`` independent tables.

Everything is static-shape jit-safe: ``m``/``n_tables``/``n_bits`` are
static, the hyperplanes are derived from a static integer seed, and
``query_rows`` (the sharded row-block entry: candidates for a shard's rows
against the full gathered pool) may be traced.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels._util import pad_to as _pad_to, round_up as _round_up
from repro.kernels.lsh_candidates.kernel import hash_codes_pallas
from repro.kernels.lsh_candidates.ref import hash_codes_ref

Array = jax.Array

MAX_N_BITS = 24  # codes are packed via fp32-exact int paths; 2^24 is the cap

# Single source of the LSH knob defaults — consumed by GraphConfig,
# build_knn_graph, and make_knn_rowblock (the same config-drift class the
# k-means tile sizes hit before being single-sourced in kernels/_util).
DEFAULT_N_TABLES = 16
DEFAULT_N_BITS = 16


def default_candidates(k: int, n_tables: int = DEFAULT_N_TABLES) -> int:
    """Default candidate budget m: ``n_tables`` windows of ``max(6k, 32)``.

    Sized so the seeded recall gate (recall@k ≥ 0.95 at n=4k clustered
    Gaussians, tests/test_kernels_lsh_candidates.py) passes with margin
    (measured ≈ 0.99 at k=10) while m stays n-independent — the O(n·m·d)
    rerank's asymptotic win over O(n²d) is the whole point.
    """
    return n_tables * max(6 * k, 32)


def make_planes(d: int, n_tables: int, n_bits: int, seed: int) -> Array:
    """[T, d, n_bits+1] hyperplane normals + tie-break direction (column
    ``n_bits``), deterministically derived from the static integer seed."""
    key = jax.random.PRNGKey(seed)
    return jax.random.normal(key, (n_tables, d, n_bits + 1), jnp.float32)


@partial(jax.jit, static_argnames=("impl", "block_n", "interpret"))
def hash_codes(
    x: Array,  # [n, d] points
    planes: Array,  # [T, d, n_bits+1] from make_planes
    *,
    impl: str = "auto",  # "auto" | "pallas" | "ref"
    block_n: int = 256,
    interpret: bool | None = None,
):
    """(codes [T, n] int32, tie [T, n] f32) — see ref.py for the contract."""
    n, d = x.shape
    n_bits = planes.shape[-1] - 1
    assert 1 <= n_bits <= MAX_N_BITS, n_bits
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl == "auto" and not on_tpu and not interpret):
        return hash_codes_ref(x, planes)
    if interpret is None:
        interpret = not on_tpu
    bn = min(block_n, _round_up(n, 128))
    n_p = _round_up(n, bn)
    d_p = _round_up(d, 128)
    b_p = _round_up(n_bits + 1, 128)
    xf = _pad_to(_pad_to(x.astype(jnp.float32), n_p, 0), d_p, 1)
    pf = _pad_to(_pad_to(planes.astype(jnp.float32), d_p, 1), b_p, 2)
    j = jnp.arange(b_p, dtype=jnp.int32)
    pows = jnp.where(j < n_bits, jnp.left_shift(1, jnp.minimum(j, n_bits)), 0)
    codes, tie = hash_codes_pallas(xf, pf, pows[None, :], n_bits, block_n=bn,
                                   interpret=interpret)
    return codes[:, 0, :n], tie[:, 0, :n]


@partial(jax.jit, static_argnames=("m", "n_tables", "n_bits", "seed", "impl",
                                   "interpret"))
def lsh_candidates(
    x: Array,  # [n, d] candidate pool
    *,
    m: int,  # candidate budget per query (static)
    n_tables: int = DEFAULT_N_TABLES,
    n_bits: int = DEFAULT_N_BITS,
    seed: int = 0,
    query_rows: Array | None = None,  # [nq] global row ids; default arange(n)
    impl: str = "auto",
    interpret: bool | None = None,
) -> Array:
    """Bounded per-query candidate sets ``[nq, m]`` int32: unique candidate
    ids, the query itself excluded, invalid slots −1.  Valid ids are in
    ascending order but −1s may be *interspersed* (duplicates are masked in
    place after one per-row sort — a second sort to compact them would be
    pure data movement and measurably dominates Stage 1 at n=50k; every
    consumer masks on ``id >= 0`` anyway).

    ``query_rows`` serves the sharded row-block Stage 1: a shard passes its
    rows' global ids (traced — ``offset + arange`` under shard_map) and gets
    candidates for those rows against the full pool ``x``.
    """
    n, d = x.shape
    if n_tables < 1 or m < n_tables:
        raise ValueError(
            f"lsh_candidates needs n_tables >= 1 and m >= n_tables (one "
            f"window slot per table), got n_tables={n_tables}, m={m}")
    win = min(max(m // n_tables, 1), n)
    planes = make_planes(d, n_tables, n_bits, seed)
    codes, tie = hash_codes(x, planes, impl=impl, interpret=interpret)

    def one_table(code_t, tie_t):
        # lexicographic (code, tie-break): sort by the tie projection, then
        # stable-sort by code — bucket grouping with in-bucket 1-D order
        p1 = jnp.argsort(tie_t)
        order = p1[jnp.argsort(code_t[p1], stable=True)].astype(jnp.int32)
        pos = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        return order, pos

    order, pos = jax.vmap(one_table)(codes, tie)  # [T, n] each

    if query_rows is None:
        qid = jnp.arange(n, dtype=jnp.int32)
        qpos = pos  # [T, n]
    else:
        qid = query_rows.astype(jnp.int32)
        qpos = pos[:, qid]  # [T, nq]
    nq = qid.shape[0]

    start = jnp.clip(qpos - win // 2, 0, n - win)  # [T, nq]
    widx = start[..., None] + jnp.arange(win, dtype=jnp.int32)  # [T, nq, win]
    cand = jax.vmap(lambda o, w: o[w])(order, widx)  # [T, nq, win]
    cand = jnp.moveaxis(cand, 0, 1).reshape(nq, n_tables * win)

    # dedup: one ascending per-row sort (self → sentinel n lands at the
    # tail), then duplicates — adjacent after the sort — masked to -1 in
    # place; valid ids stay ascending, -1s may be interspersed
    c = jnp.where(cand == qid[:, None], n, cand)
    c = jnp.sort(c, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((nq, 1), bool), c[:, 1:] == c[:, :-1]], axis=1)
    c = jnp.where(dup | (c >= n), -1, c)
    if c.shape[1] < m:  # m not a multiple of n_tables (or win clipped at n)
        c = jnp.concatenate(
            [c, jnp.full((nq, m - c.shape[1]), -1, jnp.int32)], axis=1)
    return c


# ---------------------------------------------------------------------------
# Persistent / routed tables — build once, look queries up later
# ---------------------------------------------------------------------------
#
# ``lsh_candidates`` fuses hash → sort → window per call, which is right for
# the one-shot Stage 1 but wrong for (a) serving, where the pool is fixed
# across millions of queries, and (b) the sharded ring exchange, where each
# shard hashes only its own row block ONCE and peers look their queries up
# into the visiting block's tables.  These helpers split the pipeline at the
# natural seam: ``sorted_tables`` owns the per-table (code, tie) sort;
# ``routed_candidates`` positions externally-hashed queries in those sorted
# tables (lexicographic insertion rank, computed jit-safely via one combined
# argsort) and windows/dedups exactly like ``lsh_candidates``.


class LshTables(NamedTuple):
    """Per-table sorted bucket structure of a candidate pool — the
    persistable product of hashing: for each of T tables, the pool ids in
    (bucket code, tie-break projection) ascending order plus the sorted keys
    themselves, so a query's window position is a searchsorted-style rank
    computation needing no re-hash of the pool."""

    order: Array  # [T, n] int32 — pool ids, (code, tie) ascending per table
    codes: Array  # [T, n] int32 — bucket codes in sorted order
    ties: Array  # [T, n] f32 — tie-break projections in sorted order


@jax.jit
def sorted_tables(codes: Array, ties: Array) -> LshTables:
    """Build :class:`LshTables` from :func:`hash_codes` output ([T, n] each).

    Same lexicographic (code, tie) sort as ``lsh_candidates``'s per-table
    ordering — a pool point's rank here is bitwise the window position the
    fused path would give it.
    """

    def one(code_t, tie_t):
        p1 = jnp.argsort(tie_t)
        order = p1[jnp.argsort(code_t[p1], stable=True)].astype(jnp.int32)
        return order, code_t[order], tie_t[order]

    order, cs, ts = jax.vmap(one)(codes, ties)
    return LshTables(order=order, codes=cs, ties=ts)


@partial(jax.jit, static_argnames=("win",))
def routed_candidates(
    tables: LshTables,
    qcodes: Array,  # [T, nq] query bucket codes (hash_codes on queries only)
    qties: Array,  # [T, nq] query tie-break projections
    *,
    win: int,  # window size per table (static)
    query_rows: Array | None = None,  # [nq] pool ids to self-exclude, or None
) -> Array:
    """Candidate pool ids ``[nq, T·win]`` for queries hashed *elsewhere* —
    the lookup half of ``lsh_candidates``: each query's lexicographic
    insertion rank among a table's sorted (code, tie) keys centers a
    ``win``-wide window of pool ids; the union over tables is deduped in
    place (unique ids ascending, −1 interspersed — the
    ``knn_topk_rerank`` contract).

    The rank is computed with one combined argsort over [pool keys; query
    keys] (a jit-safe lexicographic searchsorted): a query's pool-only rank
    is its combined position minus the number of queries sorted before it.
    Equal keys rank the query *after* the pool point (searchsorted-right),
    matching the fused path where a pool member windows around itself.

    ``query_rows`` masks each query's own pool id from its candidates (pass
    the local ids when queries ARE pool members — the ring's home step);
    ids outside [0, n) never match, so the ring's visiting steps pass the
    same offset expression and the exclusion only fires at home.
    """
    T, n = tables.order.shape
    nq = qcodes.shape[1]
    win = min(max(win, 1), n)

    def one(order, cs, ts, qc, qt):
        code_all = jnp.concatenate([cs, qc])
        tie_all = jnp.concatenate([ts, qt])
        p1 = jnp.argsort(tie_all)
        comb = p1[jnp.argsort(code_all[p1], stable=True)]
        isq = (comb >= n).astype(jnp.int32)
        # pool-only rank of the element at combined position p: p minus the
        # queries strictly before p (inclusive cumsum minus own flag)
        rank = (jnp.arange(n + nq, dtype=jnp.int32)
                - jnp.cumsum(isq) + isq)
        qpos = jnp.zeros((nq,), jnp.int32).at[
            jnp.where(isq == 1, comb - n, nq)].set(rank, mode="drop")
        start = jnp.clip(qpos - win // 2, 0, n - win)
        widx = start[:, None] + jnp.arange(win, dtype=jnp.int32)
        return order[widx]  # [nq, win]

    cand = jax.vmap(one)(tables.order, tables.codes, tables.ties,
                         qcodes, qties)  # [T, nq, win]
    cand = jnp.moveaxis(cand, 0, 1).reshape(nq, T * win)
    qid = (jnp.full((nq,), -1, jnp.int32) if query_rows is None
           else query_rows.astype(jnp.int32))
    c = jnp.where(cand == qid[:, None], n, cand)
    c = jnp.sort(c, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((nq, 1), bool), c[:, 1:] == c[:, :-1]], axis=1)
    return jnp.where(dup | (c >= n), -1, c)
