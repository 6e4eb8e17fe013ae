"""Stage 2b — on-device restarted Lanczos eigensolver (paper Alg. 3, TPU-native).

The paper drives ARPACK's implicitly-restarted Lanczos (IRLM) on the host and
ships one vector per iteration to the GPU for the SpMV.  A per-iteration
host↔device round trip would serialize a TPU pod, so we implement the
restarted Lanczos itself in ``jax.lax`` control flow and keep *everything*
on device:

* **thick-restart Lanczos** (Wu & Simon 2000) — for symmetric operators this
  is mathematically equivalent to ARPACK's symmetric IRLM (``dsaupd``), and
  is the standard formulation for implementations without host control;
* **full two-pass Gram-Schmidt reorthogonalization** each step (ARPACK-grade
  robustness; also what makes the implementation tolerant of the restart's
  non-tridiagonal projected matrix — we simply measure the full coefficient
  vector ``c = V·(A v_j)`` and record it as row ``j`` of the projected
  matrix ``T``, so bookkeeping is correct by construction);
* the m×m projected eigenproblem is solved with ``jnp.linalg.eigh`` on
  device — it is tiny (m ≈ 2k) relative to the n-dimensional work.

ARPACK's *reverse-communication interface* survives as a software contract:
``matvec`` is an arbitrary callable, so any operator representation (COO
segment-sum, BlockELL Pallas kernel, shard_map-distributed SpMV) plugs in —
exactly the flexibility the paper gets from RCI, minus the PCIe copies.

Complexities match the paper's Eq. (10): per restart O(m³) (eigh)
+ O(n m²) (reorth + basis rotation) + O(nnz·m) (matvecs).

**Block mode** (``LanczosConfig.block_size = b > 1``, DESIGN.md §3): each
step expands the Krylov basis by ``b`` columns via ONE multi-vector operator
application (``matmat: [n, b] → [n, b]``), so reaching basis size m streams
the sparse matrix m/b times instead of m — the dominant HBM/ICI cost of
Stage 2 drops b×.  All orthogonalization becomes [m+b, n]×[n, b] tall-skinny
GEMMs on the MXU instead of rank-1 GEMV chains; the in-block orthonormal
factorization is a [n, b] QR whose R factor is the band coupling block of
the projected matrix.  The full-coefficient bookkeeping above carries over
verbatim: T is simply block-banded instead of tridiagonal, and thick restart
keeps a block-aligned number of Ritz vectors plus the b-column residual
block.  Single-vector mode remains the ``b = 1`` special case.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


class LanczosResult(NamedTuple):
    eigenvalues: Array  # [k]  descending (for which="LA")
    eigenvectors: Array  # [n, k]
    residuals: Array  # [k]  |beta_m * s_{m,i}| per returned pair
    restarts: Array  # []   restart count actually executed
    converged: Array  # []   bool
    operator_applications: Array = None  # [] int32 mv/mm calls executed


@dataclasses.dataclass(frozen=True)
class LanczosConfig:
    k: int  # wanted eigenpairs
    m: int  # Krylov basis size (ARPACK's ncv), > k
    max_restarts: int = 100
    tol: float = 1e-6
    which: str = "LA"  # "LA": largest algebraic (the paper's D^{-1}W case)
    fixed_restarts: Optional[int] = None  # static count (dry-run / benchmark)
    dtype: jnp.dtype = jnp.float32
    block_size: int = 1  # Krylov block width b (1 = classic single-vector)


def default_config(k: int, n: int, **kw) -> LanczosConfig:
    # ARPACK's guidance: ncv >= 2k; cap at n and keep a floor for tiny k.
    m = min(n, max(2 * k, k + 16))
    return LanczosConfig(k=k, m=m, **kw)


# ---------------------------------------------------------------------------
# Static shape/cost helpers (shared by the solver, benchmarks, and tests)
# ---------------------------------------------------------------------------

def effective_basis_size(cfg: LanczosConfig) -> int:
    """m rounded up to a multiple of the block size (block steps expand the
    basis b columns at a time, so the basis must tile evenly)."""
    b = max(1, cfg.block_size)
    return ((cfg.m + b - 1) // b) * b


def restart_keep_size(cfg: LanczosConfig) -> int:
    """Number of Ritz vectors retained at a thick restart.

    Single-vector: ARPACK-style k + half the excess.  Block mode rounds the
    same target UP to a block multiple (the post-restart steps must land
    exactly on basis size m) and caps at m - b so at least one block step
    runs per cycle.
    """
    b = max(1, cfg.block_size)
    m = effective_basis_size(cfg)
    l0 = cfg.k + max(1, (m - cfg.k) // 2)
    if b == 1:
        return min(m - 1, l0)
    return min(m - b, ((l0 + b - 1) // b) * b)


def operator_passes(cfg: LanczosConfig, restarts: int) -> int:
    """Full streams of the sparse operator (SpMV/SpMM applications) executed
    by a run that performed ``restarts`` cycles (first cycle included).

    Each application streams the entire nnz structure once regardless of the
    block width, so this is THE figure of merit for HBM/ICI-bound Stage 2:
    block mode pays (m - l)/b streams per cycle instead of m - l.
    """
    b = max(1, cfg.block_size)
    m = effective_basis_size(cfg)
    l_keep = restart_keep_size(cfg)
    first = m // b
    steady = (m - l_keep) // b
    return first + max(0, int(restarts) - 1) * steady


def solver_streams(cfg, result=None) -> int:
    """Unified operator-stream accounting across Stage-2 engines — THE
    figure every bench reports, so lanczos / chebyshev / reduced-operator
    runs are comparable on one axis.

    ``cfg`` is the engine config :func:`eigsh` dispatched on:

    - :class:`~repro.core.chebyshev.ChebConfig` → the statically-known
      :func:`~repro.core.chebyshev.operator_streams` (``result`` ignored).
    - :class:`LanczosConfig` → :func:`operator_passes`, which needs the
      executed restart count: pass the :class:`LanczosResult` (its
      ``restarts`` field is read) or a plain int.

    One stream traverses the operator's stored entries once; multiply by
    ``op.nnz`` (:func:`streamed_nnz`) when comparing across operator
    *representations* or reduction levels, where per-stream cost differs.
    """
    from repro.core.chebyshev import ChebConfig
    from repro.core.chebyshev import operator_streams as _cheb_streams

    if isinstance(cfg, ChebConfig):
        return _cheb_streams(cfg)
    if not isinstance(cfg, LanczosConfig):
        raise TypeError(
            f"solver_streams expects a LanczosConfig or ChebConfig, got "
            f"{type(cfg).__name__}")
    if result is None:
        raise ValueError(
            "solver_streams(LanczosConfig) needs the executed restart count "
            "— pass the LanczosResult (or an int restart count)")
    restarts = result if isinstance(result, int) else int(result.restarts)
    return operator_passes(cfg, restarts)


def streamed_nnz(op, cfg, result=None) -> int:
    """``solver_streams × op.nnz`` — total stored entries moved by Stage 2,
    the cross-representation / cross-reduction cost figure (ELL padding and
    shard padding count: they are streamed like real entries)."""
    nnz = getattr(op, "nnz", None)
    if nnz is None:
        raise TypeError(
            f"{type(op).__name__} exposes no nnz (closure-backed operators "
            f"have no stored-entry count) — report solver_streams alone")
    return solver_streams(cfg, result) * int(nnz)


def validate_basis(cfg: LanczosConfig, n: int) -> None:
    """Eager (trace-time) sanity of the basis geometry — degenerate requests
    like ``n_eigvecs > n//2``-ish used to surface as opaque shape errors from
    inside the restart loop; this raises the actionable message instead."""
    b = max(1, cfg.block_size)
    if cfg.k < 1:
        raise ValueError(f"LanczosConfig.k must be >= 1, got {cfg.k}")
    if cfg.m <= cfg.k:
        raise ValueError(
            f"LanczosConfig.m={cfg.m} must exceed k={cfg.k} — the Krylov "
            f"basis (ARPACK's ncv) needs room beyond the wanted pairs; the "
            f"default is ~2k (see default_config / default_basis_size)")
    m = effective_basis_size(cfg)
    if m + b > n:
        raise ValueError(
            f"LanczosConfig(k={cfg.k}, m={cfg.m}, block_size={cfg.block_size})"
            f" needs {m} basis + {b} residual column(s) = {m + b} orthonormal"
            f" vectors in R^n but the operator dimension is n={n}. The "
            f"requested eigenpair count is too large for this problem (the "
            f"default basis is ~2k, so k should stay well below n/2): reduce "
            f"k / EigConfig.n_eigvecs, shrink m / EigConfig.basis_m, or use "
            f"a dense jnp.linalg.eigh — at this size it is the faster exact "
            f"solver anyway")
    if b > 1 and m < cfg.k + 2 * b:
        raise ValueError(
            f"block Lanczos needs m >= k + 2*block_size so every restart "
            f"cycle runs at least two block steps (m={m}, k={cfg.k}, "
            f"b={b}) — widen m / EigConfig.basis_m or shrink block_size")


def escalate_basis(cfg: LanczosConfig, n: int, *,
                   widen: float = 1.5) -> LanczosConfig:
    """The next rung of the non-convergence ladder: widen the Krylov basis
    (ARPACK's classic remedy for ``info=1`` — a larger ncv keeps more Ritz
    pairs per restart cycle) and double the restart budget.

    The widened m is clamped to the ``n - b`` validity bound enforced by
    :func:`validate_basis`, so the escalated config always constructs; when
    the clamp leaves m unchanged the extra restarts still make the retry
    strictly stronger.
    """
    if widen <= 1.0:
        raise ValueError(f"escalate_basis widen must be > 1, got {widen}")
    b = max(1, cfg.block_size)
    m = min(int(cfg.m * widen) + 1, n - b)
    return dataclasses.replace(
        cfg, m=max(m, cfg.m), max_restarts=max(1, cfg.max_restarts) * 2)


def scoped(name: str, fn: Callable) -> Callable:
    """``fn`` traced under ``jax.named_scope(name)``: the Stage-2 solvers
    wrap each operator application in ``scoped("spmv", ...)`` so that
    every operator class, whatever its kernel, shows under one scope in the
    compiled program's metadata and the device trace (DESIGN.md §18)."""
    def call(*args):
        with jax.named_scope(name):
            return fn(*args)
    return call


def _on_rows(a: Array, rows, dim: int) -> Array:
    """``a`` with its axis ``dim`` (length n) split over the chips as the
    sharding ``rows`` splits an [n] vector, or unchanged where ``rows`` is
    None: a row-sharded operator keeps the Krylov vectors on the chips
    that own their rows, so each Gram-Schmidt product is a local GEMV and
    one all-reduce of m + 1 couplings (DESIGN.md §20).  Where the chips do
    not divide n, GSPMD places ``a`` (an eager placement refuses uneven
    blocks)."""
    if rows is None:
        return a
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sparse.distributed import num_shards

    if a.shape[dim] % num_shards(rows.mesh, rows.spec[0]):
        return a
    spec = [None] * a.ndim
    spec[dim] = rows.spec[0]
    return jax.lax.with_sharding_constraint(
        a, NamedSharding(rows.mesh, P(*spec)))


def _orthonormal_against(v: Array, basis: Array, key: Array) -> Array:
    """Random unit vector orthogonal to the (zero-padded) basis rows —
    invariant-subspace escape hatch (ARPACK does the same on breakdown)."""
    r = jax.random.normal(key, v.shape, v.dtype)
    r = r - basis.T @ (basis @ r)
    return r / jnp.maximum(jnp.linalg.norm(r), 1e-30)


def eigsh(op, cfg, *, v0: Optional[Array] = None,
          key: Optional[Array] = None) -> LanczosResult:
    """Top-k eigenpairs of a symmetric :class:`~repro.core.operator.LinearOperator`.

    This is the operator-protocol entry point (the jax-native ARPACK
    ``dsaupd`` analogue): the solver only ever calls ``op.mv`` ([n] → [n])
    or, with ``cfg.block_size > 1``, ``op.mm`` ([n, b] → [n, b]) — any
    implementation (COO segment-sum, BlockELL Pallas SpMM, shard_map pod
    SpMV, a bare-closure :class:`~repro.core.operator.CallableOperator`)
    plugs in unchanged.

    The config type selects the engine: a :class:`LanczosConfig` runs the
    thick-restart Lanczos below; a :class:`~repro.core.chebyshev.ChebConfig`
    runs the polynomial-filter embedding
    (:func:`repro.core.chebyshev.chebyshev_eigsh`) — same operator contract,
    same :class:`LanczosResult` out.

    An operator with a ``row_sharding`` (a row-sharded operator) has the
    single-vector solver keep the Krylov basis split by rows as its
    products are.

    Every matmul the solvers trace runs at full fp32.  A TPU's default is
    one bf16 pass, which leaves Gram-Schmidt ~1e-3 short of orthogonal: on
    near-degenerate spectra (k separated blobs) Lanczos then stalls above
    ``tol`` through every escalation.
    """
    from repro.core.chebyshev import ChebConfig, chebyshev_eigsh

    with jax.default_matmul_precision("float32"):
        if isinstance(cfg, ChebConfig):
            return chebyshev_eigsh(op, cfg, v0=v0, key=key)
        n = op.shape[0]
        validate_basis(cfg, n)
        if cfg.block_size > 1:
            return _lanczos_topk_block(op.mm, n, cfg, v0=v0, key=key)
        return _lanczos_topk_single(op.mv, n, cfg, v0=v0, key=key,
                                    rows=getattr(op, "row_sharding", None))


def lanczos_topk(
    matvec: Optional[Callable[[Array], Array]],
    n: int,
    cfg: LanczosConfig,
    *,
    v0: Optional[Array] = None,
    key: Optional[Array] = None,
    matmat: Optional[Callable[[Array], Array]] = None,
) -> LanczosResult:
    """Top-k eigenpairs of the symmetric operator behind ``matvec``/``matmat``.

    Legacy closure-based surface — equivalent to wrapping the closures in a
    :class:`~repro.core.operator.CallableOperator` and calling :func:`eigsh`
    (which is exactly what it does).  ``matvec`` must map an ``[n]`` vector
    to an ``[n]`` vector and be jit-traceable (it may itself contain
    shard_map collectives).  With ``cfg.block_size > 1`` the operator
    contract widens to ``matmat: [n, b] → [n, b]``; without an explicit
    ``matmat`` the matvec is vmapped over columns as a correctness fallback.
    """
    from repro.core.operator import CallableOperator

    return eigsh(CallableOperator(n=n, matvec=matvec, matmat=matmat),
                 cfg, v0=v0, key=key)


def _lanczos_topk_single(
    matvec: Callable[[Array], Array],
    n: int,
    cfg: LanczosConfig,
    *,
    v0: Optional[Array] = None,
    key: Optional[Array] = None,
    rows=None,
) -> LanczosResult:
    """Single-vector thick-restart Lanczos (the ``block_size=1`` engine).
    ``rows``: the sharding of the operator's rows, which the basis follows
    (:func:`_on_rows`)."""
    assert matvec is not None, "need matvec for block_size=1"
    matvec = scoped("spmv", matvec)
    k, m = cfg.k, cfg.m
    assert 0 < k < m <= n, (k, m, n)
    key = jax.random.PRNGKey(0) if key is None else key
    f32 = jnp.float32

    if v0 is None:
        v0 = jax.random.normal(key, (n,), f32)
    v0 = _on_rows(v0.astype(f32), rows, 0)
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-30)

    sign = 1.0 if cfg.which == "LA" else -1.0  # "SA" negates the spectrum

    def step(j, carry):
        """One Lanczos step: expand basis row j+1, record T row/col j."""
        V, T, key, apps = carry
        w = matvec(V[j]).astype(f32) * sign
        with jax.named_scope("orthogonalize"):
            c = V @ w  # [m+1] couplings (zero rows -> zero coeffs)
            T = T.at[j, :].set(c)
            T = T.at[:, j].set(c)
            w = w - V.T @ c
            c2 = V @ w  # second Gram-Schmidt pass
            w = w - V.T @ c2
            beta = jnp.linalg.norm(w)
            key, sub = jax.random.split(key)
            v_next = jnp.where(
                beta > 1e-10, w / jnp.maximum(beta, 1e-30),
                _orthonormal_against(w, V, sub))
            V = _on_rows(V.at[j + 1].set(v_next), rows, 1)
            T = T.at[j + 1, j].set(beta)
            T = T.at[j, j + 1].set(beta)
        return V, T, key, apps + 1

    def run_cycle(V, T, l, key, apps):
        """Steps l..m-1, then Ritz extraction + thick restart state."""
        V, T, key, apps = jax.lax.fori_loop(l, m, step, (V, T, key, apps))
        with jax.named_scope("restart"):
            beta_m = T[m, m - 1]
            theta, S = jnp.linalg.eigh(T[:m, :m])  # ascending
            # top-k live in the last k columns
            res = jnp.abs(beta_m * S[m - 1, :])
            scale = jnp.maximum(jnp.max(jnp.abs(theta)), 1e-12)
            conv = res[m - k :] <= cfg.tol * scale
            n_conv = conv.sum()

            # ---- thick restart: keep l_keep top Ritz pairs + residual vector
            l_keep = restart_keep_size(cfg)
            keep = slice(m - l_keep, m)
            Y = (S[:, keep].T @ V[:m]).astype(f32)  # [l_keep, n] Ritz vectors
            V_new = jnp.zeros_like(V)
            V_new = V_new.at[:l_keep].set(Y)
            V_new = _on_rows(V_new.at[l_keep].set(V[m]), rows, 1)
            h = beta_m * S[m - 1, keep]
            T_new = jnp.zeros_like(T)
            T_new = T_new.at[jnp.arange(l_keep), jnp.arange(l_keep)].set(
                theta[keep])
            T_new = T_new.at[l_keep, :l_keep].set(h)
            T_new = T_new.at[:l_keep, l_keep].set(h)
        return (V_new, T_new, key, theta, S, V, res, apps), n_conv, l_keep

    V0 = _on_rows(jnp.zeros((m + 1, n), f32).at[0].set(v0), rows, 1)
    T0 = jnp.zeros((m + 1, m + 1), f32)

    l_keep_static = restart_keep_size(cfg)

    # --- restart control ----------------------------------------------------
    # fori_loop needs static bounds and the first cycle (l=0) differs from
    # steady-state cycles (l=l_keep), so we peel the first cycle and then
    # loop the steady-state cycle (while_loop in production; fori_loop with a
    # static trip count for the dry-run so cost_analysis sees exact op counts).
    def first_cycle(V, T, key):
        return run_cycle(V, T, 0, key, jnp.asarray(0, jnp.int32))

    def steady_cycle(V, T, key, apps):
        return run_cycle(V, T, l_keep_static, key, apps)

    out, n_conv, _ = first_cycle(V0, T0, key)

    if cfg.fixed_restarts is not None:
        # static restart count — used by the dry-run so cost_analysis sees an
        # exact, analyzable op count (no while loop).
        def fbody(_, st):
            (V, T, key, *_, apps), _ = st
            o, nc, _ = steady_cycle(V, T, key, apps)
            return o, nc

        (V, T, key, theta, S, V_old, res, apps), n_conv = jax.lax.fori_loop(
            0, cfg.fixed_restarts, fbody, (out, n_conv)
        )
        restarts = jnp.asarray(1 + cfg.fixed_restarts)
    else:
        def wcond(st):
            _, it, nc = st
            return jnp.logical_and(it < cfg.max_restarts, nc < k)

        def wbody(st):
            (V, T, key, *_, apps), it, _ = st
            o, nc, _ = steady_cycle(V, T, key, apps)
            return o, it + 1, nc

        (V, T, key, theta, S, V_old, res, apps), restarts, n_conv = \
            jax.lax.while_loop(wcond, wbody, (out, jnp.asarray(1), n_conv))

    # --- extract final top-k pairs from the last completed cycle ----------
    with jax.named_scope("restart"):
        topk = slice(m - k, m)
        vals = theta[topk][::-1] * sign  # descending, undo "SA" negation
        U = (S[:, topk].T @ V_old[:m]).astype(cfg.dtype)  # [k, n]
        U = _on_rows(U[::-1].T, rows, 0)  # [n, k] descending order
        res_k = res[topk][::-1]
    return LanczosResult(
        eigenvalues=vals.astype(cfg.dtype),
        eigenvectors=U,
        residuals=res_k.astype(cfg.dtype),
        restarts=restarts,
        converged=n_conv >= k,
        operator_applications=apps,
    )


# ---------------------------------------------------------------------------
# Block thick-restart Lanczos (DESIGN.md §3)
# ---------------------------------------------------------------------------

def _orthonormal_block_against(W: Array, basis: Array, key: Array) -> Array:
    """[n, b] random directions orthogonal to the (zero-padded) basis rows
    AND to each other — the block analogue of the breakdown escape hatch."""
    n, b = W.shape
    r = jax.random.normal(key, (n, b), jnp.float32)
    r = r - basis.T @ (basis @ r)
    q, _ = jnp.linalg.qr(r)
    return q


def _lanczos_topk_block(
    matmat: Callable[[Array], Array],
    n: int,
    cfg: LanczosConfig,
    *,
    v0: Optional[Array] = None,
    key: Optional[Array] = None,
) -> LanczosResult:
    """Block thick-restart Lanczos: basis grows b columns per operator pass.

    Invariants mirror the single-vector path exactly — full-coefficient
    bookkeeping (T rows are measured, not assumed), two-pass block
    Gram-Schmidt, eigh of the projected matrix, thick restart keeping the
    top Ritz pairs plus the residual block.  The per-step differences:

    * ONE ``matmat`` streams the operator for all b new columns;
    * reorthogonalization is two [m+b, n]·[n, b] GEMM pairs (MXU);
    * the in-block factorization is a [n, b] QR; its R factor (composed with
      the cleanup QR's R) is the band coupling block recorded in T;
    * rank-deficient residual columns (invariant subspace hit) are replaced
      by random directions orthogonal to everything, with ~zero coupling —
      identical semantics to the single-vector random restart.
    """
    k, b = cfg.k, cfg.block_size
    m = effective_basis_size(cfg)
    assert 0 < k < m and m + b <= n, (
        f"block Lanczos needs k < m and m + b <= n (k={k}, m={m}, b={b}, n={n}); "
        f"shrink block_size or the basis m for this problem size"
    )
    assert m >= k + 2 * b, f"block mode needs m >= k + 2b (m={m}, k={k}, b={b})"
    matmat = scoped("spmv", matmat)
    key = jax.random.PRNGKey(0) if key is None else key
    f32 = jnp.float32

    key, k0 = jax.random.split(key)
    X0 = jax.random.normal(k0, (n, b), f32)
    if v0 is not None:
        X0 = X0.at[:, 0].set(v0.astype(f32))
    Q0, _ = jnp.linalg.qr(X0)  # column 0 keeps v0's direction

    sign = 1.0 if cfg.which == "LA" else -1.0  # "SA" negates the spectrum

    l_keep = restart_keep_size(cfg)

    def make_step(l):
        def step(i, carry):
            """One block step: expand basis rows j+b..j+2b-1, record T blocks."""
            V, T, key, apps = carry
            j = l + i * b
            Vj = jax.lax.dynamic_slice_in_dim(V, j, b, axis=0)  # [b, n]
            W = matmat(Vj.T).astype(f32).T * sign  # [b, n] — ONE operator stream
            with jax.named_scope("orthogonalize"):
                C = V @ W.T  # [m+b, b] couplings (zero rows -> zero coeffs)
                T = jax.lax.dynamic_update_slice(T, C, (0, j))
                T = jax.lax.dynamic_update_slice(T, C.T, (j, 0))
                W = W - C.T @ V
                C2 = V @ W.T  # second Gram-Schmidt pass
                W = W - C2.T @ V
                # in-block orthonormalization: W.T = Q R, band block B = R2 @ R
                Q, R = jnp.linalg.qr(W.T)  # [n, b], [b, b]
                key, sub = jax.random.split(key)
                ok = jnp.abs(jnp.diagonal(R)) > 1e-10
                E = _orthonormal_block_against(W.T, V, sub)
                Qf = jnp.where(ok[None, :], Q, E)  # escape deficient directions
                Qf = Qf - V.T @ (V @ Qf)  # cleanup vs old basis (no-op if full rank)
                Q2, R2 = jnp.linalg.qr(Qf)
                B = R2 @ R  # deficient columns of R are ~0 -> ~zero coupling
                V = jax.lax.dynamic_update_slice(V, Q2.T, (j + b, 0))
                T = jax.lax.dynamic_update_slice(T, B, (j + b, j))
                T = jax.lax.dynamic_update_slice(T, B.T, (j, j + b))
            return V, T, key, apps + 1

        return step

    def run_cycle(V, T, l, key, apps):
        """Block steps l..m-b (stride b), then Ritz extraction + restart state."""
        V, T, key, apps = jax.lax.fori_loop(0, (m - l) // b, make_step(l),
                                            (V, T, key, apps))
        with jax.named_scope("restart"):
            Bm = T[m : m + b, m - b : m]  # last band coupling block
            theta, S = jnp.linalg.eigh(T[:m, :m])  # ascending
            # residual of Ritz pair i: ‖B_m · S[m-b:m, i]‖  (top-k in last k cols)
            res = jnp.linalg.norm(Bm @ S[m - b :, :], axis=0)
            scale = jnp.maximum(jnp.max(jnp.abs(theta)), 1e-12)
            conv = res[m - k :] <= cfg.tol * scale
            n_conv = conv.sum()

            # ---- thick restart: l_keep top Ritz pairs + the b residual columns
            keep = slice(m - l_keep, m)
            Y = (S[:, keep].T @ V[:m]).astype(f32)  # [l_keep, n] Ritz vectors
            V_new = jnp.zeros_like(V)
            V_new = V_new.at[:l_keep].set(Y)
            V_new = V_new.at[l_keep : l_keep + b].set(V[m : m + b])
            H = Bm @ S[m - b :, keep]  # [b, l_keep] restart couplings
            T_new = jnp.zeros_like(T)
            T_new = T_new.at[jnp.arange(l_keep), jnp.arange(l_keep)].set(
                theta[keep])
            T_new = T_new.at[l_keep : l_keep + b, :l_keep].set(H)
            T_new = T_new.at[:l_keep, l_keep : l_keep + b].set(H.T)
        return (V_new, T_new, key, theta, S, V, res, apps), n_conv

    V0 = jnp.zeros((m + b, n), f32).at[:b].set(Q0.T)
    T0 = jnp.zeros((m + b, m + b), f32)

    out, n_conv = run_cycle(V0, T0, 0, key, jnp.asarray(0, jnp.int32))

    def steady_cycle(V, T, key, apps):
        return run_cycle(V, T, l_keep, key, apps)

    if cfg.fixed_restarts is not None:
        def fbody(_, st):
            (V, T, key, *_, apps), _ = st
            return steady_cycle(V, T, key, apps)

        (V, T, key, theta, S, V_old, res, apps), n_conv = jax.lax.fori_loop(
            0, cfg.fixed_restarts, fbody, (out, n_conv)
        )
        restarts = jnp.asarray(1 + cfg.fixed_restarts)
    else:
        def wcond(st):
            _, it, nc = st
            return jnp.logical_and(it < cfg.max_restarts, nc < k)

        def wbody(st):
            (V, T, key, *_, apps), it, _ = st
            o, nc = steady_cycle(V, T, key, apps)
            return o, it + 1, nc

        (V, T, key, theta, S, V_old, res, apps), restarts, n_conv = \
            jax.lax.while_loop(wcond, wbody, (out, jnp.asarray(1), n_conv))

    # --- extract final top-k pairs from the last completed cycle ----------
    with jax.named_scope("restart"):
        topk = slice(m - k, m)
        vals = theta[topk][::-1] * sign  # descending, undo "SA" negation
        U = (S[:, topk].T @ V_old[:m]).astype(cfg.dtype)  # [k, n]
        U = U[::-1].T  # [n, k] descending order
        res_k = res[topk][::-1]
    return LanczosResult(
        eigenvalues=vals.astype(cfg.dtype),
        eigenvectors=U,
        residuals=res_k.astype(cfg.dtype),
        restarts=restarts,
        converged=n_conv >= k,
        operator_applications=apps,
    )
