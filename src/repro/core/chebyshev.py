"""Stage 2 alternative — Chebyshev polynomial-filter spectral embeddings.

Thick-restart Lanczos (:mod:`repro.core.lanczos`) pays for its exactness in
reorthogonalization — O(n·m²) GEMM work per restart cycle that grows with
the basis — and in the global QR that is the sharding wall at large k.
Compressive Spectral Clustering (Tremblay et al., PAPERS.md) shows the exact
eigenbasis is unnecessary for *clustering*: filtering a small block of
random signals through a polynomial approximation of the spectral projector
``P = 1_{λ ≥ λ_cut}(A)`` yields an embedding whose pairwise geometry (and
hence k-means labels) matches the eigenvector embedding.  The same
polynomial-filter machinery is what the Distributed Block Chebyshev-Davidson
algorithm (Pang & Yang, PAPERS.md) uses to accelerate an exact solver — so
this module is also the substrate for that follow-up.

The pipeline here (all driven through ``op.mm`` — the ONE primitive every
operator representation already provides, including the sharded one):

1. **spectral bounds** ``[lo, hi] ⊇ spec(A)`` from a few plain Lanczos
   steps (:func:`estimate_spectral_bounds`) — the filter's map interval;
2. **λ_cut selection** when only k is given: Chebyshev (KPM) moments of the
   spectral density from Hutchinson probes (:func:`chebyshev_moments`), then
   *free* eigencount bisection on the moment vector
   (:func:`find_cut_from_moments`) — one degree-deep pass of the operator
   for the whole bisection, not one per evaluation;
3. **Jackson-damped step filter** h ≈ 1_{[λ_cut, hi]} applied to an
   ``[n, R]`` Rademacher sketch ``G ∈ {±1}`` via the three-term recurrence
   as a ``lax.scan`` (:func:`chebyshev_filter`) — matvec-rich,
   reorthogonalization-free, no per-step orthogonalization of any kind;
4. **one QR + Rayleigh-Ritz** on the filtered block: whitens the sketch for
   k-means geometry and (for R ≥ k) rotates it onto Ritz pairs, so the
   chebyshev path returns eigenvalue estimates and an ``[n, k]`` embedding
   through the same :class:`~repro.core.lanczos.LanczosResult` contract.

Cost model: ``operator_streams(cfg)`` full nnz streams total — bounds +
(degree for the moments, only when λ_cut is unknown) + degree for the filter
+ 1 for Rayleigh-Ritz.  Fixed and *independent of convergence behaviour*;
compare :func:`repro.core.lanczos.operator_passes`, which multiplies the
basis size by the restart count.  On a sharded operator every stream is the
existing one-all-gather-per-application SpMM — the filter adds zero new
collectives (DESIGN.md §13).

Failure surface (DESIGN.md §13): a small spectral gap at λ_k makes the
damped step's transition band straddle wanted and unwanted eigenvalues —
raise ``degree``; interval misestimation (``hi`` below the true λ_max) makes
the recurrence diverge geometrically — the bounds estimator widens its Ritz
interval by the last residual norm plus a relative margin to prevent this.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lanczos import LanczosResult, scoped

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ChebConfig:
    """Chebyshev polynomial-filter embedding knobs (the ``solver="chebyshev"``
    engine behind :class:`~repro.core.spectral.EigConfig`).

    ``k`` is the number of returned columns/eigenvalue estimates (the
    embedding width); ``n_signals`` is the sketch width R (``None`` → k + 8,
    the randomized-range-finder oversampling; R < k is the CSC compressive
    regime — the embedding stays R wide and eigenvalue estimates cover only
    the R Ritz pairs).  ``lambda_cut`` is the passband edge in the
    *operator's* eigenvalue units ("keep eigenvalues ≥ λ_cut" for
    ``which="LA"``); ``None`` locates it by eigencount bisection targeting k
    eigenvalues in the passband.
    """

    k: int  # wanted embedding columns / eigenpair estimates
    degree: int = 64  # Chebyshev filter degree M (transition sharpness)
    n_signals: Optional[int] = None  # sketch width R; None → k + 8
    lambda_cut: Optional[float] = None  # passband edge; None → bisection
    which: str = "LA"  # "LA": filter the top of the spectrum ("SA" negates)
    n_probes: int = 8  # Hutchinson probes for the eigencount moments
    bisect_iters: int = 30  # bisection steps on the moment-based eigencount
    bounds_iters: int = 12  # Lanczos steps for the spectral-interval estimate
    margin: float = 0.01  # relative widening of the estimated interval
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"ChebConfig.k must be >= 1, got {self.k}")
        if self.degree < 1:
            raise ValueError(
                f"ChebConfig.degree must be >= 1, got {self.degree}")
        if self.n_signals is not None and self.n_signals < 1:
            raise ValueError(
                f"ChebConfig.n_signals must be >= 1, got {self.n_signals}")
        if self.n_probes < 1:
            raise ValueError(
                f"ChebConfig.n_probes must be >= 1, got {self.n_probes}")
        if self.bounds_iters < 2:
            raise ValueError(
                f"ChebConfig.bounds_iters must be >= 2, got {self.bounds_iters}")
        if self.which not in ("LA", "SA"):
            raise ValueError(
                f"ChebConfig.which must be 'LA' or 'SA', got {self.which!r}")


def resolved_signals(cfg: ChebConfig) -> int:
    """The sketch width R the solver will actually run (static)."""
    return cfg.n_signals if cfg.n_signals is not None else cfg.k + 8


def operator_streams(cfg: ChebConfig) -> int:
    """Full nnz streams (operator applications) of one chebyshev embedding —
    the figure of merit matching :func:`repro.core.lanczos.operator_passes`.

    Fixed by construction: bounds estimation + (moments, only when λ_cut
    must be located) + the filter recurrence + one Rayleigh-Ritz apply.
    """
    streams = cfg.bounds_iters + cfg.degree + 1
    if cfg.lambda_cut is None:
        streams += cfg.degree
    return streams


# ---------------------------------------------------------------------------
# Filter construction: Jackson-damped Chebyshev expansion of the step
# ---------------------------------------------------------------------------

def jackson_damping(degree: int) -> Array:
    """Jackson damping factors g_0..g_M — turn the truncated Chebyshev series
    into a positive kernel, killing the Gibbs overshoot that would let the
    step filter amplify eigenvalues just *below* the cut."""
    m = degree + 1
    j = jnp.arange(m, dtype=jnp.float32)
    alpha = jnp.pi / (m + 1)
    g = ((m - j + 1) * jnp.cos(j * alpha)
         + jnp.sin(j * alpha) / jnp.tan(alpha)) / (m + 1)
    return (g / g[0]).astype(jnp.float32)  # normalize g_0 = 1 exactly


def step_coefficients(a: Array, degree: int) -> Array:
    """Chebyshev coefficients c_0..c_M of the step 1_{[a, 1]} on [-1, 1]
    (closed form via t = cos θ): c_0 = arccos(a)/π, c_j = 2 sin(j·arccos(a))/(jπ)."""
    theta = jnp.arccos(jnp.clip(a, -1.0, 1.0))
    j = jnp.arange(1, degree + 1, dtype=jnp.float32)
    c0 = theta / jnp.pi
    cj = 2.0 * jnp.sin(j * theta) / (j * jnp.pi)
    return jnp.concatenate([c0[None], cj]).astype(jnp.float32)


def filter_weights(a: Array, degree: int) -> Array:
    """Damped filter coefficients g_j·c_j(a) — shared by the filter and the
    eigencount so the count bisection optimizes the exact filter applied."""
    return jackson_damping(degree) * step_coefficients(a, degree)


def filter_response(lam: Array, a: Array, lo: Array, hi: Array,
                    degree: int) -> Array:
    """Scalar transfer function h(λ) of the damped filter (diagnostics/tests:
    the dense-projector oracle is V·diag(h(Λ))·Vᵀ)."""
    t = jnp.clip((2.0 * lam - (hi + lo)) / (hi - lo), -1.0, 1.0)
    w = filter_weights(a, degree)  # [M+1]
    theta = jnp.arccos(t)
    tj = jnp.cos(jnp.arange(degree + 1, dtype=jnp.float32)[:, None]
                 * theta[None, :])  # T_j(t) = cos(j·arccos t)
    return (w[:, None] * tj).sum(0)


# ---------------------------------------------------------------------------
# Interval selection
# ---------------------------------------------------------------------------

def bounds_steps(n: int, iters: int) -> int:
    """Lanczos steps (one ``op.mv`` each) :func:`estimate_spectral_bounds`
    runs on an ``n``-dimensional operator."""
    return min(iters, max(2, n - 1))


def estimate_spectral_bounds(op, key: Array, *, iters: int = 12,
                             margin: float = 0.01) -> Tuple[Array, Array]:
    """[lo, hi] ⊇ spec(op) from ``iters`` plain Lanczos steps on ``op.mv``.

    The Ritz interval of an un-reorthogonalized Lanczos run underestimates
    the true extremes; widening by the final residual norm β (the classic
    Kaniel-Paige bound surrogate) plus a relative ``margin`` makes the
    interval safe for the Chebyshev map — an interval that *misses* part of
    the spectrum would make the recurrence diverge geometrically.
    """
    n = op.shape[0]
    steps = bounds_steps(n, iters)
    f32 = jnp.float32
    v = jax.random.normal(key, (n,), f32)
    v = v / jnp.maximum(jnp.linalg.norm(v), 1e-30)

    def body(carry, _):
        v_prev, v_cur, beta = carry
        w = op.mv(v_cur).astype(f32) - beta * v_prev
        alpha = v_cur @ w
        w = w - alpha * v_cur
        beta_new = jnp.linalg.norm(w)
        # invariant-subspace breakdown: freeze the direction; the recorded
        # beta=0 decouples the tridiagonal, which is exactly right
        v_new = jnp.where(beta_new > 1e-10,
                          w / jnp.maximum(beta_new, 1e-30), v_cur)
        return (v_cur, v_new, beta_new), (alpha, beta_new)

    (_, _, _), (alphas, betas) = jax.lax.scan(
        body, (jnp.zeros((n,), f32), v, jnp.asarray(0.0, f32)), None,
        length=steps)
    t = jnp.diag(alphas) + jnp.diag(betas[:-1], 1) + jnp.diag(betas[:-1], -1)
    ritz = jnp.linalg.eigvalsh(t)
    beta_last = betas[-1]
    lo = ritz[0] - beta_last
    hi = ritz[-1] + beta_last
    pad = margin * jnp.maximum(hi - lo, 1e-3)
    return lo - pad, hi + pad


def chebyshev_moments(op, lo: Array, hi: Array, degree: int, key: Array,
                      *, n_probes: int = 8) -> Array:
    """KPM moments μ_j ≈ tr(T_j(Ã)), j = 0..degree, from Rademacher probes
    (Hutchinson): μ_j = mean_r z_rᵀ T_j(Ã) z_r with E[z zᵀ] = I.

    ONE degree-deep recurrence on the [n, n_probes] probe block yields the
    whole moment vector; every downstream eigencount evaluation is then a
    dot product — the entire λ_cut bisection costs zero extra operator
    streams (vs re-filtering per bisection step).
    """
    n = op.shape[0]
    z = jax.random.rademacher(key, (n, n_probes), jnp.float32)
    ca = 4.0 / (hi - lo)
    cb = -2.0 * (hi + lo) / (hi - lo)
    t0 = z
    t1 = 0.5 * (ca * op.mm(z).astype(jnp.float32) + cb * z)
    mu0 = jnp.asarray(float(n), jnp.float32)  # zᵀz = n exactly
    mu1 = jnp.mean((z * t1).sum(0))

    def body(carry, _):
        tp, tc = carry
        tn = ca * op.mm(tc).astype(jnp.float32) + cb * tc - tp
        return (tc, tn), jnp.mean((z * tn).sum(0))

    if degree < 2:
        return jnp.stack([mu0, mu1])[: degree + 1]
    _, mus = jax.lax.scan(body, (t0, t1), None, length=degree - 1)
    return jnp.concatenate([jnp.stack([mu0, mu1]), mus])


def eigencount_from_moments(moments: Array, a: Array) -> Array:
    """Damped-step eigencount: #{λ : mapped(λ) ≥ a} ≈ Σ_j g_j c_j(a) μ_j.
    Smooth in ``a`` (the Jackson kernel), hence bisectable."""
    degree = moments.shape[0] - 1
    return filter_weights(a, degree) @ moments


def find_cut_from_moments(moments: Array, k: int,
                          *, iters: int = 30) -> Array:
    """Bisect the mapped cut a ∈ (-1, 1) so the damped eigencount ≈ k.

    The count is monotone non-increasing in ``a``; each evaluation is a dot
    product against the precomputed moments, so the whole search is O(iters ·
    degree) scalar FLOPs — free next to one operator stream.
    """
    target = jnp.asarray(float(k), jnp.float32)

    def body(_, ab):
        alo, ahi = ab
        mid = 0.5 * (alo + ahi)
        too_many = eigencount_from_moments(moments, mid) > target
        return jnp.where(too_many, mid, alo), jnp.where(too_many, ahi, mid)

    alo, ahi = jax.lax.fori_loop(
        0, iters, body,
        (jnp.asarray(-0.999, jnp.float32), jnp.asarray(0.999, jnp.float32)))
    return 0.5 * (alo + ahi)


# ---------------------------------------------------------------------------
# The filter
# ---------------------------------------------------------------------------

def chebyshev_filter(op, x: Array, lo: Array, hi: Array, a: Array,
                     degree: int, *, sign: float = 1.0) -> Array:
    """h(A)·x for the Jackson-damped step filter h ≈ 1_{[a, 1]} on the
    mapped spectrum — the three-term recurrence as a ``lax.scan`` over
    ``op.mm``.

    Each step is ONE operator stream plus an AXPY chain; no
    orthogonalization, no collectives beyond the operator's own.  When the
    operator provides the fused ``cheb_step`` hook (``y = ca·(A x) + cb·x −
    prev`` — :class:`~repro.core.operator.BlockEllOperator` folds it into the
    Pallas ``ell_spmm`` epilogue), the AXPY chain rides the SpMM pass instead
    of re-streaming the [n, R] block through HBM.
    """
    f32 = jnp.float32
    x = x.astype(f32)
    ca = (sign * 4.0 / (hi - lo)).astype(f32)
    cb = (-2.0 * (hi + lo) / (hi - lo)).astype(f32)
    fused = getattr(op, "cheb_step", None)
    if fused is not None:
        step = lambda t_cur, t_prev: fused(t_cur, t_prev, ca, cb)
    else:
        step = lambda t_cur, t_prev: (
            ca * op.mm(t_cur).astype(f32) + cb * t_cur - t_prev)

    w = filter_weights(a, degree)  # [M+1]
    t0 = x
    t1 = 0.5 * step(x, jnp.zeros_like(x))  # T_1 = Ã x
    acc = w[0] * t0 + w[1] * t1
    if degree < 2:
        return acc

    def body(carry, wj):
        tp, tc, acc = carry
        tn = step(tc, tp)
        return (tc, tn, acc + wj * tn), None

    (_, _, acc), _ = jax.lax.scan(body, (t0, t1, acc), w[2:])
    return acc


# ---------------------------------------------------------------------------
# The solver entry (dispatched from repro.core.lanczos.eigsh)
# ---------------------------------------------------------------------------

def chebyshev_eigsh(op, cfg: ChebConfig, *, v0: Optional[Array] = None,
                    key: Optional[Array] = None) -> LanczosResult:
    """Polynomial-filtered randomized embedding of the dominant eigenspace,
    returned through the :class:`~repro.core.lanczos.LanczosResult` contract.

    Filter an [n, R] Rademacher sketch through the damped step filter, QR the
    result (whitening — raw filtered signals are correlated through the
    filter's spectral envelope, which skews k-means geometry), then
    Rayleigh-Ritz on the R-dimensional basis: ``B = QᵀAQ`` (one extra
    stream), eigh of the R×R block, rotate.  Returns min(k, R) Ritz pairs in
    descending order — for R ≥ k these approximate the top-k eigenpairs; for
    R < k (CSC compressive mode) the R-wide whitened embedding is returned
    as-is with its R Ritz values.

    ``restarts`` reports 0 (the filter has no restart loop) and ``converged``
    is always True: this is a fixed-cost filter, not an iterative solver —
    ``residuals`` carries the Rayleigh-Ritz residual norms ‖A u − θ u‖ as
    the accuracy diagnostic (expect ~1e-3..1e-2: subspace quality, which is
    what clustering consumes, is much better than eigenpair accuracy).
    """
    n = op.shape[0]
    r = resolved_signals(cfg)
    if r > n:
        raise ValueError(
            f"ChebConfig needs n_signals <= n, got R={r} > n={n} — the "
            f"filtered sketch is QR-factorized, so at most n columns are "
            f"independent; reduce n_signals (or k: the default R is k + 8)")
    if cfg.k > n:
        raise ValueError(
            f"ChebConfig.k={cfg.k} exceeds the operator dimension n={n}")
    key = jax.random.PRNGKey(0) if key is None else key
    f32 = jnp.float32
    sign = 1.0 if cfg.which == "LA" else -1.0  # "SA" filters -A's top
    op = _Scoped(op)

    k_bounds, k_mom, k_sketch = jax.random.split(key, 3)
    lo, hi = estimate_spectral_bounds(
        _signed(op, sign), k_bounds, iters=cfg.bounds_iters, margin=cfg.margin)

    if cfg.lambda_cut is not None:
        cut = jnp.asarray(sign * cfg.lambda_cut, f32)
        a = jnp.clip((2.0 * cut - (hi + lo)) / (hi - lo), -0.999, 0.999)
    else:
        mom = chebyshev_moments(_signed(op, sign), lo, hi, cfg.degree, k_mom,
                                n_probes=cfg.n_probes)
        a = find_cut_from_moments(mom, cfg.k, iters=cfg.bisect_iters)

    g = jax.random.rademacher(k_sketch, (n, r), f32)
    if v0 is not None:
        # seed the sketch with the caller's start vector (the pipeline passes
        # the exact trivial eigenvector — guarantees it's in the subspace)
        v = v0.astype(f32)
        v = v * (jnp.sqrt(float(n)) / jnp.maximum(jnp.linalg.norm(v), 1e-30))
        g = g.at[:, 0].set(v)

    y = chebyshev_filter(op, g, lo, hi, a, cfg.degree, sign=sign)
    with jax.named_scope("orthogonalize"):
        q, _ = jnp.linalg.qr(y)  # [n, R] whitened basis
    aq = sign * op.mm(q).astype(f32)  # ONE extra stream
    with jax.named_scope("restart"):
        b = q.T @ aq
        b = 0.5 * (b + b.T)
        theta, s = jnp.linalg.eigh(b)  # ascending [R]

        kk = min(cfg.k, r)
        sel = s[:, r - kk:][:, ::-1]  # top-kk, descending
        vals = theta[r - kk:][::-1]
        u = q @ sel  # [n, kk] Ritz vectors
        resid = jnp.linalg.norm(aq @ sel - u * vals[None, :], axis=0)
    # every loop above has a static trip count, so the applications it
    # executed are the sum of those counts
    apps = (bounds_steps(n, cfg.bounds_iters) + cfg.degree + 1
            + (cfg.degree if cfg.lambda_cut is None else 0))
    return LanczosResult(
        eigenvalues=(vals * sign).astype(cfg.dtype),
        eigenvectors=u.astype(cfg.dtype),
        residuals=resid.astype(cfg.dtype),
        restarts=jnp.asarray(0),
        converged=jnp.asarray(True),
        operator_applications=jnp.asarray(apps, jnp.int32),
    )


def diverged(laplacian_eigenvalues, *, slack: float = 0.5) -> bool:
    """Host-side bounds-containment check on a finished filter embedding.

    The three-term recurrence diverges *geometrically* when a true
    eigenvalue escapes the estimated ``[lo, hi]`` interval (the mapped
    |t| > 1 regime), so a containment miss is detectable post-hoc: Ritz
    values of the sym-normalized adjacency live in [-1, 1] (Laplacian form
    in [0, 2]); non-finite or far-outside values mean the bounds estimator
    missed and the subspace is garbage, not merely inaccurate.  Consumed by
    the embed-stage escalation controller (widen ``margin`` → fall back to
    Lanczos).  Needs concrete values — call outside jit.
    """
    vals = np.asarray(laplacian_eigenvalues)
    if not np.isfinite(vals).all():
        return True
    return bool(np.max(np.abs(1.0 - vals)) > 1.0 + slack)


class _Scoped:
    """Operator view whose every application (``mv``, ``mm`` and the fused
    ``cheb_step`` where the operator has one) traces under the ``spmv``
    scope."""

    def __init__(self, op):
        self.shape = op.shape
        self.mv = scoped("spmv", op.mv)
        self.mm = scoped("spmv", op.mm)
        fused = getattr(op, "cheb_step", None)
        if fused is not None:
            self.cheb_step = scoped("spmv", fused)


class _signed:
    """Sign-flipping operator view (``which="SA"`` filters the top of −A)
    without touching the wrapped operator's pytree registration."""

    def __init__(self, op, sign: float):
        self._op = op
        self._sign = sign
        self.shape = op.shape

    def mv(self, x: Array) -> Array:
        y = self._op.mv(x)
        return y if self._sign == 1.0 else -y

    def mm(self, x: Array) -> Array:
        y = self._op.mm(x)
        return y if self._sign == 1.0 else -y
