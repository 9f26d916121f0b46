"""Rank-r pivoted Nyström preconditioner for H = K̂ + D (DESIGN.md §3.8).

The GRF estimator is *already* low-rank-structured — K̂ = ΦΦᵀ with explicit
feature rows — so a Nyström approximation is nearly free: pick r pivot rows
S of Φ and precondition with M = (K̂_nys + D)⁻¹ where

    K̂_nys = C W⁻¹ Cᵀ,   C = Φ Φ_Sᵀ  [T, r],   W = Φ_S Φ_Sᵀ  [r, r].

**Pivot rule.**  The pivots are chosen by greedy *residual*-diagonal
selection — partial pivoted Cholesky of K̂ (RPCholesky's deterministic
cousin): repeatedly take the row with the largest remaining diagonal,
append its (residual-orthogonalised) K̂ column as a factor column, and
downdate the diagonal.  After r steps F Fᵀ equals the Nyström approximation
for that pivot set *in factored form* (B = F directly — no separate W
Cholesky), and the greedy rule auto-spreads pivots across correlated row
clusters: once a row is picked, its near-duplicates' residual diagonals
collapse and are never picked again.  Ranking by the *plain* diagonal
instead wastes the whole budget on one cluster (measured: ~3× worse
residual on the clustered bench systems).

**Costs.**  Setup: r exact ``dispatch.gram_block`` columns (O(T·K²) each —
the sparse×sparse kernel, duplicate deposit columns handled) + the O(T·r²)
factor updates.  Apply: Woodbury

    M v = D⁻¹v − D⁻¹B E⁻¹ BᵀD⁻¹v,      E = I_r + BᵀD⁻¹B

is **O(T·r) per CG iteration** — the same order as the K̂ matvec itself.
E⁻¹ is formed **once** from the r×r Cholesky at build time, and the whole
apply dispatches to ``dispatch.woodbury_apply`` (kernels/woodbury_apply/):
on Pallas backends one fused pass with the rank-space intermediate and E⁻¹
VMEM-resident, on XLA two GEMVs against loop-invariant operands — never a
per-iteration triangular solve (the old ``cho_solve``-per-apply cost more
wall-clock than the iterations it saved; ISSUE 6).
When the training rows are correlated (clustered observations, solve-heavy
kernels like the regularized Laplacian) the top-r spectrum carries most of
K̂, and removing it drops the CG iteration count by the measured ≥2× at
σ_n² ≤ 1e-2 (BENCH_solvers.json).

**Adaptive rank.**  ``select_rank``/``resolve_strategy`` size r by
measurement instead of a static guess: a short batched Lanczos probe
(``cg_solve_fixed(..., with_coeffs=True)`` — the same (α,β) plumbing SLQ
integrates) yields Ritz values θ and Gauss-quadrature weights that estimate
the eigen-count function  N(x) ≈ #{λ_i(H) > x}.  From the implied spectral
quantiles λ̂_r a CG cost model (√κ iteration law × measured per-iteration
and setup costs in matvec-equivalent units) scores each candidate
r ∈ AUTO_RANKS, and the cheapest wins — rank 0 (Jacobi) when the spectrum's head is too wide for
any affordable r to capture (the N=1e6/σ_n²=1e-2 regime where the measured
iteration ratio collapses to 1.09×).

Heteroscedastic noise vectors D and the masked sandwich M K̂ M + D are both
supported (the mask scales the feature rows, which is exactly the sandwich
in factored form).  The psum-sharded path is *not*: the factor columns span
shards, so ``nystrom_precond`` raises on operators carrying a ``reduce``
hook — sharded strategies keep ``"jacobi"``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_solve

from ..core import features, linops
from ..kernels import dispatch
from ..runtime import trace_state_clean
from .strategy import AUTO_RANKS, DEFAULT_PRECOND_RANK, SolveStrategy


@functools.partial(jax.jit, static_argnums=3)
def _pivoted_cholesky(vals, cols, d0, rank: int):
    """Greedy partial pivoted Cholesky of K̂ = ΦΦᵀ from the ELL payload.

    Returns (F [T, rank], pivots [rank]) with F Fᵀ ≈ K̂ (the Nyström
    approximation anchored on the greedy pivot set).  Exhausted residuals
    (numerical rank < requested) write zero factor columns — harmless for
    the preconditioner — but pivots stay *distinct*: already-picked rows
    are masked to −∞ in the argmax, so past the numerical rank the sweep
    keeps returning fresh (zero-residual) rows instead of duplicating row
    0 — ``pivot_rows``/``init_inducing_pivoted`` expose the indices.

    jit-compiled with the rank static: an eager ``fori_loop`` re-traces its
    body closure on every call, which made each preconditioner build pay a
    full loop recompile (~1.3 s at T=400 — more than the CG iterations it
    saved).  Under the module-level jit the compile is paid once per
    (T, K, r) shape and every later build is pure compute."""
    t = vals.shape[0]

    def body(i, carry):
        fmat, d, taken, piv = carry
        p = jnp.argmax(jnp.where(taken, -jnp.inf, d))
        g = dispatch.gram_block(vals, cols, vals[p][None], cols[p][None])[:, 0]
        proj = fmat @ fmat[p]                 # columns ≥ i are still zero
        l = (g - proj) / jnp.sqrt(jnp.maximum(d[p], 1e-12))
        l = jnp.where(d[p] > 1e-10, l, jnp.zeros_like(l))
        fmat = fmat.at[:, i].set(l)
        d = jnp.maximum(d - l * l, 0.0)
        return (fmat, d, taken.at[p].set(True),
                piv.at[i].set(p.astype(jnp.int32)))

    fmat, _, _, piv = jax.lax.fori_loop(
        0, rank,
        body,
        (jnp.zeros((t, rank), vals.dtype), d0,
         jnp.zeros((t,), bool), jnp.zeros((rank,), jnp.int32)),
    )
    return fmat, piv


def pivot_rows(trace, f: jax.Array, rank: int) -> jax.Array:
    """Top-``rank`` row indices of Φ by greedy residual-diagonal pivoting —
    the Nyström pivot rule.  Shared with
    ``gp.variational.init_inducing_pivoted`` (Nyström inducing selection):
    the pivots spread across correlated clusters instead of stacking onto
    the single highest-energy one."""
    vals = features.feature_values(trace, f)
    d0 = features.khat_diag_exact(trace, f)
    _, piv = _pivoted_cholesky(vals, trace.cols, d0, rank)
    return piv


def check_operator(h) -> str | None:
    """Why ``h`` can't take a Nyström preconditioner, or None if it can.

    Shared by :func:`nystrom_precond` (which raises on it) and
    :func:`resolve_strategy` (which silently falls back to Jacobi)."""
    if not isinstance(h, linops.ShiftedOperator):
        return (
            "nystrom preconditioner needs a ShiftedOperator (H = K̂ + D) so "
            f"the pivot rows and noise diagonal are recoverable; got {type(h)}"
        )
    phi_op = h.khat.rows
    if not isinstance(phi_op, linops.PhiOperator) or phi_op is not h.khat.cols:
        return (
            "nystrom preconditioner needs a *square* K̂ over a materialised "
            "trace (PhiOperator rows); chunked/cross operators can't serve "
            "pivot rows — use preconditioner='jacobi'"
        )
    if h.khat.reduce is not None:
        return (
            "nystrom preconditioner is not available on the psum-sharded "
            "path (the Nyström factor columns span shards); sharded "
            "strategies keep preconditioner='jacobi'"
        )
    return None


def nystrom_precond(h, rank: int | None = None, jitter: float = 1e-6):
    """Build the Woodbury apply v ↦ M⁻¹v for a materialised-trace operator.

    ``h`` must be a :class:`repro.core.linops.ShiftedOperator` whose K̂ is
    square over a materialised :class:`PhiOperator` (the pivot columns are
    exact Gram rows of that trace).  Returns a callable usable as
    ``precond=`` on both CG loops; it also exposes ``.logdet()``
    (log det M⁻¹ = log det(K̂_nys + D) via the matrix determinant lemma) and
    ``.pivots``/``.rank`` for introspection.  ``rank=None`` resolves to
    ``strategy.DEFAULT_PRECOND_RANK`` — the same source of truth as
    ``SolveStrategy.precond_rank``.  ``jitter`` guards the inner r×r
    Cholesky.  The per-iteration apply dispatches to
    ``dispatch.woodbury_apply`` (fused Pallas kernel / jnp oracle), with
    E⁻¹ precomputed so no triangular solve happens inside the CG loop."""
    reason = check_operator(h)
    if reason is not None:
        raise ValueError(reason)
    if rank is None:
        rank = DEFAULT_PRECOND_RANK

    phi_op = h.khat.rows
    trace, f = phi_op.trace, phi_op.f
    t = trace.cols.shape[0]
    r = min(rank, t)

    vals = features.feature_values(trace, f)
    d0 = features.khat_diag_exact(trace, f)
    if h.mask is not None:
        # M K̂ M in factored form: scale the feature rows by the mask.
        vals = vals * h.mask[:, None]
        d0 = d0 * h.mask * h.mask
    b, piv = _pivoted_cholesky(vals, trace.cols, d0, r)

    d = jnp.broadcast_to(h.noise, (t,)).astype(b.dtype)
    dinv = jnp.where(d > 0, 1.0 / jnp.maximum(d, 1e-30), 1.0)
    e = jnp.eye(r, dtype=b.dtype) + b.T @ (dinv[:, None] * b)
    l_e = jnp.linalg.cholesky(
        e + jitter * jnp.eye(r, dtype=b.dtype)
    )
    einv = cho_solve((l_e, True), jnp.eye(r, dtype=b.dtype))

    class _NystromApply:
        """M⁻¹v via the fused Woodbury kernel; O(T·r) per apply."""

        rank = r
        pivots = piv

        def __call__(self, v):
            return dispatch.woodbury_apply(b, dinv, einv, v)

        @staticmethod
        def logdet():
            """log det(K̂_nys + D) = Σ log d + 2 Σ log diag(L_E)."""
            return jnp.sum(jnp.log(jnp.maximum(d, 1e-30))) + 2.0 * jnp.sum(
                jnp.log(jnp.diagonal(l_e))
            )

    return _NystromApply()


# ---------------------------------------------------------------------------
# Adaptive rank: size the pivot budget by measurement (ISSUE 6 tentpole 2).
# ---------------------------------------------------------------------------


def probe_spectrum(h, key: jax.Array, n_iters: int = 24, n_probes: int = 4):
    """(θ, w): Ritz values of H and eigen-count quadrature weights.

    One batched ``n_iters``-step unpreconditioned CG pass over Rademacher
    probes — the identical (α,β) → tridiagonal → Gauss-quadrature plumbing
    SLQ uses for log-det, read off for a different integral: with
    E[zzᵀ] = I the weighted node counts estimate the eigen-count function

        N(x) = #{λ_i(H) > x} ≈ Σ_k w_k · 1[θ_k > x].

    Cost: ``n_iters`` matvecs on an [T, n_probes] block — a rounding error
    next to the solve being planned."""
    from .cg import cg_solve_fixed
    from .slq import rademacher, tridiag_from_coeffs

    t = h.shape[0]
    z = rademacher(key, (t, n_probes))
    _, coeffs = cg_solve_fixed(h, z, iters=min(n_iters, t), with_coeffs=True)
    tri = tridiag_from_coeffs(coeffs)                 # [S, m, m]
    theta, vecs = jnp.linalg.eigh(tri)
    tau2 = vecs[:, 0, :] ** 2                         # e₁ weights, [S, m]
    w = coeffs.bnorm2[:, None] * tau2 / n_probes      # Σw = tr(I) ≈ T
    return theta.reshape(-1), w.reshape(-1)


def _spectral_quantile(theta: jax.Array, w: jax.Array, r) -> jax.Array:
    """λ̂_{r+1}: the estimated (r+1)-th largest eigenvalue of H.

    Interpolates the quadrature's eigen-count CDF at count r — i.e. the
    level x with N(x) = r eigenvalues above it."""
    order = jnp.argsort(-theta)
    th, cw = theta[order], jnp.cumsum(w[order])
    return jnp.interp(jnp.asarray(r, th.dtype), cw, th)


# Cost-model constants, in *matvec-equivalents* — deliberately not flop
# counts.  Measured on the bench systems (T = 4√N clustered blocks,
# N ∈ {1e4, 1e5}): per-iteration and setup wall-clock scale far more weakly
# with T than their flop counts (small sequential kernels are
# latency/dispatch-bound, not flop-bound), so an absolute-flops model
# systematically over-charges large T.  Relative units calibrate cleanly:
#   * the Woodbury apply adds ≈ 0.5 % of a matvec per unit of rank
#     (measured ~0.022 ms/rank-iter against ~3.4–5 ms matvecs), and
#   * the jitted pivoted-Cholesky setup costs ≈ 0.37 matvec-iterations per
#     unit of rank (measured 612 ms at r=256/T=400 vs 3.4 ms iterations,
#     deflated by the √κ law's uniform ~1.9× iteration under-prediction —
#     only *relative* cost ranks candidates, so the bias divides out).
# With these the model reproduces the measured argmin: rank 128 at
# N=1e4 (913 ms vs Jacobi's 1179 ms) and rank 0 at N=1e5, where the probe
# shows the spectral head too wide for any affordable r (λ̂_256 ≈ 3 ≫ λ_min).
_WOODBURY_COST = 0.005        # per-iteration multiplier per unit of rank
_SETUP_COST = 0.37            # setup, in iteration-equivalents per rank


def select_rank(
    h,
    key: jax.Array | None = None,
    ranks=AUTO_RANKS,
    tol: float = 1e-6,
    n_iters: int = 24,
    n_probes: int = 4,
) -> int:
    """Measured rank choice: argmin of a CG cost model over ``ranks``.

    For each candidate r the model predicts iterations from the √κ law —
    κ_r ≈ λ̂_{r+1}/λ_min after the preconditioner removes the top-r head —
    and charges the per-iteration Woodbury apply plus the one-off pivoted
    setup.  Rank 0 (Jacobi) wins when the head is too wide to capture
    (λ̂_r stays ≈ λ_max for every affordable r), which is exactly the
    N=1e6/σ_n²=1e-2 bench regime."""
    if key is None:
        key = jax.random.PRNGKey(0)
    theta, w = probe_spectrum(h, key, n_iters=n_iters, n_probes=n_probes)
    lam_min = jnp.maximum(jnp.min(theta), 1e-12)
    lam_max = jnp.maximum(jnp.max(theta), lam_min)

    t = h.shape[0]
    # CG iteration law: I ≈ (√κ / 2) · ln(2/tol); costs below are in units
    # of one unpreconditioned iteration (see the constants' rationale).
    iters_scale = 0.5 * math.log(2.0 / max(tol, 1e-12))

    best_rank, best_cost = 0, None
    for r in ranks:
        r = int(min(r, t))
        if r == 0:
            kappa = lam_max / lam_min
            per_iter, setup = 1.0, 0.0
        else:
            lam_r = jnp.clip(
                _spectral_quantile(theta, w, r), lam_min, lam_max
            )
            kappa = lam_r / lam_min
            per_iter = 1.0 + _WOODBURY_COST * r
            setup = _SETUP_COST * r
        iters = iters_scale * float(jnp.sqrt(kappa))
        cost = setup + iters * per_iter
        if best_cost is None or cost < best_cost:
            best_rank, best_cost = r, cost
    return best_rank


def resolve_strategy(
    h,
    strategy: SolveStrategy,
    *,
    key: jax.Array | None = None,
    n_iters: int = 24,
    n_probes: int = 4,
) -> SolveStrategy:
    """Resolve ``preconditioner="auto"`` into a concrete strategy for ``h``.

    Runs the spectral probe eagerly and returns ``"nystrom"`` with the
    measured rank, or ``"jacobi"`` when rank 0 wins.  Rank is a *static*
    loop-shape decision, so resolution must happen on concrete operands:
    under tracing (or on operators Nyström can't serve — sharded, chunked,
    bare callables) the fallback is ``"jacobi"``.  Consumers therefore
    resolve once at entry, before any jit boundary, and reuse the resolved
    strategy across refits (bo/thompson, gp/mll, serving/update all do)."""
    if strategy.preconditioner != "auto":
        return strategy
    # Under an active trace even closed-over concrete operands produce
    # tracers the moment the probe touches them, so "am I inside jit" is the
    # test — not "are the leaves tracers".
    tracing = not trace_state_clean() or any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(h)
    )
    if tracing or check_operator(h) is not None:
        return strategy.with_(preconditioner="jacobi")
    rank = select_rank(
        h, key=key, tol=strategy.tol, n_iters=n_iters, n_probes=n_probes
    )
    if rank == 0:
        return strategy.with_(preconditioner="jacobi")
    return strategy.with_(preconditioner="nystrom", precond_rank=rank)
