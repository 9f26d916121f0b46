"""Posterior inference with pathwise conditioning (paper §3.2, Eq. 12).

A posterior sample over *all* N nodes is a prior sample plus a sparse
correction:  g|y = g + K̂_{·x}(K̂_xx + σ²I)⁻¹(y − g(x) − ε),
with the prior sampled as g = Φ w, w ~ N(0, I_N)  (Cov = ΦΦᵀ = K̂).
Both terms share Φ, so a draw computes it as Φ(w + Φ_xᵀα) with one product
with the full Φ; g(x) = Φ_x w needs only the training rows.
Every product is an O(N) sparse op; the solve is CG (Lemma 1) routed
through the strategy layer (repro.solvers, DESIGN.md §3.8) — pass
``strategy=SolveStrategy(preconditioner="nystrom")`` to precondition the
training-block system with the rank-r pivoted Nyström of K̂_xx."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import obs
from ..core import features, linops, walks
from ..core.walks import DEFAULT_CHUNK, WalkConfig, WalkTrace
from ..graphs.formats import Graph
from ..kernels import dispatch
from .. import solvers
from ..solvers import SolveStrategy
from .mll import make_h_operator


def _resolve(strategy, cg_tol, cg_iters) -> SolveStrategy:
    if strategy is None:
        strategy = solvers.POSTERIOR_DEFAULT
    return strategy.with_overrides(tol=cg_tol, max_iters=cg_iters)


def _resolve_auto(strategy, trace_x, f, sigma_n2, obs_mask, n):
    """Resolve ``preconditioner="auto"`` *before* the jit boundary.

    The jitted impls rebuild H from the same pieces; resolving on an
    eagerly-built copy here is what lets auto pick a measured rank (inside
    the trace it could only fall back to Jacobi)."""
    if strategy.preconditioner != "auto":
        return strategy
    noise = (
        sigma_n2 if obs_mask is None
        else jnp.where(obs_mask > 0, sigma_n2, 1e6)
    )
    return solvers.resolve_strategy(
        make_h_operator(trace_x, f, noise, n), strategy
    )


def posterior_mean(
    trace: WalkTrace,
    train_nodes: jax.Array,
    f: jax.Array,
    sigma_n2: jax.Array,
    y: jax.Array,
    cg_tol: float | None = None,
    cg_iters: int | None = None,
    obs_mask: jax.Array | None = None,
    strategy: SolveStrategy | None = None,
) -> jax.Array:
    """MAP prediction m = K̂_{·x} (K̂_xx + σ²I)⁻¹ y over all N nodes (Eq. 3).

    ``obs_mask`` enables static-shape padding (padded slots ⇒ ∞ noise)."""
    # The spmv backend resolves at trace time, so it must be part of the jit
    # cache key — resolve it *outside* the jitted impl and pass it static.
    # The strategy is static for the same reason (it shapes the CG loop).
    strategy = _resolve(strategy, cg_tol, cg_iters)
    strategy = _resolve_auto(
        strategy, features.take_rows(trace, train_nodes), f, sigma_n2,
        obs_mask, trace.n_nodes,
    )
    with obs.span("posterior.mean") as sp:
        out = _posterior_mean(
            trace, train_nodes, f, sigma_n2, y, obs_mask,
            strategy=strategy,
            spmv_backend=dispatch.get_backend(),
            obs_tap=obs.enabled(),
        )
        sp.block_on(out)
    return out


@partial(jax.jit, static_argnames=("strategy", "spmv_backend", "obs_tap"))
def _posterior_mean(
    trace, train_nodes, f, sigma_n2, y, obs_mask, *, strategy, spmv_backend,
    obs_tap=False,
):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend):
        return _posterior_mean_impl(
            trace, train_nodes, f, sigma_n2, y, obs_mask, strategy
        )


def _posterior_mean_impl(
    trace, train_nodes, f, sigma_n2, y, obs_mask, strategy
):
    n = trace.n_nodes
    noise = sigma_n2 if obs_mask is None else jnp.where(obs_mask > 0, sigma_n2, 1e6)
    if obs_mask is not None:
        y = y * obs_mask
    trace_x = features.take_rows(trace, train_nodes)
    h = make_h_operator(trace_x, f, noise, n)
    alpha = solvers.solve(h, y, strategy).x
    return linops.khat_cross(trace, trace_x, f, n).matvec(alpha)


def pathwise_samples(
    trace: WalkTrace,
    train_nodes: jax.Array,
    f: jax.Array,
    sigma_n2: jax.Array,
    y: jax.Array,
    key: jax.Array,
    n_samples: int = 16,
    cg_tol: float | None = None,
    cg_iters: int | None = None,
    obs_mask: jax.Array | None = None,
    strategy: SolveStrategy | None = None,
    return_diagnostics: bool = False,
):
    """Draw ``n_samples`` joint posterior samples over all N nodes (Eq. 12).

    Returns [N, n_samples]; with ``return_diagnostics=True`` additionally
    returns (iters_used, converged) of the inner CG solve — the same
    honesty contract as the chunked variant (a maxed-out solve must be
    visible, not silently averaged into the samples)."""
    strategy = _resolve(strategy, cg_tol, cg_iters)
    strategy = _resolve_auto(
        strategy, features.take_rows(trace, train_nodes), f, sigma_n2,
        obs_mask, trace.n_nodes,
    )
    with obs.span("posterior.pathwise", n_samples=n_samples) as sp:
        out = _pathwise_samples(
            trace, train_nodes, f, sigma_n2, y, key, obs_mask,
            n_samples=n_samples, strategy=strategy,
            spmv_backend=dispatch.get_backend(),
            obs_tap=obs.enabled(),
        )
        sp.block_on(out)
    samples, iters, converged = out
    if return_diagnostics:
        return samples, iters, converged
    return samples


@partial(
    jax.jit,
    static_argnames=("n_samples", "strategy", "spmv_backend", "obs_tap"),
)
def _pathwise_samples(
    trace, train_nodes, f, sigma_n2, y, key, obs_mask,
    *, n_samples, strategy, spmv_backend, obs_tap=False,
):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend):
        return _pathwise_samples_impl(
            trace, train_nodes, f, sigma_n2, y, key, n_samples, obs_mask,
            strategy,
        )


def _pathwise_samples_impl(
    trace, train_nodes, f, sigma_n2, y, key, n_samples, obs_mask, strategy
):
    n = trace.n_nodes
    trace_x = features.take_rows(trace, train_nodes)
    sol, u = _pathwise_correction(
        trace_x, f, sigma_n2, y, key, n, n_samples, obs_mask, strategy
    )
    samples = linops.phi(trace, f, n).matvec(u)
    return samples, sol.iters, jnp.all(sol.converged)


def _pathwise_correction(
    trace_x, f, sigma_n2, y, key, n, n_samples, obs_mask, strategy
):
    """Everything of Eq. 12 but the one product with the full Φ.

    Φ is linear, so the prior sample and the correction share it:
    g + K̂_{·x}α = Φw + ΦΦ_xᵀα = Φ(w + Φ_xᵀα), and the prior's training rows
    are g_x = Φ_x w.  Returns the solve and u = w + Φ_xᵀα ([N, n_samples]);
    the caller streams Φ over N once, as Φu."""
    t = trace_x.cols.shape[0]
    noise = sigma_n2 if obs_mask is None else jnp.where(obs_mask > 0, sigma_n2, 1e6)
    k_w, k_eps = jax.random.split(key)
    w = jax.random.normal(k_w, (n, n_samples), dtype=jnp.float32)
    g_x = features.phi_matvec(trace_x, f, w)                   # prior at x
    eps = jnp.sqrt(sigma_n2) * jax.random.normal(k_eps, (t, n_samples))
    resid = y[:, None] - (g_x + eps)
    if obs_mask is not None:
        resid = resid * obs_mask[:, None]

    h = make_h_operator(trace_x, f, noise, n)
    sol = solvers.solve(h, resid, strategy)
    return sol, w + features.phi_t_matvec(trace_x, f, sol.x, n)


def pathwise_samples_chunked(
    graph: Graph,
    train_nodes: jax.Array,
    f: jax.Array,
    sigma_n2: jax.Array,
    y: jax.Array,
    key: jax.Array,
    walk_key: jax.Array,
    cfg: WalkConfig,
    *,
    chunk: int = DEFAULT_CHUNK,
    n_samples: int = 16,
    cg_tol: float | None = None,
    cg_iters: int | None = None,
    obs_mask: jax.Array | None = None,
    strategy: SolveStrategy | None = None,
    return_diagnostics: bool = False,
):
    """Eq. 12 over all N nodes with the full-graph Φ *never materialised*.

    The draw streams Φ once, in ``chunk``-row blocks
    (core/linops.ChunkedPhiOperator), as Φ(w + Φ_xᵀα): the prior sample Φw
    and the correction K̂_{·x}α = ΦΦ_xᵀα in one pass over N.  Only the
    training-node trace Φ_x is materialised ([T, K]); it gives the prior's
    training rows Φ_x w and the scatter Φ_xᵀα.  Because the walker RNG is
    counter-based, ``walk_key`` makes Φ_x and the streamed Φ rows of the
    same underlying feature matrix — this path equals ``pathwise_samples``
    on the monolithic trace sampled with ``walk_key``.
    Peak memory: O(chunk·K + N·n_samples) instead of O(N·K).

    The training-block solve is a strategy solve on the *materialised*
    Φ_x, so Nyström preconditioning works here even though the full Φ is
    lazy.  ``return_diagnostics=True`` additionally returns
    (iters_used, converged) of the *actual* inner CG solve — benchmarks log
    these so silent non-convergence can't skew timings; a side solve of a
    different right-hand side would not measure the same thing."""
    strategy = _resolve(strategy, cg_tol, cg_iters)
    if strategy.preconditioner == "auto":
        # The counter-based walker RNG makes this eager trace row-identical
        # to the one the jitted impl samples.
        trace_x = walks.sample_walks_for_nodes(
            graph, train_nodes, walk_key,
            cfg.n_walkers, cfg.p_halt, cfg.l_max, cfg.reweight, cfg.scheme,
        )
        strategy = _resolve_auto(
            strategy, trace_x, f, sigma_n2, obs_mask, graph.n_nodes
        )
    with obs.span("posterior.pathwise_chunked", n_samples=n_samples,
                  chunk=chunk) as sp:
        out = _pathwise_samples_chunked(
            graph, train_nodes, f, sigma_n2, y, key, walk_key, obs_mask,
            cfg=cfg, chunk=chunk, n_samples=n_samples,
            strategy=strategy,
            spmv_backend=dispatch.get_backend(),
            obs_tap=obs.enabled(),
        )
        sp.block_on(out)
    samples, iters, converged = out
    if return_diagnostics:
        return samples, iters, converged
    return samples


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk", "n_samples", "strategy", "spmv_backend", "obs_tap",
    ),
)
def _pathwise_samples_chunked(
    graph, train_nodes, f, sigma_n2, y, key, walk_key, obs_mask,
    *, cfg, chunk, n_samples, strategy, spmv_backend, obs_tap=False,
):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend):
        trace_x = walks.sample_walks_for_nodes(
            graph, train_nodes, walk_key,
            cfg.n_walkers, cfg.p_halt, cfg.l_max, cfg.reweight, cfg.scheme,
        )
        sol, u = _pathwise_correction(
            trace_x, f, sigma_n2, y, key, graph.n_nodes, n_samples, obs_mask,
            strategy,
        )
        samples = linops.chunked_phi(graph, f, walk_key, cfg, chunk).matvec(u)
        return samples, sol.iters, jnp.all(sol.converged)


def predictive_moments_from_samples(samples: jax.Array):
    """Ensemble mean/variance over pathwise samples → scalable Eq. 3/4 proxy."""
    mean = jnp.mean(samples, axis=1)
    var = jnp.var(samples, axis=1)
    return mean, var


def posterior_moments(state, query_nodes: jax.Array):
    """*Exact* closed-form Eq. 3/4 from a serving state's cached Cholesky.

    The no-CG counterpart of :func:`predictive_moments_from_samples`: where
    the ensemble estimate carries O(1/√S) Monte-Carlo error, this returns
    the GP's exact predictive mean and variance under the GRF estimator —
    μ = K̂_{q,x}(K̂_xx+σ²I)⁻¹y and σ² = K̂_qq − K̂_{q,x}(K̂_xx+σ²I)⁻¹K̂_{x,q}
    — in O(q·m²) via two triangular solves (repro.serving.state).

    ``state`` is a :class:`repro.serving.ServeState`; build one with
    ``serving.init_state`` + ``serving.ingest`` or stream observations in
    with ``serving.observe``.  Returns (mean[q], var[q])."""
    from ..serving import state as serving_state

    return serving_state.posterior_moments(state, query_nodes)


def gaussian_nlpd(y: jax.Array, mean: jax.Array, var: jax.Array) -> jax.Array:
    """Average negative log predictive density (paper's NLPD metric)."""
    var = jnp.maximum(var, 1e-10)
    return jnp.mean(0.5 * jnp.log(2 * jnp.pi * var) + 0.5 * (y - mean) ** 2 / var)


def rmse(y: jax.Array, mean: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.mean((y - mean) ** 2))
