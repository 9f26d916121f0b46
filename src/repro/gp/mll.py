"""Hyperparameter learning by iterative log-marginal-likelihood ascent
(paper §3.2, Eq. 8–11).

The gradient Eq. 9 is produced by autodiff of a *surrogate* objective built
from stop-gradded CG solves:

    s(θ) = −½ sg(v_y)ᵀ H(θ) sg(v_y) + ½·mean_s sg(v_s)ᵀ H(θ) z_s ,
    v_y = H⁻¹ y,  v_s = H⁻¹ z_s  (z_s Rademacher probes, Eq. 10)

so ∇s = −½ v_yᵀ H'v_y + ½·mean_s v_sᵀ H'z_s = ∇(−L)  (Hutchinson estimate).
All solves are CG on the sparse K̂ (Lemma 1: O(N^{3/2})) routed through the
strategy layer (repro.solvers — DESIGN.md §3.8):

  * warm starts: consecutive Adam steps solve nearly-identical systems, so
    ``_fit_chunk`` carries the solution block [v_y, v_z] in its scan state
    and reuses it as ``x0`` (probes are frozen per chunk so v_z stays a
    valid start — Hutchinson remains unbiased over the per-chunk draw);
  * the actual LML *value* (not just its gradient) comes from
    :func:`exact_lml`, which pairs a strategy solve for yᵀH⁻¹y with
    stochastic Lanczos quadrature (solvers/slq.py) for log det H.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import linops
from ..core.modulation import Modulation
from ..kernels import dispatch as _dispatch
from ..core.walks import WalkTrace
from ..optim.adamw import AdamW
from .. import solvers
from ..solvers import SolveStrategy


def init_hyperparams(mod: Modulation, key: jax.Array, init_noise: float = 0.1) -> dict:
    return {
        "mod": mod.init(key),
        "log_sigma_n": jnp.log(jnp.asarray(init_noise, jnp.float32)),
    }


def noise_var(params: dict) -> jax.Array:
    return jnp.exp(2.0 * params["log_sigma_n"])


def make_h_operator(
    trace_x: WalkTrace, f: jax.Array, sigma_n2: jax.Array, n_nodes: int
) -> linops.ShiftedOperator:
    """H = K̂_xx + D as a backend-dispatched operator (Eq. 7 remark).

    ``sigma_n2`` may be a scalar (σ_n² I) or a [T] vector (heteroscedastic
    diagonal — used by the BO loop's static-shape padding, where padded
    observation slots carry ~infinite noise and therefore no information)."""
    return linops.shifted(trace_x, f, sigma_n2, n_nodes)


def make_h_matvec(
    trace_x: WalkTrace, f: jax.Array, sigma_n2: jax.Array, n_nodes: int
) -> Callable:
    """Callable view of :func:`make_h_operator` (operators are callable)."""
    return make_h_operator(trace_x, f, sigma_n2, n_nodes)


def mll_surrogate_loss(
    params: dict,
    key: jax.Array,
    trace_x: WalkTrace,
    mod: Modulation,
    y: jax.Array,
    n_nodes: int,
    n_probes: int = 8,
    cg_tol: float | None = None,
    cg_iters: int | None = None,
    obs_mask: jax.Array | None = None,
    strategy: SolveStrategy | None = None,
    probes: jax.Array | None = None,
    x0: jax.Array | None = None,
):
    """Returns (surrogate_loss, aux).  ∇ surrogate == ∇ negative-LML (est.).

    ``obs_mask``: optional float [T] with 1 for live observations, 0 for
    static-shape padding slots (padding gets ~infinite noise, zero probes).
    ``strategy`` routes the inner solve (``cg_tol``/``cg_iters`` remain as
    legacy overrides folded into it); ``probes`` fixes the Rademacher block
    z and ``x0`` warm-starts the solve — together they let ``_fit_chunk``
    carry [v_y, v_z] across Adam steps.  aux["v"] is the (stop-gradded)
    solution block to carry."""
    if strategy is None:
        strategy = solvers.MLL_DEFAULT.with_(warm_start=x0 is not None)
    strategy = strategy.with_overrides(tol=cg_tol, max_iters=cg_iters)
    f = mod(params["mod"])
    sigma_n2_scalar = noise_var(params)
    sigma_n2 = sigma_n2_scalar
    t = y.shape[0]
    if obs_mask is not None:
        sigma_n2 = jnp.where(obs_mask > 0, sigma_n2, 1e6)
        y = y * obs_mask

    if probes is None:
        probes = solvers.rademacher(key, (t, n_probes), y.dtype)
    z = probes
    if obs_mask is not None:
        z = z * obs_mask[:, None]
    b = jnp.concatenate([y[:, None], z], axis=1)

    f_sg = jax.lax.stop_gradient(f)
    s2_sg = jax.lax.stop_gradient(sigma_n2)
    h_sg = make_h_operator(trace_x, f_sg, s2_sg, n_nodes)
    sol = solvers.solve(h_sg, b, strategy, x0=x0)
    v = jax.lax.stop_gradient(sol.x)
    v_y, v_z = v[:, 0], v[:, 1:]

    h = make_h_operator(trace_x, f, sigma_n2, n_nodes)
    hv_y = h.matvec(v_y)
    hz = h.matvec(z)
    term_fit = -0.5 * jnp.dot(v_y, hv_y)
    term_tr = 0.5 * jnp.mean(jnp.sum(v_z * hz, axis=0))
    loss = term_fit + term_tr
    aux = {
        "datafit": 0.5 * jnp.dot(y, v_y),       # ½ yᵀH⁻¹y (true value)
        "cg_iters": sol.iters,
        "cg_resnorm": jnp.max(sol.resnorm),
        "cg_converged": jnp.all(sol.converged),
        "sigma_n2": sigma_n2_scalar,
        "v": v,
    }
    return loss, aux


@dataclasses.dataclass
class FitResult:
    params: dict
    history: list


@partial(
    jax.jit,
    static_argnames=(
        "mod", "opt", "n_nodes", "n_probes", "strategy", "chunk",
        "spmv_backend", "obs_tap",
    ),
)
def _fit_chunk(
    params, opt_state, key, trace_x, y, obs_mask, v0,
    *, mod, opt, n_nodes, n_probes, strategy, chunk, spmv_backend,
    obs_tap=False,
):
    """``chunk`` Adam steps fused into one lax.scan (single dispatch/compile).

    Module-level + hashable statics ⇒ the executable is cached across
    repeated fits (critical for the BO loop, which refits every few steps).
    ``spmv_backend`` and ``strategy`` are resolved by the caller: both shape
    the traced computation, so they must participate in the jit cache key.

    Warm starts: when ``strategy.warm_start`` the scan carry includes the
    previous step's solution block v = [v_y, v_z] (fed back as ``x0``) and
    the Rademacher probes are drawn ONCE per chunk — Hutchinson stays
    unbiased over the per-chunk draw while v_z remains a valid start for
    the next step's (same-z, slightly-moved-H) system.  Across chunk
    boundaries the probes are redrawn, so the incoming carry's probe
    columns solve the *previous* chunk's systems — they are reset to a
    cold start here (the v_y column stays: y never changes)."""
    warm = strategy.warm_start
    probes = None
    if warm:
        probes = solvers.rademacher(key, (y.shape[0], n_probes), y.dtype)
        v0 = jnp.concatenate(
            [v0[:, :1], jnp.zeros_like(v0[:, 1:])], axis=1
        )

    def one(carry, key_i):
        p, s, v_prev = carry
        (loss, aux), grads = jax.value_and_grad(
            mll_surrogate_loss, has_aux=True
        )(
            p, key_i, trace_x, mod, y, n_nodes,
            n_probes=n_probes, obs_mask=obs_mask, strategy=strategy,
            probes=probes, x0=v_prev if warm else None,
        )
        p, s = opt.update(grads, s, p)
        return (p, s, aux["v"]), (
            loss, aux["datafit"], aux["sigma_n2"], aux["cg_iters"],
            aux["cg_converged"],
        )

    keys = jax.random.split(key, chunk)
    with obs.tap_scope(obs_tap), _dispatch.use_backend(spmv_backend):
        (params, opt_state, v), traces = jax.lax.scan(
            one, (params, opt_state, v0), keys
        )
    return params, opt_state, v, traces


def fit_hyperparams(
    trace_x: WalkTrace,
    mod: Modulation,
    y: jax.Array,
    n_nodes: int,
    key: jax.Array,
    steps: int = 100,
    lr: float = 0.05,
    n_probes: int = 8,
    cg_tol: float | None = None,
    cg_iters: int | None = None,
    init_params: dict | None = None,
    init_noise: float = 0.1,
    obs_mask: jax.Array | None = None,
    chunk: int = 10,
    strategy: SolveStrategy | None = None,
) -> FitResult:
    """Adam ascent on the LML (paper §3.2 'hyperparameter learning').

    ``strategy`` defaults to the cold-started ``solvers.MLL_DEFAULT`` shape
    with ``cg_tol``/``cg_iters`` folded in; pass
    ``solvers.MLL_DEFAULT`` (``warm_start=True``) to carry [v_y, v_z]
    across Adam steps — the BO refit loops do (≥1.5× fewer total CG
    iterations over a 50-step fit, BENCH_solvers.json).

    ``FitResult.history`` records EVERY step (loss, datafit, σ_n², CG
    iterations and convergence) — not just the last step of each chunk."""
    if strategy is None:
        strategy = solvers.MLL_DEFAULT.with_(warm_start=False)
    strategy = strategy.with_overrides(tol=cg_tol, max_iters=cg_iters)
    k_init, k_loop = jax.random.split(key)
    # `init_params or ...` would silently discard a legitimate empty dict.
    if init_params is None:
        init_params = init_hyperparams(mod, k_init, init_noise)
    params = init_params
    opt = AdamW(lr=lr)
    opt_state = opt.init(params)
    if obs_mask is None:
        obs_mask = jnp.ones_like(y)
    if strategy.preconditioner == "auto":
        # Resolve on the initial hyperparameters, eagerly — inside
        # _fit_chunk's trace the probe can't run and auto would silently
        # degrade to Jacobi.  The measured rank is reused for every step
        # (H only drifts by hyperparameter updates between steps).
        f0 = mod(params["mod"])
        s2 = jnp.where(obs_mask > 0, noise_var(params), 1e6)
        strategy = solvers.resolve_strategy(
            make_h_operator(trace_x, f0, s2, n_nodes), strategy, key=k_init
        )
    v = jnp.zeros((y.shape[0], 1 + n_probes), jnp.float32)

    history = []
    done = 0
    while done < steps:
        this = min(chunk, steps - done)
        with obs.span("mll.fit_chunk", steps=this) as sp:
            params, opt_state, v, traces = _fit_chunk(
                params, opt_state, jax.random.fold_in(k_loop, done),
                trace_x, y, obs_mask, v,
                mod=mod, opt=opt, n_nodes=n_nodes, n_probes=n_probes,
                strategy=strategy, chunk=this,
                spmv_backend=_dispatch.get_backend(),
                obs_tap=obs.enabled(),
            )
            sp.block_on(traces)
        loss_t, fit_t, s2_t, iters_t, conv_t = (
            np.asarray(t) for t in traces
        )
        for j in range(this):
            rec = {"step": done + j + 1, "loss": float(loss_t[j]),
                   "datafit": float(fit_t[j]), "sigma_n2": float(s2_t[j]),
                   "cg_iters": int(iters_t[j]),
                   "cg_converged": bool(conv_t[j])}
            history.append(rec)
            # Per-step diagnostics go to the flight record, not only into
            # the returned history array.
            obs.emit_event({"type": "fit_step", **rec})
        done += this
    return FitResult(params=params, history=history)


# ---------------------------------------------------------------------------
# Exact LML values (SLQ log-det) — the quantity the surrogate only
# differentiates.
# ---------------------------------------------------------------------------


def exact_lml(
    trace_x: WalkTrace,
    f: jax.Array,
    sigma_n2: jax.Array,
    y: jax.Array,
    n_nodes: int,
    key: jax.Array,
    strategy: SolveStrategy | None = None,
    n_probes: int = 32,
    slq_iters: int = 64,
    obs_mask: jax.Array | None = None,
):
    """log p(y | θ) = −½ yᵀH⁻¹y − ½ log det H − (T/2) log 2π  (Eq. 8).

    The quadratic term is a strategy solve; the log-det is stochastic
    Lanczos quadrature over the CG recurrence (solvers/slq.py) — no dense
    factorisation, O(n_probes · slq_iters) sparse matvecs.  With
    ``obs_mask`` the operator takes the masked-sandwich form M K̂ M + D with
    unit noise on dead slots, so dead rows contribute *exactly* zero to the
    log-det and the result is the live-block LML.

    Returns a dict with ``lml``, ``datafit`` (½yᵀH⁻¹y), ``logdet`` and the
    solve's ``converged`` flag (an unconverged quadratic term means the lml
    value is untrustworthy — surface it, don't average over it)."""
    if strategy is None:
        strategy = solvers.MLL_DEFAULT.with_(warm_start=False)
    if strategy.preconditioner == "auto":
        if obs_mask is None:
            h0 = make_h_operator(trace_x, f, sigma_n2, n_nodes)
        else:
            h0 = linops.ShiftedOperator(
                linops.khat(trace_x, f, n_nodes),
                jnp.where(obs_mask > 0, sigma_n2, 1.0), mask=obs_mask,
            )
        strategy = solvers.resolve_strategy(h0, strategy, key=key)
    with obs.span("mll.exact_lml") as sp:
        out = _exact_lml(
            trace_x, f, sigma_n2, y, obs_mask, key,
            strategy=strategy, n_probes=n_probes, slq_iters=slq_iters,
            n_nodes=n_nodes, spmv_backend=_dispatch.get_backend(),
            obs_tap=obs.enabled(),
        )
        sp.block_on(out)
    return out


@partial(
    jax.jit,
    static_argnames=(
        "strategy", "n_probes", "slq_iters", "n_nodes", "spmv_backend",
        "obs_tap",
    ),
)
def _exact_lml(
    trace_x, f, sigma_n2, y, obs_mask, key,
    *, strategy, n_probes, slq_iters, n_nodes, spmv_backend, obs_tap=False,
):
    with obs.tap_scope(obs_tap), _dispatch.use_backend(spmv_backend):
        t = y.shape[0]
        if obs_mask is None:
            t_live = jnp.asarray(t, jnp.float32)
            h = make_h_operator(trace_x, f, sigma_n2, n_nodes)
        else:
            t_live = jnp.sum(obs_mask)
            y = y * obs_mask
            # Unit noise outside the mask: dead rows of M K̂ M + D are
            # exactly e_i, so log det H == log det of the live block.
            noise = jnp.where(obs_mask > 0, sigma_n2, 1.0)
            h = linops.ShiftedOperator(
                linops.khat(trace_x, f, n_nodes), noise, mask=obs_mask
            )
        sol = solvers.solve(h, y, strategy)
        datafit = 0.5 * jnp.dot(y, sol.x)
        logdet = solvers.slq_logdet(
            h, t, key, n_probes=n_probes, n_iters=slq_iters
        )
        lml = -datafit - 0.5 * logdet - 0.5 * t_live * jnp.log(2.0 * jnp.pi)
        return {
            "lml": lml,
            "datafit": datafit,
            "logdet": logdet,
            "converged": jnp.all(sol.converged),
        }
