"""Incremental ServeState updates: Cholesky row-append / downdate / refit
(DESIGN.md §3.7).

The cost model that makes online BO serving-shaped:

  * :func:`observe` / :func:`observe_batch` — appending observation m+1 is
    one lazy walk_sample (O(K) — the only place N appears, through the graph
    arrays), one cross-Gram row (O(m·K²), kernels/gram_block), one forward
    triangular solve (O(m²)) and an O(m²) α re-solve: **O(m²) per step**
    against the O(N·√N) of a fresh pathwise fit.
  * :func:`forget` — removing observation p is a permutation-free shift plus
    a rank-1 Cholesky *update* of the trailing block (removing row p turns
    the outer product L[p+1:,p]·L[p+1:,p]ᵀ from factored into additive —
    LINPACK dchud), again O(m²).
  * :func:`refit` / :func:`ingest` — the O(m³) from-scratch refactorisation,
    used when hyperparameters change (every Gram entry moves) and as the
    parity reference the incremental paths are tested against.

All updates run on static-capacity buffers with a traced ``count``: the
dead block of the Cholesky is the identity and dead feature rows carry zero
loads, so every full-size solve/Gram is exact without dynamic shapes, and
nothing retraces as observations stream in.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from .. import obs
from ..core import features
from ..core.walks import WalkTrace
from ..kernels import dispatch
from ..resilience import faults
from .. import solvers
from ..solvers import SolveStrategy
from .state import ServeState, query_rows, solve_chol


# The jitted updates return ONLY these leaves: returning the whole state
# would make XLA copy the (unchanged, possibly 10⁶-node) graph arrays into
# fresh output buffers on every observe() — the host reattaches them.
_MUTABLE = (
    "nodes", "y", "count", "trace", "chol", "alpha",
    "overflow", "rejected", "needs_refit",
)

# Overflow handling when observe_batch would exceed capacity (eager host
# path; under an outer jit the jit-safe ``overflow`` flag is the signal).
OVERFLOW_POLICIES = ("raise", "forget_oldest", "reject")

# An append whose Schur complement is below this fraction of its prior
# scale k_nn + σ² is running on jitter: the row is near-linearly-dependent
# on the live block (duplicate/correlated observation or an injected
# fault), and the O(m³) refit fallback owns it.
_TINY_SCHUR_FRAC = 1e-5


def _pack(state: ServeState):
    return tuple(getattr(state, k) for k in _MUTABLE)


def _unpack(state: ServeState, packed) -> ServeState:
    return dataclasses.replace(state, **dict(zip(_MUTABLE, packed)))


def _factorize(vals_x, cols_x, live, sigma_n2):
    """Lower Cholesky of [K̂_xx + σ²I on live; I on dead] (block-diagonal).

    A jittered retry ladder backs the plain factorisation: when duplicate /
    near-duplicate observations make the live Gram numerically singular
    (K̂ is PSD, so exactly-dependent rows are possible), the Cholesky comes
    back NaN and we retry with escalating diagonal jitter.  ``lax.cond``
    runs at most one extra factorisation per rung at runtime, and the
    common (healthy) case pays only the finiteness check — this is the
    refit *fallback* path, never the O(m²) hot path."""
    gram = dispatch.gram_block(vals_x, cols_x, vals_x, cols_x)
    a = gram + jnp.diag(jnp.where(live > 0, sigma_n2, 1.0))
    chol = jnp.linalg.cholesky(a)
    scale = jnp.maximum(jnp.max(jnp.diagonal(a)), 1.0)
    for eps in (1e-6, 1e-4, 1e-2):
        chol = jax.lax.cond(
            jnp.all(jnp.isfinite(chol)),
            lambda c=chol: c,
            lambda e=eps: jnp.linalg.cholesky(
                a + (e * scale) * jnp.diag(live)
            ),
        )
    return chol


def _refit_impl(state: ServeState) -> ServeState:
    chol = _factorize(
        state.vals(), state.trace.cols, state.live_mask(), state.sigma_n2
    )
    return dataclasses.replace(
        state, chol=chol, alpha=solve_chol(chol, state.y),
        needs_refit=jnp.zeros_like(state.needs_refit),
    )


@dispatch.scoped(dispatch.CHOL_UPDATE_SCOPE)
def _append(state: ServeState, node, y_t) -> ServeState:
    """One *guarded* Cholesky row-append at position m = count (O(m²)).

    Three jit-safe health checks decide what the masked writes do
    (DESIGN.md §3.11); none can raise, all report through the ServeState
    flags:

      * non-finite row (NaN/Inf payload, target, or Schur complement) —
        the append is **rejected**: no write, ``rejected`` bumps.  K̂ is
        PSD by construction, so non-finites are corruption, not noise.
      * at capacity — the append is **dropped**: no write, ``overflow``
        bumps (the host wrapper's eviction policy normally prevents this).
      * near-zero Schur complement (duplicate / near-duplicate node, or an
        injected chol_fail) — the row **is written** under the jitter
        clamp so the factor stays SPD, and ``needs_refit`` bumps: the
        incremental factor is running on jitter and the host wrapper
        answers with an O(m³) refit.
    """
    idx = jnp.arange(state.capacity)
    m = state.count
    trace1 = query_rows(state, jnp.atleast_1d(node))
    vals1 = features.feature_values(trace1, state.f)
    k_vec = dispatch.gram_block(
        vals1, trace1.cols, state.vals(), state.trace.cols
    )[0]                                      # [capacity]; 0 on dead slots
    k_nn = features.khat_diag_exact(trace1, state.f)[0]
    with jax.named_scope(dispatch.CHOL_SOLVE_SCOPE):
        ell = solve_triangular(state.chol, k_vec, lower=True)
    d2 = k_nn + state.sigma_n2 - jnp.dot(ell, ell)
    d2 = faults.corrupt_schur(d2, node)       # injection site (off: no-op)
    finite = (
        jnp.isfinite(k_nn)
        & jnp.all(jnp.isfinite(k_vec))
        & jnp.isfinite(jnp.asarray(y_t, jnp.float32))
        & jnp.isfinite(d2)
    )
    over = m >= state.capacity
    tiny = d2 <= _TINY_SCHUR_FRAC * (k_nn + state.sigma_n2)
    write = finite & ~over
    # Jitter clamp relative to the row's own scale: an absolute floor would
    # be meaningless off the unit-diagonal regime, and too small a pivot
    # overflows f32 triangular solves when tiny pivots chain.
    d = jnp.sqrt(jnp.maximum(d2, _TINY_SCHUR_FRAC * (k_nn + state.sigma_n2)))
    row = jnp.where(idx < m, ell, 0.0)
    row = jnp.where(idx == m, d, row)
    sel = (idx == m) & write
    one = jnp.asarray(1, jnp.int32)
    zero = jnp.asarray(0, jnp.int32)
    return dataclasses.replace(
        state,
        nodes=jnp.where(sel, node, state.nodes),
        y=jnp.where(sel, y_t, state.y),
        count=m + jnp.where(write, one, zero),
        trace=WalkTrace(
            cols=jnp.where(sel[:, None], trace1.cols[0], state.trace.cols),
            loads=jnp.where(sel[:, None], trace1.loads[0], state.trace.loads),
            lens=jnp.where(sel[:, None], trace1.lens[0], state.trace.lens),
        ),
        chol=jnp.where(sel[:, None], row[None, :], state.chol),
        overflow=state.overflow + jnp.where(finite & over, one, zero),
        rejected=state.rejected + jnp.where(finite, zero, one),
        needs_refit=state.needs_refit + jnp.where(write & tiny, one, zero),
    )


def _observe_batch_impl(graph, f, sigma_n2, seed, packed, nodes, ys, *, cfg,
                        spmv_backend, obs_tap=False, fault_plan=None):
    # The immutable leaves (graph / f / sigma_n2 / seed) ride as separate
    # arguments so the mutable leaves can be donated as one pytree arg:
    # donating a buffer that is *also* reachable through a non-donated
    # argument is undefined, and the state pytree would alias both.
    state = ServeState(
        graph=graph, f=f, sigma_n2=sigma_n2, seed=seed, cfg=cfg,
        **dict(zip(_MUTABLE, packed)),
    )
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(fault_plan):
        # Scan only over the mutable leaves — the graph arrays stay scan
        # *constants* instead of riding the loop carry (at 10⁶ nodes the
        # adjacency is far larger than the whole serving state).
        def step(carry, xy):
            st = dataclasses.replace(
                state, nodes=carry[0], y=carry[1], count=carry[2],
                trace=WalkTrace(*carry[3]), chol=carry[4],
                overflow=carry[5], rejected=carry[6], needs_refit=carry[7],
            )
            st = _append(st, xy[0], xy[1])
            return (
                st.nodes, st.y, st.count,
                (st.trace.cols, st.trace.loads, st.trace.lens), st.chol,
                st.overflow, st.rejected, st.needs_refit,
            ), None

        init = (
            state.nodes, state.y, state.count,
            (state.trace.cols, state.trace.loads, state.trace.lens),
            state.chol,
            state.overflow, state.rejected, state.needs_refit,
        )
        (nodes_b, y_b, count, tr, chol, ov, rej, nrf), _ = jax.lax.scan(
            step, init, (nodes, ys)
        )
        return (nodes_b, y_b, count, WalkTrace(*tr), chol,
                solve_chol(chol, y_b), ov, rej, nrf)


_OB_STATICS = ("cfg", "spmv_backend", "obs_tap", "fault_plan")
_observe_batch = partial(jax.jit, static_argnames=_OB_STATICS)(
    _observe_batch_impl
)
# Donating the mutable leaves lets XLA update the O(capacity²) Cholesky and
# the ELL rows in place instead of reallocating them per append — after a
# call the *input* buffers are deleted, so only opt-in async callers
# (observe_batch_async / GPFleetLoop) use this variant.
_observe_batch_donated = partial(
    jax.jit, static_argnames=_OB_STATICS, donate_argnums=(4,)
)(_observe_batch_impl)


def _evict_oldest(state: ServeState, room: int) -> ServeState:
    """Make ``room`` slots by forgetting the oldest live observations —
    O(room·m²) rank-1 downdates, no refactorisation."""
    for _ in range(min(room, int(state.count))):
        state = forget(state, 0)
    return state


def observe_batch(
    state: ServeState,
    nodes,
    ys,
    *,
    on_overflow: str = "raise",
    auto_refit: bool = True,
) -> ServeState:
    """Append a batch of observations by sequential *guarded* Cholesky
    row-appends.

    α is re-solved once at the end (two O(m²) triangular solves).  Static
    shapes cannot grow, so ``on_overflow`` picks the degradation when the
    batch would exceed capacity (checkable only when ``count`` is
    concrete — under an outer jit every policy degrades to the jit-safe
    masked drop, reported via ``state.overflow``):

      * ``"raise"`` (default, the historical contract) — ValueError before
        touching the state;
      * ``"forget_oldest"`` — evict the oldest observations (rank-1
        downdates) to make room, then append everything;
      * ``"reject"`` — append until full, drop the excess, bump
        ``state.overflow`` / the ``serving.observe.overflow`` counter
        (reject-with-backpressure: the caller sees the flag and backs off).

    Appends with non-finite payloads/targets are rejected row-wise
    (``state.rejected``); near-singular appends are jitter-clamped and,
    with ``auto_refit=True``, answered by an automatic O(m³) :func:`refit`
    fallback (``serving.refit.fallback`` counter) so the returned factor
    never runs on jitter."""
    if on_overflow not in OVERFLOW_POLICIES:
        raise ValueError(
            f"unknown on_overflow {on_overflow!r}; valid: {OVERFLOW_POLICIES}"
        )
    nodes = jnp.asarray(nodes, jnp.int32).reshape(-1)
    ys = jnp.asarray(ys, jnp.float32).reshape(-1)
    eager = not isinstance(state.count, jax.core.Tracer)
    if eager:
        excess = int(state.count) + nodes.shape[0] - state.capacity
        if excess > 0:
            if on_overflow == "raise":
                raise ValueError(
                    f"observing {nodes.shape[0]} more would exceed serving "
                    f"capacity {state.capacity} (count={int(state.count)}); "
                    "build the state with a larger capacity, or pass "
                    "on_overflow='forget_oldest'/'reject' to degrade "
                    "gracefully"
                )
            if on_overflow == "forget_oldest":
                with obs.span("serving.evict", n=excess):
                    state = _evict_oldest(state, excess)
                obs.inc("serving.observe.evictions", excess)
    with obs.span("serving.observe_batch", n=int(nodes.shape[0])) as sp:
        packed = _observe_batch(
            state.graph, state.f, state.sigma_n2, state.seed, _pack(state),
            nodes, ys, cfg=state.cfg, spmv_backend=dispatch.get_backend(),
            obs_tap=obs.enabled(), fault_plan=faults.active(),
        )
        sp.block_on(packed)
    new = _unpack(state, packed)
    if eager:
        dropped = int(new.overflow) - int(state.overflow)
        if dropped:
            obs.inc("serving.observe.overflow", dropped)
        rej = int(new.rejected) - int(state.rejected)
        if rej:
            obs.inc("serving.observe.rejected", rej)
        if auto_refit and int(new.needs_refit) > 0:
            # The incremental factor is running on jitter (near-singular
            # append detected) — answer with the O(m³) refactorisation,
            # which also resets the flag.
            obs.inc("serving.refit.fallback")
            new = refit(new)
    return new


def observe(state: ServeState, node, y, **kwargs) -> ServeState:
    """Append one observation: O(m²), no CG, nothing N-scale."""
    return observe_batch(state, [node], [y], **kwargs)


def observe_batch_async(state: ServeState, nodes, ys, *,
                        donate: bool = True) -> ServeState:
    """Dispatch a guarded batched append with **no host synchronisation**.

    The fleet's mutation path (DESIGN.md §3.12): the eager
    :func:`observe_batch` wrapper costs one ``block_on`` plus several
    ``int(flag)`` device reads per call — each a full sync barrier that
    serialises the wave pipeline.  This variant returns as soon as the
    update is dispatched; overflow behaves like ``on_overflow="reject"``
    (masked drops reported via the jit-safe ``overflow`` flag) and the
    caller inspects the health flags later, at a point where it blocks
    anyway (``GPFleetLoop._check_flags``).

    With ``donate=True`` the mutable leaves are donated to XLA, so the
    O(capacity²) Cholesky and the cached ELL rows are updated in place
    instead of reallocated per call.  **The input state's mutable buffers
    are deleted after a donated call** — drop every reference to the old
    state and use the returned one (the fleet owns its state for exactly
    this reason)."""
    nodes = jnp.asarray(nodes, jnp.int32).reshape(-1)
    ys = jnp.asarray(ys, jnp.float32).reshape(-1)
    fn = _observe_batch_donated if donate else _observe_batch
    packed = fn(
        state.graph, state.f, state.sigma_n2, state.seed, _pack(state),
        nodes, ys, cfg=state.cfg, spmv_backend=dispatch.get_backend(),
        obs_tap=obs.enabled(), fault_plan=faults.active(),
    )
    return _unpack(state, packed)


def _cholupdate(chol: jax.Array, x: jax.Array) -> jax.Array:
    """L̃ with L̃L̃ᵀ = LLᵀ + xxᵀ (LINPACK dchud, columns swept in order).

    Columns where x has already been rotated to zero are no-ops (cos=1,
    sin=0), so a zero-padded x updates only the trailing block — exactly
    the forget() shift pattern.  Dead diagonal entries are 1, never 0."""
    idx = jnp.arange(chol.shape[0])

    def body(k, carry):
        ell, x = carry
        lkk, xk = ell[k, k], x[k]
        r = jnp.sqrt(lkk * lkk + xk * xk)
        cos, sin = r / lkk, xk / lkk
        below = idx > k
        col = ell[:, k]
        newcol = jnp.where(below, (col + sin * x) / cos, col).at[k].set(r)
        x = jnp.where(below, cos * x - sin * newcol, x)
        return ell.at[:, k].set(newcol), x

    chol, _ = jax.lax.fori_loop(0, chol.shape[0], body, (chol, x))
    return chol


@dispatch.scoped(dispatch.CHOL_UPDATE_SCOPE)
def _forget_step(packed, slot):
    """One downdate on the packed mutable leaves, α left stale.

    The α re-solve is deferred to the caller: forget never *reads* α, so
    in a run of k forgets the k−1 intermediate solves are unobservable —
    batching them away is bit-identical to sequential application."""
    nodes, y, count, trace, chol, alpha, overflow, rejected, needs_refit = \
        packed
    c = chol.shape[0]
    idx = jnp.arange(c)
    # Shift everything after `slot` up one position (dead fill at the top).
    src = jnp.where(idx >= slot, jnp.minimum(idx + 1, c - 1), idx)
    # Removing row/col `slot` de-factors its outer product: the trailing
    # block satisfies L̃L̃ᵀ = L'L'ᵀ + SSᵀ with S = L[slot+1:, slot].
    x = jnp.where(idx >= slot, chol[:, slot][src], 0.0)
    new_chol = _cholupdate(chol[src][:, src], x)
    new_count = count - 1
    dead = idx >= new_count
    new_chol = jnp.where(
        dead[:, None] | dead[None, :], jnp.eye(c, dtype=new_chol.dtype),
        new_chol,
    )
    live = ~dead
    return (
        jnp.where(live, nodes[src], 0),
        jnp.where(live, y[src], 0.0),
        new_count,
        WalkTrace(
            cols=jnp.where(live[:, None], trace.cols[src], 0),
            loads=jnp.where(live[:, None], trace.loads[src], 0.0),
            lens=jnp.where(live[:, None], trace.lens[src], 0),
        ),
        new_chol,
        alpha,
        overflow,
        rejected,
        needs_refit,
    )


def _resolve_alpha(packed):
    nodes, y, count, trace, chol, _, overflow, rejected, needs_refit = packed
    return (nodes, y, count, trace, chol, solve_chol(chol, y),
            overflow, rejected, needs_refit)


@jax.jit
def _forget(state: ServeState, slot):
    return _resolve_alpha(_forget_step(_pack(state), slot))


def _forget_batch_impl(packed, slots):
    out, _ = jax.lax.scan(
        lambda mut, s: (_forget_step(mut, s), None), packed, slots
    )
    return _resolve_alpha(out)


_forget_batch = jax.jit(_forget_batch_impl)
_forget_batch_donated = partial(jax.jit, donate_argnums=(0,))(
    _forget_batch_impl
)


def forget(state: ServeState, slot) -> ServeState:
    """Remove the observation in buffer position ``slot`` (0 ≤ slot < count).

    Rank-1 Cholesky downdate of the stored factor — O(m²), no
    refactorisation.  Later observations shift up one slot."""
    return _unpack(state, _forget(state, jnp.asarray(slot, jnp.int32)))


def forget_batch(state: ServeState, slots) -> ServeState:
    """Apply a sequence of forgets in ONE scanned dispatch (O(k·m²)).

    Bit-identical to folding :func:`forget` over ``slots`` — each step is
    the same shift + rank-1 downdate, with the single observable α re-solve
    done once at the end.  Slot indices are interpreted sequentially, i.e.
    against the buffer layout *after* the preceding forgets in the batch
    (``[0, 0]`` drops the two oldest observations)."""
    return _unpack(state, _forget_batch(
        _pack(state), jnp.asarray(slots, jnp.int32).reshape(-1)
    ))


def forget_batch_async(state: ServeState, slots, *,
                       donate: bool = True) -> ServeState:
    """:func:`forget_batch` without host synchronisation, mutable leaves
    donated — the fleet's forget path (one dispatch per run of queued
    forgets instead of one per slot).  Same donation contract as
    :func:`observe_batch_async`: the input state's mutable buffers are
    deleted; use the returned state."""
    fn = _forget_batch_donated if donate else _forget_batch
    return _unpack(state, fn(
        _pack(state), jnp.asarray(slots, jnp.int32).reshape(-1)
    ))


@partial(jax.jit, static_argnames=("spmv_backend", "obs_tap"))
def _ingest(state, nodes, ys, count, *, spmv_backend, obs_tap=False):
    # fault_scope(None): ingest is the from-scratch parity reference — a
    # corrupted bulk load has no incremental guard to catch it, so the
    # injection hooks are pinned off here (and ambient REPRO_FAULTS can
    # never leak into this trace's cache entry).
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(None):
        trace = query_rows(state, nodes)
        live = jnp.arange(state.capacity) < count
        state = dataclasses.replace(
            state,
            nodes=jnp.where(live, nodes, 0),
            y=jnp.where(live, ys, 0.0),
            count=count,
            trace=WalkTrace(
                cols=trace.cols,
                loads=trace.loads * live[:, None],
                lens=trace.lens,
            ),
        )
        return _pack(_refit_impl(state))


def ingest(state: ServeState, nodes, ys) -> ServeState:
    """Replace the whole observation set and refactorise once (O(m³)).

    The from-scratch entry point: BO init sets, hyperparameter refits that
    also change the data, and the parity reference for the incremental
    appends."""
    nodes = jnp.asarray(nodes, jnp.int32).reshape(-1)
    ys = jnp.asarray(ys, jnp.float32).reshape(-1)
    count = nodes.shape[0]
    if count > state.capacity:
        raise ValueError(
            f"{count} observations exceed serving capacity {state.capacity}"
        )
    pad = state.capacity - count
    with obs.span("serving.ingest", n=count) as sp:
        packed = _ingest(
            state,
            jnp.pad(nodes, (0, pad)),
            jnp.pad(ys, (0, pad)),
            jnp.asarray(count, jnp.int32),
            spmv_backend=dispatch.get_backend(),
            obs_tap=obs.enabled(),
        )
        sp.block_on(packed)
    return _unpack(state, packed)


@partial(jax.jit, static_argnames=("spmv_backend", "obs_tap"))
def _refit(state, *, spmv_backend, obs_tap=False):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend):
        return _pack(_refit_impl(state))


def refit(state: ServeState, f=None, sigma_n2=None, y=None) -> ServeState:
    """From-scratch refactorisation of the live block (O(m³)).

    Use after hyperparameter updates (new ``f``/``sigma_n2`` move every Gram
    entry, so the incremental factor is stale) or to swap the target buffer
    ``y`` (full-capacity array, dead slots zero).  The cached walk rows are
    structure-only and do not depend on ``f`` — nothing is re-sampled."""
    updates = {}
    if f is not None:
        updates["f"] = jnp.asarray(f, jnp.float32)
    if sigma_n2 is not None:
        updates["sigma_n2"] = jnp.asarray(sigma_n2, jnp.float32)
    if y is not None:
        updates["y"] = jnp.asarray(y, jnp.float32)
    if updates:
        state = dataclasses.replace(state, **updates)
    with obs.span("serving.refit") as sp:
        packed = _refit(state, spmv_backend=dispatch.get_backend(),
                        obs_tap=obs.enabled())
        sp.block_on(packed)
    return _unpack(state, packed)


# ---------------------------------------------------------------------------
# Mean-serving fast refit: warm-started strategy solve, no refactorisation.
# ---------------------------------------------------------------------------


def _refit_alpha_impl(state, alpha0, *, strategy, spmv_backend,
                      obs_tap=False):
    # ``alpha0`` rides as its own argument — the wrapper stubs the state's
    # alpha leaf to a length-0 placeholder — so the donated variant can
    # alias the warm-start iterate into the solution buffer without the
    # same buffer also being reachable through the state pytree.
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend):
        live = state.live_mask()
        gram = dispatch.gram_block(
            state.vals(), state.trace.cols, state.vals(), state.trace.cols
        )
        noise = jnp.where(live > 0, state.sigma_n2, 1.0)
        a = gram + jnp.diag(noise)
        sol = solvers.solve(
            a.__matmul__, state.y, strategy, x0=alpha0,
            precond=None if strategy.preconditioner == "none"
            else solvers.jacobi_precond(jnp.diagonal(a)),
        )
        return sol.x, sol.iters, jnp.all(sol.converged)


_RA_STATICS = ("strategy", "spmv_backend", "obs_tap")
_refit_alpha = partial(jax.jit, static_argnames=_RA_STATICS)(
    _refit_alpha_impl
)
_refit_alpha_donated = partial(
    jax.jit, static_argnames=_RA_STATICS, donate_argnums=(1,)
)(_refit_alpha_impl)


def _alpha_ladder(strategy: SolveStrategy) -> list[SolveStrategy]:
    """The dense-Gram escalation rungs for :func:`refit_alpha` — the
    subset of :func:`repro.solvers.escalation_ladder` that applies to an
    m×m serving system (no trace rows, so no Nyström rung): stronger
    preconditioning first, then iteration budget, warm-started throughout
    (each attempt resumes from the best iterate so far)."""
    rungs = [strategy]
    s = strategy
    if s.preconditioner == "none":
        s = s.with_(preconditioner="jacobi", warm_start=True)
        rungs.append(s)
    for _ in range(2):
        s = s.with_(max_iters=s.max_iters * 4, warm_start=True)
        rungs.append(s)
    return rungs


def refit_alpha(
    state: ServeState,
    f=None,
    sigma_n2=None,
    strategy: SolveStrategy | None = None,
    return_diagnostics: bool = False,
    escalate: bool = False,
    max_attempts: int = 3,
    donate: bool = False,
) -> ServeState:
    """Refresh the representer weights α after a hyperparameter move —
    **without** the O(m³) Cholesky refactorisation.

    A warm-started strategy solve (repro.solvers) of the fresh
    A(θ_new) α = y starting from the stale α: hyperparameter drift moves A
    little, so the solve converges in the handful of iterations the
    *difference* needs — O(m²·iters) against refit's O(m³).

    This is the **mean-serving fast path**: only ``alpha`` is refreshed.
    The cached Cholesky still factorises the *old* A, so variance queries
    (``posterior_moments``' second moment, ``thompson_draw``) need a full
    :func:`refit` — use this when the serving tier answers means
    (``alpha``-only reads) between scheduled refactorisations.

    With ``escalate=True`` a non-converged solve retries up to
    ``max_attempts`` times along :func:`_alpha_ladder` (stronger
    preconditioner, then 4× iteration budgets, warm-started from the best
    iterate), emitting ``solver.escalation`` obs events per attempt — the
    serving-side twin of ``solvers.solve(..., escalate=True)``.

    With ``donate=True`` each rung donates its warm-start iterate to the
    solve (the previous α buffer is reused for the new one instead of
    reallocated).  **This deletes the caller's ``state.alpha`` buffer** —
    only use it when the input state is discarded for the returned one,
    as the fleet and the benchmarks do."""
    if strategy is None:
        strategy = solvers.SERVING_DEFAULT
    if strategy.preconditioner == "auto":
        # Dense m×m serving Gram: no trace rows to pivot, so auto's only
        # candidate is the (prebuilt) Jacobi diagonal.
        strategy = strategy.with_(preconditioner="jacobi")
    if strategy.preconditioner == "nystrom":
        # The serving system is a dense m×m Gram, not a trace-backed
        # ShiftedOperator — there are no pivot rows to build Nyström from.
        # Raise rather than silently degrading to Jacobi.
        raise ValueError(
            "refit_alpha supports preconditioner 'none' or 'jacobi'; the "
            "dense serving Gram has no trace rows for 'nystrom'"
        )
    updates = {}
    if f is not None:
        updates["f"] = jnp.asarray(f, jnp.float32)
    if sigma_n2 is not None:
        updates["sigma_n2"] = jnp.asarray(sigma_n2, jnp.float32)
    if updates:
        state = dataclasses.replace(state, **updates)
    rungs = _alpha_ladder(strategy) if escalate else [strategy]
    rungs = rungs[:max_attempts] if escalate else rungs
    fn = _refit_alpha_donated if donate else _refit_alpha
    with obs.span("serving.refit_alpha") as sp:
        alpha = state.alpha
        st = dataclasses.replace(state, alpha=jnp.zeros((0,), jnp.float32))
        for attempt, s in enumerate(rungs):
            alpha, iters, converged = fn(
                st, alpha, strategy=s, spmv_backend=dispatch.get_backend(),
                obs_tap=obs.enabled(),
            )
            if not escalate:
                break
            stalled = faults.should_stall(attempt)
            ok = bool(converged) and not stalled
            obs.emit_event({
                "type": "solver.escalation", "site": "serving.refit_alpha",
                "attempt": attempt, "converged": ok,
                "forced_stall": stalled, "max_iters": s.max_iters,
                "preconditioner": s.preconditioner,
            })
            obs.inc("solver.escalation.attempts")
            if stalled:
                obs.inc("solver.escalation.forced_stalls")
            if ok:
                if attempt > 0:
                    obs.inc("solver.escalation.resolved")
                break
        else:
            obs.inc("solver.escalation.exhausted")
        sp.block_on(alpha)
    state = dataclasses.replace(state, alpha=alpha)
    if return_diagnostics:
        return state, iters, converged
    return state
