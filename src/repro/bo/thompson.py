"""Graph Thompson sampling with GRF-GPs (paper §4.3, Alg. 3).

Each BO iteration: (re)fit hyperparameters on the observation set (warm
started), draw one pathwise-conditioned posterior sample over all N nodes
(Eq. 12 — O(N^{3/2})), query the argmax among unobserved nodes.

Static shapes: observations live in a preallocated [n_init + n_steps] buffer
with an ``obs_mask``; padded slots carry ~infinite noise — the per-row
noise-vector form of :class:`repro.core.linops.ShiftedOperator`, which both
the refit (gp/mll.py) and the pathwise sampler (gp/posterior.py) assemble
internally, so the whole BO loop runs on the backend-dispatched operator
layer.  Every jitted function therefore compiles exactly once per BO run
(TPU-friendly — no retracing as the dataset grows).

The loop state is checkpointable (preemption-safe): see ``BOState`` and
repro/checkpoint.

Two loop shapes share this module: :func:`thompson_sampling` (the paper's
refit loop — N-scale pathwise draw per round) and
:func:`thompson_sampling_incremental` (the serving-shaped loop — one
``repro.serving.ServeState`` reused across the run, O(m²) Cholesky
row-appends per observation, joint Thompson draws over a candidate set)."""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import features, walks
from ..core.modulation import Modulation
from ..core.walks import DEFAULT_CHUNK, WalkConfig, WalkTrace
from ..graphs.formats import Graph
from ..gp import mll, posterior
from .. import solvers
from ..solvers import SolveStrategy


@dataclasses.dataclass
class BOState:
    """Everything needed to resume a BO run after preemption."""

    x_buf: np.ndarray          # int32[capacity] observed node ids (padded 0)
    y_buf: np.ndarray          # float32[capacity] observations (padded 0)
    count: int                 # live observations
    params: dict               # GP hyperparameters (warm start)
    regret: list               # simple regret per iteration
    iteration: int = 0

    @property
    def x_obs(self) -> np.ndarray:
        return self.x_buf[: self.count]

    @property
    def y_obs(self) -> np.ndarray:
        return self.y_buf[: self.count]


def _init_or_resume(state, n, n_init, capacity, key_np, objective, mod, key,
                    noise_std, batch_size=1):
    """Shared BO entry: draw the init set, or validate a resumed BOState.

    A resumed state must carry buffers at least ``capacity`` long (both
    loops append in place, so undersized buffers would IndexError deep in
    the run) and a count consistent with this run's n_init/batch_size —
    resuming with different round shapes would silently mis-window the
    normalisation stats instead of failing here."""
    if state is not None:
        slots = min(len(state.x_buf), len(state.y_buf))
        if slots < capacity or state.count > slots:
            raise ValueError(
                f"resumed BOState buffers hold {slots} slots "
                f"(count={state.count}) but this run needs {capacity} "
                "(n_init + n_steps*batch_size); resume with the same "
                "arguments as the original run"
            )
        expect = min(n_init, n) + state.iteration * batch_size
        if state.count != expect:
            raise ValueError(
                f"resumed BOState has count={state.count} at iteration "
                f"{state.iteration}, but n_init={n_init}/batch_size="
                f"{batch_size} imply {expect}; resume with the same "
                "arguments as the original run"
            )
        return state
    x0 = key_np.choice(n, size=min(n_init, n), replace=False)
    y0 = np.asarray(objective(x0), dtype=np.float32)
    x_buf = np.zeros(capacity, dtype=np.int32)
    y_buf = np.zeros(capacity, dtype=np.float32)
    x_buf[: len(x0)] = x0
    y_buf[: len(x0)] = y0
    params = mll.init_hyperparams(mod, key, init_noise=noise_std)
    return BOState(x_buf=x_buf, y_buf=y_buf, count=len(x0), params=params,
                   regret=[])


def _argmax_picks(samples: np.ndarray, ids, observed, batch_size: int):
    """One argmax per sample column, no duplicates within the round.

    ``samples`` is [len(ids), batch_size] (mutated); ``observed`` indexes
    rows of ``samples`` to exclude; ``ids`` maps rows to node ids."""
    samples[observed, :] = -np.inf
    picks = []
    for j in range(batch_size):
        row = int(np.argmax(samples[:, j]))
        if not np.isfinite(samples[row, j]):
            # Every candidate is already observed — argmax over an all--inf
            # column would silently return row 0 and re-query it forever.
            raise ValueError(
                "no unobserved candidates left to query (graph exhausted "
                "or candidate set fully observed); shrink n_steps or widen "
                "n_candidates"
            )
        picks.append(int(ids[row]))
        samples[row, :] = -np.inf  # no duplicate queries within a round
    return picks


def _record_round(state: BOState, picks, ys, f_max, checkpoint_cb, t):
    """Shared BO tail: append observations, track regret, checkpoint."""
    for x_t, y_t in zip(picks, ys):
        state.x_buf[state.count] = x_t
        state.y_buf[state.count] = float(y_t)
        state.count += 1
    if f_max is not None:
        regret = float(f_max - state.y_obs.max())
        state.regret.append(regret)
        obs.gauge("bo.incumbent_regret", regret)
    state.iteration = t + 1
    if checkpoint_cb is not None:
        checkpoint_cb(state)


def thompson_sampling(
    trace: WalkTrace | None,
    mod: Modulation,
    objective: Callable[[np.ndarray], np.ndarray],
    key: jax.Array,
    n_init: int = 50,
    n_steps: int = 100,
    noise_std: float = 0.1,
    refit_every: int = 5,
    refit_steps: int = 15,
    f_max: float | None = None,
    state: BOState | None = None,
    checkpoint_cb: Callable[[BOState], None] | None = None,
    batch_size: int = 1,
    graph: Graph | None = None,
    walk: WalkConfig | None = None,
    chunk: int = DEFAULT_CHUNK,
    fit_strategy: SolveStrategy | None = None,
    sample_strategy: SolveStrategy | None = None,
) -> BOState:
    """Run Alg. 3. ``objective`` maps node ids → noisy observations.

    ``batch_size`` > 1 runs *batched* Thompson sampling (beyond-paper):
    q independent pathwise posterior samples per round, one argmax each —
    the natural parallel-evaluation extension for large graphs where
    objective queries are concurrent (e.g. q profiles crawled at once).

    Pass ``graph`` + ``walk`` (and ``trace=None``) to run the *chunked*
    million-node path: the full-graph trace is never materialised — each
    posterior draw streams Φ in ``chunk``-row blocks and only the
    observation-set trace Φ_x ([capacity, K]) ever exists, so peak memory
    is O(chunk·K) instead of O(N·K).  The counter-based walker RNG makes
    both paths draw from the same Φ given the same key (DESIGN.md §3.6).

    ``fit_strategy`` / ``sample_strategy`` route the refit and pathwise
    solves through the strategy layer (repro.solvers).  The refit default
    is the *warm-started* ``MLL_DEFAULT``: each refit chunk carries
    [v_y, v_z] across Adam steps, and the hyperparameters themselves warm
    start from the previous round (``init_params=state.params``) — the two
    warm starts compose, which is what keeps per-round refits cheap
    (BENCH_solvers.json: ≥1.5× fewer total CG iterations over a fit)."""
    if fit_strategy is None:
        fit_strategy = solvers.MLL_DEFAULT
    if sample_strategy is None:
        sample_strategy = solvers.POSTERIOR_DEFAULT
    chunked = graph is not None
    if chunked and walk is None:
        raise ValueError("chunked Thompson sampling needs a WalkConfig")
    if not chunked and trace is None:
        raise ValueError(
            "pass either a materialised trace or graph= (+ walk=) for the "
            "chunked path"
        )
    n = graph.n_nodes if chunked else trace.n_nodes
    walk_key = jax.random.fold_in(key, 7919)  # Φ identity, fixed across iters
    capacity = n_init + n_steps * batch_size
    key_np = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    state = _init_or_resume(state, n, n_init, capacity, key_np, objective,
                            mod, key, noise_std, batch_size)
    capacity = min(len(state.x_buf), len(state.y_buf))  # resumed may be larger
    mask_np = np.zeros(capacity, dtype=np.float32)

    for t in range(state.iteration, n_steps):
        with obs.span("bo.round", round=t):
            mask_np[:] = 0.0
            mask_np[: state.count] = 1.0
            mask = jnp.asarray(mask_np)
            x_all = jnp.asarray(state.x_buf)
            y_live = state.y_buf[: state.count]
            ymean = float(y_live.mean())
            ystd = float(y_live.std()) + 1e-8
            y_n = jnp.asarray((state.y_buf - ymean) / ystd) * mask

            if t % refit_every == 0:
                with obs.span("bo.refit"):
                    if chunked:
                        # Φ_x rows via the counter RNG — identical to
                        # take_rows on the (never materialised) full trace.
                        trace_x = walks.sample_walks_for_nodes(
                            graph, x_all, walk_key, walk.n_walkers,
                            walk.p_halt, walk.l_max, walk.reweight,
                            walk.scheme,
                        )
                    else:
                        trace_x = features.take_rows(trace, x_all)
                    if "auto" in (fit_strategy.preconditioner,
                                  sample_strategy.preconditioner):
                        # Resolve "auto" ONCE per run, on the first refit
                        # round's operator — T is the static buffer capacity
                        # and later rounds only flip mask slots, so the
                        # measured rank keeps its meaning; re-probing every
                        # round would re-pay the measurement for nothing.
                        h0 = mll.make_h_operator(
                            trace_x, mod(state.params["mod"]),
                            jnp.where(mask > 0, mll.noise_var(state.params),
                                      1e6),
                            n,
                        )
                        fit_strategy = solvers.resolve_strategy(
                            h0, fit_strategy
                        )
                        sample_strategy = solvers.resolve_strategy(
                            h0, sample_strategy
                        )
                    res = mll.fit_hyperparams(
                        trace_x, mod, y_n, n,
                        jax.random.fold_in(key, 1000 + t),
                        steps=refit_steps, lr=0.05, init_params=state.params,
                        init_noise=noise_std, obs_mask=mask, chunk=refit_steps,
                        strategy=fit_strategy,
                    )
                    state.params = res.params

            f = mod(state.params["mod"])
            s2 = mll.noise_var(state.params)
            with obs.span("bo.draw", round=t, mode="pathwise") as sp:
                if chunked:
                    samples = posterior.pathwise_samples_chunked(
                        graph, x_all, f, s2, y_n, jax.random.fold_in(key, t),
                        walk_key, walk, chunk=chunk, n_samples=batch_size,
                        obs_mask=mask, strategy=sample_strategy,
                    )
                else:
                    samples = posterior.pathwise_samples(
                        trace, x_all, f, s2, y_n,
                        jax.random.fold_in(key, t), n_samples=batch_size,
                        obs_mask=mask, strategy=sample_strategy,
                    )
                sp.block_on(samples)
            # Mask observed nodes, pick one argmax per sample (Alg. 3 line 8).
            # Unblocked draws finish on the device inside bo.select.
            with obs.span("bo.select"):
                picks = _argmax_picks(np.array(samples), np.arange(n),
                                      state.x_obs, batch_size)
            with obs.span("bo.evaluate"):
                ys = np.asarray(objective(np.array(picks)), dtype=np.float32)
            _record_round(state, picks, ys, f_max, checkpoint_cb, t)
    return state


def thompson_sampling_incremental(
    graph: Graph,
    walk: WalkConfig,
    mod: Modulation,
    objective: Callable[[np.ndarray], np.ndarray],
    key: jax.Array,
    n_init: int = 50,
    n_steps: int = 100,
    noise_std: float = 0.1,
    refit_every: int = 5,
    refit_steps: int = 15,
    f_max: float | None = None,
    batch_size: int = 1,
    n_candidates: int | None = None,
    state: BOState | None = None,
    checkpoint_cb: Callable[[BOState], None] | None = None,
    fit_strategy: SolveStrategy | None = None,
) -> BOState:
    """Alg. 3 with one :class:`repro.serving.ServeState` reused end-to-end.

    The refit loop pays an N-scale pathwise sample *per draw* and a CG
    refit per round; here a BO step is serving-shaped (DESIGN.md §3.7):

      * acquisition — one exact *joint* Thompson draw over a candidate set
        via ``serving.thompson_draw`` (O(q·m² + q³), no CG, nothing N-long),
      * update — ``serving.observe_batch``: an O(m²) Cholesky row-append
        per new observation instead of a fresh fit,
      * hyperparameters — refit every ``refit_every`` rounds as usual; only
        then is the m×m Gram refactorised (O(m³), m = observations ≪ N).

    ``n_candidates`` bounds the per-round Thompson candidate set (default:
    every node when N ≤ 2048, else 1024 uniform draws — the q×q joint
    covariance is dense).  Resume via ``state=`` exactly as the refit loop;
    the ServeState is rebuilt from the BOState buffers on entry.

    ``fit_strategy`` routes the per-round hyperparameter refit through the
    strategy layer (warm-started ``solvers.MLL_DEFAULT`` by default — same
    composition of warm starts as :func:`thompson_sampling`)."""
    from .. import serving

    if fit_strategy is None:
        fit_strategy = solvers.MLL_DEFAULT
    n = graph.n_nodes
    walk_key = jax.random.fold_in(key, 7919)  # Φ identity, fixed across iters
    capacity = n_init + n_steps * batch_size
    key_np = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    if n_candidates is None:
        n_candidates = n if n <= 2048 else 1024
    n_candidates = min(n_candidates, n)
    cand_seed = int(jax.random.randint(jax.random.fold_in(key, 5003), (),
                                       0, 2**31 - 1))

    state = _init_or_resume(state, n, n_init, capacity, key_np, objective,
                            mod, key, noise_std, batch_size)
    capacity = min(len(state.x_buf), len(state.y_buf))  # resumed may be larger
    mask_np = np.zeros(capacity, dtype=np.float32)
    serve = None
    ymean, ystd = 0.0, 1.0

    for t in range(state.iteration, n_steps):
        y_live = state.y_buf[: state.count]

        refit_now = t % refit_every == 0
        if refit_now or serve is None:
            if refit_now:
                stats_count = state.count
            else:
                # Mid-cycle rebuild after a checkpoint resume: normalise
                # with the stats the uninterrupted run froze at its last
                # refit round (count there is derivable — each round since
                # appended exactly batch_size observations).
                t_last = (t // refit_every) * refit_every
                stats_count = state.count - (t - t_last) * batch_size
            y_stat = state.y_buf[:stats_count]
            ymean = float(y_stat.mean())
            ystd = float(y_stat.std()) + 1e-8
            if refit_now:
                # Hyperparameter refit (same warm-started LML ascent as the
                # refit loop).  A checkpoint resume mid-cycle (serve is
                # None, refit_now False) only rebuilds the ServeState below
                # — refitting there would diverge from an uninterrupted run.
                mask_np[:] = 0.0
                mask_np[: state.count] = 1.0
                mask = jnp.asarray(mask_np)
                y_n = jnp.asarray((state.y_buf - ymean) / ystd) * mask
                trace_x = walks.sample_walks_for_nodes(
                    graph, jnp.asarray(state.x_buf), walk_key,
                    walk.n_walkers, walk.p_halt, walk.l_max, walk.reweight,
                    walk.scheme,
                )
                if fit_strategy.preconditioner == "auto":
                    # Same once-per-run resolution as thompson_sampling.
                    h0 = mll.make_h_operator(
                        trace_x, mod(state.params["mod"]),
                        jnp.where(mask > 0, mll.noise_var(state.params),
                                  1e6), n,
                    )
                    fit_strategy = solvers.resolve_strategy(h0, fit_strategy)
                res = mll.fit_hyperparams(
                    trace_x, mod, y_n, n, jax.random.fold_in(key, 1000 + t),
                    steps=refit_steps, lr=0.05, init_params=state.params,
                    init_noise=noise_std, obs_mask=mask, chunk=refit_steps,
                    strategy=fit_strategy,
                )
                state.params = res.params
            # One O(m³) Gram refactorisation into a fresh ServeState.
            serve = serving.init_state(
                graph, walk_key, mod(state.params["mod"]),
                mll.noise_var(state.params), capacity, walk,
            )
            serve = serving.ingest(
                serve, state.x_obs, (y_live - ymean) / ystd
            )

        if n_candidates >= n:
            cand = np.arange(n, dtype=np.int32)
        else:
            # Seeded per (key, t) — NOT drawn from a process-positional RNG
            # stream — so a checkpoint-resumed run draws the same candidate
            # set at round t as the uninterrupted run it replaces.
            cand_rng = np.random.default_rng((cand_seed, t))
            cand = cand_rng.choice(n, size=n_candidates, replace=False).astype(
                np.int32
            )
        with obs.span("bo.draw", round=t, mode="joint"):
            # np.array blocks on the device draw inside the span window.
            draws = np.array(serving.thompson_draw(
                serve, cand, jax.random.fold_in(key, t),
                n_samples=batch_size,
            ))                                # [q, batch_size], writable
        picks = _argmax_picks(draws, cand, np.isin(cand, state.x_obs),
                              batch_size)
        ys = np.asarray(objective(np.array(picks)), dtype=np.float32)
        serve = serving.observe_batch(serve, picks, (ys - ymean) / ystd)
        _record_round(state, picks, ys, f_max, checkpoint_cb, t)
    return state
