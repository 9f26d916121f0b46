"""The solvers/ strategy layer: preconditioning, warm starts, SLQ.

Contract under test (ISSUE 5 acceptance):
  * Nyström-preconditioned and warm-started strategy solves match the dense
    ``jnp.linalg.solve`` fixed point on small graphs;
  * ``slq_logdet`` lands within 5% of ``slogdet`` (averaged over seeds) and
    the SLQ-based exact LML within 5% of the dense LML on a 500-node graph;
  * preconditioning never changes the fixed point (hypothesis property);
  * the psum-``dot`` sharded path retains parity (tests/test_distributed_gp
    covers the shard_map side; here the hook itself).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import solvers
from repro.core import linops, modulation, walks
from repro.gp import mll
from repro.graphs import generators


@pytest.fixture(scope="module")
def system():
    """A clustered-training-block GP system (correlated rows ⇒ the regime
    Nyström preconditioning exists for)."""
    g = generators.ring(2000, k=3)
    n = g.n_nodes
    cfg = walks.WalkConfig(n_walkers=8, p_halt=0.2, l_max=5)
    train = jnp.arange(96)        # contiguous ⇒ heavily overlapping walks
    tr_x = walks.sample_walks_for_nodes(
        g, train, jax.random.PRNGKey(0),
        cfg.n_walkers, cfg.p_halt, cfg.l_max, cfg.reweight,
    )
    mod = modulation.diffusion(l_max=cfg.l_max)
    f = mod({"log_beta": jnp.log(jnp.asarray(3.0)),
             "log_sigma_f": jnp.asarray(0.0)})
    h = linops.shifted(tr_x, f, jnp.asarray(1e-2), n)
    b = jnp.asarray(
        np.random.default_rng(1).standard_normal(96), jnp.float32
    )
    return h, b, tr_x, f, n


@pytest.mark.parametrize("precond", ["none", "jacobi", "nystrom"])
def test_strategy_solve_matches_dense(system, precond):
    h, b, *_ = system
    st = solvers.SolveStrategy(
        tol=1e-7, max_iters=2000, preconditioner=precond, precond_rank=32
    )
    res = solvers.solve(h, b, st)
    want = np.linalg.solve(np.array(h.dense()), np.array(b))
    assert bool(jnp.all(res.converged))
    np.testing.assert_allclose(np.array(res.x), want, rtol=2e-3, atol=2e-3)


def test_warm_start_matches_dense_and_cuts_iterations(system):
    h, b, *_ = system
    st = solvers.SolveStrategy(tol=1e-6, max_iters=2000, warm_start=True)
    cold = solvers.solve(h, b, st)
    # Warm-start from a slightly perturbed solution: same fixed point, far
    # fewer iterations (the solve only has to cover the perturbation).
    x0 = cold.x * (1.0 + 1e-3)
    warm = solvers.solve(h, b, st, x0=x0)
    want = np.linalg.solve(np.array(h.dense()), np.array(b))
    np.testing.assert_allclose(np.array(warm.x), want, rtol=2e-3, atol=2e-3)
    assert int(warm.iters) < int(cold.iters)
    # warm_start=False strategies must IGNORE x0 (cold/warm is a strategy
    # decision, not a call-site one).
    st_cold = st.with_(warm_start=False)
    ignored = solvers.solve(h, b, st_cold, x0=x0)
    assert int(ignored.iters) == int(cold.iters)


def test_nystrom_reduces_iterations_on_clustered_system(system):
    h, b, *_ = system
    kw = dict(tol=1e-6, max_iters=2000)
    jac = solvers.solve(h, b, solvers.SolveStrategy(**kw))
    nys = solvers.solve(h, b, solvers.SolveStrategy(
        preconditioner="nystrom", precond_rank=48, **kw
    ))
    assert bool(jnp.all(nys.converged))
    assert int(nys.iters) < int(jac.iters), (int(nys.iters), int(jac.iters))


def test_nystrom_heteroscedastic_and_masked(system):
    """BO's ∞-noise padding (noise vector) and the masked sandwich both
    keep the Nyström-preconditioned fixed point exact."""
    h, b, tr_x, f, n = system
    t = b.shape[0]
    mask = jnp.ones(t).at[80:].set(0.0)
    noise = jnp.where(mask > 0, 1e-2, 1e6)
    h_vec = linops.shifted(tr_x, f, noise, n)
    st = solvers.SolveStrategy(
        tol=1e-7, max_iters=2000, preconditioner="nystrom", precond_rank=32
    )
    res = solvers.solve(h_vec, b * mask, st)
    want = np.linalg.solve(np.array(h_vec.dense()), np.array(b * mask))
    np.testing.assert_allclose(np.array(res.x), want, rtol=2e-3, atol=2e-3)

    h_mask = linops.ShiftedOperator(
        linops.khat(tr_x, f, n), jnp.asarray(1e-2), mask=mask
    )
    res_m = solvers.solve(h_mask, b * mask, st)
    want_m = np.linalg.solve(np.array(h_mask.dense()), np.array(b * mask))
    np.testing.assert_allclose(np.array(res_m.x), want_m, rtol=2e-3, atol=2e-3)


def test_nystrom_rejects_sharded_and_lazy_operators(system):
    h, b, tr_x, f, n = system
    sharded = linops.ShiftedOperator(
        linops.KhatOperator(h.khat.rows, h.khat.cols, reduce=lambda u: u),
        h.noise,
    )
    with pytest.raises(ValueError, match="sharded"):
        solvers.nystrom_precond(sharded)
    with pytest.raises(ValueError, match="ShiftedOperator"):
        solvers.nystrom_precond(lambda v: v)


def test_fixed_loop_warm_start_and_coeffs(system):
    h, b, *_ = system
    res, coeffs = solvers.cg_solve_fixed(h, b, iters=40, with_coeffs=True)
    assert coeffs.alphas.shape == (40, 1)
    assert bool(jnp.all(coeffs.valid[0]))
    # Tridiagonal eigenvalues are Ritz values of H — within its spectrum.
    tri = solvers.tridiag_from_coeffs(coeffs)
    evals = np.linalg.eigvalsh(np.array(tri[0]))
    hev = np.linalg.eigvalsh(np.array(h.dense()))
    assert evals.min() >= hev.min() * 0.9
    assert evals.max() <= hev.max() * 1.1


def test_slq_logdet_within_5pct_over_seeds():
    g = generators.grid2d(10, 10)
    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=16,
                            p_halt=0.2, l_max=4)
    mod = modulation.diffusion(l_max=4)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    h = linops.shifted(tr, f, jnp.asarray(0.05), g.n_nodes)
    _, want = np.linalg.slogdet(np.array(h.dense()))
    ests = [
        float(solvers.slq_logdet(h, g.n_nodes, jax.random.PRNGKey(s),
                                 n_probes=24, n_iters=50))
        for s in range(4)
    ]
    rel = abs(np.mean(ests) - want) / abs(want)
    assert rel < 0.05, (ests, want)


def test_exact_lml_within_5pct_of_dense_500_nodes():
    """Acceptance: SLQ-based exact LML vs the dense LML on a 500-node graph."""
    g = generators.ring(500, k=2)
    n = g.n_nodes
    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=12,
                            p_halt=0.25, l_max=4)
    mod = modulation.diffusion(l_max=4)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    s2 = jnp.asarray(0.05)
    y = jnp.asarray(
        np.random.default_rng(2).standard_normal(n), jnp.float32
    )
    out = mll.exact_lml(tr, f, s2, y, n, jax.random.PRNGKey(3),
                        n_probes=32, slq_iters=64)
    assert bool(out["converged"])
    hd = np.array(linops.shifted(tr, f, s2, n).dense())
    _, logdet = np.linalg.slogdet(hd)
    dense_lml = (
        -0.5 * float(np.array(y) @ np.linalg.solve(hd, np.array(y)))
        - 0.5 * logdet - 0.5 * n * np.log(2 * np.pi)
    )
    rel = abs(float(out["lml"]) - dense_lml) / abs(dense_lml)
    assert rel < 0.05, (float(out["lml"]), dense_lml)


def test_exact_lml_masked_padding_consistent():
    """Padded slots (obs_mask) must contribute nothing to the LML."""
    g = generators.ring(300, k=2)
    n = g.n_nodes
    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=10,
                            p_halt=0.25, l_max=3)
    mod = modulation.diffusion(l_max=3)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    s2 = jnp.asarray(0.05)
    rng = np.random.default_rng(3)
    train = jnp.asarray(rng.choice(n, 40, replace=False))
    y = jnp.asarray(rng.standard_normal(40), jnp.float32)
    from repro.core import features

    tr_x = features.take_rows(tr, train)
    plain = mll.exact_lml(tr_x, f, s2, y, n, jax.random.PRNGKey(4),
                          n_probes=48, slq_iters=48)
    pad = 24
    train_p = jnp.concatenate([train, jnp.zeros(pad, train.dtype)])
    y_p = jnp.concatenate([y, jnp.zeros(pad, y.dtype)])
    mask = jnp.concatenate([jnp.ones_like(y), jnp.zeros(pad)])
    tr_xp = features.take_rows(tr, train_p)
    padded = mll.exact_lml(tr_xp, f, s2, y_p, n, jax.random.PRNGKey(4),
                           n_probes=48, slq_iters=48, obs_mask=mask)
    # Same quantity, different probe geometry: agree to a few percent.
    rel = abs(float(padded["lml"]) - float(plain["lml"])) / abs(
        float(plain["lml"])
    )
    assert rel < 0.05, (float(padded["lml"]), float(plain["lml"]))


def test_psum_dot_hook_parity(system):
    """The injectable ``dot`` is the sharded path's only CG difference; an
    identity-reduction dot must reproduce the default solve exactly.
    (test_distributed_gp exercises the real psum under shard_map.)"""
    h, b, *_ = system
    st = solvers.SolveStrategy(tol=1e-6, max_iters=2000)
    plain = solvers.solve(h, b, st)
    hooked = solvers.solve(
        h, b, st, dot=lambda u, v: jnp.sum(u * v, axis=0)
    )
    assert int(plain.iters) == int(hooked.iters)
    np.testing.assert_allclose(np.array(plain.x), np.array(hooked.x),
                               rtol=1e-6, atol=1e-6)


def test_fit_history_logs_every_step(system):
    """Satellite regression: a 25-step fit must log 25 history rows (the old
    driver kept only the last row of each chunk), each carrying the CG
    iteration count and convergence flag."""
    _, _, tr_x, f, n = system
    mod = modulation.diffusion(l_max=5)
    y = jnp.asarray(
        np.random.default_rng(5).standard_normal(96), jnp.float32
    )
    res = mll.fit_hyperparams(tr_x, mod, y, n, jax.random.PRNGKey(6),
                              steps=25, chunk=10)
    assert len(res.history) == 25
    assert [row["step"] for row in res.history] == list(range(1, 26))
    assert all("cg_iters" in row and "cg_converged" in row
               for row in res.history)


def test_warm_started_fit_uses_fewer_total_cg_iters(system):
    """Tentpole: the warm-started fit (probes frozen per chunk, [v_y, v_z]
    carried through the scan) spends measurably fewer CG iterations than
    the cold-started fit at matched settings."""
    _, _, tr_x, f, n = system
    mod = modulation.diffusion(l_max=5)
    y = jnp.asarray(
        np.random.default_rng(7).standard_normal(96), jnp.float32
    )
    kw = dict(steps=20, chunk=20, n_probes=4, lr=0.03)
    cold = mll.fit_hyperparams(
        tr_x, mod, y, n, jax.random.PRNGKey(8),
        strategy=solvers.MLL_DEFAULT.with_(warm_start=False), **kw,
    )
    warm = mll.fit_hyperparams(
        tr_x, mod, y, n, jax.random.PRNGKey(8),
        strategy=solvers.MLL_DEFAULT, **kw,
    )
    total_cold = sum(r["cg_iters"] for r in cold.history)
    total_warm = sum(r["cg_iters"] for r in warm.history)
    assert total_warm < total_cold, (total_warm, total_cold)
    assert all(r["cg_converged"] for r in warm.history)


def test_public_exports():
    import repro.gp as gp

    for name in ("SolveStrategy", "cg_solve_fixed", "slq_logdet", "solve",
                 "exact_lml", "init_inducing_pivoted"):
        assert hasattr(gp, name), name
    # The Krylov stack lives in repro.solvers alone.
    for name in ("cg", "CGResult", "cg_solve"):
        assert not hasattr(gp, name), name
    for name in ("SolveStrategy", "CGResult", "cg_solve", "cg_solve_fixed",
                 "slq_logdet", "solve", "nystrom_precond", "pivot_rows",
                 "make_preconditioner", "jacobi_precond", "resolve_strategy",
                 "select_rank", "probe_spectrum", "AUTO_RANKS",
                 "DEFAULT_PRECOND_RANK", "MATVEC_DTYPES"):
        assert hasattr(solvers, name), name


def test_serving_refit_alpha_matches_full_refit():
    """Warm-started strategy refit of α == the Cholesky refit's α (the
    mean-serving fast path after a hyperparameter move)."""
    from repro import serving

    g = generators.ring(400, k=2)
    cfg = walks.WalkConfig(n_walkers=8, p_halt=0.25, l_max=4)
    mod = modulation.diffusion(l_max=4)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    state = serving.init_state(g, jax.random.PRNGKey(0), f, 0.05, 32, cfg)
    rng = np.random.default_rng(9)
    nodes = rng.choice(400, 24, replace=False)
    ys = rng.standard_normal(24).astype(np.float32)
    state = serving.ingest(state, nodes, ys)

    f2 = np.array(f) * 1.05                     # hyperparameter drift
    want = serving.refit(state, f=f2)
    got, iters, converged = serving.update.refit_alpha(
        state, f=f2, return_diagnostics=True
    )
    assert bool(converged)
    np.testing.assert_allclose(np.array(got.alpha), np.array(want.alpha),
                               rtol=1e-3, atol=1e-4)
    # Warm start from the stale α beats a cold solve of the same system.
    cold, cold_iters, _ = serving.update.refit_alpha(
        state, f=f2,
        strategy=solvers.SERVING_DEFAULT.with_(warm_start=False),
        return_diagnostics=True,
    )
    assert int(iters) <= int(cold_iters)


def test_pivot_rows_distinct_past_numerical_rank(system):
    """Duplicated feature rows exhaust the residual diagonal; the pivot
    sweep must keep returning DISTINCT row indices anyway (the exposed
    indices feed inducing-set selection)."""
    _, _, tr_x, f, n = system
    from repro.core import features

    dup = features.take_rows(tr_x, jnp.concatenate(
        [jnp.arange(8), jnp.arange(8), jnp.arange(8)]
    ))                                    # 24 rows, numerical rank ≤ 8ish
    piv = np.array(solvers.pivot_rows(dup, f, 20))
    assert len(np.unique(piv)) == 20, piv


def test_pivoted_inducing_selection_spreads_over_clusters(system):
    """Greedy residual pivots must not stack onto one correlated cluster."""
    _, _, tr_x, f, n = system
    from repro.gp import variational

    ind = np.array(variational.init_inducing_pivoted(tr_x, f, 16))
    assert len(np.unique(ind)) == 16       # no duplicate pivots
    # rows 0..95 are one contiguous ring cluster; a plain top-energy rule
    # picks near-neighbours, the greedy rule spreads: consecutive pivots
    # should rarely be adjacent rows.
    adjacent = np.sum(np.abs(np.diff(np.sort(ind))) == 1)
    assert adjacent < 8, ind


def test_bf16_matvecs_reach_f32_fixed_point(system):
    """ISSUE 6 satellite: matvec_dtype="bfloat16" converges to the same
    fixed point as f32 up to the operator-perturbation scale (the bf16
    payload perturbs H itself by O(2⁻⁸), so the tolerance is relative and
    loose — the claim is "same solve", not bitwise equality)."""
    h, b, *_ = system
    st = solvers.SolveStrategy(tol=1e-6, max_iters=2000)
    f32 = solvers.solve(h, b, st)
    bf16 = solvers.solve(h, b, st.with_(matvec_dtype="bfloat16"))
    assert bool(jnp.all(bf16.converged))
    rel = np.linalg.norm(np.array(bf16.x) - np.array(f32.x)) / max(
        np.linalg.norm(np.array(f32.x)), 1e-12
    )
    assert rel < 5e-2, rel
    # And the nystrom-preconditioned bf16 solve lands on the same point.
    nys16 = solvers.solve(h, b, st.with_(preconditioner="nystrom",
                                         precond_rank=32,
                                         matvec_dtype="bfloat16"))
    assert bool(jnp.all(nys16.converged))
    rel = np.linalg.norm(np.array(nys16.x) - np.array(f32.x)) / max(
        np.linalg.norm(np.array(f32.x)), 1e-12
    )
    assert rel < 5e-2, rel


def test_auto_strategy_resolves_and_reports_rank(system):
    """"auto" resolves eagerly into jacobi or nystrom-with-measured-rank,
    the solve matches the dense fixed point, and CGResult.precond_rank
    reports the rank the solve actually ran with."""
    h, b, *_ = system
    st = solvers.SolveStrategy(tol=1e-6, max_iters=2000,
                               preconditioner="auto")
    resolved = solvers.resolve_strategy(h, st)
    assert resolved.preconditioner in ("jacobi", "nystrom")
    if resolved.preconditioner == "nystrom":
        assert resolved.precond_rank in solvers.AUTO_RANKS

    res = solvers.solve(h, b, st)
    assert bool(jnp.all(res.converged))
    want = np.linalg.solve(np.array(h.dense()), np.array(b))
    np.testing.assert_allclose(np.array(res.x), want, rtol=2e-3, atol=2e-3)
    if resolved.preconditioner == "nystrom":
        assert int(res.precond_rank) == resolved.precond_rank
    else:
        assert int(res.precond_rank) == 0
    # An explicit nystrom solve reports its static rank too.
    nys = solvers.solve(h, b, st.with_(preconditioner="nystrom",
                                       precond_rank=32))
    assert int(nys.precond_rank) == 32


def test_auto_strategy_falls_back_to_jacobi_under_jit(system):
    """Rank is a static loop-shape decision: under tracing the auto path
    must silently degrade to jacobi instead of leaking a tracer into the
    spectral probe — the jitted solve still converges and matches."""
    h, b, *_ = system
    st = solvers.SolveStrategy(tol=1e-6, max_iters=2000,
                               preconditioner="auto")

    @jax.jit
    def run(b):
        res = solvers.solve(h, b, st)
        return res.x, res.converged

    x, converged = run(b)
    assert bool(jnp.all(converged))
    want = np.linalg.solve(np.array(h.dense()), np.array(b))
    np.testing.assert_allclose(np.array(x), want, rtol=2e-3, atol=2e-3)
    # Operators auto can't serve (bare callables) resolve to jacobi too.
    bare = solvers.resolve_strategy(lambda v: v, st)
    assert bare.preconditioner == "jacobi"


# --- hypothesis property: preconditioning never changes the fixed point ---
# importorskip'd per-test (NOT at module level — that would skip the whole
# file on machines without the optional dep).


def _check_precond_fixed_point(seed, noise, rank):
    g = generators.ring(300, k=2)
    tr_x = walks.sample_walks_for_nodes(
        g, jnp.arange(32), jax.random.PRNGKey(seed % 7), 6, 0.3, 3, True
    )
    mod = modulation.diffusion(l_max=3)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    h = linops.shifted(tr_x, f, jnp.asarray(noise, jnp.float32), 300)
    b = jnp.asarray(
        np.random.default_rng(seed).standard_normal(32), jnp.float32
    )
    sols = []
    for pc in solvers.PRECONDITIONERS:
        st = solvers.SolveStrategy(tol=1e-8, max_iters=3000,
                                   preconditioner=pc, precond_rank=rank)
        res = solvers.solve(h, b, st)
        assert bool(jnp.all(res.converged))
        sols.append(np.array(res.x))
    for other in sols[1:]:
        np.testing.assert_allclose(sols[0], other, rtol=5e-3, atol=5e-3)


def test_property_preconditioning_preserves_fixed_point():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as hst

    run = settings(max_examples=8, deadline=None)(
        given(
            seed=hst.integers(0, 2**16),
            noise=hst.floats(1e-3, 1.0),
            rank=hst.integers(2, 24),
        )(_check_precond_fixed_point)
    )
    run()
