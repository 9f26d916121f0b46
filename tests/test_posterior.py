"""Pathwise conditioning vs exact Cholesky posterior on the SAME K̂."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features, modulation, walks
from repro.gp import exact, posterior
from repro.graphs import generators


@pytest.fixture(scope="module")
def problem():
    g = generators.grid2d(7, 7)
    n = g.n_nodes
    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=30, p_halt=0.2, l_max=6)
    mod = modulation.diffusion(l_max=6)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    rng = np.random.default_rng(0)
    train = jnp.asarray(rng.choice(n, 18, replace=False))
    y = jnp.asarray(rng.standard_normal(18), jnp.float32)
    s2 = jnp.asarray(0.05, jnp.float32)
    k_full = features.materialize_khat(tr, f, n)
    mean_exact, var_exact = exact.cholesky_posterior(k_full, train, y, s2)
    return g, tr, f, train, y, s2, mean_exact, var_exact


def test_posterior_mean_matches_cholesky(problem):
    g, tr, f, train, y, s2, mean_exact, _ = problem
    mean = posterior.posterior_mean(tr, train, f, s2, y, cg_tol=1e-7, cg_iters=600)
    np.testing.assert_allclose(np.array(mean), np.array(mean_exact),
                               rtol=1e-3, atol=1e-3)


def test_pathwise_moments_match_exact(problem):
    """Eq. 12: sample mean → exact mean, sample var → exact var (MC rate)."""
    g, tr, f, train, y, s2, mean_exact, var_exact = problem
    samples = posterior.pathwise_samples(
        tr, train, f, s2, y, jax.random.PRNGKey(7), n_samples=512,
        cg_tol=1e-6, cg_iters=600,
    )
    m, v = posterior.predictive_moments_from_samples(samples)
    scale = float(jnp.std(mean_exact)) + 1e-6
    err_m = float(jnp.abs(m - mean_exact).mean()) / scale
    assert err_m < 0.15, err_m
    # variances: compare in aggregate (MC error per node is large)
    ratio = float(jnp.mean(v) / (jnp.mean(var_exact) + 1e-9))
    assert 0.7 < ratio < 1.3, ratio


def test_pathwise_one_pass_equals_two_pass_formula(problem):
    """The draw computes Φ(w + Φ_xᵀα); the textbook order is a prior pass
    Φw plus a correction pass K̂_{·x}α.  Same keys, same solve: only the
    association of the sum differs."""
    from repro import solvers
    from repro.gp.mll import make_h_operator

    g, tr, f, train, y, s2, *_ = problem
    n, t, s = g.n_nodes, train.shape[0], 4
    key = jax.random.PRNGKey(11)
    strategy = solvers.POSTERIOR_DEFAULT.with_overrides(tol=1e-6,
                                                        max_iters=600)

    @jax.jit
    def two_pass(f, y, key):
        k_w, k_eps = jax.random.split(key)
        w = jax.random.normal(k_w, (n, s), dtype=jnp.float32)
        prior = features.phi_matvec(tr, f, w)
        eps = jnp.sqrt(s2) * jax.random.normal(k_eps, (t, s))
        resid = y[:, None] - (prior[train] + eps)
        tr_x = features.take_rows(tr, train)
        alpha = solvers.solve(make_h_operator(tr_x, f, s2, n), resid,
                              strategy).x
        return prior + features.khat_cross_matvec(tr, tr_x, f, alpha, n)

    got = posterior.pathwise_samples(tr, train, f, s2, y, key, n_samples=s,
                                     strategy=strategy)
    np.testing.assert_allclose(np.array(got), np.array(two_pass(f, y, key)),
                               rtol=1e-5, atol=1e-6)


def test_nlpd_and_rmse_shapes(problem):
    g, tr, f, train, y, s2, mean_exact, var_exact = problem
    nlpd = posterior.gaussian_nlpd(y, mean_exact[train], var_exact[train] + s2)
    assert np.isfinite(float(nlpd))
    assert float(posterior.rmse(y, mean_exact[train])) >= 0


def test_jlt_woodbury_solver(problem):
    """App. B: JLT+Woodbury approximately solves the same system."""
    from repro.core import jlt

    g, tr, f, train, y, s2, *_ = problem
    n = g.n_nodes
    tr_x = features.take_rows(tr, train)
    from repro.solvers import cg_solve
    from repro.gp.mll import make_h_matvec

    want = cg_solve(make_h_matvec(tr_x, f, s2, n), y, tol=1e-7, max_iters=500).x
    k1 = jlt.jlt_features(tr_x, f, jax.random.PRNGKey(3), m=4096, n_nodes=n)
    got = jlt.woodbury_solve(k1, s2, y)
    # JLT is a randomised approximation — expect qualitative agreement.
    corr = np.corrcoef(np.array(want), np.array(got))[0, 1]
    assert corr > 0.95, corr
