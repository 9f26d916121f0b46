"""Hyperparameter learning: the surrogate gradient equals the exact
negative-LML gradient (Eq. 9) in the dense small-N limit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features, modulation, walks
from repro.gp import exact, mll
from repro.graphs import generators


@pytest.fixture(scope="module")
def problem():
    g = generators.grid2d(6, 6)
    n = g.n_nodes
    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=20, p_halt=0.2, l_max=5)
    mod = modulation.diffusion(l_max=5)
    rng = np.random.default_rng(0)
    train = jnp.asarray(rng.choice(n, 20, replace=False))
    y = jnp.asarray(rng.standard_normal(20), jnp.float32)
    return g, tr, mod, train, y


def test_surrogate_gradient_matches_exact(problem):
    g, tr, mod, train, y = problem
    n = g.n_nodes
    tr_x = features.take_rows(tr, train)
    params = mll.init_hyperparams(mod, jax.random.PRNGKey(1))

    def exact_nlml(params):
        f = mod(params["mod"])
        k_xx = features.materialize_khat(tr_x, f, n)
        return exact.exact_nlml(k_xx, y, mll.noise_var(params))

    g_exact = jax.grad(exact_nlml)(params)

    # Average surrogate gradients over many probe draws (Hutchinson is
    # unbiased; the fit term is deterministic up to CG tolerance).
    def sur(params, key):
        return mll.mll_surrogate_loss(
            params, key, tr_x, mod, y, n, n_probes=64, cg_tol=1e-7, cg_iters=400
        )[0]

    n_draws = 12
    grads = [jax.grad(sur)(params, jax.random.PRNGKey(100 + i))
             for i in range(n_draws)]
    g_avg = jax.tree.map(lambda *xs: sum(xs) / len(xs), *grads)
    g_se = jax.tree.map(
        lambda *xs: np.std([float(x) for x in xs]) / np.sqrt(n_draws), *grads
    )

    def check(name, a, b, se):
        # Hutchinson is unbiased: the exact gradient must lie within ~4
        # standard errors (plus a small CG-tolerance floor) of the average.
        assert abs(a - b) < 4.0 * se + 0.02 * max(abs(a), 1e-2), (name, a, b, se)

    for k in ("log_beta", "log_sigma_f"):
        check(k, float(g_exact["mod"][k]), float(g_avg["mod"][k]),
              float(g_se["mod"][k]))
    check("log_sigma_n", float(g_exact["log_sigma_n"]),
          float(g_avg["log_sigma_n"]), float(g_se["log_sigma_n"]))


def test_fit_improves_exact_nlml(problem):
    g, tr, mod, train, y = problem
    n = g.n_nodes
    tr_x = features.take_rows(tr, train)

    def exact_nlml(params):
        f = mod(params["mod"])
        k_xx = features.materialize_khat(tr_x, f, n)
        return float(exact.exact_nlml(k_xx, y, mll.noise_var(params)))

    init = mll.init_hyperparams(mod, jax.random.PRNGKey(2))
    before = exact_nlml(init)
    res = mll.fit_hyperparams(tr_x, mod, y, n, jax.random.PRNGKey(3),
                              steps=40, lr=0.1, init_params=init)
    after = exact_nlml(res.params)
    assert after < before, (before, after)


def test_masked_padding_matches_unpadded(problem):
    """Static-shape padding (BO loop) must not change the solution."""
    g, tr, mod, train, y = problem
    n = g.n_nodes
    params = mll.init_hyperparams(mod, jax.random.PRNGKey(4))
    f = mod(params["mod"])
    s2 = mll.noise_var(params)

    from repro.solvers import cg_solve

    tr_x = features.take_rows(tr, train)
    mv = mll.make_h_matvec(tr_x, f, s2, n)
    want = cg_solve(mv, y, tol=1e-7, max_iters=300).x

    pad = 12
    train_p = jnp.concatenate([train, jnp.zeros(pad, train.dtype)])
    y_p = jnp.concatenate([y, jnp.zeros(pad, y.dtype)])
    mask = jnp.concatenate([jnp.ones_like(y), jnp.zeros(pad, y.dtype)])
    tr_xp = features.take_rows(tr, train_p)
    noise = jnp.where(mask > 0, s2, 1e6)
    mv_p = mll.make_h_matvec(tr_xp, f, noise, n)
    got = cg_solve(mv_p, y_p * mask, tol=1e-7, max_iters=300).x

    np.testing.assert_allclose(np.array(got[: len(y)]), np.array(want),
                               rtol=1e-3, atol=1e-4)
