"""Hypothesis property tests on system invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import modulation, walks
from repro.solvers import cg_solve
from repro.graphs import generators
from repro.kernels.ell_spmv import ell_spmv_ref


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(8, 40),
    k=st.integers(1, 3),
    n_walkers=st.integers(1, 8),
    l_max=st.integers(1, 6),
    seed=st.integers(0, 2**16),
)
def test_walk_trace_invariants(n, k, n_walkers, l_max, seed):
    """Loads are finite and non-negative-masked; cols in range; lens 0..l_max."""
    g = generators.ring(n, k=min(k, (n - 1) // 2) or 1)
    tr = walks.sample_walks(g, jax.random.PRNGKey(seed), n_walkers=n_walkers,
                            p_halt=0.3, l_max=l_max)
    cols = np.asarray(tr.cols)
    loads = np.asarray(tr.loads)
    lens = np.asarray(tr.lens)
    assert cols.min() >= 0 and cols.max() < n
    assert np.isfinite(loads).all()
    assert lens.min() == 0 and lens.max() == l_max
    # step-0 deposits always live: every walker deposits 1/n_walkers at start
    l0 = loads.reshape(n, n_walkers, l_max + 1)[:, :, 0]
    np.testing.assert_allclose(l0, 1.0 / n_walkers, rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(4, 64),
    cond=st.floats(1.0, 1e4),
    seed=st.integers(0, 2**16),
)
def test_cg_solves_random_spd(n, cond, seed):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    a = (q * np.geomspace(1.0, cond, n)) @ q.T
    b = rng.standard_normal(n)
    mv = lambda v: jnp.asarray(a, jnp.float32) @ v
    x = np.array(cg_solve(mv, jnp.asarray(b, jnp.float32), tol=1e-6,
                          max_iters=4 * n).x)
    resid = np.linalg.norm(a @ x - b) / np.linalg.norm(b)
    assert resid < 1e-2, resid


@settings(max_examples=15, deadline=None)
@given(
    m=st.integers(1, 64),
    k=st.integers(1, 16),
    n=st.integers(1, 128),
    seed=st.integers(0, 2**16),
)
def test_ell_spmv_ref_linearity(m, k, n, seed):
    """Oracle is linear in u and in vals (catches scatter/gather bugs)."""
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    cols = jnp.asarray(rng.integers(0, n, (m, k)), jnp.int32)
    u1 = jnp.asarray(rng.standard_normal(n), jnp.float32)
    u2 = jnp.asarray(rng.standard_normal(n), jnp.float32)
    lhs = ell_spmv_ref(vals, cols, u1 + 2.0 * u2)
    rhs = ell_spmv_ref(vals, cols, u1) + 2.0 * ell_spmv_ref(vals, cols, u2)
    np.testing.assert_allclose(np.array(lhs), np.array(rhs), rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(beta=st.floats(0.1, 3.0), l_max=st.sampled_from([8, 12]))
def test_diffusion_modulation_deconvolution(beta, l_max):
    """Σ_l f_l f_{r−l} = e^{−β} β^r / r!  (the defining property of f)."""
    mod = modulation.diffusion(l_max=l_max, init_beta=beta)
    f = np.array(mod({"log_beta": jnp.log(beta), "log_sigma_f": jnp.asarray(0.0)}),
                 np.float64)
    for r in range(l_max // 2):
        conv = sum(f[l] * f[r - l] for l in range(r + 1))
        want = np.exp(-beta) * beta**r / math.factorial(r)
        assert abs(conv - want) < 1e-4 * max(want, 1e-3), (r, conv, want)


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(10, 40),
    t=st.integers(2, 10),
    seed=st.integers(0, 2**16),
)
def test_posterior_mean_interpolates_at_low_noise(n, t, seed):
    """As σ→0, the GP posterior mean approaches the data at observed nodes."""
    from repro.gp import posterior

    g = generators.ring(n, k=2)
    tr = walks.sample_walks(g, jax.random.PRNGKey(seed), n_walkers=20,
                            p_halt=0.2, l_max=4)
    mod = modulation.diffusion(l_max=4)
    f = mod(mod.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(seed)
    train = jnp.asarray(rng.choice(n, t, replace=False))
    y = jnp.asarray(rng.standard_normal(t), jnp.float32)
    mean = posterior.posterior_mean(tr, train, f, jnp.asarray(1e-6), y,
                                    cg_tol=1e-8, cg_iters=800)
    np.testing.assert_allclose(np.asarray(mean[train]), np.asarray(y),
                               rtol=0.05, atol=0.05)
