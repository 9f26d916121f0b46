"""CG solver: correctness vs direct solve, preconditioning, batching."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.solvers import cg_solve, cg_solve_fixed


def _spd(n, cond=100.0, seed=0):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    evals = np.geomspace(1.0, cond, n)
    return (q * evals) @ q.T


def test_cg_matches_direct():
    a = _spd(64)
    b = np.random.default_rng(1).standard_normal(64)
    want = np.linalg.solve(a, b)
    mv = lambda v: jnp.asarray(a, jnp.float64 if v.dtype == jnp.float64 else jnp.float32) @ v
    got = cg_solve(mv, jnp.asarray(b, jnp.float32), tol=1e-7, max_iters=500).x
    np.testing.assert_allclose(np.array(got), want, rtol=2e-3, atol=2e-3)


def test_cg_batched_rhs():
    a = _spd(48, seed=2)
    b = np.random.default_rng(3).standard_normal((48, 4))
    want = np.linalg.solve(a, b)
    mv = lambda v: jnp.asarray(a, jnp.float32) @ v
    res = cg_solve(mv, jnp.asarray(b, jnp.float32), tol=1e-7, max_iters=500)
    np.testing.assert_allclose(np.array(res.x), want, rtol=3e-3, atol=3e-3)
    assert (np.array(res.resnorm) < 1e-3).all()


def test_jacobi_preconditioner_reduces_iterations():
    # strongly diagonal-dominant ill-scaled system
    rng = np.random.default_rng(4)
    d = np.geomspace(1, 1e4, 96)
    a = np.diag(d) + 0.01 * _spd(96, cond=10, seed=5)
    b = rng.standard_normal(96)
    mv = lambda v: jnp.asarray(a, jnp.float32) @ v
    plain = cg_solve(mv, jnp.asarray(b, jnp.float32), tol=1e-6, max_iters=400)
    pre = cg_solve(mv, jnp.asarray(b, jnp.float32), tol=1e-6, max_iters=400,
                   precond_diag=jnp.asarray(np.diag(a), jnp.float32))
    assert int(pre.iters) < int(plain.iters)


def test_cg_fixed_matches_while_loop():
    a = _spd(40, seed=6)
    b = np.random.default_rng(7).standard_normal(40)
    mv = lambda v: jnp.asarray(a, jnp.float32) @ v
    x1 = cg_solve(mv, jnp.asarray(b, jnp.float32), tol=0.0, max_iters=60).x
    x2 = cg_solve_fixed(mv, jnp.asarray(b, jnp.float32), iters=60).x
    np.testing.assert_allclose(np.array(x1), np.array(x2), rtol=1e-3, atol=1e-4)


def test_cg_jit_and_grad_safe():
    a = _spd(24, seed=8)

    @jax.jit
    def solve(b):
        mv = lambda v: jnp.asarray(a, jnp.float32) @ v
        return cg_solve(mv, b, tol=1e-6, max_iters=100).x

    b = jnp.asarray(np.random.default_rng(9).standard_normal(24), jnp.float32)
    assert np.isfinite(np.array(solve(b))).all()


def test_batched_rhs_every_column_meets_own_tolerance():
    """Regression (issue #1 satellite): the stopping rule must not declare
    convergence while ANY column is above its own tolerance.  Mix a
    well-conditioned RHS with hard ones so per-column convergence differs."""
    a = _spd(64, cond=5e3, seed=10)
    rng = np.random.default_rng(11)
    evecs = np.linalg.eigh(a)[1]
    # Columns aligned with extreme eigenvectors converge at very different
    # rates; a max-over-columns rule that exits early would leave some above
    # tolerance.
    b = np.stack([evecs[:, 0], evecs[:, -1],
                  rng.standard_normal(64), rng.standard_normal(64)], axis=1)
    tol = 1e-6
    mv = lambda v: jnp.asarray(a, jnp.float32) @ v
    res = cg_solve(mv, jnp.asarray(b, jnp.float32), tol=tol, max_iters=2000)
    bnorm = np.linalg.norm(b, axis=0)
    rel = np.array(res.resnorm) / np.maximum(bnorm, 1e-30)
    assert (rel <= tol * 1.01).all(), rel


def test_precond_diag_zero_rows_no_nan():
    """Isolated-node rows can have a zero diag_approx; the Jacobi
    preconditioner must fall back to identity instead of dividing by zero."""
    rng = np.random.default_rng(12)
    n = 32
    a = _spd(n, cond=50, seed=13)
    b = rng.standard_normal(n)
    diag = np.abs(np.diag(a)).astype(np.float32)
    diag[[3, 17]] = 0.0  # isolated nodes
    res = cg_solve(lambda v: jnp.asarray(a, jnp.float32) @ v,
                   jnp.asarray(b, jnp.float32), tol=1e-6, max_iters=400,
                   precond_diag=jnp.asarray(diag))
    x = np.array(res.x)
    assert np.isfinite(x).all()
    np.testing.assert_allclose(x, np.linalg.solve(a, b), rtol=2e-3, atol=2e-3)


def test_precond_zero_rows_from_dead_trace_rows():
    """End-to-end: a walk trace with an all-zero loads row (a node whose
    every deposit was masked) gives a zero khat_diag_approx entry; the GP
    solve must stay finite rather than dividing by zero."""
    import jax

    from repro.core import linops, modulation, walks
    from repro.graphs import generators

    g = generators.ring(10, k=1)
    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=6,
                            p_halt=0.3, l_max=3)
    dead = walks.WalkTrace(
        cols=tr.cols, loads=tr.loads.at[4].set(0.0), lens=tr.lens
    )
    mod = modulation.diffusion(l_max=3)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    h = linops.shifted(dead, f, jnp.asarray(0.0), 10)  # zero noise too
    pre = h.diag_approx()
    assert float(jnp.min(pre)) == 0.0  # the hazard is real
    # b must be consistent (zero on the dead row): H is singular there and
    # CG is only defined on range(H); the point is the preconditioner.
    b = jnp.ones((10,), jnp.float32).at[4].set(0.0)
    res = cg_solve(h, b, tol=1e-5, max_iters=50, precond_diag=pre)
    assert np.isfinite(np.array(res.x)).all()
