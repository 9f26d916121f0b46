"""Distributed GRF-GP (shard_map) equals the single-device computation.

Multi-device tests run in a subprocess so the 8-device XLA flag never leaks
into the rest of the suite (smoke tests must see 1 device)."""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.graphs import generators
from repro.core import walks, features, modulation
from repro.solvers import cg_solve
from repro.gp.mll import make_h_matvec
from repro.distributed.gp_shard import (
    sharded_cg_solve, sharded_cg_solve_chunked, sharded_posterior_sample)

mesh = jax.make_mesh((4, 2), ("data", "model"))
g = generators.ring(64, k=2)
tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=10, p_halt=0.2, l_max=4)
mod = modulation.diffusion(l_max=4)
f = mod(mod.init(jax.random.PRNGKey(1)))
b = jnp.asarray(np.random.default_rng(0).standard_normal(64), jnp.float32)

# 1) sharded CG == local CG
want = cg_solve(make_h_matvec(tr, f, 0.1, 64), b, tol=1e-7, max_iters=300).x
got = sharded_cg_solve(tr, f, b, mesh, sigma_n2=0.1, tol=1e-7, max_iters=300)
err = float(jnp.abs(want - got).max())
assert err < 1e-3, f"cg mismatch {err}"

# fixed/unrolled variant
got_fx = sharded_cg_solve(tr, f, b, mesh, sigma_n2=0.1, max_iters=64,
                          fixed_unrolled=True)
err = float(jnp.abs(want - got_fx).max())
assert err < 1e-2, f"fixed cg mismatch {err}"

# 1b) chunk-per-shard lazy rows == the same solve (walk key matches tr's)
got_ck = sharded_cg_solve_chunked(
    g, f, b, mesh, jax.random.PRNGKey(0),
    walks.WalkConfig(n_walkers=10, p_halt=0.2, l_max=4), chunk=8,
    sigma_n2=0.1, tol=1e-7, max_iters=300)
err = float(jnp.abs(want - got_ck).max())
assert err < 1e-3, f"chunked cg mismatch {err}"

# 2) sharded pathwise sample: finite + correct shape + respects the mask
mask = jnp.zeros(64).at[:16].set(1.0)
y = jnp.zeros(64).at[:16].set(jnp.asarray(
    np.random.default_rng(1).standard_normal(16), jnp.float32))
s = sharded_posterior_sample(tr, mask, f, y, jax.random.PRNGKey(5), mesh,
                             sigma_n2=0.05)
assert s.shape == (64,), s.shape
assert bool(jnp.isfinite(s).all())
print("DISTRIBUTED_GP_OK")
"""


def test_sharded_gp_matches_single_device():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        cwd="/root/repo",
    )
    assert "DISTRIBUTED_GP_OK" in res.stdout, res.stdout + res.stderr
