"""The float64 reference rebuilds the program's graph, walks and modulation
from the configuration alone.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

from harness import reference  # noqa: E402

SPECS = [{"generator": "ring", "n_nodes": 3000, "k": 2},
         {"generator": "grid2d", "rows": 23, "cols": 31}]


def _program_graph(spec):
    from repro.graphs import generators

    params = {k: v for k, v in spec.items() if k != "generator"}
    return getattr(generators, spec["generator"])(**params)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s["generator"])
def test_adjacency_matches_the_generator(spec):
    g = _program_graph(spec)
    adj = reference.Adjacency.from_spec(spec)
    assert np.array_equal(np.asarray(g.deg), adj.deg)
    live = np.arange(adj.neighbors.shape[1])[None, :] < adj.deg[:, None]
    assert np.array_equal(np.where(live, np.asarray(g.neighbors), 0),
                          np.where(live, adj.neighbors, 0))
    assert np.allclose(np.asarray(g.weights), adj.weights, rtol=1e-6)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s["generator"])
def test_walks_match_the_program(spec):
    import jax
    import jax.numpy as jnp
    from repro.core import walks

    g = _program_graph(spec)
    adj = reference.Adjacency.from_spec(spec)
    key = jax.random.PRNGKey(2**31 + 5)
    nodes = jnp.arange(0, g.n_nodes, 5, dtype=jnp.int32)
    tr = walks.sample_walks_for_nodes(g, nodes, key, 30, 0.15, 5)
    seed = int(walks.walk_seed(key))
    cols, loads, lens = reference.walks(adj, np.asarray(nodes), seed, 30,
                                        0.15, 5)
    assert np.array_equal(np.asarray(tr.cols), cols)
    assert np.array_equal(np.asarray(tr.lens), lens)
    assert np.allclose(np.asarray(tr.loads), loads, rtol=1e-5, atol=1e-7)


def test_diffusion_modulation_and_derivatives():
    import jax
    import jax.numpy as jnp
    from repro.core import modulation

    mod = modulation.diffusion(l_max=5)
    p = {"log_beta": jnp.float32(0.3), "log_sigma_f": jnp.float32(-0.2)}
    f, d_beta, d_sf = reference.diffusion_f(0.3, -0.2, 5)
    jac = jax.jacfwd(mod)(p)
    assert np.allclose(np.asarray(mod(p)), f, rtol=1e-6)
    assert np.allclose(np.asarray(jac["log_beta"]), d_beta, atol=1e-7)
    assert np.allclose(np.asarray(jac["log_sigma_f"]), d_sf, atol=1e-7)


def test_round_bf16():
    x = np.array([1.0, 1.0 + 2**-9, 1.0 + 3 * 2**-9, 3.14159, -2.5e-3])
    got = reference.round_bf16(x)
    import jax.numpy as jnp

    want = np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)
    assert np.array_equal(got, want)
