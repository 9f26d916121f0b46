"""The float64 reference rebuilds the program's graph, walks and modulation
from the configuration alone: the graph of every configuration in
``BENCHMARK.json``, at its test size, compared through views that do not
depend on the layout the program holds it in.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

import common  # noqa: E402
from harness import reference  # noqa: E402

CONFIGS = {c["name"]: c for c in common.configs()}


@pytest.fixture(params=sorted(CONFIGS))
def config(request):
    """A configuration of ``BENCHMARK.json`` at its test size."""
    return common.at_test_size(CONFIGS[request.param])


def test_adjacency_matches_the_generator(config):
    common.check_adjacency(config["graph"])


def test_walks_match_the_program(config):
    import jax
    import jax.numpy as jnp
    from harness import data
    from repro.core import walks

    g = data.build_graph(config["graph"])
    adj = reference.Adjacency.from_spec(config["graph"])
    wk = config["walks"]
    key = jax.random.PRNGKey(2**31 + 5)
    nodes = jnp.arange(0, g.n_nodes, 5, dtype=jnp.int32)
    tr = walks.sample_walks_for_nodes(g, nodes, key, wk["n_walkers"],
                                      wk["p_halt"], wk["l_max"])
    seed = int(walks.walk_seed(key))
    cols, loads, lens = reference.walks(adj, np.asarray(nodes), seed,
                                        wk["n_walkers"], wk["p_halt"],
                                        wk["l_max"])
    assert np.array_equal(np.asarray(tr.cols), cols)
    assert np.array_equal(np.asarray(tr.lens), lens)
    assert np.allclose(np.asarray(tr.loads), loads, rtol=1e-5, atol=1e-7)


def _heavy_tailed():
    """A hub with 40 leaves, a chain of 10 nodes, two isolated nodes (the
    last id among them), and edges given twice, once reversed."""
    star = [(0, leaf) for leaf in range(1, 41)]
    chain = [(i, i + 1) for i in range(41, 50)]
    dup = [(0, 1), (2, 0), (42, 41)]
    return np.array(star + chain + dup, np.int64), 53


def test_walks_match_the_program_on_a_heavy_tailed_graph():
    import jax
    import jax.numpy as jnp
    from repro.core import walks
    from repro.graphs import formats

    edges, n = _heavy_tailed()
    g = formats.from_edges(edges, n)
    adj = reference.Adjacency.from_edges(edges, n)
    assert np.array_equal(np.asarray(g.deg), adj.deg)
    assert adj.deg[0] == 40 and adj.deg[51] == adj.deg[52] == 0
    key = jax.random.PRNGKey(2**31 + 9)
    nodes = np.arange(n)
    tr = walks.sample_walks_for_nodes(g, jnp.asarray(nodes, jnp.int32), key,
                                      30, 0.15, 5)
    seed = int(walks.walk_seed(key))
    ref = reference.walks(adj, nodes, seed, 30, 0.15, 5)
    cols, loads, lens = ref
    assert np.array_equal(np.asarray(tr.lens), lens)
    assert np.allclose(np.asarray(tr.loads), loads, rtol=1e-5, atol=1e-7)
    # The program parks a walker at a degree-0 node on its padding (node
    # 0) with zero load; the reference keeps it in place.
    live = loads != 0
    assert np.array_equal(np.asarray(tr.cols)[live], cols[live])
    f = reference.diffusion_f(0.3, -0.2, 5)[0]
    prog = (np.asarray(tr.cols), np.asarray(tr.loads, np.float64), lens)
    phi_p = reference.phi(prog, f, n).toarray()
    phi_r = reference.phi(ref, f, n).toarray()
    assert np.allclose(phi_p, phi_r, rtol=1e-5, atol=1e-7)
    for i in (51, 52):      # only the length-0 deposit, 30 × f_0/30
        assert np.flatnonzero(phi_r[i]).tolist() == [i]
        assert np.isclose(phi_r[i, i], f[0], rtol=1e-12, atol=0)


def test_from_spec_finds_the_graph_file_by_name(tmp_path, monkeypatch):
    from harness import spec

    (tmp_path / "triangle_tail.py").write_text(
        "import numpy as np\n\n\n"
        "def edges(spec):\n"
        "    return np.array([[0, 1], [1, 2], [2, 0], [2, 3]]), spec['n']\n")
    monkeypatch.setattr(spec, "GRAPHS_DIR", str(tmp_path))
    adj = reference.Adjacency.from_spec({"generator": "triangle_tail", "n": 5})
    assert adj.offsets.tolist() == [0, 2, 4, 7, 8, 8]
    assert adj.nbr.tolist() == [1, 2, 0, 2, 0, 1, 3, 2]
    assert adj.deg.tolist() == [2, 2, 3, 1, 0]


def test_resolve_refuses_a_graph_without_its_file(tmp_path):
    from harness import spec

    bench = {"configs": [{"name": "x", "file": "bench/configs/x.json"}],
             "workloads": [{"name": "x.fit", "config": "x", "traffic": "fit",
                            "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "configs" / "x.json").write_text(
        json.dumps({"graph": {"generator": "no_such_graph", "n": 5}}))
    with pytest.raises(ValueError) as err:
        spec.resolve("x.fit", root=str(tmp_path))
    assert spec.graph_file("no_such_graph") in str(err.value)
    assert os.path.join("harness", "graphs", "no_such_graph.py") in str(
        err.value)


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
