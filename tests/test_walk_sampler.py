"""Walk-sampler kernel: oracle parity, deposit statistics, chunked paths."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features, linops, modulation, walks
from repro.graphs import formats, generators
from repro.kernels import dispatch
from repro.kernels.walk_sampler import rng, walk_sample, walk_sample_ref


@pytest.fixture(scope="module")
def grid100():
    return generators.grid2d(10, 10)


CFG = dict(n_walkers=6, p_halt=0.25, l_max=4)


def _assert_traces_match(ref, got):
    """cols/lens must be bit-exact (shared counter RNG ⇒ identical walk
    structure); loads are float chains that XLA may fuse differently across
    compilations (FMA contraction), so they match to a few ulps."""
    np.testing.assert_array_equal(np.array(ref[0]), np.array(got[0]))
    np.testing.assert_array_equal(np.array(ref[2]), np.array(got[2]))
    np.testing.assert_allclose(np.array(ref[1]), np.array(got[1]),
                               rtol=1e-6, atol=1e-9)


def test_kernel_matches_oracle(grid100):
    """Pallas-interpret and the jnp oracle share the counter RNG — the
    deposit structure is identical, not just distributionally close."""
    g = grid100
    nodes = jnp.arange(g.n_nodes, dtype=jnp.int32)
    seed = jnp.uint32(99)
    ref = walk_sample_ref(g.walk_table, g.deg, nodes, seed, **CFG)
    ker = walk_sample(g.walk_table, g.deg, nodes, seed,
                      interpret=True, **CFG)
    _assert_traces_match(ref, ker)


@pytest.mark.parametrize("block_m", [8, 32, 256])
def test_kernel_block_size_invariance(grid100, block_m):
    g = grid100
    nodes = jnp.arange(37, dtype=jnp.int32)  # non-multiple of every block
    seed = jnp.uint32(7)
    ref = walk_sample_ref(g.walk_table, g.deg, nodes, seed, **CFG)
    ker = walk_sample(g.walk_table, g.deg, nodes, seed,
                      block_m=block_m, interpret=True, **CFG)
    _assert_traces_match(ref, ker)


def test_deposit_distribution_backends_match(grid100):
    """Chi-squared: deposit-column histograms from the xla and
    pallas-interpret backends are draws from the same distribution.

    Different seeds (else the test is vacuous given bit-parity); one-step
    deposits from a fixed start node land on its 4 grid neighbours
    uniformly, so we chi-square each backend against that exact law."""
    g = grid100
    start = jnp.asarray([55], jnp.int32)  # interior node: degree 4
    kw = dict(n_walkers=64, p_halt=0.0, l_max=1)
    counts = {}
    for backend, seed0 in (("xla", 0), ("pallas-interpret", 10_000)):
        hist = np.zeros(g.n_nodes)
        for s in range(40):
            with dispatch.use_backend(backend):
                cols, loads, lens = dispatch.walk_sample(
                    g.walk_table, g.deg, start,
                    jnp.uint32(seed0 + s), **kw,
                )
            c = np.array(cols).reshape(64, 2)[:, 1]  # the l=1 deposit column
            np.add.at(hist, c, 1)
        counts[backend] = hist
    nbrs = np.array(g.neighbors[55, : int(g.deg[55])])
    for backend, hist in counts.items():
        assert hist.sum() == 64 * 40
        obs = hist[nbrs]
        assert obs.sum() == hist.sum(), f"{backend}: off-neighbour deposit"
        expected = hist.sum() / len(nbrs)
        chi2 = float(((obs - expected) ** 2 / expected).sum())
        # df=3, P(chi2 > 16.3) ≈ 0.001
        assert chi2 < 16.3, (backend, chi2, obs)


def test_moments_match_legacy_estimator(grid100):
    """E[K̂] from the dispatched sampler still matches the truncated power
    series (the Thm. 1 unbiasedness contract survived the RNG swap)."""
    from repro.core import kernels_exact

    mod = modulation.diffusion(l_max=4, init_beta=1.0)
    f = mod(mod.init(jax.random.PRNGKey(0)))
    k_target = np.array(kernels_exact.truncated_power_series_kernel(grid100, f))
    acc = 0.0
    reps = 80
    for s in range(reps):
        tr = walks.sample_walks(grid100, jax.random.PRNGKey(s), n_walkers=20,
                                p_halt=0.2, l_max=4)
        acc = acc + np.array(features.materialize_khat(tr, f))
    acc /= reps
    off = ~np.eye(grid100.n_nodes, dtype=bool)
    err = np.abs(acc - k_target)[off].max()
    assert err < 0.2 * np.abs(k_target[off]).max(), err


def test_chunked_trace_equals_monolithic(grid100):
    cfg = walks.WalkConfig(**CFG)
    key = jax.random.PRNGKey(3)
    full = walks.sample_walks(grid100, key, cfg.n_walkers, cfg.p_halt,
                              cfg.l_max)
    parts = [tr for _, tr in walks.walk_chunks(grid100, key, cfg, chunk=13)]
    np.testing.assert_array_equal(
        np.concatenate([np.array(t.cols) for t in parts]), np.array(full.cols))
    np.testing.assert_allclose(
        np.concatenate([np.array(t.loads) for t in parts]),
        np.array(full.loads), rtol=1e-6, atol=1e-9)
    # subset sampling is row-consistent with the full trace
    nodes = jnp.asarray([5, 17, 60], jnp.int32)
    sub = walks.sample_walks_for_nodes(grid100, nodes, key, cfg.n_walkers,
                                       cfg.p_halt, cfg.l_max)
    np.testing.assert_array_equal(np.array(sub.cols),
                                  np.array(full.cols)[np.array(nodes)])
    np.testing.assert_allclose(np.array(sub.loads),
                               np.array(full.loads)[np.array(nodes)],
                               rtol=1e-6, atol=1e-9)


def test_chunked_khat_agrees_through_operator_layer(grid100):
    """K̂v via ChunkedPhiOperator == dense K̂ = ΦΦᵀ from the materialised
    trace — the operator-layer acceptance check for the lazy path."""
    cfg = walks.WalkConfig(**CFG)
    key = jax.random.PRNGKey(4)
    mod = modulation.diffusion(l_max=cfg.l_max)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    tr = walks.sample_walks(grid100, key, cfg.n_walkers, cfg.p_halt, cfg.l_max)
    k_dense = np.array(features.materialize_khat(tr, f))
    v = np.random.default_rng(0).standard_normal(grid100.n_nodes).astype(
        np.float32)
    got = linops.chunked_khat(grid100, f, key, cfg, chunk=33).matvec(
        jnp.asarray(v))
    want = k_dense @ v
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.array(got) / scale, want / scale,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("padded", [False, True], ids=["unmasked", "padded"])
def test_chunked_pathwise_equals_monolithic(grid100, padded):
    """Both draws stream Φ once as Φ(w + Φ_xᵀα); with ``padded`` the last
    observation slots are dead (mask 0), as in the BO loop's buffer."""
    from repro.gp import posterior

    cfg = walks.WalkConfig(n_walkers=8, p_halt=0.2, l_max=4)
    key, wkey = jax.random.PRNGKey(0), jax.random.PRNGKey(42)
    mod = modulation.diffusion(l_max=4)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    rng = np.random.default_rng(0)
    train = jnp.asarray(rng.choice(grid100.n_nodes, 30, replace=False))
    y = jnp.asarray(rng.standard_normal(30), jnp.float32)
    tr = walks.sample_walks(grid100, wkey, cfg.n_walkers, cfg.p_halt,
                            cfg.l_max)
    mask = (jnp.arange(30) < 22).astype(jnp.float32) if padded else None
    mono = posterior.pathwise_samples(tr, train, f, 0.05, y, key, n_samples=3,
                                      obs_mask=mask)
    chnk = posterior.pathwise_samples_chunked(grid100, train, f, 0.05, y, key,
                                              wkey, cfg, chunk=29, n_samples=3,
                                              obs_mask=mask)
    np.testing.assert_allclose(np.array(mono), np.array(chnk),
                               rtol=1e-4, atol=1e-4)


def test_isolated_node_zero_load():
    """Degree-0 nodes deposit their own start (l=0) then go dead."""
    from repro.graphs.formats import from_ell

    g = generators.ring(8, k=1)
    iso = from_ell(g.neighbors, g.weights, g.deg.at[3].set(0))
    tr = walks.sample_walks(iso, jax.random.PRNGKey(0), n_walkers=4,
                            p_halt=0.2, l_max=3)
    loads = np.array(tr.loads).reshape(8, 4, 4)
    assert (loads[3, :, 0] != 0).all()      # the l=0 self-deposit survives
    assert (loads[3, :, 1:] == 0).all()     # everything after is masked


def _three_table_walk_block(neighbors, weights, deg, nodes, seed, *,
                            n_walkers, p_halt, l_max, scheme):
    """The sampler as it was before the packed walk table: each move read
    ``deg[cur]``, ``neighbors[flat]`` and ``weights[flat]``.  Frozen here as
    the reference the packed table must reproduce bit for bit."""
    m = nodes.shape[0]
    max_deg = neighbors.shape[1]
    nbr_flat = neighbors.reshape(-1)
    wgt_flat = weights.reshape(-1)
    node_u = nodes.astype(jnp.uint32)[:, None]
    walker_u = jnp.arange(n_walkers, dtype=jnp.uint32)[None, :]
    cur = jnp.broadcast_to(nodes[:, None], (m, n_walkers)).astype(jnp.int32)
    load = jnp.ones((m, n_walkers), jnp.float32)
    alive = jnp.ones((m, n_walkers), jnp.float32)
    cols_steps, loads_steps = [], []
    for step in range(l_max + 1):
        cols_steps.append(cur)
        if scheme == "grfspp":
            loads_steps.append(
                load * alive * jnp.float32((1.0 - p_halt) ** step))
        else:
            loads_steps.append(load * alive)
        u_choice = rng.counter_uniform(seed, node_u, walker_u, 2 * step)
        d = jnp.take(deg, cur)
        choice = jnp.minimum(
            (u_choice * d.astype(jnp.float32)).astype(jnp.int32),
            jnp.maximum(d - 1, 0),
        )
        flat = cur * max_deg + choice
        nxt = jnp.take(nbr_flat, flat)
        w = jnp.take(wgt_flat, flat)
        load = load * d.astype(jnp.float32) / (1.0 - p_halt) * w
        if scheme != "grfspp":
            u_halt = rng.halt_uniform(seed, node_u, walker_u, 2 * step + 1,
                                      scheme=scheme)
            alive = alive * (u_halt >= p_halt).astype(jnp.float32)
        alive = alive * (d > 0).astype(jnp.float32)
        cur = nxt
    k = n_walkers * (l_max + 1)
    cols = jnp.stack(cols_steps, axis=-1).reshape(m, k).astype(jnp.int32)
    loads = (jnp.stack(loads_steps, axis=-1) / n_walkers).reshape(m, k)
    lens = jnp.broadcast_to(
        jnp.arange(l_max + 1, dtype=jnp.int32), (m, n_walkers, l_max + 1)
    ).reshape(m, k)
    return cols, loads, lens


def _weighted_with_isolated():
    """A raw-weight (``normalize=False``) random graph whose last 15 of 60
    nodes have no edge."""
    r = np.random.default_rng(5)
    edges = r.integers(0, 45, size=(120, 2))
    edges = edges[edges[:, 0] != edges[:, 1]]
    return formats.from_edges(edges, 60, weights=r.uniform(0.1, 2.0, len(edges)),
                              normalize=False)


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("graph", ["grid2d", "weighted_isolated"])
@pytest.mark.parametrize("scheme", ["iid", "antithetic", "qmc", "grfspp"])
def test_packed_table_equals_three_table_step(scheme, graph, backend):
    """One row gather per move from ``Graph.walk_table`` gives the same
    cols, loads and lens, to the bit, as the three-table step: the same
    degree and weight values enter the same arithmetic in the same order,
    isolated nodes and padding slots included."""
    g = (generators.grid2d(10, 12) if graph == "grid2d"
         else _weighted_with_isolated())
    assert int(jnp.min(g.deg)) == (2 if graph == "grid2d" else 0)
    nodes = jnp.arange(g.n_nodes, dtype=jnp.int32)
    seed = jnp.uint32(3616000001)
    kw = dict(n_walkers=30, p_halt=0.15, l_max=5, scheme=scheme)
    want = jax.jit(functools.partial(_three_table_walk_block, **kw))(
        g.neighbors, g.weights, g.deg, nodes, seed)
    got = jax.jit(functools.partial(dispatch.walk_sample, backend=backend,
                                    **kw))(g.walk_table, g.deg, nodes, seed)
    for w, o in zip(want, got):
        assert w.dtype == o.dtype
        np.testing.assert_array_equal(np.asarray(w).view(np.uint32),
                                      np.asarray(o).view(np.uint32))
