"""Variance-reduced walk schemes (DESIGN.md §3.9): exactness + variance.

The scheme axis ("iid" | "antithetic" | "qmc" | "grfspp") must not change
*what* the sampler estimates — only the variance of the estimate.  These
tests pin that contract down: iid is bit-frozen against golden checksums,
antithetic streams are exact mirrors, every scheme keeps the chunking /
subset / kernel-parity invariances of the counter RNG, and the
variance-reduced schemes measurably beat iid on a fixed small graph.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features, kernels_exact, modulation, walks
from repro.graphs import generators
from repro.kernels.walk_sampler import rng, walk_sample, walk_sample_ref
from repro.kernels.walk_sampler.rng import SCHEMES

VR_SCHEMES = [s for s in SCHEMES if s != "iid"]


@pytest.fixture(scope="module")
def grid36():
    return generators.grid2d(6, 6)


@pytest.fixture(scope="module")
def grid100():
    return generators.grid2d(10, 10)


def test_iid_bit_exact_golden(grid36):
    """scheme="iid" reproduces the pre-scheme sampler bit-for-bit.

    Checksums of grid2d(6,6), PRNGKey(12345), 5 walkers, p_halt=0.2,
    l_max=3.  cols/lens are CRCed raw; loads get a float-sum window because
    XLA may re-associate the load product chain across compiler versions.

    The walk seed is ``jax.random.bits(key)`` (core/walks.walk_seed), whose
    value depends on jax's PRNG implementation: under jax 0.9.0 this key
    gives seed 1382428670, and cols and the loads sum moved with it (lens
    is seed-independent and kept its old checksum).  The sampler itself is
    unchanged; the seed is pinned here so a future move shows up as a seed
    change, not as a sampler change."""
    key = jax.random.PRNGKey(12345)
    assert int(walks.walk_seed(key)) == 1382428670
    tr = walks.sample_walks(grid36, key, n_walkers=5,
                            p_halt=0.2, l_max=3, scheme="iid")
    cols, loads, lens = np.array(tr.cols), np.array(tr.loads), np.array(tr.lens)
    assert zlib.crc32(cols.tobytes()) == 419449019
    assert zlib.crc32(lens.tobytes()) == 1932814751
    assert abs(float(loads.astype(np.float64).sum()) - 139.9113325179) < 1e-4
    assert abs(float(np.abs(loads).max()) - 0.5524272323) < 1e-6


def test_antithetic_halt_streams_are_exact_mirrors():
    """Walker 2k+1 reads walker 2k's halt stream reflected: u ↦ 1 − u,
    exactly (float32 1−u is exact for u ∈ [0,1])."""
    seed = jnp.uint32(7)
    node = jnp.arange(64, dtype=jnp.uint32)
    for ctr in (1, 3, 5):
        even = rng.halt_uniform(seed, node, jnp.uint32(2), jnp.uint32(ctr),
                                scheme="antithetic")
        odd = rng.halt_uniform(seed, node, jnp.uint32(3), jnp.uint32(ctr),
                               scheme="antithetic")
        np.testing.assert_array_equal(np.array(odd),
                                      1.0 - np.array(even))
        # ...and the even member is the plain iid stream of walker 2.
        base = rng.halt_uniform(seed, node, jnp.uint32(2), jnp.uint32(ctr),
                                scheme="iid")
        np.testing.assert_array_equal(np.array(even), np.array(base))


def test_qmc_stream_is_stratified_and_in_range():
    """The digitally-shifted van der Corput stream over walkers fills every
    1/W-width cell exactly once per (seed, node, ctr) — the stratification
    that buys the variance reduction — and stays inside [0, 1)."""
    seed, node, ctr = jnp.uint32(3), jnp.uint32(17), jnp.uint32(5)
    w = 16
    u = np.array([
        float(rng.halt_uniform(seed, node, jnp.uint32(k), ctr, scheme="qmc"))
        for k in range(w)
    ])
    assert (u >= 0.0).all() and (u < 1.0).all()
    cells = np.floor(u * w).astype(int)
    assert sorted(cells) == list(range(w)), cells


@pytest.mark.parametrize("scheme", ["antithetic", "qmc", "grfspp"])
def test_scheme_preserves_walk_structure_vs_choice_stream(grid36, scheme):
    """Schemes only touch termination: grfspp shares iid's cols/lens
    bit-exactly (no halt draws at all), and every scheme's deposits stay on
    the graph with the l=0 self-deposit intact."""
    key = jax.random.PRNGKey(5)
    kw = dict(n_walkers=6, p_halt=0.25, l_max=3)
    tr = walks.sample_walks(grid36, key, **kw, scheme=scheme)
    if scheme == "grfspp":
        base = walks.sample_walks(grid36, key, **kw, scheme="iid")
        np.testing.assert_array_equal(np.array(tr.cols), np.array(base.cols))
        np.testing.assert_array_equal(np.array(tr.lens), np.array(base.lens))
    lens = np.array(tr.lens).reshape(grid36.n_nodes, kw["n_walkers"],
                                     kw["l_max"] + 1)
    assert (lens[:, :, 0] == 0).all()
    cols0 = np.array(tr.cols).reshape(lens.shape)[:, :, 0]
    np.testing.assert_array_equal(
        cols0, np.arange(grid36.n_nodes)[:, None] * np.ones_like(cols0))


@pytest.mark.parametrize("scheme", list(SCHEMES))
def test_deposit_distribution_per_scheme(grid100, scheme):
    """One-step deposits from an interior grid node are uniform over its 4
    neighbours under every scheme (chi-squared, df=3) — the direction-choice
    stream is scheme-independent by construction."""
    g = grid100
    start = jnp.asarray([55], jnp.int32)
    hist = np.zeros(g.n_nodes)
    for s in range(40):
        tr = walks.sample_walks_for_nodes(
            g, start, jax.random.PRNGKey(s), 64, 0.0, 1, scheme=scheme)
        c = np.array(tr.cols).reshape(64, 2)[:, 1]
        np.add.at(hist, c, 1)
    nbrs = np.array(g.neighbors[55, : int(g.deg[55])])
    obs = hist[nbrs]
    assert obs.sum() == hist.sum() == 64 * 40, f"{scheme}: off-neighbour deposit"
    expected = hist.sum() / len(nbrs)
    chi2 = float(((obs - expected) ** 2 / expected).sum())
    # df=3, P(chi2 > 16.3) ≈ 0.001
    assert chi2 < 16.3, (scheme, chi2, obs)


@pytest.mark.parametrize("scheme", list(SCHEMES))
def test_chunked_and_subset_invariance_per_scheme(grid100, scheme):
    """The counter RNG keys on the *absolute* node id, so chunked and
    subset sampling draw rows of the same Φ under every scheme — the
    invariance the lazy/сhunked/distributed paths are built on."""
    cfg = walks.WalkConfig(6, 0.25, 4, scheme=scheme)
    key = jax.random.PRNGKey(3)
    full = walks.sample_walks(grid100, key, cfg.n_walkers, cfg.p_halt,
                              cfg.l_max, scheme=scheme)
    parts = [tr for _, tr in walks.walk_chunks(grid100, key, cfg, chunk=13)]
    np.testing.assert_array_equal(
        np.concatenate([np.array(t.cols) for t in parts]), np.array(full.cols))
    np.testing.assert_allclose(
        np.concatenate([np.array(t.loads) for t in parts]),
        np.array(full.loads), rtol=1e-6, atol=1e-9)
    nodes = jnp.asarray([5, 17, 60], jnp.int32)
    sub = walks.sample_walks_for_nodes(grid100, nodes, key, cfg.n_walkers,
                                       cfg.p_halt, cfg.l_max, scheme=scheme)
    np.testing.assert_array_equal(np.array(sub.cols),
                                  np.array(full.cols)[np.array(nodes)])
    np.testing.assert_allclose(np.array(sub.loads),
                               np.array(full.loads)[np.array(nodes)],
                               rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("scheme", list(SCHEMES))
def test_kernel_matches_oracle_per_scheme(grid100, scheme):
    """Pallas-interpret and the jnp oracle share ref.walk_block, so parity
    must hold for every scheme, including the ragged final block."""
    g = grid100
    nodes = jnp.arange(37, dtype=jnp.int32)
    seed = jnp.uint32(99)
    kw = dict(n_walkers=6, p_halt=0.25, l_max=4, scheme=scheme)
    ref = walk_sample_ref(g.walk_table, g.deg, nodes, seed, **kw)
    ker = walk_sample(g.walk_table, g.deg, nodes, seed,
                      block_m=8, interpret=True, **kw)
    np.testing.assert_array_equal(np.array(ref[0]), np.array(ker[0]))
    np.testing.assert_array_equal(np.array(ref[2]), np.array(ker[2]))
    np.testing.assert_allclose(np.array(ref[1]), np.array(ker[1]),
                               rtol=1e-6, atol=1e-9)


def _khat_mse(graph, f, k_target, scheme, seeds, n_walkers=8, p_halt=0.3,
              l_max=3):
    off = ~np.eye(graph.n_nodes, dtype=bool)
    errs = []
    for s in seeds:
        tr = walks.sample_walks(graph, jax.random.PRNGKey(s), n_walkers,
                                p_halt, l_max, scheme=scheme)
        k_hat = np.array(features.materialize_khat(tr, f))
        errs.append(((k_hat - k_target)[off] ** 2).mean())
    return float(np.mean(errs))


def test_variance_ordering(grid36):
    """Every variance-reduced scheme beats iid kernel-MSE on the fixed
    grid (30 seeds; deterministic given the counter RNG, so the inequality
    is stable, not a flaky statistical bound)."""
    mod = modulation.diffusion(l_max=3, init_beta=1.0)
    f = mod(mod.init(jax.random.PRNGKey(0)))
    k_target = np.array(kernels_exact.truncated_power_series_kernel(grid36, f))
    seeds = range(30)
    mse = {s: _khat_mse(grid36, f, k_target, s, seeds) for s in SCHEMES}
    for scheme in VR_SCHEMES:
        assert mse[scheme] < mse["iid"], mse
    # grfspp Rao-Blackwellises termination outright — it should not just
    # edge out iid but dominate the pairing/stratification schemes too.
    assert mse["grfspp"] < min(mse["antithetic"], mse["qmc"]), mse


@pytest.mark.parametrize("scheme", ["grfspp", "qmc"])
def test_scheme_estimator_unbiased(grid36, scheme):
    """E[K̂] still matches the truncated power series under the reweighted /
    stratified termination (the Thm. 1 contract survives the scheme axis)."""
    mod = modulation.diffusion(l_max=3, init_beta=1.0)
    f = mod(mod.init(jax.random.PRNGKey(0)))
    k_target = np.array(kernels_exact.truncated_power_series_kernel(grid36, f))
    acc = 0.0
    reps = 60
    for s in range(reps):
        tr = walks.sample_walks(grid36, jax.random.PRNGKey(s), n_walkers=12,
                                p_halt=0.3, l_max=3, scheme=scheme)
        acc = acc + np.array(features.materialize_khat(tr, f))
    acc /= reps
    off = ~np.eye(grid36.n_nodes, dtype=bool)
    err = np.abs(acc - k_target)[off].max()
    assert err < 0.2 * np.abs(k_target[off]).max(), err


def test_walkconfig_rejects_unknown_scheme():
    with pytest.raises(ValueError, match="scheme"):
        walks.WalkConfig(4, 0.2, 3, scheme="sobol")
