"""GRF walk sampler: unbiasedness (Thm 1 context), sparsity, ablation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features, kernels_exact, modulation, walks
from repro.graphs import formats, generators


@pytest.fixture(scope="module")
def ring32():
    return generators.ring(32, k=2)


def test_unbiased_offdiagonal(ring32):
    """E[ΦΦᵀ] matches the truncated power series Ψᵀ_truncΨ_trunc off-diagonal."""
    mod = modulation.diffusion(l_max=6, init_beta=1.0)
    f = mod(mod.init(jax.random.PRNGKey(0)))
    k_target = np.array(kernels_exact.truncated_power_series_kernel(ring32, f))

    reps, acc = 120, 0.0
    for s in range(reps):
        tr = walks.sample_walks(ring32, jax.random.PRNGKey(s), n_walkers=20,
                                p_halt=0.2, l_max=6)
        acc = acc + np.array(features.materialize_khat(tr, f))
    acc /= reps
    off = ~np.eye(32, dtype=bool)
    err = np.abs(acc - k_target)[off].max()
    scale = np.abs(k_target[off]).max()
    assert err < 0.15 * scale, (err, scale)


def test_diagonal_bias_shrinks_with_walkers(ring32):
    """Footnote 3: diagonal bias is O(1/n)."""
    mod = modulation.diffusion(l_max=6)
    f = mod(mod.init(jax.random.PRNGKey(0)))
    k_target = np.array(kernels_exact.truncated_power_series_kernel(ring32, f))

    def diag_bias(n_walkers, reps=60):
        acc = 0.0
        for s in range(reps):
            tr = walks.sample_walks(ring32, jax.random.PRNGKey(1000 + s),
                                    n_walkers=n_walkers, p_halt=0.2, l_max=6)
            acc = acc + np.array(features.materialize_khat(tr, f))
        return np.abs(np.diag(acc / reps) - np.diag(k_target)).mean()

    assert diag_bias(40) < diag_bias(5)


def test_sparsity_bound(ring32):
    """Thm 1: nnz per feature stays O(n/p) — every deposit is one of
    n·(l_max+1) slots, and live slots decay geometrically with p_halt."""
    tr = walks.sample_walks(ring32, jax.random.PRNGKey(0), n_walkers=10,
                            p_halt=0.5, l_max=20)
    nnz = np.asarray(features.nnz_per_row(tr))
    assert nnz.max() <= 10 * 21
    # With p=0.5, mean walk length ≈ 2 ⇒ nnz ≪ slot count.
    assert nnz.mean() < 10 * 6


def test_halting_masks_deposits(ring32):
    """Post-termination deposits must carry zero load."""
    tr = walks.sample_walks(ring32, jax.random.PRNGKey(3), n_walkers=4,
                            p_halt=0.9, l_max=8)
    loads = np.asarray(tr.loads).reshape(32, 4, 9)
    # with p_halt=0.9 almost every walker dies quickly: later steps ~ all zero
    assert (loads[:, :, -1] == 0).mean() > 0.95


def test_adhoc_kernel_differs_and_biased(ring32):
    """Ablation (Eq. 16): removing IS reweighting changes the estimate."""
    mod = modulation.diffusion(l_max=6)
    f = mod(mod.init(jax.random.PRNGKey(0)))
    k_target = np.array(kernels_exact.truncated_power_series_kernel(ring32, f))
    reps = 60
    acc = 0.0
    for s in range(reps):
        tr = walks.sample_walks(ring32, jax.random.PRNGKey(s), n_walkers=20,
                                p_halt=0.2, l_max=6, reweight=False)
        acc = acc + np.array(features.materialize_khat(tr, f))
    acc /= reps
    off = ~np.eye(32, dtype=bool)
    err = np.abs(acc - k_target)[off].max()
    scale = np.abs(k_target[off]).max()
    assert err > 0.3 * scale  # systematically biased, not just noisy


def test_subset_walks_match_full(ring32):
    nodes = jnp.asarray([3, 7, 11])
    tr = walks.sample_walks_for_nodes(ring32, nodes, jax.random.PRNGKey(0),
                                      n_walkers=5, p_halt=0.2, l_max=4)
    assert tr.cols.shape == (3, 5 * 5)
    assert np.isfinite(np.asarray(tr.loads)).all()


def _raw_weights_with_isolated():
    r = np.random.default_rng(11)
    edges = r.integers(0, 40, size=(90, 2))
    edges = edges[edges[:, 0] != edges[:, 1]]
    return formats.from_edges(edges, 50, weights=r.uniform(0.1, 3.0, len(edges)),
                              normalize=False)


def _rebuilt_from_ell():
    g = _raw_weights_with_isolated()
    return formats.from_ell(g.neighbors, g.weights, g.deg)


@pytest.mark.parametrize("build", [
    lambda: generators.grid2d(7, 9), _raw_weights_with_isolated,
    _rebuilt_from_ell,
], ids=["from_edges_grid", "from_edges_weighted_isolated", "from_ell"])
def test_walk_table_packs_each_slot(build):
    """Row i·max_deg + k of ``walk_table`` is (neighbors[i, k], the bits of
    weights[i, k], deg[neighbors[i, k]]), padding slots included: they hold
    node 0 and its degree."""
    g = build()
    nbr, wgt = np.asarray(g.neighbors), np.asarray(g.weights)
    deg, table = np.asarray(g.deg), np.asarray(g.walk_table)
    assert table.dtype == np.int32
    assert table.shape == (g.n_nodes * g.max_deg, 3)
    np.testing.assert_array_equal(table[:, 0], nbr.reshape(-1))
    np.testing.assert_array_equal(table[:, 1],
                                  wgt.astype(np.float32).view(np.int32).reshape(-1))
    np.testing.assert_array_equal(table[:, 2], deg[nbr].reshape(-1))
    pad = np.arange(g.max_deg)[None, :] >= deg[:, None]
    if pad.any():
        assert (table[pad.reshape(-1), 0] == 0).all()
        assert (table[pad.reshape(-1), 2] == deg[0]).all()
