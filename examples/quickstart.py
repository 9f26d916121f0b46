"""Quickstart: scalable GP regression on a graph with GRFs.

    PYTHONPATH=src python examples/quickstart.py

Builds a road-like grid graph, samples a ground-truth signal from an exact
diffusion GP, then runs the paper's three-step workflow (kernel init via
random walks → LML hyperparameter learning → pathwise-conditioned posterior)
and compares against the O(N³) exact GP.

This materialises the full [N, K] walk trace — fine up to ~10⁵ nodes.  For
the chunked 10⁶-node path (lazy Φ, O(chunk·K) peak memory) see README.md
"The 10⁶-node path" and `posterior.pathwise_samples_chunked`.

``--scheme`` picks the walker variance-reduction scheme (DESIGN.md §3.9);
``--skip-exact`` drops the O(N³) dense baseline — the shape the CI
walk-scheme smoke step runs."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features, kernels_exact, modulation, walks
from repro.graphs import generators, signals
from repro.gp import exact, mll, posterior
from repro.kernels.walk_sampler.rng import SCHEMES
from repro.runtime import enable_compile_cache


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--scheme", choices=SCHEMES, default="iid",
                        help="walker variance-reduction scheme")
    parser.add_argument("--skip-exact", action="store_true",
                        help="skip the O(N^3) exact-GP baseline")
    args = parser.parse_args()
    enable_compile_cache()
    # --- problem: noisy observations of a smooth signal on a 20×20 grid ----
    g = generators.grid2d(20, 20)
    n = g.n_nodes
    k_true = kernels_exact.diffusion_kernel(g, beta=6.0)
    ytrue = np.array(signals.gp_sample_from_dense_kernel(np.array(k_true), seed=0))
    rng = np.random.default_rng(0)
    train = rng.choice(n, n // 4, replace=False)
    y = jnp.asarray(ytrue[train] + 0.1 * rng.standard_normal(len(train)), jnp.float32)
    test = np.setdiff1d(np.arange(n), train)
    print(f"graph: {n} nodes; observations: {len(train)}")

    # --- 1) kernel initialisation: GRF random walks (Alg. 1) ---------------
    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=100,
                            p_halt=0.1, l_max=10, scheme=args.scheme)
    print(f"GRF trace [{args.scheme}]: {tr.slots} deposit slots/node "
          f"({tr.loads.size * 12 / 1e6:.1f} MB total, vs "
          f"{n * n * 4 / 1e6:.1f} MB dense)")

    # --- 2) hyperparameter learning: iterative LML ascent (Eq. 8-11) -------
    mod = modulation.learnable(l_max=10)
    fit = mll.fit_hyperparams(
        features.take_rows(tr, jnp.asarray(train)), mod, y, n,
        jax.random.PRNGKey(1), steps=80, lr=0.08,
    )
    print("fit trace:", fit.history[-1])
    f = mod(fit.params["mod"])
    s2 = mll.noise_var(fit.params)

    # --- 3) posterior inference: pathwise conditioning (Eq. 12) ------------
    samples = posterior.pathwise_samples(
        tr, jnp.asarray(train), f, s2, y, jax.random.PRNGKey(2), n_samples=64
    )
    mean, var = posterior.predictive_moments_from_samples(samples)
    rmse = float(posterior.rmse(jnp.asarray(ytrue)[test], mean[test]))
    nlpd = float(posterior.gaussian_nlpd(jnp.asarray(ytrue)[test],
                                         mean[test], var[test] + s2))
    print(f"GRF-GP  : test RMSE {rmse:.4f}  NLPD {nlpd:.4f}")

    # --- exact O(N³) baseline ----------------------------------------------
    if args.skip_exact:
        return
    p_ex, k_full = exact.fit_exact_diffusion(g, jnp.asarray(train), y, steps=150)
    m_ex, v_ex = exact.cholesky_posterior(
        k_full, jnp.asarray(train), y, jnp.exp(2 * p_ex["log_sigma_n"]))
    print(f"exact GP: test RMSE "
          f"{float(posterior.rmse(jnp.asarray(ytrue)[test], m_ex[test])):.4f}  "
          f"NLPD {float(posterior.gaussian_nlpd(jnp.asarray(ytrue)[test], m_ex[test], v_ex[test] + jnp.exp(2 * p_ex['log_sigma_n']))):.4f}")


if __name__ == "__main__":
    main()
