"""Wind-speed interpolation on the sphere (paper §4.2, ERA5 stand-in):
implicit manifold GP regression via a kNN graph + GRF kernels.

    PYTHONPATH=src python examples/wind_interpolation.py --nodes 2000
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features, modulation, walks
from repro.gp import mll, posterior
from repro.graphs import generators, signals
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--walkers", type=int, default=100)
    args = ap.parse_args()
    enable_compile_cache()

    g, xyz = generators.knn_sphere(args.nodes, k=6, seed=0)
    wind = signals.wind_field_sphere(xyz, seed=0)
    n = g.n_nodes

    # training set = a satellite-track-like band sweeping the sphere
    rng = np.random.default_rng(0)
    lon = np.arctan2(xyz[:, 1], xyz[:, 0])
    lat = np.arcsin(np.clip(xyz[:, 2], -1, 1))
    track = np.abs(np.sin(3 * lon) * 0.8 - np.sin(lat)) < 0.15
    train = np.where(track)[0]
    if len(train) < 30:
        train = rng.choice(n, n // 5, replace=False)
    test = np.setdiff1d(np.arange(n), train)
    y = jnp.asarray(wind[train] + 0.05 * rng.standard_normal(len(train)), jnp.float32)
    print(f"sphere kNN graph: {n} nodes; track observations: {len(train)}")

    tr = walks.sample_walks(g, jax.random.PRNGKey(0), n_walkers=args.walkers,
                            p_halt=0.1, l_max=8)
    for name, mod in (("diffusion-shape", modulation.diffusion(l_max=8)),
                      ("fully-learnable", modulation.learnable(l_max=8))):
        fit = mll.fit_hyperparams(
            features.take_rows(tr, jnp.asarray(train)), mod, y, n,
            jax.random.PRNGKey(1), steps=80, lr=0.08,
        )
        f = mod(fit.params["mod"])
        s2 = mll.noise_var(fit.params)
        samples = posterior.pathwise_samples(
            tr, jnp.asarray(train), f, s2, y, jax.random.PRNGKey(2), n_samples=64)
        m, v = posterior.predictive_moments_from_samples(samples)
        rmse = float(posterior.rmse(jnp.asarray(wind)[test], m[test]))
        nlpd = float(posterior.gaussian_nlpd(jnp.asarray(wind)[test],
                                             m[test], v[test] + s2))
        print(f"{name:16s}: test RMSE {rmse:.4f}  NLPD {nlpd:.4f}")


if __name__ == "__main__":
    main()
