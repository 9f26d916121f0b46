"""Inputs made from a configuration and ``--seed``: graph, walks, keys,
signals.  The program receives only what these build."""
from __future__ import annotations

import inspect
import sys
import time

import numpy as np

_T0 = time.perf_counter()


def log(msg: str) -> None:
    """A progress line on standard error, with seconds since import."""
    print(f"[{time.perf_counter() - _T0:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


def prng_key(seed: int):
    """A JAX key from a seed of any size (``PRNGKey`` keeps only the low
    32 bits, so the high bits are folded in)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def np_rng(seed: int, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng([salt, seed])


def build_graph(spec: dict):
    """``repro.graphs.generators.<generator>(**params)``, on the device."""
    import jax
    from repro.graphs import generators

    params = {k: v for k, v in spec.items() if k != "generator"}
    graph = jax.block_until_ready(
        getattr(generators, spec["generator"])(**params))
    deg = np.asarray(graph.deg, np.int64)
    log(f"graph {spec['generator']} {params}: {graph.n_nodes} nodes, "
        f"max degree {deg.max()}, {deg.sum()} stored entries")
    return graph


def walk_config(spec: dict):
    from repro.core import walks

    return walks.WalkConfig(**spec)


def modulation(spec: dict, l_max: int):
    from repro.core import modulation as mod

    return mod.REGISTRY[spec["name"]](l_max=l_max)


def signal(spec: dict, seed: int, graph=None) -> np.ndarray:
    """Ground truth over all nodes: ``repro.graphs.signals.<name>``, its
    random draws keyed on the configuration's ``seed`` where it fixes the
    deployment's data, else on the run's ``seed``.  A signal defined on the
    graph (one with a ``graph`` parameter, such as an influence proxy read
    from degrees) is handed the graph the job built."""
    from repro.graphs import signals

    fn = getattr(signals, spec["name"])
    params = {k: v for k, v in spec.items()
              if k not in ("name", "noise_std", "seed")}
    if "graph" in inspect.signature(fn).parameters:
        params["graph"] = graph
    return np.asarray(fn(**params, seed=spec.get("seed", seed) % (2**32)),
                      np.float64)
