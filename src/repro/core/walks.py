"""GRF random-walk sampling (paper Alg. 1/2) — backend-dispatched + chunked.

Alg. 2's data-dependent ``while`` loop is replaced by fixed-length masked
stepping: a halted walker keeps moving but its deposits are masked to zero.
The deposit distribution is identical (masking == rejection at the deposit
stage) and every shape is static, which makes the sampler jit-able,
vmap-able and shard_map-able (DESIGN.md §3.6).

Sampling itself is dispatched through repro.kernels.dispatch ("xla" |
"pallas" | "pallas-interpret"): the jnp oracle and the Pallas walker kernel
share a counter-based RNG keyed on (seed, absolute start node, walker,
step), so the trace for a node block is *independent of how the blocks are
cut*.  That invariance is what the chunked drivers below — and the chunked
operators in core/linops.py — are built on: sampling N nodes monolithically,
in 65536-row chunks, or shard-by-shard yields the same rows (walk structure
bit-exact; loads to FMA-contraction ulps across compilations).

The output is a :class:`WalkTrace` — a *structure-only* ELL representation
``(cols, loads, lens)``.  Feature values are ``loads * f[lens]`` for a
modulation vector ``f``; keeping ``f`` out of the trace makes the kernel
hyperparameters differentiable without re-simulating walks.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp

from .. import obs
from ..graphs.formats import Graph
from ..kernels import dispatch
from ..kernels.walk_sampler.rng import SCHEMES

DEFAULT_CHUNK = 65536


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class WalkTrace:
    """ELL-format walk deposits for a block of nodes.

    K = n_walkers * (l_max + 1) deposit slots per node.

    Attributes:
      cols:  int32[N, K] — deposit column (node where the prefix subwalk ends).
      loads: float32[N, K] — importance-sampling load, already divided by n.
              Zero for masked (post-termination) deposits.
      lens:  int32[N, K] — prefix subwalk length l of each deposit.
    """

    cols: jax.Array
    loads: jax.Array
    lens: jax.Array

    @property
    def n_nodes(self) -> int:
        return self.cols.shape[0]

    @property
    def slots(self) -> int:
        return self.cols.shape[1]

    def tree_flatten(self):
        return (self.cols, self.loads, self.lens), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class WalkConfig:
    """Hashable walk-sampling hyperparameters (static under jit).

    Bundles what every sampling call needs so the chunked operators and the
    distributed shard path can carry one value instead of four.

    ``scheme`` picks the walker variance-reduction strategy ("iid" |
    "antithetic" | "qmc" | "grfspp" — DESIGN.md §3.9).  It is part of this
    frozen config, so like the spmv backend it rides every jit cache key as
    a static and flows unchanged through the chunked / sharded / serving
    paths."""

    n_walkers: int
    p_halt: float = 0.1
    l_max: int = 10
    reweight: bool = True
    scheme: str = "iid"

    def __post_init__(self):
        if self.scheme not in SCHEMES:
            raise ValueError(
                f"unknown walk scheme {self.scheme!r}; valid: {SCHEMES}"
            )

    @property
    def slots(self) -> int:
        return self.n_walkers * (self.l_max + 1)


def walk_seed(key: jax.Array) -> jax.Array:
    """Derive the uint32 counter-RNG seed from a PRNG key.

    Every API that samples walks derives its seed through this function, so
    passing the same key to ``sample_walks``, ``sample_walks_for_nodes`` or
    a chunked operator yields rows of the *same* underlying Φ."""
    return jax.random.bits(key, (), jnp.uint32)


@partial(jax.jit, static_argnames=("cfg", "spmv_backend", "obs_tap"))
def _sample(graph: Graph, nodes: jax.Array, seed: jax.Array,
            *, cfg: WalkConfig, spmv_backend: str,
            obs_tap: bool = False) -> WalkTrace:
    # obs_tap rides the jit cache key (like spmv_backend) and pins the
    # trace, so flipping observability retraces with taps staged in/out.
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend):
        cols, loads, lens = dispatch.walk_sample(
            graph.walk_table, graph.deg, nodes, seed,
            n_walkers=cfg.n_walkers, p_halt=cfg.p_halt, l_max=cfg.l_max,
            reweight=cfg.reweight, scheme=cfg.scheme,
        )
    return WalkTrace(cols=cols, loads=loads, lens=lens)


def sample_walks(
    graph: Graph,
    key: jax.Array,
    n_walkers: int,
    p_halt: float = 0.1,
    l_max: int = 10,
    reweight: bool = True,
    scheme: str = "iid",
) -> WalkTrace:
    """Sample ``n_walkers`` truncated walks from every node (Alg. 2).

    Returns a :class:`WalkTrace` with K = n_walkers*(l_max+1) slots per node.
    """
    cfg = WalkConfig(n_walkers, p_halt, l_max, reweight, scheme)
    nodes = jnp.arange(graph.n_nodes, dtype=jnp.int32)
    with obs.span("walks.sample", rows=graph.n_nodes, scheme=scheme) as sp:
        trace = _sample(graph, nodes, walk_seed(key), cfg=cfg,
                        spmv_backend=dispatch.get_backend(),
                        obs_tap=obs.enabled())
        sp.block_on(trace)
    return trace


def sample_walks_for_nodes(
    graph: Graph,
    nodes: jax.Array,
    key: jax.Array,
    n_walkers: int,
    p_halt: float = 0.1,
    l_max: int = 10,
    reweight: bool = True,
    scheme: str = "iid",
) -> WalkTrace:
    """Sample walks only from ``nodes`` (subset features, §3.1 remark).

    With the counter RNG the returned rows equal the corresponding rows of
    ``sample_walks(graph, key, ...)`` exactly — subset traces are consistent
    with the full Φ without materialising it (every scheme keeps this: the
    driving streams are keyed on absolute node id)."""
    cfg = WalkConfig(n_walkers, p_halt, l_max, reweight, scheme)
    with obs.span("walks.sample", rows=int(nodes.shape[0]),
                  scheme=scheme) as sp:
        trace = _sample(graph, nodes.astype(jnp.int32), walk_seed(key),
                        cfg=cfg, spmv_backend=dispatch.get_backend(),
                        obs_tap=obs.enabled())
        sp.block_on(trace)
    return trace


def walk_chunks(
    graph: Graph,
    key: jax.Array,
    cfg: WalkConfig,
    chunk: int = DEFAULT_CHUNK,
) -> Iterator[tuple[int, WalkTrace]]:
    """Stream (row_start, WalkTrace) over node blocks of ``chunk`` rows.

    Peak memory is O(chunk · K) instead of O(N · K); concatenating every
    yielded trace reproduces ``sample_walks`` bit-for-bit.  This is the
    host-level view of the chunked path — the in-jit streaming consumers
    live in core/features.py / core/linops.py."""
    n = graph.n_nodes
    seed = walk_seed(key)
    backend = dispatch.get_backend()
    for start in range(0, n, chunk):
        nodes = jnp.arange(start, min(start + chunk, n), dtype=jnp.int32)
        with obs.span("walks.sample", rows=int(nodes.shape[0]),
                      scheme=cfg.scheme, chunk_start=start) as sp:
            trace = _sample(graph, nodes, seed, cfg=cfg, spmv_backend=backend,
                            obs_tap=obs.enabled())
            sp.block_on(trace)
        yield start, trace
