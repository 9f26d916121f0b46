"""Static-shape graph containers.

TPU/XLA require static shapes, so adjacency is stored in padded ELL form:
``neighbors[N, max_deg]`` / ``weights[N, max_deg]`` with zero-weight padding.
The walk sampler reads the same slots through one packed table, so that a
walk move is one row gather (DESIGN.md §3.6).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """Padded adjacency-list representation of an undirected weighted graph.

    Attributes:
      neighbors: int32[N, max_deg] — padded with 0 beyond ``deg[i]``.
      weights:   float32[N, max_deg] — walk-matrix entries; 0 beyond ``deg[i]``.
      deg:       int32[N] — unweighted node degrees (Alg. 2's ``d``).
      walk_table: int32[N·max_deg, 3] — per ELL slot ``i·max_deg + k``, the
        walk sampler's whole move: ``neighbors[i, k]``, the bits of
        ``weights[i, k]``, and ``deg[neighbors[i, k]]`` (padding slots hold
        node 0 and its degree).

    Build one with :func:`from_edges` or :func:`from_ell`, which derive the
    table from the other three.
    """

    neighbors: jax.Array
    weights: jax.Array
    deg: jax.Array
    walk_table: jax.Array

    @property
    def n_nodes(self) -> int:
        return self.neighbors.shape[0]

    @property
    def max_deg(self) -> int:
        return self.neighbors.shape[1]

    def tree_flatten(self):
        return (self.neighbors, self.weights, self.deg, self.walk_table), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def from_edges(
    edges: np.ndarray,
    n_nodes: int,
    weights: np.ndarray | None = None,
    normalize: bool = True,
) -> Graph:
    """Build a :class:`Graph` from an undirected edge list.

    Args:
      edges: int array [E, 2]; each row an undirected edge (i, j), i != j.
      n_nodes: number of nodes N.
      weights: optional float array [E]; defaults to 1.
      normalize: if True the stored walk matrix is the *normalised adjacency*
        ``Ã = D_w^{-1/2} W D_w^{-1/2}`` (D_w = weighted degree), so that kernel
        power series are in Ã and the diffusion kernel corresponds to
        ``exp(-β L̃)`` (DESIGN.md §3 — the paper's experiments use L̃-based
        kernels). If False, the raw W is stored.
    """
    edges = np.asarray(edges, dtype=np.int64)
    if weights is None:
        weights = np.ones(len(edges), dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64)
    # Symmetrise.
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    w = np.concatenate([weights, weights])
    # Drop duplicate directed edges (keep first).
    key = src * n_nodes + dst
    _, idx = np.unique(key, return_index=True)
    src, dst, w = src[idx], dst[idx], w[idx]

    if normalize:
        wdeg = np.zeros(n_nodes)
        np.add.at(wdeg, src, w)
        scale = 1.0 / np.sqrt(np.maximum(wdeg, 1e-30))
        w = w * scale[src] * scale[dst]

    deg = np.zeros(n_nodes, dtype=np.int64)
    np.add.at(deg, src, 1)
    max_deg = int(deg.max()) if len(deg) else 1
    neighbors = np.zeros((n_nodes, max_deg), dtype=np.int32)
    wmat = np.zeros((n_nodes, max_deg), dtype=np.float32)
    # Vectorised ELL fill (a per-edge Python loop is minutes at 10⁶ nodes):
    # group edges by row, then each edge's slot is its rank within the row.
    order = np.argsort(src, kind="stable")
    src_s, dst_s, w_s = src[order], dst[order], w[order]
    row_start = np.zeros(n_nodes, dtype=np.int64)
    row_start[1:] = np.cumsum(deg)[:-1]
    slot = np.arange(len(src_s)) - row_start[src_s]
    neighbors[src_s, slot] = dst_s
    wmat[src_s, slot] = w_s
    return from_ell(neighbors, wmat, deg)


def from_ell(neighbors, weights, deg) -> Graph:
    """A :class:`Graph` from its padded ELL arrays, with the walk table
    derived from them.  The table is built on the host, so the device holds
    only the finished arrays (no intermediate of the packing)."""
    neighbors = np.asarray(neighbors, np.int32)
    weights = np.asarray(weights, np.float32)
    deg = np.asarray(deg, np.int32)
    walk_table = np.stack(
        [neighbors, weights.view(np.int32), deg[neighbors]], axis=-1
    ).reshape(-1, 3)
    return Graph(jnp.asarray(neighbors), jnp.asarray(weights),
                 jnp.asarray(deg), jnp.asarray(walk_table))


def to_dense(graph: Graph) -> jax.Array:
    """Dense walk matrix (normalised adjacency) — small-N testing only."""
    n = graph.n_nodes
    dense = jnp.zeros((n, n), dtype=jnp.float32)
    rows = jnp.repeat(jnp.arange(n), graph.max_deg)
    cols = graph.neighbors.reshape(-1)
    vals = graph.weights.reshape(-1)
    return dense.at[rows, cols].add(vals)


def normalized_laplacian(graph: Graph) -> jax.Array:
    """L̃ = I − Ã for a graph stored with ``normalize=True`` (small-N only)."""
    a = to_dense(graph)
    return jnp.eye(graph.n_nodes, dtype=a.dtype) - a


@partial(jax.jit, static_argnames=("n_nodes",))
def _noop(n_nodes: int):  # pragma: no cover - keeps jit import warm
    return jnp.zeros((n_nodes,))
