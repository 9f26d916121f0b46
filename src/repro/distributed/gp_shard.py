"""Distributed GRF-GP: row-sharded features + psum-per-iteration CG.

The paper's O(N^{3/2}) inference expressed as a TPU collective schedule
(DESIGN.md §3):

  * Φ rows (the WalkTrace) are sharded over the data axes (pod, data);
    the modulation vector f and scalars replicate.
  * K̂v = Φ(Φᵀv): Φᵀv is a *local* scatter-add into a full-length partial
    vector followed by ONE psum (the only per-iteration collective);
    Φ·(·) is purely local (each device computes its own rows).
  * CG dot products psum with the same axes.

The matvec is not a fork of the single-device code: it is the *same*
:class:`repro.core.linops.KhatOperator` / :class:`ShiftedOperator` with the
psum injected as the operator's ``reduce`` hook (DESIGN.md §3), and the
solve is the *same* ``repro.solvers.solve`` under a
:class:`repro.solvers.SolveStrategy` with the psum-reducing ``dot`` hook
injected — backend dispatch, preconditioning and the mask/noise idioms stay
identical across single-device and sharded paths.  (Nyström preconditioning
is excluded on this path — assembling the pivot cross-block spans shards —
so sharded strategies keep ``"jacobi"``; ``solvers.nystrom`` raises rather
than silently degrading.)

Per CG iteration the wire traffic is exactly one all-reduce of an N-vector
(4 MB at N=1M, f32) — independent of walker count, which is why the method
scales to pods."""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core import linops
from ..core.walks import DEFAULT_CHUNK, WalkConfig, WalkTrace, walk_seed
from ..graphs.formats import Graph
from .. import solvers
from ..solvers import SolveStrategy


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checks off — the one shard_map
    entry of the CG path and of sharded serving (serving/sharded.py)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def psum_reduce(axes: Sequence[str]):
    """The all-reduce injected as the operators' ``reduce`` hook."""

    def reduce(partial):
        return jax.lax.psum(partial, axes)

    return reduce


def psum_dot(axes: Sequence[str]):
    """Column-wise inner product reduced over the data axes — the ``dot``
    hook ``solvers.solve`` takes under shard_map (one scalar-per-RHS psum
    per CG iteration on top of the operator's N-vector all-reduce)."""

    def dot(u, v):
        return jax.lax.psum(jnp.sum(u * v, axis=0), axes)

    return dot


def _resolve(strategy, tol, max_iters, adaptive=True) -> SolveStrategy:
    """Fold legacy per-call-site literals into a sharded-default strategy."""
    if strategy is None:
        strategy = solvers.SHARDED_DEFAULT
    if strategy.preconditioner == "auto":
        # The Nyström factor columns span shards, so the auto path has no
        # candidate but Jacobi here — resolve before entering shard_map
        # rather than relying on the in-trace fallback.
        strategy = strategy.with_(preconditioner="jacobi")
    return strategy.with_overrides(
        tol=tol, max_iters=max_iters, adaptive=False if not adaptive else None
    )


def sharded_h_operator(
    trace_local: WalkTrace,
    f: jax.Array,
    n_nodes: int,
    axes: Sequence[str],
    sigma_n2,
    mask: jax.Array | None = None,
) -> linops.ShiftedOperator:
    """H = (M) K̂ (M) + D over locally-owned Φ rows, psum-reduced."""
    return linops.shifted(
        trace_local, f, sigma_n2, n_nodes,
        mask=mask, reduce=psum_reduce(axes),
    )


def sharded_cg_solve(
    trace: WalkTrace,
    f: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    sigma_n2: float = 0.1,
    tol: float | None = None,
    max_iters: int | None = None,
    fixed_unrolled: bool = False,
    strategy: SolveStrategy | None = None,
    return_diagnostics: bool = False,
):
    """Solve (K̂ + σ²I) v = b with Φ rows sharded over (pod, data).

    ``fixed_unrolled`` runs exactly ``max_iters`` unrolled iterations, so
    cost_analysis sees every psum.
    ``return_diagnostics=True`` additionally returns (iters_used,
    converged) — identical on every shard (the convergence test runs on
    psum-reduced dots), so they replicate."""
    strategy = _resolve(strategy, tol, max_iters, adaptive=not fixed_unrolled)
    axes = _data_axes(mesh)
    n_nodes = trace.n_nodes
    row = P(axes)
    rowk = P(axes, None)

    @functools.partial(
        shard_map_unchecked,
        mesh=mesh,
        in_specs=(rowk, rowk, rowk, P(), row),
        out_specs=(row, P(), P()),
    )
    def run(cols, loads, lens, f, b_local):
        local = WalkTrace(cols, loads, lens)
        h = sharded_h_operator(local, f, n_nodes, axes, sigma_n2)
        res = solvers.solve(
            h, b_local, strategy, dot=psum_dot(axes), unroll=fixed_unrolled,
        )
        return res.x, res.iters, jnp.all(res.converged)

    x, iters, converged = run(trace.cols, trace.loads, trace.lens, f, b)
    if return_diagnostics:
        return x, iters, converged
    return x


def sharded_cg_solve_chunked(
    graph: Graph,
    f: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    key: jax.Array,
    walk: WalkConfig,
    chunk: int = DEFAULT_CHUNK,
    sigma_n2: float = 0.1,
    tol: float | None = None,
    max_iters: int | None = None,
    strategy: SolveStrategy | None = None,
    return_diagnostics: bool = False,
):
    """Solve (K̂ + σ²I) v = b with *chunk-per-shard lazy* Φ rows (§3.6).

    Composition of the two scaling axes: each device owns an N/n_shards row
    range of Φ which it never materialises — its ChunkedPhiOperator streams
    ``chunk``-row walk blocks per matvec — and the cross-device reduction is
    the same single psum hook KhatOperator always takes.  Per-device peak
    memory is O(chunk·K) regardless of graph size; the adjacency replicates
    (walkers cross shard boundaries).  Equals ``sharded_cg_solve`` on the
    materialised trace sampled with the same key.

    ``return_diagnostics=True`` surfaces (iters_used, converged) instead of
    discarding them — a maxed-out solve must be visible to callers."""
    strategy = _resolve(strategy, tol, max_iters)
    axes = _data_axes(mesh)
    n_nodes = graph.n_nodes
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if n_nodes % n_shards:
        raise ValueError(f"n_nodes={n_nodes} not divisible by {n_shards} shards")
    n_local = n_nodes // n_shards
    seed = walk_seed(key)
    row = P(axes)

    @functools.partial(
        shard_map_unchecked,
        mesh=mesh,
        in_specs=(P(), P(), P(), row),
        out_specs=(row, P(), P()),
    )
    def run(graph, f, seed, b_local):
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        phi_local = linops.ChunkedPhiOperator(
            graph, f, seed, walk, chunk,
            n_rows=n_local, row_start=idx * n_local,
        )
        khat = linops.KhatOperator(phi_local, phi_local,
                                   reduce=psum_reduce(axes))
        h = linops.ShiftedOperator(khat, jnp.asarray(sigma_n2, jnp.float32))
        res = solvers.solve(h, b_local, strategy, dot=psum_dot(axes))
        return res.x, res.iters, jnp.all(res.converged)

    x, iters, converged = run(graph, f, seed, b)
    if return_diagnostics:
        return x, iters, converged
    return x


def sharded_posterior_sample(
    trace: WalkTrace,
    train_mask: jax.Array,     # float32[N]: 1 for observed nodes (row-aligned)
    f: jax.Array,
    y_full: jax.Array,         # float32[N]: observations scattered to rows
    key: jax.Array,
    mesh: Mesh,
    sigma_n2: float = 0.1,
    max_iters: int | None = None,
    strategy: SolveStrategy | None = None,
    return_diagnostics: bool = False,
):
    """Pathwise posterior sample over all N nodes, fully sharded (Eq. 12).

    Training-set structure is expressed as a mask so every tensor stays
    row-sharded: H = M K̂ M + D where D = σ² on observed rows, 1e6 outside
    (infinite noise ⇒ unobserved rows carry no information) — the masked
    form of :class:`repro.core.linops.ShiftedOperator`.

    ``return_diagnostics=True`` surfaces the inner solve's (iters_used,
    converged) alongside the sample.  With no explicit strategy/max_iters
    the historical 128-iteration budget applies; an explicitly passed
    strategy is used as-is (its own max_iters wins)."""
    if strategy is None and max_iters is None:
        max_iters = 128
    strategy = _resolve(strategy, None, max_iters)
    axes = _data_axes(mesh)
    n_nodes = trace.n_nodes
    row = P(axes)
    rowk = P(axes, None)

    @functools.partial(
        shard_map_unchecked,
        mesh=mesh,
        in_specs=(rowk, rowk, rowk, P(), row, row, P()),
        out_specs=(row, P(), P()),
    )
    def run(cols, loads, lens, f, mask, y, key):
        local = WalkTrace(cols, loads, lens)
        noise = jnp.where(mask > 0, sigma_n2, 1e6)
        h = sharded_h_operator(local, f, n_nodes, axes, noise, mask=mask)
        khat = h.khat          # same operator, reduce hook included
        phi = khat.rows

        # Prior sample g = Φ w: w is length-N (column space) and must be
        # identical on every device — derive it from the replicated key.
        kw, ke = jax.random.split(key)
        w = jax.random.normal(kw, (n_nodes,), jnp.float32)
        g = phi.matvec(w)
        eps = jnp.sqrt(sigma_n2) * jax.random.normal(
            jax.random.fold_in(ke, jax.lax.axis_index(axes[-1])), g.shape
        )
        resid = mask * (y - g - eps)
        res = solvers.solve(h, resid, strategy, dot=psum_dot(axes))
        return g + khat.matvec(mask * res.x), res.iters, jnp.all(res.converged)

    s, iters, converged = run(
        trace.cols, trace.loads, trace.lens, f, train_mask, y_full, key
    )
    if return_diagnostics:
        return s, iters, converged
    return s
