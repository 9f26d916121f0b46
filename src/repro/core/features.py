"""GRF feature-matrix operations (paper §3, Thm. 2 Property 1).

Φ ∈ R^{M×N} is stored as a :class:`WalkTrace` (ELL: cols/loads/lens) plus a
modulation vector ``f``.  All products are O(M·K) where K = n·(l_max+1):

  * ``phi_matvec``     y = Φ u          (gather-reduce over slots)
  * ``phi_t_matvec``   u = Φᵀ v         (scatter-add over slots)
  * ``khat_matvec``    y = K̂ v = Φ(Φᵀv) (Thm. 2: O(N) matvec)

Every product dispatches through the backend registry in
repro.kernels.dispatch ("xla" | "pallas" | "pallas-interpret"); the Pallas
paths cover gather, scatter *and* the fused K̂-matvec, and carry custom
VJPs, so everything stays differentiable w.r.t. ``f`` on every backend
(DESIGN.md §3).  The operator-object view of the same products lives in
repro.core.linops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graphs.formats import Graph
from ..kernels import dispatch
from .walks import WalkConfig, WalkTrace


def feature_values(trace: WalkTrace, f: jax.Array) -> jax.Array:
    """vals[i,k] = loads[i,k] * f[lens[i,k]] — the GRF entries (Alg. 1 line 8).

    Supports compact traces (bf16 loads / int8 lens): math happens in f32."""
    with jax.named_scope(dispatch.PAYLOAD_SCOPE):
        return trace.loads.astype(f.dtype) * f[trace.lens.astype(jnp.int32)]


def phi_matvec(trace: WalkTrace, f: jax.Array, u: jax.Array) -> jax.Array:
    """y = Φ u.  u: [N] or [N, R] → y: [M] or [M, R]."""
    return dispatch.phi_matvec(feature_values(trace, f), trace.cols, u)


def phi_t_matvec(
    trace: WalkTrace, f: jax.Array, v: jax.Array, n_nodes: int
) -> jax.Array:
    """u = Φᵀ v.  v: [M] or [M, R] → u: [n_nodes] or [n_nodes, R]."""
    return dispatch.phi_t_matvec(
        feature_values(trace, f), trace.cols, v, n_nodes
    )


def khat_matvec(trace: WalkTrace, f: jax.Array, v: jax.Array) -> jax.Array:
    """y = K̂ v = Φ (Φᵀ v) for square Φ (M == N)."""
    vals = feature_values(trace, f)
    return dispatch.khat_matvec(
        vals, trace.cols, vals, trace.cols, v, trace.n_nodes
    )


def khat_cross_matvec(
    trace_rows: WalkTrace, trace_cols: WalkTrace, f: jax.Array, v: jax.Array,
    n_nodes: int,
) -> jax.Array:
    """y = K̂[rows, cols] v = Φ_rows (Φ_colsᵀ v) — e.g. K̂_{·,x} in Eq. 12."""
    return dispatch.khat_matvec(
        feature_values(trace_rows, f), trace_rows.cols,
        feature_values(trace_cols, f), trace_cols.cols,
        v, n_nodes,
    )


def take_rows(trace: WalkTrace, rows: jax.Array) -> WalkTrace:
    """Row-subset of Φ (training-node features Φ_x)."""
    return WalkTrace(
        cols=trace.cols[rows], loads=trace.loads[rows], lens=trace.lens[rows]
    )


def materialize_phi(trace: WalkTrace, f: jax.Array, n_nodes: int) -> jax.Array:
    """Dense Φ [M, n_nodes] — small problems / tests / the 'dense GRF' baseline."""
    vals = feature_values(trace, f)
    m = trace.cols.shape[0]
    out = jnp.zeros((m, n_nodes), vals.dtype)
    rows = jnp.repeat(jnp.arange(m), trace.slots)
    return out.at[rows, trace.cols.reshape(-1)].add(vals.reshape(-1))


def materialize_khat(trace: WalkTrace, f: jax.Array, n_nodes: int | None = None) -> jax.Array:
    """Dense K̂ = ΦΦᵀ — the paper's 'GRFs (Dense)' baseline (Table 1)."""
    n_nodes = trace.n_nodes if n_nodes is None else n_nodes
    phi = materialize_phi(trace, f, n_nodes)
    return phi @ phi.T


def khat_diag_approx(trace: WalkTrace, f: jax.Array) -> jax.Array:
    """Cheap lower bound on diag(K̂): Σ_k vals² (ignores duplicate-column
    cross terms).  Used only as a Jacobi-style preconditioner, where any SPD
    approximation is valid."""
    vals = feature_values(trace, f)
    return jnp.sum(vals * vals, axis=1)


def khat_diag_exact(trace: WalkTrace, f: jax.Array) -> jax.Array:
    """Exact diag(K̂)_i = ‖φ(i)‖² accounting for duplicate columns.

    O(M·K²); prefer :func:`khat_diag_approx` for large K.
    """
    vals = feature_values(trace, f)
    same = trace.cols[:, :, None] == trace.cols[:, None, :]
    outer = vals[:, :, None] * vals[:, None, :]
    return jnp.sum(jnp.where(same, outer, 0.0), axis=(1, 2))


# ---------------------------------------------------------------------------
# Chunked products: Φ is never materialised.  Each lax.scan step re-samples a
# `chunk`-row block of walks (counter RNG ⇒ identical to the monolithic rows)
# and streams it straight into the product, so peak memory is O(chunk·K)
# instead of O(N·K) — the 10⁶-node path (DESIGN.md §3.6).  `row_start` may be
# a traced value (shard offsets under shard_map).
# ---------------------------------------------------------------------------


def _sample_chunk_vals(graph, f, seed, start, chunk, n_rows, cfg):
    """Sample one block; returns (cols, vals) with padded rows zeroed."""
    idx = jnp.arange(chunk)
    valid = (idx < n_rows).astype(jnp.float32)
    nodes = jnp.minimum(start + idx, graph.n_nodes - 1).astype(jnp.int32)
    cols, loads, lens = dispatch.walk_sample(
        graph.walk_table, graph.deg, nodes, seed,
        n_walkers=cfg.n_walkers, p_halt=cfg.p_halt, l_max=cfg.l_max,
        reweight=cfg.reweight, scheme=cfg.scheme,
    )
    with jax.named_scope(dispatch.PAYLOAD_SCOPE):
        vals = (loads * valid[:, None]).astype(f.dtype) * f[lens]
    return cols, vals


def phi_matvec_chunked(
    graph: Graph, f: jax.Array, u: jax.Array, seed: jax.Array,
    *, cfg: WalkConfig, chunk: int, row_start=0, n_rows: int | None = None,
) -> jax.Array:
    """y = Φ u over rows [row_start, row_start+n_rows), streamed by chunks."""
    n_rows = graph.n_nodes if n_rows is None else n_rows
    nc = -(-n_rows // chunk)
    y0 = jnp.zeros((nc * chunk,) + u.shape[1:], jnp.float32)

    def step(y, i):
        cols, vals = _sample_chunk_vals(
            graph, f, seed, row_start + i * chunk, chunk, n_rows - i * chunk,
            cfg,
        )
        y_c = dispatch.phi_matvec(vals, cols, u)
        y = jax.lax.dynamic_update_slice(
            y, y_c, (i * chunk,) + (0,) * (y.ndim - 1)
        )
        return y, None

    y, _ = jax.lax.scan(step, y0, jnp.arange(nc))
    return y[:n_rows]


def phi_t_matvec_chunked(
    graph: Graph, f: jax.Array, v: jax.Array, seed: jax.Array,
    *, cfg: WalkConfig, chunk: int, row_start=0, n_rows: int | None = None,
) -> jax.Array:
    """u = Φᵀ v for the same streamed row range; accumulates into [N(, R)]."""
    n_rows = graph.n_nodes if n_rows is None else n_rows
    nc = -(-n_rows // chunk)
    pad = nc * chunk - n_rows
    if pad:
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
    u0 = jnp.zeros((graph.n_nodes,) + v.shape[1:], jnp.float32)

    def step(u, i):
        cols, vals = _sample_chunk_vals(
            graph, f, seed, row_start + i * chunk, chunk, n_rows - i * chunk,
            cfg,
        )
        v_c = jax.lax.dynamic_slice(
            v, (i * chunk,) + (0,) * (v.ndim - 1),
            (chunk,) + v.shape[1:],
        )
        u = u + dispatch.phi_t_matvec(vals, cols, v_c, graph.n_nodes)
        return u, None

    u, _ = jax.lax.scan(step, u0, jnp.arange(nc))
    return u


def khat_diag_approx_chunked(
    graph: Graph, f: jax.Array, seed: jax.Array,
    *, cfg: WalkConfig, chunk: int, row_start=0, n_rows: int | None = None,
) -> jax.Array:
    """Streamed Σ_k vals² per row — the Jacobi diagonal without the trace."""
    n_rows = graph.n_nodes if n_rows is None else n_rows
    nc = -(-n_rows // chunk)
    d0 = jnp.zeros((nc * chunk,), jnp.float32)

    def step(d, i):
        _, vals = _sample_chunk_vals(
            graph, f, seed, row_start + i * chunk, chunk, n_rows - i * chunk,
            cfg,
        )
        d = jax.lax.dynamic_update_slice(
            d, jnp.sum(vals * vals, axis=1), (i * chunk,)
        )
        return d, None

    d, _ = jax.lax.scan(step, d0, jnp.arange(nc))
    return d[:n_rows]


def nnz_per_row(trace: WalkTrace) -> jax.Array:
    """Number of distinct nonzero entries per feature (Thm. 1 sparsity)."""
    # Count distinct columns among slots with nonzero load.
    def row_nnz(cols, loads):
        live = loads != 0
        # Mark first occurrence of each live column.
        eq = (cols[:, None] == cols[None, :]) & live[None, :] & live[:, None]
        first = jnp.argmax(eq, axis=1) == jnp.arange(cols.shape[0])
        return jnp.sum(first & live)

    return jax.vmap(row_nnz)(trace.cols, trace.loads)
