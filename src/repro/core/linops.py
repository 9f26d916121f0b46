"""Backend-dispatched LinearOperators for the GRF sparse stack (DESIGN.md §3).

The paper's O(N^{3/2}) inference (Thm. 2, Lemma 1) is built from one small
family of sparse operators; this module makes that family first-class so
every consumer (gp/, distributed/, bo/, benchmarks/) assembles the same
objects instead of hand-rolling product chains:

  * :class:`PhiOperator`      Φ — walk trace + modulation ([M, N], M rows
                              over the N-node column space).
  * :class:`KhatOperator`     K̂ = Φ_rows Φ_colsᵀ, covering both the square
                              K̂_xx and the rectangular K̂_{·x} (Eq. 12).
  * :class:`ShiftedOperator`  H = K̂ + D, with D a scalar σ²I, a per-row
                              noise vector (heteroscedastic / ∞-noise
                              padding), or a masked sandwich M K̂ M + D —
                              the three obs_mask idioms formerly duplicated
                              across gp/mll.py, gp/posterior.py and
                              distributed/gp_shard.py.

All operators are frozen pytrees (jit/scan/shard_map-safe), are callable
(``op(v) == op.matvec(v)``, so they drop straight into ``cg_solve``), and
route every product through the backend registry in repro.kernels.dispatch
("xla" | "pallas" | "pallas-interpret").

Distributed use: KhatOperator takes an injectable ``reduce`` hook applied to
the intermediate u = Φᵀv.  Under shard_map, pass ``lambda u: psum(u, axes)``
and the *same* operator computes the row-sharded matvec (the psum is the
only per-iteration collective — DESIGN.md §3); no forked implementation.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from ..graphs.formats import Graph
from ..kernels import dispatch
from . import features
from .walks import DEFAULT_CHUNK, WalkConfig, WalkTrace, walk_seed


def _bcast(d, v):
    """Broadcast a scalar-or-[T] diagonal against [T] or [T, R] operands."""
    return d[:, None] if (jnp.ndim(d) == 1 and v.ndim == 2) else d


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PhiOperator:
    """Φ ∈ R^{M×N}: the GRF feature matrix as a linear map."""

    trace: WalkTrace
    f: jax.Array
    n_nodes: int

    @property
    def shape(self) -> tuple[int, int]:
        return (self.trace.cols.shape[0], self.n_nodes)

    def vals(self) -> jax.Array:
        return features.feature_values(self.trace, self.f)

    def matvec(self, u: jax.Array) -> jax.Array:
        """y = Φ u.  u: [N(, R)] → y: [M(, R)]."""
        return dispatch.phi_matvec(self.vals(), self.trace.cols, u)

    def rmatvec(self, v: jax.Array) -> jax.Array:
        """u = Φᵀ v.  v: [M(, R)] → u: [N(, R)]."""
        return dispatch.phi_t_matvec(
            self.vals(), self.trace.cols, v, self.n_nodes
        )

    def diag_approx(self) -> jax.Array:
        """diag(Φ) for square M == N (slots whose column is the own row)."""
        own = self.trace.cols == jnp.arange(self.shape[0])[:, None]
        return jnp.sum(jnp.where(own, self.vals(), 0.0), axis=1)

    def diag_sq(self) -> jax.Array:
        """Σ_k vals² per row — K̂'s Jacobi diagonal (see khat_diag_approx)."""
        return features.khat_diag_approx(self.trace, self.f)

    def dense(self) -> jax.Array:
        return features.materialize_phi(self.trace, self.f, self.n_nodes)

    def take_rows(self, rows: jax.Array) -> "PhiOperator":
        return PhiOperator(
            features.take_rows(self.trace, rows), self.f, self.n_nodes
        )

    def with_matvec_dtype(self, dtype: str) -> "PhiOperator":
        """Payload-precision variant: casting ``f`` makes the whole ELL
        payload (loads ⊙ f, see features.feature_values) stream in ``dtype``
        while every dispatched product still accumulates in f32 — the
        bf16-loads/f32-math contract (SolveStrategy.matvec_dtype)."""
        return dataclasses.replace(self, f=self.f.astype(dtype))

    __call__ = matvec

    def tree_flatten(self):
        return (self.trace, self.f), (self.n_nodes,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ChunkedPhiOperator:
    """Φ as a *lazy* linear map over a graph: no trace is ever materialised.

    Each product re-samples walks in ``chunk``-row blocks and streams them
    through the dispatched sparse products (core/features.py chunked
    drivers), so peak memory is O(chunk·K) instead of O(N·K) — this is what
    unlocks 10⁶-node graphs on one host (DESIGN.md §3.6).  Because the
    walker RNG is counter-based on absolute node ids, this operator computes
    *exactly* the same Φ as ``PhiOperator`` built from
    ``sample_walks(graph, key, ...)`` with the same key.

    ``row_start``/``n_rows`` select a row range of the full Φ (may be traced
    — the distributed path passes per-shard offsets under shard_map).
    Re-sampling trades compute for memory: every matvec redoes the walk
    simulation, O(N·n_walkers·l_max) neighbour gathers, and on a v5e that
    sampling is most of a product's time (≈ 70% of a 10⁶-node pass; the
    gather at the walk slots most of the rest).  So callers stream Φ as few
    times as they can: the pathwise draw makes one pass, Φ(w + Φ_xᵀα), and
    the training-set solves run on small materialised traces.
    """

    graph: Graph
    f: jax.Array
    seed: jax.Array
    cfg: WalkConfig
    chunk: int = DEFAULT_CHUNK
    n_rows: int | None = None
    row_start: jax.Array | int = 0

    @property
    def n_nodes(self) -> int:
        return self.graph.n_nodes

    @property
    def shape(self) -> tuple[int, int]:
        rows = self.n_nodes if self.n_rows is None else self.n_rows
        return (rows, self.n_nodes)

    def _kw(self):
        return dict(cfg=self.cfg, chunk=self.chunk, row_start=self.row_start,
                    n_rows=self.n_rows)

    def matvec(self, u: jax.Array) -> jax.Array:
        """y = Φ u, streamed: peak extra memory O(chunk·K)."""
        return features.phi_matvec_chunked(
            self.graph, self.f, u, self.seed, **self._kw()
        )

    def rmatvec(self, v: jax.Array) -> jax.Array:
        """u = Φᵀ v, streamed scatter-accumulate into [N(, R)]."""
        return features.phi_t_matvec_chunked(
            self.graph, self.f, v, self.seed, **self._kw()
        )

    def diag_sq(self) -> jax.Array:
        return features.khat_diag_approx_chunked(
            self.graph, self.f, self.seed, **self._kw()
        )

    def dense(self) -> jax.Array:
        raise NotImplementedError(
            "ChunkedPhiOperator is lazy by design (the dense Φ is the O(N·K) "
            "materialisation it exists to avoid); for small problems sample a "
            "trace with the same key and use PhiOperator.dense()."
        )

    def with_matvec_dtype(self, dtype: str) -> "ChunkedPhiOperator":
        """Same payload-precision contract as PhiOperator.with_matvec_dtype
        (the chunked drivers build each block's payload at ``f``'s dtype)."""
        return dataclasses.replace(self, f=self.f.astype(dtype))

    __call__ = matvec

    def tree_flatten(self):
        return (self.graph, self.f, self.seed, self.row_start), (
            self.cfg, self.chunk, self.n_rows,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        graph, f, seed, row_start = children
        cfg, chunk, n_rows = aux
        return cls(graph, f, seed, cfg, chunk, n_rows, row_start)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KhatOperator:
    """K̂ = Φ_rows Φ_colsᵀ — square (rows is cols) or cross-covariance.

    ``reduce`` (optional) is applied to the intermediate u = Φ_colsᵀ v; under
    shard_map inject ``lambda u: jax.lax.psum(u, axes)`` to make this the
    row-sharded distributed matvec.  When no reduce hook is set and both
    operands carry materialised traces, Pallas backends run the fused kernel
    (u never leaves VMEM); with a :class:`ChunkedPhiOperator` on either side
    the product runs as the composed lazy chain instead (peak memory
    O(chunk·K) + one N-vector).
    """

    rows: "PhiOperator | ChunkedPhiOperator"
    cols: "PhiOperator | ChunkedPhiOperator"
    reduce: Callable[[jax.Array], jax.Array] | None = None

    @property
    def n_nodes(self) -> int:
        return self.rows.n_nodes

    @property
    def shape(self) -> tuple[int, int]:
        return (self.rows.shape[0], self.cols.shape[0])

    def matvec(self, v: jax.Array) -> jax.Array:
        fusable = isinstance(self.rows, PhiOperator) and isinstance(
            self.cols, PhiOperator
        )
        if self.reduce is None and fusable:
            return dispatch.khat_matvec(
                self.rows.vals(), self.rows.trace.cols,
                self.cols.vals(), self.cols.trace.cols,
                v, self.n_nodes,
            )
        u = self.cols.rmatvec(v)
        if self.reduce is not None:
            u = self.reduce(u)
        return self.rows.matvec(u)

    def rmatvec(self, v: jax.Array) -> jax.Array:
        return self.transpose().matvec(v)

    def transpose(self) -> "KhatOperator":
        return KhatOperator(self.cols, self.rows, self.reduce)

    def diag_approx(self) -> jax.Array:
        """Jacobi-preconditioner diagonal: Σ_k vals² of the row features.

        Local per-shard rows under shard_map — no collective needed."""
        return self.rows.diag_sq()

    def dense(self) -> jax.Array:
        return self.rows.dense() @ self.cols.dense().T

    def with_matvec_dtype(self, dtype: str) -> "KhatOperator":
        """Cast both factors' payloads; the square case keeps rows/cols as
        one shared object (identity matters to the Nyström eligibility
        check in solvers/nystrom.py)."""
        rows = self.rows.with_matvec_dtype(dtype)
        cols = (
            rows if self.cols is self.rows
            else self.cols.with_matvec_dtype(dtype)
        )
        return KhatOperator(rows, cols, self.reduce)

    __call__ = matvec

    def tree_flatten(self):
        return (self.rows, self.cols), (self.reduce,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShiftedOperator:
    """H = K̂ + D (or M K̂ M + D when ``mask`` is given).

    ``noise`` is a scalar (σ²I) or per-row vector (heteroscedastic diagonal —
    e.g. the BO loop's static-shape padding, where dead observation slots
    carry ~infinite noise).  ``mask`` expresses training-set structure on
    row-sharded full-length vectors (distributed pathwise sampling)."""

    khat: KhatOperator
    noise: jax.Array
    mask: jax.Array | None = None

    @property
    def shape(self) -> tuple[int, int]:
        return self.khat.shape

    def matvec(self, v: jax.Array) -> jax.Array:
        d = _bcast(self.noise, v)
        if self.mask is None:
            return self.khat.matvec(v) + d * v
        m = _bcast(self.mask, v)
        return m * self.khat.matvec(m * v) + d * v

    rmatvec = matvec  # symmetric

    def diag_approx(self) -> jax.Array:
        k_diag = self.khat.diag_approx()
        if self.mask is not None:
            k_diag = k_diag * self.mask * self.mask
        return k_diag + self.noise

    def dense(self) -> jax.Array:
        k = self.khat.dense()
        t = k.shape[0]
        if self.mask is not None:
            k = self.mask[:, None] * k * self.mask[None, :]
        return k + jnp.diag(jnp.broadcast_to(self.noise, (t,)))

    def with_matvec_dtype(self, dtype: str) -> "ShiftedOperator":
        """Payload-precision variant of H: only K̂'s ELL payload changes
        dtype — the noise/mask diagonal arithmetic stays in f32, as does
        every product output (bf16-loads/f32-math)."""
        return dataclasses.replace(
            self, khat=self.khat.with_matvec_dtype(dtype)
        )

    __call__ = matvec

    def tree_flatten(self):
        return (self.khat, self.noise, self.mask), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


# --- constructors ----------------------------------------------------------


def phi(trace: WalkTrace, f: jax.Array, n_nodes: int | None = None) -> PhiOperator:
    """Φ from a walk trace; ``n_nodes`` defaults to the square assumption."""
    return PhiOperator(trace, f, trace.n_nodes if n_nodes is None else n_nodes)


def khat(
    trace: WalkTrace,
    f: jax.Array,
    n_nodes: int | None = None,
    reduce: Callable | None = None,
) -> KhatOperator:
    """Square K̂ = ΦΦᵀ (rows == cols)."""
    p = phi(trace, f, n_nodes)
    return KhatOperator(p, p, reduce)


def khat_cross(
    trace_rows: WalkTrace,
    trace_cols: WalkTrace,
    f: jax.Array,
    n_nodes: int,
    reduce: Callable | None = None,
) -> KhatOperator:
    """Rectangular K̂[rows, cols] = Φ_rows Φ_colsᵀ (e.g. K̂_{·x}, Eq. 12)."""
    return KhatOperator(
        PhiOperator(trace_rows, f, n_nodes),
        PhiOperator(trace_cols, f, n_nodes),
        reduce,
    )


def shifted(
    trace: WalkTrace,
    f: jax.Array,
    noise: jax.Array,
    n_nodes: int | None = None,
    mask: jax.Array | None = None,
    reduce: Callable | None = None,
) -> ShiftedOperator:
    """H = K̂ + D from a walk trace — the GP solve operator in one call."""
    return ShiftedOperator(khat(trace, f, n_nodes, reduce), noise, mask)


def chunked_phi(
    graph: Graph,
    f: jax.Array,
    key: jax.Array,
    cfg: WalkConfig,
    chunk: int = DEFAULT_CHUNK,
    n_rows: int | None = None,
    row_start: jax.Array | int = 0,
) -> ChunkedPhiOperator:
    """Lazy Φ over ``graph``; same rows as ``sample_walks(graph, key, ...)``."""
    return ChunkedPhiOperator(
        graph, f, walk_seed(key), cfg, chunk, n_rows, row_start
    )


def chunked_khat(
    graph: Graph,
    f: jax.Array,
    key: jax.Array,
    cfg: WalkConfig,
    chunk: int = DEFAULT_CHUNK,
    reduce: Callable | None = None,
) -> KhatOperator:
    """Square K̂ = ΦΦᵀ with both factors lazy/chunked (peak O(chunk·K))."""
    p = chunked_phi(graph, f, key, cfg, chunk)
    return KhatOperator(p, p, reduce)


def chunked_khat_cross(
    graph: Graph,
    trace_cols: WalkTrace,
    f: jax.Array,
    key: jax.Array,
    cfg: WalkConfig,
    chunk: int = DEFAULT_CHUNK,
    reduce: Callable | None = None,
) -> KhatOperator:
    """K̂[·, cols] = Φ_full Φ_colsᵀ with the full-graph factor lazy (Eq. 12).

    ``trace_cols`` is the small materialised trace (e.g. training nodes,
    sampled via ``sample_walks_for_nodes`` with the *same key* so its rows
    agree with the lazy Φ)."""
    return KhatOperator(
        chunked_phi(graph, f, key, cfg, chunk),
        PhiOperator(trace_cols, f, graph.n_nodes),
        reduce,
    )
