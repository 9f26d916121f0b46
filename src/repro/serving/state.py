"""Online GP serving state: cached train features + incremental Cholesky
(DESIGN.md §3.7).

Because GRFs give an explicit feature map K̂ = ΦΦᵀ, the train-block system
the posterior needs is the *m×m* matrix A = K̂_xx + σ²I (m = observations
≪ N), not anything N-scale.  :class:`ServeState` caches everything a query
needs, in static-capacity buffers so the whole serving loop compiles once:

  * ``trace`` — the observed nodes' feature rows Φ_x in ELL layout
    ([capacity, K]; dead rows carry zero loads, so they vanish from every
    Gram product),
  * ``chol``  — the lower Cholesky L of A ([capacity, capacity]; the dead
    block is the identity, so full-size triangular solves are exact and
    O(capacity²) regardless of the live count),
  * ``alpha`` — the representer weights A⁻¹ y.

A batched query for q nodes then costs O(q·K²·m) for the cross-Gram
K̂_{q,x} (kernels/gram_block — the only hot-path kernel) plus O(q·m²) for
the variance triangular solve — **no CG and nothing N-scale in the serving
hot path**; N enters only through the lazy walk_sample of the q query rows.
Appending an observation is an O(m²) Cholesky row-append
(serving/update.py), not a fresh fit.

``count`` is a traced int32, so observing never retraces; ``cfg`` rides in
the pytree aux data, so jitted consumers treat it as static for free.  All
leaves are plain arrays → the state round-trips through
repro.checkpoint.CheckpointManager unchanged (elastic across meshes).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from .. import obs
from ..core import features
from ..core.walks import WalkConfig, WalkTrace, walk_seed
from ..graphs.formats import Graph
from ..kernels import dispatch
from ..resilience import faults


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ServeState:
    """Checkpointable online-GP posterior over a fixed graph.

    Attributes:
      graph: the serving graph (walk substrate for lazy query rows).
      nodes: int32[capacity] observed node ids (0 beyond ``count``).
      y:     float32[capacity] observed targets (0 beyond ``count``).
      count: int32 scalar — live observations m (traced; no retrace on grow).
      trace: ELL feature rows of the observed nodes ([capacity, K]; rows at
             or beyond ``count`` have zero loads).
      chol:  float32[capacity, capacity] lower Cholesky of K̂_xx + σ²I on the
             live block, identity on the dead block.
      alpha: float32[capacity] representer weights (K̂_xx + σ²I)⁻¹ y.
      f:     modulation vector (kernel hyperparameters).
      sigma_n2: observation-noise variance σ².
      seed:  uint32 counter-RNG walk seed — the identity of Φ.  Query rows
             sampled with this seed are rows of the *same* feature matrix as
             the cached train rows (DESIGN.md §3.6).
      overflow: int32 scalar — appends dropped because the state was at
             capacity.  A *jit-safe health flag* (DESIGN.md §3.11): masked
             writes cannot raise under an outer jit, so degradation is
             reported in-band and the host wrapper turns deltas into the
             ``serving.observe.overflow`` obs counter.
      rejected: int32 scalar — appends refused because the payload / target
             / Schur complement was non-finite (K̂ is PSD by construction,
             so a non-finite append is corruption, never estimator noise).
      needs_refit: int32 scalar — appends whose Schur complement was
             near-zero and got jitter-clamped since the last
             refactorisation.  Non-zero means the incremental factor is
             running on jitter: the observe_batch wrapper answers with an
             automatic O(m³) refit; refit/ingest reset it to 0.
      cfg:   WalkConfig (static aux).
    """

    graph: Graph
    nodes: jax.Array
    y: jax.Array
    count: jax.Array
    trace: WalkTrace
    chol: jax.Array
    alpha: jax.Array
    f: jax.Array
    sigma_n2: jax.Array
    seed: jax.Array
    overflow: jax.Array
    rejected: jax.Array
    needs_refit: jax.Array
    cfg: WalkConfig

    @property
    def capacity(self) -> int:
        return self.nodes.shape[0]

    @property
    def n_nodes(self) -> int:
        return self.graph.n_nodes

    def live_mask(self) -> jax.Array:
        """float32[capacity]: 1 for live observation slots, 0 for dead."""
        return (jnp.arange(self.capacity) < self.count).astype(jnp.float32)

    def vals(self) -> jax.Array:
        """Cached train feature values [capacity, K] (zero on dead rows)."""
        return features.feature_values(self.trace, self.f)

    def tree_flatten(self):
        return (
            self.graph, self.nodes, self.y, self.count, self.trace,
            self.chol, self.alpha, self.f, self.sigma_n2, self.seed,
            self.overflow, self.rejected, self.needs_refit,
        ), (self.cfg,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def init_state(
    graph: Graph,
    key: jax.Array,
    f: jax.Array,
    sigma_n2,
    capacity: int,
    cfg: WalkConfig,
) -> ServeState:
    """Empty state: identity Cholesky, zero-load rows, zero observations."""
    k = cfg.slots
    return ServeState(
        graph=graph,
        nodes=jnp.zeros((capacity,), jnp.int32),
        y=jnp.zeros((capacity,), jnp.float32),
        count=jnp.asarray(0, jnp.int32),
        trace=WalkTrace(
            cols=jnp.zeros((capacity, k), jnp.int32),
            loads=jnp.zeros((capacity, k), jnp.float32),
            lens=jnp.zeros((capacity, k), jnp.int32),
        ),
        chol=jnp.eye(capacity, dtype=jnp.float32),
        alpha=jnp.zeros((capacity,), jnp.float32),
        f=jnp.asarray(f, jnp.float32),
        sigma_n2=jnp.asarray(sigma_n2, jnp.float32),
        seed=walk_seed(key),
        overflow=jnp.asarray(0, jnp.int32),
        rejected=jnp.asarray(0, jnp.int32),
        needs_refit=jnp.asarray(0, jnp.int32),
        cfg=cfg,
    )


def query_rows(state: ServeState, query_nodes: jax.Array) -> WalkTrace:
    """Lazily sample the Φ rows for ``query_nodes`` (subset mode).

    The counter RNG keyed on absolute node ids makes these rows *exactly*
    the rows of the Φ the train block was built from — no trace is stored
    for them anywhere."""
    cols, loads, lens = dispatch.walk_sample(
        state.graph.walk_table, state.graph.deg,
        query_nodes.astype(jnp.int32), state.seed,
        n_walkers=state.cfg.n_walkers, p_halt=state.cfg.p_halt,
        l_max=state.cfg.l_max, reweight=state.cfg.reweight,
        scheme=state.cfg.scheme,
    )
    # Fault-injection site (no-op — nothing staged — without an active
    # plan): every consumer of lazy rows, append and query alike, sees the
    # corruption; the append path rejects it, the query path sanitises it.
    loads = faults.corrupt_loads(loads, query_nodes)
    return WalkTrace(cols=cols, loads=loads, lens=lens)


def solve_chol(chol: jax.Array, b: jax.Array) -> jax.Array:
    """x = (L Lᵀ)⁻¹ b via two triangular solves (the no-CG serving solve)."""
    with jax.named_scope(dispatch.CHOL_SOLVE_SCOPE):
        z = solve_triangular(chol, b, lower=True)
        return solve_triangular(chol.T, z, lower=False)


def posterior_moments(state: ServeState, query_nodes: jax.Array):
    """Exact closed-form predictive mean/variance (paper Eq. 3/4).

        μ(q) = K̂_{q,x} α,          α = (K̂_xx + σ²I)⁻¹ y
        σ²(q) = K̂(q,q) − ‖L⁻¹ K̂_{x,q}‖²

    computed from the cached Cholesky — exact under the GRF estimator,
    unlike the sample-ensemble ``predictive_moments_from_samples``, and
    O(q·m²) with nothing N-scale.  Returns (mean[q], var[q])."""
    return _posterior_moments(
        state, query_nodes, spmv_backend=dispatch.get_backend(),
        obs_tap=obs.enabled(), fault_plan=faults.active(),
    )


@partial(jax.jit, static_argnames=("spmv_backend", "obs_tap", "fault_plan"))
def _posterior_moments(state, query_nodes, *, spmv_backend, obs_tap=False,
                       fault_plan=None):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(fault_plan):
        return _moments_impl(state, query_nodes)


def _query_features(state: ServeState, query_nodes: jax.Array):
    """Lazy guarded Φ rows + feature values for ``query_nodes``.

    guard_trace zeroes non-finite payload rows (only staged under an
    active fault plan): a poisoned query degrades to the prior for that
    node instead of NaN-ing the whole wave."""
    trace_q = faults.guard_trace(query_rows(state, query_nodes))
    return trace_q, features.feature_values(trace_q, state.f)


def _mean_whiten(state: ServeState, k_qx: jax.Array):
    """mean[q] and the whitened cross-block v = L⁻¹ K̂_{x,q} [c, q] from a
    cross-Gram row block — shared verbatim by the single-device and sharded
    paths, so their downstream math is bit-identical once k_qx agrees."""
    # HIGHEST: at default precision a TPU may round f32 operands to bf16.
    mean = jnp.dot(k_qx, state.alpha, precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope(dispatch.CHOL_SOLVE_SCOPE):
        v = solve_triangular(state.chol, k_qx.T, lower=True)  # [capacity, q]
    return mean, v


def _cross_solve(state: ServeState, query_nodes: jax.Array):
    """The shared query core: lazy rows, cross-Gram, mean, whitened solve.

    Returns (trace_q, vals_q, mean[q], v) with v = L⁻¹ K̂_{x,q} [c, q] —
    everything both the marginal moments and the joint Thompson draw need.
    """
    trace_q, vals_q = _query_features(state, query_nodes)
    k_qx = dispatch.gram_block(
        vals_q, trace_q.cols, state.vals(), state.trace.cols
    )  # [q, capacity]; dead train rows contribute exact zeros
    mean, v = _mean_whiten(state, k_qx)
    return trace_q, vals_q, mean, v


def _moments_tail(state: ServeState, trace_q, mean, v):
    """Marginal variance from the whitened cross-block (shared tail)."""
    k_qq = features.khat_diag_exact(trace_q, state.f)
    var_raw = k_qq - jnp.sum(v * v, axis=0)
    # K̂ is PSD by construction, so negative posterior variance is pure f32
    # cancellation — clamp to zero (an exact-interpolation answer) instead
    # of letting sqrt(var) turn it into NaN draws downstream; the tap
    # counts clamp fires (nothing staged when obs is disabled).
    obs.tap(
        "serving.var_clamped",
        jnp.sum(var_raw < 0).astype(jnp.int32),
        kind="counter",
    )
    return mean, jnp.maximum(var_raw, 0.0)


def _moments_impl(state: ServeState, query_nodes: jax.Array):
    trace_q, _, mean, v = _cross_solve(state, query_nodes)
    return _moments_tail(state, trace_q, mean, v)
