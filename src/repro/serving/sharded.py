"""Sharded GP serving state: cached train rows split across a device mesh
(DESIGN.md §3.12).

The serving hot path (state.py) is O(q·K²·m + q·m²) per wave, and the only
term that grows with the observation capacity m is the cross-Gram
K̂_{q,x} — q rows against the m cached train rows.  That work is
embarrassingly row-parallel over the *train* side, so the shard layout is:

  * ``trace`` (the cached ELL feature rows, [capacity, K]) — **row-sharded**
    over a 1-D ``("data",)`` mesh: shard i owns rows
    [i·capacity/P, (i+1)·capacity/P).
  * ``chol`` / ``alpha`` / ``y`` / ``nodes`` / scalars — **replicated**:
    the m×m triangular solves are O(q·m²) but tiny (m ≤ capacity ≈ 128)
    and replicating the factor is what keeps every shard able to answer
    the whitened solve locally.
  * the graph — replicated (walk substrate for the lazy query rows).

A sharded wave then runs under ``shard_map``: each shard lazily samples its
slice of the query rows (the counter RNG keyed on absolute node ids makes
subset sampling exact — DESIGN.md §3.6), ``all_gather``\\ s the q query rows
(tiny: [q, K]), computes its *local* cross-Gram block
``gram_block(vals_q, ·, vals_x_local, ·)`` → [q, capacity/P], scatters it
into the full [q, capacity] block at its shard offset and psum-reduces with
the same :func:`repro.distributed.gp_shard.psum_reduce` hook the CG path
injects.  Adding structural zeros is exact in floating point, so the
reduced cross-Gram is **bit-identical** to the single-device one — and
everything downstream (mean, whitened solve, variance, joint Thompson
draw) is the very same code (`_mean_whiten`, `_moments_tail`,
`_joint_draw_tail`) running on replicated values.

**Replication invariant**: mutations (observe / forget / refit / ingest)
are executed ONCE on the canonical single-device :class:`ServeState` via
the existing guarded update layer, then the mutable leaves are re-placed
(broadcast + row-shard) onto the mesh — shard state can never diverge
because shards never mutate.  Query-side state is read-only by
construction.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..core import features
from ..core.walks import WalkTrace
from ..distributed.gp_shard import psum_reduce, shard_map_unchecked
from ..kernels import dispatch
from ..launch.mesh import make_serving_mesh
from ..resilience import faults
from . import update
from .engine import _joint_draw_tail
from .state import ServeState, _mean_whiten, _moments_tail, query_rows


def _state_specs(state: ServeState, axis: str) -> ServeState:
    """PartitionSpec pytree matching ``state``: trace rows sharded over
    ``axis``, every other leaf replicated."""
    specs = jax.tree.map(lambda _: P(), state)
    return dataclasses.replace(
        specs,
        trace=WalkTrace(cols=P(axis, None), loads=P(axis, None),
                        lens=P(axis, None)),
    )


def _sharded_cross(state: ServeState, qnodes: jax.Array, mesh, axis: str):
    """psum-reduced cross-Gram K̂_{q,x} [q, capacity] + gathered query rows.

    Runs under shard_map; returns replicated outputs bit-identical to the
    single-device ``_cross_solve`` front half (structural-zero scatter +
    psum adds exact zeros)."""
    capacity = state.capacity
    n_shards = mesh.shape[axis]
    cap_local = capacity // n_shards
    reduce = psum_reduce((axis,))

    def run(st_local: ServeState, q_local: jax.Array):
        # Each shard samples its slice of the query rows: counter-RNG
        # subset invariance makes these the exact rows of the full Φ.
        trace_ql = faults.guard_trace(query_rows(st_local, q_local))
        gather = partial(jax.lax.all_gather, axis_name=axis, axis=0,
                         tiled=True)
        trace_q = WalkTrace(cols=gather(trace_ql.cols),
                            loads=gather(trace_ql.loads),
                            lens=gather(trace_ql.lens))
        vals_q = features.feature_values(trace_q, st_local.f)
        vals_xl = features.feature_values(st_local.trace, st_local.f)
        k_local = dispatch.gram_block(
            vals_q, trace_q.cols, vals_xl, st_local.trace.cols
        )  # [q, cap_local] — this shard's slice of the train rows
        shard = jax.lax.axis_index(axis)
        k_full = jnp.zeros((trace_q.cols.shape[0], capacity), k_local.dtype)
        k_full = jax.lax.dynamic_update_slice(
            k_full, k_local, (0, shard * cap_local)
        )
        return reduce(k_full), trace_q

    spec_state = _state_specs(state, axis)
    trace_spec = WalkTrace(cols=P(), loads=P(), lens=P())
    return shard_map_unchecked(
        run, mesh=mesh,
        in_specs=(spec_state, P(axis)),
        out_specs=(P(), trace_spec),
    )(state, qnodes)


def _sharded_moments_core(state, qnodes, mesh, axis):
    k_qx, trace_q = _sharded_cross(state, qnodes, mesh, axis)
    # Replicated downstream — the SAME helpers as the single-device path,
    # so sharded answers bit-match once k_qx does.
    mean, v = _mean_whiten(state, k_qx)
    return _moments_tail(state, trace_q, mean, v)


_SH_STATICS = ("mesh", "axis", "spmv_backend", "obs_tap", "fault_plan")


@partial(jax.jit, static_argnames=_SH_STATICS)
def _sharded_moments(state, qnodes, *, mesh, axis, spmv_backend,
                     obs_tap=False, fault_plan=None):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(fault_plan):
        return _sharded_moments_core(state, qnodes, mesh, axis)


@partial(jax.jit, static_argnames=_SH_STATICS)
def _sharded_engine_step(state, slot_nodes, key, *, mesh, axis,
                         spmv_backend, obs_tap=False, fault_plan=None):
    """Sharded twin of ``engine._engine_step`` — same RNG discipline, so a
    wave's marginal Thompson draws bit-match the single-device engine."""
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(fault_plan):
        mean, var = _sharded_moments_core(state, slot_nodes, mesh, axis)
        eps = jax.random.normal(key, mean.shape, dtype=jnp.float32)
        return mean, var, mean + jnp.sqrt(var) * eps


@partial(jax.jit, static_argnames=("n_samples",) + _SH_STATICS)
def _sharded_thompson(state, nodes, key, *, n_samples, mesh, axis,
                      spmv_backend, obs_tap=False, fault_plan=None):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(fault_plan):
        k_qx, trace_q = _sharded_cross(state, nodes, mesh, axis)
        vals_q = features.feature_values(trace_q, state.f)
        mean, v = _mean_whiten(state, k_qx)
        return _joint_draw_tail(trace_q, vals_q, mean, v, key, n_samples)


class ShardedServeState:
    """A :class:`ServeState` spread over a 1-D device mesh.

    Holds the **canonical** single-device state (``.state`` — the source of
    truth every mutation runs on, exactly once) and a **placed** copy
    (``.placed`` — trace rows sharded, everything else replicated) the
    query path reads.  Broadcast-after-mutate keeps the invariant trivial:
    shards never diverge because shards never write.

    ``capacity`` must divide evenly by the mesh size; query batches are
    padded to a multiple of it (node-0 padding — marginal moments are
    row-wise, so padding never changes real answers).
    """

    def __init__(self, state: ServeState, mesh=None,
                 n_shards: int | None = None):
        self.mesh = mesh if mesh is not None else make_serving_mesh(n_shards)
        if len(self.mesh.axis_names) != 1:
            raise ValueError(
                f"serving mesh must be 1-D, got axes {self.mesh.axis_names}"
            )
        self.axis = self.mesh.axis_names[0]
        n = self.n_shards
        if state.capacity % n:
            raise ValueError(
                f"capacity {state.capacity} must divide evenly across "
                f"{n} shards"
            )
        self.state = state
        self._placed_graph = jax.device_put(
            state.graph, NamedSharding(self.mesh, P())
        )
        self._replace()

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def capacity(self) -> int:
        return self.state.capacity

    def _replace(self) -> None:
        """Re-place the canonical leaves onto the mesh (graph placed once —
        it is immutable and can be 10⁶-node)."""
        st = self.state

        def put(x, spec):
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        # None is an empty pytree, so graph/trace are skipped by the map
        # and re-attached explicitly below.
        rep = jax.tree.map(
            lambda x: put(x, P()),
            dataclasses.replace(st, graph=None, trace=None),
        )
        self.placed = dataclasses.replace(
            rep,
            graph=self._placed_graph,
            trace=WalkTrace(
                cols=put(st.trace.cols, P(self.axis, None)),
                loads=put(st.trace.loads, P(self.axis, None)),
                lens=put(st.trace.lens, P(self.axis, None)),
            ),
        )

    def _pad(self, nodes):
        nodes = jnp.asarray(nodes, jnp.int32).reshape(-1)
        q = nodes.shape[0]
        pad = (-q) % self.n_shards
        if pad:
            nodes = jnp.concatenate(
                [nodes, jnp.zeros((pad,), jnp.int32)]
            )
        return nodes, q

    # -- queries (sharded) ---------------------------------------------------
    def posterior_moments(self, query_nodes):
        """Exact closed-form (mean, var).

        Bit-matches the single-device ``serving.posterior_moments`` when q
        is a multiple of the shard count (identical [q, capacity] shapes →
        identical reduction order).  Padded batches run a differently-shaped
        compiled program, so they agree to fp32 roundoff instead — the
        estimator itself is exactly the same."""
        qnodes, q = self._pad(query_nodes)
        mean, var = _sharded_moments(
            self.placed, qnodes, mesh=self.mesh, axis=self.axis,
            spmv_backend=dispatch.get_backend(), obs_tap=obs.enabled(),
            fault_plan=faults.active(),
        )
        return mean[:q], var[:q]

    def thompson_draw(self, nodes, key, n_samples: int = 1):
        """Exact joint posterior samples [q, n_samples].

        Bit-matches the single-device ``serving.thompson_draw`` when q is
        a multiple of the shard count; otherwise node-0 padding changes
        the q×q jitter/eps layout and the draw is distribution-equal but
        not bitwise."""
        qnodes, q = self._pad(nodes)
        out = _sharded_thompson(
            self.placed, qnodes, key, n_samples=n_samples, mesh=self.mesh,
            axis=self.axis, spmv_backend=dispatch.get_backend(),
            obs_tap=obs.enabled(), fault_plan=faults.active(),
        )
        return out[:q]

    # -- mutations (execute once on the canonical state, then broadcast) -----
    def _mutate(self, new_state: ServeState) -> None:
        self.state = new_state
        self._replace()

    def observe(self, node, y, **kwargs) -> None:
        self._mutate(update.observe(self.state, node, y, **kwargs))

    def observe_batch(self, nodes, ys, *, sync: bool = True,
                      **kwargs) -> None:
        """Guarded batched append.  ``sync=False`` routes through the
        donated no-sync path (``observe_batch_async``) — the fleet's
        mutation fast path; health flags are then read at the caller's
        next blocking point instead of here."""
        if sync:
            self._mutate(update.observe_batch(self.state, nodes, ys,
                                              **kwargs))
        else:
            self._mutate(update.observe_batch_async(self.state, nodes, ys))

    def forget(self, slot) -> None:
        self._mutate(update.forget(self.state, slot))

    def forget_batch(self, slots, *, sync: bool = True) -> None:
        if sync:
            self._mutate(update.forget_batch(self.state, slots))
        else:
            self._mutate(update.forget_batch_async(self.state, slots))

    def ingest(self, nodes, ys) -> None:
        self._mutate(update.ingest(self.state, nodes, ys))

    def refit(self, **kwargs) -> None:
        self._mutate(update.refit(self.state, **kwargs))

    def refit_alpha(self, **kwargs) -> None:
        res = update.refit_alpha(self.state, **kwargs)
        self._mutate(res[0] if isinstance(res, tuple) else res)
