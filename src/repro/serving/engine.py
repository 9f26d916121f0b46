"""Micro-batching GP query engine (DESIGN.md §3.7).

Fixed-capacity request slots, admission, one jitted batched step, where
the step is a GP posterior query: each wave lazily samples Φ rows for the
slot nodes (dispatch.walk_sample subset mode), takes one cross-Gram block
against the VMEM-resident train rows (kernels/gram_block), and answers
mean / variance / Thompson-draw requests from the cached Cholesky.  No CG
anywhere; a wave is O(q·K²·m + q·m²) regardless of N.

Request node-ids are admitted *individually* into slots, so a 1000-node
request simply spans several waves of a batch-64 engine — the GP analogue
of continuous batching (per-slot state is just the node id, so there is
no same-length admission constraint).

:func:`thompson_draw` is the batch-BO entry point: an exact *joint* MVN
draw over a candidate set (posterior covariance from the same cross-Gram +
triangular solve), which bo/thompson.py's incremental mode argmaxes instead
of drawing an N-long pathwise sample per step.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels import dispatch
from ..resilience import faults
from .state import ServeState, _cross_solve, _moments_impl


@dataclasses.dataclass
class GPRequest:
    """A batch of posterior queries for ``nodes`` (filled in admission order).

    ``draw`` holds one Thompson sample per node from the *marginal*
    posterior (engine waves mix nodes from different requests, so joint
    draws across a wave are not meaningful — use :func:`thompson_draw` for
    exact joint samples over one candidate set)."""

    nodes: np.ndarray
    mean: np.ndarray = None
    var: np.ndarray = None
    draw: np.ndarray = None
    admitted: int = 0
    answered: int = 0
    done: bool = False

    def __post_init__(self):
        self.nodes = np.asarray(self.nodes, dtype=np.int32).reshape(-1)
        n = len(self.nodes)
        self.mean = np.zeros(n, np.float32)
        self.var = np.zeros(n, np.float32)
        self.draw = np.zeros(n, np.float32)
        if n == 0:  # nothing to answer — never reaches a slot
            self.done = True


@partial(jax.jit, static_argnames=("spmv_backend", "obs_tap", "fault_plan"))
def _engine_step(state, slot_nodes, key, *, spmv_backend, obs_tap=False,
                 fault_plan=None):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(fault_plan):
        # var is clamped to >= 0 inside _moments_impl, so the marginal
        # Thompson draw's sqrt can never manufacture NaN.
        mean, var = _moments_impl(state, slot_nodes)
        eps = jax.random.normal(key, mean.shape, dtype=jnp.float32)
        return mean, var, mean + jnp.sqrt(var) * eps


class GPServeLoop:
    """Fixed-batch GP serving: admit up to ``batch`` concurrent node queries.

    Dead slots are padded with node 0 and answered-then-discarded — every
    wave is one call of the same compiled step (no retracing as traffic
    ebbs), mirroring the static-shape discipline of the rest of the stack.

    Partially-admitted requests queue in ``pending`` (bounded by
    ``max_pending`` requests; None = unbounded): :meth:`submit` enqueues
    with backpressure, :meth:`drain` runs the admit/step loop so callers
    don't hand-roll the retry dance around :meth:`admit` returning False.
    """

    def __init__(self, state: ServeState, batch: int,
                 key: jax.Array | None = None,
                 max_pending: int | None = None):
        self.state = state
        self.batch = batch
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.slots: list[tuple[GPRequest, int] | None] = [None] * batch
        self.slot_nodes = np.zeros(batch, dtype=np.int32)
        self.max_pending = max_pending
        self.pending: collections.deque[GPRequest] = collections.deque()

    # -- admission -----------------------------------------------------------
    def admit(self, req: GPRequest) -> bool:
        """Place pending node ids of ``req`` into free slots.

        Returns True once the request is fully admitted (its remaining
        answers arrive over the next wave(s)); False while slots ran out."""
        while req.admitted < len(req.nodes):
            try:
                slot = self.slots.index(None)
            except ValueError:
                obs.inc("serving.admit.rejects")
                return False
            self.slots[slot] = (req, req.admitted)
            self.slot_nodes[slot] = req.nodes[req.admitted]
            req.admitted += 1
            obs.inc("serving.admit.accepts")
        return True

    def submit(self, req: GPRequest) -> bool:
        """Enqueue a request for :meth:`drain` with backpressure.

        Returns False — and bumps ``serving.submit.rejects`` — when the
        bounded pending queue is full; the caller backs off (or calls
        :meth:`drain` to make room) and resubmits.  Degradation is a
        refusal at admission, never a dropped in-flight request."""
        if (self.max_pending is not None
                and len(self.pending) >= self.max_pending):
            obs.inc("serving.submit.rejects")
            return False
        self.pending.append(req)
        obs.gauge("serving.queue_depth", len(self.pending))
        return True

    # -- batched query step --------------------------------------------------
    def step(self) -> int:
        """Answer every occupied slot in one jitted wave; returns #served."""
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return 0
        self.key, sub = jax.random.split(self.key)
        fill = len(live) / self.batch
        # np.asarray blocks on the device result, so the wave span times
        # dispatch + execution honestly without an extra sync.
        with obs.span("serving.wave", fill=fill, served=len(live)):
            mean, var, draw = _engine_step(
                self.state, jnp.asarray(self.slot_nodes), sub,
                spmv_backend=dispatch.get_backend(), obs_tap=obs.enabled(),
                fault_plan=faults.active(),
            )
            mean, var, draw = (
                np.asarray(mean), np.asarray(var), np.asarray(draw)
            )
        obs.observe("serving.wave.fill", fill)
        for i in live:
            req, pos = self.slots[i]
            req.mean[pos] = mean[i]
            req.var[pos] = var[i]
            req.draw[pos] = draw[i]
            req.answered += 1
            if req.answered == len(req.nodes):
                req.done = True
            self.slots[i] = None
        return len(live)

    def drain(self, progress=None) -> int:
        """Run the admit/step loop until the pending queue and every slot
        are empty; returns the number of queries answered.  The retry loop
        callers used to hand-roll around :meth:`admit` returning False."""
        served = 0
        while self.pending or any(s is not None for s in self.slots):
            while self.pending and self.admit(self.pending[0]):
                self.pending.popleft()
            obs.gauge("serving.queue_depth", len(self.pending))
            n = self.step()
            served += n
            if progress:
                progress(n, len(self.pending))
        return served

    def run(self, requests: list[GPRequest], progress=None):
        """Enqueue ``requests`` (ignoring ``max_pending`` — an explicit
        batch is already admitted work, not new traffic) and drain."""
        self.pending.extend(requests)
        self.drain(progress)
        return requests


def thompson_draw(
    state: ServeState,
    nodes,
    key: jax.Array,
    n_samples: int = 1,
) -> jax.Array:
    """Exact joint posterior samples at ``nodes`` — returns [q, n_samples].

    Draws from N(μ, Σ) with Σ = K̂_qq − VᵀV (V = L⁻¹K̂_{x,q}) via a dense
    q×q Cholesky: O(q·m² + q³), no CG, nothing N-scale.  This is what makes
    a BO step serving-shaped — the refit loop's equivalent is an N-long
    pathwise sample per draw."""
    nodes = jnp.asarray(nodes, jnp.int32).reshape(-1)
    with obs.span("serving.thompson_draw", q=int(nodes.shape[0]),
                  n_samples=n_samples) as sp:
        out = _thompson_draw(
            state, nodes, key,
            n_samples=n_samples, spmv_backend=dispatch.get_backend(),
            obs_tap=obs.enabled(), fault_plan=faults.active(),
        )
        sp.block_on(out)
    return out


def _joint_draw_tail(trace_q, vals_q, mean, v, key, n_samples):
    """Exact joint MVN draw from the whitened cross-block (shared tail —
    the sharded engine reuses it verbatim after its psum'd cross-Gram)."""
    k_qq = dispatch.gram_block(vals_q, trace_q.cols, vals_q, trace_q.cols)
    cov = k_qq - v.T @ v
    # Estimator noise can leave tiny negative eigenvalues; a diagonal
    # jitter scaled to the prior variance keeps the q×q Cholesky SPD.
    jitter = 1e-6 * jnp.maximum(jnp.max(jnp.diag(k_qq)), 1.0)
    l_post = jnp.linalg.cholesky(
        cov + jitter * jnp.eye(cov.shape[0], dtype=cov.dtype)
    )
    # Guarded draw: if the jittered Cholesky still fails (a cov matrix
    # mangled past what jitter fixes), fall back to independent
    # marginal draws — diag(sqrt(clamped var)) — instead of returning
    # an all-NaN sample batch.  The joint structure degrades; the BO
    # loop keeps moving.
    ok = jnp.all(jnp.isfinite(l_post))
    marginal = jnp.diag(jnp.sqrt(jnp.maximum(jnp.diagonal(cov), 0.0)))
    l_post = jnp.where(ok, l_post, marginal)
    eps = jax.random.normal(
        key, (cov.shape[0], n_samples), dtype=jnp.float32
    )
    return mean[:, None] + l_post @ eps


@partial(jax.jit,
         static_argnames=("n_samples", "spmv_backend", "obs_tap",
                          "fault_plan"))
def _thompson_draw(state, nodes, key, *, n_samples, spmv_backend,
                   obs_tap=False, fault_plan=None):
    with obs.tap_scope(obs_tap), dispatch.use_backend(spmv_backend), \
            faults.fault_scope(fault_plan):
        trace_q, vals_q, mean, v = _cross_solve(state, nodes)
        return _joint_draw_tail(trace_q, vals_q, mean, v, key, n_samples)
