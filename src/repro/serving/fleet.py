"""Async double-buffered GP serving fleet (DESIGN.md §3.12).

``GPServeLoop`` (engine.py) is synchronous: every wave blocks on the
device result before the host packs the next one, and every ``observe``
pays the eager wrapper's sync barriers (``block_on`` + several
``int(flag)`` device reads).  At N=10⁶ a 64-slot wave is ~8 ms of device
work — comparable to the host-side admission/packing — so the sync loop
leaves half the machine idle.  :class:`GPFleetLoop` is the overlapped
front end, in the style of ``launch/serve.ServeLoop``:

  * **Double-buffered waves** — wave k is dispatched without
    ``block_until_ready`` and reaped at the *start* of step k+1, so the
    host admits/packs wave k+1 (and the driver submits new traffic) while
    wave k runs on device.
  * **Coalesced, donated mutations** — queued observes are batched into
    ONE ``observe_batch_async`` scan per step (one dispatch, zero syncs)
    with the mutable ServeState leaves donated, so the O(capacity²)
    Cholesky is updated in place instead of reallocated per append.
  * **Jit-safe health flags, read lazily** — overflow/rejected/needs_refit
    are checked every ``flag_check_every`` steps (and at drain), where the
    mutation chain has long retired; a pending ``needs_refit`` is answered
    with the O(m³) refit fallback exactly like the sync wrapper, just a
    few waves later (the jitter-clamped factor stays SPD meanwhile).
  * **WAL-before-dispatch** — with a ``journal``, every mutation is
    journalled (flushed, write-ahead) *before* the donated update is
    dispatched, preserving the ResilientServer recovery contract: a crash
    loses at most un-acked tail ops, never an acked mutation — and because
    donation deletes the input buffers, the journal record is the ONLY
    durable copy of an acked op the moment the dispatch returns.

**Pipeline invariant (donation safety)**: a wave in flight holds
references to the state buffers it reads, so mutations are only dispatched
at a point where no wave is in flight — :meth:`step` reaps wave k-1
*before* applying queued mutations and dispatching wave k.  Queries still
overlap fully (reap-at-next-step); only the mutate point is a pipeline
seam, never a host sync.

Works over a single-device :class:`ServeState` or a
:class:`ShardedServeState` (mutations execute once on the canonical state
and are broadcast; waves run under shard_map) — pass either to the
constructor.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels import dispatch
from ..resilience import faults
from . import update
from .engine import GPRequest, _engine_step
from .sharded import ShardedServeState, _sharded_engine_step
from .state import ServeState


@dataclasses.dataclass
class _Wave:
    """An in-flight wave: the slot snapshot + un-reaped device arrays."""

    slots: list
    mean: jax.Array
    var: jax.Array
    draw: jax.Array
    t0: float
    served: int


class GPFleetLoop:
    """Overlapped GP serving over one device or a sharded mesh.

    The submit surface mirrors ``GPServeLoop`` (PR 9 semantics):
    :meth:`submit` / :meth:`submit_observe` / :meth:`submit_forget` enqueue
    ops FIFO with bounded backpressure (``max_pending`` ops; None =
    unbounded) — a full queue refuses at admission
    (``serving.fleet.submit.rejects``), never drops in-flight work.
    :meth:`step` advances the pipeline one wave; :meth:`drain` runs it dry.

    Overflow behaves like ``on_overflow="reject"`` (the jit-safe masked
    drop): static-capacity serving cannot grow under an async pipeline, so
    excess appends bump the ``overflow`` flag and the driver sheds load —
    the same degradation ladder the sync path exposes.
    """

    def __init__(self, state: ServeState | ShardedServeState, batch: int,
                 key: jax.Array | None = None,
                 max_pending: int | None = None,
                 journal=None,
                 donate: bool = True,
                 auto_refit: bool = True,
                 flag_check_every: int = 8):
        self.sharded = isinstance(state, ShardedServeState)
        if self.sharded and batch % state.n_shards:
            raise ValueError(
                f"batch {batch} must divide evenly across "
                f"{state.n_shards} shards"
            )
        self.state = state
        self.batch = batch
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.max_pending = max_pending
        self.journal = journal
        self.donate = donate
        self.auto_refit = auto_refit
        self.flag_check_every = flag_check_every
        self.slots: list[tuple[GPRequest, int] | None] = [None] * batch
        self.slot_nodes = np.zeros(batch, dtype=np.int32)
        self.pending: collections.deque = collections.deque()
        self._inflight: _Wave | None = None
        self._flags = (0, 0)        # last-seen (overflow, rejected)
        self._steps = 0
        self.served = 0

    # -- canonical state access ----------------------------------------------
    @property
    def serve_state(self) -> ServeState:
        """The canonical single-device ServeState (source of truth)."""
        return self.state.state if self.sharded else self.state

    # -- submission (bounded, FIFO across op kinds) --------------------------
    def _submit(self, op) -> bool:
        if (self.max_pending is not None
                and len(self.pending) >= self.max_pending):
            obs.inc("serving.fleet.submit.rejects")
            return False
        self.pending.append(op)
        obs.gauge("serving.fleet.queue_depth", len(self.pending))
        return True

    def submit(self, req: GPRequest) -> bool:
        """Enqueue a query request with backpressure (False = queue full)."""
        return self._submit(("query", req))

    def submit_observe(self, nodes, ys) -> bool:
        """Enqueue observation append(s) — coalesced into one donated
        ``observe_batch`` scan with any adjacent queued observes."""
        return self._submit((
            "observe",
            np.asarray(nodes, np.int32).reshape(-1),
            np.asarray(ys, np.float32).reshape(-1),
        ))

    def submit_forget(self, slot: int) -> bool:
        """Enqueue a forget (rank-1 downdate) of buffer ``slot``."""
        return self._submit(("forget", int(slot)))

    # -- mutations (WAL → kill point → async dispatch) -----------------------
    def _apply_observe(self, nodes: np.ndarray, ys: np.ndarray) -> None:
        if self.journal is not None:
            # Write-ahead: the record must be durable BEFORE the donated
            # mutation is dispatched — donation deletes the input buffers,
            # so after dispatch the journal is the only copy of this op.
            self.journal.log(
                "observe", nodes=[int(v) for v in nodes],
                ys=[float(v) for v in ys],
                on_overflow="reject", auto_refit=self.auto_refit,
            )
        faults.kill_point("serving.fleet.observe")
        with obs.span("serving.fleet.observe", n=int(len(nodes))):
            if self.sharded:
                self.state.observe_batch(nodes, ys, sync=False)
            else:
                self.state = update.observe_batch_async(
                    self.state, nodes, ys, donate=self.donate
                )

    def _apply_forget(self, slots: list[int]) -> None:
        if self.journal is not None:
            # One record per slot: replay folds single-slot forget events,
            # and forget_batch is defined as exactly that sequential fold.
            for slot in slots:
                self.journal.log("forget", slot=int(slot))
        faults.kill_point("serving.fleet.forget")
        with obs.span("serving.fleet.forget", n=len(slots)):
            if self.sharded:
                self.state.forget_batch(slots, sync=False)
            else:
                self.state = update.forget_batch_async(
                    self.state, slots, donate=self.donate
                )

    def _process_mutations(self) -> None:
        """Apply every mutation at the queue head, coalescing runs of
        observes (and runs of forgets) into one scan dispatch each.  Stops
        at the first query so FIFO order across op kinds is preserved."""
        while self.pending and self.pending[0][0] != "query":
            if self.pending[0][0] == "observe":
                nodes, ys = [], []
                while self.pending and self.pending[0][0] == "observe":
                    _, n, yv = self.pending.popleft()
                    nodes.append(n)
                    ys.append(yv)
                self._apply_observe(np.concatenate(nodes),
                                    np.concatenate(ys))
            else:
                slots = []
                while self.pending and self.pending[0][0] == "forget":
                    _, slot = self.pending.popleft()
                    slots.append(slot)
                self._apply_forget(slots)

    # -- admission -----------------------------------------------------------
    def _admit(self, req: GPRequest) -> bool:
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

    def _admit_pending(self) -> None:
        while self.pending and self.pending[0][0] == "query":
            if not self._admit(self.pending[0][1]):
                break
            self.pending.popleft()
        obs.gauge("serving.fleet.queue_depth", len(self.pending))

    # -- the pipeline --------------------------------------------------------
    def _dispatch(self) -> None:
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return
        self.key, sub = jax.random.split(self.key)
        fill = len(live) / self.batch
        # The span times DISPATCH only (async — no block_on): device-honest
        # wave latency is serving.fleet.wave_latency, reap-to-reap.
        with obs.span("serving.fleet.dispatch", fill=fill,
                      served=len(live)):
            if self.sharded:
                mean, var, draw = _sharded_engine_step(
                    self.state.placed, jnp.asarray(self.slot_nodes), sub,
                    mesh=self.state.mesh, axis=self.state.axis,
                    spmv_backend=dispatch.get_backend(),
                    obs_tap=obs.enabled(), fault_plan=faults.active(),
                )
            else:
                mean, var, draw = _engine_step(
                    self.state, jnp.asarray(self.slot_nodes), sub,
                    spmv_backend=dispatch.get_backend(),
                    obs_tap=obs.enabled(), fault_plan=faults.active(),
                )
        self._inflight = _Wave(
            slots=list(self.slots), mean=mean, var=var, draw=draw,
            t0=time.perf_counter(), served=len(live),
        )
        # Free the slots immediately: the device holds the node ids by
        # value, so wave k+1 admission proceeds while wave k runs.
        self.slots = [None] * self.batch
        if self.sharded:
            # Every shard carries the full wave (queries replicate; train
            # rows shard), so per-shard depth is the wave size.
            for shard in range(self.state.n_shards):
                obs.gauge("serving.fleet.shard_depth", len(live),
                          labels={"shard": shard})

    def _reap(self) -> int:
        w, self._inflight = self._inflight, None
        if w is None:
            return 0
        with obs.span("serving.fleet.reap", served=w.served):
            mean = np.asarray(w.mean)
            var = np.asarray(w.var)
            draw = np.asarray(w.draw)
        obs.observe("serving.fleet.wave_latency",
                    time.perf_counter() - w.t0)
        for i, entry in enumerate(w.slots):
            if entry is None:
                continue
            req, pos = entry
            req.mean[pos] = mean[i]
            req.var[pos] = var[i]
            req.draw[pos] = draw[i]
            req.answered += 1
            if req.answered == len(req.nodes):
                req.done = True
        self.served += w.served
        return w.served

    def _check_flags(self) -> None:
        """Read the jit-safe health flags (blocks on the mutation chain —
        called where the pipeline is cheap to sync) and run the refit
        fallback if the factor has been running on jitter."""
        st = self.serve_state
        ov, rej = int(st.overflow), int(st.rejected)
        if ov > self._flags[0]:
            obs.inc("serving.observe.overflow", ov - self._flags[0])
        if rej > self._flags[1]:
            obs.inc("serving.observe.rejected", rej - self._flags[1])
        self._flags = (ov, rej)
        if self.auto_refit and int(st.needs_refit) > 0:
            obs.inc("serving.refit.fallback")
            if self.journal is not None:
                self.journal.log("refit")
            faults.kill_point("serving.fleet.refit")
            if self.sharded:
                self.state.refit()
            else:
                self.state = update.refit(self.state)

    def step(self) -> int:
        """Advance the pipeline one wave; returns #queries answered.

        Order matters: reap wave k-1 FIRST (no wave in flight afterwards —
        the donation-safety seam), then dispatch queued mutations (async,
        WAL first), admit queries into the freed slots, and dispatch wave
        k.  On return wave k runs on device while the caller does host
        work."""
        served = self._reap()
        self._process_mutations()
        self._admit_pending()
        self._dispatch()
        self._steps += 1
        if self.flag_check_every and self._steps % self.flag_check_every == 0:
            self._check_flags()
        return served

    def drain(self, progress=None) -> int:
        """Run :meth:`step` until the queue, slots and pipeline are empty;
        final flag check included.  Returns #queries answered."""
        served = 0
        while (self.pending or self._inflight is not None
               or any(s is not None for s in self.slots)):
            n = self.step()
            served += n
            if progress:
                progress(n, len(self.pending))
        self._check_flags()
        return served

    def run(self, requests: list[GPRequest], progress=None):
        """Enqueue ``requests`` (an explicit batch bypasses backpressure,
        like ``GPServeLoop.run``) and drain the pipeline."""
        for req in requests:
            self.pending.append(("query", req))
        self.drain(progress)
        return requests
