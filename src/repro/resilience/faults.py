"""Deterministic fault injection for the serving/solver stack (DESIGN.md
§3.11).

A :class:`FaultPlan` describes which failures to inject where:

  * ``nan_payload`` / ``inf_payload`` — corrupt the lazily-sampled walk
    payload rows (the only N-scale input of the serving hot path) with
    NaN/Inf at a per-node deterministic rate;
  * ``chol_fail`` — corrupt the Schur complement of a fraction of
    incremental Cholesky appends (drives the guarded-append → refit
    fallback in serving/update.py);
  * ``cg_stall`` — force the first k attempts of every *escalated* solve to
    report non-convergence (drives the solve-escalation ladder in
    solvers/escalate.py);
  * ``kill_at`` — ``os._exit`` the process at the k-th :func:`kill_point`
    event (drives the write-ahead-journal crash-recovery chaos tests).

Resolution mirrors the spmv backend registry and the obs enablement switch
exactly: :func:`use_faults` context > :func:`set_faults` global >
``REPRO_FAULTS`` env var > no faults.  The env spec is a comma-separated
``name:value`` list, e.g. ``REPRO_FAULTS=nan_payload:0.01,cg_stall:1``.

**The zero-overhead contract** is the same as obs taps: every trace-time
helper checks the active plan at *Python trace time* — with no plan active
(the default) nothing is staged and the compiled HLO is bit-identical to a
fault-free build.  The flip side is the same discipline too: instrumented
jitted consumers take the (frozen, hashable) plan as a *static* argument
and pin the trace with :func:`fault_scope`, so a plan change retraces
instead of silently reusing a clean executable.

Injection is **deterministic**: payload/append corruption is keyed on the
absolute node id hashed with ``plan.seed`` (the walk-sampler counter-RNG
discipline), so a replayed traffic stream hits byte-identical faults —
chaos runs are debuggable and the recovery tests can compare against an
uninterrupted reference run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
from contextvars import ContextVar

import jax.numpy as jnp

# Exit code used by kill_at so parents can tell an injected kill from a
# genuine crash (any other non-zero status).
KILL_EXIT_CODE = 113

_FIELDS = (
    "nan_payload", "inf_payload", "chol_fail", "cg_stall", "kill_at", "seed",
)


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """What to break, how often.  Frozen + scalar fields ⇒ hashable, so the
    plan rides jit cache keys as a static exactly like ``spmv_backend``.

    Attributes:
      nan_payload: probability a sampled walk row's payload is NaN-poisoned.
      inf_payload: probability a sampled walk row's payload is Inf-poisoned.
      chol_fail: probability an incremental append's Schur complement is
        corrupted to a near-zero value (forces the guarded-append refit
        fallback).
      cg_stall: force the first ``cg_stall`` attempts of every escalated
        solve to report non-convergence (0 = off).
      kill_at: ``os._exit(KILL_EXIT_CODE)`` at the ``kill_at``-th
        :func:`kill_point` event (1-based; -1 = off).
      seed: mixes into the per-node corruption hash.
    """

    nan_payload: float = 0.0
    inf_payload: float = 0.0
    chol_fail: float = 0.0
    cg_stall: int = 0
    kill_at: int = -1
    seed: int = 0

    def __post_init__(self):
        for name in ("nan_payload", "inf_payload", "chol_fail"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be a probability, got {v!r}")
        if self.cg_stall < 0:
            raise ValueError(f"cg_stall must be >= 0, got {self.cg_stall}")

    @property
    def corrupts_payload(self) -> bool:
        return self.nan_payload > 0 or self.inf_payload > 0

    @property
    def corrupts_schur(self) -> bool:
        return self.chol_fail > 0

    def spec(self) -> str:
        """The ``name:value`` spec string this plan round-trips through."""
        parts = []
        defaults = FaultPlan()
        for name in _FIELDS:
            v = getattr(self, name)
            if v != getattr(defaults, name):
                parts.append(f"{name}:{v}")
        return ",".join(parts)


def parse_faults(spec: str) -> FaultPlan | None:
    """``"nan_payload:0.01,cg_stall:1"`` → :class:`FaultPlan` (None when
    the spec is empty/"off").  Unknown names raise with the valid set —
    a typoed chaos run must fail loudly, not run clean."""
    spec = (spec or "").strip()
    if not spec or spec.lower() in ("0", "off", "none", "false"):
        return None
    kw: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" not in part:
            raise ValueError(
                f"fault spec entry {part!r} is not name:value; valid names: "
                f"{_FIELDS}"
            )
        name, _, raw = part.partition(":")
        name = name.strip()
        if name not in _FIELDS:
            raise ValueError(
                f"unknown fault {name!r}; valid names: {_FIELDS}"
            )
        kw[name] = (
            int(raw) if name in ("cg_stall", "kill_at", "seed") else float(raw)
        )
    return FaultPlan(**kw)


# ---------------------------------------------------------------------------
# Resolution: context > global > REPRO_FAULTS env > off — the dispatch.py /
# obs.registry pattern.  The context layer distinguishes "unset" (fall
# through) from an explicit None pin (fault_scope(None) inside a trace must
# mean *no faults*, not "whatever the env says at retrace time").
# ---------------------------------------------------------------------------

_UNSET = object()
_global_plan: FaultPlan | None | object = _UNSET
_override: ContextVar = ContextVar("repro_faults", default=_UNSET)


def active() -> FaultPlan | None:
    """Resolve the active fault plan (context > global > env > None)."""
    ov = _override.get()
    if ov is not _UNSET:
        return ov
    if _global_plan is not _UNSET:
        return _global_plan
    return parse_faults(os.environ.get("REPRO_FAULTS", ""))


def set_faults(plan: FaultPlan | str | None) -> None:
    """Set the process-global fault plan (a spec string is parsed)."""
    global _global_plan
    if isinstance(plan, str):
        plan = parse_faults(plan)
    _global_plan = plan


def reset_faults() -> None:
    """Restore env-var/default resolution (mainly for tests)."""
    global _global_plan
    _global_plan = _UNSET
    reset_kill_counter()


@contextlib.contextmanager
def use_faults(plan: FaultPlan | str | None):
    """Scoped fault plan override (a spec string is parsed; None disables)."""
    if isinstance(plan, str):
        plan = parse_faults(plan)
    token = _override.set(plan)
    try:
        yield plan
    finally:
        _override.reset(token)


@contextlib.contextmanager
def fault_scope(plan: FaultPlan | None):
    """Pin :func:`active` to exactly ``plan`` for the duration of the
    context.  Instrumented jitted functions take the plan as a static
    argument and wrap their body in this — the trace then depends only on
    the cache-keyed static, never on ambient global/env state (the
    ``tap_scope``/``use_backend`` discipline)."""
    token = _override.set(plan)
    try:
        yield
    finally:
        _override.reset(token)


# ---------------------------------------------------------------------------
# Trace-time injection + guards.  Zero staged ops when no plan is active.
# ---------------------------------------------------------------------------


def _hash01(x, seed: int):
    """Deterministic per-id uniform in [0, 1) — fmix-style integer mix of
    the absolute node id with the plan seed (the walk-RNG keying rule, so
    chunked/replayed streams hit identical faults)."""
    mix = (seed * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF
    x = x.astype(jnp.uint32) ^ jnp.uint32(mix)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x.astype(jnp.float32) * jnp.float32(1.0 / 4294967296.0)


def corrupt_loads(loads, nodes):
    """NaN/Inf-poison whole payload rows at the plan's per-node rate.

    Called from the lazy row sampler (serving.state.query_rows) at trace
    time; stages nothing when no plan corrupts payloads."""
    plan = active()
    if plan is None or not plan.corrupts_payload:
        return loads
    u = _hash01(nodes, plan.seed)
    bad_nan = u < plan.nan_payload
    bad_inf = (u >= plan.nan_payload) & (
        u < plan.nan_payload + plan.inf_payload
    )
    loads = jnp.where(bad_nan[:, None], jnp.float32(jnp.nan), loads)
    return jnp.where(bad_inf[:, None], jnp.float32(jnp.inf), loads)


def corrupt_schur(d2, node):
    """Corrupt the append's Schur complement to a near-zero negative value
    at the plan's per-node rate — the injected stand-in for catastrophic
    f32 cancellation on near-duplicate observations."""
    plan = active()
    if plan is None or not plan.corrupts_schur:
        return d2
    bad = _hash01(jnp.atleast_1d(node), plan.seed + 1)[0] < plan.chol_fail
    return jnp.where(bad, jnp.float32(-1e-6), d2)


def guard_trace(trace):
    """Sanitise a lazily-sampled query trace: zero any non-finite payload
    row so a poisoned query degrades to the prior prediction for that node
    instead of propagating NaN through the whole wave.

    Staged only when a fault plan is active — the serving *query* hot path
    stays byte-identical to the fault-free build otherwise (the estimator
    is PSD by construction, so un-injected non-finites are bugs that the
    always-on *append* guards will catch at observation time)."""
    plan = active()
    if plan is None or not plan.corrupts_payload:
        return trace
    from .. import obs
    from ..core.walks import WalkTrace

    ok = jnp.all(jnp.isfinite(trace.loads), axis=1)
    obs.taps.tap(
        "serving.query.sanitized",
        jnp.sum(~ok).astype(jnp.int32),
        kind="counter",
    )
    return WalkTrace(
        cols=trace.cols,
        loads=jnp.where(ok[:, None], trace.loads, 0.0),
        lens=trace.lens,
    )


# ---------------------------------------------------------------------------
# Host-level faults: solve stalls and process kills.
# ---------------------------------------------------------------------------


def should_stall(attempt: int) -> bool:
    """True when the active plan forces escalated-solve ``attempt``
    (0-based) to report non-convergence.  ``cg_stall:k`` stalls the first
    k attempts of *every* escalated solve — deterministic, so the ladder
    provably resolves each stall in exactly k extra rungs."""
    plan = active()
    return plan is not None and attempt < plan.cg_stall


_kill_events = 0


def reset_kill_counter() -> None:
    global _kill_events
    _kill_events = 0


def kill_events() -> int:
    """How many kill-point events the active plan has counted so far."""
    return _kill_events


def kill_point(name: str) -> None:
    """Crash site: with ``kill_at:k`` active, the k-th call (1-based,
    process-wide) exits hard with :data:`KILL_EXIT_CODE` — no atexit, no
    flushing, the honest SIGKILL stand-in the journal recovery tests
    replay against.  Free when no plan sets ``kill_at``."""
    plan = active()
    if plan is None or plan.kill_at < 0:
        return
    global _kill_events
    _kill_events += 1
    if _kill_events == plan.kill_at:
        sys.stderr.write(f"[faults] kill_at={plan.kill_at} hit at {name!r}\n")
        sys.stderr.flush()
        os._exit(KILL_EXIT_CODE)
