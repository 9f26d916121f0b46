"""Backend registry for the GRF sparse linear-algebra stack (DESIGN.md §3).

Every sparse product in the codebase — ``phi_matvec`` (gather), ``phi_t_matvec``
(scatter), the fused ``khat_matvec``, the serving cross-Gram ``gram_block``,
the Nyström ``woodbury_apply`` and the ``walk_sample`` walker — is dispatched
through this registry instead of hard-coding an implementation at the call
site.  Three backends:

  * ``"xla"``              pure-jnp gather/scatter (differentiable, portable).
  * ``"pallas"``           compiled Mosaic kernels (TPU).
  * ``"pallas-interpret"`` the same kernels through the Pallas interpreter
                           (CPU-testable bit-accurate stand-in for "pallas";
                           a test backend, refused on TPU).

Resolution order: active :func:`use_backend` context > :func:`set_backend`
global > ``REPRO_SPMV_BACKEND`` env var (how the CI backend matrix pins the
whole suite to one backend) > auto (``"pallas"`` on TPU, ``"xla"``
elsewhere).  Backend selection happens at Python trace time, so switching
backends retraces but adds zero per-call overhead inside jit.

On TPU, ``"pallas"`` is then narrowed per product by :func:`resolve`, a
fixed rule: only the products in :data:`PALLAS_ON_TPU` — the kernels that
Mosaic lowers, each compiled for v5e by tests/test_chip_compile.py — run as
Pallas kernels; the rest run their ``"xla"`` implementation, which XLA
compiles for the TPU.  DESIGN.md §3.3 gives the compiler's reason for each.

The Pallas paths are wrapped in ``jax.custom_vjp`` (all three products are
linear in both ``vals`` and the dense operand), so hyperparameter gradients
flow through the kernels — the XLA backend is never silently required.

Each product runs under a ``jax.named_scope`` of its own (:data:`SCOPES`),
so every call site's device operations carry the product's name in their
op metadata, and a profile can sum device time per product.  Scopes are
trace-time metadata: they change no operation and cost nothing at run
time.
"""
from __future__ import annotations

import contextlib
import functools
import os
from contextvars import ContextVar

import jax
import numpy as np
from jax._src import cache_key as _cache_key

VALID_BACKENDS = ("xla", "pallas", "pallas-interpret")

_global_backend: str | None = None
_override: ContextVar[str | None] = ContextVar("grf_spmv_backend", default=None)


def _check(name: str) -> str:
    if name not in VALID_BACKENDS:
        raise ValueError(f"unknown spmv backend {name!r}; valid: {VALID_BACKENDS}")
    return name


# Products whose Pallas kernel lowers on TPU.  ell_spmv / khat_fused (a
# gather and scatter-adds into VMEM) and walk_sampler (a gather over the
# VMEM-pinned adjacency) do not lower in Mosaic as written; DESIGN.md §3.3.
PALLAS_ON_TPU = frozenset({"gram_block", "woodbury_apply"})
PRODUCTS = (
    "phi_matvec", "phi_t_matvec", "khat_matvec", "gram_block",
    "woodbury_apply", "walk_sample",
)


# The name scope each product's device operations carry (DESIGN.md §3.10).
# No scope is named after a function of the program, so a match on one
# never catches an unrelated op.
SCOPES = {
    "walk_sample": "grf_walks",
    "phi_matvec": "grf_phi",
    "phi_t_matvec": "grf_phi_t",
    "khat_matvec": "grf_khat",
    "gram_block": "grf_gram",
    "woodbury_apply": "grf_woodbury",
}
# The ELL payload vals = loads · f[lens] (core/features.py).
PAYLOAD_SCOPE = "grf_payload"
# The serving factor's triangular solves, and its row append and rank-1
# downdate (serving/state.py, serving/update.py).
CHOL_SOLVE_SCOPE = "chol_solve"
CHOL_UPDATE_SCOPE = "chol_update"
NAMED_SCOPES = (*SCOPES.values(), PAYLOAD_SCOPE, CHOL_SOLVE_SCOPE,
                CHOL_UPDATE_SCOPE)


def _cache_key_names() -> str:
    return "name scopes: " + ",".join(NAMED_SCOPES)


# The persistent compilation cache keys a program with its debug info
# stripped, and a name scope is debug info: an executable compiled by a
# version of the program that named its work otherwise would be served, and
# profiled, under the old names.  So the names go into every cache key.
_cache_key.custom_hook = _cache_key_names


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``, entered
    afresh on each call (one named_scope object is not re-entrant)."""
    def wrap(fn):
        @functools.wraps(fn)
        def run_scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return run_scoped
    return wrap


def auto_backend() -> str:
    """Default backend for the current platform."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def get_backend() -> str:
    """Resolve the active backend (context > global > env var > auto)."""
    ov = _override.get()
    if ov is not None:
        return ov
    if _global_backend is not None:
        return _global_backend
    env = os.environ.get("REPRO_SPMV_BACKEND")
    if env:
        return _check(env)
    return auto_backend()


def set_backend(name: str | None) -> None:
    """Set the process-global backend; ``None`` restores auto-selection."""
    global _global_backend
    _global_backend = None if name is None else _check(name)


@contextlib.contextmanager
def use_backend(name: str):
    """Scoped backend override (re-entrant, safe under nested contexts)."""
    token = _override.set(_check(name))
    try:
        yield
    finally:
        _override.reset(token)


def resolve(product: str, backend: str | None = None) -> str:
    """The implementation ``product`` runs under ``backend`` (default: the
    active backend) on this platform — the fixed per-product TPU rule."""
    backend = get_backend() if backend is None else _check(backend)
    if jax.default_backend() != "tpu":
        return backend
    if backend == "pallas-interpret":
        raise ValueError("pallas-interpret is a CPU test backend; on TPU use "
                         "'pallas' or 'xla'")
    if backend == "pallas" and product not in PALLAS_ON_TPU:
        return "xla"
    return backend


def chosen_backends() -> dict[str, str]:
    """{product: implementation} under the active backend — what a run
    reports as its kernel choice."""
    return {p: resolve(p) for p in PRODUCTS}


def _interpret(backend: str) -> bool:
    return backend == "pallas-interpret"


# ---------------------------------------------------------------------------
# Dispatched products.  vals/cols are the ELL payload ([M, K]); the dense
# operand is [N] or [N, R].  All are linear maps with hand-written VJPs on
# the Pallas paths (see kernels/ell_spmv/ops.py).
# ---------------------------------------------------------------------------


@scoped(SCOPES["phi_matvec"])
def phi_matvec(vals, cols, u, *, backend: str | None = None):
    """y = Φ u (gather-reduce)."""
    backend = resolve("phi_matvec", backend)
    from .ell_spmv import ops

    if backend == "xla":
        return ops.spmv_xla(vals, cols, u)
    return ops.spmv_pallas(vals, cols, u, interpret=_interpret(backend))


@scoped(SCOPES["phi_t_matvec"])
def phi_t_matvec(vals, cols, v, n_nodes: int, *, backend: str | None = None):
    """u = Φᵀ v (scatter-add)."""
    backend = resolve("phi_t_matvec", backend)
    from .ell_spmv import ops

    if backend == "xla":
        return ops.spmv_t_xla(vals, cols, v, n_nodes)
    return ops.spmv_t_pallas(vals, cols, v, n_nodes, interpret=_interpret(backend))


@scoped(SCOPES["khat_matvec"])
def khat_matvec(
    vals_rows, cols_rows, vals_cols, cols_cols, v, n_nodes: int,
    *, backend: str | None = None,
):
    """y = Φ_rows (Φ_colsᵀ v) — the K̂-matvec, fused on Pallas backends.

    The fused kernel keeps the intermediate u = Φᵀv resident in VMEM across
    the gather pass (never spilling the N-vector to HBM between the two
    products); the XLA path composes the two products.
    """
    backend = resolve("khat_matvec", backend)
    from .ell_spmv import ops

    if backend == "xla":
        # Through the dispatched halves, so their scopes nest under this one.
        u = phi_t_matvec(vals_cols, cols_cols, v, n_nodes, backend="xla")
        return phi_matvec(vals_rows, cols_rows, u, backend="xla")
    return ops.khat_pallas(
        vals_rows, cols_rows, vals_cols, cols_cols, v, n_nodes,
        interpret=_interpret(backend),
    )


@scoped(SCOPES["gram_block"])
def gram_block(
    vals_rows, cols_rows, vals_cols, cols_cols, *, backend: str | None = None,
):
    """G = Φ_rows Φ_colsᵀ as a dense [M_rows, M_cols] block (no N-space).

    The serving hot path: cross-covariance K̂_{q,x} between lazily-sampled
    query rows and the cached train rows of a ServeState — O(M_r·M_c·K²)
    compare-and-accumulate, never materialising anything N-long.  Handles
    duplicate deposit columns exactly, so diag(gram_block(Φ, Φ)) is the
    *exact* ‖φ(i)‖² (cf. features.khat_diag_exact)."""
    backend = resolve("gram_block", backend)
    from .gram_block import ops

    if backend == "xla":
        return ops.gram_block_xla(vals_rows, cols_rows, vals_cols, cols_cols)
    return ops.gram_block_pallas(
        vals_rows, cols_rows, vals_cols, cols_cols,
        interpret=_interpret(backend),
    )


@scoped(SCOPES["woodbury_apply"])
def woodbury_apply(b, dinv, einv, v, *, backend: str | None = None):
    """M⁻¹v = D⁻¹v − D⁻¹B E⁻¹ BᵀD⁻¹v — the Nyström–Woodbury apply, fused
    on Pallas backends.

    All preconditioner operands (B, D⁻¹, E⁻¹) are loop-invariant across a
    CG solve; the kernel keeps the [r, R] rank-space intermediate and the
    r×r inverse capacitance VMEM-resident so the per-iteration apply is one
    pass instead of a chain of re-materialised XLA ops."""
    backend = resolve("woodbury_apply", backend)
    from .woodbury_apply import ops

    if backend == "xla":
        return ops.woodbury_xla(b, dinv, einv, v)
    return ops.woodbury_pallas(b, dinv, einv, v, interpret=_interpret(backend))


@scoped(SCOPES["walk_sample"])
def walk_sample(
    walk_table, deg, nodes, seed,
    *, n_walkers: int, p_halt: float, l_max: int, reweight: bool = True,
    scheme: str = "iid", backend: str | None = None,
):
    """(cols, loads, lens) = GRF walk deposits for ``nodes`` in ELL layout,
    walked over a graph's ``walk_table`` and ``deg`` (graphs/formats.Graph).

    The counter-based RNG (kernels/walk_sampler/rng.py) is keyed on the
    absolute start-node id, so the result is independent of how ``nodes``
    is chunked across calls — the contract the chunked drivers in
    core/walks.py and core/features.py rely on.  ``scheme`` selects the
    variance-reduction strategy ("iid" | "antithetic" | "qmc" | "grfspp",
    DESIGN.md §3.9); like the backend it is resolved at trace time and
    rides the jit cache key as a static."""
    backend = resolve("walk_sample", backend)
    from ..obs import taps as _obs_taps
    from .walk_sampler import ops

    # Rows per call are static (ELL layout): one executed-count per wave,
    # labelled by the trace-time scheme/backend statics.
    _labels = {"scheme": scheme, "backend": backend}
    _obs_taps.count("walks.rows_sampled", n=int(nodes.shape[0]), labels=_labels)
    _obs_taps.count(
        "walks.walkers_launched",
        n=int(nodes.shape[0]) * int(n_walkers),
        labels=_labels,
    )
    if backend == "xla":
        return ops.walk_sample_xla(
            walk_table, deg, nodes, seed,
            n_walkers=n_walkers, p_halt=p_halt, l_max=l_max, reweight=reweight,
            scheme=scheme,
        )
    return ops.walk_sample_pallas(
        walk_table, deg, nodes, seed,
        n_walkers=n_walkers, p_halt=p_halt, l_max=l_max, reweight=reweight,
        scheme=scheme, interpret=_interpret(backend),
    )


def float0_zeros(x):
    """Symbolic-zero cotangent for integer (non-differentiable) array args."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)
