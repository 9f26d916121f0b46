"""Pallas TPU kernel: ELL transpose matvec u = Φᵀ v (the scatter half).

Counterpart to ``ell_spmv`` (DESIGN.md §3).  The full-length output vector
``u`` [N(, R)] is pinned to block 0 of the grid so it stays *resident in
VMEM across every grid step*: each BM-row block scatters its contributions
``vals[m,k]·v[m]`` into the live accumulator at on-chip latency, and the
N-vector is flushed to HBM exactly once at the end of the grid — the
roofline optimum for a memory-bound scatter (payload streamed once, output
written once).

The scatter itself is expressed as ``acc.at[cols].add(contrib)`` over the
VMEM-resident accumulator.

**Not used on TPU.**  Mosaic has no lowering for the scatter-add
("Unimplemented primitive ... scatter-add"), so kernels/dispatch.py runs
Φᵀv through its ``"xla"`` implementation on TPU; this kernel is exercised
through the interpreter, where it is exact.

Grid: (M // BM,).  Per-step VMEM: BM·K·(4+4) + N·4·R + BM·4·R bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BM = 256


def _spmv_t_kernel(vals_ref, cols_ref, v_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    vals = vals_ref[:]                       # [BM, K]
    cols = cols_ref[:].reshape(-1)           # [BM*K]
    v = v_ref[:]                             # [BM] or [BM, R]
    acc = out_ref[:]                         # resident accumulator
    if v.ndim == 1:
        contrib = (vals * v[:, None]).reshape(-1)
    else:
        contrib = (vals[..., None] * v[:, None, :]).reshape(-1, v.shape[-1])
    out_ref[:] = acc.at[cols].add(contrib)


@functools.partial(jax.jit, static_argnames=("n_nodes", "block_m", "interpret"))
def ell_spmv_t(
    vals: jax.Array,
    cols: jax.Array,
    v: jax.Array,
    n_nodes: int,
    *,
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
) -> jax.Array:
    """u = Φᵀ v with Φ in ELL format.  See ref.py for semantics."""
    m, k = vals.shape
    single = v.ndim == 1

    bm = min(block_m, max(8, m))
    pad_m = (-m) % bm
    if pad_m:
        # Zero vals ⇒ padded rows scatter nothing (their cols point at 0).
        vals = jnp.pad(vals, ((0, pad_m), (0, 0)))
        cols = jnp.pad(cols, ((0, pad_m), (0, 0)))
        v = jnp.pad(v, ((0, pad_m),) + ((0, 0),) * (v.ndim - 1))
    mp = m + pad_m

    if single:
        out_shape = jax.ShapeDtypeStruct((n_nodes,), jnp.float32)
        out_spec = pl.BlockSpec((n_nodes,), lambda i: (0,))
        v_spec = pl.BlockSpec((bm,), lambda i: (i,))
    else:
        r = v.shape[1]
        out_shape = jax.ShapeDtypeStruct((n_nodes, r), jnp.float32)
        out_spec = pl.BlockSpec((n_nodes, r), lambda i: (0, 0))
        v_spec = pl.BlockSpec((bm, r), lambda i: (i, 0))

    return pl.pallas_call(
        _spmv_t_kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            v_spec,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(vals.astype(jnp.float32), cols, v.astype(jnp.float32))
