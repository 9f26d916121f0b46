"""Pallas TPU kernel: ELL sparse matvec y = Φ u (GRF K̂-matvec hot spot).

TPU adaptation of the paper's sparse-tensor product (DESIGN.md §3):

  * Rows are tiled into BM-row VMEM blocks; the (vals, cols) ELL payload is
    streamed HBM→VMEM exactly once — this op is memory-bound, so streaming
    the payload once is the roofline optimum.
  * The dense operand ``u`` is kept *entirely resident in VMEM* across the
    grid (block index map pins it to block 0): a 1M-node f32 vector is 4 MB
    < 16 MB VMEM, so the random per-row gathers never touch HBM.
  * The gather itself is expressed as ``jnp.take`` over the VMEM-resident
    operand, which Mosaic lowers to on-chip dynamic addressing.

Grid: (M // BM,).  Per-step VMEM: BM·K·(4+4) + N·4·R + BM·4·R bytes.

**Not used on TPU.**  Mosaic refuses the ``jnp.take`` ("Only 2D gather is
supported"), and at N=10⁶, R=9 the resident operand (36 MB) would not fit
VMEM anyway.  kernels/dispatch.py therefore runs Φu through its ``"xla"``
implementation on TPU; this kernel is exercised through the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BM = 256


def _spmv_kernel(vals_ref, cols_ref, u_ref, out_ref):
    vals = vals_ref[:]          # [BM, K]
    cols = cols_ref[:]          # [BM, K]
    u = u_ref[:]                # [N] or [N, R] — resident across grid steps
    gathered = jnp.take(u, cols, axis=0)  # [BM, K] or [BM, K, R]
    if u.ndim == 1:
        out_ref[:] = jnp.sum(vals * gathered, axis=1)
    else:
        out_ref[:] = jnp.einsum(
            "mk,mkr->mr", vals, gathered, preferred_element_type=jnp.float32
        )


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def ell_spmv(
    vals: jax.Array,
    cols: jax.Array,
    u: jax.Array,
    *,
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
) -> jax.Array:
    """y = Φ u with Φ in ELL format.  See ref.py for semantics."""
    m, k = vals.shape
    single = u.ndim == 1
    n = u.shape[0]

    # Pad rows to a BM multiple (zero vals ⇒ padded rows produce zeros).
    bm = min(block_m, max(8, m))
    pad_m = (-m) % bm
    if pad_m:
        vals = jnp.pad(vals, ((0, pad_m), (0, 0)))
        cols = jnp.pad(cols, ((0, pad_m), (0, 0)))
    mp = m + pad_m

    if single:
        out_shape = jax.ShapeDtypeStruct((mp,), jnp.float32)
        out_spec = pl.BlockSpec((bm,), lambda i: (i,))
    else:
        r = u.shape[1]
        out_shape = jax.ShapeDtypeStruct((mp, r), jnp.float32)
        out_spec = pl.BlockSpec((bm, r), lambda i: (i, 0))

    u_spec = (
        pl.BlockSpec((n,), lambda i: (0,))
        if single
        else pl.BlockSpec((n, u.shape[1]), lambda i: (0, 0))
    )

    y = pl.pallas_call(
        _spmv_kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            u_spec,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(vals.astype(jnp.float32), cols, u.astype(jnp.float32))
    return y[:m] if pad_m else y
