"""Pallas TPU kernel: sparse×sparse cross-Gram block G = Φ_rows Φ_colsᵀ.

The serving hot path (DESIGN.md §3.7): every posterior query against an
online :class:`~repro.serving.state.ServeState` reduces to one rectangular
Gram block K̂_{q,x} between the lazily-sampled query rows and the cached
train rows.  Both operands are ELL payloads, so the product never touches
the N-dimensional node space at all — the contraction is a masked
compare-and-accumulate over deposit slots.

Layout (chosen so Mosaic lowers it; DESIGN.md §3.3):

  * Query rows are tiled into BQ-row blocks held in **SMEM**: every
    (row, slot) deposit is read as a scalar at a dynamic slot index, which
    Mosaic supports where a dynamic lane slice of a vector does not.
  * The train payload is passed transposed, [K_x, M_x], and tiled into
    BX-column blocks in VMEM, so train rows lie on the 128 lanes and the
    deposit slots on sublanes.
  * Per query row a ``fori_loop`` over its K_r slots accumulates
    ``where(cols_x == c, vals_x, 0) · v`` into one [K_x, BX] tile; a single
    sublane reduction at the end gives the row of G.

Grid: (ceil(M_r / BQ), ceil(M_x / BX)).  Per-step VMEM:
  2·K_x·BX·4 (train tile) + K_x·BX·4 (accumulator) + BQ·BX·4 (output);
K_x=56, BX=512 → ~0.4 MB, so the kernel fits scoped VMEM at any M_x.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 8
DEFAULT_BX = 512


def _gram_kernel(cols_q_ref, vals_q_ref, cols_xt_ref, vals_xt_ref, out_ref):
    bq, k_r = cols_q_ref.shape
    cols_xt = cols_xt_ref[...]               # [K_x, BX]
    vals_xt = vals_xt_ref[...]
    for i in range(bq):
        def slot(k, acc, i=i):
            hit = jnp.where(cols_xt == cols_q_ref[i, k], vals_xt, 0.0)
            return acc + vals_q_ref[i, k] * hit

        acc = jax.lax.fori_loop(
            0, k_r, slot, jnp.zeros(vals_xt.shape, jnp.float32)
        )
        out_ref[i:i + 1, :] = jnp.sum(acc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def gram_block(
    vals_rows: jax.Array,
    cols_rows: jax.Array,
    vals_cols: jax.Array,
    cols_cols: jax.Array,
    *,
    block_q: int = DEFAULT_BQ,
    interpret: bool = False,
) -> jax.Array:
    """G = Φ_rows Φ_colsᵀ ∈ R^{M_r × M_c}.  See ref.py for semantics."""
    mr, kr = vals_rows.shape
    mx, kx = vals_cols.shape
    bq = block_q
    bx = min(DEFAULT_BX, -(-mx // 128) * 128)
    pad_r = (-mr) % bq
    pad_x = (-mx) % bx
    # Zero vals ⇒ padded rows on either side produce zero Gram entries.
    vals_q = jnp.pad(vals_rows.astype(jnp.float32), ((0, pad_r), (0, 0)))
    cols_q = jnp.pad(cols_rows, ((0, pad_r), (0, 0)))
    vals_xt = jnp.pad(vals_cols.astype(jnp.float32), ((0, pad_x), (0, 0))).T
    cols_xt = jnp.pad(cols_cols, ((0, pad_x), (0, 0))).T
    mp, xp = mr + pad_r, mx + pad_x

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    y = pl.pallas_call(
        _gram_kernel,
        grid=(mp // bq, xp // bx),
        in_specs=[
            smem((bq, kr), lambda i, j: (i, 0)),
            smem((bq, kr), lambda i, j: (i, 0)),
            pl.BlockSpec((kx, bx), lambda i, j: (0, j)),
            pl.BlockSpec((kx, bx), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bx), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, xp), jnp.float32),
        interpret=interpret,
    )(cols_q, vals_q, cols_xt, vals_xt)
    return y[:mr, :mx]
