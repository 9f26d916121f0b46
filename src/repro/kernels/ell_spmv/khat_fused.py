"""Pallas TPU kernel: fused K̂-matvec y = Φ_rows (Φ_colsᵀ v).

The paper's whole O(N^{3/2}) bound (Thm. 2, Lemma 1) rides on this product
chain.  Composing the two ell_spmv kernels would round-trip the N-length
intermediate u = Φᵀv through HBM between the scatter and the gather; this
kernel keeps ``u`` in a VMEM *scratch accumulator for the whole grid*:

  phase 0  (scatter):  each BM-row block of the column payload accumulates
                       vals_s·v into the resident u.
  phase 1  (gather):   each BM-row block of the row payload reads u at
                       on-chip latency and reduces into its output block.

Grid: (2, NB) with NB = ceil(max(M_rows, M_cols) / BM); both payloads are
zero-padded to NB blocks so the same grid covers the rectangular
cross-covariance form K̂[rows, cols] (Eq. 12) as well as the square K̂.
u is written to HBM zero times — it lives and dies in VMEM (N·4·R bytes;
a 1M-node f32 vector is 4 MB < 16 MB VMEM).

**Not used on TPU.**  Phase 0 is the same scatter-add as ell_spmv_t.py,
which Mosaic does not lower; kernels/dispatch.py composes the two ``"xla"``
products on TPU instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256


def _khat_kernel(vals_s_ref, cols_s_ref, v_ref, vals_g_ref, cols_g_ref,
                 out_ref, u_ref):
    phase = pl.program_id(0)

    @pl.when((phase == 0) & (pl.program_id(1) == 0))
    def _init():
        u_ref[:] = jnp.zeros_like(u_ref)

    @pl.when(phase == 0)
    def _scatter():
        # bf16 payloads stream at half bandwidth and upcast here — the
        # scatter/gather arithmetic and the resident u are always f32.
        vals = vals_s_ref[:].astype(jnp.float32)   # [BM, Ks]
        cols = cols_s_ref[:].reshape(-1)
        v = v_ref[:]                         # [BM] or [BM, R]
        if v.ndim == 1:
            contrib = (vals * v[:, None]).reshape(-1)
        else:
            contrib = (vals[..., None] * v[:, None, :]).reshape(-1, v.shape[-1])
        u_ref[:] = u_ref[:].at[cols].add(contrib)
        # Placeholder so every out block holds defined values; phase 1
        # revisits the same block index and overwrites with the real result.
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(phase == 1)
    def _gather():
        vals = vals_g_ref[:].astype(jnp.float32)   # [BM, Kg]
        cols = cols_g_ref[:]
        u = u_ref[:]                         # [N] or [N, R], resident
        gathered = jnp.take(u, cols, axis=0)
        if u.ndim == 1:
            out_ref[:] = jnp.sum(vals * gathered, axis=1)
        else:
            out_ref[:] = jnp.einsum(
                "mk,mkr->mr", vals, gathered,
                preferred_element_type=jnp.float32,
            )


def _pad_rows(a, rows):
    pad = rows - a.shape[0]
    if pad <= 0:
        return a
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "block_m", "interpret")
)
def khat_matvec_fused(
    vals_rows: jax.Array,
    cols_rows: jax.Array,
    vals_cols: jax.Array,
    cols_cols: jax.Array,
    v: jax.Array,
    n_nodes: int,
    *,
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
) -> jax.Array:
    """y = Φ_rows (Φ_colsᵀ v).  See ref.py for semantics."""
    mg, kg = vals_rows.shape
    ms, ks = vals_cols.shape
    single = v.ndim == 1

    bm = min(block_m, max(8, max(mg, ms)))
    nb = -(-max(mg, ms) // bm)               # ceil-div: shared phase length
    rows = nb * bm

    def _payload(a):
        # bf16 payloads pass through (upcast happens in-kernel, so the HBM
        # stream stays half-width); everything else normalises to f32.
        return a if a.dtype == jnp.bfloat16 else a.astype(jnp.float32)

    vals_g = _pad_rows(_payload(vals_rows), rows)
    cols_g = _pad_rows(cols_rows, rows)
    vals_s = _pad_rows(_payload(vals_cols), rows)
    cols_s = _pad_rows(cols_cols, rows)
    v = _pad_rows(v.astype(jnp.float32), rows)

    if single:
        out_shape = jax.ShapeDtypeStruct((rows,), jnp.float32)
        out_spec = pl.BlockSpec((bm,), lambda p, i: (i,))
        v_spec = pl.BlockSpec((bm,), lambda p, i: (i,))
        scratch = pltpu.VMEM((n_nodes,), jnp.float32)
    else:
        r = v.shape[1]
        out_shape = jax.ShapeDtypeStruct((rows, r), jnp.float32)
        out_spec = pl.BlockSpec((bm, r), lambda p, i: (i, 0))
        v_spec = pl.BlockSpec((bm, r), lambda p, i: (i, 0))
        scratch = pltpu.VMEM((n_nodes, r), jnp.float32)

    y = pl.pallas_call(
        _khat_kernel,
        grid=(2, nb),
        in_specs=[
            pl.BlockSpec((bm, ks), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, ks), lambda p, i: (i, 0)),
            v_spec,
            pl.BlockSpec((bm, kg), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, kg), lambda p, i: (i, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[scratch],
        interpret=interpret,
    )(vals_s, cols_s, v, vals_g, cols_g)
    return y[:mg] if rows != mg else y
