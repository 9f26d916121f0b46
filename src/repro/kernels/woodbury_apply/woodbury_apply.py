"""Pallas TPU kernel: fused Nyström–Woodbury preconditioner apply.

Per CG iteration the Nyström preconditioner (solvers/nystrom.py) computes

    M⁻¹ v = D⁻¹v − D⁻¹B E⁻¹ BᵀD⁻¹v

with loop-invariant B [T, r], D⁻¹ [T] and E⁻¹ [r, r].  Composed XLA ops
re-materialise the [T, R] intermediates (w, Bᵀw, B·s) through HBM every
iteration; this kernel is one pass in the khat_fused two-phase shape:

  phase 0 (reduce):   each BT-row block accumulates Bᵀ(D⁻¹v) into an
                      [r, R] VMEM scratch accumulator — the rank-space
                      intermediate never exists in HBM at all.
  phase 1 (expand):   at the first block the resident accumulator is folded
                      through the capacitance (s ← E⁻¹s, one [r, r]×[r, R]
                      MXU product against the block-0-pinned E⁻¹); every
                      block then emits  out = D⁻¹v − D⁻¹(B s)  fused with
                      the diagonal scale and residual subtraction.

D⁻¹ enters as a [T, 1] column: a 1-D block is tiled differently by XLA
(T(1024)) and Mosaic (T(512)), and the chip's compiler refuses the
mismatch; a 2-D [BT, 1] block has one layout on both sides.

Grid: (2, NB), NB = ceil(T / BT).  Per-step VMEM:
  BT·r·4 (factor block) + r·(R + r)·4 (scratch + resident E⁻¹)
  + BT·2R·4 (v/out blocks) + BT·128·4 (D⁻¹ block, lane-padded);
BT=512, r=256, R=9 → ~1 MB ≪ 16 MB VMEM, so the tile budget is set by
the factor block — r=256 leaves room for BT up to ~7k rows.  E⁻¹ rides the
same BlockSpec trick as gram_block's train payload (index map pinned to
block 0) so it is fetched once and stays VMEM-resident across the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BT = 512
# f32 MXU products at full precision (the default may round inputs to bf16).
_HIGHEST = jax.lax.Precision.HIGHEST


def _woodbury_kernel(b_ref, dinv_ref, einv_ref, v_ref, out_ref, s_ref):
    phase = pl.program_id(0)
    block = pl.program_id(1)

    @pl.when((phase == 0) & (block == 0))
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)

    @pl.when(phase == 0)
    def _reduce():
        w = dinv_ref[:] * v_ref[:]                      # [BT, R]
        s_ref[:] += jnp.dot(
            b_ref[:].T, w, preferred_element_type=jnp.float32,
            precision=_HIGHEST
        )                                               # [r, R]
        # Placeholder so every out block holds defined values; phase 1
        # revisits the same block index and overwrites with the result.
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when((phase == 1) & (block == 0))
    def _capacitance():
        s_ref[:] = jnp.dot(
            einv_ref[:], s_ref[:], preferred_element_type=jnp.float32,
            precision=_HIGHEST
        )

    @pl.when(phase == 1)
    def _expand():
        dinv = dinv_ref[:]                              # [BT, 1]
        bs = jnp.dot(
            b_ref[:], s_ref[:], preferred_element_type=jnp.float32,
            precision=_HIGHEST
        )                                               # [BT, R]
        out_ref[:] = dinv * (v_ref[:] - bs)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def woodbury_apply(
    b: jax.Array,
    dinv: jax.Array,
    einv: jax.Array,
    v: jax.Array,
    *,
    block_t: int = DEFAULT_BT,
    interpret: bool = False,
) -> jax.Array:
    """M⁻¹v = D⁻¹v − D⁻¹B E⁻¹ BᵀD⁻¹v.  See ref.py for semantics."""
    single = v.ndim == 1
    if single:
        v = v[:, None]
    t, r = b.shape
    rhs = v.shape[1]

    bt = min(block_t, max(8, t))
    pad = (-t) % bt
    if pad:
        # Zero dinv ⇒ padded rows contribute nothing and emit zero output.
        b = jnp.pad(b, ((0, pad), (0, 0)))
        dinv = jnp.pad(dinv, (0, pad))
        v = jnp.pad(v, ((0, pad), (0, 0)))
    tp = t + pad

    y = pl.pallas_call(
        _woodbury_kernel,
        grid=(2, tp // bt),
        in_specs=[
            pl.BlockSpec((bt, r), lambda p, i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda p, i: (i, 0)),
            pl.BlockSpec((r, r), lambda p, i: (0, 0)),
            pl.BlockSpec((bt, rhs), lambda p, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, rhs), lambda p, i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, rhs), jnp.float32),
        scratch_shapes=[pltpu.VMEM((r, rhs), jnp.float32)],
        interpret=interpret,
    )(
        b.astype(jnp.float32), dinv.astype(jnp.float32)[:, None],
        einv.astype(jnp.float32), v.astype(jnp.float32),
    )
    y = y[:t] if pad else y
    return y[:, 0] if single else y
