"""Pure-jnp oracles for the ELL sparse-product family.

These define the semantics the Pallas kernels must reproduce (parity tests
in tests/test_kernels_ell.py) and double as the ``"xla"`` backend paths in
kernels/dispatch.py — native gather / scatter-add, fully differentiable.
"""
from __future__ import annotations

import jax.numpy as jnp


def ell_spmv_ref(vals: jnp.ndarray, cols: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """y[m] = Σ_k vals[m,k] · u[cols[m,k]].

    Args:
      vals: f32[M, K] ELL values (0 for padding slots).
      cols: i32[M, K] ELL column indices.
      u: f32[N] or f32[N, R] dense operand.
    Returns: f32[M] or f32[M, R].
    """
    gathered = u[cols]  # [M, K] or [M, K, R]
    # Multiply-and-reduce, not an einsum: a length-K contraction per row is
    # no matmul, and XLA:TPU may run a dot_general at bf16 input precision.
    if u.ndim == 1:
        return jnp.sum(vals * gathered, axis=1)
    return jnp.sum(vals[:, :, None] * gathered, axis=1)


def ell_spmv_t_ref(
    vals: jnp.ndarray, cols: jnp.ndarray, v: jnp.ndarray, n_nodes: int
) -> jnp.ndarray:
    """u[j] = Σ_{m,k : cols[m,k]=j} vals[m,k] · v[m]  (u = Φᵀ v).

    Args:
      vals: f32[M, K] ELL values.
      cols: i32[M, K] ELL column indices.
      v: f32[M] or f32[M, R] dense operand.
      n_nodes: output length N.
    Returns: f32[N] or f32[N, R].
    """
    flat_cols = cols.reshape(-1)
    if v.ndim == 1:
        contrib = (vals * v[:, None]).reshape(-1)
        return jnp.zeros((n_nodes,), contrib.dtype).at[flat_cols].add(contrib)
    contrib = (vals[..., None] * v[:, None, :]).reshape(-1, v.shape[-1])
    return jnp.zeros((n_nodes, v.shape[-1]), contrib.dtype).at[flat_cols].add(contrib)


def khat_matvec_ref(
    vals_rows: jnp.ndarray,
    cols_rows: jnp.ndarray,
    vals_cols: jnp.ndarray,
    cols_cols: jnp.ndarray,
    v: jnp.ndarray,
    n_nodes: int,
) -> jnp.ndarray:
    """y = Φ_rows (Φ_colsᵀ v) — the (cross-)K̂ matvec, unfused."""
    return ell_spmv_ref(
        vals_rows, cols_rows, ell_spmv_t_ref(vals_cols, cols_cols, v, n_nodes)
    )
