"""Pallas TPU kernel: blockwise GRF walk sampling (DESIGN.md §3.6).

Grid: (M // BM,) over start-node blocks.  Per grid step:

  * the walk substrate (the packed per-slot ``walk_table`` [N·D, 3] of
    neighbour, weight bits and neighbour degree, and ``deg`` [N]) is pinned
    to block 0 so it stays *VMEM-resident across the whole grid* — every
    per-move row gather (``jnp.take`` over the table) runs at on-chip
    latency, never touching HBM;
  * randomness is the counter hash from rng.py addressed by
    (seed, start node, walker, step) — no RNG state crosses grid steps, so
    blocks are order-independent and chunked sampling is bit-identical to
    monolithic sampling;
  * the l_max+1 deposit steps are unrolled in-register and written to the
    (cols, loads, lens) outputs *directly in ELL layout* [BM, K],
    K = n_walkers·(l_max+1) — the trace never exists in any other format.

Per-step VMEM: N·D·12 + N·4 (resident substrate) + 3·BM·K·4 (outputs)
bytes.  The substrate residency bounds the compiled path to
N·(3·max_deg+1)·4 ≲ VMEM; beyond that route through the ``"xla"`` backend
(kernels/dispatch.py) or shrink max_deg — the node chunking above the
kernel, in core/walks.py, is orthogonal and works on every backend.

The step math itself is ref.walk_block — the kernel and the jnp oracle
evaluate the same function, so parity is exact, not statistical.

**Not used on TPU.**  Mosaic refuses the neighbour ``jnp.take`` ("Only 2D
gather is supported"), and the resident substrate of ``ring(10⁶, k=3)`` is
76 MB against 16 MB of VMEM.  kernels/dispatch.py runs walk sampling
through its ``"xla"`` implementation on TPU; the kernel is exercised through
the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import walk_block

DEFAULT_BM = 256


def _walk_kernel(
    nodes_ref, seed_ref, table_ref, deg_ref,
    cols_ref, loads_ref, lens_ref,
    *, n_walkers, p_halt, l_max, reweight, scheme,
):
    cols, loads, lens = walk_block(
        table_ref[:], deg_ref[:], nodes_ref[:], seed_ref[0],
        n_walkers=n_walkers, p_halt=p_halt, l_max=l_max, reweight=reweight,
        scheme=scheme,
    )
    cols_ref[:] = cols
    loads_ref[:] = loads
    lens_ref[:] = lens


@functools.partial(
    jax.jit,
    static_argnames=("n_walkers", "p_halt", "l_max", "reweight", "scheme",
                     "block_m", "interpret"),
)
def walk_sample(
    walk_table: jax.Array,
    deg: jax.Array,
    nodes: jax.Array,
    seed: jax.Array,
    *,
    n_walkers: int,
    p_halt: float,
    l_max: int,
    reweight: bool = True,
    scheme: str = "iid",
    block_m: int = DEFAULT_BM,
    interpret: bool = False,
):
    """Sample walks for ``nodes``; returns (cols, loads, lens) [M, K]."""
    m = nodes.shape[0]
    n = deg.shape[0]
    k = n_walkers * (l_max + 1)

    bm = min(block_m, max(8, m))
    pad_m = (-m) % bm
    if pad_m:
        # Padding rows start at node 0 — valid walks, sliced off below.
        nodes = jnp.pad(nodes, (0, pad_m))
    mp = m + pad_m

    kernel = functools.partial(
        _walk_kernel,
        n_walkers=n_walkers, p_halt=p_halt, l_max=l_max, reweight=reweight,
        scheme=scheme,
    )
    out_spec = pl.BlockSpec((bm, k), lambda i: (i, 0))
    cols, loads, lens = pl.pallas_call(
        kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec(walk_table.shape, lambda i: (0, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((mp, k), jnp.int32),
            jax.ShapeDtypeStruct((mp, k), jnp.float32),
            jax.ShapeDtypeStruct((mp, k), jnp.int32),
        ),
        interpret=interpret,
    )(
        nodes.astype(jnp.int32),
        jnp.asarray(seed, jnp.uint32).reshape(1),
        walk_table, deg,
    )
    if pad_m:
        return cols[:m], loads[:m], lens[:m]
    return cols, loads, lens
