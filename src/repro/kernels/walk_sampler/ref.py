"""jnp oracle for the walk-sampler kernel family.

Defines the semantics the Pallas kernel must reproduce and doubles as the
``"xla"`` backend path in kernels/dispatch.py.  The per-step math lives in
:func:`walk_block` — plain jnp on plain arrays — and the Pallas kernel calls
the *same* function on its VMEM-resident blocks, so kernel and oracle are
bit-identical by construction (the RNG is the counter hash in rng.py, keyed
on absolute start-node id — see DESIGN.md §3.6).

Semantics (paper Alg. 2, TPU-adapted as in core/walks.py): each of
``n_walkers`` walkers per start node takes ``l_max`` moves; at step l it
deposits (current node, load·alive, l) into ELL slot w·(l_max+1)+l; halting
is geometric with probability ``p_halt`` per step, and a halted walker keeps
moving with its deposits masked to zero (masking == rejection at the deposit
stage).  ``reweight`` applies the importance weight d/(1−p_halt) per move.

``scheme`` selects the variance-reduction strategy (DESIGN.md §3.9): the
halt uniforms come from :func:`rng.halt_uniform` (``iid`` / ``antithetic`` /
``qmc``), except ``grfspp``, which never draws them — the Bernoulli survival
indicator Π 1{u_j ≥ p_halt} is replaced by its expectation (1−p_halt)^l at
the deposit stage (a Rao-Blackwellised, GRFs++-style weighted deposit: same
mean by E[1{alive at l}] = (1−p_halt)^l, strictly lower variance).  Only
termination is scheme-dependent; directional choices stay iid, so the walk
*structure* law is shared and ``grfspp`` cols/lens are bit-identical to
``iid``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from . import rng


def walk_block(
    walk_table: jnp.ndarray,  # int32[N·D, 3]: neighbour, weight bits, its deg
    deg: jnp.ndarray,         # int32[N]
    nodes: jnp.ndarray,       # int32[M] absolute start-node ids
    seed: jnp.ndarray,        # uint32 scalar
    *,
    n_walkers: int,
    p_halt: float,
    l_max: int,
    reweight: bool = True,
    scheme: str = "iid",
):
    """Sample walks for a block of start nodes; returns (cols, loads, lens).

    Outputs are [M, K] with K = n_walkers·(l_max+1); loads are already
    divided by n_walkers (the estimator's 1/n).  Pure jnp — the Pallas
    kernel runs this exact function per VMEM block.

    A move is one gather of a ``walk_table`` row (``Graph.walk_table``):
    the next node, the edge's weight and the next node's degree, which the
    following move needs.  ``deg`` is read once, at the start nodes.
    """
    if scheme not in rng.SCHEMES:
        raise ValueError(f"unknown walk scheme {scheme!r}; valid: {rng.SCHEMES}")
    m = nodes.shape[0]
    max_deg = walk_table.shape[0] // deg.shape[0]

    node_u = nodes.astype(jnp.uint32)[:, None]              # [M, 1]
    walker_u = jnp.arange(n_walkers, dtype=jnp.uint32)[None, :]

    cur = jnp.broadcast_to(nodes[:, None], (m, n_walkers)).astype(jnp.int32)
    d = jnp.broadcast_to(jnp.take(deg, nodes)[:, None], (m, n_walkers))
    load = jnp.ones((m, n_walkers), jnp.float32)
    alive = jnp.ones((m, n_walkers), jnp.float32)

    cols_steps, loads_steps = [], []
    for step in range(l_max + 1):
        cols_steps.append(cur)
        if scheme == "grfspp":
            # Analytic termination: `alive` carries only the structural
            # (degree-0) mask; the survival probability enters as an exact
            # per-step weight instead of a sampled indicator.
            loads_steps.append(
                load * alive * jnp.float32((1.0 - p_halt) ** step)
            )
        else:
            loads_steps.append(load * alive)
        u_choice = rng.counter_uniform(seed, node_u, walker_u, 2 * step)
        # Guard isolated nodes: degree 0 ⇒ stay on padding with zero load.
        choice = jnp.minimum(
            (u_choice * d.astype(jnp.float32)).astype(jnp.int32),
            jnp.maximum(d - 1, 0),
        )
        flat = cur * max_deg + choice
        edge = jnp.take(walk_table, flat, axis=0)          # [M, W, 3]
        nxt = edge[..., 0]
        w = lax.bitcast_convert_type(edge[..., 1], jnp.float32)
        if reweight:
            load = load * d.astype(jnp.float32) / (1.0 - p_halt) * w
        else:
            load = load * w
        if scheme != "grfspp":
            u_halt = rng.halt_uniform(
                seed, node_u, walker_u, 2 * step + 1, scheme=scheme
            )
            alive = alive * (u_halt >= p_halt).astype(jnp.float32)
        alive = alive * (d > 0).astype(jnp.float32)
        cur, d = nxt, edge[..., 2]

    k = n_walkers * (l_max + 1)
    cols = jnp.stack(cols_steps, axis=-1).reshape(m, k).astype(jnp.int32)
    loads = (jnp.stack(loads_steps, axis=-1) / n_walkers).reshape(m, k)
    lens = jnp.broadcast_to(
        jnp.arange(l_max + 1, dtype=jnp.int32), (m, n_walkers, l_max + 1)
    ).reshape(m, k)
    return cols, loads, lens


# The oracle is the whole problem as one block.
walk_sample_ref = walk_block
