"""The least work of a kernel, whatever implements it, for roofline shares.

Each function counts what any correct implementation has to move, from the
configuration and the inputs' sizes alone: nothing of the layout that holds
the graph (ELL padding, CSR offsets) or of the program's chunking enters.
"""
from __future__ import annotations

import numpy as np

# One move of a walker reads the current node's degree, the chosen
# neighbour's id and its walk-matrix entry: 4 B each.
WALK_MOVE_BYTES = 4 + 4 + 4


def walk_rows(deg) -> int:
    """Start nodes whose walkers move at all: those of degree >= 1."""
    return int(np.count_nonzero(np.asarray(deg)))


def walk_least_bytes(rows: int, n_walkers: int, p_halt: float, l_max: int,
                     scheme: str = "iid") -> float:
    """Least bytes read to sample the walks of ``rows`` start nodes of
    degree >= 1: each walker makes one move per deposit after its first
    while it survives, Σ_{l=1..l_max} (1 − p_halt)^l expected moves; a
    ``grfspp`` walker never halts (its survival enters as a weight) and
    makes all ``l_max``.  Left out: CSR offsets, ELL padding, padded chunk
    rows and the deposits, which a fused draw need not write."""
    if scheme == "grfspp":
        moves = float(l_max)
    else:
        moves = sum((1.0 - p_halt) ** step for step in range(1, l_max + 1))
    return rows * n_walkers * moves * WALK_MOVE_BYTES
