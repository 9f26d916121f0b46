"""The one generator of open-loop serving traffic, driven by a data file.

A traffic file's ``stream`` block says, with numbers only:

  * ``rate_per_s``   — mean arrivals per second (Poisson); ``null`` in a
                       mix whose rate a sweep on the chip has not set yet;
  * ``nodes``        — request sizes: ``{"mean", "min", "max"}`` of a
                       geometric law truncated to [min, max];
  * ``zipf_theta``   — skew of the query nodes (YCSB's zipfian constant),
                       ranks mapped to node ids by a permutation drawn from
                       the seed;
  * ``write_share``  — share of operations that append one reading (each
                       paired with a forget of the oldest observation);
  * ``seconds_after``— how long arrivals continue past the window's close.

Every seed gets the same multiset of gaps, sizes and write positions, in
another order: those are drawn once from a fixed stream and shuffled by
the seed, so seeds change which nodes are asked, not how much work a run
holds.  Nodes and written values come from the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_FIXED = 0x5EED


@dataclasses.dataclass
class Stream:
    due: np.ndarray              # float64[n] seconds after the window opens
    is_write: np.ndarray         # bool[n]
    sizes: np.ndarray            # int64[n] query nodes per request (0: write)
    nodes: list                  # per op: int32 node ids (queries) or [node]

    def __len__(self) -> int:
        return len(self.due)


def zipf_ranks(rng: np.random.Generator, n_items: int, theta: float,
               size: int) -> np.ndarray:
    """Ranks in [0, n_items) with P(rank r) ∝ (r + 1)^-theta."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -theta
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      n_items - 1)


def geometric_sizes(rng: np.random.Generator, mean: float, lo: int, hi: int,
                    size: int) -> np.ndarray:
    """Geometric sizes on {lo, lo+1, ...} with the given mean, cut at hi."""
    p = 1.0 / (mean - lo + 1.0)
    return np.minimum(lo - 1 + rng.geometric(p, size), hi)


def generate(spec: dict, n_nodes: int, seed: int, seconds: float) -> Stream:
    """The operations due in ``seconds + spec['seconds_after']``."""
    if spec["rate_per_s"] is None:
        raise ValueError("the traffic mix has no rate yet: it is set from a "
                         "sweep on the chip")
    horizon = seconds + spec["seconds_after"]
    n_ops = int(np.ceil(spec["rate_per_s"] * horizon))
    fixed = np.random.default_rng(_FIXED)
    gaps = fixed.exponential(1.0 / spec["rate_per_s"], n_ops)
    n_writes = int(round(spec["write_share"] * n_ops))
    is_write = np.zeros(n_ops, bool)
    is_write[:n_writes] = True
    sz = spec["nodes"]
    query_sizes = geometric_sizes(fixed, sz["mean"], sz["min"], sz["max"],
                                  n_ops - n_writes)

    rng = np.random.default_rng([seed, 1])
    gaps = rng.permutation(gaps)
    is_write = rng.permutation(is_write)
    sizes = np.zeros(n_ops, np.int64)
    sizes[~is_write] = rng.permutation(query_sizes)
    perm = rng.permutation(n_nodes)
    ranks = zipf_ranks(rng, n_nodes, spec["zipf_theta"], int(sizes.sum()))
    query_nodes = perm[ranks].astype(np.int32)
    write_nodes = rng.integers(0, n_nodes, n_ops).astype(np.int32)
    nodes, at = [], 0
    for i in range(n_ops):
        if is_write[i]:
            nodes.append(write_nodes[i:i + 1])
        else:
            nodes.append(query_nodes[at:at + sizes[i]])
            at += sizes[i]
    return Stream(due=np.cumsum(gaps), is_write=is_write, sizes=sizes,
                  nodes=nodes)
