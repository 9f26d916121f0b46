"""Ring of ``n_nodes`` nodes, each joined to its ``k`` nearest neighbours on
either side (``k`` defaults to 1)."""
import numpy as np


def edges(spec: dict):
    n, k = spec["n_nodes"], spec.get("k", 1)
    i = np.arange(n, dtype=np.int64)
    e = np.concatenate([np.stack([i, (i + o) % n], axis=1)
                        for o in range(1, k + 1)])
    return e, n
