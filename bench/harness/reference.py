"""Plain float64 reference of the GRF-GP semantics, on the host.

Independent of the code under test: it imports nothing of ``repro`` and
rebuilds what it needs from the configuration and the seed.

  * adjacency: edge-sized (CSR), built from the edge list that
    ``bench/harness/graphs/<generator>.py`` returns; each node's
    neighbours in ascending id order, walk-matrix entries 1/sqrt(d_i d_j)
    (the symmetric normalised adjacency);
  * walks (paper Alg. 2 with fixed-length masked stepping): ``n_walkers``
    walkers per start node take ``l_max`` moves; at step l a walker
    deposits (node, load·alive, l); the move picks neighbour
    floor(u·d) with u from the murmur3-finaliser counter hash keyed on
    (seed, start node, walker, 2l); the load gains d/(1−p_halt)·w; the
    walker halts for good when the uniform keyed on 2l+1 is below p_halt;
    at a degree-0 node it stays and carries zero load from then on;
    loads are divided by n_walkers at the end;
  * Φ rows as sparse matrices with values loads·f[lens], K̂ = Φ_A Φ_Bᵀ;
  * GP algebra by dense float64 Cholesky.

``round_bf16`` is the control's precision: operands rounded to bfloat16.
"""
from __future__ import annotations

import numpy as np
import scipy.linalg
import scipy.sparse

from harness import spec

_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_M3 = np.uint32(0x27D4EB2F)


def _u32(x) -> np.ndarray:
    return (np.asarray(x, np.int64) & 0xFFFFFFFF).astype(np.uint32)


def fmix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * _M1
    h = h ^ (h >> np.uint32(13))
    h = h * _M2
    return h ^ (h >> np.uint32(16))


def counter_uniform(seed, node, walker, ctr) -> np.ndarray:
    """float32 uniform in [0, 1) from the top 24 bits of the hash chain."""
    with np.errstate(over="ignore"):
        h = _u32(seed) ^ _GOLDEN
        h = fmix32(h ^ (_u32(node) * _M1))
        h = fmix32(h ^ (_u32(walker) * _M2))
        h = fmix32(h ^ (_u32(ctr) * _M3))
    return (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


class Adjacency:
    """Edge-sized (CSR) adjacency: node i's neighbours are
    ``nbr[offsets[i]:offsets[i+1]]`` in ascending id, with walk-matrix
    entries ``w`` at the same places and degrees ``deg``."""

    def __init__(self, offsets: np.ndarray, nbr: np.ndarray):
        self.offsets, self.nbr = offsets, nbr
        self.deg = np.diff(offsets)
        src = np.repeat(np.arange(len(self.deg)), self.deg)
        d = self.deg.astype(np.float64)
        self.w = 1.0 / np.sqrt(d[src] * d[nbr])

    @property
    def n_nodes(self) -> int:
        return len(self.deg)

    @classmethod
    def from_edges(cls, edges, n_nodes: int) -> "Adjacency":
        """From an undirected edge list [E, 2]: symmetrised, each directed
        entry kept once."""
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        key = np.unique(np.concatenate([e[:, 0] * n_nodes + e[:, 1],
                                        e[:, 1] * n_nodes + e[:, 0]]))
        src, nbr = np.divmod(key, n_nodes)
        offsets = np.zeros(n_nodes + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n_nodes), out=offsets[1:])
        return cls(offsets, nbr)

    @classmethod
    def from_spec(cls, graph: dict) -> "Adjacency":
        """From a configuration's ``graph``, by the ``edges(spec)`` of
        ``bench/harness/graphs/<generator>.py``."""
        edges, n_nodes = spec.graph_edges(graph["generator"])(graph)
        return cls.from_edges(edges, n_nodes)


def walks(adj: Adjacency, nodes, seed: int, n_walkers: int, p_halt: float,
          l_max: int):
    """(cols, loads, lens), each [M, n_walkers·(l_max+1)], loads float64."""
    nodes = np.asarray(nodes, np.int64)
    m = nodes.shape[0]
    node_u = nodes[:, None]
    walker = np.arange(n_walkers)[None, :]
    cur = np.broadcast_to(node_u, (m, n_walkers)).copy()
    load = np.ones((m, n_walkers))
    alive = np.ones((m, n_walkers))
    p32 = np.float32(p_halt)
    cols, loads = [], []
    for step in range(l_max + 1):
        cols.append(cur)
        loads.append(load * alive)
        u = counter_uniform(seed, node_u, walker, 2 * step)
        d = adj.deg[cur]
        choice = np.minimum((u * d.astype(np.float32)).astype(np.int64),
                            np.maximum(d - 1, 0))
        # A degree-0 node has no entry to read (the last one's offset is E).
        live = d > 0
        at = (adj.offsets[cur] + choice)[live]
        nxt = cur.copy()
        nxt[live] = adj.nbr[at]
        w = np.zeros(cur.shape)
        w[live] = adj.w[at]
        load = load * d / (1.0 - p_halt) * w
        u_h = counter_uniform(seed, node_u, walker, 2 * step + 1)
        alive = alive * (u_h >= p32) * live
        cur = nxt
    k = n_walkers * (l_max + 1)
    cols = np.stack(cols, axis=-1).reshape(m, k)
    loads = (np.stack(loads, axis=-1) / n_walkers).reshape(m, k)
    lens = np.broadcast_to(np.arange(l_max + 1), (m, n_walkers, l_max + 1))
    return cols, loads, lens.reshape(m, k)


def phi(rows, f, n_nodes: int, dtype=np.float64):
    """Sparse Φ [M, N] with values loads·f[lens] (duplicates summed)."""
    cols, loads, lens = rows
    m, k = cols.shape
    vals = loads * np.asarray(f, np.float64)[lens]
    if dtype is not np.float64:
        vals = round_bf16(vals)
    return scipy.sparse.csr_matrix(
        (vals.ravel(), (np.repeat(np.arange(m), k), cols.ravel())),
        shape=(m, n_nodes))


def gram(phi_a, phi_b) -> np.ndarray:
    """Dense K̂_AB = Φ_A Φ_Bᵀ in float64."""
    return np.asarray((phi_a @ phi_b.T).todense(), np.float64)


def round_bf16(x) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def chol_solve(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    c = scipy.linalg.cho_factor(a, lower=True)
    return scipy.linalg.cho_solve(c, b)


def diffusion_f(log_beta: float, log_sigma_f: float, l_max: int):
    """f_l = sqrt(σ_f) e^{-β/2} (β/2)^l / l!  and  ∂f/∂(log β, log σ_f)."""
    beta, sigma_f = np.exp(log_beta), np.exp(log_sigma_f)
    ls = np.arange(l_max + 1, dtype=np.float64)
    fact = np.cumprod(np.maximum(ls, 1.0))
    f = np.sqrt(sigma_f) * np.exp(-beta / 2) * (beta / 2) ** ls / fact
    df_dlogbeta = f * (ls - beta / 2)
    df_dlogsf = 0.5 * f
    return f, df_dlogbeta, df_dlogsf


def theta(params) -> np.ndarray:
    """(log β, log σ_f, log σ_n) read from a diffusion kernel's parameters."""
    return np.array([float(params["mod"]["log_beta"]),
                     float(params["mod"]["log_sigma_f"]),
                     float(params["log_sigma_n"])])


def adam_fit(rows, n_nodes: int, y, z, mask, theta0, lr: float, steps: int,
             l_max: int) -> dict:
    """Adam on the MLL surrogate (paper §3.2) with exact Cholesky solves.

    θ = (log β, log σ_f, log σ_n) of the diffusion kernel; H = K̂ + D with
    D = σ_n² on live rows (``mask`` 1) and 1e6 on padding, y and the
    probes z zeroed on padding.  The surrogate is −½ v_yᵀH v_y +
    ½ mean_j v_zjᵀH z_j with v = H⁻¹[y, z] held fixed; its gradient is the
    LML's.  Returns each step's loss and the size of its two terms, the
    first gradient and the final θ."""
    cols, loads, lens = rows
    mask = np.asarray(mask, np.float64)
    y = np.asarray(y, np.float64) * mask
    z = np.asarray(z, np.float64) * mask[:, None]
    b = np.concatenate([y[:, None], z], axis=1)
    ones = np.ones(l_max + 1)
    per_len = [phi((cols, np.where(lens == l, loads, 0.0), lens), ones,
                   n_nodes) for l in range(l_max + 1)]
    theta = np.array(theta0, np.float64)
    mu, nu = np.zeros(3), np.zeros(3)
    b1, b2, eps = 0.9, 0.999, 1e-8
    losses, scales, grad0 = [], [], None
    for step in range(1, steps + 1):
        f, df_b, df_s = diffusion_f(theta[0], theta[1], l_max)
        s2 = np.exp(2 * theta[2])
        p = phi((cols, loads, lens), f, n_nodes)
        h = gram(p, p) + np.diag(np.where(mask > 0, s2, 1e6))
        v = chol_solve(h, b)
        v_y, v_z = v[:, 0], v[:, 1:]
        fit_term = -0.5 * v_y @ h @ v_y
        trace_term = 0.5 * np.mean(np.sum(v_z * (h @ z), axis=0))
        losses.append(fit_term + trace_term)
        scales.append(abs(fit_term) + abs(trace_term))
        pt_vy, pt_v, pt_z = p.T @ v_y, p.T @ v_z, p.T @ z
        d_f = np.empty(l_max + 1)
        for l, p_l in enumerate(per_len):
            lt_vy, lt_v, lt_z = p_l.T @ v_y, p_l.T @ v_z, p_l.T @ z
            d_f[l] = (-(lt_vy @ pt_vy)
                      + 0.5 * np.mean(np.sum(lt_v * pt_z + pt_v * lt_z,
                                             axis=0)))
        d_s = (-s2 * (mask * v_y) @ v_y
               + s2 * np.mean(np.sum(v_z * z, axis=0)))
        grad = np.array([d_f @ df_b, d_f @ df_s, d_s])
        if grad0 is None:
            grad0 = grad
        mu = b1 * mu + (1 - b1) * grad
        nu = b2 * nu + (1 - b2) * grad * grad
        mhat, vhat = mu / (1 - b1 ** step), nu / (1 - b2 ** step)
        theta = theta - lr * mhat / (np.sqrt(vhat) + eps)
    return dict(losses=losses, scales=scales, params=theta, grad0=grad0)


def leaf_gap(theta0, got, want, grad0) -> float:
    """The worst leaf's gap between the program's change of θ and the
    reference's, against that leaf's reference change or the median leaf's,
    whichever is larger.  Leaves whose first reference gradient is under a
    thousandth of the median leaf's move by round-off alone: left out."""
    d_prog = np.abs(np.asarray(got, np.float64) - theta0)
    d_ref = np.abs(np.asarray(want, np.float64) - theta0)
    g0 = np.abs(grad0)
    moved = g0 >= 1e-3 * np.median(g0)
    scale = np.maximum(d_ref, np.median(d_ref[moved]))
    return float(np.max((np.abs(d_prog - d_ref) / scale)[moved]))
