"""Open-loop online GP serving through ``serving.GPFleetLoop``.

Set-up ingests the initial observations (one O(m³) factorisation, no
hyperparameter fit) and runs one query, one append and one forget through
the fleet, which compiles every program the window uses.  The window
submits each operation of the seeded stream (``harness/traffic.py``) when
it falls due, whether or not earlier ones have finished, and steps the
fleet whenever it holds work.  A request's latency runs from when it was
due to the fleet step that answered its last node.  Arrivals go on past
the window's close until every request due in the window is answered.

Each append is paired with a forget of the oldest observation, so the
live count holds.  The fleet applies operations in submission order, so a
query sees exactly the mutations submitted before it; the check rebuilds
that observation set for a sample of requests, drawn from the seed with
the longest among them, and recomputes their mean and variance with the
float64 reference.
"""
from __future__ import annotations

import time

import numpy as np
import scipy.linalg

from harness import data, reference
from harness import traffic as traffic_gen
from harness.trace import annotate


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.counts: dict = {}
        self.latencies: list = []

    def setup(self) -> None:
        import jax
        from repro import serving

        cfg, sv = self.config, self.config["serving"]
        graph = data.build_graph(cfg["graph"])
        walk = data.walk_config(cfg["walks"])
        mod = data.modulation(cfg["modulation"], walk.l_max)
        self.n = graph.n_nodes
        key = data.prng_key(self.seed)
        self.walk_key = jax.random.fold_in(
            data.prng_key(cfg.get("walk_seed", self.seed)), 1)
        f = mod(mod.init(jax.random.fold_in(key, 2)))
        self.f = np.asarray(f, np.float64)
        self.s2 = sv["sigma_n2"]
        self.truth = data.signal(cfg["targets"], self.seed, graph)
        self.noise_std = cfg["targets"]["noise_std"]
        self.rng = data.np_rng(self.seed)
        nodes0 = self.rng.choice(self.n, sv["live"], replace=False)
        y0 = self._readings(nodes0)
        self.initial = list(zip(nodes0.tolist(), y0.tolist()))
        state = serving.init_state(graph, self.walk_key, f, self.s2,
                                   sv["capacity"], walk)
        state = serving.ingest(state, nodes0, y0)
        data.log("initial observations ingested; warm-up")
        self.serving = serving
        self.fleet = serving.GPFleetLoop(
            state, batch=sv["batch"], key=jax.random.fold_in(key, 3),
            max_pending=None)
        # Warm-up: a query, an append and a forget compile the wave, the
        # one-row append and the one-slot forget.
        self.mutations = []
        self.fleet.submit(serving.GPRequest(nodes=nodes0[:1]))
        self._write(int(self.rng.integers(self.n)))
        self.fleet.submit(serving.GPRequest(nodes=nodes0[1:2]))
        self.fleet.drain()
        data.log("warm-up done")

    def _readings(self, nodes) -> np.ndarray:
        return (self.truth[nodes] + self.noise_std
                * self.rng.standard_normal(len(nodes))).astype(np.float32)

    def _write(self, node: int) -> None:
        y = float(self._readings(np.array([node]))[0])
        self.fleet.submit_observe([node], [y])
        self.fleet.submit_forget(0)
        self.mutations.append((node, y))

    def _busy(self) -> bool:
        fl = self.fleet
        return bool(fl.pending) or fl._inflight is not None or any(
            s is not None for s in fl.slots)

    def window(self, seconds: float) -> None:
        spec = self.traffic["stream"]
        st = traffic_gen.generate(spec, self.n, self.seed, seconds)
        fleet, batch = self.fleet, self.config["serving"]["batch"]
        scan = 2 * batch + 2            # requests that can be in flight
        n_ops, i = len(st), 0
        self.queries = []               # (op index, request, mutations before)
        outstanding = []                # [(op index, request)] in FIFO order
        latency = {}
        late, done_in_window, open_in_window, waves = [], 0, 0, 0
        deadline = seconds + spec["drain_s"]
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < n_ops and st.due[i] <= now:
                late.append(now - st.due[i])
                if st.is_write[i]:
                    self._write(int(st.nodes[i][0]))
                else:
                    req = self.serving.GPRequest(nodes=st.nodes[i])
                    fleet.submit(req)
                    outstanding.append((i, req))
                    if st.due[i] < seconds:
                        self.queries.append((i, req, len(self.mutations)))
                        open_in_window += 1
                i += 1
            if self._busy():
                with annotate("bench.fleet_step"):
                    fleet.step()
                waves += fleet._inflight is not None
                t_done = time.perf_counter() - t0
                head, keep = outstanding[:scan], []
                for idx, req in head:
                    if not req.done:
                        keep.append((idx, req))
                        continue
                    if st.due[idx] < seconds:
                        latency[idx] = t_done - st.due[idx]
                        open_in_window -= 1
                    if t_done < seconds:
                        done_in_window += 1
                outstanding[:scan] = keep
            elif i < n_ops:
                with annotate("bench.wait_arrival"):
                    time.sleep(max(0.0, st.due[i] - (time.perf_counter() - t0)))
            if now >= seconds and open_in_window == 0:
                break
            if now >= deadline or (i >= n_ops and not self._busy()):
                break
        self.window_s = float(seconds)
        due = [(idx, req) for idx, req, _ in self.queries]
        self.latencies = [latency.get(idx, deadline - st.due[idx])
                          for idx, _ in due]
        failed = sum(1 for idx, _ in due if idx not in latency)
        late_ms = np.asarray(late) * 1e3
        self.counts = {
            "requests": len(due),
            "completed_in_window": done_in_window,
            "nodes": int(sum(len(r.nodes) for _, r in due)),
            "writes": int(st.is_write[st.due < seconds].sum()),
            "waves": waves,
            "generator_late_p95_ms": float(np.percentile(late_ms, 95)),
            "generator_late_max_ms": float(late_ms.max()),
        }
        self.attempted, self.failed = len(due), failed
        data.log(f"window: {self.counts}")

    def release(self) -> None:
        self.fleet = None

    # -- correctness -------------------------------------------------------
    def sample(self) -> list:
        """The checked requests: some drawn from the seed, and the longest."""
        rng = data.np_rng(self.seed, 4)
        k = min(self.traffic["check"]["requests"], len(self.queries))
        picks = set(rng.choice(len(self.queries), k, replace=False).tolist())
        picks.add(int(np.argmax([len(r.nodes) for _, r, _ in self.queries])))
        return [self.queries[j] for j in sorted(picks)]

    def check(self) -> list:
        mean_gap, var_gap = self.gaps(np.float64)
        lim = self.traffic["check"]["limits"]
        return [("mean_gap", mean_gap, float(lim["mean_gap"])),
                ("var_gap", var_gap, float(lim["var_gap"]))]

    def gaps(self, dtype):
        """Widest |Δmean| and |Δvar| over the checked requests' nodes, as a
        share of the largest prior variance there: of what the window
        served (``float64``), or of the reference computed from operands
        rounded to ``dtype`` (the control)."""
        import jax

        checked = self.sample()
        wk, live = self.config["walks"], self.config["serving"]["live"]
        adj = reference.Adjacency.from_spec(self.config["graph"])
        seed_u32 = int(np.asarray(jax.random.bits(self.walk_key, (),
                                                  np.uint32)))
        history = self.initial + self.mutations
        union = np.unique(np.concatenate(
            [np.array([n for n, _ in history]),
             np.concatenate([r.nodes for _, r, _ in checked])]))
        rows = reference.walks(adj, union, seed_u32, wk["n_walkers"],
                               wk["p_halt"], wk["l_max"])
        phi_ref = reference.phi(rows, self.f, adj.n_nodes)
        phi_use = reference.phi(rows, self.f, adj.n_nodes, dtype)
        where = {int(v): j for j, v in enumerate(union)}
        worst_mean = worst_var = 0.0
        for _, req, v in checked:
            obs = history[v:v + live]
            o_idx = [where[n] for n, _ in obs]
            q_idx = [where[int(n)] for n in req.nodes]
            y = np.array([yv for _, yv in obs], np.float64)
            want = self._moments(phi_ref, o_idx, q_idx, y)
            got = ((req.mean, req.var) if dtype is np.float64
                   else self._moments(phi_use, o_idx, q_idx, y))
            scale = want[2]
            worst_mean = max(worst_mean, float(
                np.abs(np.asarray(got[0], np.float64) - want[0]).max() / scale))
            worst_var = max(worst_var, float(
                np.abs(np.asarray(got[1], np.float64) - want[1]).max() / scale))
        return worst_mean, worst_var

    def _moments(self, phi, o_idx, q_idx, y):
        """Posterior mean, variance and the largest prior variance at the
        query rows, by a dense float64 Cholesky of K̂_oo + σ²I."""
        po, pq = phi[o_idx], phi[q_idx]
        c = np.linalg.cholesky(reference.gram(po, po)
                               + self.s2 * np.eye(len(o_idx)))
        k_oq = reference.gram(po, pq)
        alpha = scipy.linalg.cho_solve((c, True), y)
        w = scipy.linalg.solve_triangular(c, k_oq, lower=True)
        k_qq = np.diag(reference.gram(pq, pq))
        return k_oq.T @ alpha, k_qq - np.sum(w * w, axis=0), k_qq.max()
