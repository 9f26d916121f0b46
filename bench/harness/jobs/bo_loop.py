"""Closed-loop graph Thompson sampling (paper Alg. 3) on the chunked path.

Set-up draws the initial design from the seed and runs round 0 once, which
compiles every program a round uses (walks for the observation buffer, the
refit chunk, the pathwise draw).  The window restarts from the initial
design, so it starts at a refit round (one every ``refit_every`` rounds),
and runs rounds through ``bo.thompson.thompson_sampling`` until
``--seconds`` have passed, closing at the end of a whole round: the refits
that fall in the window are amortised over its rounds.  When the buffer
fills, the loop restarts from the initial design.

The draws the window produced are kept; after the window a sample of them,
drawn from the seed, is recomputed by the float64 reference at a sample of
nodes (the observed nodes, each round's pick and uniform nodes).  Every
refit of the window is kept too, and the reference follows its Adam steps
on the masked observation buffer with exact solves.
"""
from __future__ import annotations

import copy
import time

import numpy as np

from harness import data, reference, rooflines
from harness.trace import annotate


class _WindowClosed(Exception):
    """Raised after the round in which the window's time ran out."""


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 matvec_dtype: str = "float32"):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.matvec_dtype = matvec_dtype
        self.counts: dict = {}
        self.latencies: list = []
        self.draws: list = []
        self.refits: list = []

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro import solvers
        from repro.bo import thompson
        from repro.gp import mll, posterior

        cfg, bo = self.config, self.config["bo"]
        self.graph = data.build_graph(cfg["graph"])
        self.walk = data.walk_config(cfg["walks"])
        self.mod = data.modulation(cfg["modulation"], self.walk.l_max)
        self.walk_rows = rooflines.walk_rows(self.graph.deg)
        truth = data.signal(cfg["objective"], self.seed, self.graph)
        noise_rng = data.np_rng(self.seed, 1)
        noise_std = cfg["objective"]["noise_std"]

        def objective(idx):
            with annotate("bench.objective"):
                idx = np.asarray(idx)
                return truth[idx] + noise_std * noise_rng.standard_normal(
                    len(idx))

        self.key = data.prng_key(self.seed)
        self.kw = dict(
            n_init=bo["n_init"], noise_std=noise_std,
            refit_every=bo["refit_every"], refit_steps=bo["refit_steps"],
            f_max=float(truth.max()), graph=self.graph, walk=self.walk,
            batch_size=bo["batch_size"],
            fit_strategy=solvers.MLL_DEFAULT.with_(
                matvec_dtype=self.matvec_dtype),
            sample_strategy=solvers.POSTERIOR_DEFAULT.with_(
                matvec_dtype=self.matvec_dtype),
        )
        self.thompson = thompson
        first = thompson.thompson_sampling(
            None, self.mod, objective, self.key, n_steps=0, **self.kw)
        cap = bo["capacity"]
        self.rounds_per_fill = (cap - bo["n_init"]) // bo["batch_size"]
        self.init = thompson.BOState(
            x_buf=np.pad(first.x_buf, (0, cap - len(first.x_buf))),
            y_buf=np.pad(first.y_buf, (0, cap - len(first.y_buf))),
            count=first.count, params=first.params, regret=[])
        self.objective = objective

        # Every draw the loop makes goes through this wrapper, which keeps
        # the draw and its inputs; the compiled program is unchanged.
        original = posterior.pathwise_samples_chunked
        self._original_draw = original

        def recorded_draw(graph, train_nodes, f, s2, y, key, walk_key, cfg_w,
                          **kw):
            with annotate("bench.draw"):
                out = original(graph, train_nodes, f, s2, y, key, walk_key,
                               cfg_w, **kw)
            if self.recording:
                # The loop builds x and the mask from host buffers that it
                # mutates in place next round, and the CPU backend may alias
                # such buffers: keep copies.
                mask = kw.get("obs_mask")
                self.draws.append(dict(
                    x=np.array(train_nodes), f=f, s2=s2, y=np.array(y),
                    key=key, walk_key=walk_key,
                    mask=None if mask is None else np.array(mask), out=out))
                if self.refits and "x" not in self.refits[-1]:
                    # A refit round's draw observes the refit's nodes.
                    self.refits[-1].update(x=np.array(train_nodes),
                                           walk_key=walk_key)
            return out

        # Every refit goes through this wrapper, which keeps its inputs and
        # the hyperparameters it returns.
        original_fit = mll.fit_hyperparams
        self._original_fit = original_fit

        def recorded_fit(trace_x, mod, y, n_nodes, key, **kw):
            with annotate("bench.refit"):
                res = original_fit(trace_x, mod, y, n_nodes, key, **kw)
            if self.recording:
                self.refits.append(dict(
                    y=np.array(y), key=key, mask=np.array(kw["obs_mask"]),
                    init=reference.theta(kw["init_params"]),
                    params=reference.theta(res.params),
                    steps=kw["steps"], lr=kw["lr"],
                    n_probes=kw.get("n_probes", 8)))
            return res

        posterior.pathwise_samples_chunked = recorded_draw
        mll.fit_hyperparams = recorded_fit
        self.recording = False
        data.log("initial design drawn; warm-up round")
        # Warm-up: round 0 (refit + draw) compiles every program.
        self._rounds(copy.deepcopy(self.init), 1)
        jax.block_until_ready(self.init.params)
        data.log("warm-up done")

    def _rounds(self, state, n: int, deadline: float = float("inf")):
        """Up to ``n`` rounds from ``state``, which the loop advances in
        place; they stop after the round that ends past ``deadline``."""
        stamps = []

        def round_done(_):
            stamps.append(time.perf_counter())
            if stamps[-1] >= deadline:
                raise _WindowClosed

        try:
            with annotate("bench.bo_rounds"):
                self.thompson.thompson_sampling(
                    None, self.mod, self.objective, self.key,
                    n_steps=state.iteration + n, state=state,
                    checkpoint_cb=round_done, **self.kw)
        except _WindowClosed:
            pass
        return state, stamps

    # -- the window --------------------------------------------------------
    def window(self, seconds: float) -> None:
        state = copy.deepcopy(self.init)
        rounds = 0
        self.recording = True
        t0 = time.perf_counter()
        last = t0
        while True:
            state, stamps = self._rounds(
                state, self.rounds_per_fill - state.iteration, t0 + seconds)
            for s in stamps:
                self.latencies.append(s - last)
                last = s
            rounds += len(stamps)
            if state.iteration >= self.rounds_per_fill:
                state = copy.deepcopy(self.init)
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.recording = False
        self.counts = {"rounds": rounds, "draws": len(self.draws),
                       "refits": len(self.refits),
                       "walk_rows": self.walk_rows}
        self.attempted, self.failed = rounds, 0

    def release(self) -> None:
        """Drop the program's state; keep the recorded draws on the host."""
        from repro.gp import mll, posterior

        posterior.pathwise_samples_chunked = self._original_draw
        mll.fit_hyperparams = self._original_fit
        self.loop_gaps = self._loop_gaps()
        rng = data.np_rng(self.seed, 2)
        n_check = min(self.traffic["check"]["rounds"], len(self.draws))
        picks = sorted(rng.choice(len(self.draws), n_check, replace=False))
        kept = []
        for i in picks:
            d = self.draws[i]
            kept.append({k: (None if v is None else np.asarray(v))
                         for k, v in d.items()})
        self.draws = kept
        self.graph = None

    def _loop_gaps(self) -> int:
        """Rounds whose observation buffer does not hold the previous
        round's pick (the argmax of its draw over unobserved nodes) in the
        next slot: a loop that drops, repeats or misplaces an observation."""
        bo = self.config["bo"]
        gaps, prev = 0, None
        for d in self.draws:
            x = np.asarray(d["x"])
            count = int(np.asarray(d["mask"]).sum())
            if prev is not None:
                p_count, p_picks = prev
                full = p_count + bo["batch_size"] >= bo["capacity"]
                if count == p_count + bo["batch_size"]:
                    gaps += int(not np.array_equal(x[p_count:count], p_picks))
                elif not (full and count == bo["n_init"]):
                    gaps += 1           # only a full buffer restarts
            out = np.array(d["out"], np.float64)
            out[x[:count], :] = -np.inf
            picks = []
            for j in range(out.shape[1]):
                picks.append(int(np.argmax(out[:, j])))
                out[picks[-1], :] = -np.inf
            prev = (count, np.array(picks))
        return gaps

    # -- correctness -------------------------------------------------------
    def check(self) -> list:
        """[(name, value, limit)]: the widest gap of the program's draws
        from the reference's, over the checked rounds and nodes, as a share
        of the largest reference value there; the worst leaf's gap of the
        hyperparameters' change over each refit of the window; and the
        rounds whose buffer lost the previous pick (exact, limit 0)."""
        adj = reference.Adjacency.from_spec(self.config["graph"])
        return [("draw_gap", self.draw_gap(adj), self._limit("draw_gap")),
                ("refit_gap", self.refit_gap(adj), self._limit("refit_gap")),
                ("loop_gap", float(self.loop_gaps), self._limit("loop_gap"))]

    def refit_gap(self, adj: reference.Adjacency) -> float:
        import jax

        wk = self.config["walks"]
        worst = 0.0
        for r in self.refits:
            seed_u32 = int(np.asarray(jax.random.bits(
                r["walk_key"], (), np.uint32)))
            rows = reference.walks(adj, r["x"], seed_u32, wk["n_walkers"],
                                   wk["p_halt"], wk["l_max"])
            # fit_hyperparams: one chunk of all the steps, whose probes are
            # drawn once from the chunk's key.
            _, k_loop = jax.random.split(r["key"])
            z = np.asarray(jax.random.bernoulli(
                jax.random.fold_in(k_loop, 0), 0.5,
                (len(r["x"]), r["n_probes"])), np.float64) * 2.0 - 1.0
            ref = reference.adam_fit(rows, adj.n_nodes, r["y"], z, r["mask"],
                                     r["init"], r["lr"], r["steps"],
                                     wk["l_max"])
            worst = max(worst, reference.leaf_gap(
                r["init"], r["params"], ref["params"], ref["grad0"]))
        return worst

    def draw_gap(self, adj: reference.Adjacency) -> float:
        import jax

        cfg = self.config
        wk = cfg["walks"]
        n = adj.n_nodes
        rng = data.np_rng(self.seed, 3)
        worst = 0.0
        for d in self.draws:
            seed_u32 = int(np.asarray(jax.random.bits(
                d["walk_key"], (), np.uint32)))
            k_w, k_eps = jax.random.split(d["key"])
            n_samples = d["out"].shape[1]
            w = np.asarray(jax.random.normal(k_w, (n, n_samples)), np.float64)
            t = d["x"].shape[0]
            eps = np.sqrt(np.float64(d["s2"])) * np.asarray(
                jax.random.normal(k_eps, (t, n_samples)), np.float64)
            picks = np.argmax(d["out"], axis=0)
            nodes = np.unique(np.concatenate([
                d["x"], picks,
                rng.choice(n, self.traffic["check"]["nodes"], replace=False)]))
            rows = lambda v: reference.walks(
                adj, v, seed_u32, wk["n_walkers"], wk["p_halt"], wk["l_max"])
            phi_x = reference.phi(rows(d["x"]), d["f"], n)
            phi_q = reference.phi(rows(nodes), d["f"], n)
            mask = d["mask"].astype(np.float64)
            noise = np.where(mask > 0, np.float64(d["s2"]), 1e6)
            h = reference.gram(phi_x, phi_x) + np.diag(noise)
            resid = (d["y"][:, None] - (phi_x @ w + eps)) * mask[:, None]
            v = reference.chol_solve(h, resid)
            want = phi_q @ w + reference.gram(phi_q, phi_x) @ v
            got = d["out"][nodes].astype(np.float64)
            scale = np.abs(want).max()
            worst = max(worst, float(np.abs(got - want).max() / scale))
        return worst

    def _limit(self, name: str) -> float:
        return float(self.traffic["check"]["limits"][name])
