#!/usr/bin/env python3
"""Bring-up smoke of the GRF-GP main path on a TPU, at the paper's size.

    python chip_smoke.py              # one chip: fit, BO draw, serving
    python chip_smoke.py --chips 4    # four chips: sharded serving + CG only

One chip (N = 10⁶ nodes, ``generators.ring(10⁶, k=3)``):

  * fit      — T = 4√N = 4,000 clustered training nodes, a few
               ``gp.mll.fit_hyperparams`` steps (R = 9 CG columns,
               Nyström rank 128); every step must converge, and one solve
               is checked against a dense float32 reference (K̂_train built
               with ``dispatch.gram_block``, Cholesky on the device);
  * BO step  — one ``posterior.pathwise_samples_chunked`` draw over all N
               nodes; finite, converged, argmax = next query; the same path
               on a small ring is checked against the monolithic draw;
  * serving  — ``serving.init_state`` at capacity 128, 64 observations,
               ``GPFleetLoop`` waves of 64 queries, then
               ``posterior_moments``, checked against the ``serving.refit``
               oracle (a from-scratch refactorisation).

``--chips 4`` runs only the mesh paths, each against its single-device
twin: ``ShardedServeState`` over ``make_serving_mesh(4)`` and
``gp_shard.sharded_cg_solve``.

Every phase prints its wall time — the first call, which compiles, apart
from a warm repeat — and each check with its tolerance.  A failed check or
an exception exits non-zero.  The last line of a passing run is one JSON
object naming the device.  Without a TPU the script exits non-zero before
any phase and prints no such line: it never runs on the CPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_NODES = 1_000_000
T_TRAIN = 4 * math.isqrt(N_NODES)        # 4√N clustered training nodes
FIT_STEPS = 3
N_PROBES = 8                             # R = 1 + 8 CG columns
NYSTROM_RANK = 128
CAPACITY = 128
N_OBSERVE = 64
BATCH = 64
WAVES = 4
CHUNK = 65536
SMALL_N = 8192                           # reference-sized ring for the BO check


class Checks:
    """Collects pass/fail lines; a failure is reported, never swallowed."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'PASS' if ok else 'FAIL'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


def timed(label: str, fn):
    """Run ``fn`` twice, blocking on its output: the first call compiles
    (or hits the persistent cache), the second is the warm run."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    print(f"time {label}: first call {t1 - t0:.3f} s (incl. compile), "
          f"warm {t2 - t1:.3f} s", flush=True)
    return out


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def walk_config():
    from repro.core import walks

    # The solver benchmark's walk config (benchmarks/bench_solvers.py).
    return walks.WalkConfig(n_walkers=8, p_halt=0.15, l_max=6)


def build_graph(n: int):
    import jax
    from repro.graphs import generators

    t0 = time.perf_counter()
    graph = jax.block_until_ready(generators.ring(n, k=3))
    print(f"time graph ring({n}, k=3): {time.perf_counter() - t0:.3f} s "
          f"(max degree {graph.neighbors.shape[1]})", flush=True)
    return graph


def fit_phase(graph, check: Checks, t_train: int = T_TRAIN):
    """MLL fit on T clustered nodes + one solve against a dense Cholesky."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.scipy.linalg import cho_solve

    from repro import solvers
    from repro.core import features, modulation, walks
    from repro.gp import mll
    from repro.kernels import dispatch

    n = graph.n_nodes
    cfg = walk_config()
    mod = modulation.diffusion(l_max=cfg.l_max)
    train = jnp.arange(t_train, dtype=jnp.int32)
    rng = np.random.default_rng(0)
    pos = np.arange(t_train) / t_train
    y = jnp.asarray(np.sin(6 * np.pi * pos)
                    + 0.1 * rng.standard_normal(t_train), jnp.float32)
    walk_key = jax.random.PRNGKey(0)
    trace_x = timed("fit.sample_walks", lambda: walks.sample_walks_for_nodes(
        graph, train, walk_key, cfg.n_walkers, cfg.p_halt, cfg.l_max))
    strategy = solvers.MLL_DEFAULT.with_(
        warm_start=False, preconditioner="nystrom",
        precond_rank=NYSTROM_RANK)

    fits = []

    def fit():
        fits.append(mll.fit_hyperparams(
            trace_x, mod, y, n, jax.random.PRNGKey(3), steps=FIT_STEPS,
            chunk=FIT_STEPS, n_probes=N_PROBES, strategy=strategy))
        return fits[-1].params

    params = timed(f"fit.mll_{FIT_STEPS}_steps", fit)
    history = fits[-1].history
    for rec in history:
        print(f"  fit step {rec['step']}: loss {rec['loss']:.4f} "
              f"sigma_n2 {rec['sigma_n2']:.5f} cg_iters {rec['cg_iters']} "
              f"converged {rec['cg_converged']}", flush=True)
    check("fit.cg_converged",
          all(r["cg_converged"] for r in history),
          f"{FIT_STEPS} steps, R={1 + N_PROBES}, tol {strategy.tol}")
    finite = all(np.isfinite(r["loss"]) for r in history)
    check("fit.loss_finite", finite, "every step's surrogate loss")

    f = mod(params["mod"])
    s2 = mll.noise_var(params)
    solve_strategy = solvers.POSTERIOR_DEFAULT.with_(
        preconditioner="nystrom", precond_rank=NYSTROM_RANK)
    solve = jax.jit(lambda tr, f, s2, y: solvers.solve(
        mll.make_h_operator(tr, f, s2, n), y, solve_strategy))
    sol = timed("fit.cg_solve", lambda: solve(trace_x, f, s2, y))

    def dense_reference(tr, f, s2, y):
        vals = features.feature_values(tr, f)
        gram = dispatch.gram_block(vals, tr.cols, vals, tr.cols)
        a = gram + s2 * jnp.eye(gram.shape[0], dtype=jnp.float32)
        chol = jnp.linalg.cholesky(a)
        return a, cho_solve((chol, True), y)

    a, v_ref = timed("fit.dense_reference",
                     lambda: jax.jit(dense_reference)(trace_x, f, s2, y))
    # The residual is taken on the host in float64: only the operands come
    # from the device, so the check cannot inherit a device matmul's
    # rounding.
    a64 = np.asarray(a, np.float64)
    resid = _rel(a64 @ np.asarray(sol.x, np.float64), y)
    diff = _rel(sol.x, v_ref)
    check("fit.solve_converged", bool(jnp.all(sol.converged)),
          f"{int(sol.iters)} iters, Nyström rank {int(sol.precond_rank)}")
    check("fit.solve_vs_dense_residual", resid <= 1e-4,
          f"‖A v_cg − y‖/‖y‖ = {resid:.3e} ≤ 1e-4 against the dense "
          f"K̂_train + σ²I")
    check("fit.solve_vs_cholesky", diff <= 1e-3,
          f"‖v_cg − v_chol‖/‖v_chol‖ = {diff:.3e} ≤ 1e-3")
    return dict(f=f, s2=s2, y=y, train=train, walk_key=walk_key, cfg=cfg)


def bo_phase(graph, fitted, check: Checks, small_graph):
    """One pathwise posterior draw over all N nodes (the Thompson step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import walks
    from repro.gp import posterior

    f, s2, y, train = fitted["f"], fitted["s2"], fitted["y"], fitted["train"]
    walk_key, cfg = fitted["walk_key"], fitted["cfg"]
    key = jax.random.PRNGKey(4)
    draw, iters, conv = timed("bo.pathwise_draw", lambda: (
        posterior.pathwise_samples_chunked(
            graph, train, f, s2, y, key, walk_key, cfg, chunk=CHUNK,
            n_samples=1, return_diagnostics=True)))
    draw = np.asarray(draw)
    check("bo.draw_shape", draw.shape == (graph.n_nodes, 1), str(draw.shape))
    check("bo.draw_finite", bool(np.isfinite(draw).all()),
          f"{draw.size} values")
    check("bo.solve_converged", bool(conv), f"{int(iters)} iters")
    print(f"  next query (argmax of the draw): node {int(np.argmax(draw))}",
          flush=True)

    # The same chunked path against the monolithic Eq. 12 draw on a graph
    # small enough to materialise its whole trace (same keys, same Φ).
    n_small = small_graph.n_nodes
    t_small = 4 * math.isqrt(n_small)
    train_s = jnp.arange(t_small, dtype=jnp.int32)
    y_s = y[:t_small]
    chunked = posterior.pathwise_samples_chunked(
        small_graph, train_s, f, s2, y_s, key, walk_key, cfg,
        chunk=n_small // 4, n_samples=2)
    trace = walks.sample_walks(small_graph, walk_key, cfg.n_walkers,
                               cfg.p_halt, cfg.l_max)
    mono = posterior.pathwise_samples(trace, train_s, f, s2, y_s, key,
                                      n_samples=2)
    err = float(np.abs(np.asarray(chunked) - np.asarray(mono)).max())
    scale = max(float(np.abs(np.asarray(mono)).max()), 1.0)
    check("bo.chunked_vs_monolithic", err <= 1e-4 * scale,
          f"N={n_small}: max |Δ| = {err:.3e} ≤ 1e-4·{scale:.3f}")


def _serving_inputs(n: int, seed: int = 1):
    """Observed nodes, targets, and query nodes (half next to observations,
    half uniform over the graph)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    obs_nodes = rng.choice(n, N_OBSERVE + 8, replace=False).astype(np.int32)
    obs_y = rng.standard_normal(N_OBSERVE + 8).astype(np.float32)
    near = (obs_nodes[rng.integers(0, len(obs_nodes), WAVES * BATCH // 2)]
            + rng.integers(-4, 5, WAVES * BATCH // 2)) % n
    far = rng.choice(n, WAVES * BATCH // 2, replace=False)
    queries = np.concatenate([near, far]).astype(np.int32)
    return obs_nodes, obs_y, queries


def serving_phase(graph, fitted, check: Checks):
    """Online serving: appends, fleet waves, closed-form moments vs refit."""
    import jax
    import numpy as np

    from repro import serving

    obs_nodes, obs_y, queries = _serving_inputs(graph.n_nodes)
    empty = serving.init_state(graph, fitted["walk_key"], fitted["f"],
                               fitted["s2"], CAPACITY, fitted["cfg"])

    fleets = []

    def serve():
        state = serving.observe_batch(empty, obs_nodes[:N_OBSERVE],
                                      obs_y[:N_OBSERVE])
        fleet = serving.GPFleetLoop(state, batch=BATCH,
                                    key=jax.random.PRNGKey(6))
        # A donated append through the fleet, then query waves.
        fleet.submit_observe(obs_nodes[N_OBSERVE:], obs_y[N_OBSERVE:])
        reqs = [serving.GPRequest(nodes=queries[i * BATCH:(i + 1) * BATCH])
                for i in range(WAVES)]
        fleet.run(reqs)
        fleets.append((fleet, reqs))
        return fleet.serve_state

    state = timed(f"serving.observe+{WAVES}_fleet_waves", serve)
    fleet, reqs = fleets[-1]
    check("serving.count", int(state.count) == N_OBSERVE + 8,
          f"{int(state.count)} live observations")
    check("serving.health", int(state.rejected) == 0
          and int(state.overflow) == 0,
          f"rejected {int(state.rejected)}, overflow {int(state.overflow)}")
    check("serving.all_answered", all(r.done for r in reqs),
          f"{fleet.served} queries in {WAVES} waves")

    mean, var = timed("serving.posterior_moments",
                      lambda: serving.posterior_moments(state, queries))
    mean, var = np.asarray(mean), np.asarray(var)
    fleet_mean = np.concatenate([r.mean for r in reqs])
    fleet_var = np.concatenate([r.var for r in reqs])
    d_fleet = max(float(np.abs(fleet_mean - mean).max()),
                  float(np.abs(fleet_var - var).max()))
    check("serving.fleet_vs_moments", d_fleet <= 1e-5,
          f"max |Δ| = {d_fleet:.3e} ≤ 1e-5")

    # The oracle shares gram_block with the path under test, so this checks
    # the incremental Cholesky and whitening algebra; gram_block itself is
    # checked by fit.solve_vs_dense_residual against the XLA K̂ path.
    oracle = serving.refit(state)
    mean_r, var_r = serving.posterior_moments(oracle, queries)
    mean_r, var_r = np.asarray(mean_r), np.asarray(var_r)
    ok = (np.allclose(mean, mean_r, rtol=1e-5, atol=1e-6)
          and np.allclose(var, var_r, rtol=1e-5, atol=1e-6))
    d_mean = float(np.abs(mean - mean_r).max())
    d_var = float(np.abs(var - var_r).max())
    check("serving.moments_vs_refit", ok,
          f"max |Δmean| {d_mean:.3e}, max |Δvar| {d_var:.3e}; "
          f"rtol 1e-5, atol 1e-6 over {len(queries)} queries; gram_block "
          "itself is checked by fit.solve_vs_dense_residual")
    check("serving.finite", bool(np.isfinite(mean).all()
                                 and np.isfinite(var).all()
                                 and (var >= 0).all()),
          "mean finite, var finite and ≥ 0")


def mesh_phase(graph, check: Checks, n_chips: int):
    """Sharded serving and sharded CG against their single-device twins."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import serving, solvers
    from repro.core import linops, modulation, walks
    from repro.distributed import gp_shard
    from repro.launch.mesh import make_serving_mesh

    cfg = walk_config()
    mod = modulation.diffusion(l_max=cfg.l_max)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    walk_key = jax.random.PRNGKey(0)
    mesh = make_serving_mesh(n_chips)
    print(f"mesh: {dict(mesh.shape)} over "
          f"{[d.id for d in mesh.devices.reshape(-1)]}", flush=True)

    obs_nodes, obs_y, queries = _serving_inputs(graph.n_nodes)
    state = serving.ingest(
        serving.init_state(graph, walk_key, f, 0.05, CAPACITY, cfg),
        obs_nodes, obs_y)
    sharded = serving.ShardedServeState(state, mesh=mesh)
    q_even = queries[:BATCH]                  # q divides the mesh
    q_odd = queries[:BATCH - 2]               # padded to the mesh (4 chips)
    ms, vs = timed("mesh.sharded_moments",
                   lambda: sharded.posterior_moments(q_even))
    m1, v1 = serving.posterior_moments(state, q_even)
    bitwise = (np.array_equal(np.asarray(ms), np.asarray(m1))
               and np.array_equal(np.asarray(vs), np.asarray(v1)))
    check("mesh.sharded_serving_bitwise", bitwise,
          f"q={len(q_even)} over {n_chips} shards, mean and var bit-equal")
    ms, vs = sharded.posterior_moments(q_odd)
    m1, v1 = serving.posterior_moments(state, q_odd)
    d_odd = max(float(np.abs(np.asarray(ms) - np.asarray(m1)).max()),
                float(np.abs(np.asarray(vs) - np.asarray(v1)).max()))
    check("mesh.sharded_serving_padded", d_odd <= 1e-5,
          f"q={len(q_odd)} (padded to the mesh): max |Δ| = {d_odd:.3e} "
          f"≤ 1e-5")
    fleet = serving.GPFleetLoop(sharded, batch=BATCH,
                                key=jax.random.PRNGKey(6))
    reqs = [serving.GPRequest(nodes=queries[i * BATCH:(i + 1) * BATCH])
            for i in range(WAVES)]
    fleet.run(reqs)
    m_all, v_all = serving.posterior_moments(state, queries)
    d_fleet = max(
        float(np.abs(np.concatenate([r.mean for r in reqs])
                     - np.asarray(m_all)).max()),
        float(np.abs(np.concatenate([r.var for r in reqs])
                     - np.asarray(v_all)).max()))
    check("mesh.sharded_fleet", all(r.done for r in reqs)
          and d_fleet <= 1e-5,
          f"{fleet.served} queries, max |Δ| vs single device = "
          f"{d_fleet:.3e} ≤ 1e-5")

    # Sharded CG over all N rows of Φ (one N-vector psum per iteration).
    n = graph.n_nodes
    trace = timed("mesh.sample_walks_full", lambda: walks.sample_walks(
        graph, walk_key, cfg.n_walkers, cfg.p_halt, cfg.l_max))
    b = jnp.asarray(np.random.default_rng(2).standard_normal(n), jnp.float32)
    strategy = solvers.SolveStrategy(tol=1e-6, max_iters=512)
    single_cg = jax.jit(lambda tr, f, b: solvers.solve(
        linops.shifted(tr, f, jnp.float32(0.1), n), b, strategy).x)
    want = timed("mesh.single_device_cg", lambda: single_cg(trace, f, b))
    sharded_cg = jax.jit(lambda tr, f, b: gp_shard.sharded_cg_solve(
        tr, f, b, mesh, sigma_n2=0.1, strategy=strategy,
        return_diagnostics=True))
    got, iters, conv = timed("mesh.sharded_cg",
                             lambda: sharded_cg(trace, f, b))
    err = float(jnp.abs(want - got).max())
    check("mesh.sharded_cg", bool(conv) and err <= 1e-5,
          f"{int(iters)} iters, max |Δ| vs single device = {err:.3e} ≤ 1e-5")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh paths, on four chips")
    args = ap.parse_args()

    from repro import runtime

    cache_dir = runtime.enable_compile_cache()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {platform!r} devices); "
              "refusing to run on another platform", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from importlib import metadata

    from repro.kernels import dispatch

    def version(pkg):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return "not installed"

    print(f"platform {platform}, device_kind {devices[0].device_kind}, "
          f"devices {len(devices)}", flush=True)
    print(f"jax {jax.__version__}, jaxlib {version('jaxlib')}, "
          f"libtpu {version('libtpu')}", flush=True)
    print(f"kernels: {dispatch.chosen_backends()}", flush=True)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({entries} entries at start)",
          flush=True)

    check = Checks()
    graph = build_graph(N_NODES)
    if args.chips > 1:
        mesh_phase(graph, check, args.chips)
    else:
        from repro.graphs import generators

        fitted = fit_phase(graph, check)
        bo_phase(graph, fitted, check, generators.ring(SMALL_N, k=3))
        serving_phase(graph, fitted, check)

    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{', '.join(check.failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
