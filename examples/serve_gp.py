"""Online GP serving quickstart: a 10⁶-node graph behind the micro-batching
engine (DESIGN.md §3.7).

    PYTHONPATH=src python examples/serve_gp.py                  # 1M nodes
    PYTHONPATH=src python examples/serve_gp.py --nodes 20000    # small/smoke
    PYTHONPATH=src python examples/serve_gp.py --nodes 20000 \
        --record run.jsonl --fit-steps 3       # + flight record with solves

Builds a ServeState (cached train features + m×m Gram Cholesky), streams
observations in via O(m²) incremental appends, then serves batched
mean/variance queries — no CG and nothing N-scale in the hot path, so
queries run at the same speed on 10⁶ nodes as on 10⁴.

With ``--record PATH`` the run streams a JSONL flight record (spans for
sampling/solves/serving waves, per-wave latency histograms, CG diagnostics)
and prints the obs summary table at exit; validate the artifact with
``python -m repro.obs.report --validate PATH``.  ``--fit-steps K`` runs K
LML-ascent steps on the streamed observations first (a noise/lengthscale
calibration pass) — that is what puts per-solve CG diagnostics into the
record, since the serving hot path itself is CG-free by design.

``--mesh N`` re-serves the state over an N-device mesh (DESIGN.md §3.12):
the cached train rows are row-sharded, queries run under shard_map, and the
script asserts bitwise parity against the single-device answers — the CI
distributed-serving smoke.  On a TPU host the mesh is the chips; without an
accelerator the flag makes XLA expose N host devices (it sets
``--xla_force_host_platform_device_count=N`` before jax initialises, which
only affects the CPU platform), so it also works on a plain CPU runner:

    PYTHONPATH=src python examples/serve_gp.py --nodes 20000 --mesh 2

Memory: the default Barabási–Albert graph pads every adjacency row to the
maximum degree (graphs/formats.py).  At 10⁶ nodes that is ≈ 2,575 slots,
≈ 20.6 GB of neighbours and weights — more than one TPU v5e chip's 16 GB of
HBM — so on one chip pass ``--nodes`` ≤ ~10⁵, or use the ring graph of
``chip_smoke.py``, until the offsets-based adjacency (ROADMAP R2) lands.
"""
import argparse
import contextlib
import os
import sys
import time

# --mesh on a host without an accelerator needs the forced host device
# count in XLA_FLAGS before the backend initialises — i.e. before jax is
# imported.  The flag only affects the CPU platform, so a TPU host still
# shards over its chips.
_mesh_arg = next(
    (i for i, a in enumerate(sys.argv) if a.startswith("--mesh")), None
)
if _mesh_arg is not None:
    _raw = sys.argv[_mesh_arg]
    _n = int(_raw.split("=", 1)[1] if "=" in _raw
             else sys.argv[_mesh_arg + 1])
    if _n > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_n}"
        ).strip()

import jax
import numpy as np

from repro import obs, serving
from repro.core import modulation, walks
from repro.graphs import generators
from repro.resilience import faults
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1_000_000)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--observe", type=int, default=50)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64,
                    help="engine slots per wave")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the serve state over an N-way host mesh "
                         "and assert parity with the single-device path")
    ap.add_argument("--record", metavar="PATH", default=None,
                    help="stream a JSONL flight record of the run")
    ap.add_argument("--fit-steps", type=int, default=0,
                    help="LML-ascent steps on the observations before "
                         "serving (exercises the CG solve path)")
    args = ap.parse_args()
    enable_compile_cache()

    recording = (
        obs.recording(args.record) if args.record is not None
        else contextlib.nullcontext()
    )
    with recording:
        run(args)
    if args.record is not None:
        print(f"\nflight record written to {args.record}")
        print(obs.summary())


def run(args):
    plan = faults.active()
    if plan is not None:
        # Chaos mode (REPRO_FAULTS, resilience/faults.py): the guards must
        # absorb every injected fault — this script's assertions are the
        # CI chaos-smoke gate.
        print(f"chaos mode: injected fault plan [{plan.spec()}]")
    print(f"building Barabási–Albert graph with {args.nodes} nodes ...")
    t0 = time.time()
    g = generators.barabasi_albert(args.nodes, m=3, seed=0)
    deg = np.asarray(g.deg, float)
    signal = (deg - deg.mean()) / (deg.std() + 1e-9)   # influence proxy
    rng = np.random.default_rng(0)
    print(f"  built in {time.time()-t0:.1f}s")

    cfg = walks.WalkConfig(n_walkers=8, p_halt=0.2, l_max=5)
    mod = modulation.diffusion(l_max=cfg.l_max)
    params = mod.init(jax.random.PRNGKey(1))
    f = mod(params)

    obs_nodes = rng.choice(
        args.nodes, args.observe, replace=False
    ).astype(np.int32)
    y = (signal[obs_nodes]
         + 0.05 * rng.standard_normal(args.observe)).astype(np.float32)
    sigma_n2 = 0.05

    if args.fit_steps > 0:
        # Hyperparameter calibration on the observation set: strategy-solved
        # CG per Adam step — the solves whose diagnostics land in the
        # flight record.
        from repro.gp import mll

        print(f"fitting hyperparameters for {args.fit_steps} steps ...")
        trace_x = walks.sample_walks_for_nodes(
            g, obs_nodes, jax.random.PRNGKey(0),
            cfg.n_walkers, cfg.p_halt, cfg.l_max, cfg.reweight, cfg.scheme,
        )
        res = mll.fit_hyperparams(
            trace_x, mod, y, g.n_nodes, jax.random.PRNGKey(2),
            steps=args.fit_steps, chunk=args.fit_steps,
            init_noise=float(np.sqrt(sigma_n2)),
        )
        f = mod(res.params["mod"])
        sigma_n2 = float(mll.noise_var(res.params))
        last = res.history[-1]
        print(f"  step {last['step']}: loss {last['loss']:.3f}, "
              f"sigma_n2 {last['sigma_n2']:.4f}, "
              f"cg_iters {last['cg_iters']}")

    # Empty state: nothing N-scale is ever materialised — train rows are
    # sampled lazily per observation, query rows lazily per wave.
    state = serving.init_state(
        g, jax.random.PRNGKey(0), f, sigma_n2, args.capacity, cfg
    )

    print(f"streaming {args.observe} observations "
          f"(incremental Cholesky appends) ...")
    t0 = time.time()
    state = serving.observe_batch(state, obs_nodes, y)
    jax.block_until_ready(state.chol)
    t_first = time.time() - t0
    # two more single appends: the first compiles the batch-1 step, the
    # second is the steady-state latency
    state = serving.observe(state, int(rng.integers(args.nodes)),
                            float(rng.standard_normal()))
    jax.block_until_ready(state.chol)
    t0 = time.time()
    state = serving.observe(state, int(rng.integers(args.nodes)),
                            float(rng.standard_normal()))
    jax.block_until_ready(state.chol)
    print(f"  batch ingested in {t_first:.2f}s (incl. compile); "
          f"steady-state observe() {1e3*(time.time()-t0):.1f} ms")
    assert np.isfinite(np.asarray(state.chol)).all(), \
        "guarded appends left a non-finite Cholesky"
    if int(state.rejected) > 0:
        print(f"  {int(state.rejected)} poisoned append(s) rejected by the "
              f"guards")

    # Refresh the representer weights through the escalation ladder — under
    # a cg_stall fault plan this is the solve the ladder must rescue.
    state, alpha_iters, alpha_conv = serving.refit_alpha(
        state, escalate=True, return_diagnostics=True
    )
    assert bool(alpha_conv), "escalated refit_alpha did not converge"
    print(f"  refit_alpha converged in {int(alpha_iters)} iters "
          f"(escalation ladder armed)")

    print(f"serving {args.queries} queries through batch-{args.batch} "
          f"waves ...")
    loop = serving.GPServeLoop(state, batch=args.batch)
    qnodes = rng.choice(args.nodes, args.queries, replace=False)
    requests = [serving.GPRequest(nodes=qnodes[i:i + 16])
                for i in range(0, args.queries, 16)]
    loop.run(requests)          # compile wave
    requests = [serving.GPRequest(nodes=qnodes[i:i + 16])
                for i in range(0, args.queries, 16)]
    t0 = time.time()
    loop.run(requests)
    dt = time.time() - t0
    assert all(r.done for r in requests), "unanswered queries"
    mean = np.concatenate([r.mean for r in requests])
    var = np.concatenate([r.var for r in requests])
    answered = int((np.isfinite(mean) & np.isfinite(var) & (var >= 0)).sum())
    assert answered == len(mean), \
        f"only {answered}/{len(mean)} queries answered finitely"
    best = qnodes[int(np.argmax(mean))]
    print(f"  {args.queries} queries in {dt*1e3:.0f} ms "
          f"({args.queries/dt:.0f} queries/s)")
    print(f"  top posterior mean {mean.max():.3f} at node {best} "
          f"(degree {int(deg[best])}); mean predictive sd "
          f"{np.sqrt(var).mean():.3f}")

    # Exact closed-form moments are also one call without the engine:
    m2, v2 = serving.posterior_moments(state, qnodes[:8].astype(np.int32))
    print(f"  posterior_moments head: mean {np.array(m2)[:3].round(3)}, "
          f"var {np.array(v2)[:3].round(3)}")

    if args.mesh > 1:
        # Distributed serving smoke: same state, row-sharded over the host
        # mesh, must answer bit-identically (structural-zero psum).
        print(f"re-serving over a {args.mesh}-way "
              f"{jax.default_backend()} mesh ...")
        sharded = serving.ShardedServeState(state, n_shards=args.mesh)
        qsub = qnodes[:64].astype(np.int32)
        ms, vs = sharded.posterior_moments(qsub)
        m1, v1 = serving.posterior_moments(state, qsub)
        diff = max(
            float(np.abs(np.asarray(ms) - np.asarray(m1)).max()),
            float(np.abs(np.asarray(vs) - np.asarray(v1)).max()),
        )
        assert diff == 0.0, \
            f"sharded moments diverge from single-device (max diff {diff})"
        fleet = serving.GPFleetLoop(sharded, batch=args.batch)
        reqs = [serving.GPRequest(nodes=qnodes[i:i + 16])
                for i in range(0, min(args.queries, 128), 16)]
        t0 = time.time()
        fleet.run(reqs)
        assert all(r.done for r in reqs), "fleet left unanswered queries"
        print(f"  sharded parity OK (bitwise over {len(qsub)} nodes); "
              f"fleet answered {fleet.served} queries in "
              f"{(time.time()-t0)*1e3:.0f} ms")

    if obs.enabled():
        # Per-wave latency straight from the registry — the numbers the
        # ad-hoc prints above approximate, now with percentiles.
        snap = obs.REGISTRY.snapshot()
        wave = snap["histograms"].get("span.serving.wave")
        if wave:
            print(f"  wave latency p50 {wave['p50']*1e3:.1f} ms / "
                  f"p99 {wave['p99']*1e3:.1f} ms over {wave['count']} waves")


if __name__ == "__main__":
    main()
