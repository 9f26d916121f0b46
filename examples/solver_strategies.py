"""Solver strategy layer quickstart (DESIGN.md §3.8) — also the CI smoke.

One clustered GP training block, solved under every preconditioner
(including ``"auto"``, whose spectrally-probed rank choice is printed), a
mixed-precision (bf16-payload) solve, and a warm start, plus an SLQ-based
exact LML — every path through ``repro.solvers.solve``/``SolveStrategy``.
Exits non-zero if any solve fails to converge or the solutions disagree, so
the CI backend matrix (xla / pallas-interpret) can use it as a cheap
end-to-end gate.

    PYTHONPATH=src python examples/solver_strategies.py --nodes 5000
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import solvers
from repro.core import linops, modulation, walks
from repro.gp import mll
from repro.graphs import generators
from repro.runtime import enable_compile_cache


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--train", type=int, default=256)
    ap.add_argument("--rank", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    g = generators.ring(args.nodes, k=3)
    cfg = walks.WalkConfig(n_walkers=8, p_halt=0.15, l_max=5)
    mod = modulation.diffusion(l_max=cfg.l_max)
    f = mod({"log_beta": jnp.log(jnp.asarray(3.0)),
             "log_sigma_f": jnp.asarray(0.0)})
    train = jnp.arange(args.train)          # contiguous ⇒ correlated rows
    trace_x = walks.sample_walks_for_nodes(
        g, train, jax.random.PRNGKey(0),
        cfg.n_walkers, cfg.p_halt, cfg.l_max, cfg.reweight,
    )
    h = linops.shifted(trace_x, f, jnp.asarray(1e-2), args.nodes)
    y = jnp.asarray(
        np.random.default_rng(0).standard_normal(args.train), jnp.float32
    )

    sols, ok = {}, True
    for pc in solvers.PRECONDITIONERS:
        st = solvers.SolveStrategy(tol=1e-6, max_iters=2000,
                                   preconditioner=pc,
                                   precond_rank=args.rank)
        res = solvers.solve(h, y, st)
        conv = bool(jnp.all(res.converged))
        ok &= conv
        sols[pc] = np.array(res.x)
        print(f"{pc:>8}: iters={int(res.iters):4d} converged={conv}"
              + (f" rank={int(res.precond_rank)}" if pc == "auto" else ""))

    # Mixed precision: bf16 payload matvecs, f32 recurrence — must reach the
    # same fixed point (rel err is κ·bf16-eps-scale, loose tolerance below).
    bf16 = solvers.solve(
        h, y, solvers.SolveStrategy(tol=1e-6, max_iters=2000,
                                    preconditioner="jacobi",
                                    precond_rank=args.rank,
                                    matvec_dtype="bfloat16"),
    )
    conv = bool(jnp.all(bf16.converged))
    ok &= conv
    sols["bf16"] = np.array(bf16.x)
    print(f"{'bf16':>8}: iters={int(bf16.iters):4d} converged={conv}")

    warm = solvers.solve(
        h, y, solvers.SolveStrategy(tol=1e-6, max_iters=2000,
                                    warm_start=True),
        x0=jnp.asarray(sols["jacobi"]),
    )
    print(f"{'warm':>8}: iters={int(warm.iters):4d} "
          f"converged={bool(jnp.all(warm.converged))}")
    ok &= bool(jnp.all(warm.converged)) and int(warm.iters) <= 3

    for pc, x in sols.items():
        if pc == "bf16":
            # bf16 payloads perturb the *operator*, not just the solve — the
            # fixed point moves by O(κ·2⁻⁸), so the check is norm-relative.
            rel = np.linalg.norm(x - sols["none"]) / np.linalg.norm(
                sols["none"]
            )
            if rel > 5e-2:
                print(f"MISMATCH: bf16 rel err {rel:.3f} vs unpreconditioned")
                ok = False
        elif not np.allclose(sols["none"], x, rtol=5e-3, atol=5e-3):
            print(f"MISMATCH: {pc} disagrees with unpreconditioned solve")
            ok = False

    out = mll.exact_lml(trace_x, f, jnp.asarray(1e-2), y, args.nodes,
                        jax.random.PRNGKey(1), n_probes=16, slq_iters=48)
    print(f"exact LML = {float(out['lml']):.2f} "
          f"(datafit {float(out['datafit']):.2f}, "
          f"logdet {float(out['logdet']):.2f}, "
          f"converged={bool(out['converged'])})")
    ok &= bool(out["converged"]) and np.isfinite(float(out["lml"]))

    print("SOLVER_SMOKE_OK" if ok else "SOLVER_SMOKE_FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
