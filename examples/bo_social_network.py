"""End-to-end driver (the paper's flagship application, §4.3): find the most
influential user in a social network by Thompson-sampling BO with GRF-GPs.

    PYTHONPATH=src python examples/bo_social_network.py --nodes 20000
    PYTHONPATH=src python examples/bo_social_network.py --nodes 1000000  # 1M

Default engine is the *incremental* serving loop (repro/serving): one
ServeState reused across the run, O(m²) Cholesky appends per observation,
joint Thompson draws over a candidate set — no full-graph trace and no
N-scale pathwise draw per step.  ``--engine refit`` restores the paper's
from-scratch loop (materialised trace + pathwise sample per round).

The BO state checkpoints every iteration — kill and rerun to resume.

``--record PATH`` streams a JSONL flight record (per-round draw spans,
refit solve diagnostics, incumbent regret) and prints the obs summary
table — per-round draw p50/p99 and observation counts — at exit."""
import argparse
import contextlib
import time

import jax
import numpy as np

from repro import obs
from repro.bo import baselines, thompson
from repro.checkpoint import CheckpointManager
from repro.core import modulation, walks
from repro.graphs import generators
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--init", type=int, default=200)
    ap.add_argument("--walkers", type=int, default=20)
    ap.add_argument("--engine", choices=["incremental", "refit"],
                    default="incremental")
    ap.add_argument("--candidates", type=int, default=2048,
                    help="Thompson candidate set per round (incremental)")
    ap.add_argument("--ckpt", default="/tmp/grf_bo_ckpt")
    ap.add_argument("--record", metavar="PATH", default=None,
                    help="stream a JSONL flight record of the run")
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
    print(f"building Barabási–Albert graph with {args.nodes} nodes ...")
    t0 = time.time()
    g = generators.barabasi_albert(args.nodes, m=3, seed=0)
    deg = np.asarray(g.deg, float)
    objective_true = (deg - deg.mean()) / (deg.std() + 1e-9)  # influence proxy
    fmax = float(objective_true.max())
    rng = np.random.default_rng(0)
    obj = lambda idx: objective_true[idx] + 0.05 * rng.standard_normal(len(idx))
    print(f"  graph built in {time.time()-t0:.1f}s; max degree {int(deg.max())}")

    cfg = walks.WalkConfig(n_walkers=args.walkers, p_halt=0.15, l_max=5)
    tr = None
    if args.engine == "refit":
        print("sampling GRF walks (kernel initialisation, O(N)) ...")
        t0 = time.time()
        tr = walks.sample_walks(g, jax.random.PRNGKey(0),
                                n_walkers=args.walkers, p_halt=0.15, l_max=5)
        print(f"  {args.nodes} nodes × {tr.slots} slots in "
              f"{time.time()-t0:.1f}s ({tr.loads.size * 12 / 1e9:.2f} GB)")
    else:
        print("incremental engine: no full-graph trace — walk rows are "
              "sampled lazily per observation/query")

    mod = modulation.diffusion(l_max=5)
    mgr = CheckpointManager(args.ckpt, keep=2)

    state = None
    if mgr.latest_step() is not None:
        print("resuming BO from checkpoint ...")
        # BOState is plain numpy + params pytree: rebuild via example tree.
        example = thompson.BOState(
            x_buf=np.zeros(args.init + args.steps, np.int32),
            y_buf=np.zeros(args.init + args.steps, np.float32),
            count=0, params=thompson.mll.init_hyperparams(mod, jax.random.PRNGKey(0)),
            regret=[],
        )
        tree, manifest = mgr.restore(
            {"x_buf": example.x_buf, "y_buf": example.y_buf,
             "params": example.params})
        state = thompson.BOState(
            x_buf=tree["x_buf"], y_buf=tree["y_buf"],
            count=int(manifest["extra"]["count"]),
            params=jax.tree.map(jax.numpy.asarray, tree["params"]),
            regret=list(manifest["extra"]["regret"]),
            iteration=int(manifest["extra"]["iteration"]),
        )

    def ckpt_cb(st):
        mgr.save(st.iteration,
                 {"x_buf": st.x_buf, "y_buf": st.y_buf, "params": st.params},
                 blocking=False,
                 extra={"count": st.count, "iteration": st.iteration,
                        "regret": st.regret})

    t0 = time.time()
    if args.engine == "incremental":
        st = thompson.thompson_sampling_incremental(
            g, cfg, mod, obj, jax.random.PRNGKey(1), n_init=args.init,
            n_steps=args.steps, refit_every=10, refit_steps=10, f_max=fmax,
            n_candidates=args.candidates, state=state,
            checkpoint_cb=ckpt_cb,
        )
    else:
        st = thompson.thompson_sampling(
            tr, mod, obj, jax.random.PRNGKey(1), n_init=args.init,
            n_steps=args.steps, refit_every=10, refit_steps=10, f_max=fmax,
            state=state, checkpoint_cb=ckpt_cb,
        )
    mgr.wait()
    print(f"BO finished in {time.time()-t0:.1f}s; final simple regret "
          f"{st.regret[-1]:.4f}")

    if obs.enabled():
        snap = obs.REGISTRY.snapshot()
        draw = snap["histograms"].get("span.bo.draw")
        if draw:
            print(f"  per-round draw p50 {draw['p50']*1e3:.1f} ms / "
                  f"p99 {draw['p99']*1e3:.1f} ms over {draw['count']} rounds")

    for name, fn in (("random", baselines.random_search),
                     ("bfs", baselines.bfs_search),
                     ("dfs", baselines.dfs_search)):
        r = fn(g, obj, 0, args.init, args.steps, fmax)
        print(f"  baseline {name:7s}: final regret {r[-1]:.4f}")


if __name__ == "__main__":
    main()
