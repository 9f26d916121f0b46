"""Walk-sampling + chunked-feature benchmark (the 10⁶-node scenario).

Times the GRF walk sampler over N ∈ {1e4, 1e5, 1e6} on a ring graph and
writes ``BENCH_walks.json`` at the repo root — the longitudinal artifact the
CI bench-regression job diffs against.  Three measurements per size:

  * ``sample_chunked``   one full sampling pass streamed in CHUNK-row blocks
                         (peak trace memory O(chunk·K) — the number that
                         stays flat as N grows);
  * ``sample_monolithic`` the one-shot [N, K] trace, *skipped* above
                         ``MONO_LIMIT`` where the O(N·K) materialisation is
                         the memory wall the chunked path exists to avoid;
  * ``bo_step``          an end-to-end BO posterior draw at that scale:
                         pathwise_samples_chunked (CG on the observation
                         set, then one chunked pass Φ(w + Φ_xᵀα)).

The JSON also records the analytic peak trace bytes for both paths so the
memory claim is auditable, not just the wall-clock.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import bench_main, provenance, timeit, timeit_result
from repro.core import modulation, walks
from repro.gp import posterior
from repro.graphs import generators

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_walks.json")

CHUNK = 65536
MONO_LIMIT = 200_000          # monolithic [N, K] trace skipped above this
N_OBS = 256                   # synthetic observation set for the BO step


def _time(fn, reps: int = 1) -> float:
    return timeit(fn, reps) * 1e3  # ms


def _consume_chunks(graph, key, cfg, chunk):
    last = None
    for _, tr in walks.walk_chunks(graph, key, cfg, chunk=chunk):
        last = tr.loads
    return last


def run(fast: bool = True):
    sizes = [10_000, 100_000, 1_000_000]
    cfg = (
        walks.WalkConfig(n_walkers=4, p_halt=0.25, l_max=4)
        if fast
        else walks.WalkConfig(n_walkers=16, p_halt=0.1, l_max=8)
    )
    key = jax.random.PRNGKey(0)
    mod = modulation.diffusion(l_max=cfg.l_max)
    f = mod(mod.init(jax.random.PRNGKey(1)))

    slot_bytes = cfg.slots * 12  # cols i32 + loads f32 + lens i32 per node
    rows, table = [], {}
    for n in sizes:
        graph = generators.ring(n, k=3)
        rng = np.random.default_rng(n)
        obs = jnp.asarray(rng.choice(n, N_OBS, replace=False).astype(np.int32))
        y = jnp.asarray(rng.standard_normal(N_OBS), jnp.float32)

        ms_chunk = _time(lambda: _consume_chunks(graph, key, cfg, CHUNK))
        table[f"sample_chunked/N{n}"] = ms_chunk
        rows.append(dict(
            name=f"walks_sample_chunked_N{n}", us_per_call=f"{ms_chunk * 1e3:.0f}",
            N=n, K=cfg.slots, chunk=CHUNK,
            peak_trace_mb=round(min(n, CHUNK) * slot_bytes / 1e6, 2),
        ))

        if n <= MONO_LIMIT:
            ms_mono = _time(
                lambda: walks.sample_walks(
                    graph, key, cfg.n_walkers, cfg.p_halt, cfg.l_max
                ).loads
            )
            table[f"sample_monolithic/N{n}"] = ms_mono
            rows.append(dict(
                name=f"walks_sample_monolithic_N{n}",
                us_per_call=f"{ms_mono * 1e3:.0f}", N=n, K=cfg.slots,
                peak_trace_mb=round(n * slot_bytes / 1e6, 2),
            ))
        else:
            rows.append(dict(
                name=f"walks_sample_monolithic_N{n}", skipped=True,
                reason=f"O(N*K) trace = {n * slot_bytes / 1e6:.0f} MB "
                       f"(> {MONO_LIMIT}-node limit); chunked path covers it",
            ))

        # The timed call surfaces its own inner-CG diagnostics
        # (CGResult.converged via return_diagnostics): a silently maxed-out
        # CG would make the timing meaningless.
        sec, (_, cg_iters_used, cg_conv) = timeit_result(
            lambda: posterior.pathwise_samples_chunked(
                graph, obs, f, 0.05, y, jax.random.PRNGKey(2), key, cfg,
                chunk=CHUNK, n_samples=1, cg_iters=64,
                return_diagnostics=True,
            )
        )
        ms_bo = sec * 1e3
        table[f"bo_step/N{n}"] = ms_bo
        rows.append(dict(
            name=f"walks_bo_step_N{n}", us_per_call=f"{ms_bo * 1e3:.0f}",
            N=n, n_obs=N_OBS, chunk=CHUNK,
            cg_iters_used=int(cg_iters_used),
            cg_converged=bool(cg_conv),
        ))

    artifact = {
        "provenance": provenance(fast),
        "host_backend": jax.default_backend(),
        "unit": "ms_per_call",
        "chunk": CHUNK,
        "walk_config": dict(n_walkers=cfg.n_walkers, p_halt=cfg.p_halt,
                            l_max=cfg.l_max),
        "results": table,
    }
    with open(OUT_PATH, "w") as fh:
        json.dump(artifact, fh, indent=2, sort_keys=True)
    rows.append(dict(name="walks_artifact", path=os.path.abspath(OUT_PATH)))
    return rows


if __name__ == "__main__":
    bench_main(run)
