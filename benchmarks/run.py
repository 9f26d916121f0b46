"""Benchmark driver — one module per paper table/figure (deliverable (d)).

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is the
wall-time of the benchmark unit where meaningful (scaling rows) and blank
for quality metrics; ``derived`` carries the metric payload.

Flags:
  --full             larger problem sizes (CI uses the fast defaults)
  --backend=NAME     route every GRF sparse product through the given
                     backend ("xla" | "pallas" | "pallas-interpret") via
                     repro.kernels.dispatch — the whole GP stack obeys it.
  --only=PREFIX      run only suites whose label starts with PREFIX

Fast mode (no --full) pins JAX_PLATFORMS=cpu before jax initialises unless
the environment already chose a platform — the same contract as the
``python -m benchmarks.bench_*`` entry points, so CI and local runs agree;
every artifact's provenance names the device that did the work.

``serving_load`` starts one worker process per mode.  A chip belongs to one
process at a time, and the in-process suites hold it once they have run, so
off the CPU ``serving_load`` runs only in an invocation of its own
(``--only=serving_load``); combined with other suites it is skipped with a
note.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _emit(rows):
    for row in rows:
        name = row.pop("name")
        us = row.pop("us_per_call", "")
        print(f"{name},{us},{json.dumps(row, default=str)}", flush=True)


def main() -> None:
    argv = sys.argv[1:]
    fast = "--full" not in argv
    backend = None
    only = None
    for arg in argv:
        if arg.startswith("--backend="):
            backend = arg.split("=", 1)[1]
        if arg.startswith("--only="):
            only = arg.split("=", 1)[1]

    if fast:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from repro.runtime import enable_compile_cache

    enable_compile_cache()

    if backend is not None:
        from repro.kernels import dispatch

        dispatch.set_backend(backend)
        print(f"# spmv backend: {backend}", flush=True)

    from benchmarks import (
        bench_ablation,
        bench_bo,
        bench_classification,
        bench_estimator,
        bench_regression,
        bench_resilience,
        bench_scaling,
        bench_serving,
        bench_serving_load,
        bench_solvers,
        bench_spmv,
        bench_walks,
    )

    suites = [
        ("spmv (backend registry / BENCH_spmv.json)", bench_spmv),
        ("walks (walk sampler / BENCH_walks.json)", bench_walks),
        ("estimator (walk schemes / BENCH_estimator.json)", bench_estimator),
        ("serving (online engine / BENCH_serving.json)", bench_serving),
        ("serving_load (traffic replay / BENCH_serving_load.json)",
         bench_serving_load),
        ("solvers (Krylov strategy layer / BENCH_solvers.json)", bench_solvers),
        ("resilience (fault-tolerant serving / BENCH_resilience.json)",
         bench_resilience),
        ("scaling (Table 1 / Fig 2)", bench_scaling),
        ("ablation (Table 5)", bench_ablation),
        ("regression (Fig 3)", bench_regression),
        ("bo (Fig 4)", bench_bo),
        ("classification (Table 7)", bench_classification),
    ]
    import jax

    if only is not None:
        # Exact first-token match wins over prefix: --only=serving must run
        # the serving suite alone, not also serving_load.
        exact = [s for s in suites if s[0].split(" ", 1)[0] == only]
        suites = exact if exact else [s for s in suites if s[0].startswith(only)]
    # Asking JAX for the platform takes the chip, which is harmless only
    # when this process runs in-process suites anyway.
    if len(suites) > 1 and jax.default_backend() != "cpu":
        kept = [s for s in suites if s[1] is not bench_serving_load]
        if len(kept) < len(suites):
            print("# serving_load skipped: its workers need the chip, which "
                  "this process holds once another suite has run; run "
                  "--only=serving_load on its own", flush=True)
        suites = kept
    for label, mod in suites:
        t0 = time.time()
        try:
            rows = mod.run(fast=fast)
        except Exception as e:  # noqa: BLE001
            rows = [dict(name=f"{mod.__name__}_FAILED", error=f"{type(e).__name__}: {e}")]
        print(f"# {label} ({time.time()-t0:.1f}s)", flush=True)
        _emit(rows)


if __name__ == "__main__":
    main()
