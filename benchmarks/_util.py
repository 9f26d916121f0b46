"""Shared benchmark plumbing: timing discipline + the fast-mode CLI contract.

One implementation of (a) the compile-warmup / block_until_ready timing loop
and (b) the ``--full`` flag + fast-mode ``JAX_PLATFORMS=cpu`` pin, so
``python -m benchmarks.bench_*``, ``benchmarks/run.py`` and the CI job all
measure the same way (the PR-2 bench_spmv unification — keep it single)."""
from __future__ import annotations

import argparse
import os
import subprocess

import jax


def device_info() -> dict:
    """The devices this process's jax computations ran on."""
    devices = jax.devices()
    return {
        "backend": devices[0].platform,
        "device_kind": getattr(devices[0], "device_kind", str(devices[0])),
        "device_count": len(devices),
    }


def provenance(fast: bool | None = None, device: dict | None = None) -> dict:
    """Where/how this artifact was produced — stamped into every BENCH_*.json.

    Cross-machine regression-gate trips are undiagnosable without knowing
    both sides' git commit, jax version, backend/device and fast-vs-full
    mode; check_regression.py prints this block from both artifacts in its
    failure messages.  ``device`` is the :func:`device_info` of the process
    that did the work, when that was not this one (worker processes)."""
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(__file__), capture_output=True, text=True,
            timeout=10,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        commit = "unknown"
    prov = {
        "git_commit": commit,
        "jax_version": jax.__version__,
        **(device if device is not None else device_info()),
    }
    if fast is not None:
        prov["mode"] = "fast" if fast else "full"
    return prov


def timeit(fn, reps: int = 1) -> float:
    """Seconds per call after a compile/warmup invocation."""
    return timeit_result(fn, reps)[0]


def timeit_result(fn, reps: int = 1, best: bool = False):
    """(seconds per call, last call's result) — same discipline as timeit.

    For benches that must also *read* the timed call's output (e.g. the CG
    iters_used/converged diagnostics) without paying an extra run of a
    multi-second workload.  ``best=True`` blocks per rep and returns the
    minimum instead of the mean — the right estimator when a *blocking*
    gate compares two rows on a shared CI runner (contention only ever adds
    time, so min-of-reps converges on the true cost from one side)."""
    import time

    jax.block_until_ready(fn())
    times = []
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return (min(times) if best else sum(times) / len(times)), out


def bench_main(run) -> None:
    """CLI entry shared by the bench modules (``run(fast: bool) -> rows``).

    Fast mode pins JAX_PLATFORMS=cpu before the first jax computation unless
    the caller already chose a platform — the same contract as run.py; the
    artifact's provenance then names the CPU, the device that did the
    work."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--full", action="store_true")
    args = parser.parse_args()
    if not args.full:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    for row in run(fast=not args.full):
        print(row)
