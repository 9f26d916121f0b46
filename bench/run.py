#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from ``BENCHMARK.json``
and the files it names (``bench/harness/spec.py``).  Set-up builds the
inputs from the seed and warms every program the window uses; the window
then measures for at least ``--seconds`` seconds.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries its per-layer
metrics, read from the trace by ``bench/metrics/<name>.py``.  After the
window the run compares what the window produced with the float64
reference; each number compared is printed beside its limit, as the last
lines of standard error and under ``checks`` in the result line.

Without a TPU, or with fewer chips than the cell asks for, the run exits 3
and prints no result.  A metric of the cell whose reader finds nothing to
read (a program or name scope missing from the trace) ends the run with
exit 4 and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH_DIR)

from harness import device, spec, trace  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


class Run:
    """What a metric reader sees of one run."""

    def __init__(self, cell, drv, setup_s, trace_data, peaks):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.setup_s = setup_s
        self.window_s = drv.window_s
        self.counts = drv.counts
        self.latencies = drv.latencies
        self.trace = trace_data
        self.peaks = peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload)
    device.compile_cache()
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    info = device.info(devices)
    peaks = device.peaks(info["kind"])

    drv = spec.job(cell.traffic["job"]).Job(
        cell.config, cell.traffic, args.seed)
    drv.setup()
    setup_s = time.perf_counter() - T_START

    trace_data = None
    if args.trace:
        with trace.capture(TRACE_DIR):
            with trace.annotate(trace.WINDOW):
                drv.window(args.seconds)
        trace_data = trace.load(trace.newest_xplane(TRACE_DIR))
    else:
        drv.window(args.seconds)
    info["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    drv.release()
    checks = drv.check()

    run = Run(cell, drv, setup_s, trace_data, peaks)
    entries = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in entries:
        read = spec.reader(m["name"])
        value = read(run)
        if value is None:
            # The cell declares the metric, so its reader must find what it
            # reads: a program or scope missing from the trace is a fault.
            what = (read.__globals__.get("__doc__") or "").split("\n")[0]
            print(f"bench: {m['name']} found nothing to read in this run "
                  f"({what}); no result", file=sys.stderr)
            if trace_data is not None:
                print(f"bench: the trace holds {trace_data.summary()}",
                      file=sys.stderr)
            return 4
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": drv.attempted,
        "failed": drv.failed,
        "metrics": metrics,
        "device": info,
    }
    if trace_data is not None:
        info["busy_s"] = trace_data.busy_s()
        info["window_s"] = trace_data.window_s
        result["breakdown"] = {"device_ops": trace_data.top_ops(),
                               "idle_gaps": trace_data.idle_gaps()}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    sys.stdout.flush()
    for name, v, lim in checks:
        print(f"check {name}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
