#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--seconds S] [--control] [--fault NAME] [--out FILE]

For each seed, in one process: set up the cell, run a short window at the
cell's own load, and print the numbers ``bench/run.py`` compares.  With
``--control`` each seed is read a second time through the control: the
program's own bfloat16 matvec path where it has one (the fit's and the
draw's CG), else the reference computed from operands rounded to
bfloat16.  With ``--fault`` every reading runs with that plant of
``harness/faults.py`` under the timed path.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)

from harness import data, device, faults, spec  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool) -> list:
    """[(control?, {number: value})] for one seed."""
    make = spec.job(cell.traffic["job"]).Job
    names = list(cell.traffic["check"]["limits"])
    # A job whose program has a bfloat16 path of its own takes it as an
    # argument; the others read the control from the reference.
    bf16_program = "matvec_dtype" in inspect.signature(make).parameters
    out = []
    for ctl in ([False, True] if control and bf16_program else [False]):
        kw = {"matvec_dtype": "bfloat16"} if ctl else {}
        drv = make(cell.config, cell.traffic, seed, **kw)
        drv.setup()
        drv.window(seconds)
        drv.release()
        out.append((ctl, {name: v for name, v, _ in drv.check()}))
        if control and not bf16_program:
            out.append((True, dict(zip(names, drv.gaps("bfloat16")))))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None, help="a plant of harness/faults.py")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload)
    device.compile_cache()
    try:
        device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    patch = faults.Patch()
    if args.fault:
        getattr(faults, args.fault)(patch)
    out = open(args.out, "a") if args.out else None
    try:
        for seed in args.seeds:
            for ctl, values in readings(cell, seed, args.seconds,
                                        args.control):
                row = {"workload": args.workload, "seed": seed,
                       "control": ctl, "fault": args.fault, **values}
                data.log(json.dumps(row))
                print(json.dumps(row), flush=True)
                if out:
                    out.write(json.dumps(row) + "\n")
                    out.flush()
    finally:
        patch.undo()
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
