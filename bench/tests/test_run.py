"""Whole runs of ``bench/run.py`` at a size a test can hold, on the CPU.

The look for a chip is stubbed out; everything else is the run as the
chip sees it: set-up, window, the float64 reference and the result line.
Each cell's runs must come out correct, and come out not correct when the
timed path is broken underneath in each way the cell can break: a step
that leaves its state unchanged, half of a batch left out, an answer
altered where it is produced.  The control (the program's own bfloat16
matvec path, or the reference from bfloat16 operands) must fail a limit.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import contextlib
import io
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from harness import device, faults, spec  # noqa: E402

BO, FIT = "grid1000_bo.thompson", "ring2p20_regression.fit"
SERVE = "ring2p20_regression.serve_zipf"


def _shrink(root):
    """A copy of BENCHMARK.json, configurations and traffic mixes under
    ``root``, each cut to a test's size (widths, walks and mixes kept)."""
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # The serving cell waits for its rate from a sweep on the chip; its job,
    # traffic mix and reference are tested here all the same.
    if SERVE not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append({"name": SERVE,
                                   "config": "ring2p20_regression",
                                   "traffic": "serve_zipf", "chips": 1})
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    for c in bench["configs"]:
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        g = cfg["graph"]
        if g["generator"] == "grid2d":
            g.update(rows=30, cols=40)
            cfg["objective"].update(rows=30, cols=40)
            cfg["bo"].update(n_init=20, capacity=60, refit_every=5)
        else:
            g["n_nodes"] = cfg["targets"]["n_nodes"] = 4096
            cfg["n_train"] = 256
            if "serving" in cfg:
                cfg["serving"].update(capacity=256, live=224, batch=32)
        with open(os.path.join(root, c["file"]), "w") as fh:
            json.dump(cfg, fh)
    for w in bench["workloads"]:
        tr = spec.load_json(os.path.join(BENCH, "traffic",
                                         f"{w['traffic']}.json"))
        if "fit" in tr:
            tr["fit"]["steps"] = 20
        if "stream" in tr:
            tr["stream"].update(rate_per_s=150.0, seconds_after=2.0)
        if "nodes" in tr["check"]:
            tr["check"]["nodes"] = 200
        with open(os.path.join(root, "bench", "traffic",
                               f"{w['traffic']}.json"), "w") as fh:
            json.dump(tr, fh)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = str(tmp_path_factory.mktemp("bench_root"))
    _shrink(r)
    return r


@pytest.fixture
def cpu_run(root, monkeypatch, tmp_path):
    """run.main against the shrunken tree, with the chip check stubbed."""
    import jax

    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(device, "compile_cache", lambda: None)
    monkeypatch.setattr(device, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(device, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(device, "memory_peak_bytes", lambda devs: 0)
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))

    def go(workload, trace=0, seconds=1.5, seed=2**31 + 3):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                                 "--seconds", str(seconds),
                                 "--trace", str(trace)])
        assert rc == 0
        return json.loads(out.getvalue().strip().splitlines()[-1])

    return go


@pytest.mark.parametrize("workload", [BO, FIT, SERVE])
def test_run_is_correct(cpu_run, workload):
    res = cpu_run(workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"
    cell = spec.resolve(workload)
    for m in cell.end_to_end:
        assert m["name"] in res["metrics"], m["name"]


def test_traced_run(cpu_run, capsys):
    """A traced run reads the trace; on the CPU there is no device plane,
    so a device-trace metric the cell declares finds nothing, and the run
    ends with no result, naming the metric."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", FIT, "--seed", "12345",
                             "--seconds", "1.5", "--trace", "1"])
    assert rc == 4
    assert out.getvalue().strip() == ""
    assert "cg_iter_roofline.fit found nothing to read" in capsys.readouterr().err


# -- faults planted under the timed path --------------------------------------

FAULTS = [
    (FIT, faults.fit_state_unchanged), (FIT, faults.fit_half_batch),
    (BO, faults.bo_answer_altered), (BO, faults.bo_half_batch),
    (BO, faults.bo_state_unchanged), (BO, faults.bo_refit_stuck),
    (SERVE, faults.serve_answer_altered), (SERVE, faults.serve_half_batch),
    (SERVE, faults.serve_state_unchanged),
]


@pytest.mark.parametrize("workload,plant", FAULTS,
                         ids=[p.__name__ for _, p in FAULTS])
def test_fault_is_not_correct(cpu_run, monkeypatch, workload, plant):
    plant(monkeypatch)
    # The BO window holds several rounds, as at the cell's size: a loop
    # that stops appending shows from its second round on.
    res = cpu_run(workload, seconds=1.0 if workload == BO else 0.1)
    assert not res["correct"], res["checks"]


# -- the control ----------------------------------------------------------------


@pytest.mark.parametrize("workload", [BO, FIT, SERVE])
def test_control_fails_a_limit(root, monkeypatch, workload):
    """The control, read as ``bench/calibrate.py --control`` reads it, fails
    at least one of the cell's limits while the program passes them all."""
    import calibrate

    monkeypatch.setattr(spec, "ROOT", root)
    cell = spec.resolve(workload)
    limits = cell.traffic["check"]["limits"]
    rows = calibrate.readings(cell, 2**31 + 11, 1.5, control=True)
    prog = [v for ctl, v in rows if not ctl]
    ctl = [v for c, v in rows if c]
    assert prog and ctl
    assert all(v[k] <= limits[k] for v in prog for k in limits), prog
    assert any(v[k] > limits[k] for v in ctl for k in v), ctl
