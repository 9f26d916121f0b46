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

import common  # noqa: E402
import run as bench_run  # noqa: E402
from harness import device, faults, spec  # noqa: E402

BO, FIT = "grid1000_bo.thompson", "ring2p20_regression.fit"
SERVE = "ring2p20_regression.serve_zipf"


# The serving cell waits for its rate from a sweep on the chip; its job,
# traffic mix and reference are tested here all the same.
SERVE_CELL = {"name": SERVE, "config": "ring2p20_regression",
              "traffic": "serve_zipf", "chips": 1}


def _shrink(root, src=ROOT, cells=()):
    """A copy of ``src``'s BENCHMARK.json (with ``cells`` added where it
    lacks them), configurations and traffic mixes under ``root``, each
    configuration cut by its sizes file (``common.at_test_size``) and each
    mix to a test's length (widths, walks and mixes kept)."""
    bench = spec.load_json(os.path.join(src, "BENCHMARK.json"))
    names = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [w for w in cells if w["name"] not in names]
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"]), "w") as fh:
            json.dump(common.at_test_size(c, src), fh)
    for w in bench["workloads"]:
        tr = spec.load_json(os.path.join(src, "bench", "traffic",
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
    _shrink(r, cells=[SERVE_CELL])
    return r


def _on_the_cpu(root, monkeypatch, trace_dir):
    """run.main against the shrunken tree ``root``, with the chip check
    stubbed: ``go(workload, ...)`` returns the result line."""
    import jax

    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(device, "compile_cache", lambda: None)
    monkeypatch.setattr(device, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(device, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(device, "memory_peak_bytes", lambda devs: 0)
    monkeypatch.setattr(bench_run, "TRACE_DIR", trace_dir)

    def go(workload, trace=0, seconds=1.5, seed=2**31 + 3):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                                 "--seconds", str(seconds),
                                 "--trace", str(trace)])
        assert rc == 0
        return json.loads(out.getvalue().strip().splitlines()[-1])

    return go


@pytest.fixture
def cpu_run(root, monkeypatch, tmp_path):
    return _on_the_cpu(root, monkeypatch, str(tmp_path / "trace"))


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


# -- a configuration of a new graph generator, as new files only --------------

TOY = "toy_bo"
TOY_CELL = {"name": f"{TOY}.thompson", "config": TOY, "traffic": "thompson",
            "chips": 1, "why": "a graph with no coordinates and a skewed "
            "degree law, its objective read from the graph"}

# The reference's edge list: a chain, each node i >= 1 also hanging from
# node isqrt(i) - 1 (so node j has about 2j children), and the last
# ``isolated`` ids left without an edge.
TOY_EDGES = '''"""A chain with a square-root tree over it, some isolated nodes last."""
import numpy as np


def edges(spec):
    n, live = spec["n_nodes"], spec["n_nodes"] - spec["isolated"]
    i = np.arange(1, live)
    parent = np.sqrt(i).astype(np.int64)
    parent -= parent * parent > i
    return np.concatenate([np.stack([i - 1, i], 1),
                           np.stack([parent - 1, i], 1)]), n
'''

TOY_CONFIG = {
    "name": TOY,
    "source": "a test's own configuration",
    "graph": {"generator": "sqrt_tree", "n_nodes": 1 << 20, "isolated": 64},
    "walks": {"n_walkers": 30, "p_halt": 0.15, "l_max": 5},
    "modulation": {"name": "diffusion"},
    "objective": {"name": "degree_influence", "noise_std": 0.05},
    "bo": {"n_init": 100, "refit_every": 15, "refit_steps": 8,
           "capacity": 400, "batch_size": 1},
    "precision": "float32",
}
TOY_SIZES = {"graph": {"n_nodes": 1200},
             "bo": {"n_init": 20, "capacity": 60, "refit_every": 5}}


def sqrt_tree(n_nodes, isolated):
    """The program's side of the toy generator, built edge by edge."""
    import math

    from repro.graphs import formats

    edges = []
    for i in range(1, n_nodes - isolated):
        edges += [(i - 1, i), (math.isqrt(i) - 1, i)]
    return formats.from_edges(edges, n_nodes)


def degree_influence(graph, seed=0):
    """An influence proxy read from the graph: log(1 + degree), jittered."""
    import numpy as np

    deg = np.asarray(graph.deg, np.float64)
    return np.log1p(deg) + 0.1 * np.random.default_rng(seed).standard_normal(
        len(deg))


def _plant(tree):
    """A copy of the benchmark's files under ``tree`` with the toy
    configuration's files added and its cell listed; no file edited."""
    import shutil

    shutil.copytree(BENCH, os.path.join(tree, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": TOY, "source": TOY_CONFIG["source"],
                             "file": f"bench/configs/{TOY}.json",
                             "reduced": [], "why": TOY_CELL["why"]})
    bench["workloads"].append(TOY_CELL)
    for m in bench["end_to_end"]:
        if BO in m.get("workloads", []):
            m["workloads"].append(TOY_CELL["name"])
    new = {"BENCHMARK.json": json.dumps(bench),
           f"bench/configs/{TOY}.json": json.dumps(TOY_CONFIG),
           f"bench/tests/sizes/{TOY}.json": json.dumps(TOY_SIZES),
           "bench/harness/graphs/sqrt_tree.py": TOY_EDGES}
    for path, text in new.items():
        full = os.path.join(tree, path)
        assert path == "BENCHMARK.json" or not os.path.exists(full)
        with open(full, "w") as fh:
            fh.write(text)


def test_a_new_generator_enters_as_new_files(tmp_path, monkeypatch):
    from repro.graphs import generators, signals

    monkeypatch.setattr(generators, "sqrt_tree", sqrt_tree, raising=False)
    monkeypatch.setattr(signals, "degree_influence", degree_influence,
                        raising=False)
    tree, root = str(tmp_path / "tree"), str(tmp_path / "root")
    _plant(tree)
    monkeypatch.setattr(spec, "GRAPHS_DIR",
                        os.path.join(tree, "bench", "harness", "graphs"))
    _shrink(root, src=tree)
    cfg = spec.load_json(os.path.join(root, "bench", "configs",
                                      f"{TOY}.json"))
    assert cfg["graph"] == {"generator": "sqrt_tree", "n_nodes": 1200,
                            "isolated": 64}
    cell = spec.resolve(TOY_CELL["name"], root=root)
    assert [m["name"] for m in cell.end_to_end] == ["bo_round_s", "setup_s"]
    common.check_adjacency(cfg["graph"])
    go = _on_the_cpu(root, monkeypatch, str(tmp_path / "trace"))
    res = go(TOY_CELL["name"], seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_every_configuration_has_its_sizes_file():
    for c in common.configs():
        assert os.path.isfile(common.sizes_file(c["name"])), c["name"]


def test_a_configuration_without_sizes_is_named(tmp_path):
    entry = {"name": "no_sizes", "file": "bench/configs/no_sizes.json"}
    with pytest.raises(FileNotFoundError) as err:
        common.at_test_size(entry, str(tmp_path))
    assert common.sizes_file("no_sizes", str(tmp_path)) in str(err.value)
