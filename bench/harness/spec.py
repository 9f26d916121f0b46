"""Resolve a cell of ``BENCHMARK.json`` to its files.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

  * a configuration: the ``file`` of its ``configs`` entry;
  * a traffic mix: ``bench/traffic/<traffic>.json``, whose ``job`` key
    names the kind of job under ``bench/harness/jobs/``;
  * a metric: ``bench/metrics/<metric name>.py``, with ``read(run)``;
  * a graph's float64 reference: ``bench/harness/graphs/<generator>.py``,
    with ``edges(spec)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
GRAPHS_DIR = os.path.join(BENCH_DIR, "harness", "graphs")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list             # metric entries reported by --trace 0
    per_layer: list              # metric entries reported by --trace 1


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: str | None = None) -> Cell:
    root = ROOT if root is None else root
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    # The reference runs only after the window: a graph it cannot build
    # is refused here, before any set-up.
    if "graph" in config:
        path = graph_file(config["graph"]["generator"])
        if not os.path.isfile(path):
            raise ValueError(
                f"configuration {w['config']!r}: no reference edge list for "
                f"graph generator {config['graph']['generator']!r} ({path})")
    traffic = load_json(os.path.join(root, os.path.basename(BENCH_DIR),
                                     "traffic", f"{w['traffic']}.json"))
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    return _load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def job(kind: str):
    """The module of a kind of job (``bench/harness/jobs/<kind>.py``)."""
    path = os.path.join(BENCH_DIR, "harness", "jobs", f"{kind}.py")
    return _load_module(path, "bench_job_" + kind)


def graph_file(generator: str) -> str:
    return os.path.join(GRAPHS_DIR, f"{generator}.py")


def graph_edges(generator: str):
    """The ``edges(spec) -> (int64[E, 2], n_nodes)`` function of
    ``bench/harness/graphs/<generator>.py``."""
    return _load_module(graph_file(generator), "bench_graph_" + generator).edges
