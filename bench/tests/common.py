"""What several test files share: each configuration at a test's size, and
the program's graph checked against the float64 reference's through views
that do not depend on the layout that holds the graph.

A configuration's test size is its overlay ``bench/tests/sizes/<config
name>.json``, merged key by key into the configuration: a nested group is
merged, any other value replaced.  A new configuration brings its own.
"""
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

from harness import reference, spec  # noqa: E402


def merge(base: dict, overlay: dict) -> dict:
    out = dict(base)
    for k, v in overlay.items():
        out[k] = (merge(base[k], v)
                  if isinstance(v, dict) and isinstance(base.get(k), dict)
                  else v)
    return out


def sizes_file(name: str, root: str = ROOT) -> str:
    return os.path.join(root, os.path.basename(BENCH), "tests", "sizes",
                        f"{name}.json")


def configs(root: str = ROOT) -> list:
    """The ``configs`` entries of ``BENCHMARK.json`` under ``root``."""
    return spec.load_json(os.path.join(root, "BENCHMARK.json"))["configs"]


def at_test_size(entry: dict, root: str = ROOT) -> dict:
    """The configuration of a ``configs`` entry, cut by its sizes file."""
    path = sizes_file(entry["name"], root)
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"configuration {entry['name']!r} has no test sizes: add {path}, "
            "an overlay of the configuration's keys cut to a CPU test's size")
    with open(path) as fh:
        overlay = json.load(fh)
    return merge(spec.load_json(os.path.join(root, entry["file"])), overlay)


def check_adjacency(graph_spec: dict) -> None:
    """The program's graph (built as the jobs build it) equals the
    reference's: degrees exactly, and the dense walk matrix with the same
    non-zero pattern and its values to rtol 1e-6."""
    from harness import data
    from repro.graphs import formats

    g = data.build_graph(graph_spec)
    adj = reference.Adjacency.from_spec(graph_spec)
    assert np.array_equal(np.asarray(g.deg), adj.deg)
    assert adj.offsets[-1] == len(adj.nbr) == len(adj.w)
    got = np.asarray(formats.to_dense(g), np.float64)
    want = np.zeros((adj.n_nodes, adj.n_nodes))
    want[np.repeat(np.arange(adj.n_nodes), adj.deg), adj.nbr] = adj.w
    assert np.array_equal(got != 0, want != 0)
    assert np.allclose(got, want, rtol=1e-6, atol=0)
