"""The traffic generator matches its parameters and is an open loop.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import traffic  # noqa: E402

SPEC = {"rate_per_s": 2000.0, "nodes": {"mean": 8, "min": 1, "max": 64},
        "zipf_theta": 0.99, "write_share": 0.05, "seconds_after": 5.0,
        "drain_s": 60.0}
N = 1 << 14


@pytest.fixture(scope="module")
def stream():
    return traffic.generate(SPEC, N, seed=2**31 + 99, seconds=25.0)


def test_same_seed_same_stream(stream):
    again = traffic.generate(SPEC, N, seed=2**31 + 99, seconds=25.0)
    assert np.array_equal(stream.due, again.due)
    assert np.array_equal(stream.is_write, again.is_write)
    assert all(np.array_equal(a, b) for a, b in zip(stream.nodes, again.nodes))


def test_other_seed_same_work_other_order(stream):
    other = traffic.generate(SPEC, N, seed=7, seconds=25.0)
    assert len(other) == len(stream)
    assert np.isclose(other.due[-1], stream.due[-1])
    assert np.array_equal(np.sort(other.sizes), np.sort(stream.sizes))
    assert other.is_write.sum() == stream.is_write.sum()
    assert not np.array_equal(other.due, stream.due)


def test_open_loop_arrivals(stream):
    """Due times are fixed before any request is served: a Poisson stream
    at the stated rate, increasing, over the whole horizon."""
    gaps = np.diff(stream.due)
    assert (gaps > 0).all()
    horizon = 25.0 + SPEC["seconds_after"]
    assert len(stream) == int(np.ceil(SPEC["rate_per_s"] * horizon))
    assert abs(gaps.mean() * SPEC["rate_per_s"] - 1) < 0.02
    # Exponential gaps: coefficient of variation 1.
    assert abs(gaps.std() / gaps.mean() - 1) < 0.03


def test_write_share(stream):
    share = stream.is_write.mean()
    assert abs(share - SPEC["write_share"]) < 1e-3
    assert all(len(stream.nodes[i]) == 1 for i in np.flatnonzero(stream.is_write))


def test_request_sizes(stream):
    q = stream.sizes[~stream.is_write]
    assert q.min() >= 1 and q.max() <= 64
    assert abs(q.mean() - SPEC["nodes"]["mean"]) < 0.15
    assert all(len(stream.nodes[i]) == stream.sizes[i]
               for i in np.flatnonzero(~stream.is_write)[:500])


def test_zipf_ranks_follow_theta():
    rng = np.random.default_rng(0)
    ranks = traffic.zipf_ranks(rng, 1000, 0.99, 400_000)
    counts = np.bincount(ranks, minlength=1000)
    want = np.arange(1, 1001) ** -0.99
    want = want / want.sum() * len(ranks)
    # The head ranks, where counts are large, within a few percent.
    assert np.allclose(counts[:20], want[:20], rtol=0.05)
    # Log-log slope over the first 100 ranks close to -theta.
    slope = np.polyfit(np.log(np.arange(1, 101)), np.log(counts[:100]), 1)[0]
    assert abs(slope + 0.99) < 0.05


def test_query_nodes_are_skewed(stream):
    nodes = np.concatenate([stream.nodes[i]
                            for i in np.flatnonzero(~stream.is_write)])
    counts = np.sort(np.bincount(nodes, minlength=N))[::-1]
    # Under Zipf(0.99) over 2^14 ids the hottest node takes ~10% of the
    # asks; uniform keys would give each ~0.006%.
    assert counts[0] / len(nodes) > 0.05
    assert nodes.min() >= 0 and nodes.max() < N
