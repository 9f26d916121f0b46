"""Fault tolerance: atomic checkpoints, kill/resume bit-exactness, elastic."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.checkpoint import CheckpointManager
from repro.core import modulation, walks
from repro.graphs import generators
from repro.serving import update


def test_save_restore_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "b": {"c": jnp.asarray([1, 2, 3], jnp.int32), "d": jnp.asarray(2.5)},
    }
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(5, tree, extra={"note": "hi"})
    restored, manifest = mgr.restore(tree)
    assert manifest["step"] == 5 and manifest["extra"]["note"] == "hi"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_n_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.asarray(float(s))})
    assert mgr.steps() == [3, 4]


def test_interrupted_save_invisible(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.asarray(1.0)})
    # simulate a crash mid-save: tmp dir without MANIFEST
    os.makedirs(tmp_path / "step_0000000002.tmp")
    os.makedirs(tmp_path / "step_0000000003")  # no manifest either
    assert mgr.latest_step() == 1


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, {"x": jnp.ones((256, 256))}, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 7


def test_kill_and_resume_serving_state_is_bit_exact(tmp_path):
    """10 straight observations == 6 + checkpoint + simulated preemption +
    restore into a fresh template + 4 more, saving the mutable leaves the
    way resilience/journal.py recovers them."""
    cfg = walks.WalkConfig(n_walkers=6, p_halt=0.25, l_max=4)
    g = generators.ring(64, k=2)
    mod = modulation.diffusion(l_max=cfg.l_max)
    f = mod(mod.init(jax.random.PRNGKey(1)))
    rng = np.random.default_rng(3)
    nodes = rng.choice(64, 10, replace=False).astype(np.int32)
    ys = rng.standard_normal(10).astype(np.float32)

    def template():
        return serving.init_state(g, jax.random.PRNGKey(0), f, 0.05,
                                  capacity=16, cfg=cfg)

    straight = serving.observe_batch(template(), nodes, ys)

    d = str(tmp_path / "run")
    first = serving.observe_batch(template(), nodes[:6], ys[:6])
    CheckpointManager(d).save(6, update._pack(first))
    # 'preemption': a brand-new process rebuilds the empty state and
    # restores the latest checkpoint into it.
    example = template()
    packed, manifest = CheckpointManager(d).restore(update._pack(example))
    assert manifest["step"] == 6
    resumed = serving.observe_batch(update._unpack(example, packed),
                                    nodes[6:], ys[6:])

    for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    q = jnp.arange(64, dtype=jnp.int32)
    for a, b in zip(serving.posterior_moments(straight, q),
                    serving.posterior_moments(resumed, q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_restore_shape_check(tmp_path):
    """Restore validates shapes — a mismatched architecture is rejected."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.ones((4, 4))})
    with pytest.raises(ValueError):
        mgr.restore({"w": jnp.ones((8, 8))})
    with pytest.raises(KeyError):
        mgr.restore({"v": jnp.ones((4, 4))})
